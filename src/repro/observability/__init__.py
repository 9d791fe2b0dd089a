"""Telemetry for the asymmetric-scheduling stack: spans, metrics, probe.

Three surfaces, one switch:

  * :mod:`repro.observability.trace` — contextvar-nested spans with two
    sinks: the JAX profiler's trace (``TraceAnnotation``s, on the device
    operations' clock, whenever a profiler session is active) and a
    bounded in-memory event buffer, exported as Chrome-trace/Perfetto
    JSON.  Spans carry the scheduling provenance the rest of the repo
    already proves (device class, backend variant, ``block_source``).
  * :mod:`repro.observability.compiles` — one ``jax.monitoring``
    listener counting the programs compiled (or loaded from the
    persistent cache) and the seconds spent compiling.
  * :mod:`repro.observability.metrics` — a registry of labeled
    counters/gauges/histograms with Prometheus text exposition and a
    JSON snapshot.
  * :mod:`repro.observability.probe` — the measured per-pod step-time
    probe that plugs into ``ServingEngine(pod_time_hook=...)`` and
    closes the paper's DAS calibration loop (§5.2.2/§5.4) on real
    timings instead of fabricated ones, when built with ``always=True``.

**Off is free.**  Everything here is disabled by default; the disabled
path is a ``None`` check per instrumentation site, and a span's also
asks ``TraceAnnotation.is_enabled()``.  Importing the package imports no
jax (spans and the compile counter import it on first use),
instrumentation never alters a jitted program, and the default engine
probe returns ``None`` (frozen calibration, zero work) whatever is
enabled — the contract the ``bench_serving`` gate enforces.

See the engine's spans beside the device's operations by running any
``jax.profiler.trace`` around serving; enable the buffer with
:func:`enable` (or ``repro.launch.serve --trace/--metrics``) and
summarize it with ``python -m repro.observability.report``.
"""

from repro.observability import metrics  # noqa: F401
from repro.observability.metrics import REGISTRY  # noqa: F401
from repro.observability.trace import (  # noqa: F401
    disable,
    enable,
    enabled,
    get_buffer,
)

__all__ = ["enable", "disable", "enabled", "get_buffer", "metrics", "REGISTRY"]
