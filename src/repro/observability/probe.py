"""Measured per-pod step times: the probe that closes the DAS loop.

One SPMD step yields a single wall time, so per-pod attribution needs a
measurement substrate (serving.py's long-standing caveat; PR 5 removed
the fabricated equal-times fallback precisely because occupancy would
masquerade as speed).  :class:`StepTimeProbe` supplies the honest
signal the way ``benchmarks.bench_schedulers.measure_class_step_times``
does for calibration: periodically time a probe program under each
class's execution context — the class's own control tree picks its
backend and block shapes, so the measurement reflects that class's real
per-row cost — and between refreshes report

    ``times[pod] = units[pod] * row_seconds[class(pod)]``

for the units the engine actually ran on each pod.  Under
``DynamicScheduler.observe`` the rate then reduces to
``units / (units * s_c) = 1 / s_c`` — pure class speed, independent of
occupancy, which is exactly the quantity the paper's §5.2.2/§5.4
feedback is defined over.

The probe is the engine's default ``pod_time_hook`` but stays inert
(returns ``None``; calibration frozen, zero work) unless it is built
with ``always=True`` or :meth:`StepTimeProbe.refresh` is called: turning
tracing on does not arm it, since its GEMMs would run inside the traced
window and take device time from the steps being measured.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence

from repro.observability import metrics as MET
from repro.observability import trace as T

_ROW_SECONDS = MET.gauge(
    "probe_row_seconds",
    "Measured per-row step cost of one device class (last refresh)",
    labels=("device_class",),
)
_REFRESHES = MET.counter(
    "probe_refreshes_total", "Probe re-measurement rounds performed"
)


class StepTimeProbe:
    """``ServingEngine(pod_time_hook=...)`` implementation on measured time.

    Parameters
    ----------
    asym : the engine's :class:`~repro.core.asymmetric.AsymmetricMesh`
        (its per-class execution contexts are what get timed).
    probe_shape : GEMM the default workload times under each class's
        context; rows (``m``) are the per-row normalizer.  Small by
        default — a refresh costs ~classes × reps × one tiny GEMM.
    interval : steps between re-measurements (the first refresh lands in
        the engine's step-0 compile window, so steady-state decode pays
        nothing until the next interval boundary).
    reps : timing repetitions per class (median taken).
    workloads : optional ``{class_name: zero-arg callable}`` override —
        the callable is timed in place of the probe GEMM (still under
        the class's context, still normalized by ``probe_shape[0]``
        rows).  Lets tests and fleets probe with representative work.
    always : measure on every call (otherwise the probe returns ``None``).
    """

    def __init__(
        self,
        asym,
        *,
        probe_shape: tuple[int, int, int] = (128, 128, 128),
        interval: int = 64,
        reps: int = 2,
        workloads: Optional[dict[str, Callable[[], object]]] = None,
        always: bool = False,
    ):
        self.asym = asym
        self.probe_shape = tuple(probe_shape)
        self.interval = max(1, int(interval))
        self.reps = max(1, int(reps))
        self.workloads = dict(workloads) if workloads else None
        self.always = bool(always)
        self._pod_class = asym.pod_class_indices()
        self._row_seconds: Optional[list[float]] = None  # per class index
        self.last_measured: dict[str, float] = {}
        self.refreshes = 0

    def active(self) -> bool:
        return self.always

    def _default_workload(self, ctx):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.kernels import ops

        m, k, n = self.probe_shape
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        return lambda: jax.block_until_ready(ops.gemm(a, b))

    def refresh(self) -> list[float]:
        """Re-measure every class's per-row cost; returns the new table."""

        with T.span("probe.refresh", cat="probe", shape=list(self.probe_shape)):
            rows = max(1, self.probe_shape[0])
            out = []
            for c in self.asym.classes:
                ctx = self.asym.execution_context(c.name, shape=self.probe_shape)
                with ctx:
                    work = (
                        self.workloads.get(c.name) if self.workloads else None
                    ) or self._default_workload(ctx)
                    work()  # warmup: compile/dispatch cost is not step cost
                    times = []
                    for _ in range(self.reps):
                        t0 = time.perf_counter()
                        work()
                        times.append(time.perf_counter() - t0)
                times.sort()
                sec = times[len(times) // 2]
                out.append(sec / rows)
                self.last_measured[c.name] = sec
                _ROW_SECONDS.labels(device_class=c.name).set(sec / rows)
        self._row_seconds = out
        self.refreshes += 1
        _REFRESHES.inc()
        T.instant(
            "probe.measured", cat="probe",
            row_seconds={c.name: out[i] for i, c in enumerate(self.asym.classes)},
        )
        return out

    def __call__(
        self, step: int, pod_units: Optional[Sequence[int]] = None
    ) -> Optional[list[float]]:
        """Per-pod seconds for this step, or ``None`` while inactive.

        ``pod_units`` is the per-pod active unit count the engine ran
        (rows / slots); omitted, each pod is charged one unit.
        """

        if not self.active():
            return None
        if self._row_seconds is None or step % self.interval == 0:
            self.refresh()
        if pod_units is None:
            pod_units = [1] * len(self._pod_class)
        return [
            float(u) * self._row_seconds[self._pod_class[pod]]
            for pod, u in enumerate(pod_units)
        ]


__all__ = ["StepTimeProbe"]
