#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, and what they tell.

    python3 chipbench/hostspans.py <trace.xplane.pb[.gz]> [--top 10]

``repro.observability.trace`` writes the serving engine's spans into the
profiler's trace whenever a profiler session is active, on the host's
line and on the same clock as the device's operations: ``engine.step``
with its children ``.inputs``, ``.launch``, ``.wait``, ``.retire`` and
``.calibrate``; ``engine.admit`` with ``.route``, ``.inputs``,
``.launch``, ``.wait`` and ``.retire``; and ``host.gc`` around each
Python collection.  The benchmark's own spans (``chipbench.*``) wrap whole
engine calls; these name what the host did inside them.

* An idle gap of the first chip whose midpoint lies in a program span is
  named ``<benchmark span>/<innermost program span>``
  (``step/engine.step.inputs``, ``step/host.gc``); any other keeps its
  name.
* ``step_host_ms``: the median over the window's ``engine.step`` spans of
  the span's duration less its ``engine.step.wait`` child, the engine's
  host turn per decode step.
* ``step_idle_host_ms``: the first chip's idle time inside ``engine.step``
  but outside its ``.wait``, summed over the window and divided by the
  number of ``engine.step`` spans, the part of that turn the device
  waits for.

Both readings are ``None`` on a trace with no ``engine.step`` span (a
program that writes none).  The CLI prints them with the longest idle
gaps so named and the median ``chipbench.step`` span, as one JSON line.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path
from typing import Optional

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import tracefile  # noqa: E402
from chipbench.tracefile import Interval  # noqa: E402

PROGRAM_PREFIXES = ("engine.", "host.")
STEP = "engine.step"
STEP_WAIT = "engine.step.wait"


def program_spans(pd) -> list[Interval]:
    """The host plane's ``engine.*`` and ``host.*`` events, by start time
    (an enclosing span before the spans it holds)."""

    out = []
    for plane in pd.planes:
        if plane.name == tracefile.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES):
                        out.append(Interval(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return sorted(out, key=lambda s: (s.start, -s.end))


def innermost(spans: list[Interval], t: float) -> Optional[Interval]:
    """The shortest program span around time ``t``, if any."""

    around = [s for s in spans if s.start <= t <= s.end]
    return min(around, key=lambda s: s.dur) if around else None


def named_gaps(summary, spans: list[Interval]) -> list[Interval]:
    """``summary.gaps``, each named by the innermost program span around
    its midpoint where there is one."""

    out = []
    for g in summary.gaps:
        p = innermost(spans, (g.start + g.end) / 2)
        out.append(Interval(g.start, g.end, f"{g.name}/{p.name}") if p else g)
    return out


def _steps(summary, spans):
    """``(engine.step span, its .wait child or None)`` for each step that
    starts inside the summary's window."""

    lo, hi = summary.window
    waits = [s for s in spans if s.name == STEP_WAIT]
    out = []
    for st in (s for s in spans if s.name == STEP and lo <= s.start < hi):
        wait = next((w for w in waits if st.start <= w.start and w.end <= st.end), None)
        out.append((st, wait))
    return out


def _overlap(gaps: list[Interval], lo: float, hi: float) -> float:
    return sum(max(0.0, min(g.end, hi) - max(g.start, lo)) for g in gaps)


def step_host_ms(summary, spans: list[Interval]) -> Optional[float]:
    steps = _steps(summary, spans)
    if not steps:
        return None
    return 1e-6 * statistics.median(st.dur - (w.dur if w else 0.0) for st, w in steps)


def step_idle_host_ms(summary, spans: list[Interval]) -> Optional[float]:
    steps = _steps(summary, spans)
    if not steps:
        return None
    idle = 0.0
    for st, w in steps:
        idle += _overlap(summary.gaps, st.start, st.end)
        if w is not None:
            idle -= _overlap(summary.gaps, w.start, w.end)
    return 1e-6 * idle / len(steps)


def report(summary, spans: list[Interval], top: int = 10) -> dict:
    """Both readings, the longest idle gaps named by program span (s) and
    the median ``chipbench.step`` span (ms)."""

    gaps = sorted(named_gaps(summary, spans), key=lambda g: -g.dur)[:top]
    bench_steps = [s.dur for s in summary.spans if s.name == "chipbench.step"]
    return {
        "step_host_ms": step_host_ms(summary, spans),
        "step_idle_host_ms": step_idle_host_ms(summary, spans),
        "engine_steps": len(_steps(summary, spans)),
        "chipbench_step_ms_p50": 1e-6 * statistics.median(bench_steps) if bench_steps else None,
        "idle_gaps": [[g.name, g.dur * 1e-9] for g in gaps],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    pd = tracefile.load(args.trace)
    print(json.dumps(report(tracefile.summarize(pd), program_spans(pd), args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
