"""The program's own host spans: gap names and the two host-turn readings,
on hand-built intervals and on the committed dense trace (which holds
none of the program's spans)."""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import hostspans, tracefile
from chipbench.tracefile import Interval, Summary

DENSE = Path(__file__).parent / "data" / "v5e_engine_2layer.xplane.pb.gz"
MS = 1e5  # ns per unit of the hand-built timeline below


def _iv(a, b, name):
    return Interval(a * MS, b * MS, name)


# Two decode steps inside a window of 0-1000; a third starts at its end.
SPANS = sorted([
    _iv(100, 400, "engine.step"),
    _iv(100, 150, "engine.step.inputs"), _iv(150, 160, "engine.step.launch"),
    _iv(160, 380, "engine.step.wait"), _iv(380, 395, "engine.step.retire"),
    _iv(395, 400, "engine.step.calibrate"),
    _iv(500, 760, "engine.step"),
    _iv(500, 540, "engine.step.inputs"), _iv(540, 550, "engine.step.launch"),
    _iv(550, 700, "engine.step.wait"), _iv(700, 750, "engine.step.retire"),
    _iv(710, 740, "host.gc"), _iv(750, 760, "engine.step.calibrate"),
    _iv(1000, 1100, "engine.step"), _iv(1010, 1090, "engine.step.wait"),
], key=lambda s: (s.start, -s.end))

# The first chip's idle gaps, named by the benchmark's span around each.
GAPS = [_iv(90, 140, "step"), _iv(390, 520, "client"), _iv(600, 610, "step"),
        _iv(715, 735, "step"), _iv(900, 950, "client")]


def _summary(gaps=GAPS):
    return Summary(window=(0.0, 1000 * MS), n_devices=1, busy_ns=0.0, ops={}, self_ns={},
                   modules=[], gaps=gaps, spans=[])


def test_gaps_take_the_innermost_program_span():
    named = hostspans.named_gaps(_summary(), SPANS)
    assert [g.name for g in named] == [
        "step/engine.step.inputs",   # midpoint 115: inside the first step's inputs
        "client",                    # midpoint 455: between the steps
        "step/engine.step.wait",
        "step/host.gc",              # inside retire, inside the collection
        "client",
    ]
    assert [(g.start, g.end) for g in named] == [(g.start, g.end) for g in GAPS]


def test_step_host_ms():
    # Each step less its wait: 300 - 220 and 260 - 150 units; the step that
    # starts at the window's end is left out.
    assert hostspans.step_host_ms(_summary(), SPANS) == pytest.approx(95 * MS * 1e-6)


def test_step_idle_host_ms():
    # Idle inside the first step: 40 (inputs) + 10 (before its end); inside
    # the second: 20 + 10 + 20, less the 10 inside its wait.  Over 2 steps.
    assert hostspans.step_idle_host_ms(_summary(), SPANS) == pytest.approx(45 * MS * 1e-6)
    # No idle time inside any step reads 0, not None.
    assert hostspans.step_idle_host_ms(_summary([_iv(900, 950, "client")]), SPANS) == 0.0


def test_nothing_to_read_without_program_spans():
    assert hostspans.step_host_ms(_summary(), []) is None
    assert hostspans.step_idle_host_ms(_summary(), []) is None
    assert hostspans.named_gaps(_summary(), []) == GAPS


def test_committed_dense_trace_holds_no_program_spans():
    pd = tracefile.load(DENSE)
    s = tracefile.summarize(pd)
    spans = hostspans.program_spans(pd)
    assert spans == []
    out = hostspans.report(s, spans)
    assert out["step_host_ms"] is None and out["step_idle_host_ms"] is None
    assert out["engine_steps"] == 0
    assert [name for name, _ in out["idle_gaps"]] == [
        name for name, _ in tracefile.breakdown(s)["idle_gaps"]]
    assert out["chipbench_step_ms_p50"] > 0
