"""The trace reduction and the program's own spans on a paged v5e trace of
the longctx cell, recorded on the chip by ``record_trace.py`` (seed 7):
deepseek-7b widths with 3 layers, 8 paged sessions after their admission
(contexts 1026-1540), then three decode steps, each an ``engine.step``
span with its children inside a ``chipbench.step``."""

from __future__ import annotations

import json
import re
import statistics
from pathlib import Path

import pytest

from chipbench import hostspans, readings, spec, tracefile
from chipbench.harness import Call, Record, Run

dense = spec.load_family("dense")
DATA = Path(__file__).parent / "data"
TRACE = DATA / "v5e_longctx_3layer.xplane.pb.gz"
CALLS = json.loads((DATA / "v5e_longctx_3layer.calls.json").read_text())
DIMS = dense.Dims(**CALLS["dims"])
STEPS, ROWS = 3, 8
CHILDREN = ["engine.step.inputs", "engine.step.launch", "engine.step.wait",
            "engine.step.retire", "engine.step.calibrate"]


@pytest.fixture(scope="module")
def profile():
    pd = tracefile.load(TRACE)
    return tracefile.summarize(pd), hostspans.program_spans(pd)


def _count(summary, pattern):
    return sum(c for (_, text), (_, c) in summary.ops.items() if re.search(pattern, text))


def _run(summary):
    calls = [Call(**{**c, "contexts": tuple(c["contexts"])}) for c in CALLS["calls"]]
    rec = Record(t_start=0.0, t0=calls[0].t0, t1=calls[-1].t1, trace_t0=calls[0].t0)
    rec.calls = calls
    return Run(cell=None, family=dense, dims=DIMS, geometry=None, record=rec, device_kind="TPU v5 lite",
               trace=summary)


def test_paged_kernels_by_stable_name(profile):
    s, _ = profile
    assert s.module_ns(readings.STEP_PROGRAM)[1] == STEPS
    # One paged-attention kernel per layer and step; the step's GEMMs.
    assert _count(s, readings.PAGED_ATTN_KERNEL) == STEPS * DIMS.n_layers
    assert _count(s, readings.GEMM_KERNEL) == STEPS * len(dense.step_gemms(DIMS, ROWS))


def test_readers_on_the_paged_trace(profile):
    s, _ = profile
    run = _run(s)
    assert 0 < readings.paged_attn_roofline_pct(run) <= 100
    assert 0 < readings.gemm_roofline_pct(run) <= 100
    assert 0 < readings.idle_share_pct(run) < 100
    assert 0 < readings.step_mfu_pct(run) <= 100
    assert readings.decode_step_ms(run) == pytest.approx(
        s.module_ns(readings.STEP_PROGRAM)[0] * 1e-6 / STEPS)


def test_program_spans_on_the_paged_trace(profile):
    s, spans = profile
    steps = [sp for sp in spans if sp.name == "engine.step"]
    assert len(steps) == STEPS
    bench = [sp for sp in s.spans if sp.name == "chipbench.step"]
    turns = []
    for st, outer in zip(steps, bench):
        assert outer.start <= st.start and st.end <= outer.end
        kids = [sp for sp in spans if sp.name.startswith("engine.step.")
                and st.start <= sp.start and sp.end <= st.end]
        assert [k.name for k in kids] == CHILDREN
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        turns.append(st.dur - kids[2].dur)
    host = hostspans.step_host_ms(s, spans)
    assert host == pytest.approx(1e-6 * statistics.median(turns))
    assert 0 < hostspans.step_idle_host_ms(s, spans) <= 1e-6 * max(turns)
    # Every idle gap whose midpoint lies in an engine.step is named by the
    # innermost program span there.
    for g in hostspans.named_gaps(s, spans):
        mid = (g.start + g.end) / 2
        if any(st.start <= mid <= st.end for st in steps):
            assert g.name.startswith("step/engine.step"), g
