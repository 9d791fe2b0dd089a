"""The trace reduction on a small v5e trace of the engine, recorded on the
chip by ``chipbench``'s own spans: internlm2-1.8b widths with 2 layers,
2 pods x 4 dense slots of 1024, one admission round of a 64- and a
40-token prompt, then three decode steps."""

from __future__ import annotations

import re
from pathlib import Path

import pytest

from chipbench import readings, spec, tracefile
from chipbench.harness import Call, Record, Run

dense = spec.load_family("dense")
TRACE = Path(__file__).parent / "data" / "v5e_engine_2layer.xplane.pb.gz"
DIMS = dense.Dims(n_layers=2, d_model=2048, n_heads=16, n_kv_heads=8, d_head=128, d_ff=8192,
            vocab=92544, rope_theta=1e6, norm_eps=1e-5)
ROUND, STEPS, ROWS = 64, 3, 8


def own_ns(s, pattern):
    """Time and count of the matching operations alone, without their
    operands' producers."""

    hits = [v for (_, text), v in s.ops.items() if re.search(pattern, text)]
    return sum(t for t, _ in hits), sum(c for _, c in hits)


@pytest.fixture(scope="module")
def summary():
    return tracefile.summarize(tracefile.load(TRACE))


def test_window_busy_and_gaps(summary):
    s = summary
    assert s.n_devices == 1
    assert s.window == (s.spans[0].start, max(sp.end for sp in s.spans))
    assert 0 < s.busy_ns < s.window_ns
    # One chip: the idle gaps are exactly the window less the union of
    # the device's operations.
    assert sum(g.dur for g in s.gaps) == pytest.approx(s.window_ns - s.busy_ns)
    assert {g.name for g in s.gaps} <= {"client", "admit", "step"}
    assert [sp.name for sp in s.spans] == ["chipbench.client", "chipbench.admit"] + [
        "chipbench.client", "chipbench.step"] * STEPS


def test_programs_and_kernels_by_stable_name(summary):
    s = summary
    assert s.module_ns(r"^jit_prefill_fn\(")[1] == 1
    assert s.module_ns(readings.STEP_PROGRAM)[1] == STEPS
    # Every GEMM of the model, found by the kernel's name: one decode
    # step's (7 per layer and the head) per scanned prompt position and
    # per decode step.
    per_step = len(dense.step_gemms(DIMS, ROWS))
    assert per_step == 2 * 7 + 1
    assert own_ns(s, readings.GEMM_KERNEL)[1] == (ROUND + STEPS) * per_step
    # A kernel's time takes in the operations that stage its operands into
    # fast memory (the weight slices XLA writes to S(1)), and none that
    # writes to HBM: the LM head's weight, padded at every prompt position,
    # is left out.
    ns, n = s.kernel_ns(readings.GEMM_KERNEL)
    own, _ = own_ns(s, readings.GEMM_KERNEL)
    staged = {(p, text.split(" = ")[0]) for (p, text) in s.ops if tracefile.in_fast_memory(text)}
    added = 0.0
    for (p, text), (t, _) in s.ops.items():
        if re.search(readings.GEMM_KERNEL, text):
            continue
        name = text.split(" = ")[0]
        feeds = any(name in tracefile.operand_names(k) for (q, k) in s.ops
                    if q == p and re.search(readings.GEMM_KERNEL, k))
        if feeds and (p, name) in staged:
            added += t
    head_pad = sum(t for (_, text), (t, _) in s.ops.items()
                   if text.startswith("%pad.") and "bf16[2048,93184]" in text.split(" = ")[1][:20])
    assert n == own_ns(s, readings.GEMM_KERNEL)[1]
    assert head_pad > 0
    assert ns == pytest.approx(own + added) and own < ns < own + head_pad
    # The dense engine runs no paged-attention kernel.
    assert own_ns(s, readings.PAGED_ATTN_KERNEL) == (0, 0)


def _run(summary):
    rec = Record(t_start=0.0, t0=0.0, t1=1.0, trace_t0=0.0)
    rec.calls = [Call("admit", 0.1, 0.3, ROWS, round_len=ROUND,
                      contexts=(64, 40))]
    rec.calls += [Call("step", 0.4 + i / 10, 0.45 + i / 10, ROWS, contexts=(65 + i, 41 + i))
                  for i in range(STEPS)]
    return Run(cell=None, family=dense, dims=DIMS, geometry=None, record=rec, device_kind="TPU v5 lite",
               trace=summary)


def test_readers_on_the_trace(summary):
    run = _run(summary)
    assert 0 < readings.idle_share_pct(run) < 100
    assert 0 < readings.gemm_roofline_pct(run) <= 100
    # The kernels alone read faster than HBM allows: the staging copies
    # that read the weights from HBM belong to their time.
    own, _ = own_ns(summary, readings.GEMM_KERNEL)
    assert readings.gemm_roofline_pct(run) * summary.kernel_ns(readings.GEMM_KERNEL)[0] / own > 100
    assert 0 < readings.step_mfu_pct(run) <= 100
    assert readings.paged_attn_roofline_pct(run) is None
    assert readings.decode_step_ms(run) == pytest.approx(
        summary.module_ns(readings.STEP_PROGRAM)[0] * 1e-6 / STEPS)


def test_breakdown(summary):
    b = tracefile.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    # Self times never exceed the window's busy time.
    assert sum(summary.self_ns.values()) <= summary.busy_ns * 1.0001
    assert all(label.startswith(("jit_", "no program")) for label, _ in b["device_ops"])


# The paged-attention kernel of the longctx cell as a v5e trace names it
# (3 layers, 8 slots, 80 pages of 512 in each pool).
PAGED_ATTN_HLO = (
    "%closed_call.79 = bf16[8,32,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} custom-call(s32[8,8]{1,0:T"
    "(8,128)S(1)} %copy-done.1, s32[8]{0:T(128)S(1)} %copy-done.14, bf16[8,32,1,128]{3,2,1,0:"
    "T(2,128)(2,1)S(1)} %copy-done.11, bf16[80,32,512,128]{3,2,1,0:T(8,128)(2,1)} %copy_bitca"
    "st_fusion.4, bf16[80,32,512,128]{3,2,1,0:T(8,128)(2,1)} %copy_bitcast_fusion.5), custom_"
    "call_target=\"tpu_custom_call\", operand_layout_constraints={s32[8,8]{1,0}, s32[8]{0}, bf1"
    "6[8,32,1,128]{3,2,1,0}, bf16[80,32,512,128]{3,2,1,0}, bf16[80,32,512,128]{3,2,1,0}}, fro"
    "ntend_attributes={kernel_metadata={}}")


def test_paged_attention_kernel_name():
    assert re.search(readings.PAGED_ATTN_KERNEL, PAGED_ATTN_HLO)
    assert not re.search(readings.GEMM_KERNEL, PAGED_ATTN_HLO)
    # Its operands are read from HBM (the transposed K and V pools) or
    # staged into fast memory (page table, positions, queries).
    staged = [tracefile.in_fast_memory(f"%x = {a.split(' %')[0]} copy()")
              for a in tracefile.parse_op(PAGED_ATTN_HLO)[3].split(", ")]
    assert staged == [True, True, True, False, False]
