"""Percentiles, window arithmetic, interval unions, traffic quotas and work
counts, on numbers worked out by hand."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import check, readings, stats, tracefile, traffic, work
from chipbench.harness import Call, Record, Run
from chipbench.model import Dims


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys_linear(n, q):
    xs = np.random.default_rng(n).lognormal(size=n)
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_takes_all_samples():
    # Two chunks whose medians are 1 and 10: a median of the chunks' medians
    # would read 5.5; the median of all samples is 2.
    xs = [1, 1, 1, 2, 10, 10, 10]
    assert stats.percentile(xs, 50) == 2


def _run(rec):
    return Run(cell=None, dims=None, geometry=None, record=rec, device_kind="cpu")


def test_window_arithmetic():
    rec = Record(t_start=0.0, t0=10.0, t1=20.0)
    # Request 1: first token in set-up, three more in the window.
    rec.token_times[1] = [9.0, 11.0, 11.5, 13.0]
    # Request 2: arrives in the window, two tokens inside, one after it.
    rec.token_times[2] = [12.5, 12.6, 21.0]
    # Request 3: arrives near the close, first token after it.
    rec.token_times[3] = [20.5]
    run = _run(rec)
    assert readings.window_tokens(run) == 5
    assert sorted(readings.inter_token_gaps_ms(run)) == pytest.approx([100.0, 500.0, 1500.0])


def test_union_and_gaps():
    assert tracefile.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 12)], 1, 10) == [
        (1, 3), (5, 7), (9, 10)]
    spans = [tracefile.Interval(0, 4, "chipbench.step"), tracefile.Interval(4, 9, "chipbench.admit")]
    starts = [s.start for s in spans]
    assert tracefile.covering(spans, starts, 3.5) == "step"
    assert tracefile.covering(spans, starts, 8.0) == "admit"
    assert tracefile.covering(spans, starts, 9.5) == "outside the benchmark's spans"
    assert tracefile.op_label(
        "%copy.146 = bf16[24,8,1024,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[24,8]{1,0} %x)"
    ) == "copy bf16[24,8,1024,8,128] copy.146"
    assert tracefile.op_label(
        "%convert.27 = bf16[3,4096,11008]{2,1,0} convert(f32[3,4096,11008]{2,1,0} "
        "%params__blocks____mlp____w1__.1)") == "convert bf16[3,4096,11008] convert.27 of blocks.mlp.w1"
    assert tracefile.op_label(
        '%closed_call.7 = bf16[128,8]{1,0} custom-call(bf16[128,64]{1,0} %a, bf16[64,8]{1,0} %b), '
        'custom_call_target="tpu_custom_call"') == "pallas bf16[128,8] closed_call.7"


def test_fast_memory_is_the_result_layout():
    assert tracefile.in_fast_memory(
        "%fusion.230 = bf16[2048,1024]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[2,2048,1024]{2,1,0} %g)")
    # Read from fast memory, written to HBM: not a staging operation.
    assert not tracefile.in_fast_memory(
        "%fusion.237 = bf16[2048,8192]{1,0:T(8,128)(2,1)} fusion(bf16[2,2048,8192]{2,1,0:S(1)} %g)")
    assert tracefile.in_fast_memory(
        "%copy-start.1 = (bf16[8]{0:S(1)}, bf16[8]{0}, u32[]{:S(2)}) copy-start(bf16[8]{0} %p)")
    assert not tracefile.in_fast_memory("%while.5 = (s32[]{:T(128)}, bf16[8]{0}) while(%t)")


def test_traffic_is_the_same_work_for_every_seed():
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 2.0,
                        "prompt_len": {"values": [64, 128, 256, 512], "weights": [0.4, 0.3, 0.2, 0.1]},
                        "output_len": {"lognormal": {"median": 64, "sigma": 0.8}, "min": 16, "max": 448}}}
    plans = [traffic.plan(mix, s, 50.0, vocab=100, lane=1024, pods=2, slots_per_pod=4)
             for s in (1, 2**33 + 7)]
    a, b = (p.arrivals for p in plans)
    assert len(a) == len(b) == 100
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a].count(64) == 40
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.at for r in a] != [r.at for r in b]
    assert a[-1].at == pytest.approx(b[-1].at)
    assert plans[0].warm_prompt_lens == [64, 128, 256, 512]
    assert all(16 <= r.max_new <= 448 for r in a)


def test_sessions_fill_every_pod():
    mix = {"sessions": {"prompt_len": {"values": [1024, 1536], "weights": [1, 1]},
                        "output_len": "to_lane_end"},
           "backlog": {"prompt_len": 64, "output_len": "to_lane_end"}}
    p = traffic.plan(mix, 9, 30.0, vocab=100, lane=4096, pods=2, slots_per_pod=4)
    assert sorted(r.route_class for r in p.sessions) == [0, 0, 0, 0, 1, 1, 1, 1]
    assert sorted(len(r.prompt) for r in p.sessions) == [1024] * 4 + [1536] * 4
    assert all(len(r.prompt) + r.max_new == 4096 for r in p.sessions)
    assert p.warm_prompt_lens == [64, 1536]


def _served(rid, slot, n):
    return check.Served(rid, np.zeros(4, np.int32), np.zeros(n, np.int32), slot=slot)


@pytest.mark.parametrize("seed", range(6))
def test_sample_covers_every_slot(seed):
    # Eight slots; the longest request (rid 0) is in slot 0, and slot 7
    # served only one short request.
    served = [_served(0, 0, 900)] + [_served(r, r % 7, 100 + r) for r in range(1, 40)]
    served.append(_served(40, 7, 3))
    got = check.sample(served, np.random.default_rng(seed), min_tokens=400, min_requests=2)
    assert got[0].rid == 0
    assert {s.slot for s in got} == set(range(8))
    assert len({s.rid for s in got}) == len(got)
    # Nothing beyond the slots is needed: 900 tokens passed 400.
    assert len(got) == 8


def test_sample_fills_to_the_token_floor():
    served = [_served(r, 0, 10) for r in range(50)]
    got = check.sample(served, np.random.default_rng(1), min_tokens=95, min_requests=2)
    assert len(got) == 10 and len({s.rid for s in got}) == 10


DIMS = Dims(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, d_head=4, d_ff=16, vocab=10,
            rope_theta=1e4, norm_eps=1e-6)


def test_work_counts():
    shapes = work.step_gemms(DIMS, rows=3)
    assert len(shapes) == 2 * 7 + 1 and shapes[-1] == (3, 8, 10)
    # One GEMM 4x4x4 in bf16: 128 FLOP, 96 bytes.
    assert work.gemm_roofline_s([(4, 4, 4)], 128.0, 1e9) == pytest.approx(1.0)
    assert work.gemm_roofline_s([(4, 4, 4)], 1e9, 96.0) == pytest.approx(1.0)
    # Paged attention, two rows of 3 and 5 keys, 2 layers: K and V of 8
    # keys x 1 head x 4 dims x 2 bytes x 2 = 128 bytes; q and out 2 rows x
    # 2 heads x 4 x 2 bytes x 2 = 64 bytes; per layer.
    assert work.paged_attn_roofline_s(DIMS, [3, 5], 1e30, 1.0) == pytest.approx(2 * 192)
    # Non-embedding parameters: 2 layers x (64+32+32+64 + 3*128 + 16) + 8 + 80.
    n = 2 * (64 + 32 + 32 + 64 + 3 * 128 + 16) + 8 + 80
    assert work.token_flops(DIMS, 5) == 2 * n + 4 * 2 * 5 * 2 * 4
    assert work.prompt_flops(DIMS, 3) == sum(work.token_flops(DIMS, k) for k in (1, 2, 3))


def test_mfu_counts_prompts_and_tokens():
    rec = Record(t_start=0.0, t0=0.0, t1=2.0, trace_t0=1.0)
    rec.calls = [Call("admit", 0.5, 0.9, 4, round_len=3, contexts=(3,)),
                 Call("admit", 1.0, 1.2, 4, round_len=3, contexts=(3, 2)),
                 Call("step", 1.3, 1.4, 4, contexts=(4, 3))]

    class Trace:
        window_ns = 1e9

    run = Run(cell=None, dims=DIMS, geometry=None, record=rec, device_kind="TPU v5 lite",
              trace=Trace())
    flops = (work.prompt_flops(DIMS, 3) + work.prompt_flops(DIMS, 2)
             + work.token_flops(DIMS, 4) + work.token_flops(DIMS, 3))
    assert readings.step_mfu_pct(run) == pytest.approx(100 * flops / 197e12)
    assert len(readings._gemm_shapes(run)) == (3 + 1) * 15
