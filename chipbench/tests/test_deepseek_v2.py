"""The ``deepseek_v2`` family (latent attention, a dense layer, then a share
of the experts) as the benchmark finds and judges it: its configuration
and cell are found by name, and at a size a CPU test run holds a sound run
is ``correct`` while the float8 control and a step that drops one routed
expert's output are not."""

from __future__ import annotations

import json
import time

import jax
import pytest

from chipbench import harness, spec
from chipbench.tests import tiny

# DeepSeek-V2-Lite's config.json keys at a size the CPU holds: 3 layers (1
# dense), this share holding 4 of 8 experts, top-3, YaRN as published.
# The limit lies between a sound run's widest gap (0.0047-0.0087 at three
# seeds) and that of a step that drops expert 0 (0.038-0.159).
CONFIG = {
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "kv_lora_rank": 32, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "topk_method": "greedy", "v_head_dim": 16, "vocab_size": 256,
    "chipbench": {"family": "deepseek_v2", "arch": "deepseek-v2-lite", "name": "tiny-v2",
                  "reduced": ["num_hidden_layers", "n_routed_experts"],
                  "published": {"n_routed_experts": 8}, "first_held_expert": 0,
                  "engine": {"pods": 2, "slots_per_pod": 2, "lane": 128, "paged": "on"},
                  "limits": {"widest_gap": 0.02}},
}


def _cell(tmp_path):
    root = tiny.make(tmp_path, paged="on")
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(CONFIG))
    return root, spec.load_cell("tiny.burst", root, root / "chipbench")


def _run(tmp_path, **kw):
    root, cell = _cell(tmp_path)
    return harness.run(cell, seed=2**33 + 7, seconds=2.0, trace=False, root=root,
                       t_start=time.perf_counter(), **kw)


def test_family_and_cell_are_found_by_name():
    cell = spec.load_cell("deepseek-v2-lite-1chip.mladecode", tiny.ROOT)
    assert cell.family.__file__ == str(tiny.ROOT / "chipbench/families/deepseek_v2.py")
    assert {m.name for m in cell.per_layer} == {
        "decode_step_ms.mladecode", "device_idle_share.mladecode",
        "mla_attn_roofline.mladecode", "expert_gemm_roofline.mladecode",
        "moe_route_share.mladecode", "step_mfu.mladecode"}
    d = cell.family.dims(cell.config)
    assert (d.n_experts, d.n_router, d.first_expert, d.top_k, d.n_dense) == (8, 64, 0, 6, 1)
    assert d.latent == 576 and d.d_shared == 2 * 1408
    assert d.scale == pytest.approx(0.114721, abs=1e-6)
    cfg = cell.family.arch_config(cell.config, d)
    assert (cfg.moe.n_experts, cfg.moe.router_width, cfg.moe.norm_topk_prob) == (8, 64, False)
    assert cfg.mla.scale == pytest.approx(d.scale)


def test_control_fails_the_limit_the_program_passes(tmp_path):
    out = _run(tmp_path, control_bits=3)
    c, ctl = out["checks"]["widest_gap"], out["control"]["checks"]["widest_gap"]
    assert c["value"] <= c["limit"] == ctl["limit"] < ctl["value"]
    assert out["correct"]
    assert out["control"]["correct"] is False


def drop_expert(eng, expert=0):
    """The decode step computes without one held expert's output: its
    down projection is zero in every MoE layer, in the step alone."""

    step = eng._step

    def broken(params, batch, state, pos):
        moe = params["blocks"]["moe"]
        w2 = moe["w2"].at[:, expert].set(0.0)
        params = {**params, "blocks": {**params["blocks"], "moe": {**moe, "w2": w2}}}
        return step(params, batch, state, pos)

    eng._step = jax.jit(broken)


def test_step_that_drops_an_expert_is_not_correct(tmp_path):
    out = _run(tmp_path, engine_hook=drop_expert)
    c = out["checks"]["widest_gap"]
    assert not out["correct"]
    assert c["value"] > c["limit"]
