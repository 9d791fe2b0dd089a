"""DeepSeek-V2's layers against their plain float32 reference, on the CPU.

A tiny DeepSeek-V2-shaped configuration (latent attention with YaRN, one
dense layer, then MoE layers holding a share of the experts) with seeded
random weights:

  * the program's prefill logits against the reference of
    ``chipbench/families/deepseek_v2.py``, and the same comparison with
    each of YaRN, the softmax scale's ``m²``, ``kv_a_layernorm``, the
    un-renormalised top-k weights and the shared experts left out of the
    reference, each of which the tolerance must catch;
  * paged absorbed decode through ``ServingEngine`` (the XLA gather and
    the Pallas kernel's interpreter) against the reference's full
    forward pass, as logits;
  * the paged latent kernel against its float32 oracle;
  * YaRN's frequencies against the published closed form;
  * dropless routing against a masked dense sum, and the 8 shares of an
    MoE layer (the shared expert counted once) against the uncut layer.

Tolerances are relative L2 norms of the logit (or output) difference.
The program computes in bfloat16 (8 significand bits, about 0.4% a
rounding) against a float32 reference: through 3 layers its logits read
about 0.03 from the reference, and every omission above reads 0.11 or
more, so 0.06 sits about twice from each.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec
from chipbench.tests.test_deepseek_v2 import CONFIG
from repro.core.asymmetric import AsymmetricMesh, DeviceClass
from repro.kernels import ref as R
from repro.kernels.paged_attention import paged_mla_pallas_interpret, paged_mla_xla
from repro.models import layers as L
from repro.models import model_zoo as Z
from repro.models.moe import MoEConfig, apply_moe_dropless, init_moe
from repro.runtime.serving import ServingEngine

FAMILY = spec.load_family("deepseek_v2")
LOGIT_RTOL = 0.06     # bfloat16 program against the float32 reference (module docstring)

# chipbench's tiny DeepSeek-V2-Lite configuration: 3 layers (1 dense), this
# share holding 4 of 8 experts, top-3, YaRN as published.
DIMS = FAMILY.dims(CONFIG)
CFG = FAMILY.arch_config(CONFIG, DIMS)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def params():
    return jax.jit(FAMILY.init, static_argnums=1)(jax.random.PRNGKey(0), DIMS)


def reference_logits(params, tokens, dims=DIMS):
    with jax.default_matmul_precision("highest"):
        return FAMILY.logits(params, FAMILY.hidden(params, dims, jnp.asarray(tokens, jnp.int32)))


TOKENS = np.random.default_rng(1).integers(0, DIMS.vocab, 128).astype(np.int32)


@pytest.fixture(scope="module")
def prefill_logits(params):
    return jax.jit(Z.make_prefill_fn(CFG))(params, {"tokens": jnp.asarray(TOKENS)[None]})[0]


def test_prefill_matches_reference(params, prefill_logits):
    assert rel(prefill_logits, reference_logits(params, TOKENS)) < LOGIT_RTOL


class _NoM2(FAMILY.Dims):
    @property
    def scale(self):
        return (self.qk_nope + self.qk_rope) ** -0.5


def _plain_rope(d):
    return d.rope_theta ** (-np.arange(0, d.qk_rope, 2) / d.qk_rope)


OMISSIONS = {
    "yarn": lambda mp: mp.setattr(FAMILY, "yarn_inv_freq", _plain_rope),
    "m_squared": lambda mp: None,
    "kv_a_layernorm": lambda mp: mp.setattr(
        FAMILY, "rms_norm",
        lambda x, w, eps, f=FAMILY.rms_norm: x if w.shape[-1] == DIMS.kv_rank else f(x, w, eps)),
    "topk_not_renormalised": lambda mp: None,
    "shared_experts": lambda mp: mp.setattr(
        FAMILY, "moe", lambda m, n, d, bits=None: FAMILY._experts(m, n, FAMILY.gates(m, n, d, bits), bits)),
}


@pytest.mark.parametrize("left_out", list(OMISSIONS))
def test_tolerance_catches_an_omission(params, prefill_logits, left_out, monkeypatch):
    """The reference with one part of the equations left out reads farther
    from the program than the tolerance allows."""

    OMISSIONS[left_out](monkeypatch)
    dims = DIMS
    if left_out == "m_squared":
        dims = _NoM2(**dataclasses.asdict(DIMS))
    if left_out == "topk_not_renormalised":
        dims = dataclasses.replace(DIMS, norm_topk=True)
    assert rel(prefill_logits, reference_logits(params, TOKENS, dims)) > LOGIT_RTOL


@pytest.mark.parametrize("paged_attn", ["paged_attn_xla", "paged_attn_pallas_interpret"])
def test_engine_decode_matches_reference(params, paged_attn, monkeypatch):
    """Prompts prefilled and decoded through the paged latent cache by the
    serving engine: each busy slot's next-step logits against the
    reference's full forward pass over its prompt and served tokens."""

    made = AsymmetricMesh.execution_context

    def ctx(self, *a, **kw):
        return dataclasses.replace(made(self, *a, **kw), paged_attn=paged_attn)

    monkeypatch.setattr(AsymmetricMesh, "execution_context", ctx)
    asym = AsymmetricMesh([DeviceClass(name="big", n_pods=1, chips_per_pod=1)],
                          strategy="ca-das", batch_tile=1)
    eng = ServingEngine(CFG, params, asym, seq_cap=64, slots_per_pod=3, paged="on",
                        class_sharded="off")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, DIMS.vocab, n).astype(np.int32) for n in (20, 13)]
    for p in prompts:
        eng.submit(p, 12)
    assert eng.admit() == 2
    for _ in range(6):
        eng.step()
    logits = np.asarray(eng.step_logits(), np.float32)[:, 0]
    for slot, req in eng._slot_req.items():
        seq = np.concatenate([req.prompt, np.asarray(eng._slot_toks[slot], np.int32)])
        want = reference_logits(params, seq)[-1]
        assert rel(logits[slot], want) < LOGIT_RTOL, slot


def test_paged_latent_kernel_matches_oracle():
    rng = np.random.default_rng(3)
    b, h, r, e, n_layers, n_pages, ps, w = 5, 4, 32, 8, 3, 12, 8, 4
    q_c = jnp.asarray(rng.normal(size=(b, h, r)), jnp.bfloat16)
    q_pe = jnp.asarray(rng.normal(size=(b, h, e)), jnp.bfloat16)
    pages_c = jnp.asarray(rng.normal(size=(n_layers, n_pages, ps, r)), jnp.bfloat16)
    pages_pe = jnp.asarray(rng.normal(size=(n_layers, n_pages, e, ps)), jnp.bfloat16)
    table = rng.integers(0, n_pages, (b, w))
    table[0, 3] = 1 << 30                                    # an unallocated page past pos
    pos = jnp.asarray([0, 7, 8, 20, 31 - 8], jnp.int32)
    table = jnp.asarray(table, jnp.int32)
    want = R.paged_mla_ref(q_c, q_pe, pages_c, pages_pe, table, pos, 1, scale=0.3)
    for fn in (paged_mla_xla, paged_mla_pallas_interpret):
        got = fn(q_c, q_pe, pages_c, pages_pe, table, pos, jnp.int32(1), scale=0.3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_yarn_frequencies_match_closed_form():
    """DeepSeek-V2-Lite's rope (dim 64, theta 1e4, factor 40 over 4096):
    correction range [10, 23], ``f/40·ramp + f·(1 - ramp)``."""

    yarn = L.Yarn(factor=40, original_max_position=4096, beta_fast=32, beta_slow=1,
                  mscale=0.707, mscale_all_dim=0.707)
    f = 10000.0 ** (-np.arange(32) * 2 / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = f / 40 * ramp + f * (1 - ramp)
    np.testing.assert_allclose(L.yarn_inv_freq(64, 10000.0, yarn), want, rtol=1e-6)
    full = FAMILY.dims({**CONFIG, "qk_rope_head_dim": 64})
    np.testing.assert_allclose(FAMILY.yarn_inv_freq(full), want, rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    mla = L.MLAConfig(d_model=2048, n_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, yarn=yarn)
    assert mla.scale == pytest.approx(192 ** -0.5 * m * m) == pytest.approx(0.114721, abs=1e-6)


def _moe(n_experts=64, top_k=6, **kw):
    cfg = MoEConfig(d_model=32, n_experts=n_experts, top_k=top_k, d_ff_expert=16,
                    d_ff_shared=32, norm_topk_prob=False, shared_gate=False, **kw)
    return cfg, init_moe(jax.random.PRNGKey(2), cfg)


X = jnp.asarray(np.random.default_rng(4).normal(size=(40, 32)), jnp.float32)


def test_dropless_matches_masked_dense_sum():
    """Every token's top-k choices computed (none dropped), weighted by
    their un-renormalised probabilities, plus the ungated shared expert."""

    cfg, p = _moe()
    y, routed = apply_moe_dropless(p, X, cfg)
    # The program routes its bfloat16 activations, in float32.
    xr = X.astype(jnp.bfloat16).astype(jnp.float32)
    probs = jax.nn.softmax(jnp.dot(xr, p["router"], precision="highest"), -1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(idx, cfg.n_experts))

    def glu(m, v):
        return (jax.nn.silu(v @ m["w1"]) * (v @ m["w3"])) @ m["w2"]

    want = sum(gates[:, e:e + 1] * glu({k: p[k][e] for k in ("w1", "w3", "w2")}, X)
               for e in range(cfg.n_experts)) + glu(p["shared"], X)
    assert rel(y, want) < 2e-2
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(gates > 0).astype(np.int32))


def test_expert_shares_sum_to_the_uncut_layer():
    """8 chips' shares of a 64-expert layer, each holding 8 experts and
    routing over all 64: their outputs, with the shared expert (which
    every share computes alike) counted once, add up to the uncut layer."""

    cfg, p = _moe()
    whole, _ = apply_moe_dropless(p, X, cfg)
    parts, counts = [], 0
    for i in range(8):
        share = dataclasses.replace(cfg, n_experts=8, n_router=64, first_expert=8 * i)
        held = {k: p[k][8 * i:8 * i + 8] for k in ("w1", "w3", "w2")}
        y, routed = apply_moe_dropless({**p, **held}, X, share)
        parts.append(np.asarray(y, np.float32))
        counts += int(routed.sum())
    shared = np.asarray(L.apply_glu(p["shared"], X.astype(jnp.bfloat16)), np.float32)
    assert rel(sum(parts) - 7 * shared, whole) < 2e-2
    assert counts == X.shape[0] * cfg.top_k
