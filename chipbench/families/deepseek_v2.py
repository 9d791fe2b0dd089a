"""DeepSeek-V2: latent attention over every layer, leading dense layers,
then mixture-of-experts layers (arXiv:2405.04434; the published
``modeling_deepseek.py`` of DeepSeek-V2-Lite).

Its plain reference is written from the published equations, with nothing
taken from the program under test.  Per layer, with ``n = RMSNorm(x)``:

    q = Wq · n, split per head into q_nope (128) | q_pe (64)
    [c_kv | k_pe] = Wkva · n                     (kv_lora_rank | 64)
    c_kv = RMSNorm(c_kv)                         (kv_a_layernorm, eps as the model's)
    [k_nope | v] = Wkvb · c_kv, per head         (128 | 128)
    q_pe, k_pe = YaRN-RoPE(q_pe), YaRN-RoPE(k_pe)   (k_pe one head, shared)
    attn = softmax(scale · [q_nope|q_pe]·[k_nope|k_pe]ᵀ, causal) · v
    h = x + Wo · attn,   scale = (128 + 64)^-1/2 · m²,  m = 0.1·mscale_all_dim·ln(factor) + 1

then ``h + SwiGLU(RMSNorm(h))`` in the first ``first_k_dense_replace``
layers, and in the others

    p = softmax(RMSNorm(h) · Wgate) over all router outputs, in float32
    w, e = greedy top-k of p                     (not renormalised when
                                                  norm_topk_prob is false)
    out = h + Σ_{k: e_k held} routed_scaling_factor · w_k · E_{e_k}(n2) + S(n2)

with ``S`` the shared experts as one ungated SwiGLU of width
``n_shared_experts · moe_intermediate_size``.  YaRN (the published
``DeepseekV2YarnRotaryEmbedding``): ``f_i = theta^(-2i/d)``, correction
range ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` with
``c(r) = d·ln(L0 / (2π r)) / (2 ln theta)``, ``ramp = clip((i - low) /
(high - low), 0, 1)``, ``inv_freq = f/factor · ramp + f · (1 - ramp)``,
cos and sin scaled by ``yarn(mscale) / yarn(mscale_all_dim)`` (1 here).

Departures, each on both sides: the chip holds experts ``[first, first +
n_routed_experts)`` of the router's published count, and the other experts'
part is left out (the configuration's ``deployment``); rope rotates the
pairs ``(2i, 2i+1)`` in place, where the published code de-interleaves them
to the two halves first (the same rotation of the same pairs: a dot
product of two such vectors is unchanged).  Every product runs in float32
at ``precision="highest"``; ``bits`` is the control's rounding
(``reference.round_mantissa``).  The experts are a masked dense sum: every
token through every held expert, weighted by its gate (0 where it did not
choose that expert).

The work counts: the Pallas GEMMs' operands are bfloat16 at the step's row
count; latent attention reads each live context's latent rows (576 values
a token a layer) once; a token's FLOPs count every parameter it uses on
this chip, its routed experts at the routed share.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, _mm, rms_norm
from chipbench.work import BF16


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference and the work counts need."""

    n_layers: int           # all layers: the dense ones first
    n_dense: int            # first_k_dense_replace
    d_model: int
    n_heads: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    kv_rank: int
    d_ff: int               # the dense layers' MLP
    d_expert: int
    n_experts: int          # held on this chip
    first_expert: int       # the first held expert's router index
    n_router: int           # the router's outputs: every expert of the model
    top_k: int
    norm_topk: bool
    routed_scale: float
    d_shared: int
    vocab: int
    rope_theta: float
    yarn: tuple             # (factor, original_max, beta_fast, beta_slow, mscale, mscale_all_dim)
    norm_eps: float

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def latent(self) -> int:
        return self.kv_rank + self.qk_rope

    @property
    def scale(self) -> float:
        factor, _, _, _, _, m_all = self.yarn
        m = 0.1 * m_all * math.log(factor) + 1.0 if factor > 1 and m_all else 1.0
        return (self.qk_nope + self.qk_rope) ** -0.5 * m * m


def dims(c: dict) -> Dims:
    rs = c["rope_scaling"]
    if c.get("q_lora_rank") is not None:
        raise ValueError("the deepseek_v2 family covers latent attention without q compression")
    if c["scoring_func"] != "softmax" or c["topk_method"] != "greedy" or rs["type"] != "yarn":
        raise ValueError("the deepseek_v2 family covers softmax, greedy top-k routing and YaRN")
    return Dims(
        n_layers=int(c["num_hidden_layers"]),
        n_dense=int(c["first_k_dense_replace"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        qk_nope=int(c["qk_nope_head_dim"]),
        qk_rope=int(c["qk_rope_head_dim"]),
        v_dim=int(c["v_head_dim"]),
        kv_rank=int(c["kv_lora_rank"]),
        d_ff=int(c["intermediate_size"]),
        d_expert=int(c["moe_intermediate_size"]),
        n_experts=int(c["n_routed_experts"]),
        first_expert=int(c["chipbench"].get("first_held_expert", 0)),
        n_router=int(c["chipbench"].get("published", {}).get(
            "n_routed_experts", c["n_routed_experts"])),
        top_k=int(c["num_experts_per_tok"]),
        norm_topk=bool(c["norm_topk_prob"]),
        routed_scale=float(c["routed_scaling_factor"]),
        d_shared=int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
        vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        yarn=(float(rs["factor"]), int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
              float(rs["mscale_all_dim"])),
        norm_eps=float(c["rms_norm_eps"]),
    )


def arch_config(c: dict, d: Dims):
    """The registry's ``ArchConfig`` with every size the file states."""

    from repro.configs import get_config
    from repro.models.layers import MLAConfig, Yarn

    base = get_config(c["chipbench"]["arch"])
    if base.mla is None or base.moe is None or c.get("hidden_act", "silu") != "silu":
        raise ValueError("the deepseek_v2 family covers latent-attention MoE decoders")
    if d.routed_scale != 1.0:
        raise ValueError("the program scales no routed output: routed_scaling_factor must be 1")
    factor, l0, bf, bs, ms, ms_all = d.yarn
    mla = MLAConfig(d_model=d.d_model, n_heads=d.n_heads, kv_lora_rank=d.kv_rank,
                    qk_nope_head_dim=d.qk_nope, qk_rope_head_dim=d.qk_rope,
                    v_head_dim=d.v_dim, rope_theta=d.rope_theta, norm_eps=d.norm_eps,
                    yarn=Yarn(factor=factor, original_max_position=l0, beta_fast=bf,
                              beta_slow=bs, mscale=ms, mscale_all_dim=ms_all))
    moe = dataclasses.replace(
        base.moe, d_model=d.d_model, n_experts=d.n_experts, top_k=d.top_k,
        d_ff_expert=d.d_expert, d_ff_shared=d.d_shared, n_router=d.n_router,
        first_expert=d.first_expert, norm_topk_prob=d.norm_topk, shared_gate=False)
    return dataclasses.replace(
        base, name=c["chipbench"].get("name", base.name), n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_heads, d_head=d.v_dim,
        d_ff=d.d_ff, vocab=d.vocab, rope_theta=d.rope_theta, norm_eps=d.norm_eps,
        first_k_dense=d.n_dense, mla=mla, moe=moe)


# ---------------------------------------------------------------------------
# The parameter tree
# ---------------------------------------------------------------------------


def _attn_shapes(d: Dims, n: int) -> dict:
    h = d.n_heads
    return {"wq": (n, d.d_model, h * (d.qk_nope + d.qk_rope)),
            "wkv_a": (n, d.d_model, d.latent),
            "kv_norm": (n, d.kv_rank),
            "wkv_b": (n, d.kv_rank, h * (d.qk_nope + d.v_dim)),
            "wo": (n, h * d.v_dim, d.d_model)}


def _glu_shapes(n: int, dm: int, f: int) -> dict:
    return {"w1": (n, dm, f), "w3": (n, dm, f), "w2": (n, f, dm)}


def param_shapes(d: Dims) -> dict:
    """The engine's parameter tree: the dense layers under ``dense``, the
    MoE layers under ``blocks``, each stacked on a leading axis."""

    D, n = d.d_model, d.n_moe
    moe = {"router": (n, D, d.n_router),
           "w1": (n, d.n_experts, D, d.d_expert), "w3": (n, d.n_experts, D, d.d_expert),
           "w2": (n, d.n_experts, d.d_expert, D),
           "shared": _glu_shapes(n, D, d.d_shared)}
    return {
        "dense": {"ln1": (d.n_dense, D), "attn": _attn_shapes(d, d.n_dense),
                  "ln2": (d.n_dense, D), "mlp": _glu_shapes(d.n_dense, D, d.d_ff)},
        "blocks": {"ln1": (n, D), "attn": _attn_shapes(d, n), "ln2": (n, D), "moe": moe},
        "final_norm": (D,),
        "lm_head": (D, d.vocab),
        "embed": (d.vocab, D),
    }


def init(key, d: Dims):
    shapes = param_shapes(d)
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    leaves, tree = jax.tree.flatten(shapes, is_leaf=is_shape)
    keys = jax.random.split(key, len(leaves))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]]
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        z = jax.random.normal(k, shape, jnp.float32)
        if "ln" in path or "norm" in path:
            w = 1.0 + 0.1 * z                      # norm gains near 1
        elif "embed" in path or "lm_head" in path:
            w = 0.02 * z
        else:
            w = z / np.sqrt(shape[-2])             # fan-in scaling (the router too)
        out.append(w)
    return jax.tree.unflatten(tree, out)


@functools.cache
def param_count(d: Dims, *, embed: bool = True) -> int:
    n = sum(int(np.prod(s)) for s in
            jax.tree.leaves(param_shapes(d), is_leaf=lambda s: isinstance(s, tuple)))
    return n if embed else n - d.vocab * d.d_model


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def yarn_inv_freq(d: Dims) -> np.ndarray:
    """The rope's ``qk_rope / 2`` frequencies (module docstring)."""

    factor, l0, beta_fast, beta_slow, _, _ = d.yarn
    dim, theta = d.qk_rope, d.rope_theta
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    corr = lambda r: dim * math.log(l0 / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def rope_pairs(x, inv_freq):
    """x: (S, H, D) at positions 0..S-1; rotates the pairs (2i, 2i+1)."""

    s, _, dim = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _swiglu(n, m, bits):
    return _mm(jax.nn.silu(_mm(n, m["w1"], bits)) * _mm(n, m["w3"], bits), m["w2"], bits)


def _attention(q, k, v, scale: float, q_chunk: int):
    """Causal attention: q, k (S, H, Dk), v (S, H, Dv); queries in chunks."""

    s, h, _ = q.shape

    def chunk(args):
        i, qc = args
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST) * scale
        qi = i * q_chunk + jnp.arange(q_chunk)
        sc = jnp.where(qi[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision=HIGHEST)

    out = jax.lax.map(chunk, (jnp.arange(s // q_chunk), q.reshape(s // q_chunk, q_chunk, h, -1)))
    return out.reshape(s, -1)


def _mla(a, n, d: Dims, bits, q_chunk):
    s, h = n.shape[0], d.n_heads
    inv = yarn_inv_freq(d)
    q = _mm(n, a["wq"], bits).reshape(s, h, d.qk_nope + d.qk_rope)
    kv = _mm(n, a["wkv_a"], bits)
    c = rms_norm(kv[:, : d.kv_rank], a["kv_norm"], d.norm_eps)
    kvb = _mm(c, a["wkv_b"], bits).reshape(s, h, d.qk_nope + d.v_dim)
    k_pe = rope_pairs(kv[:, None, d.kv_rank:], inv)
    q = jnp.concatenate([q[..., : d.qk_nope], rope_pairs(q[..., d.qk_nope:], inv)], axis=-1)
    k = jnp.concatenate([kvb[..., : d.qk_nope], jnp.broadcast_to(k_pe, (s, h, d.qk_rope))], -1)
    o = _attention(q, k, kvb[..., d.qk_nope:], d.scale, q_chunk)
    return _mm(o, a["wo"], bits)


def gates(m, n, d: Dims, bits):
    """(S, held experts): each token's routing weight of each held expert,
    0 where it did not choose it."""

    p = jax.nn.softmax(_mm(n, m["router"], bits), axis=-1)
    w, e = jax.lax.top_k(p, d.top_k)
    if d.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(e - d.first_expert, d.n_experts, dtype=jnp.float32)  # 0 if not held
    return jnp.einsum("sk,ske->se", w * d.routed_scale, onehot, precision=HIGHEST)


def _experts(m, n, g, bits):
    """Σ_e g[:, e] · E_e(n) over the held experts, one expert at a time."""

    def one(y, args):
        w1, w3, w2, ge = args
        return y + ge[:, None] * _swiglu(n, {"w1": w1, "w3": w3, "w2": w2}, bits), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (m["w1"], m["w3"], m["w2"], g.T))
    return y


def moe(m, n, d: Dims, bits=None):
    """The MoE layer's output (without the residual) for normed ``n``."""

    return _experts(m, n, gates(m, n, d, bits), bits) + _swiglu(n, m["shared"], bits)


def hidden(params, dims: Dims, tokens, *, bits: Optional[int] = None, q_chunk: int = 256):
    """Final-normed hidden states ``(S, d_model)`` of ``tokens`` (S,)."""

    q_chunk = min(q_chunk, tokens.shape[0])
    x = params["embed"][tokens].astype(jnp.float32)

    def dense_layer(x, p):
        x = x + _mla(p["attn"], rms_norm(x, p["ln1"], dims.norm_eps), dims, bits, q_chunk)
        return x + _swiglu(rms_norm(x, p["ln2"], dims.norm_eps), p["mlp"], bits), None

    def moe_layer(x, p):
        x = x + _mla(p["attn"], rms_norm(x, p["ln1"], dims.norm_eps), dims, bits, q_chunk)
        return x + moe(p["moe"], rms_norm(x, p["ln2"], dims.norm_eps), dims, bits), None

    x, _ = jax.lax.scan(dense_layer, x, params["dense"])
    x, _ = jax.lax.scan(moe_layer, x, params["blocks"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)


def logits(params, h, *, bits: Optional[int] = None):
    return _mm(h, params["lm_head"], bits)


# ---------------------------------------------------------------------------
# The work
# ---------------------------------------------------------------------------


def step_gemms(d: Dims, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every Pallas GEMM of one decode step over ``rows`` rows:
    the attention projections, the dense layers' MLP, the shared experts and
    the LM head (the router, the absorbed products and the grouped expert
    GEMMs are other kernels)."""

    h, D = d.n_heads, d.d_model
    attn = [(rows, D, h * (d.qk_nope + d.qk_rope)), (rows, D, d.latent), (rows, h * d.v_dim, D)]
    dense = [(rows, D, d.d_ff), (rows, D, d.d_ff), (rows, d.d_ff, D)]
    shared = [(rows, D, d.d_shared), (rows, D, d.d_shared), (rows, d.d_shared, D)]
    return ((attn + dense) * d.n_dense + (attn + shared) * d.n_moe
            + [(rows, D, d.vocab)])


def latent_bytes(d: Dims, contexts) -> float:
    """Bytes the latent kernel must read in one decode step: every live
    context's latent rows in every layer, the absorbed queries in and the
    latent sums (float32) out."""

    rows = len(contexts)
    cache = float(sum(contexts)) * d.latent * BF16
    qo = rows * d.n_heads * (d.latent * BF16 + d.kv_rank * 4)
    return d.n_layers * (cache + qo)


def attn_roofline_s(d: Dims, contexts, peak_flops: float, peak_bw: float) -> float:
    """Least time for one decode step's latent attention over all layers:
    each row's heads score its live context's latent rows (``latent``
    wide) and sum their ``kv_rank`` part."""

    flops = 2.0 * float(sum(contexts)) * d.n_heads * (d.latent + d.kv_rank) * d.n_layers
    return max(flops / peak_flops, latent_bytes(d, contexts) / peak_bw)


def expert_token_flops(d: Dims) -> float:
    """FLOPs of one token through one routed expert."""

    return 2.0 * 3 * d.d_model * d.d_expert


def routed_per_token(d: Dims) -> float:
    """Held experts a token reaches over all MoE layers under uniform
    routing: ``top_k · held / router`` per layer."""

    return d.n_moe * d.top_k * d.n_experts / d.n_router


def dense_token_flops(d: Dims, context: int) -> float:
    """Model FLOPs of one token at position ``context - 1`` without its
    routed experts: two per parameter it uses (the LM head included, the
    held experts' weights excluded) and the attention over its
    ``context`` keys (full form: 192-wide scores, 128-wide values)."""

    experts = d.n_moe * d.n_experts * 3 * d.d_model * d.d_expert
    attn = 2.0 * d.n_layers * context * d.n_heads * (d.qk_nope + d.qk_rope + d.v_dim)
    return 2.0 * (param_count(d, embed=False) - experts) + attn


def token_flops(d: Dims, context: int) -> float:
    """Model FLOPs of one token: :func:`dense_token_flops` and its routed
    experts at the uniform routed share."""

    return dense_token_flops(d, context) + routed_per_token(d) * expert_token_flops(d)


def prompt_flops(d: Dims, p: int) -> float:
    """Model FLOPs of prefilling a ``p``-token prompt."""

    return sum(token_flops(d, k) for k in range(1, p + 1))
