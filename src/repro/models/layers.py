"""Shared neural-net layers (pure-functional, explicit param pytrees).

Conventions:
  * params are stored fp32 (master weights); compute casts to bf16 at the
    point of use (mixed-precision policy),
  * normalizations and softmax run in fp32,
  * every dense projection routes through :func:`repro.kernels.ops.gemm`
    so the paper's control-tree block configuration governs the hot loops,
  * attention is *chunked over queries* (scores never materialize more than
    ``q_chunk × S_k``), which together with layer remat bounds activation
    memory — see EXPERIMENTS.md §Perf for the measured effect.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, scale: Optional[float] = None, dtype=PARAM_DTYPE):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype=PARAM_DTYPE):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding (half-rotation / LLaMA convention)
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D); positions: (..., S) int32."""

    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def chunked_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    scale: Optional[float] = None,
):
    """GQA-native attention, chunked over queries (scores ≤ q_chunk × S_k).

    q: (B, Sq, Hq, D); k: (B, Sk, Hkv, D); v: (B, Sk, Hkv, Dv) with
    Hq % Hkv == 0.  Grouped
    einsums keep the KV-head dim explicit — repeating KV heads materializes
    a G×-larger tensor and (sharded) triggers involuntary SPMD
    rematerialization, measured at +115 GiB/device on mixtral decode
    (EXPERIMENTS.md §Perf).  The q-offset convention assumes queries are
    the *suffix* of the key sequence.
    """

    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    q_chunk = min(q_chunk, sq)
    pad = (-sq) % q_chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    n_chunks = qp.shape[1] // q_chunk

    kT = k.transpose(0, 2, 3, 1).astype(COMPUTE_DTYPE)  # (B,Hkv,D,Sk)
    vT = v.transpose(0, 2, 1, 3).astype(COMPUTE_DTYPE)  # (B,Hkv,Sk,D)

    # Sliding-window block skipping (paper-style iteration-space
    # restriction): a q-chunk can only attend to the trailing
    # ``q_chunk + window`` keys, so slice K/V instead of masking the full
    # row — an Sk/(q_chunk+window) FLOP and score-traffic reduction
    # (8.6× on mixtral prefill_32k; EXPERIMENTS.md §Perf B).
    span = sk
    if window is not None and causal:
        span = min(sk, q_chunk + window)

    def one_chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(qp, i * q_chunk, q_chunk, axis=1)
        qc = qc.reshape(b, q_chunk, hkv, g, d).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,qc,D)
        qc = qc.astype(COMPUTE_DTYPE)
        q_idx = (sk - sq) + i * q_chunk + jnp.arange(q_chunk)
        if span < sk:
            start = jnp.clip((sk - sq) + i * q_chunk + q_chunk - span, 0, sk - span)
            kc = jax.lax.dynamic_slice_in_dim(kT, start, span, axis=3)
            vc = jax.lax.dynamic_slice_in_dim(vT, start, span, axis=2)
            k_idx = start + jnp.arange(span)
        else:
            kc, vc = kT, vT
            k_idx = jnp.arange(sk)
        s = jnp.einsum("bhgqd,bhds->bhgqs", qc, kc, preferred_element_type=jnp.float32)
        s = s * scale
        mask = jnp.ones((q_chunk, span), bool)
        if causal:
            mask &= q_idx[:, None] >= k_idx[None, :]
        if window is not None:
            mask &= (q_idx[:, None] - k_idx[None, :]) < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(COMPUTE_DTYPE)
        o = jnp.einsum("bhgqs,bhsd->bhgqd", p, vc, preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))  # (n,B,Hkv,G,qc,Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, n_chunks * q_chunk, hq, v.shape[-1])
    return out[:, :sq]


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None      # sliding-window attention (Mixtral)
    causal: bool = True
    use_rope: bool = True


def init_attention(key, cfg: AttnConfig):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.n_heads * cfg.d_head)),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
        "wo": dense_init(ks[3], (cfg.n_heads * cfg.d_head, cfg.d_model)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * cfg.d_head,), PARAM_DTYPE)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), PARAM_DTYPE)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * cfg.d_head,), PARAM_DTYPE)
    return p


def _qkv(p, x, cfg: AttnConfig, positions):
    b, s, _ = x.shape
    c = lambda w: w.astype(COMPUTE_DTYPE)
    q = ops.linear(x, c(p["wq"]), p.get("bq"))
    k = ops.linear(x, c(p["wk"]), p.get("bk"))
    v = ops.linear(x, c(p["wv"]), p.get("bv"))
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attention(p, x, cfg: AttnConfig, *, positions=None):
    """Full-sequence attention (training / prefill). x: (B,S,D)."""

    from repro.distributed.sharding import constrain_qkv_context_parallel

    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    q, k, v = constrain_qkv_context_parallel(q, k, v, cfg.n_heads)
    o = chunked_attention(q, k, v, causal=cfg.causal, window=cfg.window)
    o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE)), (k, v)


def decode_attention(p, x, cfg: AttnConfig, cache_k, cache_v, pos, *, live=None):
    """Single-token decode against a (ring or linear) KV cache.

    x: (B, 1, D); cache_k/v: (B, S_cache, Hkv, Dh); pos: int32 — the
    absolute position of the new token.  Either a scalar (same position
    across the batch, static batching) or a ``(B,)`` vector of *per-row*
    positions (the serving engine's slot table, where every slot ages
    independently).  With a sliding window the cache is a ring buffer of
    size ``window`` and ``pos`` indexes modulo the window.

    The two forms are value-identical when the vector is constant: the
    per-row cache write places the same bits at the same slot, and the
    validity mask broadcasts to the same elements — the engine's
    vector-position step is bit-identical to the scalar-position path
    (asserted in tests/test_serving.py).  A vector position past the cache
    length simply writes nothing (the one-hot hits no slot), so retired
    slots can keep aging harmlessly until they are re-admitted.  (The
    scalar path's ``dynamic_update_slice`` *clamps* instead of dropping —
    scalar callers never run phantom lanes, so the distinction is moot
    there.)

    ``live`` (optional, ``(B,)`` bool) marks rows whose attention output
    is real; dead rows (retired-but-not-refreshed phantom lanes) have
    their attention output zeroed before the output projection so their
    row content is engine-defined (identical between the dense and paged
    engines) rather than whatever their stale cache produces.  Masked
    lanes never influence *other* rows either way — every op here is
    row-local — so ``live=None`` keeps the historical output bit-for-bit.
    """

    b = x.shape[0]
    s_cache = cache_k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim > 0  # (B,) per-row absolute positions
    positions = pos[:, None] if per_slot else jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)

    slot = pos % s_cache if cfg.window is not None else pos
    if per_slot:
        # Per-row scatter: O(B·Hkv·Dh) written, aliasable in place under
        # donation (a broadcast-select would rewrite the whole cache every
        # token).  mode="drop" skips rows whose position is past the cache
        # length — the retired phantom lanes write nothing.
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, slot].set(
            k[:, 0].astype(cache_k.dtype), mode="drop"
        )
        cache_v = cache_v.at[rows, slot].set(
            v[:, 0].astype(cache_v.dtype), mode="drop"
        )
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=1)

    # GQA-native grouped einsum over the raw cache — no KV repetition.
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, 1, cfg.n_kv_heads, g, cfg.d_head).astype(COMPUTE_DTYPE)
    s = jnp.einsum(
        "bqhgd,bshd->bhgqs", qg, cache_k.astype(COMPUTE_DTYPE),
        preferred_element_type=jnp.float32,
    ) / np.sqrt(cfg.d_head)
    k_idx = jnp.arange(s_cache)
    if per_slot:
        if cfg.window is not None:
            # ring buffer: all slots valid once wrapped
            valid = (k_idx[None, :] <= slot[:, None]) | (pos[:, None] >= s_cache)
        else:
            # Clamp before comparing: a phantom lane aged past the cache
            # (pos >= s_cache) must saturate to "whole cache valid", never
            # overflow-wrap the comparison.  (Equivalent for every
            # in-range pos — k_idx stays < s_cache — but explicit.)
            valid = k_idx[None, :] <= jnp.minimum(pos[:, None], s_cache - 1)
        s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    else:
        if cfg.window is not None:
            valid = (k_idx <= slot) | (pos >= s_cache)
        else:
            valid = k_idx <= pos
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    pattn = jax.nn.softmax(s, axis=-1).astype(COMPUTE_DTYPE)
    o = jnp.einsum(
        "bhgqs,bshd->bqhgd", pattn, cache_v.astype(COMPUTE_DTYPE),
        preferred_element_type=jnp.float32,
    )
    if live is not None:
        o = jnp.where(live[:, None, None, None, None], o, 0.0)
    o = o.astype(x.dtype).reshape(b, 1, cfg.n_heads * cfg.d_head)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE)), (cache_k, cache_v)


def decode_attention_paged(
    p, x, cfg: AttnConfig, pages_k, pages_v, layer, page_table, pos, *,
    live=None, backend: str = "auto",
):
    """Single-token decode of one layer against the *paged* KV pool.

    x: (B, 1, D); pages_k/v: (L, P, Hkv, page_size, Dh) — the stacked,
    head-major page arena of every layer, updated and read in place;
    layer: int32 scalar — the arena layer this block owns; page_table:
    (B, W) int32 — each row's pages, where ``W · page_size`` is the
    logical cache length ``S_cache``; pos: (B,) int32 per-row absolute
    positions (always per-slot — the paged path only exists for the
    serving engine).

    Write side mirrors the dense per-slot scatter: the new K/V row lands
    at ``(layer, page, head, off)`` for logical slot ``pos % S_cache`` (ring)
    / ``pos`` (linear) — one scatter of ``B · Hkv · Dh`` values into the
    stacked arena, in place under donation; rows whose table entry is
    unallocated (SENTINEL) or whose linear position is past the cache
    write nothing (``mode="drop"`` — same semantics as the dense
    phantom-lane drop).  Rows sharing a page (the engine's shared phantom
    lane) write *identical* values by construction, so scatter order
    cannot matter.

    Read side routes the stacked arena and the layer through
    ``execution.dispatch_paged_attention``: the XLA gather route
    reproduces the dense arithmetic bit-for-bit; the Pallas route streams
    pages with an online softmax (tolerance-tested).  ``live`` as in
    :func:`decode_attention`.
    """

    from repro.core.execution import dispatch_paged_attention

    b = x.shape[0]
    n_pages, page_size = pages_k.shape[1], pages_k.shape[3]
    w = page_table.shape[1]
    s_cache = w * page_size
    pos = jnp.asarray(pos, jnp.int32)
    q, k, v = _qkv(p, x, cfg, pos[:, None])

    slot = pos % s_cache if cfg.window is not None else pos
    rows = jnp.arange(b)
    page = page_table[rows, jnp.clip(slot // page_size, 0, w - 1)]
    # Linear positions past the cache write nothing, as on the dense path
    # (any out-of-range page — this marker or a SENTINEL table entry —
    # makes the scatter drop the row).
    page = jnp.where(slot < s_cache, page, jnp.int32(n_pages))
    off = slot % page_size
    # One scatter window of Dh per (row, head): with the window Dh alone
    # XLA keeps the arena in its default head-major layout, the kernel's;
    # a (Hkv, Dh) window makes it lay the arena out page-major and copy
    # it to and from the kernel's layout every layer.
    heads = jnp.arange(cfg.n_kv_heads)[None, :]
    at = (layer, page[:, None], heads, off[:, None])  # (B, Hkv) rows of Dh
    pages_k = pages_k.at[at].set(k[:, 0].astype(pages_k.dtype), mode="drop")
    pages_v = pages_v.at[at].set(v[:, 0].astype(pages_v.dtype), mode="drop")

    o = dispatch_paged_attention(
        q[:, 0], pages_k, pages_v, page_table, pos, layer, backend=backend
    )  # (B, Hq, Dh)
    if live is not None:
        o = jnp.where(live[:, None, None], o, jnp.zeros((), o.dtype))
    o = o.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE)), (pages_k, pages_v)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling as DeepSeek-V2's ``rope_scaling`` states it."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: Optional[Yarn]) -> np.ndarray:
    """Rotary frequencies of ``dim / 2`` pairs; with ``yarn``, the pairs
    below the correction range keep ``f``, those above it take ``f /
    factor``, and a linear ramp blends the range between."""

    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return f.astype(np.float32)

    def corr(rot):
        return dim * np.log(yarn.original_max_position / (rot * 2 * np.pi)) / (2 * np.log(theta))

    low = max(int(np.floor(corr(yarn.beta_fast))), 0)
    high = min(int(np.ceil(corr(yarn.beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / yarn.factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rope_pairs(x, positions, inv_freq):
    """Rotate the pairs ``(2i, 2i+1)`` of ``x`` (..., S, H, D) by
    ``positions`` (..., S) times ``inv_freq`` (D/2,)."""

    d = x.shape[-1]
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Latent attention without query compression (``q_lora_rank`` null).

    The cache holds one latent row per token: the normed ``c_kv``
    (``kv_lora_rank``) then the roped shared key ``k_pe``
    (``qk_rope_head_dim``)."""

    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    yarn: Optional[Yarn] = None

    @property
    def latent(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn is not None and self.yarn.mscale_all_dim:
            s *= yarn_mscale(self.yarn.factor, self.yarn.mscale_all_dim) ** 2
        return s

    def inv_freq(self) -> np.ndarray:
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta, self.yarn)


def init_mla(key, cfg: MLAConfig):
    ks = jax.random.split(key, 4)
    h = cfg.n_heads
    return {
        "wq": dense_init(ks[0], (cfg.d_model, h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))),
        "wkv_a": dense_init(ks[1], (cfg.d_model, cfg.latent)),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), PARAM_DTYPE),
        "wkv_b": dense_init(ks[2], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
        "wo": dense_init(ks[3], (h * cfg.v_head_dim, cfg.d_model)),
    }


def _mla_q_latent(p, x, cfg: MLAConfig, positions):
    """``q`` split into (nope, roped pe) heads and the token's latent row
    ``[RMSNorm(c_kv) | RoPE(k_pe)]``.  x: (B, S, D)."""

    b, s, _ = x.shape
    c = lambda w: w.astype(COMPUTE_DTYPE)
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    inv = cfg.inv_freq()
    q = ops.linear(x, c(p["wq"])).reshape(b, s, cfg.n_heads, nope + rope_d)
    q_pe = rope_pairs(q[..., nope:], positions, inv)
    kv = ops.linear(x, c(p["wkv_a"]))
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(kv[..., None, cfg.kv_lora_rank:], positions, inv)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)


def apply_mla(p, x, cfg: MLAConfig, *, positions=None):
    """Full-form latent attention over the sequence (training / prefill):
    keys and values expanded per head from the latent.  x: (B, S, D)."""

    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    h, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, lat = _mla_q_latent(p, x, cfg, positions)
    kv = ops.linear(lat[..., :r], p["wkv_b"].astype(COMPUTE_DTYPE))
    kv = kv.reshape(b, s, h, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(lat[..., None, r:], (b, s, h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe.astype(kv.dtype)], axis=-1)
    with jax.named_scope("mla.attn"):
        o = chunked_attention(q, k, kv[..., nope:], causal=True, scale=cfg.scale)
    o = o.reshape(b, s, h * cfg.v_head_dim)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE)), lat


def _mla_absorb(p, cfg: MLAConfig):
    """``W_UK`` and ``W_UV`` of ``wkv_b``, each (kv_lora_rank, H, 128)."""

    w = p["wkv_b"].astype(COMPUTE_DTYPE).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _mla_decode_q(p, x, cfg: MLAConfig, pos):
    """Absorbed query ``q_nope · W_UK`` (B, H, rank) and roped ``q_pe``
    (B, H, rope), and the new token's latent row (B, rank + rope), at
    per-row positions ``pos`` (B,)."""

    q_nope, q_pe, lat = _mla_q_latent(p, x, cfg, pos[:, None])
    w_uk, _ = _mla_absorb(p, cfg)
    q_c = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk, preferred_element_type=jnp.float32)
    return q_c.astype(COMPUTE_DTYPE), q_pe[:, 0].astype(COMPUTE_DTYPE), lat[:, 0]


def _mla_decode_out(p, o_c, cfg: MLAConfig, live):
    """``W_UV`` after the weighted latent sum, then ``o_proj``.
    o_c: (B, H, kv_lora_rank)."""

    b = o_c.shape[0]
    if live is not None:
        o_c = jnp.where(live[:, None, None], o_c, jnp.zeros((), o_c.dtype))
    _, w_uv = _mla_absorb(p, cfg)
    o = jnp.einsum("bhc,chd->bhd", o_c.astype(COMPUTE_DTYPE), w_uv,
                   preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
    o = o.reshape(b, 1, cfg.n_heads * cfg.v_head_dim)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE))


def mla_latent_attention(q_c, q_pe, c, pe, valid, scale: float):
    """Absorbed attention over latent rows: ``q_c`` (B, H, rank) against
    ``c`` (B, S, rank) plus ``q_pe`` (B, H, rope) against ``pe`` (B, S,
    rope); ``valid`` (B, S).  Returns the weighted sum of ``c``, (B, H,
    rank), in float32."""

    ct = q_c.dtype
    s = jnp.einsum("bhc,bsc->bhs", q_c, c.astype(ct), preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhr,bsr->bhs", q_pe, pe.astype(ct), preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, :], s * scale, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(ct)
    return jnp.einsum("bhs,bsc->bhc", pr, c.astype(ct), preferred_element_type=jnp.float32)


def decode_mla(p, x, cfg: MLAConfig, cache_c, cache_pe, pos, *, live=None):
    """One decode token against dense latent lanes, absorbed form.

    x: (B, 1, D); cache_c: (B, S_cache, rank); cache_pe: (B, S_cache,
    rope); pos: (B,) per-row positions (a row past the lane writes
    nothing)."""

    b, s_cache = x.shape[0], cache_c.shape[1]
    r = cfg.kv_lora_rank
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with jax.named_scope("mla.attn"):
        q_c, q_pe, row = _mla_decode_q(p, x, cfg, pos)
        rows = jnp.arange(b)
        cache_c = cache_c.at[rows, pos].set(row[:, :r].astype(cache_c.dtype), mode="drop")
        cache_pe = cache_pe.at[rows, pos].set(row[:, r:].astype(cache_pe.dtype), mode="drop")
        valid = jnp.arange(s_cache)[None, :] <= jnp.minimum(pos[:, None], s_cache - 1)
        o_c = mla_latent_attention(q_c, q_pe, cache_c, cache_pe, valid, cfg.scale)
    return _mla_decode_out(p, o_c, cfg, live), (cache_c, cache_pe)


def decode_mla_paged(p, x, cfg: MLAConfig, pages_c, pages_pe, layer, page_table, pos, *,
                     live=None, backend: str = "auto"):
    """One decode token of one layer against the stacked latent arenas,
    updated and read in place: ``pages_c`` (L, P, page_size, rank) holds
    the normed ``c_kv`` rows, ``pages_pe`` (L, P, rope, page_size) the
    roped ``k_pe`` with the page's positions minor (a minor dim of 64
    would be laid out transposed by the compiler, and copied for the
    kernel every layer).  The row is written at ``(layer, page, off)``
    (dropped past the lane or on an unallocated page), then the paged
    latent kernel attends each row's pages with the absorbed query."""

    from repro.core.execution import dispatch_paged_mla

    b = x.shape[0]
    n_pages, page_size = pages_c.shape[1], pages_c.shape[2]
    w = page_table.shape[1]
    r = cfg.kv_lora_rank
    pos = jnp.asarray(pos, jnp.int32)
    with jax.named_scope("mla.attn"):
        q_c, q_pe, row = _mla_decode_q(p, x, cfg, pos)
        page = page_table[jnp.arange(b), jnp.clip(pos // page_size, 0, w - 1)]
        page = jnp.where(pos < w * page_size, page, jnp.int32(n_pages))
        off = pos % page_size
        pages_c = pages_c.at[layer, page, off].set(row[:, :r].astype(pages_c.dtype), mode="drop")
        pages_pe = pages_pe.at[layer, page, :, off].set(row[:, r:].astype(pages_pe.dtype),
                                                        mode="drop")
        o_c = dispatch_paged_mla(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer,
                                 scale=cfg.scale, backend=backend)
    return _mla_decode_out(p, o_c, cfg, live), (pages_c, pages_pe)


def cross_attention(p, x, enc_k, enc_v, cfg: AttnConfig):
    """Decoder→encoder attention (Whisper). enc_k/v precomputed (B,Se,Hkv,Dh)."""

    b, s, _ = x.shape
    c = lambda w: w.astype(COMPUTE_DTYPE)
    q = ops.linear(x, c(p["wq"]), p.get("bq")).reshape(b, s, cfg.n_heads, cfg.d_head)
    o = chunked_attention(
        q, enc_k.astype(COMPUTE_DTYPE), enc_v.astype(COMPUTE_DTYPE), causal=False
    )
    o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
    return ops.linear(o, p["wo"].astype(COMPUTE_DTYPE))


def init_cross_kv(key, cfg: AttnConfig):
    ks = jax.random.split(key, 2)
    return {
        "wk": dense_init(ks[0], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
        "wv": dense_init(ks[1], (cfg.d_model, cfg.n_kv_heads * cfg.d_head)),
    }


def encode_cross_kv(p, enc_out, cfg: AttnConfig):
    b, s, _ = enc_out.shape
    c = lambda w: w.astype(COMPUTE_DTYPE)
    k = ops.linear(enc_out, c(p["wk"])).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = ops.linear(enc_out, c(p["wv"])).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_glu(key, d_model: int, d_ff: int):
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], (d_model, d_ff)),
        "w3": dense_init(ks[1], (d_model, d_ff)),
        "w2": dense_init(ks[2], (d_ff, d_model)),
    }


def apply_glu(p, x):
    c = lambda w: w.astype(COMPUTE_DTYPE)
    h = jax.nn.silu(ops.gemm(x, c(p["w1"])).astype(jnp.float32)).astype(COMPUTE_DTYPE)
    h = h * ops.gemm(x, c(p["w3"]))
    return ops.gemm(h, c(p["w2"]))


def init_mlp(key, d_model: int, d_ff: int):
    ks = jax.random.split(key, 2)
    return {
        "w1": dense_init(ks[0], (d_model, d_ff)),
        "b1": jnp.zeros((d_ff,), PARAM_DTYPE),
        "w2": dense_init(ks[1], (d_ff, d_model)),
        "b2": jnp.zeros((d_model,), PARAM_DTYPE),
    }


def apply_mlp(p, x):
    c = lambda w: w.astype(COMPUTE_DTYPE)
    h = ops.linear(x, c(p["w1"]), p["b1"])
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    return ops.linear(h, c(p["w2"]), p["b2"])


def sinusoidal_positions(s: int, d: int):
    pos = np.arange(s)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    ang = pos / np.power(10000.0, dim / d)
    out = np.zeros((s, d), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


__all__ = [
    "COMPUTE_DTYPE",
    "PARAM_DTYPE",
    "AttnConfig",
    "dense_init",
    "embed_init",
    "rms_norm",
    "layer_norm",
    "rope",
    "repeat_kv",
    "chunked_attention",
    "init_attention",
    "apply_attention",
    "decode_attention",
    "decode_attention_paged",
    "Yarn",
    "MLAConfig",
    "yarn_inv_freq",
    "rope_pairs",
    "init_mla",
    "apply_mla",
    "decode_mla",
    "decode_mla_paged",
    "mla_latent_attention",
    "cross_attention",
    "init_cross_kv",
    "encode_cross_kv",
    "init_glu",
    "apply_glu",
    "init_mlp",
    "apply_mlp",
    "sinusoidal_positions",
]
