"""The plain reference: a dense decoder's forward pass in float32.

Written from the published equations of the LLaMA-style dense decoder that
both configurations are (InternLM2, arXiv:2403.17297; DeepSeek LLM,
arXiv:2401.02954), with nothing taken from the program under test:

    h   = x + Wo · attn(RoPE(Wq · n1), RoPE(Wk · n1), Wv · n1),  n1 = RMSNorm(x)
    out = h + W2 · (silu(W1 · n2) * (W3 · n2)),                   n2 = RMSNorm(h)

with causal grouped-query attention (``n_heads / n_kv_heads`` query heads
per key head), rotary embeddings on pairs ``(i, i + d/2)`` at the
configuration's theta, and a final RMSNorm before the untied LM head.
Every product runs in float32 at ``precision="highest"``.

``mantissa_bits`` is the control: it rounds every weight and every
matmul input to that many explicit mantissa bits (3 is float8 e4m3's
mantissa) by arithmetic, so that no compiler pass can fold the rounding
away, and keeps the exponent range unlimited.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def round_mantissa(x, bits: Optional[int]):
    """``x`` rounded to ``bits`` explicit mantissa bits (float32 out)."""

    x = x.astype(jnp.float32)
    if bits is None:
        return x
    m, e = jnp.frexp(x)                       # x = m * 2**e, |m| in [0.5, 1)
    scale = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, D) at positions 0..S-1; rotates pairs (i, i + D/2)."""

    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _mm(x, w, bits):
    return jnp.matmul(round_mantissa(x, bits), round_mantissa(w, bits), precision=HIGHEST)


def attention(q, k, v, *, q_chunk: int):
    """Causal GQA: q (S, Hq, D), k/v (S, Hkv, D); queries in chunks."""

    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(s // q_chunk, q_chunk, hkv, g, d)

    def chunk(args):
        i, qc = args
        sc = jnp.einsum("qhgd,khd->hgqk", qc, k, precision=HIGHEST) / jnp.sqrt(float(d))
        qi = i * q_chunk + jnp.arange(q_chunk)
        mask = qi[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    out = jax.lax.map(chunk, (jnp.arange(s // q_chunk), qg))
    return out.reshape(s, hq * d)


def hidden(params, dims, tokens, *, bits: Optional[int] = None, q_chunk: int = 256):
    """Final-normed hidden states ``(S, d_model)`` of ``tokens`` (S,)."""

    s = tokens.shape[0]
    q_chunk = min(q_chunk, s)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        n1 = rms_norm(x, p["ln1"], dims.norm_eps)
        q = _mm(n1, a["wq"], bits).reshape(s, dims.n_heads, dims.d_head)
        k = _mm(n1, a["wk"], bits).reshape(s, dims.n_kv_heads, dims.d_head)
        v = _mm(n1, a["wv"], bits).reshape(s, dims.n_kv_heads, dims.d_head)
        o = attention(rope(q, dims.rope_theta), rope(k, dims.rope_theta), v, q_chunk=q_chunk)
        x = x + _mm(o, a["wo"], bits)
        m = p["mlp"]
        n2 = rms_norm(x, p["ln2"], dims.norm_eps)
        x = x + _mm(jax.nn.silu(_mm(n2, m["w1"], bits)) * _mm(n2, m["w3"], bits), m["w2"], bits)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)


def logits(params, h, *, bits: Optional[int] = None):
    return _mm(h, params["lm_head"], bits)
