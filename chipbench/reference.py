"""What every family's plain reference is built from, in float32.

A family's reference (``families/<family>.py``: ``hidden`` and ``logits``)
is written from its published equations with these parts, and takes
nothing from the program under test.  Every product runs in float32 at
``precision="highest"``.

``round_mantissa`` is the control: with ``bits`` it rounds every weight and
every matmul input to that many explicit mantissa bits (3 is float8
e4m3's mantissa) by arithmetic, so that no compiler pass can fold the
rounding away, and keeps the exponent range unlimited.  A family honours
``bits`` by making its products through ``_mm``.
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
