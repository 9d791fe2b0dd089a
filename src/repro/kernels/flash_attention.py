"""Blocked (flash) attention — the paper's tiling insight applied beyond GEMM.

Not a paper contribution, but the same architecture-aware principle: tile
the (S_q, S_k) iteration space into VMEM-resident blocks so each staged
block amortizes maximal compute, with an online-softmax accumulator taking
the role of the fp32 GEMM accumulator.  Used as the TPU hot path for the
transformer architectures; the pure-jnp chunked implementation in
``models/layers.py`` is the portable/SPMD path.

Grid: (batch*heads, S_q/bq, S_k/bk) with the K dimension sequential
("arbitrary") carrying (m, l, acc) scratch state; causal and sliding-window
masks are applied per block, and fully-masked blocks produce zero updates
(the index map still visits them — block skipping is a TODO noted in
EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, window, bq, bk, sk, q_offset):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]  # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    q_idx = q_offset + qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_idx < sk  # padded K positions are invalid
    if causal:
        mask &= q_idx >= k_idx
    if window is not None:
        mask &= (q_idx - k_idx) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention over (B, S, H, D) tensors; GQA handled by the caller.

    ``q``/``k``/``v`` must share H here — the model layer repeats KV heads
    before calling (or maps over groups).  S_q and S_k are padded to block
    multiples; padded K positions are masked off via the window/causal
    logic plus an explicit validity mask on the final slice.
    """

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    psq, psk = sq + pad_q, sk + pad_k

    # (B, S, H, D) -> (B*H, S, D)
    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qb_, kb_, vb_ = bh(qp), bh(kp), bh(vp)

    grid = (b * h, psq // block_q, psk // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        bq=block_q,
        bk=block_k,
        sk=sk,
        q_offset=sk - sq,  # causal alignment when the query is a suffix
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, i, j: (bh_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, i, j: (bh_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, psq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
    )(qb_, kb_, vb_)

    out = out.reshape(b, h, psq, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


__all__ = ["flash_attention"]
