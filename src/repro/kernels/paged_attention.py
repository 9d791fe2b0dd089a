"""Ragged paged-attention decode kernels (single-token query, paged KV).

The serving engine's paged KV pool stores each slot's cache as a list of
fixed-size pages in a shared arena (``runtime/paging.py``); decode
attention must gather K/V *through the page table*.  Two routes, both
registered in ``execution.BACKENDS`` (op family ``"paged_attn"``):

  * :func:`paged_attention_xla` — gather + masked softmax in exactly the
    dense decode path's primitive sequence (same einsum contractions,
    same fp32 softmax, same ``-1e30`` masking), so on identical cache
    *values* the result is **bit-identical** to
    ``layers.decode_attention`` over a dense lane.  The CPU/CI route and
    the engine's exactness reference.
  * :func:`paged_attention_pallas` — a Pallas kernel streaming one page
    per grid step with an online-softmax accumulator (the
    ``flash_attention.py`` pattern), the page table scalar-prefetched so
    each step's DMA source address is a *data-dependent* page.  Online
    softmax reorders the reduction, so this route is tolerance-equal,
    not bit-equal (per-dtype tolerances in tests).  ``interpret=True``
    is its CPU twin for the parity harness.

Shapes (one decode token per row):

  q           (B, Hq, Dh)        the new token's query heads
  pages_k/v   (P, ps, Hkv, Dh)   the page arena (one layer's)
  page_table  (B, W)  int32      per-row page ids; ``W * ps == s_cache``
  pos         (B,)    int32      per-row absolute positions

Masking: a row attends its logical cache prefix ``[0, min(pos+1,
s_cache))`` — equivalent to the dense path's linear mask *and* its ring
(sliding-window) mask, since a wrapped ring attends its full buffer.
Unallocated table entries are far-out-of-range sentinels; gathers clip
them to an arbitrary page whose positions the mask always excludes (the
allocator guarantees every in-prefix page is allocated).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _check_shapes(q, pages_k, pages_v, page_table, pos):
    b, hq, d = q.shape
    p, ps, hkv, d2 = pages_k.shape
    if pages_v.shape != pages_k.shape:
        raise ValueError(f"k/v arenas differ: {pages_k.shape} vs {pages_v.shape}")
    if d2 != d or hq % hkv:
        raise ValueError(f"q {q.shape} incompatible with pages {pages_k.shape}")
    if page_table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {page_table.shape} / pos {pos.shape} do not cover batch {b}"
        )
    return b, hq, d, p, ps, hkv, page_table.shape[1]


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Materialize per-row dense views: (P, ps, H, D) → (B, W·ps, H, D).

    Sentinel entries clip to the last page; the caller's validity mask
    must exclude every position they back (the pool's invariant).
    """

    p, ps, h, d = pages.shape
    b, w = page_table.shape
    idx = jnp.clip(page_table, 0, p - 1)
    view = pages[idx]  # (B, W, ps, H, D)
    return view.reshape(b, w * ps, h, d)


def _valid_mask(pos: jnp.ndarray, s_cache: int) -> jnp.ndarray:
    """(B, s_cache) bool — the logical prefix each row may attend.

    ``k_idx < min(pos+1, s_cache)``: equals the dense linear mask
    (``k_idx <= pos``, with every index valid once ``pos >= s_cache``)
    and the dense ring mask (``k_idx <= pos % s_cache`` until wrapped,
    everything after) on their shared domain ``k_idx ∈ [0, s_cache)``.
    """

    k_idx = jnp.arange(s_cache)
    limit = jnp.minimum(pos[:, None] + 1, s_cache)
    return k_idx[None, :] < limit


def paged_attention_xla(q, pages_k, pages_v, page_table, pos):
    """Gather fallback — the dense decode arithmetic over a paged gather.

    Primitive-for-primitive the same sequence as
    ``layers.decode_attention``'s read side (grouped GQA einsums, fp32
    scores scaled by ``1/sqrt(Dh)``, ``-1e30`` mask, fp32 softmax), so
    given bitwise-equal cache values it is bitwise-equal to the dense
    path: masked lanes contribute exactly ``0.0`` (``exp`` underflow),
    making the output independent of garbage behind sentinel pages.
    """

    b, hq, d, _, ps, hkv, w = _check_shapes(q, pages_k, pages_v, page_table, pos)
    s_cache = w * ps
    g = hq // hkv
    ct = pages_k.dtype  # the cache/compute dtype (bf16 policy)
    view_k = paged_gather(pages_k, page_table)  # (B, s_cache, Hkv, Dh)
    view_v = paged_gather(pages_v, page_table)
    qg = q.reshape(b, 1, hkv, g, d).astype(ct)
    s = jnp.einsum(
        "bqhgd,bshd->bhgqs", qg, view_k.astype(ct),
        preferred_element_type=jnp.float32,
    ) / np.sqrt(d)
    valid = _valid_mask(jnp.asarray(pos, jnp.int32), s_cache)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p_attn = jax.nn.softmax(s, axis=-1).astype(ct)
    o = jnp.einsum(
        "bhgqs,bshd->bqhgd", p_attn, view_v.astype(ct),
        preferred_element_type=jnp.float32,
    )
    return o.astype(q.dtype).reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Pallas kernel: one page per grid step, online softmax
# ---------------------------------------------------------------------------


def _paged_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, ps, s_cache):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (G, Dh)
    k = k_ref[0, 0]  # (ps, Dh) — this step's page
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, ps)
    idx = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    limit = jnp.minimum(pos_ref[b] + 1, s_cache)
    s = jnp.where(idx < limit, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def paged_attention_pallas(q, pages_k, pages_v, page_table, pos, *,
                           interpret: bool = False):
    """Pallas route: grid ``(B, Hkv, W)``, the page dim sequential.

    The page table and positions ride as scalar-prefetch operands
    (``PrefetchScalarGridSpec``), so each grid step's K/V *block index* —
    which arena page to DMA — is computed from the table before the body
    runs: ragged, data-dependent paging without host round-trips.
    Sentinel entries clip to the last page; the in-kernel prefix mask
    zeroes their contribution.
    """

    b, hq, d, p_total, ps, hkv, w = _check_shapes(
        q, pages_k, pages_v, page_table, pos
    )
    g = hq // hkv
    s_cache = w * ps
    q4 = q.reshape(b, hkv, g, d)
    # Page-major → head-major pages so one (page, head) pair is one block.
    kt = pages_k.transpose(0, 2, 1, 3)  # (P, Hkv, ps, Dh)
    vt = pages_v.transpose(0, 2, 1, 3)
    table = jnp.asarray(page_table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)

    def page_index(bb, h, j, table_ref, pos_ref):
        return (jnp.clip(table_ref[bb, j], 0, p_total - 1), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, w),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, t, pp: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, d), page_index),
            pl.BlockSpec((1, 1, ps, d), page_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bb, h, j, t, pp: (bb, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / np.sqrt(d), ps=ps, s_cache=s_cache
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(table, pos, q4, kt, vt)
    return out.reshape(b, hq, d)


def paged_attention_pallas_interpret(q, pages_k, pages_v, page_table, pos):
    return paged_attention_pallas(q, pages_k, pages_v, page_table, pos,
                                  interpret=True)


__all__ = [
    "paged_attention_xla",
    "paged_attention_pallas",
    "paged_attention_pallas_interpret",
    "paged_gather",
]
