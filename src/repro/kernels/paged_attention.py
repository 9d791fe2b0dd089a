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

  q           (B, Hq, Dh)            the new token's query heads
  pages_k/v   (L, P, Hkv, ps, Dh)    the stacked arena, every layer's
  page_table  (B, W)  int32          per-row page ids; ``W * ps == s_cache``
  pos         (B,)    int32          per-row absolute positions
  layer       ()      int32          the layer whose pages are read

The arena is the decode state's own leaf, read where it lies: the layer
loop carries it and updates it in place, and every backend indexes
``(layer, page)`` itself, so nothing the size of a layer's pool is sliced
out for the call.  It is head-major, so one ``(page, head)`` pair is one
contiguous ``(ps, Dh)`` tile: the Pallas kernel's block, with no
transpose.

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
    _, p, hkv, ps, d2 = pages_k.shape
    if pages_v.shape != pages_k.shape:
        raise ValueError(f"k/v arenas differ: {pages_k.shape} vs {pages_v.shape}")
    if d2 != d or hq % hkv:
        raise ValueError(f"q {q.shape} incompatible with pages {pages_k.shape}")
    if page_table.shape[0] != b or pos.shape != (b,):
        raise ValueError(
            f"table {page_table.shape} / pos {pos.shape} do not cover batch {b}"
        )
    return b, hq, d, p, ps, hkv, page_table.shape[1]


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray, layer) -> jnp.ndarray:
    """Materialize one layer's per-row dense views: (L, P, H, ps, D) →
    (B, W·ps, H, D), the dense cache lane's layout.

    Only the table's pages are gathered.  Sentinel entries clip to the
    last page; the caller's validity mask must exclude every position
    they back (the pool's invariant).
    """

    _, p, h, ps, d = pages.shape
    b, w = page_table.shape
    idx = jnp.clip(page_table, 0, p - 1)
    view = pages[layer, idx]  # (B, W, H, ps, D)
    return view.transpose(0, 1, 3, 2, 4).reshape(b, w * ps, h, d)


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


def paged_attention_xla(q, pages_k, pages_v, page_table, pos, layer):
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
    view_k = paged_gather(pages_k, page_table, layer)  # (B, s_cache, Hkv, Dh)
    view_v = paged_gather(pages_v, page_table, layer)
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


def _paged_kernel(table_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, ps, s_cache):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (G, Dh)
    k = k_ref[0, 0, 0]  # (ps, Dh) — this step's (page, head) tile
    v = v_ref[0, 0, 0]

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


def paged_attention_pallas(q, pages_k, pages_v, page_table, pos, layer, *,
                           interpret: bool = False):
    """Pallas route: grid ``(B, Hkv, W)``, the page dim sequential.

    The page table, positions and layer ride as scalar-prefetch operands
    (``PrefetchScalarGridSpec``), in that order, so each grid step's K/V
    *block index* — which ``(layer, page, head)`` tile of the stacked
    arena to DMA — is computed from the table before the body runs:
    ragged, data-dependent paging without host round-trips, and no copy
    of the arena.  Sentinel entries clip to the last page; the in-kernel
    prefix mask zeroes their contribution.
    """

    b, hq, d, p_total, ps, hkv, w = _check_shapes(
        q, pages_k, pages_v, page_table, pos
    )
    g = hq // hkv
    s_cache = w * ps
    q4 = q.reshape(b, hkv, g, d)
    table = jnp.asarray(page_table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def page_index(bb, h, j, table_ref, pos_ref, layer_ref):
        page = jnp.clip(table_ref[bb, j], 0, p_total - 1)
        return (layer_ref[0], page, h, 0, 0)

    def row_index(bb, h, j, table_ref, pos_ref, layer_ref):
        return (bb, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, w),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), row_index),
            pl.BlockSpec((1, 1, 1, ps, d), page_index),
            pl.BlockSpec((1, 1, 1, ps, d), page_index),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), row_index),
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
    )(table, pos, layer, q4, pages_k, pages_v)
    return out.reshape(b, hq, d)


def paged_attention_pallas_interpret(q, pages_k, pages_v, page_table, pos, layer):
    return paged_attention_pallas(q, pages_k, pages_v, page_table, pos, layer,
                                  interpret=True)


# ---------------------------------------------------------------------------
# Paged latent attention (MLA decode, absorbed form)
# ---------------------------------------------------------------------------
#
#   q_c         (B, H, R)          absorbed query q_nope·W_UK, R = kv_lora_rank
#   q_pe        (B, H, E)          roped query, E = qk_rope_head_dim
#   pages_c     (L, P, ps, R)      normed c_kv rows, one per token
#   pages_pe    (L, P, E, ps)      roped k_pe, the page's positions minor
#   page_table  (B, W), pos (B,), layer ()  as above
#   returns     (B, H, R) float32: softmax(scale·(q_c·cᵀ + q_pe·pe)) · c
#
# Every head reads the same latent rows, so a page is one (ps, R) and one
# (E, ps) tile for all H heads of a row.


def paged_mla_xla(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, *, scale: float):
    """Gather route: one layer's pages of each table, masked softmax."""

    from repro.models.layers import mla_latent_attention

    _, p, ps, r = pages_c.shape
    b, w = page_table.shape
    idx = jnp.clip(page_table, 0, p - 1)
    c = pages_c[layer, idx].reshape(b, w * ps, r)
    pe = pages_pe[layer, idx].transpose(0, 1, 3, 2).reshape(b, w * ps, -1)
    valid = _valid_mask(jnp.asarray(pos, jnp.int32), w * ps)
    return mla_latent_attention(q_c, q_pe, c, pe, valid, scale)


def _paged_mla_kernel(table_ref, pos_ref, layer_ref, qc_ref, qpe_ref, c_ref, pe_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, scale, ps, s_cache):
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Pages past the row's last live token hold nothing it attends: their
    # block index repeats the last live page's (no DMA) and they compute
    # nothing.
    @pl.when(j * ps <= pos)
    def _page():
        c = c_ref[0, 0]                    # (ps, R)
        s = jax.lax.dot_general(
            qc_ref[0], c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            qpe_ref[0], pe_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                  # (H, ps)
        s = s * scale
        idx = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < jnp.minimum(pos + 1, s_cache), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_mla_pallas(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, *,
                     scale: float, interpret: bool = False):
    """Pallas route: grid ``(B, W)``, the page dim sequential, the table,
    positions and layer scalar-prefetched (in that order) so each step
    DMAs its ``(layer, page)`` tiles of the arenas where they lie."""

    b, h, r = q_c.shape
    e = q_pe.shape[-1]
    _, p_total, ps, r2 = pages_c.shape
    w = page_table.shape[1]
    if (r2 != r or pages_pe.shape != (pages_c.shape[0], p_total, e, ps)
            or page_table.shape[0] != b or pos.shape != (b,)):
        raise ValueError(f"q {q_c.shape}/{q_pe.shape}, pages {pages_c.shape}/"
                         f"{pages_pe.shape}, table {page_table.shape}, pos {pos.shape}")
    s_cache = w * ps
    table = jnp.asarray(page_table, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def page_index(bb, j, table_ref, pos_ref, layer_ref):
        last = jnp.minimum(pos_ref[bb], s_cache - 1) // ps
        page = jnp.clip(table_ref[bb, jnp.minimum(j, last)], 0, p_total - 1)
        return (layer_ref[0], page, 0, 0)

    def row_index(bb, j, table_ref, pos_ref, layer_ref):
        return (bb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, h, r), row_index),
            pl.BlockSpec((1, h, e), row_index),
            pl.BlockSpec((1, 1, ps, r), page_index),
            pl.BlockSpec((1, 1, e, ps), page_index),
        ],
        out_specs=pl.BlockSpec((1, h, r), row_index),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_mla_kernel, scale=float(scale), ps=ps, s_cache=s_cache)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(table, pos, layer, q_c, q_pe, pages_c, pages_pe)


def paged_mla_pallas_interpret(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, *,
                               scale: float):
    return paged_mla_pallas(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer,
                            scale=scale, interpret=True)


__all__ = [
    "paged_attention_xla",
    "paged_attention_pallas",
    "paged_attention_pallas_interpret",
    "paged_gather",
    "paged_mla_xla",
    "paged_mla_pallas",
    "paged_mla_pallas_interpret",
]
