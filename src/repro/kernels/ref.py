"""Pure-jnp oracles for the GEMM kernels.

Two references:

  * :func:`gemm_ref` — the ground truth (``jnp.dot`` with fp32
    accumulation), used by every kernel allclose test.
  * :func:`blocked_gemm_ref` — a faithful transcription of the paper's
    Figure 1 five-loop BLIS algorithm (Loop 1 over ``n_c``, Loop 2 over
    ``k_c`` packing ``B_c``, Loop 3 over ``m_c`` packing ``A_c``, Loops 4/5
    over ``n_r``/``m_r`` around the micro-kernel).  It exists to validate
    the *loop structure and packing* semantics that the Pallas kernel
    mirrors at TPU block granularity.  Python loops → small shapes only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocking import BlockConfig, GotoBlocking


def gemm_ref(a: jnp.ndarray, b: jnp.ndarray, out_dtype=None) -> jnp.ndarray:
    """C = A @ B with fp32 accumulation (the oracle)."""

    out_dtype = out_dtype or a.dtype
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def blocked_gemm_ref(
    a: np.ndarray,
    b: np.ndarray,
    cfg: GotoBlocking,
) -> np.ndarray:
    """Paper Figure 1, verbatim loop structure (numpy, fp32 accumulate)."""

    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    c = np.zeros((m, n), np.float32)
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)

    for jc in range(0, n, cfg.nc):                      # Loop 1
        nc = min(cfg.nc, n - jc)
        for pc in range(0, k, cfg.kc):                  # Loop 2
            kc = min(cfg.kc, k - pc)
            b_c = b[pc : pc + kc, jc : jc + nc].copy()  # pack B_c
            for ic in range(0, m, cfg.mc):              # Loop 3
                mc = min(cfg.mc, m - ic)
                a_c = a[ic : ic + mc, pc : pc + kc].copy()  # pack A_c
                # Macro-kernel: Loops 4 and 5 around the micro-kernel.
                for jr in range(0, nc, cfg.nr):         # Loop 4
                    nr = min(cfg.nr, nc - jr)
                    for ir in range(0, mc, cfg.mr):     # Loop 5
                        mr = min(cfg.mr, mc - ir)
                        # Micro-kernel: rank-k_c update of an m_r x n_r tile.
                        c[ic + ir : ic + ir + mr, jc + jr : jc + jr + nr] += (
                            a_c[ir : ir + mr, :] @ b_c[:, jr : jr + nr]
                        )
    return c


def blocked_gemm_tpu_ref(a: jnp.ndarray, b: jnp.ndarray, cfg: BlockConfig) -> jnp.ndarray:
    """Block-accumulation oracle matching the Pallas kernel's tiling.

    Computes C block-by-block with per-(bm,bn) fp32 accumulators over bk
    slices — the same arithmetic order as the Pallas grid, so comparisons
    are bit-friendlier than against one big dot.
    """

    m, k = a.shape
    _, n = b.shape
    out = jnp.zeros((m, n), jnp.float32)
    for i0 in range(0, m, cfg.bm):
        for j0 in range(0, n, cfg.bn):
            acc = jnp.zeros((min(cfg.bm, m - i0), min(cfg.bn, n - j0)), jnp.float32)
            for k0 in range(0, k, cfg.bk):
                ab = a[i0 : i0 + cfg.bm, k0 : k0 + cfg.bk]
                bb = b[k0 : k0 + cfg.bk, j0 : j0 + cfg.bn]
                acc = acc + jnp.dot(ab, bb, preferred_element_type=jnp.float32)
            out = out.at[i0 : i0 + cfg.bm, j0 : j0 + cfg.bn].set(acc)
    return out.astype(a.dtype)


def attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Dense (B, S, H, D) attention oracle with optional causal/SWA mask."""

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qi = jnp.arange(sq)[:, None] + (sk - sq)
    ki = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qi >= ki
    if window is not None:
        mask &= qi - ki < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,
    pages_k: jnp.ndarray,
    pages_v: jnp.ndarray,
    page_table: jnp.ndarray,
    pos: jnp.ndarray,
    layer,
) -> jnp.ndarray:
    """Paged single-token decode-attention oracle (fp32 end to end).

    Deliberately *not* the production op order: ungrouped fp32 einsums
    over an eagerly sliced layer and gathered dense view, so both the XLA
    gather route and the online-softmax Pallas kernel are checked against
    independent arithmetic.  Shapes as in ``kernels.paged_attention``:
    ``q`` is ``(B, Hq, Dh)``, the stacked arenas ``(L, P, Hkv, ps, Dh)``,
    the table ``(B, W)`` with ``W·ps`` the logical cache length, ``pos``
    ``(B,)``, ``layer`` the arena layer read.
    """

    b, hq, d = q.shape
    _, p, hkv, ps, _ = pages_k.shape
    w = page_table.shape[1]
    s_cache = w * ps
    g = hq // hkv
    idx = jnp.clip(page_table, 0, p - 1)

    def view(pages):
        v = jnp.take(pages, layer, axis=0)[idx]  # (B, W, Hkv, ps, Dh)
        v = jnp.moveaxis(v, 2, 3).reshape(b, s_cache, hkv, d)
        return v.astype(jnp.float32)

    view_k, view_v = view(pages_k), view(pages_v)
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, view_k) / np.sqrt(d)
    limit = jnp.minimum(jnp.asarray(pos, jnp.int32)[:, None] + 1, s_cache)
    valid = jnp.arange(s_cache)[None, :] < limit
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", pr, view_v)
    return o.reshape(b, hq, d).astype(q.dtype)


def paged_mla_ref(q_c, q_pe, pages_c, pages_pe, page_table, pos, layer, *, scale: float):
    """Paged latent-attention oracle (fp32 end to end): the layer sliced
    out eagerly, each row's pages gathered, the keys rebuilt as
    ``[c | pe]`` rows and one plain masked softmax.  Shapes as in
    ``kernels.paged_attention.paged_mla_pallas``."""

    b = q_c.shape[0]
    _, p, ps, r = pages_c.shape
    w = page_table.shape[1]
    idx = jnp.clip(page_table, 0, p - 1)
    c = jnp.take(pages_c, layer, axis=0).astype(jnp.float32)[idx].reshape(b, w * ps, r)
    pe = jnp.take(pages_pe, layer, axis=0).astype(jnp.float32)[idx]      # (B, W, E, ps)
    pe = jnp.swapaxes(pe, 2, 3).reshape(b, w * ps, -1)
    keys = jnp.concatenate([c, pe], axis=-1)
    q = jnp.concatenate([q_c, q_pe], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bhc,bsc->bhs", q, keys) * scale
    limit = jnp.minimum(jnp.asarray(pos, jnp.int32)[:, None] + 1, w * ps)
    s = jnp.where((jnp.arange(w * ps)[None, :] < limit)[:, None, :], s, -1e30)
    return jnp.einsum("bhs,bsc->bhc", jax.nn.softmax(s, axis=-1), c)


__all__ = [
    "gemm_ref",
    "blocked_gemm_ref",
    "blocked_gemm_tpu_ref",
    "attention_ref",
    "paged_attention_ref",
    "paged_mla_ref",
]
