"""GotoBLAS-style blocked GEMM as Pallas TPU kernels — per-class variants.

TPU adaptation of the paper's Figure 1.  The mapping of the five BLIS loops
onto the Pallas grid (HBM → VMEM → MXU instead of RAM → L2 → L1 → regs):

  ==========  =============================  =================================
  BLIS loop   paper role                     Pallas realization
  ==========  =============================  =================================
  Loop 1/3    coarse partition across        grid dims 0/1 over (M/bm, N/bn)
              clusters / L2-resident A_c     — "parallel" semantics; blocks
                                             staged into VMEM by BlockSpec
  Loop 2      k_c panels / pack B_c          grid dim 2 over K/bk —
                                             "arbitrary" (sequential) with a
                                             VMEM fp32 accumulator
  Loop 4/5    micro-kernel sweep from L1     the jnp.dot inside the kernel
                                             body, lowered onto the MXU
  micro-k     m_r x n_r register tile        128x128 systolic MXU tile
  packing     explicit A_c/B_c copies        implicit: BlockSpec index_map +
                                             double-buffered HBM→VMEM DMA
  ==========  =============================  =================================

Two micro-kernel variants share this scaffolding (the paper's §5.3 point
that each core class may want its *own* micro-kernel, not just its own
blocking):

  * :func:`gemm_pallas` — the default pipelined kernel: a 3-D grid whose
    K dimension is sequential, with the Pallas pipeline double-buffering
    the A/B block staging (working set ``2·(A+B) + acc``).
  * :func:`gemm_pallas_lean` — the VMEM-lean k-streaming variant for
    little-VMEM classes: a 2-D grid over output tiles; K is streamed
    *inside* the kernel body with single-buffered manual DMA
    (``make_async_copy``) while one fp32 accumulator tile stays resident
    (working set ``(A+B) + acc``, plus what both kernels share: the
    output block's pipeline buffers and the compiler's A operand copy —
    ``BlockConfig.vmem_bytes``).  Trading the double-buffering depth for
    footprint lets a class like ``TPU_LITTLE`` run the full shared (bm, bn)
    panel instead of shrinking ``bm`` — at the cost of not overlapping the
    HBM streams with the MXU (the tuning cost model charges exactly that).

The per-class ``BlockConfig`` (control tree) chooses (bm, bk, bn) exactly
like the paper chooses (m_c, k_c) per core type.  On the CPU the kernels
are validated with ``interpret=True``; on TPU the same code JITs through
Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import BlockConfig, _round_up, pad_to_blocks

# Block dims may not exceed the problem rounded up to this lane tile: a
# bigger block silently multiplies padded FLOPs (a cache entry from the
# wrong bucket, a hand-typed config) instead of helping.  ``LANE`` is the
# public name — ``repro.analysis.configcheck`` enforces the same
# padded-problem bound on committed tuning-cache entries with it.
LANE = _LANE = 128


def resolve_block_config(
    m: int, k: int, n: int, dtype, *, double_buffer: bool = True
) -> BlockConfig:
    """Config used when the caller passes ``cfg=None``.

    Delegates to the single resolution path in
    :func:`repro.core.execution.resolve_block_config`: with
    ``$REPRO_TUNING_CACHE`` set, the tuned entry for this
    (spec, dtype, shape bucket) wins; otherwise — and always when the env
    var is unset — the analytical derivation is used, so defaults are
    unchanged.  The kernel itself is identical either way; only the block
    shapes differ.  ``double_buffer=False`` is the lean kernel's VMEM
    model (single-buffered staging admits larger panels).
    """

    from repro.core.execution import resolve_block_config as _resolve

    cfg, _ = _resolve(
        m, k, n,
        dtype_name=dtype.name,
        dtype_bytes=dtype.itemsize,
        double_buffer=double_buffer,
    )
    return cfg


# ---------------------------------------------------------------------------
# Shared pallas_call scaffolding (validation, padding)
# ---------------------------------------------------------------------------


def validate_block_config(m: int, k: int, n: int, cfg: BlockConfig) -> None:
    """Reject blocks that exceed the lane-padded problem, loudly.

    ``pad_to_blocks`` rounds every dim up to its block, so an oversized
    block used to be *silently accepted* — e.g. ``bk=256`` against
    ``K=100`` padded K all the way to 256 and more than doubled the padded
    FLOPs of every grid step.  Any dim only ever needs padding up to the
    128-lane MXU tile; a block beyond that is a misconfiguration (a cache
    entry from another shape bucket, a hand-typed config) and now raises a
    :class:`ValueError` naming the offending dimension.
    """

    for name, dim, blk in (("bm", m, cfg.bm), ("bk", k, cfg.bk), ("bn", n, cfg.bn)):
        padded = _round_up(dim, _LANE)
        if blk > padded:
            axis = {"bm": "M", "bk": "K", "bn": "N"}[name]
            raise ValueError(
                f"block config {name}={blk} exceeds padded {axis}={padded} "
                f"(problem {m}x{k}x{n}, lane tile {_LANE}); blocks larger "
                f"than the padded problem only multiply padding waste"
            )


def _check_operands(a: jnp.ndarray, b: jnp.ndarray) -> None:
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"gemm kernels are 2-D: got {a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")


def _pad_operands(
    a: jnp.ndarray, b: jnp.ndarray, cfg: BlockConfig
) -> tuple[jnp.ndarray, jnp.ndarray, int, int, int]:
    """Pad (M, K, N) up to block multiples (the paper's partial-panel edge
    handling); returns the padded operands and dims."""

    m, k = a.shape
    _, n = b.shape
    pm, pk, pn = pad_to_blocks(m, k, n, cfg)
    if (pm, pk) != (m, k):
        a = jnp.pad(a, ((0, pm - m), (0, pk - k)))
    if (pk, pn) != (k, n):
        b = jnp.pad(b, ((0, pk - k), (0, pn - n)))
    return a, b, pm, pk, pn


# ---------------------------------------------------------------------------
# Default pipelined kernel (double-buffered BlockSpec staging)
# ---------------------------------------------------------------------------


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref):
    """Grid point (i, j, k): C[i,j] += A[i,k] @ B[k,j] with fp32 VMEM acc."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    cfg: Optional[BlockConfig] = None,
    *,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``C = A @ B`` via the blocked (pipelined) Pallas kernel.

    Launches the (M/bm, N/bn, K/bk) grid; the Pallas pipeline stages A/B
    blocks HBM→VMEM double-buffered.  ``interpret=True`` executes the
    kernel body in Python on CPU — the validation mode the test suite and
    the parity harness use.
    """

    _check_operands(a, b)
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    if cfg is None:
        cfg = resolve_block_config(m, k, n, a.dtype)
    validate_block_config(m, k, n, cfg)

    a, b, pm, pk, pn = _pad_operands(a, b, cfg)
    grid = (pm // cfg.bm, pn // cfg.bn, pk // cfg.bk)

    out = pl.pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cfg.bm, cfg.bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((cfg.bk, cfg.bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((cfg.bm, cfg.bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(a, b)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# VMEM-lean k-streaming kernel (single-buffered manual DMA)
# ---------------------------------------------------------------------------


def _gemm_lean_kernel(bm: int, bk: int, bn: int, n_k: int):
    """Kernel factory: output tile (i, j) streams K in bk slices.

    The operands stay in HBM (``memory_space=ANY``); each K step DMAs one
    (bm, bk) A slice and one (bk, bn) B slice into a *single* VMEM buffer
    pair and accumulates into the resident fp32 tile.  No second buffer →
    no DMA/compute overlap, but half the input staging footprint — the
    deliberate trade of :class:`BlockConfig` ``vmem_bytes(False)``.
    """

    def kernel(a_hbm, b_hbm, o_ref, a_vmem, b_vmem, acc_ref, sem_a, sem_b):
        i = pl.program_id(0)
        j = pl.program_id(1)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def body(kk, carry):
            cp_a = pltpu.make_async_copy(
                a_hbm.at[pl.ds(i * bm, bm), pl.ds(kk * bk, bk)], a_vmem, sem_a
            )
            cp_b = pltpu.make_async_copy(
                b_hbm.at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)], b_vmem, sem_b
            )
            cp_a.start()
            cp_b.start()
            cp_a.wait()
            cp_b.wait()
            acc_ref[...] += jnp.dot(
                a_vmem[...], b_vmem[...], preferred_element_type=jnp.float32
            )
            return carry

        jax.lax.fori_loop(0, n_k, body, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return kernel


def gemm_pallas_lean(
    a: jnp.ndarray,
    b: jnp.ndarray,
    cfg: Optional[BlockConfig] = None,
    *,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``C = A @ B`` via the VMEM-lean k-streaming Pallas kernel.

    The ``TPU_LITTLE``-class variant: a (M/bm, N/bn) grid whose kernel
    body streams K with single-buffered manual DMA while the fp32
    accumulator tile stays resident (see :func:`_gemm_lean_kernel`).  With
    ``cfg=None`` the block shapes resolve under the *single-buffer* VMEM
    model, so the same budget admits larger (bm, bn) panels than the
    pipelined default.
    """

    _check_operands(a, b)
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    if cfg is None:
        cfg = resolve_block_config(m, k, n, a.dtype, double_buffer=False)
    validate_block_config(m, k, n, cfg)

    a, b, pm, pk, pn = _pad_operands(a, b, cfg)
    grid = (pm // cfg.bm, pn // cfg.bn)

    out = pl.pallas_call(
        _gemm_lean_kernel(cfg.bm, cfg.bk, cfg.bn, pk // cfg.bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((cfg.bm, cfg.bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((cfg.bm, cfg.bk), a.dtype),
            pltpu.VMEM((cfg.bk, cfg.bn), b.dtype),
            pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
    )(a, b)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("cfg", "out_dtype", "interpret"))
def gemm_pallas_jit(a, b, cfg=None, out_dtype=None, interpret=False):
    return gemm_pallas(a, b, cfg, out_dtype=out_dtype, interpret=interpret)


# The micro-kernel variant registry: variant name -> kernel entry point.
# This is the single source the tuner's search dimension
# (candidates.KERNEL_BACKENDS), the wallclock timer, and the benchmarks
# all derive from — registering a new hardware variant here propagates to
# all three (its execution.BACKENDS/INTERPRET_TWIN dispatch entries are
# guarded separately by the parity harness).
GEMM_KERNELS = {
    "pallas": gemm_pallas,
    "pallas_lean": gemm_pallas_lean,
}


__all__ = [
    "GEMM_KERNELS",
    "LANE",
    "gemm_pallas",
    "gemm_pallas_lean",
    "gemm_pallas_jit",
    "resolve_block_config",
    "validate_block_config",
]
