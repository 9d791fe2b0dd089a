"""Cache/VMEM-aware GEMM blocking configuration.

This module is the TPU adaptation of the paper's Section 3.3 ("Cache
optimization for the big and LITTLE cores").  The paper determines, per core
type, the BLIS parameters ``(m_c, k_c, n_c, m_r, n_r)`` such that

  * the ``k_c x n_r`` micro-panel ``B_r`` streams from the L1 cache,
  * the ``m_c x k_c`` macro-panel ``A_c`` resides in the L2 cache,
  * ``n_c`` is bounded by the L3 cache (absent on the Exynos 5422, so
    ``n_c = 4096``).

On TPU the memory hierarchy is HBM -> VMEM -> vector registers, with a
software-managed VMEM (~16 MiB per core on v5e) feeding a 128x128 MXU.  The
analogous derivation (the "analytical modeling is enough" route of Low et
al., which the paper cites as an alternative to its empirical search) picks
Pallas block shapes ``(bm, bk, bn)`` such that the A-block, B-block, fp32
accumulator and output block — A, B and the output double-buffered for the
HBM<->VMEM pipeline, plus the compiler's own A operand copy — fit a VMEM
budget below the compiler's default scoped-VMEM limit, with MXU-aligned
dimensions.

Both derivations live here:

  * :func:`derive_goto_blocking` — the paper's CPU derivation (used by the
    calibrated big.LITTLE simulator and the CPU benchmarks).
  * :func:`derive_block_config` — the TPU/Pallas derivation (used by the
    kernels and control trees).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

# ---------------------------------------------------------------------------
# Hardware descriptions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheHierarchy:
    """A classical cache hierarchy (paper's target)."""

    name: str
    l1_bytes: int
    l2_bytes: int
    l3_bytes: int = 0  # Exynos 5422 has no L3
    line_bytes: int = 64
    # Fraction of each level the GEMM working set may claim.  The remainder
    # is reserved for the C micro-tile, stack, and streaming interference —
    # mirroring how the paper's empirical optima sit below full capacity.
    l1_fill: float = 0.95
    l2_fill: float = 0.60


@dataclasses.dataclass(frozen=True)
class TpuCoreSpec:
    """A TPU TensorCore as seen by the blocking derivation."""

    name: str = "tpu-v5e"
    vmem_bytes: int = 16 * 1024 * 1024
    mxu: int = 128              # systolic array dimension
    lane: int = 128             # last-dim register tiling
    sublane: int = 8            # second-minor tiling unit for fp32
    peak_flops: float = 197e12  # bf16
    hbm_bw: float = 819e9
    # Fraction of VMEM available to the GEMM pipeline (the rest holds
    # semaphores, spills, and the scalar prefetch state).
    vmem_fill: float = 0.9


# Paper's platform (Section 3.2): per-core L1d 32 KiB; L2 shared per
# cluster — 2 MiB for the Cortex-A15 quad, 512 KiB for the Cortex-A7 quad.
CORTEX_A15 = CacheHierarchy("cortex-a15", l1_bytes=32 * 1024, l2_bytes=2 * 1024 * 1024)
CORTEX_A7 = CacheHierarchy("cortex-a7", l1_bytes=32 * 1024, l2_bytes=512 * 1024)

TPU_V5E = TpuCoreSpec()

# The degraded device class of the motivating heterogeneous fleet (see
# ``repro.core.asymmetric.biglittle_classes``): half the VMEM, half the
# sustained FLOPs and HBM bandwidth.  Single source of truth — the
# asymmetric mesh, the tuning SPECS registry, and the ratio calibration
# all mean *this* hardware when they say "tpu-little".
TPU_LITTLE = TpuCoreSpec(
    name="tpu-little",
    vmem_bytes=8 * 1024 * 1024,
    peak_flops=99e12,
    hbm_bw=410e9,
)


# ---------------------------------------------------------------------------
# Block configurations
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GotoBlocking:
    """The paper's five BLIS parameters for one core class."""

    mc: int
    kc: int
    nc: int
    mr: int = 4
    nr: int = 4

    def a_panel_bytes(self, dtype_bytes: int = 8) -> int:
        return self.mc * self.kc * dtype_bytes

    def b_micropanel_bytes(self, dtype_bytes: int = 8) -> int:
        return self.kc * self.nr * dtype_bytes


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Pallas GEMM block shapes (the TPU analogue of ``GotoBlocking``).

    ``bm x bk`` A-blocks and ``bk x bn`` B-blocks are staged HBM->VMEM
    (double buffered by the Pallas pipeline); a ``bm x bn`` fp32 accumulator
    persists in VMEM across the K grid dimension, and the ``bm x bn``
    output block (operand dtype, the kernels' default ``out_dtype``) is
    written back through a double-buffered pipeline slot.
    """

    bm: int
    bk: int
    bn: int
    dtype_bytes: int = 2          # bf16 operands
    acc_bytes: int = 4            # fp32 accumulator

    def vmem_bytes(self, double_buffer: bool = True) -> int:
        """Working set: ``double_buffer=False`` is the VMEM-lean k-streaming
        kernel (``gemm_pallas_lean``), which stages one A/B block at a time
        instead of the pipelined pair — half the input footprint, so larger
        (bm, bn) panels fit the same budget.

        Both kernels also hold what the TPU compiler adds on its own: the
        output block's two pipeline buffers, and one more A block (the
        MXU operand copy; compiling both kernels for a v5e measured
        ``bm*bk*dtype_bytes`` plus under 0.4 MiB above the rest)."""

        mult = 2 if double_buffer else 1
        a = self.bm * self.bk * self.dtype_bytes
        b = self.bk * self.bn * self.dtype_bytes
        c = self.bm * self.bn * self.acc_bytes
        out = 2 * self.bm * self.bn * self.dtype_bytes
        return mult * (a + b) + a + c + out

    def fits(self, spec: TpuCoreSpec = TPU_V5E, *, double_buffer: bool = True) -> bool:
        return self.vmem_bytes(double_buffer) <= spec.vmem_bytes * spec.vmem_fill

    def arithmetic_intensity(self) -> float:
        """FLOPs per HBM byte moved for one (bm, bn) output block column."""
        flops = 2.0 * self.bm * self.bn * self.bk
        bytes_moved = (self.bm * self.bk + self.bk * self.bn) * self.dtype_bytes
        return flops / bytes_moved


# ---------------------------------------------------------------------------
# Paper derivation (CPU caches)
# ---------------------------------------------------------------------------


def derive_goto_blocking(
    cache: CacheHierarchy,
    *,
    dtype_bytes: int = 8,
    mr: int = 4,
    nr: int = 4,
    kc_cap: Optional[int] = None,
    shared_kc: Optional[int] = None,
) -> GotoBlocking:
    """Analytic (m_c, k_c, n_c) for a cache hierarchy, per paper Section 3.3.

    * ``k_c``: the B micro-panel ``k_c x n_r`` must stream from L1 —
      ``k_c * n_r * dtype_bytes <= l1_fill * l1_bytes``.
    * ``m_c``: the A macro-panel ``m_c x k_c`` must reside in L2 —
      ``m_c * k_c * dtype_bytes <= l2_fill * l2_bytes``.
    * ``n_c``: bounded by L3 when present, otherwise the paper's 4096.

    ``shared_kc`` reproduces the Section 5.3 constraint: when Loop 3 is the
    inter-cluster loop the ``B_c`` buffer is shared, forcing a common
    ``k_c`` across classes and a re-derived (smaller) ``m_c`` for the class
    whose L2 cannot hold ``m_c x k_c`` at the shared ``k_c``.
    """

    if shared_kc is not None:
        kc = shared_kc
    else:
        kc = int(cache.l1_fill * cache.l1_bytes / (nr * dtype_bytes))
        # Keep a multiple of 8 like BLIS does for vector-friendly strides.
        kc = max(8, (kc // 8) * 8)
        if kc_cap is not None:
            kc = min(kc, kc_cap)

    mc = int(cache.l2_fill * cache.l2_bytes / (kc * dtype_bytes))
    mc = max(mr, (mc // mr) * mr)
    # Degenerate hierarchies (L2 ≈ L1): the m_c >= m_r floor can overflow
    # L2 — give k_c back until the minimal m_r-row panel fits.
    if shared_kc is None:
        while mc * kc * dtype_bytes > cache.l2_bytes and kc > 8:
            kc = max(8, ((kc // 2) // 8) * 8)
            mc = max(mr, (int(cache.l2_fill * cache.l2_bytes / (kc * dtype_bytes)) // mr) * mr)

    if cache.l3_bytes:
        nc = int(0.5 * cache.l3_bytes / (kc * dtype_bytes))
        nc = max(nr, (nc // nr) * nr)
    else:
        nc = 4096  # paper: "n_c plays a minor role ... set to 4096"
    return GotoBlocking(mc=mc, kc=kc, nc=nc, mr=mr, nr=nr)


# The paper's empirically-determined optima (Section 3.3 / Figure 4),
# recorded for validation and used verbatim by the calibrated simulator.
PAPER_A15 = GotoBlocking(mc=152, kc=952, nc=4096)
PAPER_A7 = GotoBlocking(mc=80, kc=352, nc=4096)
# Section 5.3: shared k_c = 952 (Loop-3 coarse partitioning) forces the
# Cortex-A7 macro-panel down to m_c = 32.
PAPER_A7_SHARED_KC = GotoBlocking(mc=32, kc=952, nc=4096)


# ---------------------------------------------------------------------------
# TPU derivation (VMEM)
# ---------------------------------------------------------------------------


def _round_down(x: int, mult: int) -> int:
    return max(mult, (x // mult) * mult)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def derive_block_config(
    m: int,
    k: int,
    n: int,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype_bytes: int = 2,
    max_bm: int = 1024,
    max_bk: int = 2048,
    max_bn: int = 1024,
    double_buffer: bool = True,
) -> BlockConfig:
    """Pick ``(bm, bk, bn)`` maximizing arithmetic intensity under VMEM.

    Mirrors the paper's capacity argument: the bigger the resident panel,
    the more compute amortizes each byte staged into fast memory.  We grow
    ``bk`` first (it amortizes both A and B traffic, like the paper grows
    ``k_c`` to fill L1), then balance ``bm``/``bn``.  All dims are
    MXU/lane aligned; dims are clamped to the (padded) problem size so tiny
    problems do not claim VMEM they cannot use, and ``bk`` divides the
    lane-padded K so no K padding beyond the lane is computed.

    ``double_buffer=False`` derives for the VMEM-lean k-streaming kernel
    (single-buffered input staging): the same budget admits larger
    (bm, bn) panels — the paper's §5.3 observation that a class with less
    fast memory wants a *different micro-kernel*, not just smaller blocks.
    """

    budget = int(spec.vmem_bytes * spec.vmem_fill)
    align = spec.mxu

    pm = _round_up(min(m, max_bm), align)
    pn = _round_up(min(n, max_bn), align)
    pk = _round_up(min(k, max_bk), align)
    lane_k = _round_up(k, align)

    best: Optional[BlockConfig] = None
    bm = pm
    while bm >= align:
        bn = pn
        while bn >= align:
            # Largest aligned bk that fits the budget for this (bm, bn)
            # under BlockConfig.vmem_bytes: the fp32 accumulator and the
            # double-buffered output block are fixed; A+B staging
            # (pipelined pair or one lean buffer) and the compiler's A
            # operand copy grow with bk.
            fixed = bm * bn * (4 + 2 * dtype_bytes)
            per_k = ((2 if double_buffer else 1) * (bm + bn) + bm) * dtype_bytes
            if fixed >= budget:
                bn //= 2
                continue
            bk = _round_down(min(pk, (budget - fixed) // per_k), align)
            # ... that divides the lane-padded K: any other bk pads K up to
            # a multiple of bk, MXU work spent on zeros.
            while lane_k % bk:
                bk -= align
            cfg = BlockConfig(bm=bm, bk=bk, bn=bn, dtype_bytes=dtype_bytes)
            if cfg.fits(spec, double_buffer=double_buffer):
                if best is None or cfg.arithmetic_intensity() > best.arithmetic_intensity():
                    best = cfg
                elif (
                    math.isclose(cfg.arithmetic_intensity(), best.arithmetic_intensity())
                    and cfg.vmem_bytes() < best.vmem_bytes()
                ):
                    best = cfg
            bn //= 2
        bm //= 2
    assert best is not None, "no feasible block config — VMEM budget too small"
    return best


def pad_to_blocks(m: int, k: int, n: int, cfg: BlockConfig) -> tuple[int, int, int]:
    """Padded problem dims so the Pallas grid divides evenly."""

    return (_round_up(m, cfg.bm), _round_up(k, cfg.bk), _round_up(n, cfg.bn))


def search_grid(
    coarse: bool,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype_bytes: int = 2,
) -> list[BlockConfig]:
    """Candidate (bm, bk) grid for the empirical search benchmark.

    The paper runs a coarse sweep over (m_c, k_c) and then refines around
    the best region (Figure 4).  This enumerates the same two-stage
    structure over MXU-aligned Pallas blocks; ``bn`` is fixed at 256 like
    the paper fixes ``n_r``.
    """

    step = 256 if coarse else 128
    out = []
    for bm in range(128, 1025, step):
        for bk in range(128, 2049, step):
            cfg = BlockConfig(bm=bm, bk=bk, bn=256, dtype_bytes=dtype_bytes)
            if cfg.fits(spec):
                out.append(cfg)
    return out


__all__ = [
    "CacheHierarchy",
    "TpuCoreSpec",
    "GotoBlocking",
    "BlockConfig",
    "CORTEX_A15",
    "CORTEX_A7",
    "TPU_V5E",
    "TPU_LITTLE",
    "PAPER_A15",
    "PAPER_A7",
    "PAPER_A7_SHARED_KC",
    "derive_goto_blocking",
    "derive_block_config",
    "pad_to_blocks",
    "search_grid",
]
