"""Candidate ``BlockConfig`` enumeration for the empirical search.

The paper searches the (m_c, k_c) plane in two stages — a coarse sweep and
a refinement around the winner (Section 3.3 / Figure 4).  The TPU analogue
enumerated here is the set of MXU/lane-aligned ``(bm, bk, bn)`` triples
whose double-buffered working set fits the per-core VMEM budget, clamped
to the (padded) problem so tiny problems do not claim blocks they cannot
fill.  The analytical optimum of :func:`derive_block_config` is always a
member — the search can therefore only match or beat it — and an explicit
neighborhood around it provides the paper's "refine near the model's
prediction" structure.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

from repro.core.blocking import (
    TPU_LITTLE,
    TPU_V5E,
    BlockConfig,
    TpuCoreSpec,
    _round_up,
    derive_block_config,
)

# Named core specs addressable from the CLI / cache keys.  ``tpu-little``
# is the degraded class of ``repro.core.asymmetric.biglittle_classes`` —
# the same ``TPU_LITTLE`` object, so tuned entries and calibration agree
# on what the name means.
SPECS: dict[str, TpuCoreSpec] = {
    TPU_V5E.name: TPU_V5E,
    TPU_LITTLE.name: TPU_LITTLE,
}


def get_spec(name: str) -> TpuCoreSpec:
    try:
        return SPECS[name]
    except KeyError:
        raise KeyError(f"unknown core spec {name!r}; known: {sorted(SPECS)}") from None


def analytical_config(
    m: int,
    k: int,
    n: int,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype_bytes: int = 2,
    double_buffer: bool = True,
) -> BlockConfig:
    """The model-derived default (the search's baseline and seed).

    ``double_buffer=False`` seeds the VMEM-lean kernel's search: the
    single-buffer working-set model admits larger panels.
    """

    return derive_block_config(
        m, k, n, spec=spec, dtype_bytes=dtype_bytes, double_buffer=double_buffer
    )


def _axis_values(problem_dim: int, cap: int, align: int) -> list[int]:
    """Aligned power-of-two ladder up to min(padded problem, cap)."""

    hi = min(_round_up(problem_dim, align), cap)
    vals = []
    v = align
    while v <= hi:
        vals.append(v)
        v *= 2
    if not vals or vals[-1] != hi:
        vals.append(hi)
    return vals


def neighborhood(
    cfg: BlockConfig, *, spec: TpuCoreSpec = TPU_V5E, double_buffer: bool = True
) -> list[BlockConfig]:
    """One-step refinements around ``cfg`` (the paper's fine sweep).

    Perturbs each dimension by ±1 alignment step and ±2x, keeping only
    feasible (aligned, VMEM-fitting under the given buffering model)
    results.
    """

    align = spec.mxu
    out = []
    for dim in ("bm", "bk", "bn"):
        base = getattr(cfg, dim)
        for nxt in (base - align, base + align, base // 2, base * 2):
            if nxt < align or nxt % align:
                continue
            cand = dataclasses.replace(cfg, **{dim: nxt})
            if cand.fits(spec, double_buffer=double_buffer):
                out.append(cand)
    return out


def enumerate_candidates(
    m: int,
    k: int,
    n: int,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype_bytes: int = 2,
    max_bm: int = 1024,
    max_bk: int = 2048,
    max_bn: int = 1024,
    extra: Optional[Iterable[BlockConfig]] = None,
    double_buffer: bool = True,
) -> list[BlockConfig]:
    """The deduplicated feasible candidate set for one GEMM shape.

    Every returned config is MXU-aligned in all three dims and fits the
    VMEM budget under the requested buffering model (``cfg.fits(spec,
    double_buffer=...)``); the analytical optimum and its neighborhood are
    always included.  Deterministic order: analytical first, then
    ascending ``(bm, bk, bn)``.
    """

    align = spec.mxu
    seed = analytical_config(
        m, k, n, spec=spec, dtype_bytes=dtype_bytes, double_buffer=double_buffer
    )

    pool: list[BlockConfig] = [seed]
    pool += neighborhood(seed, spec=spec, double_buffer=double_buffer)
    for bm in _axis_values(m, max_bm, align):
        for bn in _axis_values(n, max_bn, align):
            for bk in _axis_values(k, max_bk, align):
                cand = BlockConfig(bm=bm, bk=bk, bn=bn, dtype_bytes=dtype_bytes)
                if cand.fits(spec, double_buffer=double_buffer):
                    pool.append(cand)
    if extra:
        pool += [c for c in extra if c.fits(spec, double_buffer=double_buffer)]

    seen: set[tuple[int, int, int]] = set()
    out: list[BlockConfig] = []
    for cand in [seed] + sorted(pool, key=lambda c: (c.bm, c.bk, c.bn)):
        key = (cand.bm, cand.bk, cand.bn)
        if key in seen:
            continue
        if cand.bm % align or cand.bk % align or cand.bn % align:
            continue
        seen.add(key)
        out.append(cand)
    return out


# ---------------------------------------------------------------------------
# Micro-kernel variants as a search dimension (paper §5.3)
# ---------------------------------------------------------------------------

# The kernel variants the search enumerates by default: every entry of
# the variant registry (the pipelined default plus the VMEM-lean
# k-streaming kernel).  Interpret twins and "xla" are execution modes /
# dispatch entries, not separate search points — neither the cost model
# nor the wallclock timer can model them as kernels.
def _kernel_backends() -> tuple[str, ...]:
    from repro.kernels.gemm import GEMM_KERNELS

    return tuple(GEMM_KERNELS)


KERNEL_BACKENDS: tuple[str, ...] = _kernel_backends()


@dataclasses.dataclass(frozen=True)
class KernelCandidate:
    """One search point: a block config *and* the kernel variant to run it.

    The lean variant's single-buffered working set admits (bm, bn) panels
    the pipelined kernel cannot hold — the variant dimension genuinely
    widens the feasible set, it is not a relabeling.
    """

    cfg: BlockConfig
    backend: str = "pallas"

    @property
    def key(self) -> tuple[int, int, int, str]:
        return (self.cfg.bm, self.cfg.bk, self.cfg.bn, self.backend)


def enumerate_kernel_candidates(
    m: int,
    k: int,
    n: int,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype_bytes: int = 2,
    backends: Iterable[str] = KERNEL_BACKENDS,
    **kwargs,
) -> list[KernelCandidate]:
    """The (config, variant) candidate set for one GEMM shape.

    Per variant, configs are enumerated under *that kernel's* VMEM model
    (double-buffered for ``"pallas"``, single-buffered for
    ``"pallas_lean"``); duplicates of (bm, bk, bn, backend) are dropped.
    Order: each variant's analytical seed first (default variant leading),
    then the merged grids.
    """

    from repro.core.execution import backend_double_buffers
    from repro.kernels.gemm import GEMM_KERNELS

    backends = list(backends)
    for b in backends:
        # Validate against the *kernel* registry, not the dispatch table:
        # "xla" and the interpret twins are not timeable search variants.
        if b not in GEMM_KERNELS:
            raise ValueError(
                f"unknown kernel backend {b!r}; searchable variants: "
                f"{sorted(GEMM_KERNELS)}"
            )
    out: list[KernelCandidate] = []
    seen: set[tuple[int, int, int, str]] = set()
    per_backend = [
        (
            b,
            enumerate_candidates(
                m, k, n,
                spec=spec,
                dtype_bytes=dtype_bytes,
                double_buffer=backend_double_buffers(b),
                **kwargs,
            ),
        )
        for b in backends
    ]
    # Seeds first (search_shape treats candidate #0 as the baseline).
    for b, cands in per_backend:
        cand = KernelCandidate(cfg=cands[0], backend=b)
        if cand.key not in seen:
            seen.add(cand.key)
            out.append(cand)
    for b, cands in per_backend:
        for cfg in cands[1:]:
            cand = KernelCandidate(cfg=cfg, backend=b)
            if cand.key not in seen:
                seen.add(cand.key)
                out.append(cand)
    return out


__all__ = [
    "KERNEL_BACKENDS",
    "SPECS",
    "KernelCandidate",
    "get_spec",
    "analytical_config",
    "neighborhood",
    "enumerate_candidates",
    "enumerate_kernel_candidates",
]
