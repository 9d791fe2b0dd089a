"""Candidate scoring: roofline cost model and wall-clock backends.

Two interchangeable backends score a ``BlockConfig`` for one GEMM shape:

  * ``cost-model`` — deterministic seconds estimate from the same roofline
    terms as :mod:`repro.launch.roofline` (compute vs HBM traffic, per the
    core spec's ``peak_flops`` / ``hbm_bw``), plus a per-grid-step launch
    overhead.  Pure Python, no JAX tracing — this is what tests and CI run,
    and what the ``--backend cost-model`` search uses.
  * ``wallclock`` — median wall time of the real Pallas kernel
    (:func:`repro.kernels.gemm.gemm_pallas`): ``interpret=True`` on CPU,
    compiled through Mosaic on TPU.  The paper's actual Section 3.3
    protocol; only meaningful on hardware.

The cost model deliberately charges what the analytical derivation cannot
see: padding waste on ragged shapes (a block bigger than the problem pays
for zeros) and grid-step overhead (too-small blocks launch thousands of
steps) — the two effects the paper's empirical search exists to capture.

Both backends take the micro-kernel variant (``kernel_backend``) as a
scoring dimension: the pipelined default overlaps the HBM streams with
the MXU (``max(compute, memory)``), while the VMEM-lean single-buffered
kernel serializes them (``compute + memory``) in exchange for fitting
larger panels — the §5.3 per-class trade the search now weighs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from repro.core.blocking import TPU_V5E, BlockConfig, TpuCoreSpec, pad_to_blocks
from repro.core.execution import backend_double_buffers

# Fixed cost per grid step (DMA issue + pipeline bubble).  Order of
# magnitude from TPU kernel practice; the precise value only needs to rank
# "thousands of tiny blocks" below "tens of large blocks".
GRID_STEP_OVERHEAD_S = 1e-6

# The measurement-backend vocabulary (a *scorer* name, not a kernel
# backend — ``execution.BACKENDS`` is that other, disjoint vocabulary).
# ``repro.analysis``'s drift detector admits these tokens alongside the
# kernel registry so ``--backend cost-model`` CLI plumbing stays legal.
MEASURE_BACKEND_NAMES: tuple[str, ...] = ("cost-model", "wallclock")


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """Roofline terms for one (shape, config, variant) cell."""

    cfg: BlockConfig
    compute_s: float
    memory_s: float
    overhead_s: float
    grid: tuple[int, int, int]
    # Micro-kernel variant the estimate models; decides stream overlap.
    kernel_backend: str = "pallas"

    @property
    def time_s(self) -> float:
        """Step-time lower bound.

        The pipelined kernel double-buffers, so HBM traffic hides under
        the MXU (``max``); the lean kernel single-buffers, so each K step
        waits for its DMA before computing (``sum``).
        """

        if backend_double_buffers(self.kernel_backend):
            return max(self.compute_s, self.memory_s) + self.overhead_s
        return self.compute_s + self.memory_s + self.overhead_s

    @property
    def bottleneck(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


def cost_breakdown(
    m: int,
    k: int,
    n: int,
    cfg: BlockConfig,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    kernel_backend: str = "pallas",
) -> CostBreakdown:
    """Deterministic roofline estimate of one blocked-GEMM invocation.

    Traffic model matches the Pallas grids of ``kernels/gemm.py``: per
    (i, j, kk) step an ``(bm, bk)`` A-block and ``(bk, bn)`` B-block are
    staged HBM->VMEM, so A is re-read once per j column and B once per i
    row; the fp32 accumulator lives in VMEM and C is written once.  (The
    lean kernel walks the same (i, j, kk) space — its inner fori_loop
    issues the same per-step DMAs, so the traffic and overhead terms are
    shared; only the overlap differs, see :class:`CostBreakdown`.)
    Compute covers the *padded* problem — padding waste is charged.
    """

    pm, pk, pn = pad_to_blocks(m, k, n, cfg)
    gm, gn, gk = pm // cfg.bm, pn // cfg.bn, pk // cfg.bk

    flops = 2.0 * pm * pk * pn
    a_bytes = gm * gn * gk * cfg.bm * cfg.bk * cfg.dtype_bytes
    b_bytes = gm * gn * gk * cfg.bk * cfg.bn * cfg.dtype_bytes
    c_bytes = pm * pn * cfg.dtype_bytes
    return CostBreakdown(
        cfg=cfg,
        compute_s=flops / spec.peak_flops,
        memory_s=(a_bytes + b_bytes + c_bytes) / spec.hbm_bw,
        overhead_s=gm * gn * gk * GRID_STEP_OVERHEAD_S,
        grid=(gm, gn, gk),
        kernel_backend=kernel_backend,
    )


def cost_model_time(
    m: int,
    k: int,
    n: int,
    cfg: BlockConfig,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    kernel_backend: str = "pallas",
) -> float:
    """Seconds the cost-model backend estimates."""

    return cost_breakdown(
        m, k, n, cfg, spec=spec, kernel_backend=kernel_backend
    ).time_s


def wallclock_time(
    m: int,
    k: int,
    n: int,
    cfg: BlockConfig,
    *,
    dtype=None,
    interpret: Optional[bool] = None,
    reps: int = 3,
    warmup: int = 1,
    kernel_backend: str = "pallas",
) -> float:
    """Median wall seconds of the real Pallas kernel on this host.

    ``interpret`` defaults to True off-TPU (the validation path) and False
    on TPU (the Mosaic-compiled perf path).  ``kernel_backend`` selects
    the micro-kernel variant being timed (``"pallas"``/``"pallas_lean"``).
    """

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.gemm import GEMM_KERNELS

    try:
        kernel = GEMM_KERNELS[kernel_backend]
    except KeyError:
        raise ValueError(
            f"wallclock cannot time kernel backend {kernel_backend!r}; "
            f"known: {sorted(GEMM_KERNELS)}"
        ) from None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dtype = dtype or (jnp.bfloat16 if cfg.dtype_bytes == 2 else jnp.float32)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)

    def call():
        return jax.block_until_ready(kernel(a, b, cfg, interpret=interpret))

    for _ in range(warmup):
        call()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def make_backend(
    name: str,
    *,
    spec: TpuCoreSpec = TPU_V5E,
    dtype=None,
) -> Callable[..., float]:
    """Resolve a backend name to a ``(m, k, n, cfg) -> seconds`` scorer.

    Scorers also accept ``kernel_backend=`` (the micro-kernel variant
    being scored; default ``"pallas"``) — the search passes it when the
    variant dimension is enabled.
    """

    if name == "cost-model":
        return lambda m, k, n, cfg, kernel_backend="pallas": cost_model_time(
            m, k, n, cfg, spec=spec, kernel_backend=kernel_backend
        )
    if name == "wallclock":
        return lambda m, k, n, cfg, kernel_backend="pallas": wallclock_time(
            m, k, n, cfg, dtype=dtype, kernel_backend=kernel_backend
        )
    raise ValueError(f"unknown measure backend {name!r} (cost-model|wallclock)")


__all__ = [
    "GRID_STEP_OVERHEAD_S",
    "MEASURE_BACKEND_NAMES",
    "CostBreakdown",
    "cost_breakdown",
    "cost_model_time",
    "wallclock_time",
    "make_backend",
]
