"""The model's work, counted from the configuration's shapes.

Nothing here or in a family's counts (``families/<family>.py``:
``step_gemms``, ``attn_roofline_s``, ``token_flops``, ``prompt_flops``)
reads the compiled program: padding, converts or a change of kernel do not
change these counts.  GEMM operands are bfloat16, the engine's compute
type.
"""

from __future__ import annotations

BF16 = 2


def gemm_roofline_s(shapes, peak_flops: float, peak_bw: float) -> float:
    """Least time the chip could spend on these GEMMs, each bound by the
    larger of its operations over peak FLOP/s and its bytes over peak
    bandwidth."""

    t = 0.0
    for m, k, n in shapes:
        flops = 2.0 * m * k * n
        nbytes = BF16 * (m * k + k * n + m * n)
        t += max(flops / peak_flops, nbytes / peak_bw)
    return t
