"""The model's work, counted from the configuration's shapes.

Nothing here reads the compiled program: padding, converts or a change of
kernel do not change these counts.  GEMM operands are bfloat16 (the
engine's compute type) at the step's row count; paged attention reads the
live contexts' keys and values once.
"""

from __future__ import annotations

from chipbench.model import Dims, param_count

BF16 = 2


def step_gemms(d: Dims, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every GEMM of one decode step over ``rows`` rows."""

    hq, hkv = d.n_heads * d.d_head, d.n_kv_heads * d.d_head
    layer = [(rows, d.d_model, hq), (rows, d.d_model, hkv), (rows, d.d_model, hkv),
             (rows, hq, d.d_model), (rows, d.d_model, d.d_ff), (rows, d.d_model, d.d_ff),
             (rows, d.d_ff, d.d_model)]
    return layer * d.n_layers + [(rows, d.d_model, d.vocab)]


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


def paged_attn_roofline_s(d: Dims, contexts, peak_flops: float, peak_bw: float) -> float:
    """Least time for one decode step's paged attention over all layers:
    each row attends to its live context (``contexts`` tokens per row)."""

    ctx = float(sum(contexts))
    rows = len(contexts)
    kv_bytes = ctx * d.n_kv_heads * d.d_head * 2 * BF16          # keys and values
    qo_bytes = rows * d.n_heads * d.d_head * 2 * BF16            # query in, output out
    flops = 4.0 * ctx * d.n_heads * d.d_head                     # q.k and p.v
    return d.n_layers * max(flops / peak_flops, (kv_bytes + qo_bytes) / peak_bw)


def token_flops(d: Dims, context: int) -> float:
    """Model FLOPs of one token at position ``context - 1``: two per
    non-embedding parameter (the LM head included) and the attention over
    its ``context`` keys."""

    return 2.0 * param_count(d, embed=False) + 4.0 * d.n_layers * context * d.n_heads * d.d_head


def prompt_flops(d: Dims, p: int) -> float:
    """Model FLOPs of prefilling a ``p``-token prompt: every position
    ``k`` (1-based) attends to ``k`` keys."""

    return 2.0 * param_count(d, embed=False) * p + 4.0 * d.n_layers * d.n_heads * d.d_head * p * (p + 1) / 2
