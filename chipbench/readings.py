"""Shared arithmetic of the metric readers (``endtoend/*.py``, ``metrics/*.py``).

Host-clock readings come from the window's record: every token's host
stamp and every engine call.  Device
readings come from the trace summary (``tracefile.Summary``) and the work
the configuration's shapes imply (``work.py``), against the chip's peaks
(``peaks.py``).  A reader that finds nothing to read returns ``None``.
"""

from __future__ import annotations

from chipbench import peaks, work

# Stable names, found by hand in v5e traces of the engine.  Programs: the
# XLA module carries the jitted function's name.  Kernels: a Pallas kernel
# is a ``tpu_custom_call`` whose HLO text names no kernel, so each is known
# by its operands: the GEMM takes two 2-D bfloat16 arrays; paged attention
# takes the page table and the positions (int32, scalar-prefetched) first.
STEP_PROGRAM = r"^jit_step_fn\("
_TPU_KERNEL = r'\), custom_call_target="tpu_custom_call"'
_ARG = r"\{[^}]*\} %[\w.\-]+"
GEMM_KERNEL = rf"custom-call\(bf16\[\d+,\d+\]{_ARG}, bf16\[\d+,\d+\]{_ARG}{_TPU_KERNEL}"
PAGED_ATTN_KERNEL = rf"custom-call\(s32\[\d+,\d+\]{_ARG}, s32\[\d+\]{_ARG}, .*{_TPU_KERNEL}"


def window_tokens(run) -> int:
    r = run.record
    return sum(1 for ts in r.token_times.values() for t in ts if r.t0 <= t <= r.t1)


def inter_token_gaps_ms(run) -> list[float]:
    """Every gap between consecutive tokens of a request, both inside the
    window."""

    r = run.record
    out = []
    for ts in r.token_times.values():
        inside = [t for t in ts if r.t0 <= t <= r.t1]
        out += [1e3 * (b - a) for a, b in zip(inside, inside[1:])]
    return out


# ---------------------------------------------------------------------------
# From the trace
# ---------------------------------------------------------------------------


def _traced(run):
    return run.trace is not None and run.trace.window_ns > 0


def idle_share_pct(run):
    if not _traced(run):
        return None
    return 100.0 * (1.0 - run.trace.busy_ns / run.trace.window_ns)


def decode_step_ms(run):
    if not _traced(run):
        return None
    ns, n = run.trace.module_ns(STEP_PROGRAM)
    return ns * 1e-6 / n if n else None


def _gemm_shapes(run):
    """Every GEMM the traced calls ran: one decode step per step call, one
    per scanned prompt position per admission round."""

    d = run.dims
    shapes = []
    for c in run.calls_in_trace():
        steps = c.round_len if c.kind == "admit" else 1
        shapes += work.step_gemms(d, c.rows) * steps
    return shapes


def gemm_roofline_pct(run):
    if not _traced(run):
        return None
    ns, n = run.trace.kernel_ns(GEMM_KERNEL)
    shapes = _gemm_shapes(run)
    if not n or not shapes:
        return None
    p = peaks.for_kind(run.device_kind)
    return 100.0 * work.gemm_roofline_s(shapes, p.bf16_flops, p.hbm_bytes_per_s) / (ns * 1e-9)


def paged_attn_roofline_pct(run):
    if not _traced(run):
        return None
    ns, n = run.trace.kernel_ns(PAGED_ATTN_KERNEL)
    steps = [c for c in run.calls_in_trace() if c.kind == "step"]
    if not n or not steps:
        return None
    p = peaks.for_kind(run.device_kind)
    t = sum(work.paged_attn_roofline_s(run.dims, c.contexts, p.bf16_flops, p.hbm_bytes_per_s)
            for c in steps)
    return 100.0 * t / (ns * 1e-9)


def step_mfu_pct(run):
    """Model FLOPs of every token the traced calls produced or prefilled,
    over the traced window and the chip's peak."""

    if not _traced(run):
        return None
    d = run.dims
    flops = 0.0
    for c in run.calls_in_trace():
        if c.kind == "admit":
            flops += sum(work.prompt_flops(d, p) for p in c.contexts)
        else:
            flops += sum(work.token_flops(d, k) for k in c.contexts)
    if not flops:
        return None
    p = peaks.for_kind(run.device_kind)
    return 100.0 * flops / (run.trace.window_ns * 1e-9) / p.bf16_flops
