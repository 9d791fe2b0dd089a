"""Readings of a latent-attention mixture-of-experts step (the readers of
``metrics/*.mladecode.py``), beside the shared ones of ``readings.py``.

* Routed tokens come from the program's counters: the engine counts, per
  MoE decode step, the busy slots' tokens
  (``engine_moe_step_tokens_total``) and their choices of each held expert
  over the MoE layers (``engine_moe_routed_tokens_total{expert}``).  Their
  ratio, over the whole run, is the routed choices per token.
* Kernels are known by their HLO text: the paged latent kernel by its
  operands; the grouped expert GEMMs by the instruction name XLA gives a
  ``ragged_dot`` (``%ragged-dot-none``, and ``%ragged-dot-metadata``
  that groups the rows).
* The routing and combining operations (``moe.route`` / ``moe.combine``
  in the program's named scopes, which the trace's operation names do not
  carry) are known by the shapes only they have: the ``rows · top_k``
  routing choices (and the rows gathered and combined per choice), the
  router's ``(rows, n_router)`` probabilities, the ``(rows, top_k)``
  picks and the ``n_experts + 1`` group sizes.  Operations that hold
  others (a loop, a call) and those that take no time of their own are
  not counted.
"""

from __future__ import annotations

import re

from chipbench import peaks, readings, tracefile
from chipbench.work import BF16

_ARG = r"\{[^}]*\} %[\w.\-]+"
MLA_KERNEL = (rf"custom-call\(s32\[\d+,\d+\]{_ARG}, s32\[\d+\]{_ARG}, s32\[1\]{_ARG}, "
              rf"bf16\[\d+,\d+,\d+\]{_ARG}, bf16\[\d+,\d+,\d+\]{_ARG}, "
              rf"bf16\[\d+,\d+,\d+,\d+\]{_ARG}, bf16\[\d+,\d+,\d+,\d+\]{_ARG}"
              rf"\), custom_call_target=\"tpu_custom_call\"")
EXPERT_GEMM = r"^%ragged-dot"
ROUTED = "engine_moe_routed_tokens_total"
STEP_TOKENS = "engine_moe_step_tokens_total"


def _traced(run) -> bool:
    return run.trace is not None and run.trace.window_ns > 0


def routed_per_token(run):
    """Held-expert choices per busy decode token, over all MoE layers, as
    the program counted them in this run; ``None`` where it counts none."""

    from repro.observability import metrics

    snap = metrics.REGISTRY.snapshot()
    routed = sum(s["value"] for s in snap.get(ROUTED, {}).get("samples", []))
    tokens = sum(s["value"] for s in snap.get(STEP_TOKENS, {}).get("samples", []))
    return routed / tokens if tokens else None


def mla_attn_roofline_pct(run):
    if not _traced(run):
        return None
    ns, n = run.trace.kernel_ns(MLA_KERNEL)
    steps = [c for c in run.calls_in_trace() if c.kind == "step"]
    if not n or not steps:
        return None
    p = peaks.for_kind(run.device_kind)
    t = sum(run.family.attn_roofline_s(run.dims, c.contexts, p.bf16_flops, p.hbm_bytes_per_s)
            for c in steps)
    return 100.0 * t / (ns * 1e-9)


def expert_gemm_roofline_pct(run):
    """Least time of the traced steps' grouped expert GEMMs (the held
    experts' bfloat16 weights read once a layer, the routed rows in and
    out, the routed FLOPs) over their kernels' device time."""

    ratio = routed_per_token(run)
    if not _traced(run) or ratio is None:
        return None
    ns, n = run.trace.kernel_ns(EXPERT_GEMM)
    steps = [c for c in run.calls_in_trace() if c.kind == "step"]
    if not n or not steps:
        return None
    d = run.dims
    p = peaks.for_kind(run.device_kind)
    weights = d.n_experts * 3 * d.d_model * d.d_expert * BF16
    t = 0.0
    for c in steps:
        routed = ratio * len(c.contexts) / d.n_moe            # choices a layer
        flops = routed * run.family.expert_token_flops(d)
        rows = routed * 2 * (d.d_model * BF16 + d.d_expert * 4 + d.d_expert * BF16 + d.d_model * 4)
        t += d.n_moe * max(flops / p.bf16_flops, (weights + rows) / p.hbm_bytes_per_s)
    return 100.0 * t / (ns * 1e-9)


def route_patterns(run) -> list[re.Pattern]:
    d, g = run.dims, run.geometry
    rows = g.pods * g.slots_per_pod
    tk = rows * d.top_k
    return [re.compile(p) for p in (
        rf"\[{tk}(,\d+)?\]",                       # the routing choices, and rows gathered per choice
        rf"[fs]32\[{rows},{d.n_router}\]",         # router logits and probabilities
        rf"\[{rows},{d.top_k}(,{d.n_experts})?\]",  # the top-k picks
        rf"s32\[{d.n_experts + 1}\]",              # group sizes
    )]


# Operations that hold others or take no time of their own.
_NOT_OWN = {"while", "conditional", "call", "tuple", "get-tuple-element", "bitcast",
            "parameter", "constant"}


def moe_route_share_pct(run):
    """Device time of the step's routing and combining operations over the
    step program's device time (%)."""

    if not _traced(run):
        return None
    step_ns, n = run.trace.module_ns(readings.STEP_PROGRAM)
    if not n:
        return None
    d, g = run.dims, run.geometry
    tk = g.pods * g.slots_per_pod * d.top_k
    expert_rows = re.compile(rf"\[{tk},{d.d_expert}\]")   # the experts' SwiGLU between GEMMs
    pats = route_patterns(run)
    step = re.compile(readings.STEP_PROGRAM)
    t = 0.0
    for (prog, text), (ns, _) in run.trace.ops.items():
        if not step.search(prog) or re.search(EXPERT_GEMM, text) or " = " not in text:
            continue
        _, typ, opcode, _ = tracefile.parse_op(text)
        if opcode in _NOT_OWN or expert_rows.search(typ):
            continue
        if any(p.search(text.split(" = ", 1)[1]) for p in pats):
            t += ns
    return 100.0 * t / step_ns


def step_mfu_pct(run):
    """Model FLOPs of the traced calls' tokens, each token's routed experts
    at the counted routed share, over the traced window and the chip's
    peak (%)."""

    ratio = routed_per_token(run)
    if not _traced(run) or ratio is None:
        return None
    f, d = run.family, run.dims
    per_expert = f.expert_token_flops(d)
    flops = 0.0
    for c in run.calls_in_trace():
        if c.kind == "admit":
            flops += sum(f.prompt_flops(d, k) for k in c.contexts)
        else:
            flops += sum(f.dense_token_flops(d, k) + ratio * per_expert for k in c.contexts)
    if not flops:
        return None
    p = peaks.for_kind(run.device_kind)
    return 100.0 * flops / (run.trace.window_ns * 1e-9) / p.bf16_flops
