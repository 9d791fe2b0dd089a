"""Mixture-of-Experts FFN: capacity dispatch for training, dropless for serving.

Implements top-k routing (Mixtral 8×top-2; Qwen2-MoE 60×top-4 + shared
experts) via the scatter/gather capacity formulation: tokens are grouped by
their batch row (which is the data-sharded axis, so dispatch stays local
under SPMD), ranked within their expert by a cumulative-sum position, and
scattered into per-expert capacity buffers.  Expert GEMMs are batched
einsums whose compiled FLOPs ≈ active FLOPs × capacity factor — a
requirement for the roofline's MODEL_FLOPS/HLO_FLOPs ratio to be honest.

Routing is itself an asymmetric scheduling problem (balancing a shared
iteration space across unequal consumers); the capacity factor plays the
role of the paper's ratio knob, and the auxiliary load-balance loss is the
feedback controller.  See DESIGN.md §2.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    d_ff_shared: int = 0          # aggregated shared-expert width (Qwen2-MoE)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # Reduce-scatter the expert output buffer over "model" (wins 4× on
    # wide-expert MoE like Mixtral; measured to HURT fine-grained-expert
    # MoE, whose weights are FSDP-only — see EXPERIMENTS.md §Perf C).
    rs_output: bool = True
    n_router: Optional[int] = None  # router outputs (all experts); None: n_experts
    first_expert: int = 0           # the first held expert's router index
    norm_topk_prob: bool = True     # renormalise the top-k weights to sum to 1
    shared_gate: bool = True        # sigmoid gate on the shared expert

    @property
    def router_width(self) -> int:
        return self.n_router or self.n_experts


def init_moe(key, cfg: MoEConfig):
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (cfg.d_model, cfg.router_width), scale=0.02),
        "w1": L.dense_init(ks[1], (cfg.n_experts, cfg.d_model, cfg.d_ff_expert)),
        "w3": L.dense_init(ks[2], (cfg.n_experts, cfg.d_model, cfg.d_ff_expert)),
        "w2": L.dense_init(ks[3], (cfg.n_experts, cfg.d_ff_expert, cfg.d_model)),
    }
    if cfg.d_ff_shared:
        p["shared"] = L.init_glu(ks[4], cfg.d_model, cfg.d_ff_shared)
        if cfg.shared_gate:
            p["shared_gate"] = L.dense_init(ks[4], (cfg.d_model, 1), scale=0.02)
    return p


def route(p, x2, cfg: MoEConfig):
    """Router of tokens ``x2`` (T, D): softmax over the full router width
    in float32, greedy top-k, optionally renormalised.  Returns ``(probs (T, n_router), weights (T, k),
    held-local expert ids (T, k), held (T, k) bool)``."""

    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    local = idx - cfg.first_expert
    held = (local >= 0) & (local < cfg.n_experts)
    return probs, w, local, held


def _shared(p, xc, cfg: MoEConfig):
    y = L.apply_glu(p["shared"], xc)
    if not cfg.shared_gate:
        return y
    g = jax.nn.sigmoid(
        jnp.einsum("...d,do->...o", xc, p["shared_gate"].astype(L.COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32)
    ).astype(L.COMPUTE_DTYPE)
    return g * y


def _capacity(tokens_per_group: int, cfg: MoEConfig) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    c = ((c + 7) // 8) * 8
    # Never allocate more slots than routing decisions exist — a capacity
    # floor at tiny group sizes (decode: 1 token/group) would compute
    # E/top_k times more expert FLOPs than useful.
    return max(1, min(c if c else 1, tokens_per_group * cfg.top_k))


def apply_moe(p, x, cfg: MoEConfig):
    """x: (B, S, D) -> (y, aux_loss).  Groups = batch rows (data-sharded).

    Decode-time group merging: with one token per sequence the per-row
    groups are too small for capacity dispatch (slot waste = E/top_k), so
    rows are merged into groups of >=256 tokens before routing — the
    serving-side analogue of batching micro-kernels into panels.
    """

    b, s, d = x.shape
    if s < 256 and b > 1:
        merge = min(b, max(1, 256 // max(s, 1)))
        while b % merge:
            merge -= 1
        if merge > 1:
            y, aux = apply_moe(p, x.reshape(b // merge, merge * s, d), cfg)
            return y.reshape(b, s, d), aux
    kk = cfg.top_k
    e = cfg.n_experts
    cap = _capacity(s, cfg)

    xc = x.astype(L.COMPUTE_DTYPE)
    er = cfg.router_width
    probs, gate_w, expert_idx, held = route(p, xc.reshape(b * s, d), cfg)
    probs = probs.reshape(b, s, er)                       # (B,S,E_router) fp32
    gate_w = gate_w.reshape(b, s, kk)
    held = held.reshape(b, s, kk)
    router_idx = (expert_idx + cfg.first_expert).reshape(b, s, kk)
    expert_idx = jnp.where(held, expert_idx.reshape(b, s, kk), 0)   # held-local

    # Load-balance auxiliary loss (Switch-style), computed per group over
    # the router's full width.
    me = probs.mean(axis=1)                               # (B,E_router)
    ce = jnp.zeros((b, er), jnp.float32)
    for j in range(kk):  # k is tiny (2 or 4)
        ce = ce + jax.nn.one_hot(router_idx[..., j], er, dtype=jnp.float32).mean(axis=1)
    aux = (me * ce).sum(-1).mean() * er * cfg.router_aux_weight

    # Position of each routing decision inside its expert's capacity buffer
    # (choices of experts held elsewhere take no place).
    flat_e = expert_idx.reshape(b, s * kk)                # (B, S*k)
    flat_held = held.reshape(b, s * kk)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32) * flat_held[..., None]  # (B, S*k, E)
    pos = jnp.cumsum(oh, axis=1) - 1                      # (B, S*k, E)
    pos = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]  # (B, S*k)
    keep = (flat_held & (pos < cap)).astype(jnp.float32) * gate_w.reshape(b, s * kk)

    # Scatter tokens into (E, C, D) buffers per group.
    xr = jnp.repeat(xc, kk, axis=1)                        # (B, S*k, D)
    pos_c = jnp.clip(pos, 0, cap - 1)

    def scatter_group(xg, eg, pg, keepg):
        buf = jnp.zeros((e, cap, d), L.COMPUTE_DTYPE)
        return buf.at[eg, pg].add(xg * (keepg[:, None] > 0))

    buf = jax.vmap(scatter_group)(xr, flat_e, pos_c, keep)  # (B,E,C,D)

    c = lambda w: w.astype(L.COMPUTE_DTYPE)
    h1 = jnp.einsum("becd,edf->becf", buf, c(p["w1"]),
                    preferred_element_type=L.COMPUTE_DTYPE)
    h3 = jnp.einsum("becd,edf->becf", buf, c(p["w3"]),
                    preferred_element_type=L.COMPUTE_DTYPE)
    h = jax.nn.silu(h1.astype(jnp.float32)).astype(L.COMPUTE_DTYPE) * h3
    out_buf = jnp.einsum("becf,efd->becd", h, c(p["w2"]),
                         preferred_element_type=L.COMPUTE_DTYPE)  # (B,E,C,D)
    if cfg.rs_output:
        # The w2 contraction runs over the model-sharded d_ff dim; pinning
        # the output D dim to "model" turns GSPMD's fp32 all-reduce of the
        # whole capacity buffer into a bf16 reduce-scatter (the combine
        # gather below is pointwise in D, so it composes).
        from repro.distributed.sharding import constrain as _constrain

        out_buf = _constrain(out_buf, (None, None, None, "model"))

    def gather_group(bufg, eg, pg, keepg):
        return bufg[eg, pg] * keepg[:, None].astype(L.COMPUTE_DTYPE)

    y = jax.vmap(gather_group)(out_buf, flat_e, pos_c, keep)  # (B,S*k,D)
    y = y.reshape(b, s, kk, d).sum(axis=2)

    if cfg.d_ff_shared:
        y = y + _shared(p, xc, cfg)
    return y.astype(x.dtype), aux


def apply_moe_dropless(p, x, cfg: MoEConfig):
    """Dropless expert layer: x (..., D) -> (y, routed (T, n_experts) int32).

    Every token's top-k choices that name a held expert are computed; the
    choices are sorted by expert and each held expert's group runs through
    ``ragged_dot``, which reads that expert's weights once for its whole
    group.  Choices of experts held elsewhere sort last, past every group,
    and add nothing.  ``routed[t, e]`` is 1 where token ``t`` chose held
    expert ``e`` (the per-row routing the serving engine counts).
    """

    shape = x.shape
    d = shape[-1]
    xc = x.reshape(-1, d).astype(L.COMPUTE_DTYPE)
    t, kk, e = xc.shape[0], cfg.top_k, cfg.n_experts
    c = lambda w: w.astype(L.COMPUTE_DTYPE)
    with jax.named_scope("moe.route"):
        _, w, local, held = route(p, xc, cfg)
        key = jnp.where(held, local, e).reshape(-1)           # (T*k,), e = not held
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        rows = order // kk                                     # token of each sorted choice
        gate = jnp.where(held, w, 0.0).reshape(-1)[order]
        xs = xc[rows]                                          # (T*k, D)
        routed = jax.nn.one_hot(key, e + 1, dtype=jnp.int32)[:, :e].reshape(t, kk, e).sum(1)
    with jax.named_scope("moe.experts"):
        h1 = jax.lax.ragged_dot(xs, c(p["w1"]), sizes, preferred_element_type=jnp.float32)
        h3 = jax.lax.ragged_dot(xs, c(p["w3"]), sizes, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(h1) * h3).astype(L.COMPUTE_DTYPE)
        out = jax.lax.ragged_dot(h, c(p["w2"]), sizes, preferred_element_type=jnp.float32)
    with jax.named_scope("moe.combine"):
        inside = jnp.arange(t * kk) < sizes.sum()
        contrib = jnp.where(inside[:, None], out * gate[:, None], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[rows].add(contrib)
    if cfg.d_ff_shared:
        y = y + _shared(p, xc, cfg).astype(jnp.float32)
    return y.astype(x.dtype).reshape(shape), routed


def moe_active_params(cfg: MoEConfig) -> int:
    """Per-token active parameter count (for MODEL_FLOPS = 6·N_active·D)."""

    expert = 3 * cfg.d_model * cfg.d_ff_expert
    n = cfg.top_k * expert + cfg.d_model * cfg.router_width
    if cfg.d_ff_shared:
        n += 3 * cfg.d_model * cfg.d_ff_shared + cfg.d_model * cfg.shared_gate
    return n


__all__ = ["MoEConfig", "init_moe", "route", "apply_moe", "apply_moe_dropless",
           "moe_active_params"]
