"""Generic decoder-only LM covering the dense / MoE / SSM / hybrid families.

Structure:

  * layer params are **stacked** along a leading ``L`` axis and driven by
    ``jax.lax.scan`` (compile-once-per-layer — the 512-device AOT compiles
    in seconds instead of minutes),
  * every layer body is wrapped in ``jax.checkpoint`` (remat) so the
    backward pass recomputes activations instead of saving 100+ GB/device,
  * hybrid (Zamba2) runs the Mamba2 stack in groups of
    ``shared_attn_every`` with a weight-shared attention+MLP block between
    groups,
  * decode carries caches through the same scan (KV ring buffers for SWA,
    constant-size SSD states for Mamba2, latent rows for MLA),
  * ``first_k_dense`` leading layers (DeepSeek-V2) are a dense-MLP stack
    of their own (``params["dense"]``) scanned before the MoE stack,
  * serving (decode and inference prefill) routes MoE tokens dropless;
    training keeps capacity dispatch.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.distributed.sharding import constrain_batch
from repro.kernels import ops
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------


def attn_config(cfg: ArchConfig, *, causal: bool = True) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        d_head=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        window=cfg.swa_window,
        causal=causal,
    )


def block_kind(cfg: ArchConfig) -> str:
    return {"dense": "attn_mlp", "moe": "attn_moe", "ssm": "mamba", "hybrid": "mamba"}[
        cfg.family
    ]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ArchConfig):
    if cfg.mla is not None:
        return L.init_mla(key, cfg.mla)
    return L.init_attention(key, attn_config(cfg))


def _init_block(key, cfg: ArchConfig, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "attn_mlp":
        return {
            "ln1": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "attn": _init_attn(ks[0], cfg),
            "ln2": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "mlp": L.init_glu(ks[1], cfg.d_model, cfg.d_ff),
        }
    if kind == "attn_moe":
        return {
            "ln1": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "attn": _init_attn(ks[0], cfg),
            "ln2": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "moe": M.init_moe(ks[1], cfg.moe),
        }
    if kind == "mamba":
        return {
            "ln": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "mamba": S.init_mamba2(ks[0], cfg.ssm),
        }
    raise ValueError(kind)


def init_lm(key, cfg: ArchConfig):
    kind = block_kind(cfg)
    k_emb, k_blocks, k_head, k_shared = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    kd = cfg.first_k_dense
    blocks = jax.vmap(lambda k: _init_block(k, cfg, kind))(layer_keys[kd:])
    params: dict[str, Any] = {
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
        "lm_head": L.dense_init(k_head, (cfg.d_model, cfg.vocab), scale=0.02),
    }
    if not cfg.embed_inputs:
        params["embed"] = L.embed_init(k_emb, (cfg.vocab, cfg.d_model))
    if cfg.shared_attn_every:
        params["shared"] = _init_block(k_shared, cfg, "attn_mlp")
    if kd:
        params["dense"] = jax.vmap(lambda k: _init_block(k, cfg, "attn_mlp"))(layer_keys[:kd])
    return params


def stages(cfg: ArchConfig) -> list[tuple[str, int, int, bool]]:
    """``(params key, first layer, end layer, MoE?)`` of each scanned stack
    of an attention family, in order: the leading dense layers, then the
    rest."""

    kd = cfg.first_k_dense
    out = [("dense", 0, kd, False)] if kd else []
    return out + [("blocks", kd, cfg.n_layers, block_kind(cfg) == "attn_moe")]


def n_moe_layers(cfg: ArchConfig) -> int:
    return cfg.n_layers - cfg.first_k_dense if block_kind(cfg) == "attn_moe" else 0


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_attn_block(p, x, cfg: ArchConfig, positions, *, with_moe: bool,
                      dropless: bool = False):
    n1 = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        h, kv = L.apply_mla(p["attn"], n1, cfg.mla, positions=positions)
    else:
        h, kv = L.apply_attention(p["attn"], n1, attn_config(cfg), positions=positions)
    # Pin each sub-block output to the (sequence-sharded) residual layout
    # in bf16 *before* the residual add: GSPMD then reduce-scatters the
    # bf16 row-parallel partials instead of all-reducing an fp32
    # intermediate (EXPERIMENTS.md §Perf A).
    x = x + constrain_batch(h)
    if with_moe and dropless:
        h, aux = M.apply_moe_dropless(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)[0], 0.0
    elif with_moe:
        h, aux = M.apply_moe(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)
    else:
        h, aux = L.apply_glu(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps)), 0.0
    return x + constrain_batch(h), aux, kv


def _layer_fn(cfg: ArchConfig, kind: str, positions, dropless: bool = False):
    def f(x, p):
        # Pin batch-sharding (and, when enabled, sequence-sharding) of the
        # residual carry at every layer boundary.  Mamba blocks keep the
        # sequence whole (conv + chunked scan want contiguous S).
        x = constrain_batch(x, allow_seq=(kind != "mamba"))
        if kind == "attn_mlp":
            x, aux, _ = _apply_attn_block(p, x, cfg, positions, with_moe=False)
        elif kind == "attn_moe":
            x, aux, _ = _apply_attn_block(p, x, cfg, positions, with_moe=True,
                                          dropless=dropless)
        elif kind == "mamba":
            h, _ = S.apply_mamba2(p["mamba"], L.rms_norm(x, p["ln"], cfg.norm_eps), cfg.ssm)
            x, aux = x + h, 0.0
        else:
            raise ValueError(kind)
        return x, jnp.asarray(aux, jnp.float32)

    return f


def embed_tokens(params, cfg: ArchConfig, batch):
    if cfg.embed_inputs:
        x = batch["embeds"].astype(L.COMPUTE_DTYPE)
    else:
        x = params["embed"][batch["tokens"]].astype(L.COMPUTE_DTYPE)
    return constrain_batch(x)


def _cast_params(tree):
    """fp32 master -> bf16 compute cast, applied per-shard BEFORE the FSDP
    all-gathers so weights cross the interconnect in bf16 (2× less wire
    traffic than gathering fp32 masters; EXPERIMENTS.md §Perf A-4)."""

    return jax.tree.map(
        lambda w: w.astype(L.COMPUTE_DTYPE) if w.dtype == jnp.float32 else w, tree
    )


def forward_lm(params, cfg: ArchConfig, batch, *, remat: bool = True,
               dropless: bool = False):
    """Returns (logits_bf16, aux_loss).  ``dropless``: MoE layers route
    dropless (inference), else by capacity (training)."""

    x = embed_tokens(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    kind = block_kind(cfg)
    params = dict(params, blocks=_cast_params(params["blocks"]),
                  lm_head=_cast_params(params["lm_head"]))
    for extra in ("shared", "dense"):
        if extra in params:
            params = dict(params, **{extra: _cast_params(params[extra])})
    body = _layer_fn(cfg, kind, positions, dropless)
    if remat:
        body = jax.checkpoint(body)
    if "dense" in params:
        lead = _layer_fn(cfg, "attn_mlp", positions)
        x, _ = jax.lax.scan(jax.checkpoint(lead) if remat else lead, x, params["dense"])

    if cfg.shared_attn_every:
        # Zamba2: groups of `every` mamba layers + a weight-shared attn block.
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        shared = params["shared"]
        aux_total = jnp.float32(0)
        for g in range(n_groups):
            seg = jax.tree.map(lambda a: a[g * every : (g + 1) * every], params["blocks"])
            x, aux = jax.lax.scan(body, x, seg)
            aux_total += aux.sum()
            shared_fn = lambda xx: _apply_attn_block(shared, xx, cfg, positions, with_moe=False)[0]
            x = jax.checkpoint(shared_fn)(x) if remat else shared_fn(x)
        aux = aux_total
    else:
        x, aux_l = jax.lax.scan(body, x, params["blocks"])
        aux = aux_l.sum()

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ops.gemm(x, params["lm_head"].astype(L.COMPUTE_DTYPE))
    return constrain_batch(logits, extra=("model",)), aux


# ---------------------------------------------------------------------------
# Loss / train objective
# ---------------------------------------------------------------------------


def cross_entropy(logits, labels, mask=None):
    """Softmax CE in reduction form — no vocab gather, so the vocab axis
    stays model-sharded under GSPMD (a take_along_axis here forces an
    all-gather of fp32 logits: +100 GiB/device at 102k vocab; see
    EXPERIMENTS.md §Perf iteration 0)."""

    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, vocab, dtype=shifted.dtype)
    ll = jnp.sum(shifted * onehot, axis=-1) - lse
    if mask is None:
        return -ll.mean()
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(params, cfg: ArchConfig, batch, *, remat: bool = True):
    logits, aux = forward_lm(params, cfg, batch, remat=remat)
    ce = cross_entropy(logits, batch["labels"], batch.get("mask"))
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def cache_len(cfg: ArchConfig, seq_len: int) -> int:
    if cfg.swa_window is not None:
        return min(cfg.swa_window, seq_len)
    return seq_len


def init_decode_state(cfg: ArchConfig, batch: int, seq_len: int):
    """Abstract-friendly cache pytree (call under jax.eval_shape for specs)."""

    kind = block_kind(cfg)
    ll = cfg.n_layers
    if kind == "mamba":
        st = S.init_mamba2_state(batch, cfg.ssm)
        state = {"mamba": jax.tree.map(
            lambda a: jnp.zeros((ll,) + a.shape, a.dtype), st)}
    elif cfg.mla is not None:
        sc = cache_len(cfg, seq_len)
        state = {
            "c": jnp.zeros((ll, batch, sc, cfg.mla.kv_lora_rank), L.COMPUTE_DTYPE),
            "pe": jnp.zeros((ll, batch, sc, cfg.mla.qk_rope_head_dim), L.COMPUTE_DTYPE),
        }
    else:
        sc = cache_len(cfg, seq_len)
        kv_shape = (ll, batch, sc, cfg.n_kv_heads, cfg.head_dim)
        state = {
            "k": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
            "v": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
        }
    state.update(_routed_state(cfg, batch))
    if cfg.shared_attn_every:
        n_apps = cfg.n_layers // cfg.shared_attn_every
        # The shared attention block sees the full sequence; cap its cache
        # at a practical attention window for long-context decode.
        sc = min(seq_len, 32768)
        kv_shape = (n_apps, batch, sc, cfg.n_kv_heads, cfg.head_dim)
        state["shared_k"] = jnp.zeros(kv_shape, L.COMPUTE_DTYPE)
        state["shared_v"] = jnp.zeros(kv_shape, L.COMPUTE_DTYPE)
    return state


def _routed_state(cfg: ArchConfig, batch: Optional[int]) -> dict:
    """MoE decode state's ``moe_routed`` (n_moe_layers, B, n_experts)
    int32: which held experts each row's token chose at the last step
    (the step's output; its input value is never read)."""

    n = n_moe_layers(cfg)
    if not n:
        return {}
    if batch is None:
        raise ValueError(f"{cfg.name}: a MoE decode state needs its row count")
    return {"moe_routed": jnp.zeros((n, batch, cfg.moe.n_experts), jnp.int32)}


def init_decode_state_paged(cfg: ArchConfig, n_pages: int, page_size: int,
                            rows: Optional[int] = None):
    """Paged decode cache: one shared page arena per layer, no batch dim.

    Replaces the dense ``(L, B, S_cache, Hkv, Dh)`` lanes with stacked
    ``(L, n_pages, Hkv, page_size, Dh)`` arenas; rows find their cache
    through the ``batch["page_table"]`` passed to :func:`decode_step`.
    Head-major, so one ``(page, head)`` pair is a contiguous
    ``(page_size, Dh)`` tile — the paged kernel's block, read in place —
    and pages stay at dim 1, where the pod partition shards them.
    Latent attention (MLA) pages its latent rows instead:
    ``(L, n_pages, page_size, kv_lora_rank)`` and the roped shared key
    ``(L, n_pages, qk_rope_head_dim, page_size)`` (see
    ``layers.decode_mla_paged``).  A MoE family adds ``moe_routed`` with
    ``rows`` rows.  Only the pure KV-cache families page — recurrent
    (Mamba2) state is constant-size per slot already, and the
    hybrid/encdec caches carry extra leaves the page pool does not cover.
    """

    kind = block_kind(cfg)
    if kind == "mamba" or cfg.shared_attn_every:
        raise ValueError(
            f"paged KV state requires a pure KV-cache family, not "
            f"{cfg.family!r} (recurrent state has no pages to allocate)"
        )
    if cfg.mla is not None:
        m = cfg.mla
        state = {
            "pages_c": jnp.zeros((cfg.n_layers, n_pages, page_size, m.kv_lora_rank),
                                 L.COMPUTE_DTYPE),
            "pages_pe": jnp.zeros((cfg.n_layers, n_pages, m.qk_rope_head_dim, page_size),
                                  L.COMPUTE_DTYPE),
        }
    else:
        kv_shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
        state = {
            "pages_k": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
            "pages_v": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
        }
    state.update(_routed_state(cfg, rows))
    return state


def _decode_attn_block(p, x, cfg, ck, cv, pos, *, window=None, live=None):
    acfg = attn_config(cfg)
    if window is not None:
        acfg = L.AttnConfig(**{**acfg.__dict__, "window": window})
    h, (ck, cv) = L.decode_attention(
        p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), acfg, ck, cv, pos,
        live=live,
    )
    x = x + h
    h = L.apply_glu(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
    return x + h, ck, cv


def decode_step(params, cfg: ArchConfig, batch, state, pos):
    """One-token serve step.

    batch: {"tokens": (B,1)} (or {"embeds": (B,1,D)}); pos: int32 absolute
    position — a scalar (static batching) or a (B,) vector of per-row
    positions (slot-table serving; see layers.decode_attention).  Returns
    (logits (B,1,V), new_state).

    Two optional batch keys extend the serving contract:
      * ``"page_table"`` (B, W) int32 — required when ``state`` is the
        paged cache from :func:`init_decode_state_paged` (detected by its
        ``"pages_k"`` leaf); rows then read/write KV through the page
        arena (layers.decode_attention_paged).
      * ``"live"`` (B,) bool — rows whose attention output is real;
        absent means all live (bit-identical to the historical step).
    """

    x = embed_tokens(params, cfg, batch)
    kind = block_kind(cfg)

    if kind == "mamba":
        def body(x, inputs):
            p, st = inputs
            h, st = S.decode_mamba2(p["mamba"], L.rms_norm(x, p["ln"], cfg.norm_eps),
                                    cfg.ssm, st)
            return x + h, st

        if cfg.shared_attn_every:
            every = cfg.shared_attn_every
            n_groups = cfg.n_layers // every
            shared = params["shared"]
            new_mamba, new_sk, new_sv = [], [], []
            for g in range(n_groups):
                seg_p = jax.tree.map(lambda a: a[g * every : (g + 1) * every], params["blocks"])
                seg_s = jax.tree.map(lambda a: a[g * every : (g + 1) * every], state["mamba"])
                x, st = jax.lax.scan(body, x, (seg_p, seg_s))
                new_mamba.append(st)
                # Shared attention caps its own window (ring if needed).
                sc = state["shared_k"].shape[2]
                x2, ck, cv = _decode_attn_block(
                    shared, x, cfg, state["shared_k"][g], state["shared_v"][g], pos,
                    window=sc if sc < 524288 else None,
                )
                x = x2
                new_sk.append(ck)
                new_sv.append(cv)
            new_state = {
                "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_mamba),
                "shared_k": jnp.stack(new_sk, 0),
                "shared_v": jnp.stack(new_sv, 0),
            }
        else:
            x, st = jax.lax.scan(body, x, (params["blocks"], state["mamba"]))
            new_state = {"mamba": st}
    else:
        new_state = _decode_attention_stacks(params, cfg, batch, state, pos, x)
        x = new_state.pop("x")

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ops.gemm(x, params["lm_head"].astype(L.COMPUTE_DTYPE))
    return logits, new_state


def _decode_attention_stacks(params, cfg: ArchConfig, batch, state, pos, x):
    """The attention families' decode: each stack of :func:`stages` scanned
    in turn over the caches.  Paged arenas ride in the carry, never in
    xs/ys: each layer scatters its rows into them in place and the kernel
    reads its pages where they lie, so no layer's pool is sliced out,
    written back or copied.  Dense lanes ride in xs/ys.  Returns the new
    state with the hidden rows under ``"x"``."""

    live = batch.get("live")
    table = batch.get("page_table")
    paged = "pages_k" in state or "pages_c" in state
    if cfg.mla is not None:
        keys = ("pages_c", "pages_pe") if paged else ("c", "pe")
    else:
        keys = ("pages_k", "pages_v") if paged else ("k", "v")
    acfg = attn_config(cfg)

    def block(p, x, caches, layer, with_moe):
        n1 = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.mla is not None and paged:
            h, caches = L.decode_mla_paged(p["attn"], n1, cfg.mla, *caches, layer, table, pos,
                                           live=live)
        elif cfg.mla is not None:
            h, caches = L.decode_mla(p["attn"], n1, cfg.mla, *caches, pos, live=live)
        elif paged:
            h, caches = L.decode_attention_paged(p["attn"], n1, acfg, *caches, layer, table,
                                                 pos, live=live)
        else:
            h, caches = L.decode_attention(p["attn"], n1, acfg, *caches, pos, live=live)
        x = x + h
        n2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if with_moe:
            h, routed = M.apply_moe_dropless(p["moe"], n2, cfg.moe)
        else:
            h, routed = L.apply_glu(p["mlp"], n2), None
        return x + h, caches, routed

    caches = tuple(state[k] for k in keys)
    new_state = {}
    lanes = []
    for name, lo, hi, with_moe in stages(cfg):
        layers = jnp.arange(lo, hi, dtype=jnp.int32)
        if paged:
            def body(carry, inputs, with_moe=with_moe):
                x, caches = carry
                p, layer = inputs
                x, caches, routed = block(p, x, caches, layer, with_moe)
                return (x, caches), routed

            (x, caches), routed = jax.lax.scan(body, (x, caches), (params[name], layers))
        else:
            def body(x, inputs, with_moe=with_moe):
                p, *cs = inputs
                x, cs, routed = block(p, x, tuple(cs), None, with_moe)
                return x, (cs, routed)

            x, (cs, routed) = jax.lax.scan(
                body, x, (params[name], *(c[lo:hi] for c in caches)))
            lanes.append(cs)
        if with_moe:
            new_state["moe_routed"] = routed
    if not paged:
        caches = tuple(jnp.concatenate(parts, 0) if len(parts) > 1 else parts[0]
                       for parts in zip(*lanes))
    new_state.update(zip(keys, caches))
    new_state["x"] = x
    return new_state


def prefill(params, cfg: ArchConfig, batch):
    """Full-sequence inference forward; returns logits (no grad, remat off,
    MoE dropless)."""

    logits, _ = forward_lm(params, cfg, batch, remat=False, dropless=True)
    return logits


__all__ = [
    "attn_config",
    "block_kind",
    "init_lm",
    "forward_lm",
    "loss_fn",
    "cross_entropy",
    "decode_step",
    "prefill",
    "init_decode_state",
    "init_decode_state_paged",
    "cache_len",
    "stages",
    "n_moe_layers",
]
