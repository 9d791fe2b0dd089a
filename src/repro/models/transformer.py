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
    constant-size SSD states for Mamba2).
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


def _init_block(key, cfg: ArchConfig, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "attn_mlp":
        return {
            "ln1": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "attn": L.init_attention(ks[0], attn_config(cfg)),
            "ln2": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "mlp": L.init_glu(ks[1], cfg.d_model, cfg.d_ff),
        }
    if kind == "attn_moe":
        return {
            "ln1": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
            "attn": L.init_attention(ks[0], attn_config(cfg)),
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
    blocks = jax.vmap(lambda k: _init_block(k, cfg, kind))(layer_keys)
    params: dict[str, Any] = {
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.d_model,), L.PARAM_DTYPE),
        "lm_head": L.dense_init(k_head, (cfg.d_model, cfg.vocab), scale=0.02),
    }
    if not cfg.embed_inputs:
        params["embed"] = L.embed_init(k_emb, (cfg.vocab, cfg.d_model))
    if cfg.shared_attn_every:
        params["shared"] = _init_block(k_shared, cfg, "attn_mlp")
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_attn_block(p, x, cfg: ArchConfig, positions, *, with_moe: bool):
    acfg = attn_config(cfg)
    h, kv = L.apply_attention(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), acfg,
                              positions=positions)
    # Pin each sub-block output to the (sequence-sharded) residual layout
    # in bf16 *before* the residual add: GSPMD then reduce-scatters the
    # bf16 row-parallel partials instead of all-reducing an fp32
    # intermediate (EXPERIMENTS.md §Perf A).
    x = x + constrain_batch(h)
    if with_moe:
        h, aux = M.apply_moe(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)
    else:
        h, aux = L.apply_glu(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps)), 0.0
    return x + constrain_batch(h), aux, kv


def _layer_fn(cfg: ArchConfig, kind: str, positions):
    def f(x, p):
        # Pin batch-sharding (and, when enabled, sequence-sharding) of the
        # residual carry at every layer boundary.  Mamba blocks keep the
        # sequence whole (conv + chunked scan want contiguous S).
        x = constrain_batch(x, allow_seq=(kind != "mamba"))
        if kind == "attn_mlp":
            x, aux, _ = _apply_attn_block(p, x, cfg, positions, with_moe=False)
        elif kind == "attn_moe":
            x, aux, _ = _apply_attn_block(p, x, cfg, positions, with_moe=True)
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


def forward_lm(params, cfg: ArchConfig, batch, *, remat: bool = True):
    """Returns (logits_bf16, aux_loss)."""

    x = embed_tokens(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    kind = block_kind(cfg)
    params = dict(params, blocks=_cast_params(params["blocks"]),
                  lm_head=_cast_params(params["lm_head"]))
    if "shared" in params:
        params = dict(params, shared=_cast_params(params["shared"]))
    body = _layer_fn(cfg, kind, positions)
    if remat:
        body = jax.checkpoint(body)

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
    else:
        sc = cache_len(cfg, seq_len)
        kv_shape = (ll, batch, sc, cfg.n_kv_heads, cfg.head_dim)
        state = {
            "k": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
            "v": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
        }
    if cfg.shared_attn_every:
        n_apps = cfg.n_layers // cfg.shared_attn_every
        # The shared attention block sees the full sequence; cap its cache
        # at a practical attention window for long-context decode.
        sc = min(seq_len, 32768)
        kv_shape = (n_apps, batch, sc, cfg.n_kv_heads, cfg.head_dim)
        state["shared_k"] = jnp.zeros(kv_shape, L.COMPUTE_DTYPE)
        state["shared_v"] = jnp.zeros(kv_shape, L.COMPUTE_DTYPE)
    return state


def init_decode_state_paged(cfg: ArchConfig, n_pages: int, page_size: int):
    """Paged decode cache: one shared page arena per layer, no batch dim.

    Replaces the dense ``(L, B, S_cache, Hkv, Dh)`` lanes with stacked
    ``(L, n_pages, Hkv, page_size, Dh)`` arenas; rows find their cache
    through the ``batch["page_table"]`` passed to :func:`decode_step`.
    Head-major, so one ``(page, head)`` pair is a contiguous
    ``(page_size, Dh)`` tile — the paged kernel's block, read in place —
    and pages stay at dim 1, where the pod partition shards them.
    Only the pure KV-cache families page — recurrent (Mamba2) state is
    constant-size per slot already, and the hybrid/encdec caches carry
    extra leaves the page pool does not cover.
    """

    kind = block_kind(cfg)
    if kind == "mamba" or cfg.shared_attn_every:
        raise ValueError(
            f"paged KV state requires a pure KV-cache family, not "
            f"{cfg.family!r} (recurrent state has no pages to allocate)"
        )
    kv_shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    return {
        "pages_k": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
        "pages_v": jnp.zeros(kv_shape, L.COMPUTE_DTYPE),
    }


def _decode_attn_block(p, x, cfg, ck, cv, pos, *, with_moe: bool, window=None,
                       live=None):
    acfg = attn_config(cfg)
    if window is not None:
        acfg = L.AttnConfig(**{**acfg.__dict__, "window": window})
    h, (ck, cv) = L.decode_attention(
        p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), acfg, ck, cv, pos,
        live=live,
    )
    x = x + h
    if with_moe:
        h, _ = M.apply_moe(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)
    else:
        h = L.apply_glu(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
    return x + h, ck, cv


def _decode_attn_block_paged(p, x, cfg, pk, pv, layer, table, pos, *,
                             with_moe: bool, live=None):
    acfg = attn_config(cfg)
    h, (pk, pv) = L.decode_attention_paged(
        p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), acfg, pk, pv,
        layer, table, pos, live=live,
    )
    x = x + h
    if with_moe:
        h, _ = M.apply_moe(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg.moe)
    else:
        h = L.apply_glu(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
    return x + h, pk, pv


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
                    with_moe=False,
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
        with_moe = kind == "attn_moe"
        live = batch.get("live")

        if "pages_k" in state:
            table = batch["page_table"]

            # The stacked arenas ride in the carry, never in xs/ys: each
            # layer scatters its rows into them in place and the kernel
            # reads its pages where they lie, so no layer's pool is
            # sliced out, written back or copied.
            def body(carry, inputs):
                x, pk, pv = carry
                p, layer = inputs
                x, pk, pv = _decode_attn_block_paged(
                    p, x, cfg, pk, pv, layer, table, pos,
                    with_moe=with_moe, live=live,
                )
                return (x, pk, pv), None

            (x, pks, pvs), _ = jax.lax.scan(
                body, (x, state["pages_k"], state["pages_v"]),
                (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
            )
            new_state = {"pages_k": pks, "pages_v": pvs}
        else:
            def body(x, inputs):
                p, ck, cv = inputs
                x, ck, cv = _decode_attn_block(
                    p, x, cfg, ck, cv, pos, with_moe=with_moe, live=live
                )
                return x, (ck, cv)

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["blocks"], state["k"], state["v"])
            )
            new_state = {"k": ks, "v": vs}

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = ops.gemm(x, params["lm_head"].astype(L.COMPUTE_DTYPE))
    return logits, new_state


def prefill(params, cfg: ArchConfig, batch):
    """Full-sequence inference forward; returns logits (no grad, remat off)."""

    logits, _ = forward_lm(params, cfg, batch, remat=False)
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
]
