"""Family dispatch: one uniform API over all ten architectures.

  * ``init_params(key, cfg)``
  * ``make_loss_fn(cfg)``        -> (params, batch) -> (loss, metrics)
  * ``make_prefill_fn(cfg)``     -> (params, batch) -> logits
    (``with_cache=True``: the fused bulk prefill,
    (params, batch, state, pos0) -> (last_logits, state))
  * ``make_decode_fn(cfg)``      -> (params, batch, state, pos) -> (logits, state)
  * ``init_decode_state(cfg, batch, seq_len)``
  * ``batch_spec(cfg, shape)``   -> ShapeDtypeStruct inputs for that cell
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig, ShapeSpec
from repro.models import encdec as E
from repro.models import layers as L
from repro.models import transformer as T


def init_params(key, cfg: ArchConfig):
    if cfg.family == "encdec":
        return E.init_encdec(key, cfg)
    return T.init_lm(key, cfg)


def make_loss_fn(cfg: ArchConfig, *, remat: bool = True):
    if cfg.family == "encdec":
        def f(params, batch):
            return E.loss_fn(params, cfg, batch, remat=remat)
    else:
        def f(params, batch):
            return T.loss_fn(params, cfg, batch, remat=remat)
    return f


def make_prefill_fn(cfg: ArchConfig, *, with_cache: bool = False):
    """Prefill forward.

    ``with_cache=False`` (default): the full-sequence training-style
    forward, ``(params, batch) -> logits`` — the throughput path for
    logits-only prefill (dry-run, scoring).

    ``with_cache=True``: the **fused bulk prefill** the serving stack
    uses, ``(params, batch, state, pos0) -> (last_logits, state)`` — one
    jitted forward over the whole prompt that writes the decode state
    (KV caches / SSM states) in one shot.  See
    :func:`bulk_prefill_from_decode` for the exactness contract (the
    written state is bit-identical to the token-by-token decode replay,
    which the one-shot host loop never was going to get from the chunked
    training forward).
    """

    if with_cache:
        return bulk_prefill_from_decode(make_decode_fn(cfg))
    if cfg.family == "encdec":
        def f(params, batch):
            return E.forward_encdec(params, cfg, batch, remat=False)[0]
    else:
        def f(params, batch):
            return T.prefill(params, cfg, batch)
    return f


def bulk_prefill_from_decode(decode_fn):
    """Build the fused bulk prefill from any decode-step-compatible fn.

    ``decode_fn(params, {"tokens": (B,1)}, state, pos) -> (logits, state)``
    becomes ``(params, {"tokens": (B,P)}, state, pos0) -> (logits, state)``:
    the whole prompt is consumed inside a single jitted program (a
    ``lax.scan`` over prompt positions), so the host dispatches **one**
    call per prompt instead of P — and, donated, the decode state updates
    in place instead of being copied P times through the host loop.

    The scan body *is* the decode recurrence, which makes the resulting
    cache **bit-identical** to the token-by-token replay — the property
    the slot-table serving engine needs (a prefilled slot must be
    indistinguishable from one that decoded those tokens), and one no
    chunked full-sequence forward can provide: its attention/SSD
    reductions are associativity-reordered relative to the recurrent
    form, so its cache agrees only to tolerance.  Bit-identity for every
    token-in zoo arch is asserted in tests/test_serving.py.

    ``pos0`` is the absolute position of the first prompt token — a
    scalar, or a (B,) vector of per-slot positions.  Accepts the wrapped
    ``decode_fn`` so callers can prefill through a class-sharded mixed
    step (``AsymmetricMesh.class_sharded``) as well as the plain zoo fn.

    Every batch key besides ``"tokens"`` (``"page_table"``, ``"live"``)
    is passed through to each decode step unchanged — the paged serving
    path prefills through the same page tables it decodes through.

    ``plens`` (optional, (B,) int32) supports **mixed-length prompts in
    one fused call**: prompts are right-padded to the batch's max length,
    every row runs all padded steps (pad writes land past each row's
    live positions, where the decode mask already hides them — the same
    argument that makes stale cache content invisible), and each row's
    *returned* logits are the ones from its own last real token
    ``t == plens[row] - 1`` instead of the final padded step.  ``None``
    keeps the single-length behavior bit-for-bit.
    """

    def f(params, batch, state, pos0, plens=None):
        if "tokens" not in batch:
            raise ValueError("bulk prefill needs a token-in batch ({'tokens': (B,P)})")
        tokens = batch["tokens"]
        extras = {k: v for k, v in batch.items() if k != "tokens"}
        pos0 = jnp.asarray(pos0, jnp.int32)
        plen = tokens.shape[1]

        def step(state, tok, p):
            return decode_fn(params, dict(extras, tokens=tok), state, p)

        def select(sel, lg, t):
            if plens is None:
                return lg
            keep = (jnp.asarray(plens, jnp.int32) - 1 == t)[:, None, None]
            return jnp.where(keep, lg, sel)

        logits, state = step(state, tokens[:, :1], pos0)
        logits = select(logits, logits, 0)
        if plen > 1:
            def body(carry, t):
                st, sel = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
                lg, st = step(st, tok, pos0 + t)
                return (st, select(sel, lg, t)), None

            (state, logits), _ = jax.lax.scan(
                body, (state, logits), jnp.arange(1, plen)
            )
        return logits, state

    return f


def make_decode_fn(cfg: ArchConfig):
    if cfg.family == "encdec":
        def f(params, batch, state, pos):
            return E.decode_step(params, cfg, batch, state, pos)
    else:
        def f(params, batch, state, pos):
            return T.decode_step(params, cfg, batch, state, pos)
    return f


def init_decode_state(cfg: ArchConfig, batch: int, seq_len: int):
    if cfg.family == "encdec":
        return E.init_decode_state(None, cfg, batch, seq_len)
    return T.init_decode_state(cfg, batch, seq_len)


def init_decode_state_paged(cfg: ArchConfig, n_pages: int, page_size: int,
                            rows=None):
    """Paged decode cache (pure KV-cache families only; see transformer);
    ``rows``, the step's row count, sizes a MoE family's routing leaf."""

    if cfg.family == "encdec":
        raise ValueError("paged KV state does not cover the encdec cross-KV cache")
    return T.init_decode_state_paged(cfg, n_pages, page_size, rows=rows)


def batch_spec(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct inputs for one (arch × shape) cell."""

    b, s = shape.global_batch, shape.seq_len
    tok = lambda ss: jax.ShapeDtypeStruct((b, ss), jnp.int32)
    emb = lambda ss: jax.ShapeDtypeStruct((b, ss, cfg.d_model), L.COMPUTE_DTYPE)

    if shape.kind == "decode":
        batch = {"embeds": emb(1)} if cfg.embed_inputs else {"tokens": tok(1)}
        return batch

    if cfg.family == "encdec":
        out = {"frames": emb(s), "tokens": tok(s)}
    elif cfg.embed_inputs:
        out = {"embeds": emb(s)}
    else:
        out = {"tokens": tok(s)}
    if shape.kind == "train":
        out["labels"] = tok(s)
    return out


def decode_state_spec(cfg: ArchConfig, batch: int, seq_len: int):
    return jax.eval_shape(lambda: init_decode_state(cfg, batch, seq_len))


__all__ = [
    "init_params",
    "make_loss_fn",
    "make_prefill_fn",
    "bulk_prefill_from_decode",
    "make_decode_fn",
    "init_decode_state",
    "init_decode_state_paged",
    "decode_state_spec",
    "batch_spec",
]
