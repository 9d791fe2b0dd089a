"""The dense SwiGLU decoder: the LLaMA-style family of both first
configurations (InternLM2, arXiv:2403.17297; DeepSeek LLM,
arXiv:2401.02954).

Its plain reference is written from the published equations, with nothing
taken from the program under test:

    h   = x + Wo · attn(RoPE(Wq · n1), RoPE(Wk · n1), Wv · n1),  n1 = RMSNorm(x)
    out = h + W2 · (silu(W1 · n2) * (W3 · n2)),                   n2 = RMSNorm(h)

with causal grouped-query attention (``n_heads / n_kv_heads`` query heads
per key head), rotary embeddings on pairs ``(i, i + d/2)`` at the
configuration's theta, and a final RMSNorm before the untied LM head.
Every product runs in float32 at ``precision="highest"``; ``bits`` is the
control's rounding (``reference.round_mantissa``).

The work counts: GEMM operands are bfloat16 (the engine's compute type) at
the step's row count; paged attention reads the live contexts' keys and
values once; model FLOPs count every parameter, all of them active.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _mm, attention, rms_norm, rope
from chipbench.work import BF16


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference and the work counts need."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float


def dims(c: dict) -> Dims:
    return Dims(
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_head=int(c.get("head_dim", c["hidden_size"] // c["num_attention_heads"])),
        d_ff=int(c["intermediate_size"]),
        vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
    )


def arch_config(c: dict, d: Dims):
    """The registry's ``ArchConfig`` with every size the file states."""

    from repro.configs import get_config

    base = get_config(c["chipbench"]["arch"])
    if base.family != "dense" or c.get("hidden_act", "silu") != "silu":
        raise ValueError("the dense family covers dense SwiGLU decoders only")
    return dataclasses.replace(
        base, name=c["chipbench"].get("name", base.name), n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, d_head=d.d_head,
        d_ff=d.d_ff, vocab=d.vocab, rope_theta=d.rope_theta, norm_eps=d.norm_eps,
        qkv_bias=bool(c.get("bias", c.get("attention_bias", False))),
    )


# ---------------------------------------------------------------------------
# The parameter tree
# ---------------------------------------------------------------------------


def param_shapes(d: Dims) -> dict:
    """The engine's parameter tree: layer weights stacked on a leading axis."""

    L, D, F, V = d.n_layers, d.d_model, d.d_ff, d.vocab
    hq, hkv = d.n_heads * d.d_head, d.n_kv_heads * d.d_head
    return {
        "blocks": {
            "ln1": (L, D),
            "attn": {"wq": (L, D, hq), "wk": (L, D, hkv), "wv": (L, D, hkv), "wo": (L, hq, D)},
            "ln2": (L, D),
            "mlp": {"w1": (L, D, F), "w3": (L, D, F), "w2": (L, F, D)},
        },
        "final_norm": (D,),
        "lm_head": (D, V),
        "embed": (V, D),
    }


def init(key, d: Dims):
    shapes = param_shapes(d)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        z = jax.random.normal(k, shape, jnp.float32)
        if "ln" in path or "norm" in path:
            w = 1.0 + 0.1 * z                      # norm gains near 1
        elif "embed" in path or "lm_head" in path:
            w = 0.02 * z
        else:
            w = z / np.sqrt(shape[-2])             # fan-in scaling
        out.append(w)
    return jax.tree.unflatten(tree, out)


@functools.cache
def param_count(d: Dims, *, embed: bool = True) -> int:
    n = sum(int(np.prod(s)) for s in
            jax.tree.leaves(param_shapes(d), is_leaf=lambda s: isinstance(s, tuple)))
    return n if embed else n - d.vocab * d.d_model


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def hidden(params, dims: Dims, tokens, *, bits: Optional[int] = None, q_chunk: int = 256):
    """Final-normed hidden states ``(S, d_model)`` of ``tokens`` (S,)."""

    s = tokens.shape[0]
    q_chunk = min(q_chunk, s)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        n1 = rms_norm(x, p["ln1"], dims.norm_eps)
        q = _mm(n1, a["wq"], bits).reshape(s, dims.n_heads, dims.d_head)
        k = _mm(n1, a["wk"], bits).reshape(s, dims.n_kv_heads, dims.d_head)
        v = _mm(n1, a["wv"], bits).reshape(s, dims.n_kv_heads, dims.d_head)
        o = attention(rope(q, dims.rope_theta), rope(k, dims.rope_theta), v, q_chunk=q_chunk)
        x = x + _mm(o, a["wo"], bits)
        m = p["mlp"]
        n2 = rms_norm(x, p["ln2"], dims.norm_eps)
        x = x + _mm(jax.nn.silu(_mm(n2, m["w1"], bits)) * _mm(n2, m["w3"], bits), m["w2"], bits)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)


def logits(params, h, *, bits: Optional[int] = None):
    return _mm(h, params["lm_head"], bits)


# ---------------------------------------------------------------------------
# The work
# ---------------------------------------------------------------------------


def step_gemms(d: Dims, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every GEMM of one decode step over ``rows`` rows."""

    hq, hkv = d.n_heads * d.d_head, d.n_kv_heads * d.d_head
    layer = [(rows, d.d_model, hq), (rows, d.d_model, hkv), (rows, d.d_model, hkv),
             (rows, hq, d.d_model), (rows, d.d_model, d.d_ff), (rows, d.d_model, d.d_ff),
             (rows, d.d_ff, d.d_model)]
    return layer * d.n_layers + [(rows, d.d_model, d.vocab)]


def attn_roofline_s(d: Dims, contexts, peak_flops: float, peak_bw: float) -> float:
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
