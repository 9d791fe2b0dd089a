"""A configuration file made into the model the engine serves.

The configuration file (``configs/<name>.json``) gives the model's sizes
under the published ``config.json`` key names, as they are run.  Its
``chipbench`` entry names the registry architecture the engine builds
(``arch``) and the engine geometry.  The weights are the benchmark's own:
one jitted call on the device draws every leaf from the seed, in the
layout and the dtype (float32) the engine serves, so neither the engine
nor the reference makes or changes them.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


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

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
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


@dataclasses.dataclass(frozen=True)
class Geometry:
    pods: int
    slots_per_pod: int
    lane: int          # tokens per slot: prompt plus output
    paged: str         # "off" | "on"

    @classmethod
    def from_config(cls, c: dict) -> "Geometry":
        e = c["chipbench"]["engine"]
        return cls(pods=int(e["pods"]), slots_per_pod=int(e["slots_per_pod"]),
                   lane=int(e["lane"]), paged=e["paged"])


def arch_config(c: dict):
    """The registry's ``ArchConfig`` with every size the file states."""

    from repro.configs import get_config

    d = Dims.from_config(c)
    base = get_config(c["chipbench"]["arch"])
    if base.family != "dense" or c.get("hidden_act", "silu") != "silu":
        raise ValueError("the benchmark's reference covers dense SwiGLU decoders only")
    return dataclasses.replace(
        base, name=c["chipbench"].get("name", base.name), n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads, d_head=d.d_head,
        d_ff=d.d_ff, vocab=d.vocab, rope_theta=d.rope_theta, norm_eps=d.norm_eps,
        qkv_bias=bool(c.get("bias", c.get("attention_bias", False))),
    )


def key_for(seed: int, stream: str):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""

    words = np.random.SeedSequence([int(seed), *map(ord, stream)]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])), int(words[1]))


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


def _init(key, d: Dims):
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


def make_params(seed: int, d: Dims):
    """Every weight, float32, made on the device in one jitted call."""

    params = jax.jit(_init, static_argnums=1)(key_for(seed, "weights"), d)
    jax.block_until_ready(params)
    return params


@functools.cache
def param_count(d: Dims, *, embed: bool = True) -> int:
    n = sum(int(np.prod(s)) for s in
            jax.tree.leaves(param_shapes(d), is_leaf=lambda s: isinstance(s, tuple)))
    return n if embed else n - d.vocab * d.d_model
