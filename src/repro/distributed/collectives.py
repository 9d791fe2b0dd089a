"""Distributed-optimization collectives.

``compressed_crosspod_mean`` implements int8-quantized gradient reduction
across the ``pod`` axis with error feedback: within a pod gradients reduce
in full precision over ICI (cheap); across pods (DCI — the expensive hop)
each pod exchanges int8 blocks via all_gather and sums locally.  Wire
bytes drop 4× vs fp32 all-reduce; the quantization residual is carried to
the next step (error feedback), keeping convergence unbiased in practice
[Seide et al. 2014; Karimireddy et al. 2019].

Implemented with ``shard_map`` so the collective schedule is explicit —
the HLO the roofline parser sees contains the real int8 all-gather.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.execution import unchecked_shard_map


def quantize_int8(x: jnp.ndarray):
    """Symmetric per-tensor int8 quantization -> (q, scale)."""

    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray):
    return q.astype(jnp.float32) * scale


def _crosspod_mean_one(g, err, axis: str):
    """Per-shard body: quantize (g + err), all_gather int8, local sum."""

    gf = g.astype(jnp.float32) + err
    q, scale = quantize_int8(gf)
    new_err = gf - dequantize_int8(q, scale)
    qs = jax.lax.all_gather(q, axis)          # (n_pods, ...) int8 on the wire
    scales = jax.lax.all_gather(scale, axis)  # (n_pods,) fp32 (tiny)
    mean = jnp.tensordot(
        scales, qs.astype(jnp.float32), axes=([0], [0])
    ) / jax.lax.psum(1, axis)
    return mean.astype(g.dtype), new_err


def compressed_crosspod_mean(grads, err_tree, mesh: Mesh, *, axis: str = "pod"):
    """Mean gradients across the pod axis with int8 wire format.

    grads: pytree already reduced within pods (i.e. per-pod means);
    err_tree: error-feedback residuals (same structure, fp32).
    Returns (mean_grads, new_err_tree).
    """

    if axis not in mesh.axis_names:
        return grads, err_tree

    other = tuple(a for a in mesh.axis_names if a != axis)

    def one(g, e):
        gspec = P(*([None] * g.ndim))
        fn = unchecked_shard_map(
            functools.partial(_crosspod_mean_one, axis=axis),
            mesh=mesh,
            in_specs=(gspec, gspec),
            out_specs=(gspec, gspec),
        )
        return fn(g, e)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_tree)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (
        jax.tree.unflatten(tdef, [o[0] for o in out]),
        jax.tree.unflatten(tdef, [o[1] for o in out]),
    )


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


__all__ = [
    "quantize_int8",
    "dequantize_int8",
    "compressed_crosspod_mean",
    "init_error_feedback",
]
