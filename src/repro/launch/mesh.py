"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods × 256
chips as (pod=2, data=16, model=16) — the ``pod`` axis is the coarse
(asymmetric-schedulable) axis, ``data``/``model`` the symmetric intra-pod
axes (see DESIGN.md §2).

Functions, not module constants: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(*, model: int = 1, data: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — tests/examples."""

    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


__all__ = ["make_production_mesh", "make_host_mesh"]
