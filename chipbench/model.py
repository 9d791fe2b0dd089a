"""What every model family shares: the family's name, the engine geometry,
and the weights made from the seed.

The configuration file (``configs/<name>.json``) gives the model's sizes
under the published ``config.json`` key names, as they are run.  Its
``chipbench`` entry names the model family (``family``: the file
``families/<family>.py``, which holds the parameter tree, the plain
reference and the work counts), the registry architecture the engine
builds (``arch``) and the engine geometry.  The weights are the
benchmark's own: one jitted call on the device draws every leaf of the
family's tree from the seed, in the layout and the dtype (float32) the
engine serves, so neither the engine nor the reference makes or changes
them.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

# The family of a configuration whose file names none: the first
# configurations' files predate the key.
DEFAULT_FAMILY = "dense"


def family_name(c: dict) -> str:
    return c["chipbench"].get("family", DEFAULT_FAMILY)


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


def key_for(seed: int, stream: str):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""

    words = np.random.SeedSequence([int(seed), *map(ord, stream)]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])), int(words[1]))


def make_params(seed: int, family, dims):
    """Every weight of ``family``'s tree at ``dims``, float32, made on the
    device in one jitted call."""

    params = jax.jit(family.init, static_argnums=1)(key_for(seed, "weights"), dims)
    jax.block_until_ready(params)
    return params
