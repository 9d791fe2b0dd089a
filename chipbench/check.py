"""What decides ``correct``: served tokens against the plain reference.

After the window closes, a sample of the requests that were served (drawn
from the seed, with the longest and one request of every slot that served
in it) is run once through the
plain reference of the cell's model family (``families/<family>.py``,
float32 at ``highest``), each request as its
prompt followed by the tokens it was served.  At every served position the
number read is the *gap*: how far the served token's reference logit lies
below the reference's best logit there.  The engine decodes greedily, so a
served token is the argmax of the engine's own logits and its gap is
rounding; the widest gap over the sample is compared with the cell's limit.

The control is put in the program's place: at each position of the same
sample, the token that the reference computed with its weights and matmul
inputs rounded to float8 e4m3's mantissa (``bits=3``) puts first, the
precision below the bfloat16 the engine computes in, is read as if served,
and ``verdict`` judges its widest gap as it judges the program's.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Positions of the LM head evaluated at once: (HEAD_CHUNK, vocab) float32.
HEAD_CHUNK = 256


@dataclasses.dataclass(frozen=True)
class Served:
    rid: int
    prompt: np.ndarray      # (P,) int32
    tokens: np.ndarray      # (n,) int32 served after the prompt
    slot: int = -1          # the slot-table row that served it


def sample(served: list[Served], rng, *, min_tokens: int, min_requests: int) -> list[Served]:
    """The longest request, then one request of every other slot that
    served (drawn by ``rng``), then others in a seeded order, until the
    sample holds ``min_tokens`` served tokens and ``min_requests`` requests
    (or every request)."""

    if not served:
        return []
    longest, *others = sorted(served, key=lambda s: (-len(s.tokens), s.rid))
    shuffled = [others[i] for i in rng.permutation(len(others))]
    out = [longest]
    covered = {longest.slot}
    for s in shuffled:
        if s.slot not in covered:
            covered.add(s.slot)
            out.append(s)
    taken = {s.rid for s in out}
    rest = [s for s in shuffled if s.rid not in taken]
    while rest and (sum(len(s.tokens) for s in out) < min_tokens or len(out) < min_requests):
        out.append(rest.pop())
    return out


def verdict(sample: list[Served], widest_gap: float, compiles: int, limits: dict) -> bool:
    """``correct``: a sample to compare, every compared token within the
    limit of the reference's best, and nothing compiled in the window."""

    return bool(sample) and widest_gap <= limits["widest_gap"] and compiles == 0


@functools.partial(jax.jit, static_argnames=("family", "dims", "bits"))
def _gaps(params, family, dims, tokens, idx, served, valid, bits: Optional[int]):
    """Per position of ``idx``: the reference's gap of ``served`` and, with
    ``bits``, the gap of the token the rounded reference puts first."""

    with jax.default_matmul_precision("highest"):
        h = family.hidden(params, dims, tokens)[idx]
        hc = family.hidden(params, dims, tokens, bits=bits)[idx] if bits is not None else h
        n = idx.shape[0]

        def chunk(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * HEAD_CHUNK, HEAD_CHUNK)  # noqa: E731
            lg = family.logits(params, sl(h))
            best = lg.max(-1)
            got = jnp.take_along_axis(lg, sl(served)[:, None], -1)[:, 0]
            pick = family.logits(params, sl(hc), bits=bits).argmax(-1) if bits is not None else lg.argmax(-1)
            ctrl = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
            return best - got, best - ctrl, lg.argmax(-1) == sl(served)

        gap, ctrl, agree = jax.lax.map(chunk, jnp.arange(n // HEAD_CHUNK))
        flat = lambda a: a.reshape(n)  # noqa: E731
        v = valid
        return (jnp.where(v, flat(gap), 0.0).max(), jnp.where(v, flat(ctrl), 0.0).max(),
                jnp.where(v, flat(agree), False).sum())


def gaps(params, family, dims, lane: int, sample: list[Served], *,
         bits: Optional[int] = None) -> dict:
    """Widest gap over ``sample`` by ``family``'s reference: of the served
    tokens, and (``bits``) of the rounded reference's first choices; with
    the argmax agreement."""

    widest, widest_ctrl, agree, n = 0.0, 0.0, 0, 0
    n_idx = -(-lane // HEAD_CHUNK) * HEAD_CHUNK
    for s in sample:
        seq = np.concatenate([s.prompt, s.tokens]).astype(np.int32)
        if len(seq) > lane:
            raise ValueError(f"request {s.rid}: {len(seq)} tokens exceed the lane {lane}")
        k = len(s.tokens)
        tokens = np.zeros(lane, np.int32)
        tokens[: len(seq)] = seq
        # The logits at position p predict token p + 1: served token j sits
        # at len(prompt) + j and is predicted at len(prompt) + j - 1.
        idx = np.zeros(n_idx, np.int32)
        idx[:k] = len(s.prompt) - 1 + np.arange(k)
        served = np.zeros(n_idx, np.int32)
        served[:k] = s.tokens
        valid = np.arange(n_idx) < k
        g, c, a = _gaps(params, family, dims, jnp.asarray(tokens), jnp.asarray(idx),
                        jnp.asarray(served), jnp.asarray(valid), bits)
        widest, widest_ctrl = max(widest, float(g)), max(widest_ctrl, float(c))
        agree += int(a)
        n += k
    out = {"widest_gap": widest, "tokens": n, "requests": len(sample),
           "argmax_agreement": agree / max(n, 1)}
    if bits is not None:
        out["control_widest_gap"] = widest_ctrl
    return out
