"""The one traffic generator: a mix's parameters in, a seeded plan out.

A mix (``traffic/<mix>.json``) may give three parts, each optional:

* ``sessions``: requests admitted during set-up, before the window opens,
  one per slot, placed so that every pod's slot region fills
  (``route_class`` of the pod).
* ``arrivals``: an open loop of requests during the window, ``"poisson"``
  at ``rate_per_s``.
* ``backlog``: a request that replaces each one that retires inside the
  window, in the same pod's class, so that the slots stay full.

Lengths are ``{"values": [...], "weights": [...]}`` (a categorical),
``{"lognormal": {"median", "sigma"}, "min", "max"}``, or ``"to_lane_end"``
(the output runs to the end of the slot's lane).  Token ids are uniform
over the vocabulary.

Every seed gets the same multiset of sizes and of gaps between arrivals:
the categorical is filled by exact quota, and the lognormal and
exponential draws are their distributions' quantiles at ``(i + 0.5) / n``,
shuffled.  So the work of a run does not change with the seed; only its
order and the token ids do.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    prompt: np.ndarray      # (P,) int32 token ids
    max_new: int
    at: float = 0.0         # scheduled arrival, seconds after the window opens
    route_class: Optional[int] = None


@dataclasses.dataclass
class Plan:
    sessions: list[Req]
    arrivals: list[Req]
    backlog: Optional[dict]          # the mix's backlog entry, or None
    warm_prompt_lens: list[int]      # every round-maximum prompt length possible


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any non-negative seed."""

    return np.random.default_rng([int(seed), *map(ord, stream)])


def quota(values, weights, n: int) -> list:
    """``n`` items of ``values`` in proportion to ``weights`` (largest
    remainder), in value order."""

    w = np.asarray(weights, np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [v for v, c in zip(values, counts) for _ in range(c)]


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec, n: int, rng, *, lane: int, prompt_lens=None) -> list[int]:
    """``n`` lengths from one length spec, shuffled by ``rng``."""

    if spec == "to_lane_end":
        return [lane - int(p) for p in prompt_lens]
    if "values" in spec:
        out = quota(spec["values"], spec["weights"], n)
    elif "lognormal" in spec:
        ln = spec["lognormal"]
        z = [statistics.NormalDist().inv_cdf(q) for q in quantiles(n)]
        out = [min(spec["max"], max(spec["min"], round(ln["median"] * math.exp(ln["sigma"] * x))))
               for x in z]
    else:
        raise ValueError(f"unknown length spec {spec!r}")
    out = [int(x) for x in out]
    rng.shuffle(out)
    return out


def prompt_ids(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n), dtype=np.int32)


def possible_round_maxima(spec) -> list[int]:
    """Every length a round's longest prompt can take."""

    if isinstance(spec, dict) and "values" in spec:
        return sorted(set(int(v) for v, w in zip(spec["values"], spec["weights"]) if w > 0))
    if isinstance(spec, int):
        return [spec]
    raise ValueError(f"prompt lengths must come from a fixed set, not {spec!r}")


def plan(mix: dict, seed: int, seconds: float, *, vocab: int, lane: int,
         pods: int, slots_per_pod: int) -> Plan:
    """The seeded plan of one run of ``mix``."""

    sessions: list[Req] = []
    arrivals: list[Req] = []
    warm: set[int] = set()

    s = mix.get("sessions")
    if s:
        rng = rng_for(seed, "sessions")
        n = pods * slots_per_pod
        plens = lengths(s["prompt_len"], n, rng, lane=lane)
        outs = lengths(s["output_len"], n, rng, lane=lane, prompt_lens=plens)
        for i, (p, o) in enumerate(zip(plens, outs)):
            sessions.append(Req(prompt_ids(rng, p, vocab), o, route_class=i % pods))
        # Sessions are admitted in one round: its longest prompt is the only
        # prefill length they need.
        warm.add(max(plens))

    a = mix.get("arrivals")
    if a:
        if a["kind"] != "poisson":
            raise ValueError(f"unknown arrival process {a['kind']!r}")
        rng = rng_for(seed, "arrivals")
        rate = float(a["rate_per_s"])
        n = max(1, round(rate * seconds))
        gaps = -np.log1p(-quantiles(n)) / rate
        rng.shuffle(gaps)
        plens = lengths(a["prompt_len"], n, rng, lane=lane)
        outs = lengths(a["output_len"], n, rng, lane=lane, prompt_lens=plens)
        t = 0.0
        for g, p, o in zip(gaps, plens, outs):
            t += float(g)
            arrivals.append(Req(prompt_ids(rng, p, vocab), o, at=t))
        warm.update(possible_round_maxima(a["prompt_len"]))

    b = mix.get("backlog")
    if b:
        warm.add(int(b["prompt_len"]))
    for r in sessions + arrivals:
        if len(r.prompt) + r.max_new > lane:
            raise ValueError(f"a request of {len(r.prompt)}+{r.max_new} tokens exceeds the lane {lane}")
    return Plan(sessions=sessions, arrivals=arrivals, backlog=b, warm_prompt_lens=sorted(warm))


def backlog_request(backlog: dict, rng, *, vocab: int, lane: int, route_class: int) -> Req:
    p = int(backlog["prompt_len"])
    (o,) = lengths(backlog["output_len"], 1, rng, lane=lane, prompt_lens=[p])
    return Req(prompt_ids(rng, p, vocab), o, route_class=route_class)
