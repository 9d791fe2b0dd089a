"""A numpy stand-in for :class:`repro.runtime.serving.ServingEngine`.

Implements exactly the engine surface :class:`repro.runtime.fleet.Fleet`
touches — slot table, class queues, submit/admit/step, the fleet
drain/export/health methods — with a deterministic token function in
place of the jitted decode: generated token ``k`` of a request is a pure
function of its prompt, so bit-identity across engines, migrations, and
retries holds for the stub exactly as greedy decode makes it hold for
the real engine.  This keeps the hypothesis conservation property fast
enough to explore hundreds of seeded fault plans; the real-engine
bit-identity matrix lives in ``test_fleet.py``.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from repro.runtime.serving import Request


def stub_tokens(prompt: np.ndarray, n: int) -> np.ndarray:
    """The stub's "greedy decode": ``n`` generated tokens, a pure
    function of the prompt (the property every fleet exactness test
    leans on)."""

    seed = int(np.asarray(prompt, np.int64).sum()) % 997
    return np.asarray([(seed * 7 + k * 13) % 997 for k in range(n)], np.int32)


@dataclasses.dataclass
class StubCompletion:
    rid: int
    tokens: np.ndarray
    prompt_len: int
    stop: str = "budget"


class _StubStats:
    def __init__(self):
        self.tokens = 0
        self.modeled_decode_s = 0.0


class StubEngine:
    """Slot-table serving semantics without jax: one class queue,
    ``speed`` generated tokens per slot per step on a modeled clock of
    ``1/speed`` seconds per step (so calibrated tps == active slots ×
    speed, like the real engine's row-rate calibration)."""

    def __init__(self, n_slots: int = 2, speed: float = 1.0):
        if n_slots < 1 or speed <= 0:
            raise ValueError("need n_slots >= 1 and speed > 0")
        self.n_slots = int(n_slots)
        self.speed = float(speed)
        self.queues = [collections.deque()]
        self.slot_rid = np.full(self.n_slots, -1, np.int64)
        self._slot_req: dict[int, Request] = {}
        self._slot_toks: dict[int, list[int]] = {}
        self._slot_remaining: dict[int, int] = {}
        self._next_rid = 0
        self.completions: list[StubCompletion] = []
        self.stats = _StubStats()

    # -- the engine API the fleet drives ----------------------------------

    def submit(self, prompt, max_new_tokens: int) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queues[0].append(
            Request(
                rid=rid,
                prompt=np.asarray(prompt, np.int32).reshape(-1),
                max_new_tokens=int(max_new_tokens),
            )
        )
        return rid

    def admit(self) -> int:
        admitted = 0
        for slot in np.nonzero(self.slot_rid < 0)[0]:
            if not self.queues[0]:
                break
            req = self.queues[0].popleft()
            slot = int(slot)
            self.slot_rid[slot] = req.rid
            self._slot_req[slot] = req
            self._slot_toks[slot] = []
            self._slot_remaining[slot] = req.max_new_tokens
            admitted += 1
        return admitted

    def step(self) -> int:
        active = np.nonzero(self.slot_rid >= 0)[0]
        if len(active) == 0:
            return 0
        for slot in active:
            slot = int(slot)
            req = self._slot_req[slot]
            k = len(self._slot_toks[slot])
            self._slot_toks[slot].append(
                int(stub_tokens(req.prompt, k + 1)[k])
            )
            self._slot_remaining[slot] -= 1
            if self._slot_remaining[slot] == 0:
                self._retire(slot)
        self.stats.tokens += len(active)
        self.stats.modeled_decode_s += 1.0 / self.speed
        return len(active)

    def _retire(self, slot: int) -> None:
        req = self._slot_req.pop(slot)
        toks = np.asarray(self._slot_toks.pop(slot), np.int32)
        del self._slot_remaining[slot]
        self.slot_rid[slot] = -1
        self.completions.append(
            StubCompletion(
                rid=req.rid,
                tokens=np.concatenate([req.prompt, toks]),
                prompt_len=len(req.prompt),
            )
        )

    # -- the fleet surface -------------------------------------------------

    def withdraw(self, rid: int):
        for i, req in enumerate(self.queues[0]):
            if req.rid == rid:
                del self.queues[0][i]
                return req
        return None

    def export_queued(self) -> list[Request]:
        out = list(self.queues[0])
        self.queues[0].clear()
        out.sort(key=lambda r: r.rid)
        return out

    def partial_tokens(self, rid: int):
        for slot, req in self._slot_req.items():
            if req.rid == rid:
                return np.asarray(self._slot_toks[slot], np.int32)
        return None

    def calibrated_tps(self) -> float:
        return self.speed

    def health(self) -> dict:
        return {
            "queued": len(self.queues[0]),
            "active": int((self.slot_rid >= 0).sum()),
            "slots": self.n_slots,
            "calibrated_tps": self.calibrated_tps(),
            "completed": len(self.completions),
        }
