"""The check catches a broken timed path.

Each test drives a whole run of the throwaway cell on the CPU (the look
for a chip is the entry point's, and is skipped), with the engine's
decode step broken underneath, and sees ``correct`` come out false."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, spec
from chipbench.tests import tiny


@pytest.fixture(autouse=True)
def slots_alone(monkeypatch):
    """The sample as small as the check makes it: the longest request and
    one of every slot, no floor of tokens to widen it."""

    monkeypatch.setattr(harness, "CHECK_MIN_TOKENS", 1)


def _run(tmp_path, hook, paged="off"):
    root = tiny.make(tmp_path, paged=paged)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    return harness.run(cell, seed=17, seconds=2.0, trace=False, root=root,
                       t_start=time.perf_counter(), engine_hook=hook)


def _altered_token_in(slot_of_engine):
    def fault(eng):
        step = eng._step
        slot = slot_of_engine(eng)

        def broken(params, batch, state, pos):
            nxt, state = step(params, batch, state, pos)
            return nxt.at[slot, 0].set((nxt[slot, 0] + 1) % eng.cfg.vocab), state

        eng._step = broken

    return fault


# Every token of one slot changed where the decode step produces it: the
# first slot of pod 0, and the first slot of pod 1 (the little class, on
# its own GEMM path).
altered_token = _altered_token_in(lambda eng: 0)
altered_token.__name__ = "altered_token"
altered_token_pod1 = _altered_token_in(lambda eng: eng.c_max)
altered_token_pod1.__name__ = "altered_token_pod1"


def state_unchanged(eng):
    """The decode step returns the state it was given: no key or value
    of a decoded token is ever written."""

    step = eng._step

    def broken(params, batch, state, pos):
        kept = jax.tree.map(jnp.copy, state)
        nxt, _ = step(params, batch, state, pos)
        return nxt, kept

    eng._step = broken


@pytest.mark.parametrize("paged", ["off", "on"])
def test_sound_run_is_correct(tmp_path, paged):
    out = _run(tmp_path, None, paged)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [altered_token, altered_token_pod1, state_unchanged],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("paged", ["off", "on"])
def test_fault_is_not_correct(tmp_path, fault, paged):
    out = _run(tmp_path, fault, paged)
    c = out["checks"]["widest_gap"]
    assert not out["correct"]
    assert c["value"] > c["limit"]
