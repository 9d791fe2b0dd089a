"""Fault-tolerant fleet layer: scheduling, injection, exactness.

The ISSUE-10 acceptance criteria, as tests:

  * **fault matrix** — under each injectable fault (engine stall, pod
    death, admission failure, latency spike) every submitted request
    completes exactly once with tokens **bit-identical** to a fault-free
    single-engine run, including requests migrated while queued and
    requests retried after an engine death;
  * **fault injection off is free** — no plan armed means the fault
    points reduce to one module-global ``None`` check and the fleet
    never consults a plan;
  * **health hysteresis** — ``unhealthy_after`` consecutive bad ticks
    trip an engine, ``healthy_after`` good ticks restore it;
  * the engine's fleet surface (``withdraw`` / ``export_queued``) rolls
    the router's counts back so future routing reflects kept work only.

Real engines (row-local arch — greedy decode is a pure function of each
request's own prompt) prove bit-identity; the numpy ``fleetstub`` engine
covers the control-plane paths (health, deadlines, streaming)
where jit time would buy nothing.
"""

import asyncio

import numpy as np
import pytest

import jax

from fleetstub import StubEngine, stub_tokens
from repro.configs import get_config
from repro.core.asymmetric import AsymmetricMesh, DeviceClass
from repro.core.schedule import deficit_route, fleet_scheduler
from repro.distributed import sharding as SH
from repro.models import model_zoo as Z
from repro.runtime import faults
from repro.runtime.fleet import Fleet
from repro.runtime.serving import ServingEngine

GEN_LEN = 6
SEQ_CAP = 32


@pytest.fixture(scope="module")
def zoo():
    cfg = get_config("internlm2-1.8b").reduced()
    SH.use_mesh_for_activations(None)
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, *, slots_per_pod=2):
    asym = AsymmetricMesh(
        [DeviceClass("only", chips_per_pod=1)], strategy="ca-das", batch_tile=1
    )
    return ServingEngine(
        cfg, params, asym, seq_cap=SEQ_CAP, slots_per_pod=slots_per_pod,
        class_sharded="off",
    )


def _requests(cfg, n=10):
    rng = np.random.default_rng(3)
    return [
        rng.integers(0, cfg.vocab, (4 if i % 2 else 8,), dtype=np.int32)
        for i in range(n)
    ]


def _run(fleet, prompts, plan=None):
    with faults.injected(plan) if plan else _null():
        for p in prompts:
            fleet.submit(p, GEN_LEN)
        fleet.run()
    return {c.rid: np.asarray(c.tokens) for c in fleet.completions}


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def reference(zoo):
    """Fault-free single-engine tokens: the exactness yardstick."""

    cfg, params = zoo
    fleet = Fleet([_engine(cfg, params)])
    return _run(fleet, _requests(cfg))


# ---------------------------------------------------------------------------
# The fault matrix: exactly-once, bit-identical under every fault type
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("point", sorted(faults.FAULT_POINTS))
def test_fault_matrix_bit_identical(zoo, reference, point):
    cfg, params = zoo
    prompts = _requests(cfg)
    plan = faults.FaultPlan(
        [faults.FaultEvent(point=point, engine=0, tick=2, duration=3)]
    )
    fleet = Fleet([_engine(cfg, params) for _ in range(2)])
    toks = _run(fleet, prompts, plan)

    assert fleet.stats.submitted == len(prompts)
    assert fleet.stats.completed == len(prompts)
    assert fleet.stats.duplicate_completions == 0
    assert set(toks) == set(reference)
    for rid in reference:
        assert np.array_equal(toks[rid], reference[rid]), (
            f"{point}: tokens diverged from fault-free run for rid={rid}"
        )
    if point == "pod_death":
        assert fleet.stats.engine_kills == 1
        assert sum(fleet._alive) == 1
        # The dead engine's queue migrated and its in-flight retried.
        assert fleet.stats.migrated > 0
        assert fleet.stats.retries > 0
    if point == "engine_stall":
        assert fleet.stats.stalled_ticks == 3
    if point == "admission_fail":
        assert fleet.stats.admission_faults == 3
    if point == "latency_spike":
        assert fleet.stats.latency_spikes == 3
        assert fleet.stats.migrated == 0  # perf fault, not a correctness one


def test_nofault_fleet_bit_identical(zoo, reference):
    cfg, params = zoo
    fleet = Fleet([_engine(cfg, params) for _ in range(2)])
    toks = _run(fleet, _requests(cfg))
    assert fleet.stats.completed == fleet.stats.submitted
    for rid in reference:
        assert np.array_equal(toks[rid], reference[rid])
    # Both engines actually served (the scheduler split the trace).
    assert all(e.stats.tokens > 0 for e in fleet.engines)


def test_queued_requests_migrate_off_dead_engine(zoo, reference):
    """Tiny slot tables force deep queues; the kill must migrate them."""

    cfg, params = zoo
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="pod_death", engine=0, tick=2)]
    )
    fleet = Fleet([_engine(cfg, params, slots_per_pod=1) for _ in range(2)])
    toks = _run(fleet, _requests(cfg), plan)
    assert fleet.stats.completed == fleet.stats.submitted
    assert fleet.stats.migrated > 0
    for rid in reference:
        assert np.array_equal(toks[rid], reference[rid])
    # Everything finished on the survivor.
    assert all(c.engine == 1 for c in fleet.completions
               if c.attempts > 1 or c.migrations > 0)


# ---------------------------------------------------------------------------
# Fault plumbing: off is free, arming, validation, seeded plans
# ---------------------------------------------------------------------------


def test_fault_injection_off_is_free():
    # The off path is one module-global None check, mirroring trace._BUFFER.
    assert faults._PLAN is None
    assert not faults.armed()
    assert faults.fault_active("pod_death", engine=0, tick=1) is None


def test_arm_disarm_and_injected_restores():
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="engine_stall", engine=0, tick=1)]
    )
    faults.arm(plan)
    try:
        assert faults.armed()
        assert faults.fault_active("engine_stall", engine=0, tick=1) is not None
        assert faults.fault_active("engine_stall", engine=1, tick=1) is None
        assert faults.fault_active("pod_death", engine=0, tick=1) is None
    finally:
        faults.disarm()
    assert not faults.armed()
    with pytest.raises(RuntimeError):
        with faults.injected(plan):
            assert faults.armed()
            raise RuntimeError("boom")
    assert not faults.armed()  # the context disarms on exceptions too


def test_fault_validation():
    with pytest.raises(ValueError):
        faults.validate_point("not_a_point")  # repro: noqa=RPR006 -- negative test: validation must reject drift
    with pytest.raises(ValueError):
        faults.FaultEvent(point="not_a_point", engine=0, tick=1)  # repro: noqa=RPR006 -- negative test: validation must reject drift
    with pytest.raises(ValueError):
        faults.FaultEvent(point="engine_stall", engine=-1, tick=1)
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="engine_stall", engine=0, tick=1)]
    )
    with pytest.raises(ValueError):
        plan.active("not_a_point", 0, 1)


def test_seeded_plan_deterministic_and_keeps_survivor():
    a = faults.FaultPlan.seeded(11, n_engines=3, horizon=20, n_events=6)
    b = faults.FaultPlan.seeded(11, n_engines=3, horizon=20, n_events=6)
    assert a.events == b.events
    assert len(a.events) <= 6
    killed = {e.engine for e in a.events if e.point == "pod_death"}
    assert len(killed) < 3  # at least one engine survives every seeded plan
    for ev in a.events:
        assert ev.point in faults.FAULT_POINTS
        assert 0 <= ev.engine < 3


def test_pod_death_is_permanent():
    ev = faults.FaultEvent(point="pod_death", engine=0, tick=5)
    assert not ev.covers(4)
    assert ev.covers(5) and ev.covers(500)
    stall = faults.FaultEvent(point="engine_stall", engine=0, tick=5, duration=2)
    assert stall.covers(5) and stall.covers(6) and not stall.covers(7)


# ---------------------------------------------------------------------------
# Scheduling adapter: deficit routing over DAS shares
# ---------------------------------------------------------------------------


def test_deficit_route_tracks_weights():
    routed = [0, 0]
    for _ in range(30):
        routed[deficit_route([2.0, 1.0], routed)] += 1
    assert routed == [20, 10]


def test_deficit_route_validation():
    with pytest.raises(ValueError):
        deficit_route([0.0, 0.0], [0, 0])
    with pytest.raises(ValueError):
        deficit_route([1.0], [0, 0])
    with pytest.raises(ValueError):
        fleet_scheduler([])
    with pytest.raises(ValueError):
        fleet_scheduler([1.0, 0.0])


def test_fleet_routes_proportional_to_throughput():
    fast, slow = StubEngine(n_slots=8, speed=3.0), StubEngine(n_slots=8, speed=1.0)
    fleet = Fleet([fast, slow])
    for i in range(40):
        fleet.submit(np.asarray([i], np.int32), 2)
    assert abs(fleet._routed[0] - 30) <= 2  # ~3:1 split by calibrated tps


# ---------------------------------------------------------------------------
# Control plane on the stub: health, deadlines, streaming
# ---------------------------------------------------------------------------


def _stub_fleet(n=2, **kw):
    return Fleet([StubEngine(n_slots=2) for _ in range(n)], **kw)


def test_health_hysteresis_trip_and_recover():
    fleet = _stub_fleet(unhealthy_after=2, healthy_after=2)
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="engine_stall", engine=0, tick=1, duration=3)]
    )
    with faults.injected(plan):
        for i in range(12):
            fleet.submit(np.asarray([i], np.int32), 2)
        for _ in range(8):
            fleet.tick()
        assert fleet.stats.health_trips == 1
        assert fleet.stats.health_recoveries == 1
        assert fleet.health()["unhealthy"] == []
        fleet.run()
    assert fleet.stats.completed == fleet.stats.submitted
    assert fleet.stats.duplicate_completions == 0


def test_deadline_requeues_stranded_request():
    # Skew routing hard onto engine 0 (1 slot), so the third request
    # queues behind a full table and its deadline moves it to engine 1.
    fleet = Fleet(
        [StubEngine(n_slots=1), StubEngine(n_slots=1)],
        rel_throughput=[1000.0, 1.0],
    )
    for i in range(3):
        fleet.submit(np.asarray([10 + i], np.int32), 8, deadline=1)
    for _ in range(4):
        fleet.tick()
    assert fleet.stats.deadline_requeues >= 1
    fleet.run()
    assert fleet.stats.completed == 3
    assert fleet.stats.duplicate_completions == 0


def test_withdraw_and_export_rollback_router_counts(zoo):
    cfg, params = zoo
    eng = _engine(cfg, params)
    rids = [eng.submit(p, GEN_LEN) for p in _requests(cfg, n=4)]
    routed_before = list(eng._routed)
    req = eng.withdraw(rids[1])
    assert req is not None and req.rid == rids[1]
    assert eng.withdraw(rids[1]) is None  # gone means gone
    assert sum(eng._routed) == sum(routed_before) - 1
    rest = eng.export_queued()
    assert [r.rid for r in rest] == [rids[0], rids[2], rids[3]]
    assert all(len(q) == 0 for q in eng.queues)
    assert sum(eng._routed) == 0


def test_stub_engine_matches_contract():
    eng = StubEngine(n_slots=2)
    prompt = np.asarray([5, 6, 7], np.int32)
    eng.submit(prompt, 4)
    eng.admit()
    while not eng.completions:
        eng.step()
    c = eng.completions[0]
    assert np.array_equal(c.tokens[:3], prompt)
    assert np.array_equal(c.tokens[3:], stub_tokens(prompt, 4))


# ---------------------------------------------------------------------------
# Async surface: streaming across the tick loop
# ---------------------------------------------------------------------------


def test_stream_yields_generated_tokens():
    async def main():
        fleet = _stub_fleet()
        prompt = np.asarray([3, 1, 4], np.int32)
        rid = await fleet.submit_async(prompt, 5)
        chunks = []

        async def consume():
            async for ch in fleet.stream(rid):
                chunks.append(np.asarray(ch))

        task = asyncio.ensure_future(consume())
        await fleet.run_async()
        await task
        got = np.concatenate(chunks)
        assert np.array_equal(got, stub_tokens(prompt, 5))
        done = await fleet.complete_async(rid)
        assert done.rid == rid

    asyncio.run(main())


def test_stream_consistent_across_engine_kill():
    async def main():
        plan = faults.FaultPlan(
            [faults.FaultEvent(point="pod_death", engine=0, tick=2)]
        )
        fleet = Fleet(
            [StubEngine(n_slots=1), StubEngine(n_slots=1)],
            rel_throughput=[1000.0, 1.0],  # pin the request to the victim
        )
        prompt = np.asarray([9, 9], np.int32)
        with faults.injected(plan):
            rid = await fleet.submit_async(prompt, 6)
            chunks = []

            async def consume():
                async for ch in fleet.stream(rid):
                    chunks.append(np.asarray(ch))

            task = asyncio.ensure_future(consume())
            await fleet.run_async()
            await task
        # The retry reproduces the identical prefix, so the stitched
        # stream is exactly the generated tokens, no repeats or holes.
        got = np.concatenate(chunks)
        assert np.array_equal(got, stub_tokens(prompt, 6))

    asyncio.run(main())


def test_all_engines_dead_raises():
    # Conservation failures are loud: losing the last engine with work
    # pending raises (from the kill's forced re-place or the run loop).
    fleet = Fleet([StubEngine(n_slots=1)])
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="pod_death", engine=0, tick=1)]
    )
    with faults.injected(plan):
        fleet.submit(np.asarray([1], np.int32), 4)
        with pytest.raises(RuntimeError, match="engine"):
            fleet.run()
