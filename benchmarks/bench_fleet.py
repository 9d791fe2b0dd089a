"""Fleet benchmark: bursty mixed-length trace, with and without a kill.

Exercises the fault-tolerant fleet layer (ISSUE 10 / ROADMAP
"Multi-engine fleet") on the **modeled clock** — the deterministic
time base every engine accumulates per decode step
(``stats.modeled_decode_s``), host-independent by construction:

  * **no-fault lane** — the N-engine fleet against a single engine on
    the identical bursty arrival trace; engines tick in lockstep (they
    would run concurrently in production), so the fleet's modeled span
    is the *max* over engines and the speedup gate is real parallelism,
    not bookkeeping.
  * **kill lane** — the same trace with a seeded ``pod_death`` injected
    at tick K through :mod:`repro.runtime.faults`; the bench measures
    the surviving engine's post-kill throughput against its standalone
    (single-engine) rate — *recovered* means the fleet redistributed the
    dead engine's queued work and kept the survivor saturated.

Every lane asserts the exactness contract while it is here: each
submitted request completes exactly once (``completed == submitted``,
zero duplicates) with tokens bit-identical across the single-engine,
no-fault-fleet, and kill-fleet runs.  Results land in
``artifacts/bench/BENCH_fleet.json``; CI smoke-runs this module with
``--check`` (no-fault speedup >= 1.5x, kill lane recovered).

Run::

    PYTHONPATH=src python -m benchmarks.bench_fleet [--check]
"""

from __future__ import annotations

import argparse
import contextlib

import jax
import numpy as np

from benchmarks.harness import Row, write_json
from repro.configs import get_config
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.distributed import sharding as SH
from repro.models import model_zoo as Z
from repro.runtime import faults

# Bursty arrival trace: BURSTS arrivals land at once every GAP ticks.
# Prompt lengths cycle over a small set so the compile-key space stays
# bounded (each distinct length compiles one prefill per engine).
PROMPT_LENS = (4, 8, 12)
KILL_TICK = 6


def _mk_engine(cfg, params, seq_cap, slots_per_pod):
    from repro.runtime.serving import ServingEngine

    asym = AsymmetricMesh(
        biglittle_classes(chips_per_pod=1), strategy="ca-das", batch_tile=1
    )
    return ServingEngine(
        cfg, params, asym, seq_cap=seq_cap, slots_per_pod=slots_per_pod,
        class_sharded="off",
    )


def make_trace(cfg, *, bursts=3, burst_size=8, gap=4, seed=7):
    """``[(arrival_tick, prompt), ...]`` — identical for every lane."""

    rng = np.random.default_rng(seed)
    trace = []
    for b in range(bursts):
        for _ in range(burst_size):
            plen = int(rng.choice(PROMPT_LENS))
            prompt = rng.integers(0, cfg.vocab, (plen,), dtype=np.int32)
            trace.append((b * gap, prompt))
    return trace


def drive(fleet, trace, gen_len, *, plan=None, snap_tick=None, snap_engine=None):
    """Submit per the arrival trace and tick the fleet to completion.

    Returns ``(tokens_by_rid, postkill)`` where ``postkill`` is the
    ``(tokens, modeled_s)`` delta of ``snap_engine`` from just before
    internal tick ``snap_tick`` (the tick the plan's kill fires on) to
    the end of the run — its post-kill throughput numerator/denominator.
    """

    ctx = faults.injected(plan) if plan is not None else contextlib.nullcontext()
    snap = None
    with ctx:
        i, tick = 0, 0
        while True:
            while i < len(trace) and trace[i][0] <= tick:
                fleet.submit(trace[i][1], gen_len)
                i += 1
            if i >= len(trace) and len(fleet.completions) == len(trace):
                break
            # tick() moves the fleet to internal tick ``tick + 1`` — so a
            # snapshot taken here, at ``tick == snap_tick - 1``, brackets
            # everything from the kill tick onward.
            if snap_tick is not None and tick == snap_tick - 1:
                e = fleet.engines[snap_engine]
                snap = (e.stats.tokens, e.stats.modeled_decode_s)
            fleet.tick()
            tick += 1
            if tick > 10_000:
                raise RuntimeError("bench_fleet: fleet failed to converge")
    postkill = None
    if snap is not None:
        e = fleet.engines[snap_engine]
        postkill = (e.stats.tokens - snap[0], e.stats.modeled_decode_s - snap[1])
    toks = {c.rid: np.asarray(c.tokens) for c in fleet.completions}
    return toks, postkill


def _fleet_tps(fleet):
    """Tokens per modeled second with engines running in lockstep: the
    span is the slowest (max) engine's modeled time."""

    tokens = sum(e.stats.tokens for e in fleet.engines)
    span = max(e.stats.modeled_decode_s for e in fleet.engines)
    return tokens / span if span > 0 else 0.0


def run(arch: str = "internlm2-1.8b", n_engines: int = 2, gen_len: int = 8,
        slots_per_pod: int = 2, seq_cap: int = 32) -> list[Row]:
    """Three lanes on one trace; writes ``BENCH_fleet.json``."""

    from repro.runtime.fleet import Fleet

    cfg = get_config(arch).reduced()
    SH.use_mesh_for_activations(None)
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    trace = make_trace(cfg)

    # Lane 1: single engine, the reference for tokens and throughput.
    single = Fleet([_mk_engine(cfg, params, seq_cap, slots_per_pod)])
    single_toks, _ = drive(single, trace, gen_len)
    single_tps = _fleet_tps(single)

    # Lane 2: the no-fault fleet.
    fleet = Fleet(
        [_mk_engine(cfg, params, seq_cap, slots_per_pod)
         for _ in range(n_engines)]
    )
    fleet_toks, _ = drive(fleet, trace, gen_len)
    fleet_tps = _fleet_tps(fleet)
    speedup = fleet_tps / single_tps if single_tps else 0.0

    # Lane 3: same fleet shape, engine 0 killed at tick KILL_TICK.
    plan = faults.FaultPlan(
        [faults.FaultEvent(point="pod_death", engine=0, tick=KILL_TICK)]
    )
    kfleet = Fleet(
        [_mk_engine(cfg, params, seq_cap, slots_per_pod)
         for _ in range(n_engines)]
    )
    survivor = 1
    kill_toks, postkill = drive(
        kfleet, trace, gen_len,
        plan=plan, snap_tick=KILL_TICK, snap_engine=survivor,
    )
    pk_tokens, pk_s = postkill
    postkill_tps = pk_tokens / pk_s if pk_s > 0 else 0.0
    # Recovered: after the kill the survivor sustains at least 80% of
    # what it delivers standing alone on this whole trace — i.e. the
    # fleet actually moved the dead engine's work over and kept the
    # survivor saturated rather than stranding requests.
    recovered = postkill_tps >= 0.8 * single_tps

    # Exactness across all three lanes: same rids, bit-identical tokens.
    for name, toks in (("fleet", fleet_toks), ("kill", kill_toks)):
        assert set(toks) == set(single_toks), f"{name}: request set diverged"
        for rid in single_toks:
            assert np.array_equal(toks[rid], single_toks[rid]), (
                f"{name}: tokens diverged from single-engine run for "
                f"rid={rid}"
            )
    for f in (single, fleet, kfleet):
        assert f.stats.completed == f.stats.submitted, (
            f"conservation: {f.stats.completed}/{f.stats.submitted}"
        )
        assert f.stats.duplicate_completions == 0

    record = {
        "arch": cfg.name,
        "n_engines": n_engines,
        "requests": len(trace),
        "gen_len": gen_len,
        "slots_per_pod": slots_per_pod,
        "kill_tick": KILL_TICK,
        "single": {"modeled_tokens_per_s": round(single_tps, 1)},
        "fleet": {
            "modeled_tokens_per_s": round(fleet_tps, 1),
            "speedup_vs_single": round(speedup, 3),
            **{k: v for k, v in fleet.stats.snapshot().items()
               if k in ("submitted", "completed", "migrated", "retries",
                        "duplicate_completions", "ticks")},
        },
        "kill": {
            "postkill_tokens_per_s": round(postkill_tps, 1),
            "recovered": recovered,
            **{k: v for k, v in kfleet.stats.snapshot().items()
               if k in ("submitted", "completed", "migrated", "retries",
                        "duplicate_completions", "engine_kills", "ticks")},
        },
        "tokens_identical": True,
    }
    rows = [
        Row("fleet_single_engine", 0.0,
            f"modeled_tokens_per_s={single_tps:.1f}"),
        Row("fleet_nofault", 0.0,
            f"modeled_tokens_per_s={fleet_tps:.1f} "
            f"speedup_vs_single={speedup:.3f} "
            f"submitted={fleet.stats.submitted} "
            f"completed={fleet.stats.completed} "
            f"duplicates={fleet.stats.duplicate_completions}"),
        Row("fleet_engine_kill", 0.0,
            f"postkill_tokens_per_s={postkill_tps:.1f} "
            f"recovered={recovered} "
            f"submitted={kfleet.stats.submitted} "
            f"completed={kfleet.stats.completed} "
            f"duplicates={kfleet.stats.duplicate_completions} "
            f"migrated={kfleet.stats.migrated} "
            f"retries={kfleet.stats.retries}"),
    ]
    path = write_json("BENCH_fleet.json", [record], bench="fleet",
                      arch=cfg.name)
    print(f"wrote {path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--engines", type=int, default=2)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--slots-per-pod", type=int, default=2)
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the no-fault fleet beats the "
                         "single engine by >= 1.5x on the modeled clock and "
                         "the kill lane recovers")
    args = ap.parse_args()
    rows = run(args.arch, args.engines, args.gen_len, args.slots_per_pod)
    for r in rows:
        print(f"{r.name},{r.us_per_call:.1f},{r.derived}")
    if args.check:
        nofault = next(r for r in rows if r.name == "fleet_nofault")
        speed = float(
            nofault.derived.split("speedup_vs_single=")[1].split()[0]
        )
        if speed < 1.5:
            raise SystemExit(f"fleet speedup below 1.5x: {speed}")
        kill = next(r for r in rows if r.name == "fleet_engine_kill")
        if "recovered=True" not in kill.derived:
            raise SystemExit(
                "kill lane did not recover: " + kill.derived
            )


if __name__ == "__main__":
    main()
