"""Serving benchmark: per-step-relayout baseline vs the persistent engine.

Measures the hot-path win the slot-table engine exists for (ISSUE 5 /
ROADMAP "Serving"): the baseline emulates the pre-engine serving loop in
which **every generated token** pays

  * a host chunk-table re-derivation + pod-major re-pad of the token
    batch (``pad_requests``) and its device transfer,
  * a full decode-state copy (state threaded through jit *without*
    donation),
  * a host round-trip for the argmax feedback token,

while the persistent engine keeps requests pinned to their slots (zero
per-step relayout), donates the decode state (in-place cache update), and
keeps the token feedback resident.  Both sides decode the identical
padded batch with the identical model program; the measurement interleaves
several rounds per side and compares **medians** of steady-state tokens/s
(jit compile excluded — reported separately as ``compile_s``), so a stray
scheduler hiccup on a loaded CI box cannot flip the verdict.  The gate
runs the single-program path (no shard_map) because the 8-forced-device
shard_map barrier adds CPU thread-scheduling noise an order of magnitude
above the measured effect; ``--mixed`` adds an informational class-sharded
row.  Results land in ``artifacts/bench/BENCH_serving.json`` with the
speedup; CI smoke-runs this module and asserts the engine is strictly
faster (``--check``).

Run::

    PYTHONPATH=src python -m benchmarks.bench_serving [--check] [--mixed]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import Row, write_json
from repro.configs import get_config
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.distributed import sharding as SH
from repro.models import model_zoo as Z


def _mk_asym():
    return AsymmetricMesh(
        biglittle_classes(chips_per_pod=1), strategy="ca-das", batch_tile=1
    )


def baseline_rounds(cfg, params, prompts, gen_len, seq_cap, reps):
    """The pre-engine loop, ``reps`` rounds: relayout + undonated state per token."""

    from repro.launch.serve import pad_requests

    asym = _mk_asym()
    b, plen = prompts.shape
    layout = asym.batch_layout(b)
    padded, order0 = pad_requests(prompts, layout)
    decode = jax.jit(Z.make_decode_fn(cfg))  # NO donation: full state copy/step
    prefill = jax.jit(Z.make_prefill_fn(cfg, with_cache=True))

    compile_s, rates = 0.0, []
    for rep in range(reps):
        state = Z.init_decode_state(cfg, padded.shape[0], seq_cap)
        t0 = time.perf_counter()
        logits, state = prefill(
            params, {"tokens": jnp.asarray(padded)}, state, jnp.int32(0)
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1).astype(jnp.int32))[order0, None]
        if rep == 0:
            compile_s += time.perf_counter() - t0
        decode_s, steps = 0.0, 0
        for t in range(plen, plen + gen_len):
            t1 = time.perf_counter()
            # Host relayout, every token: re-derive, re-pad, re-upload.
            lay = asym.batch_layout(b)
            tok_padded, order = pad_requests(nxt, lay)
            logits, state = decode(
                params, {"tokens": jnp.asarray(tok_padded)}, state, jnp.int32(t)
            )
            nxt = np.asarray(
                jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            )[order, None]
            dt = time.perf_counter() - t1
            if rep == 0 and t == plen:
                compile_s += dt  # first decode call compiles
            else:
                decode_s += dt
                steps += 1
        rates.append(b * steps / decode_s)
    return {"compile_s": compile_s, "rates": rates}


def engine_rounds(cfg, params, prompts, gen_len, seq_cap, reps, *, mixed):
    """The persistent engine, ``reps`` waves through one long-lived engine."""

    from repro.runtime.serving import ServingEngine

    asym = _mk_asym()
    layout = asym.batch_layout(prompts.shape[0])
    eng = ServingEngine(
        cfg, params, asym, seq_cap=seq_cap, slots_per_pod=layout.c_max,
        class_sharded="auto" if mixed else "off",
    )
    rates = []
    prev_tokens = prev_s = 0.0
    for _ in range(reps):
        eng.generate(prompts, gen_len)
        st = eng.stats
        dtok, ds = st.tokens - prev_tokens, st.decode_s - prev_s
        prev_tokens, prev_s = st.tokens, st.decode_s
        rates.append(dtok / ds if ds else 0.0)
    return {
        "compile_s": eng.stats.compile_s,
        "rates": rates,
        "host_relayouts": eng.stats.host_relayouts,
        "rebalances": eng.stats.rebalances,
        "mixed": eng.mixed,
    }


def paged_ab(cfg, params, gen_len, seq_cap, reps, *, slots_per_pod=8,
             page_size=16):
    """Dense vs paged engine A/B: high slot count, mixed prompt lengths.

    Both sides run identical mixed-length request waves through a full
    slot table.  The dense engine allocates ``n_slots × seq_cap`` KV
    lanes up front; the paged engine's footprint is its page pool's
    high-water mark (``peak_kv_bytes`` — phantom lanes included), which
    at realistic request lengths is a small fraction of the dense
    reservation.  ``memory_reduction`` is the headline ratio; tokens are
    asserted bit-identical between the sides while we're here.
    """

    from repro.runtime.serving import ServingEngine

    def side(paged):
        asym = _mk_asym()
        eng = ServingEngine(
            cfg, params, asym, seq_cap=seq_cap, slots_per_pod=slots_per_pod,
            class_sharded="off", paged=paged,
            page_size=page_size if paged == "on" else None,
        )
        # One wave fills the whole table with heterogeneous prompts —
        # every length admits in the same continuous-batching round.
        plens = [4 + 2 * (i % 7) for i in range(eng.n_slots)]
        prompts = rng.integers(0, cfg.vocab, (eng.n_slots, max(plens)),
                               dtype=np.int32)
        rates, prev_t, prev_s = [], 0.0, 0.0
        for _ in range(reps):
            for i, pl in enumerate(plens):
                eng.submit(prompts[i][:pl], gen_len)
            eng.run()
            st = eng.stats
            dtok, ds = st.tokens - prev_t, st.decode_s - prev_s
            prev_t, prev_s = st.tokens, st.decode_s
            rates.append(dtok / ds if ds else 0.0)
        toks = {c.rid: c.tokens for c in eng.completions}
        return eng, float(np.median(rates)), toks

    # Re-seed per side so both submit identical prompt waves.
    rng = np.random.default_rng(2)
    dense_eng, dense_tps, dense_toks = side("off")
    rng = np.random.default_rng(2)
    paged_eng, paged_tps, paged_toks = side("on")
    assert set(dense_toks) == set(paged_toks)
    for rid in dense_toks:
        assert np.array_equal(dense_toks[rid], paged_toks[rid]), (
            f"paged tokens diverged from dense for rid={rid}"
        )

    dense_kv = dense_eng.kv_stats()
    paged_kv = paged_eng.kv_stats()
    reduction = dense_kv["kv_bytes"] / max(paged_kv["peak_kv_bytes"], 1)
    return {
        "slots": [paged_eng.n_pods, paged_eng.c_max],
        "seq_cap": seq_cap,
        "page_size": paged_kv["page_size"],
        "dense": {"tokens_per_s": round(dense_tps, 1),
                  "kv_bytes": dense_kv["kv_bytes"]},
        "paged": {"tokens_per_s": round(paged_tps, 1),
                  "peak_kv_bytes": paged_kv["peak_kv_bytes"],
                  "peak_live_pages": paged_kv["peak_live_pages"],
                  "phantom_pages": paged_kv["phantom_pages"],
                  "admission_deferrals": paged_eng.stats.admission_deferrals},
        "tokens_identical": True,
        "memory_reduction": round(reduction, 2),
    }


def objective_ab(cfg, params, gen_len, seq_cap, reps, *,
                 objectives=("energy",), wave=3, prompt_len=8,
                 slots_per_pod=4):
    """``perf`` vs objective-engine A/B at low offered load.

    Every side serves identical low-depth request waves (``wave`` requests
    against ``2 × slots_per_pod`` slots — the regime where the non-perf
    objectives park the big pod and serve from little).  Compared on the
    *modeled* power-clock columns (``energy_j`` / ``tokens_per_j`` /
    ``modeled_tokens_per_s``), which are deterministic across hosts; the
    wall-clock SPMD program is the same on every side, so tokens are
    asserted bit-identical and the existing speedup gate is untouched.
    The single ``perf`` reference run is shared across all requested
    ``objectives``; one block per objective is returned, each carrying the
    shared perf columns so every block is self-contained (the RPR202
    artifact shape).  The check gate asserts the requested objective
    actually buys joules (``energy_ratio`` strictly < 1) at a bounded
    modeled-throughput loss.
    """

    from repro.runtime.serving import ServingEngine

    def side(obj):
        asym = AsymmetricMesh(
            biglittle_classes(chips_per_pod=1), strategy="ca-das",
            batch_tile=1, objective=obj,
        )
        eng = ServingEngine(
            cfg, params, asym, seq_cap=seq_cap, slots_per_pod=slots_per_pod,
            class_sharded="off",
        )
        rng = np.random.default_rng(2)
        outs = []
        for _ in range(reps):
            prompts = rng.integers(0, cfg.vocab, (wave, prompt_len),
                                   dtype=np.int32)
            outs.append(eng.generate(prompts, gen_len))
        return eng, outs

    def cols(st):
        return {
            "energy_j": round(st.energy_j, 4),
            "tokens_per_j": round(st.tokens_per_j, 3),
            "modeled_tokens_per_s": round(st.modeled_tokens_per_s, 1),
            "pod_parks": st.pod_parks,
            "pod_unparks": st.pod_unparks,
        }

    perf_eng, perf_outs = side("perf")
    ps = perf_eng.stats
    blocks = {}
    for objective in objectives:
        obj_eng, obj_outs = side(objective)
        for a, b in zip(perf_outs, obj_outs):
            assert np.array_equal(a, b), (
                f"{objective}-objective tokens diverged from perf"
            )
        os_ = obj_eng.stats
        energy_ratio = os_.energy_j / ps.energy_j if ps.energy_j else 0.0
        throughput_ratio = (
            os_.modeled_tokens_per_s / ps.modeled_tokens_per_s
            if ps.modeled_tokens_per_s else 0.0
        )
        blocks[objective] = {
            "objective": objective,
            "wave": wave,
            "reps": reps,
            "gen_len": gen_len,
            "perf": cols(ps),
            objective: cols(os_),
            "tokens_identical": True,
            "energy_ratio": round(energy_ratio, 3),
            "throughput_ratio": round(throughput_ratio, 3),
        }
    return blocks


def run(arch: str = "internlm2-1.8b", batch: int = 8, prompt_len: int = 8,
        gen_len: int = 48, seq_cap: int = 512, reps: int = 3,
        mixed: bool = False, obs: bool = False, paged: bool = False,
        objective: str | None = None) -> list[Row]:
    """Both sides on identical prompts/layout; writes ``BENCH_serving.json``.

    ``seq_cap`` is deliberately larger than prompt+gen: the decode-state
    size (what the undonated baseline copies every token) scales with it,
    exactly as production caches dwarf the per-token math.
    """

    cfg = get_config(arch).reduced()
    SH.use_mesh_for_activations(None)
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (batch, prompt_len), dtype=np.int32)
    seq_cap = max(seq_cap, prompt_len + gen_len)

    base = baseline_rounds(cfg, params, prompts, gen_len, seq_cap, reps)
    eng = engine_rounds(cfg, params, prompts, gen_len, seq_cap, reps, mixed=False)

    base_tps = float(np.median(base["rates"]))
    eng_tps = float(np.median(eng["rates"]))
    speedup = eng_tps / base_tps if base_tps else 0.0
    record = {
        "arch": cfg.name,
        "batch": batch,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
        "seq_cap": seq_cap,
        "reps": reps,
        "baseline": {"tokens_per_s": round(base_tps, 1),
                     "rounds": [round(r, 1) for r in base["rates"]],
                     "compile_s": round(base["compile_s"], 3)},
        "engine": {"tokens_per_s": round(eng_tps, 1),
                   "rounds": [round(r, 1) for r in eng["rates"]],
                   "compile_s": round(eng["compile_s"], 3),
                   "host_relayouts": eng["host_relayouts"],
                   "rebalances": eng["rebalances"]},
        "speedup": round(speedup, 3),
    }
    rows = [
        Row("serve_baseline_relayout", 1e6 / max(base_tps, 1e-9),
            f"tokens_per_s={base_tps:.1f}"),
        Row("serve_engine_persistent", 1e6 / max(eng_tps, 1e-9),
            f"tokens_per_s={eng_tps:.1f}"),
        Row("serve_engine_speedup", 0.0, f"speedup={speedup:.3f}"),
    ]
    if mixed:
        # Informational: the class-sharded engine (two per-class programs
        # in one SPMD step) — noisy on forced host devices, not gated.
        emix = engine_rounds(cfg, params, prompts, gen_len, seq_cap, reps,
                             mixed=True)
        mix_tps = float(np.median(emix["rates"]))
        record["engine_mixed"] = {
            "tokens_per_s": round(mix_tps, 1), "mixed": emix["mixed"],
        }
        rows.append(Row("serve_engine_mixed", 1e6 / max(mix_tps, 1e-9),
                        f"tokens_per_s={mix_tps:.1f}"))
    if obs:
        # Informational: the engine with the trace buffer and metrics on —
        # the measured enabled-path overhead of the observability
        # contract.  Not gated (the gate runs disabled).
        from repro import observability as OBS

        OBS.enable()
        try:
            eobs = engine_rounds(cfg, params, prompts, gen_len, seq_cap, reps,
                                 mixed=False)
        finally:
            buf = OBS.disable()
        obs_tps = float(np.median(eobs["rates"]))
        overhead = 1.0 - obs_tps / eng_tps if eng_tps else 0.0
        record["engine_observed"] = {
            "tokens_per_s": round(obs_tps, 1),
            "overhead_pct": round(100.0 * overhead, 1),
            "trace_events": len(buf.events) if buf else 0,
        }
        rows.append(Row("serve_engine_traced", 1e6 / max(obs_tps, 1e-9),
                        f"tokens_per_s={obs_tps:.1f} "
                        f"overhead_pct={100.0 * overhead:.1f}"))
    if paged:
        # The paged-KV A/B: memory proportional to live tokens instead of
        # slots × seq_cap, tokens bit-identical.  Gated on the memory side
        # (--check asserts memory_reduction >= 2); tokens/s informational.
        ab = paged_ab(cfg, params, gen_len, seq_cap, reps)
        record["paged_ab"] = ab
        rows.append(Row(
            "serve_engine_paged",
            1e6 / max(ab["paged"]["tokens_per_s"], 1e-9),
            f"tokens_per_s={ab['paged']['tokens_per_s']:.1f} "
            f"memory_reduction={ab['memory_reduction']:.2f}"))
    records = [record]
    if objective:
        # The objective A/B on the modeled power clock: lower modeled
        # joules than the perf run on the same trace, tokens bit-identical.
        # Both non-perf objectives run against ONE shared perf reference;
        # the requested one lands in this record's ``objective_ab`` (and
        # is what --check gates), the other becomes its own informational
        # record so BENCH_serving.json always carries the energy-vs-edp
        # comparison.
        both = ("energy", "edp")
        blocks = objective_ab(cfg, params, gen_len, seq_cap, reps,
                              objectives=both)
        record["objective_ab"] = blocks[objective]
        for obj in both:
            ab = blocks[obj]
            if obj != objective:
                records.append(
                    {"name": f"serve_objective_{obj}", "objective_ab": ab}
                )
            rows.append(Row(
                f"serve_engine_{obj}", 0.0,
                f"energy_ratio={ab['energy_ratio']:.3f} "
                f"throughput_ratio={ab['throughput_ratio']:.3f} "
                f"tokens_per_j={ab[obj]['tokens_per_j']:.3f}"))
    path = write_json("BENCH_serving.json", records, bench="serving",
                      arch=cfg.name)
    print(f"wrote {path}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=48)
    ap.add_argument("--seq-cap", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--mixed", action="store_true",
                    help="add the informational class-sharded engine row")
    ap.add_argument("--obs", action="store_true",
                    help="add the informational tracing-enabled engine row "
                         "(measures the observability enabled-path overhead)")
    ap.add_argument("--paged", action="store_true",
                    help="add the paged-vs-dense KV A/B rows (high slot "
                         "count, mixed lengths, memory_reduction field)")
    ap.add_argument("--objective", default=None, choices=["energy", "edp"],
                    help="add the perf-vs-objective engine A/B (modeled "
                         "energy_j / tokens_per_j columns; tokens must stay "
                         "bit-identical)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless the engine is strictly faster "
                         "(with --paged, the paged pool at least halves peak "
                         "KV memory; with --objective, modeled joules drop "
                         "strictly below the perf run at a bounded modeled-"
                         "throughput loss)")
    args = ap.parse_args()
    rows = run(args.arch, args.batch, args.prompt_len, args.gen_len,
               args.seq_cap, args.reps, args.mixed, args.obs, args.paged,
               args.objective)
    for r in rows:
        print(f"{r.name},{r.us_per_call:.1f},{r.derived}")
    if args.check:
        speed = float(rows[2].derived.split("=")[1])
        if speed <= 1.0:
            raise SystemExit(f"persistent engine not faster: speedup={speed}")
        if args.paged:
            paged_row = next(r for r in rows if r.name == "serve_engine_paged")
            red = float(paged_row.derived.split("memory_reduction=")[1])
            if red < 2.0:
                raise SystemExit(
                    f"paged KV pool reduction below 2x: {red}"
                )
        if args.objective:
            obj_row = next(
                r for r in rows if r.name == f"serve_engine_{args.objective}"
            )
            eratio = float(
                obj_row.derived.split("energy_ratio=")[1].split()[0]
            )
            tratio = float(
                obj_row.derived.split("throughput_ratio=")[1].split()[0]
            )
            if eratio >= 1.0:
                raise SystemExit(
                    f"{args.objective} objective saved no modeled energy: "
                    f"energy_ratio={eratio}"
                )
            if tratio < 0.2:
                raise SystemExit(
                    f"{args.objective} objective lost too much modeled "
                    f"throughput: throughput_ratio={tratio}"
                )


if __name__ == "__main__":
    main()
