"""Serving driver: a thin CLI over the persistent slot-table engine.

The default path is :class:`repro.runtime.serving.ServingEngine` — the
fixed pod-major slot table with per-class request queues, fused bulk
prefill, donated decode state, and zero per-step host relayout (the
paper's keep-your-assignment scheduling, §5.4, applied to serving).  The
legacy **one-shot** path (``--one-shot``) keeps the pre-engine behavior —
re-pad per the chunk table once per generate call, per-token jit
dispatches — as the comparison baseline; its tokens are bit-identical to
the engine's (tested), so the JSON speed numbers are apples-to-apples.

Example (CPU, reduced config)::

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
        --batch 8 --prompt-len 16 --gen-len 8
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.distributed import sharding as SH
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo as Z


def generate(cfg, params, prompts, gen_len: int, seq_cap: int, decode=None,
             prefill=None, donate: bool = True):
    """Greedy decode: fused bulk prefill, then token-by-token.

    Prefill is the fused bulk path (`model_zoo.make_prefill_fn(cfg,
    with_cache=True)`): one jitted forward over the whole prompt writes
    the cache in one shot, bit-identical to the token-by-token replay it
    replaced (tested).  The decode state is donated through both jits so
    the cache updates in place instead of being copied every token.

    Returns ``(tokens, timings)`` where ``timings`` splits jit compile
    time from steady-state decode: ``compile_s`` (first prefill + first
    decode call), ``decode_s``/``decode_steps`` (remaining steps), so
    callers can report steady-state tokens/s instead of folding XLA
    compilation into the throughput number.
    """

    b, plen = prompts.shape
    donate_state = (2,) if donate else ()
    if decode is None:
        decode = jax.jit(Z.make_decode_fn(cfg), donate_argnums=donate_state)
    if prefill is None:
        prefill = jax.jit(
            Z.make_prefill_fn(cfg, with_cache=True), donate_argnums=donate_state
        )
    state = Z.init_decode_state(cfg, b, seq_cap)

    t0 = time.perf_counter()
    logits, state = prefill(params, {"tokens": prompts}, state, jnp.int32(0))
    jax.block_until_ready(logits)
    timings = {"compile_s": time.perf_counter() - t0,
               "decode_s": 0.0, "decode_steps": 0}
    out = [np.asarray(prompts)]
    for t in range(plen, plen + gen_len):
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out.append(np.asarray(nxt))
        t1 = time.perf_counter()
        logits, state = decode(params, {"tokens": nxt}, state, jnp.int32(t))
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t1
        if t == plen:  # first decode call compiles
            timings["compile_s"] += dt
        else:
            timings["decode_s"] += dt
            timings["decode_steps"] += 1
    return np.concatenate(out, axis=1), timings


def mixed_decode_step(cfg, asym, mesh, batch_padded: int, seq_cap: int):
    """The decode fn wrapped so each pod decodes its request shard under
    its own class's control tree (true CA-SAS serving: one SPMD step, two
    per-class programs).  Decode is pure data parallelism over requests —
    no cross-pod collectives, so no epilogue."""

    state_spec = jax.eval_shape(
        lambda: Z.init_decode_state(cfg, batch_padded, seq_cap)
    )
    sspecs = SH.pod_state_specs(state_spec)
    bspecs = SH.pod_batch_specs({"tokens": 0})  # the decode batch tree
    return asym.class_sharded(
        Z.make_decode_fn(cfg),
        mesh=mesh,
        in_specs=(P(), bspecs, sspecs, P()),
        out_specs=(P("pod"), sspecs),
    )


def pad_requests(prompts: np.ndarray, layout):
    """Lay requests out pod-major per the chunk table; returns (padded,
    order) with ``padded[order] == prompts`` row-for-row.

    This is the **one-shot** path's host relayout.  The persistent engine
    never calls it after admission: requests keep their slot until they
    complete (asserted in tests/test_serving.py)."""

    c_max = layout.c_max
    padded = np.zeros((len(layout.sizes) * c_max,) + prompts.shape[1:], prompts.dtype)
    order, pos = [], 0
    for i, size in enumerate(layout.sizes):
        padded[i * c_max : i * c_max + size] = prompts[pos : pos + size]
        order.extend(range(i * c_max, i * c_max + size))
        pos += size
    return padded, np.asarray(order, np.int64)


def _one_shot(cfg, params, asym, prompts, args, seq_cap):
    """The legacy path: chunk-table relayout once per call, per-token jits."""

    mixed = (
        args.class_sharded != "off"
        and args.device_class is None  # explicit class selection wins
        and len(asym.classes) > 1
        and jax.device_count() >= asym.n_pods
    )
    if args.class_sharded == "on" and not mixed:
        raise SystemExit(
            f"--class-sharded on needs {asym.n_pods} devices, "
            f"have {jax.device_count()}"
        )
    layout = asym.batch_layout(args.batch)
    print("request split across classes:", layout.sizes)
    if mixed:
        # One SPMD decode step, one program per class: pod i's shard runs
        # under class(i)'s control tree (paper §5.3, serving side).
        mesh = make_host_mesh(pod=asym.n_pods)
        padded, order = pad_requests(prompts, layout)
        step = mixed_decode_step(cfg, asym, mesh, padded.shape[0], seq_cap)
        out_padded, timings = generate(
            cfg, params, jnp.asarray(padded), args.gen_len, seq_cap,
            decode=jax.jit(step, donate_argnums=(2,)),
            prefill=jax.jit(
                Z.bulk_prefill_from_decode(step), donate_argnums=(2,)
            ),
        )
        out = out_padded[order]
        shard_classes = [(p.pod, p.device_class, p.block_source, p.backend)
                         for p in step.provenance]
        # A mixed step may run a different micro-kernel variant per class
        # (big -> pallas, little -> pallas_lean): report every variant.
        device_class = "mixed"
        exec_backend = "+".join(sorted({p.backend for p in step.provenance}))
    else:
        # Every decode matmul runs under the serving class's control tree —
        # the context is active while the decode fn traces (first call).
        exec_ctx = asym.execution_context(args.device_class)
        with exec_ctx:
            out, timings = generate(
                cfg, params, jnp.asarray(prompts), args.gen_len, seq_cap
            )
        shard_classes = None
        device_class, exec_backend = exec_ctx.device_class, exec_ctx.backend()
    return out, timings, device_class, exec_backend, shard_classes, None


def truncate_at_eos(out: np.ndarray, prompt_len: int, eos_id: int):
    """EOS-aware stop for the one-shot path's dense output.

    The one-shot loop always decodes ``gen_len`` steps; with an EOS id the
    generated region of each row is cut after its first EOS (the EOS token
    itself is kept, the tail zeroed — matching the engine's per-row
    completions).  Returns ``(out, n_eos, n_budget)``.
    """

    out = out.copy()
    gen = out[:, prompt_len:]
    hit = gen == eos_id
    n_eos = 0
    for r in range(out.shape[0]):
        idx = np.nonzero(hit[r])[0]
        if len(idx):
            gen[r, idx[0] + 1:] = 0
            n_eos += 1
    return out, n_eos, out.shape[0] - n_eos


def serving_mesh(args) -> AsymmetricMesh:
    """The big/little two-pod mesh the engine schedules over, one chip per
    pod."""

    return AsymmetricMesh(biglittle_classes(chips_per_pod=1), strategy=args.strategy,
                          batch_tile=1)


def make_engine(cfg, params, asym, args, seq_cap, c_max):
    """A ``ServingEngine`` configured from the CLI's arguments;
    ``--slots-per-pod`` defaults to the request layout's ``c_max``."""

    from repro.runtime.serving import ServingEngine

    return ServingEngine(
        cfg, params, asym,
        seq_cap=seq_cap,
        slots_per_pod=args.slots_per_pod or c_max,
        class_sharded=args.class_sharded,
        paged=args.paged,
        page_size=args.page_size,
        pool_pages=args.pool_pages,
        eos_id=args.eos_id,
    )


def _engine(cfg, params, asym, prompts, args, seq_cap):
    """The persistent slot-table engine path (the default)."""

    layout = asym.batch_layout(args.batch)
    print("request split across classes:", layout.sizes)
    eng = make_engine(cfg, params, asym, args, seq_cap, layout.c_max)
    out = eng.generate(prompts, args.gen_len)
    st = eng.stats
    # st.tokens counts active-slot tokens only — with fewer active slots
    # than requests (small slot table, multiple waves) batch×steps would
    # overstate the throughput.
    timings = {"compile_s": st.compile_s, "decode_s": st.decode_s,
               "decode_steps": st.decode_steps, "tokens": st.tokens}
    if eng.mixed:
        shard_classes = [(p.pod, p.device_class, p.block_source, p.backend)
                         for p in eng.provenance]
        device_class = "mixed"
        exec_backend = "+".join(sorted({p.backend for p in eng.provenance}))
    else:
        ctx = asym.execution_context()
        shard_classes = None
        device_class, exec_backend = ctx.device_class, ctx.backend()
    engine_stats = {"slots": [eng.n_pods, eng.c_max], **st.snapshot(),
                    "kv_pool": eng.kv_stats()}
    return out, timings, device_class, exec_backend, shard_classes, engine_stats


def _fleet(cfg, params, asym, prompts, args, seq_cap):
    """The multi-engine fleet path (``--fleet N``): N engines, one
    submit/stream front, DAS request scheduling over calibrated
    per-engine throughput (see runtime/fleet.py)."""

    from repro.runtime.fleet import Fleet

    engines = []
    for _ in range(args.fleet):
        a = serving_mesh(args)
        layout = a.batch_layout(max(1, args.batch // args.fleet))
        engines.append(make_engine(cfg, params, a, args, seq_cap, layout.c_max))
    fleet = Fleet(engines)
    print("fleet rel_throughput:", [round(r, 3) for r in fleet.rel_throughput])
    out = fleet.generate(prompts, args.gen_len)
    # Engines tick in lockstep and would run concurrently in production,
    # so the fleet's modeled span is the max over engines, and compile is
    # paid once per engine in parallel.
    timings = {
        "compile_s": max(e.stats.compile_s for e in engines),
        "decode_s": max(e.stats.decode_s for e in engines),
        "decode_steps": max(e.stats.decode_steps for e in engines),
        "tokens": sum(e.stats.tokens for e in engines),
    }
    ctx = engines[0].asym.execution_context()
    device_class = "mixed" if engines[0].mixed else ctx.device_class
    exec_backend = (
        "+".join(sorted({p.backend for p in engines[0].provenance}))
        if engines[0].mixed
        else ctx.backend()
    )
    engine_stats = {
        "fleet": fleet.stats.snapshot(),
        "health": fleet.health(),
        "engines": [e.stats.snapshot() for e in engines],
        # the stop-count surface _engine provides, fleet-wide
        "completed_eos": sum(e.stats.completed_eos for e in engines),
        "completed_budget": sum(e.stats.completed_budget for e in engines),
    }
    return out, timings, device_class, exec_backend, None, engine_stats


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--strategy", default="ca-das")
    ap.add_argument("--device-class", default=None,
                    help="serve under this class's control tree (default: fastest)")
    ap.add_argument("--class-sharded", default="auto", choices=["auto", "on", "off"],
                    help="decode each pod's request shard under its own class's "
                         "tree in one SPMD step; auto = on when the host has a "
                         "device per pod")
    ap.add_argument("--one-shot", action="store_true",
                    help="legacy path: chunk-table relayout per call + "
                         "per-token jit dispatches (comparison baseline)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through a fault-tolerant fleet of N engines "
                         "behind one scheduler (0 = single engine)")
    ap.add_argument("--slots-per-pod", type=int, default=None,
                    help="engine slot-region size (default: the layout's c_max)")
    ap.add_argument("--paged", default="off", choices=["auto", "on", "off"],
                    help="engine KV storage: paged page-pool instead of dense "
                         "per-slot lanes (memory proportional to live tokens; "
                         "bit-identical tokens)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: derived from the "
                         "classes' tuned block configs)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical KV pages per pod partition (default: "
                         "full-occupancy capacity — never defers)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request at this token id (engine: the slot "
                         "retires and its pages free mid-stream; one-shot: "
                         "rows are truncated after their first EOS)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable observability and write the trace here "
                         "(native format; summarize / export Chrome trace "
                         "with python -m repro.observability.report)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable observability and write a metrics JSON "
                         "snapshot here")
    return ap


def main():
    args = build_parser().parse_args()
    use_compile_cache()

    if args.trace or args.metrics:
        from repro import observability as OBS

        OBS.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    SH.use_mesh_for_activations(None)

    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    if cfg.embed_inputs or cfg.family == "encdec":
        raise SystemExit(f"{cfg.name}: serving demo targets token-in archs")

    # Asymmetric request routing: split the request batch across classes.
    asym = serving_mesh(args)
    if args.class_sharded == "on" and args.device_class is not None:
        raise SystemExit(
            "--class-sharded on serves every class simultaneously; "
            "it cannot be combined with --device-class"
        )
    if not args.one_shot and args.device_class is not None:
        raise SystemExit("--device-class applies to the --one-shot path only")
    if args.one_shot and args.paged != "off":
        raise SystemExit("--paged applies to the engine path only")
    if args.fleet and args.one_shot:
        raise SystemExit("--fleet fronts engine instances; it cannot be "
                         "combined with --one-shot")
    if args.fleet < 0:
        raise SystemExit(f"--fleet must be >= 0, got {args.fleet}")

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len), dtype=np.int32)
    seq_cap = args.prompt_len + args.gen_len

    t0 = time.time()
    run = _one_shot if args.one_shot else (_fleet if args.fleet else _engine)
    out, timings, device_class, exec_backend, shard_classes, engine_stats = run(
        cfg, params, asym, prompts, args, seq_cap
    )
    dt = time.time() - t0
    stop_counts = None
    if args.eos_id is not None:
        if engine_stats is not None:
            stop_counts = {"eos": engine_stats["completed_eos"],
                           "budget": engine_stats["completed_budget"]}
        else:
            out, n_eos, n_budget = truncate_at_eos(out, args.prompt_len, args.eos_id)
            stop_counts = {"eos": n_eos, "budget": n_budget}
    # Steady-state throughput: warmup/compile excluded.  The one-shot path
    # used to fold jit compile time into tokens_per_s, which made every
    # comparison against it meaningless on the first run.  The engine
    # reports its actual active-slot token count; the one-shot path
    # decodes the full batch every step.
    tokens = timings.get("tokens", args.batch * timings["decode_steps"])
    steady = tokens / timings["decode_s"] if timings["decode_s"] > 0 else 0.0
    summary = {
        "arch": cfg.name,
        "path": ("one-shot" if args.one_shot
                 else f"fleet:{args.fleet}" if args.fleet else "engine"),
        "device_class": device_class,
        "exec_backend": exec_backend,
        "class_sharded": shard_classes is not None,
        "shard_classes": shard_classes,
        "batch": args.batch,
        "generated": out.shape[1] - args.prompt_len,
        "wall_s": round(dt, 2),
        "compile_s": round(timings["compile_s"], 3),
        "tokens_per_s": round(steady, 1),
        "sample": out[0, -8:].tolist(),
    }
    if stop_counts is not None:
        summary["stop_counts"] = stop_counts
    if engine_stats is not None:
        summary["engine"] = engine_stats
    if args.trace or args.metrics:
        from repro import observability as OBS
        from repro.observability import trace as TR

        buf = TR.get_buffer()
        if args.trace:
            summary["trace"] = buf.save(args.trace)
        if args.metrics:
            from repro.util.atomic import atomic_write_json

            summary["metrics"] = atomic_write_json(
                args.metrics, OBS.REGISTRY.snapshot(), indent=1, sort_keys=True
            )
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
