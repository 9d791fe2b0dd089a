#!/usr/bin/env python3
"""Smoke run of the serving engine on a TPU: internlm2-1.8b at published widths.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # a four-chip host: the class-sharded step

One chip: checks ``gemm_pallas`` / ``gemm_pallas_lean`` against ``jnp.dot``
on the model's decode GEMM shapes, then serves seeded requests through
:class:`repro.runtime.serving.ServingEngine` (built as ``repro.launch.serve``
builds it, ``class_sharded="off"``) twice — dense KV lanes, then the paged
pool.  Each engine must resolve the Pallas GEMM and paged-attention kernels,
compile them into its decode step, agree with the same step under an
all-XLA context (and that check must reject the XLA step run on faulted
inputs), and complete every request.

``--chips 4``: only the multi-chip path users get by default on such a host
— the engine with ``class_sharded="on"`` (pod 0 under the big tree, pod 1
under the little tree, one chip each) — compared with the one-chip engine
on the same requests.

Everything runs in this one process, which holds the chips.  It exits
non-zero and prints no result when JAX finds no TPU, when the ``src/``
tree is missing, or when any check fails.  The last line of standard output
is ``{"ok": true, "device": {...}}``.  Timings it prints are smoke timings
of one cold run, not benchmarks.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "internlm2-1.8b"
SEED = 0
# 2 pods x 4 slots x 1024-token lanes: the decode step then needs about
# 12.2 GB of the chip's 15.75 GB (8.4 GB of arguments, mostly the float32
# weights, and 3.8 GB of temporaries).  16 slots x 2048 is refused at
# compile time for HBM.
SLOTS_PER_POD = 4
SEQ_CAP = 1024
N_REQUESTS = 8
PROMPT_LENS = (32, 256)  # inclusive range the seeded prompt lengths come from
MAX_NEW = 32

# The model's decode GEMMs at 16 rows: (M, K, N).
DECODE_GEMMS = ((16, 2048, 8192), (16, 8192, 2048), (16, 2048, 92544))
# A Pallas GEMM rounds its float32 accumulator to bfloat16 once: at most
# 2^-8 relative (bfloat16's unit roundoff).  The float32 reference differs
# besides only in summation order, ~1e-5 on these unit-scale outputs.
GEMM_RTOL = 2.0**-8
GEMM_ATOL = 1e-4
# Per-slot relative L2 error of one decode step's logits, Pallas kernels
# against XLA's dot and gather attention.  Both compute in bfloat16 with
# float32 accumulation; carried through 24 layers their rounding reads
# about 1.6e-2 on a v5e, as far as either lies from the same step in
# float32 at precision="highest".  A slot's position off by one reads
# about 0.23 and a swapped page entry about 1.4: the limit sits between.
LOGITS_REL_L2 = 3e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(*parts):
    print("chip_smoke:", *parts, flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def check_gemms():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.gemm import gemm_pallas, gemm_pallas_lean

    kernels = {f.__name__: jax.jit(f) for f in (gemm_pallas, gemm_pallas_lean)}
    key = jax.random.PRNGKey(SEED)
    for m, k, n in DECODE_GEMMS:
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
        b = (jax.random.normal(kb, (k, n), jnp.float32) / np.sqrt(k)).astype(jnp.bfloat16)
        ref = np.asarray(jnp.dot(a, b, precision="highest",
                                 preferred_element_type=jnp.float32))
        for name, kernel in kernels.items():
            got = np.asarray(kernel(a, b).astype(jnp.float32))
            excess = np.abs(got - ref) - GEMM_RTOL * np.abs(ref)
            log(f"{name} {m}x{k}x{n}: max |diff| "
                f"{float(np.abs(got - ref).max()):.3e}, max |diff| - rtol*|ref| "
                f"{float(excess.max()):.3e} (rtol 2^-8, atol {GEMM_ATOL:.0e})")
            np.testing.assert_allclose(got, ref, rtol=GEMM_RTOL, atol=GEMM_ATOL)


def init_params(cfg, sharding=None):
    """Random weights from the seed, made on the device."""

    import jax

    from repro.models import model_zoo as Z

    init = jax.jit(lambda key: Z.init_params(key, cfg), out_shardings=sharding)
    params = init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    return params


def make_requests(cfg):
    import numpy as np

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [rng.integers(0, cfg.vocab, size=int(n), dtype=np.int32) for n in lens]


def build_engine(cfg, params, *, paged: str, class_sharded: str):
    """The engine as ``repro.launch.serve`` builds it from its arguments."""

    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--arch", ARCH, "--slots-per-pod", str(SLOTS_PER_POD),
        "--paged", paged, "--class-sharded", class_sharded,
    ])
    return serve.make_engine(cfg, params, serve.serving_mesh(args), args,
                             SEQ_CAP, SLOTS_PER_POD)


def check_backends(eng):
    """Every GEMM on a Pallas kernel, paged attention on the Pallas kernel;
    block shapes from the analytical derivation (no tuning cache)."""

    for tree in eng.asym.control_trees().values():
        check(tree.block_source == "analytical",
              f"{tree.device_class}: block from {tree.block_source}, not derived")
    if eng.mixed:
        backends = {p.backend for p in eng.provenance}
        check(backends == {"pallas", "pallas_lean"},
              f"mixed step backends {sorted(backends)}")
        return sorted(backends)
    ctx = eng.asym.execution_context()
    check(ctx.backend() == "pallas", f"GEMM backend {ctx.backend()!r}, not 'pallas'")
    out = [ctx.backend()]
    if eng.paged:
        attn = ctx.paged_attn_backend()
        check(attn == "paged_attn_pallas",
              f"paged attention backend {attn!r}, not 'paged_attn_pallas'")
        out.append(attn)
    return out


def xla_step(cfg):
    """The decode step's logits under an all-XLA context (``jnp.dot``
    GEMMs, ``paged_attention_xla``), jitted: the reference."""

    import jax

    from repro.core import execution as X
    from repro.models import model_zoo as Z

    ctx = X.default_context(backend="xla", paged_attn="paged_attn_xla")
    decode = Z.make_decode_fn(cfg)

    def f(params, batch, state, pos):
        with ctx:
            return decode(params, batch, state, pos)[0]

    return jax.jit(f)


def row_rel_l2(got, ref):
    """Relative L2 error of each slot's logits, ``(n_slots,)``."""

    import numpy as np

    got = np.asarray(got, np.float32).reshape(len(got), -1)
    ref = np.asarray(ref, np.float32).reshape(len(ref), -1)
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def compare_logits(got, ref, what):
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{what}: shapes {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all() and np.isfinite(ref).all(), f"{what}: non-finite logits")
    rel = row_rel_l2(got, ref)
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"{what}: logits {got.shape} per-slot rel-L2 max {rel.max():.3e} "
        f"(limit {LOGITS_REL_L2:.0e}), mean {rel.mean():.3e}, "
        f"argmax agreement {agree:.3f}")
    check(rel.max() <= LOGITS_REL_L2,
          f"{what}: per-slot rel-L2 {rel.max():.3e} > {LOGITS_REL_L2:.0e}")


def check_faults(eng, ref_step, got, label):
    """The logits check must reject the reference step run on faulted
    inputs — one slot's position off by one, and (paged) one page-table
    entry swapped between two slots — or it could not tell such a fault
    from rounding."""

    import numpy as np

    batch, pos = eng.step_inputs()
    r0, r1 = np.flatnonzero(np.asarray(batch["live"]))[:2]
    faults = {"position +1 on one slot": (batch, pos.at[r0].add(1))}
    if "page_table" in batch:
        t = batch["page_table"]
        swapped = t.at[np.array([r0, r1]), 0].set(t[np.array([r1, r0]), 0])
        faults["one page entry swapped between two slots"] = (
            dict(batch, page_table=swapped), pos)
    for what, (b, p) in faults.items():
        rel = row_rel_l2(got, ref_step(eng.params, b, eng.state, p)).max()
        log(f"{label} control, {what}: per-slot rel-L2 max {rel:.3e} "
            f"(must exceed {LOGITS_REL_L2:.0e})")
        check(rel > LOGITS_REL_L2, f"{label}: the logits check passes a fault ({what})")


def serve(cfg, params, prompts, *, paged: str, class_sharded: str, label: str,
          reference: bool = True):
    """Serve ``prompts``; returns the first step's logits and the tokens.

    After the first admission round the engine's next decode step is
    compiled and inspected, and its logits are taken (and, with
    ``reference``, compared with the all-XLA step) before the engine runs
    every request to completion.
    """

    import jax
    import numpy as np

    eng = build_engine(cfg, params, paged=paged, class_sharded=class_sharded)
    log(f"{label}: backends {check_backends(eng)}, slots {eng.n_pods}x{eng.c_max}, "
        f"seq_cap {eng.seq_cap}, kv {eng.kv_stats()}")
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    check(eng.admit() > 0, f"{label}: first admission admitted nothing")
    slot_rid = eng.slot_rid.copy()

    t1 = time.perf_counter()
    batch, pos = eng.step_inputs()
    compiled = eng._step.lower(eng.params, batch, eng.state, pos).compile()
    # Pallas kernels appear in a compiled TPU program as Mosaic custom calls.
    n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    log(f"{label}: decode step compiled in {time.perf_counter() - t1:.2f} s, "
        f"{n_kernels} Pallas kernel calls, {compiled.memory_analysis()}")
    check(n_kernels > 0, f"{label}: no tpu_custom_call in the decode step")

    logits = eng.step_logits()
    if reference:
        ref_step = xla_step(cfg)
        compare_logits(logits, ref_step(eng.params, batch, eng.state, pos),
                       f"{label} pallas vs xla")
        check_faults(eng, ref_step, logits, label)
    logits = np.asarray(logits, np.float32)

    eng.run()
    wall = time.perf_counter() - t0
    done = {c.rid: c for c in eng.completions}
    check(sorted(done) == sorted(rids), f"{label}: completed {sorted(done)} of {rids}")
    for rid, p in zip(rids, prompts):
        c = done[rid]
        gen = c.tokens[len(p):]
        check(len(gen) == MAX_NEW and c.stop == "budget",
              f"{label}: request {rid} stopped after {len(gen)} ({c.stop})")
        check(((gen >= 0) & (gen < cfg.vocab)).all(), f"{label}: token out of vocab")
    st = eng.stats
    generated = sum(len(done[r].tokens) - len(p) for r, p in zip(rids, prompts))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    step_ms = 1e3 * st.decode_s / max(st.decode_steps, 1)
    log(f"{label}: {len(done)}/{len(rids)} requests, {generated} tokens generated "
        f"in {wall:.2f} s wall, {st.compiles} programs compiled in {st.compile_s:.2f} s, "
        f"{st.admission_rounds} admission rounds ({st.prefill_s:.2f} s of prefill), "
        f"{st.decode_steps} decode steps ({step_ms:.2f} ms per step), compiles excluded "
        f"(smoke timing, not a benchmark); device 0 peak_bytes_in_use {peak}")
    tokens = {r: done[r].tokens for r in rids}
    return eng, logits, slot_rid, tokens


def free(*trees):
    """Release device buffers now, whatever still refers to them."""

    import jax

    for x in jax.tree.leaves(trees):
        x.delete()


def pod_devices(eng):
    """Pod -> the device holding that pod's slice of the decode state."""

    leaf = next(iter(eng.state.values()))
    per = leaf.shape[1] // eng.n_pods
    out = {}
    for shard in leaf.addressable_shards:
        start = shard.index[1].start or 0
        out.setdefault(start // per, set()).add(shard.device)
    return out


def one_chip(cfg):
    import jax

    check_gemms()
    t0 = time.perf_counter()
    params = init_params(cfg)
    log(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
        f"(float32) made on the device in {time.perf_counter() - t0:.2f} s")
    prompts = make_requests(cfg)
    log(f"requests: {len(prompts)}, prompt lengths {[len(p) for p in prompts]}, "
        f"{MAX_NEW} new tokens each")
    for paged in ("off", "on"):
        eng, *_ = serve(cfg, params, prompts, paged=paged, class_sharded="off",
                        label=f"engine paged={paged}")
        free(eng.state, eng.tokens)
        del eng
        gc.collect()


def four_chips(cfg):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    prompts = make_requests(cfg)
    params = init_params(cfg)
    eng, ref_logits, ref_slots, ref_tokens = serve(
        cfg, params, prompts, paged="off", class_sharded="off",
        label="one-chip engine", reference=False)
    free(eng.state, eng.tokens, params)
    del eng, params
    gc.collect()

    # The weights replicated once over the devices of the engine's own
    # pod mesh (the same call the engine makes), rather than copied from
    # one chip on every step.
    mesh = make_host_mesh(pod=2)
    params = init_params(cfg, NamedSharding(mesh, P()))
    eng, logits, slots, tokens = serve(
        cfg, params, prompts, paged="off", class_sharded="on",
        label="class-sharded engine", reference=False)
    check(eng.mixed, "class_sharded='on' did not build the mixed step")
    for p in eng.provenance:
        log(f"pod {p.pod}: class {p.device_class}, backend {p.backend}, block {p.block}")
    where = pod_devices(eng)
    for pod, devs in sorted(where.items()):
        log(f"pod {pod}: decode state on {sorted(str(d) for d in devs)}")
    log(f"mesh devices {[str(d) for d in eng.mesh.devices.flat]} "
        f"of {[str(d) for d in jax.devices()]}")
    chips = [frozenset(where[p]) for p in sorted(where)]
    check(len(chips) == eng.n_pods and len(set(chips)) == eng.n_pods,
          f"pods share a chip: {where}")
    check((slots == ref_slots).all(), "requests landed in different slots")
    compare_logits(logits, ref_logits, "class-sharded vs one-chip first step")
    same = np.mean([np.array_equal(tokens[r], ref_tokens[r]) for r in tokens])
    log(f"requests with identical tokens to the one-chip engine: {same:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "runtime" / "serving.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # Blocks come from the analytical derivation, never a tuning cache.
    os.environ.pop("REPRO_TUNING_CACHE", None)
    os.environ.pop("REPRO_TUNING_SPEC", None)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.distributed import sharding as SH
    from repro.launch.compile_cache import use_compile_cache

    log(f"devices: {len(devices)} x {dev.device_kind} ({dev.platform}), "
        f"jax {jax.__version__}, compile cache {use_compile_cache()}")
    cfg = get_config(ARCH)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}")
    SH.use_mesh_for_activations(None)
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg)
    except Exception as e:  # any failed phase: say which, print no result
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
