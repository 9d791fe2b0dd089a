#!/usr/bin/env python3
"""Record the small v5e trace that ``test_trace.py`` reads, on the chip.

    python3 chipbench/tests/record_trace.py --workload deepseek-7b-1chip.longctx \
        --seed 7 --steps 3 --out <dir>

Sets a cell up as a benchmark run does (weights from the seed, the engine,
the mix's set-up sessions), then traces ``--steps`` decode steps under the
benchmark's own host spans and writes ``<dir>/<name>.xplane.pb.gz`` and
``<dir>/<name>.calls.json`` (each traced call's kind, rows and contexts, as
the window's record holds them).  It needs a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--name", default="v5e_longctx_3layer")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from chipbench import harness, spec, traffic
    from chipbench.model import Dims, Geometry, arch_config, make_params

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload, ROOT)
    harness.use_compile_cache(ROOT)
    dims, geometry = Dims.from_config(cell.config), Geometry.from_config(cell.config)
    params = make_params(args.seed, dims)
    eng = harness.build_engine(arch_config(cell.config), params, geometry)
    plan = traffic.plan(cell.traffic, args.seed, 1.0, vocab=dims.vocab, lane=geometry.lane,
                        pods=geometry.pods, slots_per_pod=geometry.slots_per_pod)
    rec = harness.Record(t_start=time.perf_counter())
    win = harness.Window(eng, plan, rec, lane=geometry.lane, vocab=dims.vocab, seed=args.seed)
    for req in plan.sessions:
        win.submit(req)
    win.admit()
    win.step()   # the step program runs once before the trace
    n_before = len(rec.calls)

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(tmp)
    for _ in range(args.steps):
        with jax.profiler.TraceAnnotation("chipbench.client"):
            time.sleep(0.002)
        win.step()
    jax.profiler.stop_trace()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (raw,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    data = Path(raw).read_bytes()
    (out / f"{args.name}.xplane.pb.gz").write_bytes(gzip.compress(data, mtime=0))
    calls = [dataclasses.asdict(c) for c in rec.calls[n_before:]]
    (out / f"{args.name}.calls.json").write_text(json.dumps(
        {"workload": cell.name, "seed": args.seed, "dims": dataclasses.asdict(dims),
         "calls": calls}, indent=1))
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"raw_bytes": len(data), "steps": args.steps,
                      "contexts": [c["contexts"] for c in calls]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
