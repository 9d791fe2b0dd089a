#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the TPU this process finds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``src/`` (the system under
test).  The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
which the last lines of standard error repeat.

It exits non-zero and prints no result when JAX's first device is not a
TPU, when it has fewer chips than the cell asks for, when ``src/`` is
missing, or when the run fails.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro" / "runtime" / "serving.py").is_file():
        print(f"chipbench: no system under test: {ROOT / 'src'} holds no repro package",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, spec

    cell = spec.load_cell(args.workload, ROOT)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX's first device is {devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX has {len(devices)}",
              file=sys.stderr)
        return 1
    try:
        out = harness.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                          root=ROOT, t_start=T_START)
    except Exception as e:  # any failed phase: say which, print no result
        traceback.print_exc()
        print(f"chipbench: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(f"chipbench: correct {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"chipbench: check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
