#!/usr/bin/env python3
"""Read the check's control on the chip: the program's widest gap and the
control's, per seed, for one cell, in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 20

Each seed is a whole run of the cell (set-up, a window of ``--seconds``,
the check); the control is the reference with its weights and matmul
inputs rounded to float8 e4m3's 3 explicit mantissa bits, the precision
below the bfloat16 the engine computes in, put in the program's place on
the same sampled requests and judged as the program is (``"control"``:
its ``correct`` has to come out false).  Prints one JSON line per seed;
the limit in the configuration file has to lie above every program
reading and below every control reading.  The benchmark's own runs never
read the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CONTROL_BITS = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, spec

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed=seed, seconds=args.seconds, trace=False, root=ROOT,
                          t_start=time.perf_counter(), control_bits=CONTROL_BITS)
        print(json.dumps({"workload": cell.name, "seed": seed, "bits": CONTROL_BITS,
                          "correct": out["correct"], "checks": out["checks"],
                          "control": out["control"], "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
