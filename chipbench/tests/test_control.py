"""The check's control at a size a CPU test run holds: put in the
program's place on the same sampled requests, the reference computed with
float8 e4m3's mantissa is judged not correct by the same verdict that
passes the program's served tokens.  On the chip the same reading is
``chipbench/control.py``."""

from __future__ import annotations

import time

from chipbench import harness, spec
from chipbench.tests import tiny


def test_control_fails_the_limit_the_program_passes(tmp_path):
    root = tiny.make(tmp_path)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    out = harness.run(cell, seed=23, seconds=2.0, trace=False, root=root,
                      t_start=time.perf_counter(), control_bits=3)
    c, ctl = out["checks"]["widest_gap"], out["control"]["checks"]["widest_gap"]
    assert c["value"] <= c["limit"] == ctl["limit"] < ctl["value"]
    assert out["correct"]
    assert out["control"]["correct"] is False
