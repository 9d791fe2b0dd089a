"""A new configuration, mix and per-layer metric are files plus
``BENCHMARK.json`` entries alone: the harness finds them by name, and
no file the benchmark already had changes."""

from __future__ import annotations

import hashlib
import time

from chipbench import harness, spec
from chipbench.tests import tiny


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    before = _digests(tiny.ROOT)
    root = tiny.make(tmp_path)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    assert cell.config["hidden_size"] == 64
    assert "arrivals" in cell.traffic
    assert [m.name for m in cell.per_layer] == ["requests_started.tiny"]
    assert [m.name for m in cell.end_to_end] == [
        "output_tok_s", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    # The benchmark's own files, unchanged: the copy holds them byte for byte.
    copied = _digests(root)
    for rel, digest in before.items():
        if "tests" not in rel.parts and "__pycache__" not in rel.parts:
            assert copied[rel] == digest, rel
    assert {rel for rel in copied if rel not in before} == {
        p.relative_to(root) for p in (root / "chipbench/configs/tiny.json",
                                      root / "chipbench/traffic/burst.json",
                                      root / "chipbench/metrics/requests_started.tiny.py")}


def test_existing_cells_keep_their_metrics(tmp_path):
    root = tiny.make(tmp_path)
    longctx = spec.load_cell("deepseek-7b-1chip.longctx", root, root / "chipbench")
    assert [m.name for m in longctx.end_to_end] == [
        "output_tok_s", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    assert {m.name for m in longctx.per_layer} == {
        "decode_step_ms.longctx", "device_idle_share.longctx", "gemm_roofline.longctx",
        "paged_attn_roofline.longctx", "step_mfu.longctx"}


def test_new_cell_runs_end_to_end(tmp_path):
    root = tiny.make(tmp_path)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    out = harness.run(cell, seed=2**33 + 5, seconds=2.0, trace=False, root=root,
                      t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert list(out)[-1] == "checks"
    # The throwaway per-layer reader is called by name in a traced run;
    # here it is read directly on the same kind of record.
    assert cell.per_layer[0].read(harness.Run(cell, None, None, harness.Record(0.0), "cpu")) == 0.0
