"""A new configuration, model family, mix and per-layer metric are files
plus ``BENCHMARK.json`` entries alone: the harness finds them by name, and
no file the benchmark already had changes."""

from __future__ import annotations

import hashlib
import re
import time

import pytest

from chipbench import harness, spec
from chipbench.tests import tiny


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*")) if p.is_file()}


@pytest.mark.parametrize("qkv_bias", [False, True], ids=["dense", "qkv_bias"])
def test_new_files_are_found_by_name(tmp_path, qkv_bias):
    before = _digests(tiny.ROOT)
    root = tiny.make(tmp_path, qkv_bias=qkv_bias)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    assert cell.config["hidden_size"] == 64
    assert "arrivals" in cell.traffic
    assert [m.name for m in cell.per_layer] == ["requests_started.tiny"]
    assert [m.name for m in cell.end_to_end] == [
        "output_tok_s", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    family = "qkv_bias" if qkv_bias else "dense"
    assert cell.family.__file__ == str(root / f"chipbench/families/{family}.py")
    # The benchmark's own files, unchanged: the copy holds them byte for byte.
    copied = _digests(root)
    for rel, digest in before.items():
        if "tests" not in rel.parts and "__pycache__" not in rel.parts:
            assert copied[rel] == digest, rel
    added = {"configs/tiny.json", "traffic/burst.json", "metrics/requests_started.tiny.py"}
    if qkv_bias:
        added.add("families/qkv_bias.py")
    assert {rel for rel in copied if rel not in before} == {
        (root / "chipbench" / a).relative_to(root) for a in added}


def test_existing_cells_keep_their_metrics(tmp_path):
    root = tiny.make(tmp_path)
    longctx = spec.load_cell("deepseek-7b-1chip.longctx", root, root / "chipbench")
    assert [m.name for m in longctx.end_to_end] == [
        "output_tok_s", "itl_p50_ms", "itl_p95_ms", "setup_s"]
    assert {m.name for m in longctx.per_layer} == {
        "decode_step_ms.longctx", "device_idle_share.longctx", "gemm_roofline.longctx",
        "paged_attn_roofline.longctx", "step_mfu.longctx"}
    assert longctx.family.__file__ == str(root / "chipbench/families/dense.py")


@pytest.mark.parametrize("qkv_bias", [False, True], ids=["dense", "qkv_bias"])
def test_new_cell_runs_end_to_end(tmp_path, qkv_bias):
    root = tiny.make(tmp_path, qkv_bias=qkv_bias)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    out = harness.run(cell, seed=2**33 + 5, seconds=2.0, trace=False, root=root,
                      t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert list(out)[-1] == "checks"
    # The throwaway per-layer reader is called by name in a traced run;
    # here it is read directly on the same kind of record.
    run = harness.Run(cell, cell.family, None, None, harness.Record(0.0), "cpu")
    assert cell.per_layer[0].read(run) == 0.0


def test_check_reads_the_cells_family(tmp_path):
    """The same served tokens judged by the throwaway family's reference
    with the biases left out: not correct, so the check is the family's
    and not the dense one's."""

    root = tiny.make(tmp_path, qkv_bias=True, reference_biases=False)
    cell = spec.load_cell("tiny.burst", root, root / "chipbench")
    out = harness.run(cell, seed=2**33 + 5, seconds=2.0, trace=False, root=root,
                      t_start=time.perf_counter())
    c = out["checks"]["widest_gap"]
    assert not out["correct"]
    assert c["value"] > c["limit"]


def test_shared_modules_name_no_family():
    """Only a family file and a configuration name a family or a registry
    architecture; the harness reaches each through the cell."""

    from repro.configs import get_config, list_configs

    names = set(list_configs()) | {get_config(a).family for a in list_configs()}
    names |= {p.stem for p in (tiny.ROOT / "chipbench/families").glob("*.py")}
    pattern = re.compile(r"\b(" + "|".join(map(re.escape, sorted(names))) + r")\b")
    for module in ("harness", "check", "readings", "control", "spec"):
        text = (tiny.ROOT / f"chipbench/{module}.py").read_text()
        assert not pattern.search(text), (module, pattern.search(text))
