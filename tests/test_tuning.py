"""Tests for the repro.tuning autotuning subsystem."""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocking as B
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.kernels import ref
from repro.kernels.gemm import gemm_pallas, resolve_block_config
from repro.tuning import cache as C
from repro.tuning import candidates as CAND
from repro.tuning import measure as M
from repro.tuning import ratio as R
from repro.tuning import tune as T

SHAPES = [(256, 256, 256), (512, 512, 512), (300, 1100, 200), (1024, 2048, 512)]


# ---------------------------------------------------------------------------
# Candidates: every candidate feasible, analytical always included
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("spec_name", sorted(CAND.SPECS))
def test_candidates_feasible_and_aligned(shape, spec_name):
    m, k, n = shape
    spec = CAND.get_spec(spec_name)
    cands = CAND.enumerate_candidates(m, k, n, spec=spec)
    assert cands, "candidate set must be non-empty"
    for cfg in cands:
        assert cfg.fits(spec), f"{cfg} exceeds the VMEM budget of {spec_name}"
        assert cfg.bm % spec.mxu == 0
        assert cfg.bk % spec.mxu == 0
        assert cfg.bn % spec.mxu == 0


@pytest.mark.parametrize("shape", SHAPES)
def test_candidates_include_analytical(shape):
    m, k, n = shape
    seed = CAND.analytical_config(m, k, n)
    cands = CAND.enumerate_candidates(m, k, n)
    assert cands[0] == seed
    keys = {(c.bm, c.bk, c.bn) for c in cands}
    assert len(keys) == len(cands), "candidates must be deduplicated"


def test_neighborhood_feasible():
    seed = CAND.analytical_config(512, 512, 512)
    for cfg in CAND.neighborhood(seed):
        assert cfg.fits(B.TPU_V5E)
        assert cfg != seed or True  # perturbed dims stay aligned
        assert cfg.bm % 128 == 0 and cfg.bk % 128 == 0 and cfg.bn % 128 == 0


# ---------------------------------------------------------------------------
# Cost model: deterministic, sane, and the search never loses to analytical
# ---------------------------------------------------------------------------


def test_cost_model_deterministic_and_positive():
    cfg = B.BlockConfig(bm=256, bk=256, bn=256)
    t1 = M.cost_model_time(512, 512, 512, cfg)
    t2 = M.cost_model_time(512, 512, 512, cfg)
    assert t1 == t2 > 0.0


def test_cost_model_charges_padding():
    # A 1024-block on a 512 problem pays for computed zeros.
    small = B.BlockConfig(bm=512, bk=512, bn=512)
    big = B.BlockConfig(bm=1024, bk=512, bn=512)
    assert M.cost_model_time(512, 512, 512, big) > M.cost_model_time(512, 512, 512, small)


def test_cost_model_charges_grid_overhead():
    # Thousands of tiny blocks launch-cost more than tens of large ones.
    tiny = B.BlockConfig(bm=128, bk=128, bn=128)
    large = B.BlockConfig(bm=512, bk=512, bn=512)
    assert M.cost_model_time(2048, 2048, 2048, tiny) > M.cost_model_time(
        2048, 2048, 2048, large
    )


@pytest.mark.parametrize("shape", SHAPES)
def test_search_no_worse_than_analytical(shape):
    m, k, n = shape
    backend = M.make_backend("cost-model")
    res = T.search_shape(m, k, n, spec=B.TPU_V5E, dtype_bytes=2, backend=backend)
    assert res.best_time_s <= res.analytical_time_s
    assert res.speedup >= 1.0


# ---------------------------------------------------------------------------
# Cache: roundtrip, version invalidation, atomicity, fallback
# ---------------------------------------------------------------------------


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cfg = B.BlockConfig(bm=256, bk=512, bn=256)
    cache.put("tpu-v5e", "bfloat16", 512, 512, 512, cfg, backend="cost-model", time_s=1e-3)
    cache.save()

    loaded = C.TuningCache.load(path)
    got = loaded.get("tpu-v5e", "bfloat16", 512, 512, 512)
    assert got == cfg
    # Bucketing: a shape padding to the same 128-aligned dims hits the entry.
    assert loaded.get("tpu-v5e", "bfloat16", 500, 450, 390) == cfg
    # A smaller problem in a different bucket must NOT alias onto it —
    # its blocks would overshoot the problem and pay padded FLOPs.
    assert loaded.get("tpu-v5e", "bfloat16", 260, 260, 260) is None
    # Different dtype / spec miss.
    assert loaded.get("tpu-v5e", "float32", 512, 512, 512) is None
    assert loaded.get("tpu-little", "bfloat16", 512, 512, 512) is None


def test_cache_version_mismatch_invalidates(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        json.dump(
            {
                "version": C.CACHE_VERSION + 1,
                "entries": {"tpu-v5e/bfloat16/512x512x512": {"bm": 256, "bk": 256, "bn": 256}},
            },
            f,
        )
    loaded = C.TuningCache.load(path)
    assert loaded.entries == {}
    # Fallback on miss returns the analytical derivation.
    cfg, hit = loaded.lookup_or_analytical(512, 512, 512)
    assert not hit
    assert cfg == B.derive_block_config(512, 512, 512, dtype_bytes=2)


def test_cache_corrupt_file_starts_empty(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write("{not json")
    assert C.TuningCache.load(path).entries == {}


def test_cache_non_object_json_starts_empty(tmp_path):
    # e.g. $REPRO_TUNING_CACHE accidentally pointed at BENCH_gemm.json
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        json.dump([{"bench": "gemm"}], f)
    assert C.TuningCache.load(path).entries == {}


def test_cache_malformed_entry_is_a_miss(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    key = C.shape_bucket_key("tpu-v5e", "float32", 256, 256, 256)
    with open(path, "w") as f:
        json.dump({"version": C.CACHE_VERSION, "entries": {key: {"oops": 1}}}, f)
    loaded = C.TuningCache.load(path)
    assert loaded.get("tpu-v5e", "float32", 256, 256, 256) is None
    # ...and the kernel hot path falls back to analytical instead of crashing.
    monkeypatch.setenv(C.ENV_VAR, path)
    cfg = resolve_block_config(256, 256, 256, jnp.dtype(jnp.float32))
    assert cfg == B.derive_block_config(256, 256, 256, dtype_bytes=4)


def test_cache_atomic_write_leaves_no_temp(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put("tpu-v5e", "bfloat16", 128, 128, 128, B.BlockConfig(128, 128, 128))
    cache.save()
    leftovers = [p for p in os.listdir(tmp_path) if p.startswith(".tuning-cache-")]
    assert leftovers == []
    assert json.load(open(path))["version"] == C.CACHE_VERSION


# ---------------------------------------------------------------------------
# tune CLI: search -> write -> second run hits the cache
# ---------------------------------------------------------------------------


def test_tune_cli_writes_cache_and_hits_on_rerun(tmp_path, caplog):
    path = str(tmp_path / "cache.json")
    argv = [
        "--spec", "tpu-v5e", "--backend", "cost-model",
        "--shapes", "512x512x512,1024x1024x1024", "--cache", path,
    ]
    summary = T.main(argv)
    assert os.path.exists(path)
    assert len(summary["shapes"]) == 2
    for rec in summary["shapes"]:
        assert not rec["cache_hit"]
        assert rec["best_time_s"] <= rec["analytical_time_s"]

    import logging

    with caplog.at_level(logging.INFO, logger="repro.tuning.tune"):
        summary2 = T.main(argv)
    assert all(rec["cache_hit"] for rec in summary2["shapes"])
    assert any("cache hit" in r.message for r in caplog.records)


def test_tune_cli_calibrate_ratios_with_wallclock_backend(tmp_path):
    # --calibrate-ratios must not crash under --backend wallclock: the
    # ratio calibration always uses the cost model (one host cannot
    # wallclock-compare heterogeneous specs).
    path = str(tmp_path / "cache.json")
    summary = T.main(
        ["--backend", "wallclock", "--shapes", "128x128x128", "--cache", path,
         "--max-candidates", "1", "--calibrate-ratios"]
    )
    assert len(summary["init_ratios"]) == 2
    assert summary["init_ratios"][1] < 1.0


def test_tune_cli_dry_run_writes_nothing(tmp_path):
    path = str(tmp_path / "cache.json")
    summary = T.main(
        ["--backend", "cost-model", "--cache", path, "--dry-run"]
    )
    assert summary["cache_path"] is None
    assert not os.path.exists(path)
    assert summary["shapes"], "dry run still searches the default shapes"


def test_parse_shapes_rejects_garbage():
    assert T.parse_shapes("512x512x512") == [(512, 512, 512)]
    with pytest.raises(ValueError):
        T.parse_shapes("512x512")
    with pytest.raises(ValueError):
        T.parse_shapes("")


# ---------------------------------------------------------------------------
# Kernel integration: REPRO_TUNING_CACHE drives cfg=None resolution
# ---------------------------------------------------------------------------


def _write_cache(tmp_path, cfg, m, k, n, dtype_name="float32"):
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put("tpu-v5e", dtype_name, m, k, n, cfg, backend="test")  # repro: noqa=RPR005 -- fixture provenance label, not a dispatch token
    cache.save()
    return path


def test_gemm_resolves_cached_config(tmp_path, monkeypatch):
    # A deliberately distinctive config the analytical route would not pick.
    tuned = B.BlockConfig(bm=128, bk=256, bn=128, dtype_bytes=4)
    path = _write_cache(tmp_path, tuned, 256, 256, 256)
    monkeypatch.setenv(C.ENV_VAR, path)
    cfg = resolve_block_config(256, 256, 256, jnp.dtype(jnp.float32))
    assert (cfg.bm, cfg.bk, cfg.bn) == (128, 256, 128)

    # Unset -> analytical, untouched defaults.
    monkeypatch.delenv(C.ENV_VAR)
    cfg = resolve_block_config(256, 256, 256, jnp.dtype(jnp.float32))
    assert cfg == B.derive_block_config(256, 256, 256, dtype_bytes=4)


def test_gemm_pallas_with_cache_matches_oracle(tmp_path, monkeypatch):
    m = k = n = 256
    tuned = B.BlockConfig(bm=128, bk=128, bn=256, dtype_bytes=4)
    path = _write_cache(tmp_path, tuned, m, k, n)
    monkeypatch.setenv(C.ENV_VAR, path)

    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out_cached = gemm_pallas(a, b, interpret=True)

    monkeypatch.delenv(C.ENV_VAR)
    expect = ref.gemm_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out_cached), np.asarray(expect), rtol=1e-5, atol=1e-4
    )
    # And explicitly through the tuned config equals the cached-path result
    # bit for bit (same block shapes -> same arithmetic order).
    out_explicit = gemm_pallas(a, b, tuned, interpret=True)
    assert np.array_equal(np.asarray(out_cached), np.asarray(out_explicit))


def test_cached_config_dtype_bytes_reconciled(tmp_path, monkeypatch):
    # Cache tuned for bf16; a float32 call must not inherit dtype_bytes=2.
    tuned = B.BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=2)
    path = _write_cache(tmp_path, tuned, 128, 128, 128, dtype_name="float32")
    monkeypatch.setenv(C.ENV_VAR, path)
    cfg = resolve_block_config(128, 128, 128, jnp.dtype(jnp.float32))
    assert cfg.dtype_bytes == 4


# ---------------------------------------------------------------------------
# Ratio calibration: measured ratios replace hand-typed rel_throughput
# ---------------------------------------------------------------------------


def test_calibrate_biglittle_ratios():
    classes = biglittle_classes()
    cal = R.calibrate_class_ratios(classes, backend="cost-model")
    assert cal.class_names == ("big", "little")
    assert cal.ratios[0] == 1.0
    # The little spec has half the peak FLOPs and HBM bandwidth — the
    # calibrated ratio must reflect real hardware degradation, not just
    # block-config noise (regression: a spec that only overrode VMEM made
    # this come out ~0.78).
    assert 0.0 < cal.ratios[1] < 0.6
    assert cal.knob() > 1.5


def test_mesh_from_calibration():
    classes = biglittle_classes()
    mesh = AsymmetricMesh.from_calibration(classes, strategy="ca-sas", batch_tile=8)
    assert mesh.calibration is not None
    assert mesh.classes[0].rel_throughput == 1.0
    assert mesh.classes[1].rel_throughput == pytest.approx(
        mesh.calibration.ratios[1]
    )
    # The calibrated mesh still schedules exactly.
    layout = mesh.batch_layout(256)
    assert sum(layout.sizes) == 256
    # The faster class gets strictly more work.
    assert layout.sizes[0] > layout.sizes[1]


def test_mesh_from_calibration_explicit_calibration():
    classes = biglittle_classes()
    cal = R.Calibration(
        class_names=("big", "little"),
        ratios=(1.0, 0.5),
        probe_shape=(512, 512, 512),
        backend="cost-model",
        times_s=(1.0, 2.0),
    )
    mesh = AsymmetricMesh.from_calibration(classes, cal, strategy="sas")
    assert mesh.classes[1].rel_throughput == 0.5


def test_wallclock_calibration_rejects_heterogeneous_specs():
    # One host cannot time two different core specs; the calibration must
    # refuse rather than silently produce ~1:1 ratios.
    with pytest.raises(ValueError, match="heterogeneous"):
        R.calibrate_class_ratios(biglittle_classes(), backend="wallclock")


def test_sweep_ratio_knob_prefers_asymmetric():
    best, results = R.sweep_ratio_knob(2048, ratios=(1, 2, 3, 4, 5, 6, 7))
    # The paper's sweep peaks in the 3-6 region (A15:A7 ≈ 4), never at 1.
    assert best > 1.0
    assert len(results) == 7


# ---------------------------------------------------------------------------
# Measurement backends agree on ordering for a clear-cut case
# ---------------------------------------------------------------------------


def test_wallclock_backend_runs_small():
    cfg = B.BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
    t = M.wallclock_time(128, 128, 128, cfg, dtype=jnp.float32, reps=1, warmup=0)
    assert t > 0.0


def test_wallclock_times_the_lean_kernel_too():
    cfg = B.BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
    t = M.wallclock_time(128, 128, 128, cfg, dtype=jnp.float32, reps=1, warmup=0,
                         kernel_backend="pallas_lean")
    assert t > 0.0
    with pytest.raises(ValueError, match="cannot time kernel backend"):
        M.wallclock_time(128, 128, 128, cfg, kernel_backend="xla")


# ---------------------------------------------------------------------------
# Micro-kernel variants as a search dimension (paper §5.3)
# ---------------------------------------------------------------------------

# A deliberately constrained, memory-bound core: 2 MiB VMEM and thin HBM.
# Here the lean kernel's larger single-buffered panels beat the pipelined
# kernel's overlap — the regime the variant dimension exists for.
NANO = B.TpuCoreSpec(
    name="tpu-nano", vmem_bytes=2 * 1024 * 1024,
    peak_flops=200e12, hbm_bw=50e9,
)


def test_kernel_candidates_widen_the_feasible_set():
    cands = CAND.enumerate_kernel_candidates(
        1024, 1024, 1024, spec=NANO, dtype_bytes=4
    )
    by_backend = {}
    for c in cands:
        by_backend.setdefault(c.backend, []).append(c.cfg)
    assert set(by_backend) == {"pallas", "pallas_lean"}
    # Every candidate is feasible under its own kernel's VMEM model...
    for cfg in by_backend["pallas"]:
        assert cfg.fits(NANO)
    for cfg in by_backend["pallas_lean"]:
        assert cfg.fits(NANO, double_buffer=False)
    # ...and the lean set contains configs the pipelined kernel cannot
    # hold (the variant genuinely widens the search space).
    lean_only = [c for c in by_backend["pallas_lean"] if not c.fits(NANO)]
    assert lean_only
    # Dedup covers the variant axis: (cfg, backend) pairs are unique.
    keys = {c.key for c in cands}
    assert len(keys) == len(cands)
    with pytest.raises(ValueError, match="unknown kernel backend"):
        CAND.enumerate_kernel_candidates(256, 256, 256, backends=["mosaic"])
    # Dispatch entries that are not timeable kernels are rejected too:
    # "xla" and the interpret twins are execution modes, not variants a
    # scorer can model (regression: they used to pass validation and leak
    # into the cache's recorded-variant field).
    for not_a_kernel in ("xla", "pallas_interpret", "pallas_lean_interpret"):
        with pytest.raises(ValueError, match="unknown kernel backend"):
            CAND.enumerate_kernel_candidates(256, 256, 256,
                                             backends=[not_a_kernel])


def test_kernel_backends_derive_from_the_registry():
    """One variant registry: the search dimension, the wallclock timer,
    and the benchmarks all derive from kernels.gemm.GEMM_KERNELS, and
    every registered variant has dispatch + interpret-twin entries."""

    from repro.core import execution as X
    from repro.kernels.gemm import GEMM_KERNELS

    assert CAND.KERNEL_BACKENDS == tuple(GEMM_KERNELS)
    for name in GEMM_KERNELS:
        assert name in X.BACKENDS
        assert X.interpret_twin(name) in X.BACKENDS


def test_cost_model_serializes_lean_streams():
    """Pipelined: max(compute, memory) + overhead.  Lean single-buffers,
    so each K step waits for its DMA: compute + memory + overhead."""

    cfg = B.BlockConfig(bm=256, bk=256, bn=256, dtype_bytes=4)
    pip = M.cost_breakdown(512, 512, 512, cfg, spec=NANO)
    lean = M.cost_breakdown(512, 512, 512, cfg, spec=NANO,
                            kernel_backend="pallas_lean")
    assert pip.compute_s == lean.compute_s and pip.memory_s == lean.memory_s
    assert pip.time_s == max(pip.compute_s, pip.memory_s) + pip.overhead_s
    assert lean.time_s == lean.compute_s + lean.memory_s + lean.overhead_s
    assert lean.time_s > pip.time_s  # same config: overlap always wins


def test_search_picks_lean_when_panels_beat_overlap(tmp_path):
    """On the constrained memory-bound spec the lean-only panels cut HBM
    re-reads by more than the lost overlap costs: the search organically
    selects pallas_lean and the cache records the winning variant."""

    cache = C.TuningCache(path=str(tmp_path / "cache.json"))
    res = T.tune_shapes(
        [(1024, 1024, 1024)], spec=NANO, dtype="f32",
        backend_name="cost-model", cache=cache,
    )[0]
    assert res.best_backend == "pallas_lean"
    assert res.best_time_s < res.analytical_time_s  # beats the pipelined seed
    assert not res.best.fits(NANO)                  # a lean-only panel won
    assert res.best.fits(NANO, double_buffer=False)

    key = C.shape_bucket_key(NANO.name, "float32", 1024, 1024, 1024)
    entry = cache.entries[key]
    assert entry["backend"] == "pallas_lean"
    assert entry["measured_with"] == "cost-model"

    # A rerun is a cache hit that reports the recorded variant.
    hit = T.tune_shapes(
        [(1024, 1024, 1024)], spec=NANO, dtype="f32",
        backend_name="cost-model", cache=cache,
    )[0]
    assert hit.cache_hit and hit.best_backend == "pallas_lean"


def test_single_variant_search_unchanged():
    """kernel_backends=('pallas',) calls the scorer 4-arg (old protocol)
    and never proposes lean-only configs."""

    calls = []

    def scorer(m, k, n, cfg):  # no kernel_backend kwarg: the old contract
        calls.append(cfg)
        return M.cost_model_time(m, k, n, cfg, spec=NANO)

    res = T.search_shape(512, 512, 512, spec=NANO, dtype_bytes=4,
                         backend=scorer, kernel_backends=("pallas",))
    assert res.best_backend == "pallas"
    assert calls and all(c.fits(NANO) for c in calls)


def test_old_cache_backend_field_not_misread_as_variant(tmp_path, monkeypatch):
    """Pre-variant caches stored the measurement backend ("cost-model")
    under "backend"; consumers must treat that as 'no variant recorded'
    and keep the default kernel."""

    from repro.core import execution as X

    cfg = B.BlockConfig(bm=256, bk=256, bn=256, dtype_bytes=2)
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put(B.TPU_V5E.name, "bfloat16", 512, 512, 512, cfg, backend="cost-model")
    cache.save()
    monkeypatch.setenv(C.ENV_VAR, path)

    assert C.cached_kernel_backend(512, 512, 512, "bfloat16",
                                   spec_name=B.TPU_V5E.name) == "cost-model"
    assert X.tuned_kernel_backend(512, 512, 512, spec=B.TPU_V5E,
                                  dtype_name="bfloat16") is None

    from repro.core.control_tree import build_control_trees

    tree = build_control_trees(
        {"x": B.TPU_V5E}, 512, 512, 512, backend="pallas_interpret"
    )["x"]
    assert tree.block_source == "tuned" and tree.block == cfg
    assert tree.backend == "pallas_interpret"  # default kernel kept


def test_lean_recorded_entry_never_reaches_pipelined_consumers(
    tmp_path, monkeypatch
):
    """Regression: a cache winner recorded for the lean kernel carries a
    single-buffer-only block; the pipelined kernel's working set is twice
    what that block was validated under, so every double-buffered lookup
    path must treat the entry as a miss (and the lean paths keep it)."""

    from repro.core import execution as X

    # Lean-only on TPU_LITTLE: 6.5 MiB single- vs 8.4 MiB double-buffered.
    cfg = B.BlockConfig(bm=512, bk=640, bn=1024, dtype_bytes=2)
    assert not cfg.fits(B.TPU_LITTLE) and cfg.fits(B.TPU_LITTLE, double_buffer=False)
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put(B.TPU_LITTLE.name, "bfloat16", 2048, 2048, 2048, cfg,
              backend="pallas_lean")
    cache.save()
    monkeypatch.setenv(C.ENV_VAR, path)
    monkeypatch.setenv(C.ENV_SPEC_VAR, B.TPU_LITTLE.name)

    # The kernel-path resolver: pipelined consumer misses, lean consumer hits.
    got, src = X.resolve_block_config(
        2048, 2048, 2048, spec=B.TPU_LITTLE, dtype_name="bfloat16",
        dtype_bytes=2, double_buffer=True,
    )
    assert src == "analytical" and got.fits(B.TPU_LITTLE)
    got, src = X.resolve_block_config(
        2048, 2048, 2048, spec=B.TPU_LITTLE, dtype_name="bfloat16",
        dtype_bytes=2, double_buffer=False,
    )
    assert src == "tuned" and got == cfg
    # Same via the env-spec (cfg=None kernel path, spec=None).
    _, src = X.resolve_block_config(2048, 2048, 2048, dtype_name="bfloat16",
                                    dtype_bytes=2, double_buffer=True)
    assert src == "analytical"

    # The per-call context path: a pipelined tree skips the lean-only
    # entry for off-bucket calls and derives a block its kernel can hold.
    from repro.core.control_tree import ControlTree

    tree = ControlTree(
        device_class="little",
        block=B.derive_block_config(256, 256, 256, spec=B.TPU_LITTLE),
        backend="pallas_interpret", spec=B.TPU_LITTLE,
        problem_shape=(256, 256, 256),
    )
    got = X.context_for_tree(tree).block_config(2048, 2048, 2048, "bfloat16", 2)
    assert got.fits(B.TPU_LITTLE)
    # ...while the tree-build path pairs the entry with the lean backend.
    from repro.core.control_tree import build_control_trees

    built = build_control_trees(
        {"little": B.TPU_LITTLE}, 2048, 2048, 2048, backend="pallas_interpret"
    )["little"]
    assert built.block_source == "tuned" and built.block == cfg
    assert built.backend == "pallas_lean_interpret"


def test_cache_aware_false_baseline_stays_uniform(tmp_path, monkeypatch):
    """Regression: the single-control-tree SAS baseline (cache_aware=False)
    must mirror the *first* class's configuration wholesale — per-class
    recorded variants may not leak into the deliberately uniform run."""

    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cfg = B.BlockConfig(bm=256, bk=256, bn=256, dtype_bytes=2)
    cache.put(B.TPU_V5E.name, "bfloat16", 512, 512, 512, cfg, backend="pallas")
    cache.put(B.TPU_LITTLE.name, "bfloat16", 512, 512, 512, cfg,
              backend="pallas_lean")
    cache.save()
    monkeypatch.setenv(C.ENV_VAR, path)

    from repro.core.control_tree import build_control_trees

    trees = build_control_trees(
        {"big": B.TPU_V5E, "little": B.TPU_LITTLE}, 512, 512, 512,
        backend="pallas", cache_aware=False,
    )
    assert trees["little"].block == trees["big"].block
    assert trees["little"].backend == trees["big"].backend == "pallas"


def test_recorded_variant_reaches_the_tree(tmp_path, monkeypatch):
    """A cache entry recording pallas_lean routes that class's tree to the
    lean kernel (mapped onto the requested compiled/interpret family)."""

    cfg = B.BlockConfig(bm=256, bk=256, bn=256, dtype_bytes=2)
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put(B.TPU_LITTLE.name, "bfloat16", 512, 512, 512, cfg,
              backend="pallas_lean")
    cache.save()
    monkeypatch.setenv(C.ENV_VAR, path)

    from repro.core.control_tree import build_control_trees

    tree = build_control_trees(
        {"little": B.TPU_LITTLE}, 512, 512, 512, backend="pallas_interpret"
    )["little"]
    assert tree.block_source == "tuned"
    assert tree.backend == "pallas_lean_interpret"
    tree_hw = build_control_trees(
        {"little": B.TPU_LITTLE}, 512, 512, 512, backend="pallas"
    )["little"]
    assert tree_hw.backend == "pallas_lean"
    # XLA trees ignore kernel variants (blocks are decorative there).
    tree_xla = build_control_trees(
        {"little": B.TPU_LITTLE}, 512, 512, 512, backend="xla"
    )["little"]
    assert tree_xla.backend == "xla"
