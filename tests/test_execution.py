"""Class-routed execution contexts: dispatch, nesting, per-class routing.

Covers the PR-2 acceptance criteria: with no context active, ``ops.gemm``
behaves bit-identically to the pre-context defaults; with a ``biglittle``
context active (and a tuning cache set), each class's matmuls run under
its own tuned control tree.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import blocking as B
from repro.core import execution as X
from repro.core.asymmetric import AsymmetricMesh, DeviceClass, biglittle_classes
from repro.core.control_tree import build_control_trees
from repro.kernels import ref
from repro.kernels.gemm import gemm_pallas
from repro.kernels.ops import gemm, gemm_with_tree
from repro.tuning import cache as C
from repro.tuning import ratio as R

RNG = np.random.default_rng(3)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _ctx(backend="xla", spec=B.TPU_V5E, shape=(256, 256, 256), name="t"):
    tree = build_control_trees({name: spec}, *shape, backend=backend)[name]
    return X.context_for_tree(tree)


# ---------------------------------------------------------------------------
# Context nesting / restore semantics
# ---------------------------------------------------------------------------


class TestContextScoping:
    def test_nesting_and_restore(self):
        assert X.current_context() is None
        a, b = _ctx(name="a"), _ctx(name="b")
        with a:
            assert X.current_context() is a
            with b:
                assert X.current_context() is b
                with a:  # reentrancy: the same object can nest again
                    assert X.current_context() is a
                assert X.current_context() is b
            assert X.current_context() is a
        assert X.current_context() is None

    def test_restore_on_exception(self):
        ctx = _ctx()
        with pytest.raises(RuntimeError):
            with ctx:
                raise RuntimeError("boom")
        assert X.current_context() is None

    def test_shared_context_concurrent_threads(self):
        # One long-lived context (e.g. a Trainer's) entered from several
        # threads: token stacks are thread-local, so exits never pop
        # another thread's token.
        import threading

        ctx = _ctx()
        errors = []

        def worker():
            try:
                for _ in range(50):
                    with ctx:
                        assert X.current_context() is ctx
                assert X.current_context() is None
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors

    def test_shared_context_interleaved_async_tasks(self):
        # Two asyncio tasks on one thread enter/exit the same context in
        # interleaved order; token stacks are per-task (ContextVar), so
        # neither task can pop the other's token.
        import asyncio

        ctx = _ctx()

        async def main():
            a_in, b_in, a_out = asyncio.Event(), asyncio.Event(), asyncio.Event()

            async def task_a():
                with ctx:
                    a_in.set()
                    await b_in.wait()  # b enters while a is inside
                a_out.set()
                assert X.current_context() is None

            async def task_b():
                await a_in.wait()
                with ctx:
                    b_in.set()
                    await a_out.wait()  # a exits while b is inside
                assert X.current_context() is None

            await asyncio.gather(task_a(), task_b())

        asyncio.run(main())
        assert X.current_context() is None

    def test_backend_table_is_the_vocabulary(self):
        assert set(X.BACKEND_NAMES) == {
            "xla",
            "pallas",
            "pallas_interpret",
            "pallas_lean",
            "pallas_lean_interpret",
            "paged_attn_xla",
            "paged_attn_pallas",
            "paged_attn_pallas_interpret",
        }
        # The table spans two op families; the GEMM view is the old set.
        assert set(X.GEMM_BACKEND_NAMES) == {
            "xla",
            "pallas",
            "pallas_interpret",
            "pallas_lean",
            "pallas_lean_interpret",
        }
        assert set(X.BACKEND_OPS) == set(X.BACKENDS)
        with pytest.raises(ValueError, match="unknown backend"):
            X.resolve_backend("mosaic")  # repro: noqa=RPR005 -- negative test: unknown name must raise
        # Op-family guards: a GEMM resolver must reject an attention
        # kernel and vice versa — a tree or CLI flag can never route a
        # GEMM into a paged-attention kernel.
        with pytest.raises(ValueError, match="not a GEMM"):
            X.resolve_backend("paged_attn_xla")
        with pytest.raises(ValueError, match="not a paged-attention"):
            X.resolve_paged_attn_backend("pallas")
        assert X.resolve_paged_attn_backend("auto") in X.BACKENDS
        # auto resolves to a concrete table entry (xla on this CPU host).
        assert X.resolve_backend("auto") in X.BACKENDS
        # Every table entry has a CPU-runnable interpret twin and a
        # buffering model — the invariants the parity harness and the
        # control trees rely on.
        for name in X.BACKENDS:
            assert X.interpret_twin(name) in X.BACKENDS
            assert isinstance(X.backend_double_buffers(name), bool)
        assert X.interpret_twin("pallas_lean") == "pallas_lean_interpret"
        assert not X.backend_double_buffers("pallas_lean")
        assert X.align_backend_family("pallas_lean", "pallas_interpret") \
            == "pallas_lean_interpret"
        assert X.align_backend_family("pallas_lean", "pallas") == "pallas_lean"
        # The family mapping is symmetric (regression): an interpret name
        # that leaked into a cache must come back compiled on a hardware
        # tree, never run the Python interpreter silently.
        assert X.align_backend_family("pallas_lean_interpret", "pallas") \
            == "pallas_lean"
        assert X.align_backend_family("pallas_interpret", "pallas") == "pallas"


# ---------------------------------------------------------------------------
# No context == today's defaults (bit-identical)
# ---------------------------------------------------------------------------


class TestNoContextDefaults:
    def test_bare_gemm_matches_explicit_xla(self):
        a, b = _rand((130, 70)), _rand((70, 50))
        base = gemm(a, b)  # auto -> xla on CPU, no context
        explicit = gemm(a, b, backend="xla")
        assert np.array_equal(np.asarray(base), np.asarray(explicit))

    def test_xla_context_is_behavior_neutral(self):
        a, b = _rand((2, 3, 64)), _rand((64, 32))
        base = gemm(a, b)
        with _ctx(backend="xla"):
            under_ctx = gemm(a, b)
        assert np.array_equal(np.asarray(base), np.asarray(under_ctx))

    def test_explicit_args_win_over_context(self):
        a, b = _rand((130, 70)), _rand((70, 50))
        base = gemm(a, b, backend="xla")
        with _ctx(backend="pallas_interpret", shape=(130, 70, 50)):
            forced = gemm(a, b, backend="xla")
        assert np.array_equal(np.asarray(base), np.asarray(forced))

    def test_resolve_block_config_defaults_analytical(self, monkeypatch):
        monkeypatch.delenv(C.ENV_VAR, raising=False)
        cfg, src = X.resolve_block_config(256, 256, 256, dtype_bytes=4,
                                          dtype_name="float32")
        assert src == "analytical"
        assert cfg == B.derive_block_config(256, 256, 256, dtype_bytes=4)

    def test_paged_attention_auto_follows_xla_context(self, monkeypatch):
        # As on a TPU: "auto" is the Pallas paged kernel whatever GEMM the
        # context runs; only a context's own paged_attn entry (the all-XLA
        # reference step sets it) routes dispatch elsewhere.
        monkeypatch.setattr(X, "on_tpu", lambda: True)
        seen = []
        for name in ("paged_attn_xla", "paged_attn_pallas"):
            monkeypatch.setitem(X.BACKENDS, name, lambda *a, n=name: seen.append(n))
        assert X.resolve_paged_attn_backend("auto") == "paged_attn_pallas"
        with _ctx(backend="xla") as ctx:
            assert ctx.paged_attn_backend() == "paged_attn_pallas"
            X.dispatch_paged_attention(None, None, None, None, None)
        ref = X.default_context(backend="xla", paged_attn="paged_attn_xla")
        with ref:
            assert ref.paged_attn_backend() == "paged_attn_xla"
            X.dispatch_paged_attention(None, None, None, None, None)
            X.dispatch_paged_attention(None, None, None, None, None,
                                       backend="paged_attn_pallas")
        assert seen == ["paged_attn_pallas", "paged_attn_xla", "paged_attn_pallas"]
        with pytest.raises(ValueError, match="not a paged-attention"):
            X.default_context(paged_attn="pallas").paged_attn_backend()


# ---------------------------------------------------------------------------
# Per-class routing under a biglittle mesh
# ---------------------------------------------------------------------------


class TestPerClassRouting:
    def test_biglittle_trees_differ(self):
        am = AsymmetricMesh(biglittle_classes(), tree_shape=(4096, 4096, 4096))
        trees = am.control_trees()
        big, little = trees["big"], trees["little"]
        assert big.block.bk == little.block.bk  # shared B panel (Loop 3)
        assert little.block.bm <= big.block.bm
        assert little.block.vmem_bytes() <= B.TPU_LITTLE.vmem_bytes * B.TPU_LITTLE.vmem_fill
        assert big.spec is B.TPU_V5E and little.spec is B.TPU_LITTLE

    def test_default_context_is_fastest_class(self):
        am = AsymmetricMesh(biglittle_classes())
        assert am.execution_context().device_class == "big"
        assert am.execution_context("little").device_class == "little"
        with pytest.raises(KeyError):
            am.execution_context("medium")

    def test_context_selects_class_tree(self):
        am = AsymmetricMesh(biglittle_classes(), tree_shape=(4096, 4096, 4096))
        trees = am.control_trees()
        with am.execution_context("little") as ctx:
            assert X.current_context().tree is trees["little"]
            assert ctx.spec is B.TPU_LITTLE
        with am.execution_context("big"):
            assert X.current_context().tree is trees["big"]

    def test_gemm_under_class_context_matches_oracle(self):
        # End to end through the interpret kernel: each class's context
        # produces the correct product with its own block shapes.
        a, b = _rand((256, 256)), _rand((256, 256))
        am = AsymmetricMesh(
            biglittle_classes(), tree_shape=(256, 256, 256),
            backend="pallas_interpret",
        )
        expect = np.asarray(ref.gemm_ref(a, b))
        for name in ("big", "little"):
            with am.execution_context(name):
                out = gemm(a, b)
            np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-4)

    def test_anchor_is_fastest_class_regardless_of_listing_order(self):
        # Slow class listed first must NOT anchor the shared B panel: the
        # trees sort by throughput, so big's bk anchors and little
        # re-derives — identical to the big-first listing.
        big, little = biglittle_classes()
        reversed_mesh = AsymmetricMesh([little, big], tree_shape=(4096, 4096, 4096))
        canonical = AsymmetricMesh([big, little], tree_shape=(4096, 4096, 4096))
        for name in ("big", "little"):
            assert (
                reversed_mesh.control_trees()[name].block
                == canonical.control_trees()[name].block
            )
        assert reversed_mesh.execution_context().device_class == "big"

    def test_gemm_with_tree_uses_trees_block(self):
        # The canonical-shape call reuses tree.block verbatim (shared-panel
        # structure preserved) — bit-identical to the explicit-config call.
        a, b = _rand((256, 256)), _rand((256, 256))
        tree = build_control_trees(
            {"x": B.TPU_V5E}, 256, 256, 256, backend="pallas_interpret"
        )["x"]
        via_tree = gemm_with_tree(a, b, tree)
        explicit = gemm_pallas(a, b, tree.block, interpret=True)
        assert np.array_equal(np.asarray(via_tree), np.asarray(explicit))


# ---------------------------------------------------------------------------
# Cache-hit vs analytical-fallback paths
# ---------------------------------------------------------------------------


def _write_biglittle_cache(tmp_path, big_cfg, little_cfg, m, k, n,
                           dtype_name="float32"):
    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    cache.put(B.TPU_V5E.name, dtype_name, m, k, n, big_cfg, backend="test")  # repro: noqa=RPR005 -- fixture provenance label, not a dispatch token
    cache.put(B.TPU_LITTLE.name, dtype_name, m, k, n, little_cfg, backend="test")  # repro: noqa=RPR005 -- fixture provenance label, not a dispatch token
    cache.save()
    return path


class TestTunedRouting:
    def test_trees_consume_per_class_cache(self, tmp_path, monkeypatch):
        # Distinctive tuned entries the analytical route would not pick;
        # same bk so the shared-B-panel constraint admits both.
        big_cfg = B.BlockConfig(bm=256, bk=128, bn=128, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=128, bk=128, bn=256, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, big_cfg, little_cfg, 256, 256, 256)
        monkeypatch.setenv(C.ENV_VAR, path)

        trees = build_control_trees(
            {"big": B.TPU_V5E, "little": B.TPU_LITTLE}, 256, 256, 256,
            dtype_bytes=4,
        )
        assert trees["big"].block_source == "tuned"
        assert trees["big"].block == big_cfg
        assert trees["little"].block_source == "tuned"
        assert trees["little"].block == little_cfg

    def test_tuned_entry_with_mismatched_bk_rejected(self, tmp_path, monkeypatch):
        # Under Loop-3 row partitioning the B panel is shared: a little
        # entry disagreeing on bk must fall back to the bm re-derivation.
        big_cfg = B.BlockConfig(bm=256, bk=128, bn=128, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=128, bk=256, bn=128, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, big_cfg, little_cfg, 256, 256, 256)
        monkeypatch.setenv(C.ENV_VAR, path)

        trees = build_control_trees(
            {"big": B.TPU_V5E, "little": B.TPU_LITTLE}, 256, 256, 256,
            dtype_bytes=4,
        )
        assert trees["little"].block_source == "analytical"
        assert trees["little"].block.bk == big_cfg.bk  # shared bk wins

    def test_analytical_fallback_without_cache(self, monkeypatch):
        monkeypatch.delenv(C.ENV_VAR, raising=False)
        trees = build_control_trees(
            {"big": B.TPU_V5E, "little": B.TPU_LITTLE}, 512, 512, 512
        )
        assert {t.block_source for t in trees.values()} == {"analytical"}

    def test_biglittle_matmuls_run_under_own_tuned_tree(self, tmp_path, monkeypatch):
        """The acceptance criterion end to end: REPRO_TUNING_CACHE set,
        biglittle contexts active — each class's gemm demonstrably executes
        with its own tuned block config (bit-equal to the explicit call)."""

        m = k = n = 256
        # Distinctive bm/bn per class; bk=256 agrees with the (bf16) tree's
        # shared B panel, so the rows-coarse guard admits both entries.
        big_cfg = B.BlockConfig(bm=256, bk=256, bn=128, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=128, bk=256, bn=256, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, big_cfg, little_cfg, m, k, n)
        monkeypatch.setenv(C.ENV_VAR, path)

        am = AsymmetricMesh(
            biglittle_classes(), tree_shape=(m, k, n), backend="pallas_interpret"
        )
        a, b = _rand((m, k)), _rand((k, n))
        for name, tuned in (("big", big_cfg), ("little", little_cfg)):
            with am.execution_context(name) as ctx:
                # Per-call resolution hits this class's cache entry (the
                # mesh trees themselves are bf16-keyed; the f32 call
                # re-resolves against the class's spec).
                assert ctx.block_config(m, k, n, "float32", 4) == tuned
                out = gemm(a, b)
            explicit = gemm_pallas(a, b, tuned, interpret=True)
            assert np.array_equal(np.asarray(out), np.asarray(explicit)), name

    def test_dtype_relabel_preserves_shared_panel(self, monkeypatch):
        # A float32 call at the canonical shape of a bf16-keyed tree keeps
        # the tree's block *shapes* (shared bk intact), only re-labelling
        # the operand bytes — it must not silently re-derive per spec.
        # (At 256^3 the float32 relabel fits little's VMEM.)
        monkeypatch.delenv(C.ENV_VAR, raising=False)
        am = AsymmetricMesh(biglittle_classes(), tree_shape=(256, 256, 256))
        trees = am.control_trees()
        little = am.execution_context("little")
        cfg = little.block_config(256, 256, 256, "float32", 4)
        blk = trees["little"].block
        assert (cfg.bm, cfg.bk, cfg.bn) == (blk.bm, blk.bk, blk.bn)
        assert cfg.dtype_bytes == 4
        assert cfg.bk == trees["big"].block.bk  # shared B panel survives

    def test_context_rejects_tuned_entry_off_shared_bk(self, tmp_path,
                                                       monkeypatch):
        # Same rule as build_control_trees: under a rows-coarse tree, a
        # per-call tuned entry disagreeing on the shared bk is rejected —
        # the dtype-relabelled tree block (panel intact) wins instead.
        m = k = n = 256
        off_bk = B.BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, off_bk, off_bk, m, k, n)
        monkeypatch.setenv(C.ENV_VAR, path)

        am = AsymmetricMesh(biglittle_classes(), tree_shape=(m, k, n))
        trees = am.control_trees()  # bf16-keyed: bk=256 shared panel
        assert trees["little"].block.bk == 256
        ctx = am.execution_context("little")
        cfg = ctx.block_config(m, k, n, "float32", 4)
        assert cfg.bk == 256  # relabelled tree block, not the off-bk entry
        assert cfg.dtype_bytes == 4

    def test_dtype_relabel_falls_back_when_vmem_overflows(self, monkeypatch):
        # At 1024^3 the bf16 blocks nearly fill VMEM; the f32 relabel does
        # not fit, so safety wins: re-derive a block this class can hold.
        monkeypatch.delenv(C.ENV_VAR, raising=False)
        am = AsymmetricMesh(biglittle_classes(), tree_shape=(1024, 1024, 1024))
        little = am.execution_context("little")
        cfg = little.block_config(1024, 1024, 1024, "float32", 4)
        assert cfg.fits(B.TPU_LITTLE)

    def test_hand_built_tree_block_is_authoritative(self):
        # ControlTree built directly (problem_shape=None): gemm_with_tree
        # must honor its block verbatim, as before the context layer.
        from repro.core.control_tree import ControlTree

        custom = B.BlockConfig(bm=128, bk=128, bn=256, dtype_bytes=4)
        tree = ControlTree(device_class="x", block=custom,
                           backend="pallas_interpret")
        a, b = _rand((256, 256)), _rand((256, 256))
        via_tree = gemm_with_tree(a, b, tree)
        explicit = gemm_pallas(a, b, custom, interpret=True)
        assert np.array_equal(np.asarray(via_tree), np.asarray(explicit))

    def test_hand_built_tree_clamps_to_smaller_call_shapes(self):
        # Regression: a hand-built tree applies to every call shape; a
        # 512-row block reused for a 128-row matmul must clamp to the
        # lane-padded call dims (pre-validation it silently padded; the
        # kernels' shape validation would now reject the oversize block).
        from repro.core.control_tree import ControlTree

        custom = B.BlockConfig(bm=512, bk=128, bn=256, dtype_bytes=4)
        tree = ControlTree(device_class="x", block=custom,
                           backend="pallas_interpret")
        ctx = X.context_for_tree(tree)
        cfg = ctx.block_config(128, 128, 64, "float32", 4)
        assert (cfg.bm, cfg.bk, cfg.bn) == (128, 128, 128)
        a, b = _rand((128, 128)), _rand((128, 64))
        via_tree = gemm_with_tree(a, b, tree)
        np.testing.assert_allclose(
            np.asarray(via_tree), np.asarray(ref.gemm_ref(a, b)),
            rtol=1e-5, atol=1e-4,
        )

    def test_hand_built_tree_beats_cache_across_dtypes(self, tmp_path,
                                                       monkeypatch):
        # A tuned cache entry must not override a hand-picked block even
        # when the call dtype differs from the block's: the relabelled
        # hand-built shapes win over the cache.
        from repro.core.control_tree import ControlTree

        cached = B.BlockConfig(bm=512, bk=128, bn=256, dtype_bytes=4)
        path = str(tmp_path / "cache.json")
        cache = C.TuningCache(path=path)
        cache.put(B.TPU_V5E.name, "float32", 256, 256, 256, cached, backend="t")  # repro: noqa=RPR005 -- fixture provenance label, not a dispatch token
        cache.save()
        monkeypatch.setenv(C.ENV_VAR, path)

        custom = B.BlockConfig(bm=256, bk=128, bn=128, dtype_bytes=2)
        tree = ControlTree(device_class="x", block=custom)
        ctx = X.context_for_tree(tree)
        cfg = ctx.block_config(256, 256, 256, "float32", 4)
        assert (cfg.bm, cfg.bk, cfg.bn) == (256, 128, 128)
        assert cfg.dtype_bytes == 4

    def test_context_block_config_resolves_off_bucket_shapes(self, tmp_path,
                                                             monkeypatch):
        # A call outside the tree's shape bucket re-resolves per spec: the
        # little class must get a block fitting its own (smaller) VMEM.
        monkeypatch.delenv(C.ENV_VAR, raising=False)
        am = AsymmetricMesh(biglittle_classes(), tree_shape=(256, 256, 256))
        ctx = am.execution_context("little")
        cfg = ctx.block_config(4096, 4096, 4096, "bfloat16", 2)
        assert cfg.fits(B.TPU_LITTLE)
        assert cfg == B.derive_block_config(4096, 4096, 4096, spec=B.TPU_LITTLE)


# ---------------------------------------------------------------------------
# CA tiles regression (satellite: slower classes get smaller strides)
# ---------------------------------------------------------------------------


class TestCaTiles:
    def test_biglittle_tiles_distinct(self):
        am = AsymmetricMesh(biglittle_classes(), strategy="ca-das", batch_tile=8)
        tiles = am.scheduler.tiles
        assert tiles == [8, 2]  # little at 0.25 rel throughput -> 8 * 0.25
        assert len(set(tiles)) == len(am.classes)

    def test_tiles_proportional_and_floored(self):
        am = AsymmetricMesh(
            [DeviceClass("a"), DeviceClass("b", rel_throughput=0.5),
             DeviceClass("c", rel_throughput=0.01)],
            strategy="ca-sas", batch_tile=4,
        )
        assert am.scheduler.tiles == [4, 2, 1]  # floored at 1, never 0

    def test_plain_strategies_keep_common_tile(self):
        am = AsymmetricMesh(biglittle_classes(), strategy="das", batch_tile=8)
        assert am.scheduler.tiles == [8, 8]


# ---------------------------------------------------------------------------
# Wallclock calibration off measured step times (satellite)
# ---------------------------------------------------------------------------


class TestWallclockCalibration:
    def test_measurements_enable_heterogeneous_wallclock(self):
        classes = biglittle_classes(chips_per_pod=1)
        meas = [
            R.ClassMeasurement(name="big", units=512, seconds=0.1),
            R.ClassMeasurement(name="little", units=512, seconds=0.4),
        ]
        cal = R.calibrate_class_ratios(classes, backend="wallclock",
                                       measurements=meas)
        assert cal.ratios[0] == 1.0
        assert cal.ratios[1] == pytest.approx(0.25)
        assert cal.times_s == (0.1, 0.4)

    def test_measurements_normalize_per_chip(self):
        # A wide pod must not look fast merely by having more chips.
        classes = [DeviceClass("wide", chips_per_pod=4),
                   DeviceClass("narrow", chips_per_pod=1)]
        meas = [R.ClassMeasurement("wide", units=400, seconds=1.0),
                R.ClassMeasurement("narrow", units=100, seconds=1.0)]
        cal = R.calibrate_class_ratios(classes, backend="wallclock",
                                       measurements=meas)
        assert cal.ratios == (1.0, 1.0)

    def test_missing_class_measurement_raises(self):
        classes = biglittle_classes(chips_per_pod=1)
        with pytest.raises(ValueError, match="missing"):
            R.calibrate_class_ratios(
                classes, backend="wallclock",
                measurements=[R.ClassMeasurement("big", 1, 1.0)],
            )

    def test_from_calibration_wallclock_measurements(self):
        classes = biglittle_classes(chips_per_pod=1)
        meas = [R.ClassMeasurement("big", 512, 0.1),
                R.ClassMeasurement("little", 512, 0.2)]
        mesh = AsymmetricMesh.from_calibration(
            classes, backend="wallclock", measurements=meas,
            strategy="ca-das", batch_tile=2,
        )
        assert mesh.calibration.backend == "wallclock"
        assert mesh.classes[1].rel_throughput == pytest.approx(0.5)
        layout = mesh.batch_layout(96)
        assert sum(layout.sizes) == 96
        assert layout.sizes[0] > layout.sizes[1]

    def test_heterogeneous_wallclock_still_rejected_without_measurements(self):
        with pytest.raises(ValueError, match="heterogeneous"):
            R.calibrate_class_ratios(biglittle_classes(), backend="wallclock")


# ---------------------------------------------------------------------------
# Two-stage coarse -> fine search (satellite)
# ---------------------------------------------------------------------------


class TestTwoStageSearch:
    def test_prefilter_prunes_expensive_timings(self):
        from repro.tuning import measure as M
        from repro.tuning import tune as T

        m = k = n = 1024
        calls = []

        def counting_backend(mm, kk, nn, cfg):
            calls.append(cfg)
            return M.cost_model_time(mm, kk, nn, cfg)

        full = T.search_shape(m, k, n, spec=B.TPU_V5E, dtype_bytes=2,
                              backend=counting_backend)
        n_full = len(calls)
        calls.clear()

        pruned = T.search_shape(
            m, k, n, spec=B.TPU_V5E, dtype_bytes=2, backend=counting_backend,
            prefilter=lambda mm, kk, nn, cfg: M.cost_model_time(mm, kk, nn, cfg),
            coarse_keep=4,
        )
        assert len(calls) < n_full
        assert pruned.n_pruned > 0
        # The prefilter is the same objective here, so no quality loss.
        assert pruned.best_time_s == pytest.approx(full.best_time_s)
        assert pruned.best_time_s <= pruned.analytical_time_s

    def test_tune_shapes_auto_enables_for_wallclock(self, tmp_path):
        from repro.tuning import tune as T

        # cost-model backend: two_stage auto stays off -> exhaustive count.
        res = T.tune_shapes([(512, 512, 512)], spec=B.TPU_V5E,
                            backend_name="cost-model")[0]
        assert res.n_pruned == 0

        res2 = T.tune_shapes([(512, 512, 512)], spec=B.TPU_V5E,
                             backend_name="cost-model", two_stage=True,
                             coarse_keep=3)[0]
        assert res2.n_pruned > 0
        assert res2.best_time_s <= res2.analytical_time_s
