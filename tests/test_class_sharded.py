"""True per-class programs in one SPMD step (shard_map over the pod axis).

The PR-3 acceptance criteria: a 2-class ``AsymmetricMesh`` step traced
through ``class_sharded`` provably uses each class's own tuned block
config (asserted via ``block_source`` provenance per shard *and* by
bit-equality with the explicit per-config kernel call), and the
single-class fallback is bit-identical to the no-shard_map path.

Runs on the 8 forced host devices the conftest sets up
(``--xla_force_host_platform_device_count=8``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import blocking as B
from repro.core import execution as X
from repro.core import schedule as S
from repro.core.asymmetric import AsymmetricMesh, DeviceClass, biglittle_classes
from repro.kernels.gemm import gemm_pallas
from repro.kernels.ops import gemm
from repro.launch.mesh import make_host_mesh
from repro.tuning import cache as C

RNG = np.random.default_rng(7)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2, reason="class_sharded tests need >=2 host devices"
)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _pod_mesh(n=2):
    return make_host_mesh(pod=n)


def _write_biglittle_cache(tmp_path, big_cfg, little_cfg, m, k, n,
                           big_backend="test", little_backend="test"):
    """Per-class tuned entries under both dtype keys: bfloat16 so the mesh
    trees themselves resolve tuned (block_source provenance), float32 so
    the f32 test calls re-resolve to the same shapes.  The ``*_backend``
    fields record a per-class micro-kernel variant ("test" is not a
    BACKENDS key, so the default kernel applies — the pre-variant
    behavior)."""

    import dataclasses

    path = str(tmp_path / "cache.json")
    cache = C.TuningCache(path=path)
    for dtype_name, nbytes in (("bfloat16", 2), ("float32", 4)):
        for spec, cfg, backend in (
            (B.TPU_V5E, big_cfg, big_backend),
            (B.TPU_LITTLE, little_cfg, little_backend),
        ):
            cache.put(spec.name, dtype_name, m, k, n,
                      dataclasses.replace(cfg, dtype_bytes=nbytes),
                      backend=backend)
    cache.save()
    return path


# ---------------------------------------------------------------------------
# Per-shard config routing (the acceptance criterion)
# ---------------------------------------------------------------------------


class TestPerShardRouting:
    def test_each_shard_runs_its_own_tuned_config(self, tmp_path, monkeypatch):
        """REPRO_TUNING_CACHE set with distinct per-class entries: the big
        pod's shard computes with big's tuned block config and the little
        pod's with little's — asserted via provenance AND numerics (each
        shard bit-equal to the explicit gemm_pallas call with that
        class's config)."""

        m = k = n = 128
        big_cfg = B.BlockConfig(bm=128, bk=128, bn=64, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=64, bk=128, bn=128, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, big_cfg, little_cfg, m, k, n)
        monkeypatch.setenv(C.ENV_VAR, path)

        am = AsymmetricMesh(
            biglittle_classes(chips_per_pod=1),
            tree_shape=(m, k, n), backend="pallas_interpret",
        )
        mesh = _pod_mesh(2)
        step = am.class_sharded(
            lambda x, w: gemm(x, w),
            mesh=mesh, in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        assert step.mixed

        # block_source provenance per shard: both classes tuned, each
        # shard owned by its own class with its own block config.
        assert [(p.pod, p.device_class, p.block_source) for p in step.provenance] \
            == [(0, "big", "tuned"), (1, "little", "tuned")]
        for prov, cfg in zip(step.provenance, (big_cfg, little_cfg)):
            assert (prov.block.bm, prov.block.bk, prov.block.bn) \
                == (cfg.bm, cfg.bk, cfg.bn)

        x = _rand((2 * m, k))  # rows split pod-major: big gets [:m], little [m:]
        w = _rand((k, n))
        out = np.asarray(jax.jit(step)(x, w))

        big_expect = np.asarray(gemm_pallas(x[:m], w, big_cfg, interpret=True))
        little_expect = np.asarray(gemm_pallas(x[m:], w, little_cfg, interpret=True))
        assert np.array_equal(out[:m], big_expect)
        assert np.array_equal(out[m:], little_expect)
        # The two configs genuinely differ, so this could not have been a
        # single-program run.
        assert big_cfg != little_cfg
        # Both class trees were traced, each under its own ambient context.
        assert set(step.trace_log) == {("big", "tuned"), ("little", "tuned")}

    def test_mixed_vs_primary_context_differ_in_program(self, tmp_path,
                                                        monkeypatch):
        # The pre-PR behavior ran everything under the primary tree: the
        # little rows then used big's config.  Under class_sharded the
        # little shard's result matches little's config — and differs from
        # what big's config computes only in provenance, not numerics
        # (same math), so assert on the trace instead: the old path logs
        # one class, the new path logs both.
        m = k = n = 128
        big_cfg = B.BlockConfig(bm=128, bk=128, bn=64, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=64, bk=128, bn=128, dtype_bytes=4)
        path = _write_biglittle_cache(tmp_path, big_cfg, little_cfg, m, k, n)
        monkeypatch.setenv(C.ENV_VAR, path)

        am = AsymmetricMesh(
            biglittle_classes(chips_per_pod=1),
            tree_shape=(m, k, n), backend="pallas_interpret",
        )
        with am.execution_context() as ctx:  # the old single-primary path
            assert ctx.device_class == "big"
        step = am.class_sharded(
            lambda x, w: gemm(x, w),
            mesh=_pod_mesh(2), in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        jax.jit(step)(_rand((2 * m, k)), _rand((k, n)))
        assert {c for c, _ in step.trace_log} == {"big", "little"}


# ---------------------------------------------------------------------------
# Per-shard micro-kernel variants (big -> pallas, little -> pallas_lean)
# ---------------------------------------------------------------------------


class TestPerShardVariantRouting:
    def test_mixed_step_runs_two_kernel_variants(self, tmp_path, monkeypatch):
        """One SPMD step, two micro-kernels: the cache records the lean
        variant as little's winner, so the mixed step runs the big shard
        through the pipelined kernel and the little shard through the
        VMEM-lean k-streaming kernel — proven by ShardProvenance AND by
        bit-equality of each shard with the explicit per-variant call."""

        from repro.kernels.gemm import gemm_pallas_lean

        m = k = n = 128
        big_cfg = B.BlockConfig(bm=128, bk=128, bn=64, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=64, bk=128, bn=128, dtype_bytes=4)
        path = _write_biglittle_cache(
            tmp_path, big_cfg, little_cfg, m, k, n,
            big_backend="pallas", little_backend="pallas_lean",
        )
        monkeypatch.setenv(C.ENV_VAR, path)

        am = AsymmetricMesh(
            biglittle_classes(chips_per_pod=1),
            tree_shape=(m, k, n), backend="pallas_interpret",
        )
        # The per-class trees name *different* dispatch-table entries,
        # each mapped onto the interpret family this CPU host runs.
        assert am.class_backends() == {
            "big": "pallas_interpret",
            "little": "pallas_lean_interpret",
        }

        step = am.class_sharded(
            lambda x, w: gemm(x, w),
            mesh=_pod_mesh(2), in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        assert step.mixed
        assert [(p.pod, p.device_class, p.backend) for p in step.provenance] \
            == [(0, "big", "pallas_interpret"),
                (1, "little", "pallas_lean_interpret")]

        x = _rand((2 * m, k))  # rows split pod-major: big [:m], little [m:]
        w = _rand((k, n))
        out = np.asarray(jax.jit(step)(x, w))

        big_expect = np.asarray(gemm_pallas(x[:m], w, big_cfg, interpret=True))
        little_expect = np.asarray(
            gemm_pallas_lean(x[m:], w, little_cfg, interpret=True)
        )
        assert np.array_equal(out[:m], big_expect)
        assert np.array_equal(out[m:], little_expect)
        assert set(step.trace_log) == {("big", "tuned"), ("little", "tuned")}

    def test_mixed_variant_step_bit_close_to_single_backend_run(
        self, tmp_path, monkeypatch
    ):
        """The lean variant changes scheduling, not numerics: the mixed
        two-variant step is bit-identical to the same step with every
        shard on the default pipelined kernel."""

        m = k = n = 128
        big_cfg = B.BlockConfig(bm=128, bk=128, bn=64, dtype_bytes=4)
        little_cfg = B.BlockConfig(bm=64, bk=128, bn=128, dtype_bytes=4)
        x, w = _rand((2 * m, k)), _rand((k, n))

        outs = {}
        for tag, little_backend in (("mixed", "pallas_lean"), ("single", "pallas")):
            path = _write_biglittle_cache(
                tmp_path / tag, big_cfg, little_cfg, m, k, n,
                big_backend="pallas", little_backend=little_backend,
            )
            monkeypatch.setenv(C.ENV_VAR, path)
            am = AsymmetricMesh(
                biglittle_classes(chips_per_pod=1),
                tree_shape=(m, k, n), backend="pallas_interpret",
            )
            step = am.class_sharded(
                lambda a, b: gemm(a, b),
                mesh=_pod_mesh(2), in_specs=(P("pod"), P()), out_specs=P("pod"),
            )
            outs[tag] = np.asarray(jax.jit(step)(x, w))  # repro: noqa=RPR003 -- two iterations, fresh step per cache config by design
        assert np.array_equal(outs["mixed"], outs["single"])

    def test_vmem_forced_lean_upgrade_no_cache(self, monkeypatch):
        """Without any tuned entries, the §5.3 shared-B-panel constraint
        itself forces little onto the lean kernel at big shapes: the lean
        working set keeps a 4x larger bm than the pipelined shrink."""

        monkeypatch.delenv(C.ENV_VAR, raising=False)
        am = AsymmetricMesh(
            biglittle_classes(chips_per_pod=1),
            tree_shape=(2048, 2048, 2048), backend="pallas_interpret",
        )
        trees = am.control_trees()
        assert am.class_backends() == {
            "big": "pallas_interpret",
            "little": "pallas_lean_interpret",
        }
        big, little = trees["big"], trees["little"]
        assert little.block.bk == big.block.bk       # shared B panel
        assert little.block.bm == 4 * 128            # lean keeps bm=512...
        from repro.core.control_tree import _rederive_bm

        pipelined = _rederive_bm(B.TPU_LITTLE, big.block, 2)
        assert little.block.bm > pipelined.bm        # ...vs 128 pipelined
        assert little.block.fits(B.TPU_LITTLE, double_buffer=False)
        assert not little.block.fits(B.TPU_LITTLE)
        # Provenance surfaces the variant per shard before any tracing.
        step = am.class_sharded(
            lambda a, b: gemm(a, b),
            mesh=_pod_mesh(2), in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        assert [p.backend for p in step.provenance] \
            == ["pallas_interpret", "pallas_lean_interpret"]


# ---------------------------------------------------------------------------
# Single-class fallback: bit-identical, no shard_map
# ---------------------------------------------------------------------------


class TestSingleClassFallback:
    def test_fallback_is_bit_identical(self):
        am = AsymmetricMesh(
            [DeviceClass("only", chips_per_pod=1, n_pods=2)],
            tree_shape=(128, 128, 128), backend="xla",
        )
        step = am.class_sharded(
            lambda x, w: gemm(x, w),
            mesh=_pod_mesh(2), in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        assert not step.mixed  # no shard_map on the fallback
        x, w = _rand((256, 128)), _rand((128, 128))
        with am.execution_context():
            expect = gemm(x, w)
        assert np.array_equal(np.asarray(step(x, w)), np.asarray(expect))

    def test_no_pod_axis_falls_back(self):
        am = AsymmetricMesh(biglittle_classes(chips_per_pod=1))
        step = am.class_sharded(
            lambda x, w: gemm(x, w),
            mesh=make_host_mesh(),  # no pod axis
            in_specs=(P("pod"), P()), out_specs=P("pod"),
        )
        assert not step.mixed

    def test_validation(self):
        ctxs = [X.default_context()]
        with pytest.raises(ValueError, match="out of range"):
            X.class_sharded(
                lambda x: x, mesh=_pod_mesh(2), contexts=ctxs, pod_class=[0, 1],
                in_specs=(P("pod"),), out_specs=P("pod"),
            )
        two = [X.default_context(device_class="a"),
               X.default_context(device_class="b")]
        with pytest.raises(ValueError, match="size"):
            X.class_sharded(
                lambda x: x, mesh=_pod_mesh(2), contexts=two,
                pod_class=[0, 1, 1],
                in_specs=(P("pod"),), out_specs=P("pod"),
            )
        with pytest.raises(ValueError, match="no 'pod' axis|has no"):
            X.class_sharded(
                lambda x: x, mesh=make_host_mesh(), contexts=two,
                pod_class=[0, 1],
                in_specs=(P("pod"),), out_specs=P("pod"),
            )


# ---------------------------------------------------------------------------
# Trainer integration: the mixed step trains, exactly
# ---------------------------------------------------------------------------


class TestTrainerMixedStep:
    def _fixture(self):
        from repro.configs import get_config
        from repro.data.pipeline import AsymmetricBatcher, SyntheticLM
        from repro.models import model_zoo as Z

        cfg = get_config("internlm2-1.8b").reduced()
        params = Z.init_params(jax.random.PRNGKey(0), cfg)
        loss_fn = Z.make_loss_fn(cfg)
        asym = AsymmetricMesh(
            [DeviceClass("a", chips_per_pod=1),
             DeviceClass("b", chips_per_pod=1, rel_throughput=0.5)],
            strategy="sas", batch_tile=2,
        )
        src = SyntheticLM(vocab=cfg.vocab, seed=0)
        bw = AsymmetricBatcher(src, asym).batch(0, 6, 16)
        batch = jax.tree.map(jnp.asarray, dict(bw.arrays))
        return cfg, params, loss_fn, asym, batch, bw.layout

    def test_weighted_epilogue_equals_manual_split(self):
        """The mixed step's gradients equal splitting the batch per pod in
        python and taking the mask-weighted sum — the shard_map adds no
        deviation of its own beyond float32 reduction order."""

        from repro.optim import adamw as O
        from repro.runtime.trainer import build_class_sharded_grad_step

        cfg, params, loss_fn, asym, batch, layout = self._fixture()
        c = layout.c_max
        outs = []
        for i in range(len(layout.sizes)):
            sub = {k: v[i * c : (i + 1) * c] for k, v in batch.items()}
            _, _, g = O.accumulate_gradients(loss_fn, params, sub, 1)
            outs.append((float(sub["mask"].sum()), g))
        total = sum(w for w, _ in outs)
        manual = jax.tree.map(
            lambda *gs: sum(w / total * g for (w, _), g in zip(outs, gs)),
            *[g for _, g in outs],
        )

        mesh = _pod_mesh(2)
        grad_fn = build_class_sharded_grad_step(loss_fn, asym, mesh)
        assert grad_fn.mixed
        _, _, g_mix = jax.jit(grad_fn)(params, batch)
        # Not bit-equal: XLA orders the weighted psum's float32 reduction
        # differently from the python sum (one ulp apart with jax 0.9).
        for a, b in zip(jax.tree.leaves(g_mix), jax.tree.leaves(manual)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-9)

    def test_n_micro_accumulation_weighted_by_valid_tokens(self):
        """Regression: with n_micro > 1 a shard's tail micro-batches are
        pure padding; the unweighted micro mean deflated that shard's
        loss/grads before the w_i/W scaling.  The masked-weighted micro
        accumulation must still give exactly the global masked mean (loss)
        and the bit-exact Σ w_ij·g_ij / W gradients."""

        from repro.optim import adamw as O
        from repro.runtime.trainer import build_class_sharded_grad_step

        cfg, params, loss_fn, asym, batch, layout = self._fixture()
        c, n_micro = layout.c_max, 2
        assert c % n_micro == 0
        # little's shard is half padding -> its second micro is all-pad.
        assert layout.sizes[1] <= c // 2

        l_plain, _, _ = O.accumulate_gradients(loss_fn, params, batch, 1)
        grad_fn = build_class_sharded_grad_step(
            loss_fn, asym, _pod_mesh(2), n_micro=n_micro
        )
        l_mix, _, g_mix = jax.jit(grad_fn)(params, batch)
        assert float(l_mix) == pytest.approx(float(l_plain), rel=1e-5)

        # Manual oracle: per pod, per micro, fp32-accumulate w_ij * g_ij
        # in the same order, divide by the global weight.
        mc = c // n_micro
        acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        total = 0.0
        per_pod = []
        for i in range(len(layout.sizes)):
            pod_acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            pod_w = 0.0
            for j in range(n_micro):
                lo = i * c + j * mc
                sub = {k: v[lo : lo + mc] for k, v in batch.items()}
                (_, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, sub)
                w = float(sub["mask"].sum())
                pod_acc = jax.tree.map(lambda a, x: a + w * x, pod_acc, g)
                pod_w += w
            per_pod.append((pod_acc, pod_w))
            total += pod_w
        # Mirror the implementation's order: per-shard mean, then w_i/W.
        manual = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        for pod_acc, pod_w in per_pod:
            scale = jnp.float32(pod_w) / total
            manual = jax.tree.map(
                lambda a, x: a + (x / max(pod_w, 1.0)) * scale, manual, pod_acc
            )
        for a, b in zip(jax.tree.leaves(g_mix), jax.tree.leaves(manual)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_mixed_loss_matches_global_masked_mean(self):
        from repro.optim import adamw as O
        from repro.runtime.trainer import build_class_sharded_grad_step

        cfg, params, loss_fn, asym, batch, _ = self._fixture()
        l_plain, _, _ = O.accumulate_gradients(loss_fn, params, batch, 1)
        grad_fn = build_class_sharded_grad_step(loss_fn, asym, _pod_mesh(2))
        l_mix, _, _ = jax.jit(grad_fn)(params, batch)
        assert float(l_plain) == pytest.approx(float(l_mix), rel=1e-5)

    def test_trainer_runs_and_exposes_provenance(self, tmp_path):
        from repro.configs import get_config
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.trainer import Trainer, TrainerConfig

        asym = AsymmetricMesh(
            [DeviceClass("fast", chips_per_pod=1),
             DeviceClass("slow", chips_per_pod=1, rel_throughput=0.5)],
            strategy="ca-das", batch_tile=1,
        )
        t = Trainer(
            get_config("internlm2-1.8b").reduced(),
            _pod_mesh(2),
            tcfg=TrainerConfig(steps=3, global_batch=8, seq_len=32,
                               ckpt_dir=str(tmp_path), ckpt_every=3),
            opt_cfg=AdamWConfig(lr=1e-3, total_steps=3, warmup_steps=1),
            asym=asym,
        )
        assert t.class_sharded_enabled()
        assert [(p.pod, p.device_class) for p in t.class_sharded_step.provenance] \
            == [(0, "fast"), (1, "slow")]
        hist = t.run()
        assert len(hist) == 3 and np.isfinite(hist[-1]["loss"])
        assert {c for c, _ in t.class_sharded_step.trace_log} == {"fast", "slow"}

    def test_trainer_auto_gate_and_force(self, tmp_path):
        from repro.configs import get_config
        from repro.runtime.trainer import Trainer, TrainerConfig

        asym = AsymmetricMesh(
            [DeviceClass("a", chips_per_pod=1),
             DeviceClass("b", chips_per_pod=1, rel_throughput=0.5)],
        )
        # No pod axis: auto stays off (legacy single-context path)...
        t = Trainer(
            get_config("internlm2-1.8b").reduced(), make_host_mesh(),
            tcfg=TrainerConfig(steps=1, global_batch=4, seq_len=16,
                               ckpt_dir=str(tmp_path)),
            asym=asym,
        )
        assert not t.class_sharded_enabled()
        assert t.class_sharded_step is None
        # ...and forcing it is a loud error, not a silent fallback.
        with pytest.raises(ValueError, match="class_sharded=True"):
            Trainer(
                get_config("internlm2-1.8b").reduced(), make_host_mesh(),
                tcfg=TrainerConfig(steps=1, global_batch=4, seq_len=16,
                                   ckpt_dir=str(tmp_path), class_sharded=True),
                asym=asym,
            )


# ---------------------------------------------------------------------------
# DynamicScheduler fed from per-shard timings (CA-DAS feedback closes)
# ---------------------------------------------------------------------------


class TestPerShardFeedback:
    def test_converges_to_calibrated_ratio(self):
        """Per-shard step times derived from the §5.2.2 wallclock
        calibration's measured per-class rates drive the scheduler to the
        calibrated ratio — the full DAS loop: mixed step out, per-shard
        timings in, chunk table re-derived."""

        from benchmarks.bench_schedulers import measure_class_step_times
        from repro.tuning.ratio import calibrate_class_ratios

        classes = biglittle_classes(chips_per_pod=1)
        meas = measure_class_step_times(classes, probe_shape=(128, 128, 128))
        cal = calibrate_class_ratios(classes, backend="wallclock",
                                     measurements=meas)
        per_unit = [m.seconds / m.units for m in meas]

        am = AsymmetricMesh(classes, strategy="ca-das", batch_tile=2)
        for _ in range(25):
            layout = am.batch_layout(64)
            times = [s * t + 1e-12 for s, t in zip(layout.sizes, per_unit)]
            am.observe_step(layout.sizes, times)

        sched_ratio = S.balanced_ratio(list(am.scheduler.rates))
        cal_ratio = S.balanced_ratio(list(cal.ratios))
        assert sched_ratio == pytest.approx(cal_ratio, rel=0.35)

    def test_bench_mixed_step_mode_runs(self):
        from benchmarks.bench_schedulers import mixed_step

        rows = mixed_step(n_rounds=2, global_batch=16,
                          probe_shape=(128, 128, 128), reps=1)
        names = [r.name for r in rows]
        assert "sched_mixed_step" in names
        assert any("shards=[0:big,1:little]" in r.derived for r in rows)


# ---------------------------------------------------------------------------
# Activation constraints under manual axes
# ---------------------------------------------------------------------------


class TestManualAxesGuard:
    def test_pod_spec_helpers(self):
        from repro.distributed import sharding as SH

        am = AsymmetricMesh(biglittle_classes(chips_per_pod=1))
        idx, spec = SH.pod_class_specs(am)
        assert list(idx) == [0, 1] and spec == P("pod")
        assert SH.pod_batch_specs({"tokens": 0, "mask": 0}) == \
            {"tokens": P("pod"), "mask": P("pod")}
        state = {"k": jnp.zeros((2, 4, 3))}
        assert SH.pod_state_specs(state) == {"k": P(None, "pod", None)}

    def test_constrain_drops_manual_axes(self):
        from repro.distributed import sharding as SH

        mesh = _pod_mesh(2)
        SH.use_mesh_for_activations(mesh)
        x = jnp.ones((4, 8))
        with SH.activation_manual_axes(("pod",)):
            # dp axes = ("pod", "data"); pod is manual -> only data (size
            # 1) survives; must trace without touching the pod axis.
            y = SH.constrain_batch(x)
        assert y.shape == x.shape
        assert SH._ACT_MANUAL == frozenset()  # restored on exit
