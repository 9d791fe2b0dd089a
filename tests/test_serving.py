"""Persistent serving runtime: slot table, router, fused prefill, donation.

The ISSUE-5 acceptance criteria, as tests:

  * the fused bulk prefill writes a cache **bit-identical** to the
    token-by-token replay for every token-in zoo arch;
  * admission routes each request into its class's slot region, slots are
    reused after completion, and the slot budgets re-derive only past the
    scheduler's hysteresis threshold;
  * steady-state decode performs **zero** per-step host relayout (no
    ``pad_requests`` / chunk-table work inside the decode loop);
  * the donated decode-state path returns tokens identical to the
    undonated one (and the trainer's donated step identical params);
  * the mixed class-sharded engine's tokens are bit-identical to the
    one-shot ``pad_requests`` path on the 8 forced host devices, with
    ``ShardProvenance`` still proving the per-class programs.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_configs
from repro.core.asymmetric import AsymmetricMesh, DeviceClass, biglittle_classes
from repro.distributed import sharding as SH
from repro.launch import serve
from repro.launch.mesh import make_host_mesh
from repro.models import model_zoo as Z
from repro.runtime.serving import ServingEngine

TOKEN_IN = [
    n for n in list_configs()
    if not get_config(n).embed_inputs and get_config(n).family != "encdec"
]

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def zoo():
    out = {}
    for name in TOKEN_IN:
        cfg = get_config(name).reduced()
        out[name] = (cfg, Z.init_params(jax.random.PRNGKey(0), cfg))
    return out


def _biglittle(**kw):
    kw.setdefault("strategy", "ca-das")
    kw.setdefault("batch_tile", 1)
    return AsymmetricMesh(biglittle_classes(chips_per_pod=1), **kw)


def _single(**kw):
    kw.setdefault("strategy", "ca-das")
    kw.setdefault("batch_tile", 1)
    return AsymmetricMesh([DeviceClass("only", chips_per_pod=1)], **kw)


def _oneshot_mixed(cfg, params, prompts, gen_len, seq_cap, asym):
    """The legacy path verbatim: pad once, replay prompt token-by-token."""

    layout = asym.batch_layout(len(prompts))
    mesh = make_host_mesh(pod=asym.n_pods)
    step = serve.mixed_decode_step(
        cfg, asym, mesh, len(layout.sizes) * layout.c_max, seq_cap
    )
    padded, order = serve.pad_requests(prompts, layout)
    decode = jax.jit(step)
    state = Z.init_decode_state(cfg, padded.shape[0], seq_cap)
    tok = jnp.asarray(padded)
    plen = prompts.shape[1]
    logits = None
    for t in range(plen):
        logits, state = decode(params, {"tokens": tok[:, t:t + 1]}, state, jnp.int32(t))
    out = [padded]
    for t in range(plen, plen + gen_len):
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(np.asarray(nxt))
        logits, state = decode(params, {"tokens": nxt}, state, jnp.int32(t))
    return np.concatenate(out, axis=1)[order], step


# ---------------------------------------------------------------------------
# Fused bulk prefill: cache bit-identity with the token-by-token replay
# ---------------------------------------------------------------------------


class TestBulkPrefill:
    @pytest.mark.parametrize("arch", TOKEN_IN)
    def test_cache_bit_identical_to_replay(self, zoo, arch):
        """One fused forward over the whole prompt must write exactly the
        state the per-token decode replay writes — KV caches (linear and
        ring), SSM/conv states, shared-attention caches — plus the same
        last-position logits.  Prompt length exceeds mixtral's reduced
        window (8) so the ring wrap is exercised."""

        cfg, params = zoo[arch]
        b, plen = 2, 10
        seq_cap = plen + 4
        prompts = jnp.asarray(RNG.integers(0, cfg.vocab, (b, plen)), jnp.int32)

        state = Z.init_decode_state(cfg, b, seq_cap)
        decode = jax.jit(Z.make_decode_fn(cfg))
        logits = None
        for t in range(plen):
            logits, state = decode(
                params, {"tokens": prompts[:, t:t + 1]}, state, jnp.int32(t)
            )

        bulk = jax.jit(Z.make_prefill_fn(cfg, with_cache=True))
        logits2, state2 = bulk(
            params, {"tokens": prompts}, Z.init_decode_state(cfg, b, seq_cap),
            jnp.int32(0),
        )
        for a, bb in zip(jax.tree.leaves(state), jax.tree.leaves(state2)):
            assert np.array_equal(np.asarray(a), np.asarray(bb))
        assert np.array_equal(
            np.asarray(logits, np.float32), np.asarray(logits2, np.float32)
        )

    def test_vector_positions_bit_identical_to_scalar(self, zoo):
        """The slot engine's (B,) per-row position vector is value-identical
        to the scalar-position decode when the positions coincide — the
        property that lets persistent slots reproduce static batching."""

        cfg, params = zoo["mixtral-8x7b"]  # ring cache + MoE routing
        b, seq_cap = 3, 12
        state = Z.init_decode_state(cfg, b, seq_cap)
        tok = jnp.asarray(RNG.integers(0, cfg.vocab, (b, 1)), jnp.int32)
        decode = jax.jit(Z.make_decode_fn(cfg))
        l1, s1 = decode(params, {"tokens": tok}, state, jnp.int32(5))
        l2, s2 = decode(params, {"tokens": tok}, state, jnp.full((b,), 5, jnp.int32))
        assert np.array_equal(np.asarray(l1, np.float32), np.asarray(l2, np.float32))
        for a, bb in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            assert np.array_equal(np.asarray(a), np.asarray(bb))

    def test_heterogeneous_positions_decode(self, zoo):
        """Slots at different ages decode in one step (finite logits, and a
        position past the cache length writes nothing — retired lanes)."""

        cfg, params = zoo["internlm2-1.8b"]
        b, seq_cap = 3, 8
        state = Z.init_decode_state(cfg, b, seq_cap)
        tok = jnp.asarray(RNG.integers(0, cfg.vocab, (b, 1)), jnp.int32)
        pos = jnp.asarray([2, 5, seq_cap + 3], jnp.int32)  # last: phantom lane
        logits, s2 = jax.jit(Z.make_decode_fn(cfg))(
            params, {"tokens": tok}, state, pos
        )
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        # The out-of-range row wrote no cache entry.
        assert np.array_equal(np.asarray(s2["k"])[:, 2], np.asarray(state["k"])[:, 2])

    def test_rejects_non_token_batches(self, zoo):
        cfg, _ = zoo["internlm2-1.8b"]
        f = Z.make_prefill_fn(cfg, with_cache=True)
        with pytest.raises(ValueError, match="token-in"):
            f(None, {"embeds": jnp.zeros((1, 2, 4))}, None, 0)


# ---------------------------------------------------------------------------
# Admission router + slot table
# ---------------------------------------------------------------------------


class TestRouterAndSlots:
    def _engine(self, zoo, asym=None, **kw):
        cfg, params = zoo["internlm2-1.8b"]
        kw.setdefault("seq_cap", 32)
        kw.setdefault("slots_per_pod", 4)
        kw.setdefault("class_sharded", "off")
        return cfg, ServingEngine(cfg, params, asym or _biglittle(), **kw)

    def test_admission_lands_in_class_region(self, zoo):
        """Requests routed to a class must occupy slots inside that class's
        pods' regions, and the router split must track the chunk table."""

        cfg, eng = self._engine(zoo)
        prompts = RNG.integers(0, cfg.vocab, (6, 4), dtype=np.int32)
        rid_class = {}
        for p in prompts:
            rid = eng.submit(p, 3)
            ci = next(
                ci for ci, q in enumerate(eng.queues) if any(r.rid == rid for r in q)
            )
            rid_class[rid] = ci
        # Router split == chunk-table split aggregated by class.
        sizes = eng.asym.chunk_table(6).sizes()
        by_class = [0] * len(eng.asym.classes)
        for pod, s in enumerate(sizes):
            by_class[eng.asym.pod_class_indices()[pod]] += s
        assert sorted(rid_class.values()) == sorted(
            ci for ci, n in enumerate(by_class) for _ in range(n)
        )
        eng.admit()
        for slot, rid in enumerate(eng.slot_rid):
            if rid < 0:
                continue
            pod = slot // eng.c_max
            assert eng.asym.pod_class_indices()[pod] == rid_class[rid]

    def test_slot_reuse_after_completion(self, zoo):
        """A second wave reuses the freed slots, and (dense arch: row-local
        math) its tokens are bit-identical to a fresh engine's."""

        cfg, eng = self._engine(zoo, asym=_single())
        w1 = RNG.integers(0, cfg.vocab, (4, 6), dtype=np.int32)
        w2 = RNG.integers(0, cfg.vocab, (4, 6), dtype=np.int32)
        eng.generate(w1, 4)
        slots1 = sorted(c.slot for c in eng.completions)
        got = eng.generate(w2, 4)
        slots2 = sorted(c.slot for c in eng.completions[4:])
        assert slots1 == slots2  # the freed slots were re-admitted

        _, fresh = self._engine(zoo, asym=_single())
        assert np.array_equal(got, fresh.generate(w2, 4))
        assert eng.stats.completed == 8

    def test_mixed_prompt_lengths_stream(self, zoo):
        """Requests with different prompt lengths admit in ONE continuous-
        batching round (right-padded to the round max, each row's first
        token selected at its own last real prompt token) and decode
        concurrently at heterogeneous slot positions."""

        cfg, eng = self._engine(zoo, asym=_single(), seq_cap=64)
        short = RNG.integers(0, cfg.vocab, (4,), dtype=np.int32)
        long = RNG.integers(0, cfg.vocab, (9,), dtype=np.int32)
        r1 = eng.submit(short, 3)
        r2 = eng.submit(long, 5)
        done = {c.rid: c for c in eng.run()}
        assert set(done) == {r1, r2}
        assert len(done[r1].tokens) == 4 + 3
        assert len(done[r2].tokens) == 9 + 5
        assert eng.stats.admission_rounds == 1
        assert np.array_equal(done[r1].tokens[:4], short)
        assert np.array_equal(done[r2].tokens[:9], long)

    @pytest.mark.parametrize("paged", ["off", "on"])
    def test_step_logits_are_the_next_steps_logits(self, zoo, paged):
        """step_logits() evaluates the next decode step without advancing
        the engine; its argmax is the token step() then emits per slot."""

        cfg, eng = self._engine(zoo, asym=_single(), paged=paged)
        for n in (4, 7):
            eng.submit(RNG.integers(0, cfg.vocab, (n,), dtype=np.int32), 3)
        eng.admit()
        pos = eng.slot_pos.copy()
        logits = np.asarray(eng.step_logits())
        assert logits.shape == (eng.n_slots, 1, cfg.vocab)
        assert np.array_equal(eng.slot_pos, pos)  # nothing advanced
        # The lowered step is the donating program: the state aliases out.
        batch, q = eng.step_inputs()
        lowered = eng._step.lower(eng.params, batch, eng.state, q)
        assert lowered.compile().memory_analysis().alias_size_in_bytes > 0
        assert eng.step() == 2
        assert np.array_equal(np.asarray(eng.tokens)[:, 0], logits[:, -1].argmax(-1))

    def test_submit_validation(self, zoo):
        cfg, eng = self._engine(zoo, seq_cap=8)
        with pytest.raises(ValueError, match="seq_cap"):
            eng.submit(np.zeros(6, np.int32), 4)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(np.zeros(2, np.int32), 0)

    def test_rebalance_only_past_hysteresis(self, zoo):
        """Slot budgets re-derive only when the calibrated ratio drifts past
        the scheduler threshold — noise-level jitter never resizes the
        regions; a genuine straggler does."""

        cfg, params = zoo["internlm2-1.8b"]
        prompts = RNG.integers(0, cfg.vocab, (6, 4), dtype=np.int32)
        # Per-pod times consistent with the calibrated 4:1 ratio (the [5,1]
        # split gives per-pod times [5/4, 1/1]) plus ±2% measurement noise:
        # normalized-rate drift stays under the 5% threshold.
        jitter = ServingEngine(
            cfg, params, _biglittle(), seq_cap=32, slots_per_pod=5,
            class_sharded="off",
            pod_time_hook=lambda step: [1.25 * (1.02 if step % 2 else 0.98),
                                        1.00 * (0.99 if step % 3 else 1.01)],
        )
        jitter.generate(prompts, 4)
        jitter.generate(prompts, 4)  # second admission: budgets refresh
        assert jitter.stats.rebalances == 0

        straggler = ServingEngine(
            cfg, params, _biglittle(), seq_cap=32, slots_per_pod=5,
            class_sharded="off",
            # big pod suddenly 20x slower per unit than calibrated
            pod_time_hook=lambda step: [5.0, 0.1],
        )
        straggler.generate(prompts, 4)
        straggler.generate(prompts, 4)
        assert straggler.stats.rebalances >= 1

    def test_zero_host_relayout_in_decode_loop(self, zoo, monkeypatch):
        """Steady-state decode must not touch pad_requests or re-derive the
        chunk table: both are poisoned after admission and the loop still
        runs.  The one-shot path, by contrast, calls pad_requests."""

        cfg, eng = self._engine(zoo, asym=_single())
        prompts = RNG.integers(0, cfg.vocab, (4, 4), dtype=np.int32)
        for p in prompts:
            eng.submit(p, 6)

        def boom(*a, **k):
            raise AssertionError("host relayout inside the decode loop")

        monkeypatch.setattr(serve, "pad_requests", boom)
        eng.admit()
        monkeypatch.setattr(eng.asym, "chunk_table", boom)
        monkeypatch.setattr(eng.asym, "batch_layout", boom)
        while (eng.slot_rid >= 0).any():
            eng.step()
        assert eng.stats.completed == 4
        assert eng.stats.host_relayouts == 0


# ---------------------------------------------------------------------------
# Buffer donation
# ---------------------------------------------------------------------------


class TestDonation:
    def test_engine_donated_path_identical_tokens(self, zoo):
        cfg, params = zoo["internlm2-1.8b"]
        prompts = RNG.integers(0, cfg.vocab, (4, 5), dtype=np.int32)
        outs = {}
        for donate in (True, False):
            eng = ServingEngine(
                cfg, params, _single(), seq_cap=24, slots_per_pod=4,
                class_sharded="off", donate=donate,
            )
            outs[donate] = eng.generate(prompts, 5)
            if donate:
                # The donation is real: the pre-step state buffers are gone.
                old = eng.state
                eng.generate(prompts, 2)
                assert all(x.is_deleted() for x in jax.tree.leaves(old))
        assert np.array_equal(outs[True], outs[False])

    def test_serve_generate_donates_and_matches(self, zoo):
        cfg, params = zoo["internlm2-1.8b"]
        prompts = jnp.asarray(RNG.integers(0, cfg.vocab, (3, 6)), jnp.int32)
        out_d, _ = serve.generate(cfg, params, prompts, 4, 12, donate=True)
        out_n, _ = serve.generate(cfg, params, prompts, 4, 12, donate=False)
        assert np.array_equal(out_d, out_n)

    def test_trainer_donated_step_identical_params(self, tmp_path):
        """The trainer threads params/opt state through its jitted step with
        donate_argnums; the donated update must equal the undonated one."""

        from repro.optim import adamw as O
        from repro.runtime.trainer import Trainer, TrainerConfig

        def mk(sub):
            return Trainer(
                get_config("internlm2-1.8b").reduced(), make_host_mesh(),
                tcfg=TrainerConfig(steps=1, global_batch=4, seq_len=16,
                                   ckpt_dir=str(tmp_path / sub)),
                opt_cfg=O.AdamWConfig(lr=1e-3, total_steps=1, warmup_steps=1),
            )

        # Twin trainers (same seed -> identical jit-initialized state):
        # snapshotting the live buffers instead would pin them via the
        # Array's cached host copy and silently disable the donation
        # under test.
        t, ref = mk("don"), mk("ref")
        batch, _ = t._next_batch(0)
        batch_ref, _ = ref._next_batch(0)

        undonated = jax.jit(ref._make_train_step())  # same step fn, no donation
        p_ref, o_ref, _ = undonated(ref.params, ref.opt_state, batch_ref)
        old_params, old_opt = t.params, t.opt_state
        with t.mesh:
            p_don, o_don, _ = t.train_step(t.params, t.opt_state, batch)
        # Donation actually happened (params AND optimizer state)...
        assert all(x.is_deleted() for x in jax.tree.leaves(old_params))
        assert all(x.is_deleted() for x in jax.tree.leaves(old_opt))
        # ...and changed nothing.
        for a, b in zip(jax.tree.leaves(p_don), jax.tree.leaves(p_ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(o_don), jax.tree.leaves(o_ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Mixed class-sharded engine vs the one-shot pad_requests path
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 host devices")
class TestMixedEngineParity:
    @pytest.mark.parametrize("arch", ["internlm2-1.8b", "mixtral-8x7b"])
    def test_engine_bit_identical_to_one_shot(self, zoo, arch):
        """Same prompts, same greedy decode: the persistent class-sharded
        engine must emit exactly the one-shot mixed path's tokens —
        including through MoE capacity routing, whose cross-row coupling
        makes this sensitive to every lane of the slot table."""

        cfg, params = zoo[arch]
        SH.use_mesh_for_activations(None)
        b, plen, gen = 6, 8, 5
        seq_cap = plen + gen
        prompts = RNG.integers(0, cfg.vocab, (b, plen), dtype=np.int32)

        ref, step = _oneshot_mixed(
            cfg, params, prompts, gen, seq_cap, _biglittle()
        )
        asym = _biglittle()
        eng = ServingEngine(
            cfg, params, asym, seq_cap=seq_cap,
            slots_per_pod=asym.batch_layout(b).c_max,
        )
        got = eng.generate(prompts, gen)
        assert eng.mixed
        assert np.array_equal(got, ref)

        # ShardProvenance still proves the per-class programs (paper §5.3).
        assert [(p.pod, p.device_class) for p in eng.provenance] \
            == [(0, "big"), (1, "little")]
        assert [(p.pod, p.device_class, p.backend) for p in eng.provenance] \
            == [(p.pod, p.device_class, p.backend) for p in step.provenance]
        assert eng.stats.host_relayouts == 0

    def test_class_sharded_on_requires_devices(self, zoo):
        cfg, params = zoo["internlm2-1.8b"]
        big = AsymmetricMesh(
            [DeviceClass("a", chips_per_pod=1, n_pods=9),
             DeviceClass("b", chips_per_pod=1, rel_throughput=0.5)],
        )
        with pytest.raises(ValueError, match="devices"):
            ServingEngine(cfg, params, big, seq_cap=16, class_sharded="on")

    def test_engine_rejects_non_token_archs(self, zoo):
        cfg, params = zoo["internlm2-1.8b"]
        whisper = get_config("whisper-small").reduced()
        with pytest.raises(ValueError, match="token-in"):
            ServingEngine(whisper, None, _biglittle(), seq_cap=16)


# ---------------------------------------------------------------------------
# Serve CLI: steady-state timing split
# ---------------------------------------------------------------------------


class TestServeCLI:
    def _run(self, monkeypatch, capsys, *extra):
        argv = ["serve", "--arch", "internlm2-1.8b", "--reduced",
                "--batch", "4", "--prompt-len", "4", "--gen-len", "4",
                "--class-sharded", "off", *extra]
        monkeypatch.setattr("sys.argv", argv)
        # The persistent compile cache is the CLI's; keep this process off it.
        monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
        serve.main()
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1])

    def test_engine_json_reports_compile_and_steady_separately(
        self, monkeypatch, capsys
    ):
        js = self._run(monkeypatch, capsys)
        assert js["path"] == "engine"
        assert js["compile_s"] > 0
        assert js["tokens_per_s"] > 0
        # compile time is NOT folded into the throughput number
        assert js["tokens_per_s"] > js["batch"] * js["generated"] / js["wall_s"]
        assert js["engine"]["host_relayouts"] == 0

    def test_one_shot_json_same_tokens(self, monkeypatch, capsys):
        js_e = self._run(monkeypatch, capsys)
        js_o = self._run(monkeypatch, capsys, "--one-shot")
        assert js_o["path"] == "one-shot"
        assert js_o["compile_s"] > 0
        assert js_e["sample"] == js_o["sample"]
