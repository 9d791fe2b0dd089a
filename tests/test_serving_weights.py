"""The serving engine serves from its GEMM weights cast to bfloat16 once.

``ServingEngine`` builds its weight tree with ``serving_params``: the
leaves named in ``layers.GEMM_WEIGHTS`` in the compute dtype, every other
leaf the caller's own array.  Every reader casts those leaves to the
compute dtype before its GEMM, so rounding them once gives the numbers
that rounding them every step gave.  As tests, for every token-in
registry architecture at tiny widths:

  * the engine's tree: the allow-listed leaves in bfloat16, every other
    leaf the caller's own array (``is``), the caller's tree unchanged;
  * decode and bulk-prefill logits from the cast tree equal the float32
    tree's bitwise (a dense paged arch, latent attention with dropless
    MoE, dense lanes with biases, a shared-gate MoE, the Mamba2 hybrid);
  * the served tokens equal those of an engine handed pre-cast weights,
    and those of the float32 tree's programs;
  * the lowered decode step converts no float32 weight (the same step
    lowered with the float32 tree does);
  * the ``engine_weight_bytes`` gauge and ``EngineStats.weight_bytes``.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import spec
from chipbench.tests.test_deepseek_v2 import CONFIG as V2_CONFIG
from repro.configs import get_config, list_configs
from repro.core.asymmetric import AsymmetricMesh, DeviceClass
from repro.models import layers as L
from repro.models import model_zoo as Z
from repro.observability import metrics as MET
from repro.runtime.serving import ServingEngine, serving_params, weight_bytes

TOKEN_IN = [
    n for n in list_configs()
    if not get_config(n).embed_inputs and get_config(n).family != "encdec"
]

# (arch, paged): a dense paged arch, latent attention with dropless MoE,
# dense lanes with q/k/v biases, a shared-gate MoE, the Mamba2 hybrid.
LOGIT_CASES = [("deepseek-7b", "on"), ("deepseek-v2-lite", "on"), ("qwen2.5-32b", "off"),
               ("qwen2-moe-a2.7b", "on"), ("zamba2-2.7b", "off")]
TOKEN_CASES = [LOGIT_CASES[i] for i in (0, 1, 4)]

SEQ_CAP = 32
SLOTS_PER_POD = 3


@pytest.fixture(scope="module")
def zoo():
    """Each token-in arch at tiny widths with float32 weights, made once."""

    out = {}
    for name in TOKEN_IN:
        cfg = get_config(name).reduced()
        out[name] = cfg, Z.init_params(jax.random.PRNGKey(0), cfg)
    family = spec.load_family("deepseek_v2")
    dims = family.dims(V2_CONFIG)
    out["chipbench:deepseek_v2"] = (family.arch_config(V2_CONFIG, dims),
                                    jax.jit(family.init, static_argnums=1)(
                                        jax.random.PRNGKey(1), dims))
    return out


def _asym():
    return AsymmetricMesh([DeviceClass(name="big", n_pods=1, chips_per_pod=1)],
                          strategy="ca-das", batch_tile=1)


def _engine(cfg, params, paged="auto"):
    return ServingEngine(cfg, params, _asym(), seq_cap=SEQ_CAP, slots_per_pod=SLOTS_PER_POD,
                         paged=paged, class_sharded="off", pod_time_hook=None)


def _cast(path) -> bool:
    return path[-1].key in L.GEMM_WEIGHTS


def _with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


# ---------------------------------------------------------------------------
# The tree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", TOKEN_IN + ["chipbench:deepseek_v2"])
def test_engine_tree_casts_the_allow_list_and_shares_the_rest(zoo, arch):
    """The registry's trees, and chipbench's DeepSeek-V2 family tree as the
    benchmark makes its weights."""

    cfg, params = zoo[arch]
    before = [(w, np.asarray(w).copy()) for w in jax.tree.leaves(params)]
    eng = _engine(cfg, params)

    served = _with_paths(eng.params)
    assert jax.tree.structure(eng.params) == jax.tree.structure(params)
    assert any(_cast(path) for path, _ in served)
    for (path, mine), (_, got) in zip(_with_paths(params), served):
        if _cast(path):
            assert got.dtype == L.COMPUTE_DTYPE, path
            assert _bits_equal(got, mine.astype(L.COMPUTE_DTYPE)), path
        else:
            assert got is mine, path
    # The caller's tree is neither changed nor donated.
    for (w, copy), now in zip(before, jax.tree.leaves(params)):
        assert now is w and not w.is_deleted() and _bits_equal(w, copy)


def test_compute_dtype_leaves_pass_through_uncopied(zoo):
    cfg, params = zoo["internlm2-1.8b"]
    cast = serving_params(params)
    again = serving_params(cast)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(cast)))
    eng = _engine(cfg, cast)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(cast)))


def test_cast_keeps_each_leafs_sharding(zoo):
    """Leaves spread over a mesh of the host devices keep their sharding."""

    cfg, params = zoo["deepseek-7b"]
    mesh = jax.make_mesh((len(jax.devices()),), ("x",))
    n = mesh.shape["x"]

    def place(path, w):
        axis = next((i for i, d in enumerate(w.shape) if d % n == 0), None)
        parts = [None] * w.ndim
        if axis is not None:
            parts[axis] = "x"
        return jax.device_put(w, NamedSharding(mesh, P(*parts)))

    placed = jax.tree_util.tree_map_with_path(place, params)
    for (path, a), b in zip(_with_paths(placed), jax.tree.leaves(serving_params(placed))):
        assert b.sharding.is_equivalent_to(a.sharding, a.ndim), path
        assert b.dtype == (L.COMPUTE_DTYPE if _cast(path) else a.dtype)


# ---------------------------------------------------------------------------
# The numbers
# ---------------------------------------------------------------------------


def _prefill_inputs(eng, cfg, rng):
    n, plen = eng.n_slots, 9
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (n, plen)), jnp.int32),
             "live": jnp.ones((n,), bool)}
    if eng.paged:
        w = eng.pool.spec.pages_per_slot
        batch["page_table"] = jnp.arange(n * w, dtype=jnp.int32).reshape(n, w)
    plens = jnp.asarray(rng.integers(4, plen + 1, n), jnp.int32)
    return batch, plens


@pytest.mark.parametrize("arch,paged", LOGIT_CASES, ids=[a for a, _ in LOGIT_CASES])
def test_logits_from_the_cast_tree_equal_the_float32_trees(zoo, arch, paged):
    """The engine's own programs over both trees: bulk-prefill logits, the
    caches it writes, and the next decode step's logits, bit for bit."""

    cfg, params = zoo[arch]
    eng = _engine(cfg, params, paged)
    assert eng.paged == (paged == "on")
    batch, plens = _prefill_inputs(eng, cfg, np.random.default_rng(3))
    pos0 = jnp.zeros((eng.n_slots,), jnp.int32)
    bulk = Z.bulk_prefill_from_decode(eng._core)
    prefill = jax.jit(lambda p, s: bulk(p, batch, s, pos0, plens=plens))
    decode = jax.jit(eng._core)

    got = {}
    for name, tree in (("float32", params), ("served", eng.params)):
        logits, state = prefill(tree, eng.state)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        step, _ = decode(tree, dict(batch, tokens=nxt), state, plens)
        got[name] = logits, state, step
    (lg32, st32, d32), (lg16, st16, d16) = got["float32"], got["served"]
    assert _bits_equal(lg16, lg32)
    assert all(_bits_equal(a, b) for a, b in zip(jax.tree.leaves(st16), jax.tree.leaves(st32)))
    assert _bits_equal(d16, d32)


@pytest.mark.parametrize("arch,paged", TOKEN_CASES, ids=[a for a, _ in TOKEN_CASES])
def test_served_tokens_equal_a_precast_engines(zoo, arch, paged):
    """An engine handed float32 weights, one handed them pre-cast, and one
    whose programs run over the float32 tree (the per-step converts)
    serve the same tokens."""

    cfg, params = zoo[arch]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (5, 11, 3, 8)]
    precast = serving_params(params)
    served = {}
    for name, tree in (("float32", params), ("precast", precast), ("per_step", None)):
        eng = _engine(cfg, params if tree is None else tree, paged)
        if tree is None:
            eng.params = params
        for p in prompts:
            eng.submit(p, 6)
        served[name] = {c.rid: c.tokens.tolist() for c in eng.run()}
        if tree is precast:
            assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                              jax.tree.leaves(precast)))
    assert len(served["float32"]) == len(prompts)
    assert served["float32"] == served["precast"] == served["per_step"]


_CONVERT = re.compile(r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)xf32>\) -> tensor<\1xbf16>")


def _weight_converts(text: str, params) -> set[tuple[int, ...]]:
    """Shapes of the float32→bfloat16 converts in ``text`` whose operand has
    the shape of an allow-listed weight, whole or one layer of its stack."""

    shapes = set()
    for path, w in _with_paths(params):
        if _cast(path):
            shapes |= {tuple(w.shape), tuple(w.shape[1:])}
    found = {tuple(int(d) for d in m.split("x")) for m in _CONVERT.findall(text)}
    return found & shapes


@pytest.mark.parametrize("arch", TOKEN_IN)
def test_lowered_step_converts_no_float32_weight(zoo, arch):
    cfg, params = zoo[arch]
    eng = _engine(cfg, params)
    batch, pos = eng.step_inputs()
    served = eng._step.lower(eng.params, batch, eng.state, pos).as_text()
    assert _weight_converts(served, params) == set()
    # The same step over the caller's float32 tree converts its weights.
    per_step = eng._step.lower(params, batch, eng.state, pos).as_text()
    assert _weight_converts(per_step, params)


# ---------------------------------------------------------------------------
# The counter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "zamba2-2.7b"])
def test_weight_bytes_gauge(zoo, arch):
    cfg, params = zoo[arch]
    want = {"bfloat16": 0, "float32": 0}
    for path, w in _with_paths(params):
        want["bfloat16" if _cast(path) else "float32"] += w.size * (2 if _cast(path) else 4)
    eng = _engine(cfg, params)
    assert eng.stats.weight_bytes == want
    assert eng.stats.snapshot()["weight_bytes"] == want
    samples = MET.REGISTRY.snapshot()["engine_weight_bytes"]["samples"]
    assert {s["labels"]["dtype"]: s["value"] for s in samples} == want


# The bytes each benchmark configuration serves in bfloat16 at its published
# widths: 1.91 GiB for deepseek-7b-1chip (3 layers and the head), 2.41 GiB
# for deepseek-v2-lite-1chip (11 layers, 8 of 64 experts, and the head).
SERVED_BF16_BYTES = {"deepseek-7b-1chip": 2_053_111_808, "deepseek-v2-lite-1chip": 2_586_836_992}


@pytest.mark.parametrize("config", list(SERVED_BF16_BYTES))
def test_benchmark_trees_serve_their_gemm_weights_in_bfloat16(config):
    """The benchmark's configurations, shapes only: the bytes the engine
    serves in bfloat16, and the float32 leaves it shares with the caller."""

    import json
    from pathlib import Path

    c = json.loads((Path(spec.__file__).parent / "configs" / f"{config}.json").read_text())
    family = spec.load_family(c["chipbench"].get("family", "dense"))
    dims = family.dims(c)
    tree = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), dims))
    got = weight_bytes(jax.eval_shape(serving_params, tree))
    assert got["bfloat16"] == SERVED_BF16_BYTES[config]
    assert got["float32"] + 2 * got["bfloat16"] == weight_bytes(tree)["float32"]
