"""Real-width compiles of the main path's kernels for a described TPU v5e.

Nothing runs: each case lowers and compiles for one chip of a ``v5e:2x2``
topology that is described, not attached, so the TPU compiler refuses here
what it would refuse on the chip — a block over the scoped-VMEM limit, a
slice off the tiling, a program over the chip's HBM.  Shapes are
internlm2-1.8b's published widths (d_model 2048, d_ff 8192, vocab 92544,
16/8 heads of 128) at the serving engine's ``chip_smoke.py`` slot table
(2 pods x 4 slots, 1024-token lanes), and for the paged KV arena's memory
guards deepseek-7b's (32/32 heads of 128, d_ff 11008, vocab 102400; 3
layers) at the benchmark's slot table (2 pods x 4 slots, 4096-token
lanes), and for the latent kernel and arenas DeepSeek-V2-Lite's (16 heads,
``kv_lora_rank`` 512, rope 64; 8 of 64 experts) at 2 pods x 64 slots.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import execution as X
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.kernels.gemm import gemm_pallas, gemm_pallas_lean
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import model_zoo as Z
from repro.runtime.paging import divisor_page_size

# The HBM the TPU compiler grants one v5e program ("... of 15.75G hbm").
V5E_HBM_BYTES = int(15.75 * 2**30)

SLOTS = 8          # chip_smoke.py: 2 pods x slots_per_pod=4
SEQ_CAP = 1024     # chip_smoke.py: per-slot cache length

DECODE_GEMMS = [(16, 2048, 8192), (16, 8192, 2048), (16, 2048, 92544)]
PREFILL_GEMM = (4096, 2048, 8192)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""

    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, hlo: bool = False, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    ma = compiled.memory_analysis()
    print(ma)
    assert "tpu_custom_call" in compiled.as_text()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES
    return (ma, compiled.as_text()) if hlo else ma


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", [gemm_pallas, gemm_pallas_lean],
                         ids=["pallas", "pallas_lean"])
@pytest.mark.parametrize("mkn", DECODE_GEMMS + [PREFILL_GEMM],
                         ids=lambda s: "x".join(map(str, s)))
def test_gemm_compiles(one_chip, kernel, mkn):
    m, k, n = mkn
    a = _spec((m, k), jnp.bfloat16, one_chip)
    b = _spec((k, n), jnp.bfloat16, one_chip)
    _compile(kernel, a, b)


def _engine_page_size(monkeypatch, seq_cap: int = SEQ_CAP) -> int:
    """The page size the engine derives for a slot table of ``seq_cap``
    lanes on a TPU, where the control trees run the Pallas GEMM kernels
    and paged attention the Pallas kernel."""

    monkeypatch.setattr(X, "on_tpu", lambda: True)
    trees = AsymmetricMesh(biglittle_classes(chips_per_pod=1)).control_trees()
    return divisor_page_size(seq_cap, min(t.block.bm for t in trees.values()))


@pytest.mark.parametrize("page_size", ["engine", 128])
def test_paged_attention_compiles(one_chip, page_size, monkeypatch):
    cfg = get_config("internlm2-1.8b")
    if page_size == "engine":
        page_size = _engine_page_size(monkeypatch)
    w = SEQ_CAP // page_size
    n_pages = 2 * (SLOTS // 2 + 1) * w  # per pod: 4 slot lanes + 1 phantom lane
    q = _spec((SLOTS, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    pages = _spec((cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim),
                  jnp.bfloat16, one_chip)
    table = _spec((SLOTS, w), jnp.int32, one_chip)
    pos = _spec((SLOTS,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    _compile(paged_attention_pallas, q, pages, pages, table, pos, layer)


def test_decode_step_compiles(one_chip):
    """The engine's decode step (argmax over the last logits, donated
    state) at full width, every matmul on the Pallas GEMM."""

    cfg = get_config("internlm2-1.8b")
    ctx = AsymmetricMesh(
        biglittle_classes(chips_per_pod=1), batch_tile=1, backend="pallas"
    ).execution_context()
    assert ctx.backend() == "pallas"
    decode = Z.make_decode_fn(cfg)

    def step(params, batch, state, pos):
        with ctx:
            logits, state = decode(params, batch, state, pos)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None], state

    place = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _spec(s.shape, s.dtype, one_chip), t)
    params = place(jax.eval_shape(lambda: Z.init_params(jax.random.PRNGKey(0), cfg)))
    state = place(Z.decode_state_spec(cfg, SLOTS, SEQ_CAP))
    batch = {"tokens": _spec((SLOTS, 1), jnp.int32, one_chip),
             "live": _spec((SLOTS,), jnp.bool_, one_chip)}
    pos = _spec((SLOTS,), jnp.int32, one_chip)
    _compile(step, params, batch, state, pos, donate_argnums=(2,))


LONG_LANE = 4096   # chipbench's deepseek-7b-1chip: 2 pods x 4 slots of 4096


def _place(tree, one_chip, dtype=None):
    return jax.tree.map(lambda s: _spec(s.shape, dtype or s.dtype, one_chip), tree)


def _paged_engine_program(cfg, rows, program, prompt_len, params, one_chip, monkeypatch):
    """The engine's paged decode step (``program="step"``) or bulk prefill
    of a ``prompt_len``-token prompt over ``rows`` slots of 4096-token
    lanes in 2 pods, as ``ServingEngine`` builds them on a TPU: ``(fn,
    args, state)``, the arena ``state`` donated as argument 2."""

    page_size = _engine_page_size(monkeypatch, LONG_LANE)
    w = LONG_LANE // page_size
    n_pages = 2 * (rows // 2 + 1) * w     # per pod: its slot lanes + 1 phantom lane
    ctx = AsymmetricMesh(biglittle_classes(chips_per_pod=1)).execution_context()
    assert ctx.paged_attn_backend() == "paged_attn_pallas"
    decode = Z.make_decode_fn(cfg)

    def core(params, batch, state, pos):
        with ctx:
            return decode(params, batch, state, pos)

    if program == "step":
        def fn(params, batch, state, pos):
            logits, state = core(params, batch, state, pos)
            return jnp.argmax(logits[:, -1], axis=-1), state
        tokens = (rows, 1)
    else:
        bulk = Z.bulk_prefill_from_decode(core)

        def fn(params, batch, state, plens):
            pos0 = jnp.zeros((rows,), jnp.int32)
            logits, state = bulk(params, batch, state, pos0, plens=plens)
            return jnp.argmax(logits[:, -1], axis=-1), state
        tokens = (rows, prompt_len)

    state = _place(jax.eval_shape(
        lambda: Z.init_decode_state_paged(cfg, n_pages, page_size, rows=rows)), one_chip)
    batch = {"tokens": _spec(tokens, jnp.int32, one_chip),
             "page_table": _spec((rows, w), jnp.int32, one_chip),
             "live": _spec((rows,), jnp.bool_, one_chip)}
    return fn, (params, batch, state, _spec((rows,), jnp.int32, one_chip)), state


def _bf16_params(cfg, one_chip):
    return _place(jax.eval_shape(lambda: Z.init_params(jax.random.PRNGKey(0), cfg)),
                  one_chip, jnp.bfloat16)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_paged_arena_is_updated_in_place(one_chip, program, monkeypatch):
    """The engine's paged decode step and its bulk prefill (a 16-token
    prompt), arena donated, at deepseek-7b's widths over 3 layers: the
    stacked K/V arenas alias input to output, and no program keeps a
    temporary as large as one layer's K pool — the arena is carried
    through the layer loop and updated in place, never sliced out per
    layer, transposed for the kernel, written back or copied.

    Every weight is bfloat16 here, so no convert of a weight (the engine
    converts none) can hide the arena's copies."""

    cfg = dataclasses.replace(get_config("deepseek-7b"), n_layers=3)
    fn, args, state = _paged_engine_program(cfg, SLOTS, program, 16,
                                            _bf16_params(cfg, one_chip), one_chip, monkeypatch)
    ma = _compile(fn, *args, donate_argnums=(2,))

    arena_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state))
    layer_pool_bytes = arena_bytes // (2 * cfg.n_layers)
    assert ma.alias_size_in_bytes >= arena_bytes
    assert ma.temp_size_in_bytes < layer_pool_bytes


V2_SLOTS = 128     # chipbench's deepseek-v2-lite-1chip: 2 pods x 64 slots of 4096


def _v2_config(n_layers):
    """DeepSeek-V2-Lite at published widths, ``n_layers`` deep (layer 0
    dense), this chip holding 8 of its 64 routed experts."""

    cfg = get_config("deepseek-v2-lite")
    moe = dataclasses.replace(cfg.moe, n_experts=8, n_router=64)
    return dataclasses.replace(cfg, n_layers=n_layers, moe=moe)


def test_paged_latent_kernel_compiles(one_chip, monkeypatch):
    """The latent kernel over the engine's arena reads it where it lies:
    no temporary (a latent row of 576 in one arena would be laid out
    transposed by the compiler and copied for the kernel every call)."""

    from repro.kernels.paged_attention import paged_mla_pallas

    cfg = _v2_config(11)
    m = cfg.mla
    page_size = _engine_page_size(monkeypatch, LONG_LANE)
    w = LONG_LANE // page_size
    n_pages = 2 * (V2_SLOTS // 2 + 1) * w
    args = (_spec((V2_SLOTS, m.n_heads, m.kv_lora_rank), jnp.bfloat16, one_chip),
            _spec((V2_SLOTS, m.n_heads, m.qk_rope_head_dim), jnp.bfloat16, one_chip),
            _spec((cfg.n_layers, n_pages, page_size, m.kv_lora_rank), jnp.bfloat16, one_chip),
            _spec((cfg.n_layers, n_pages, m.qk_rope_head_dim, page_size), jnp.bfloat16, one_chip),
            _spec((V2_SLOTS, w), jnp.int32, one_chip), _spec((V2_SLOTS,), jnp.int32, one_chip),
            _spec((), jnp.int32, one_chip))
    ma = _compile(lambda *a: paged_mla_pallas(*a, scale=m.scale), *args)
    assert ma.temp_size_in_bytes < 2**20


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_paged_latent_arena_is_updated_in_place(one_chip, program, monkeypatch):
    """The engine's paged decode step and bulk prefill at DeepSeek-V2-Lite's
    widths over 3 layers (bfloat16 weights): both latent arenas alias input
    to output, no temporary is as large as one layer's ``c_kv`` pool, and
    no operation copies or reshapes an arena — the roped-key arena
    (positions minor) is written in place, never flattened for the scatter
    and relaid out (that costs a temporary of the whole arena, and its
    copy, in every layer)."""

    cfg = _v2_config(3)
    fn, args, state = _paged_engine_program(cfg, V2_SLOTS, program, 16,
                                            _bf16_params(cfg, one_chip), one_chip, monkeypatch)
    ma, text = _compile(fn, *args, hlo=True, donate_argnums=(2,))

    arenas = state["pages_c"], state["pages_pe"]
    for a in arenas:
        shape = ",".join(map(str, a.shape))
        assert not re.search(rf"= bf16\[{shape}\]\S* (copy|reshape)\(", text), shape
    arena_bytes = sum(a.size * a.dtype.itemsize for a in arenas)
    c_pool_bytes = arenas[0].size * arenas[0].dtype.itemsize // cfg.n_layers
    assert ma.alias_size_in_bytes >= arena_bytes
    assert ma.temp_size_in_bytes < c_pool_bytes


# The two cells' engines at their full geometry, with the benchmark's
# longest prompt: (config, slots over 2 pods).
CELLS = {"deepseek-7b-1chip": (dataclasses.replace(get_config("deepseek-7b"), n_layers=3), SLOTS),
         "deepseek-v2-lite-1chip": (_v2_config(11), V2_SLOTS)}


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_engine_fits_beside_the_float32_weights(one_chip, cell, program, monkeypatch):
    """Each cell's paged step and 1536-token bulk prefill, compiled over the
    engine's own weight tree (``serving_params``: GEMM weights bfloat16,
    the rest float32), fit the chip beside the caller's float32 copies of
    the cast weights, which the benchmark keeps for its reference check."""

    from repro.models.layers import GEMM_WEIGHTS
    from repro.runtime.serving import serving_params

    cfg, rows = CELLS[cell]
    tree = jax.eval_shape(lambda: Z.init_params(jax.random.PRNGKey(0), cfg))
    params = _place(jax.eval_shape(serving_params, tree), one_chip)
    fn, args, _ = _paged_engine_program(cfg, rows, program, 1536, params, one_chip, monkeypatch)
    ma = _compile(fn, *args, donate_argnums=(2,))

    caller_f32 = sum(4 * w.size for path, w in jax.tree_util.tree_flatten_with_path(tree)[0]
                     if path[-1].key in GEMM_WEIGHTS)
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"{cell} {program}: program {used / 2**30:.2f} GiB + float32 weights "
          f"{caller_f32 / 2**30:.2f} GiB")
    assert used + caller_f32 <= V5E_HBM_BYTES
