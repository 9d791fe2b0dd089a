"""Real-width compiles of the main path's kernels for a described TPU v5e.

Nothing runs: each case lowers and compiles for one chip of a ``v5e:2x2``
topology that is described, not attached, so the TPU compiler refuses here
what it would refuse on the chip — a block over the scoped-VMEM limit, a
slice off the tiling, a program over the chip's HBM.  Shapes are
internlm2-1.8b's published widths (d_model 2048, d_ff 8192, vocab 92544,
16/8 heads of 128) at the serving engine's ``chip_smoke.py`` slot table
(2 pods x 4 slots, 1024-token lanes).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

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


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    ma = compiled.memory_analysis()
    print(ma)
    assert "tpu_custom_call" in compiled.as_text()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES
    return ma


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


def _engine_page_size(monkeypatch) -> int:
    """The page size the engine derives for chip_smoke.py's slot table on a
    TPU, where the control trees run the Pallas GEMM kernels."""

    monkeypatch.setattr(X, "on_tpu", lambda: True)
    trees = AsymmetricMesh(biglittle_classes(chips_per_pod=1)).control_trees()
    return divisor_page_size(SEQ_CAP, min(t.block.bm for t in trees.values()))


@pytest.mark.parametrize("page_size", ["engine", 128])
def test_paged_attention_compiles(one_chip, page_size, monkeypatch):
    cfg = get_config("internlm2-1.8b")
    if page_size == "engine":
        page_size = _engine_page_size(monkeypatch)
    w = SEQ_CAP // page_size
    n_pages = 2 * (SLOTS // 2 + 1) * w  # per pod: 4 slot lanes + 1 phantom lane
    q = _spec((SLOTS, cfg.n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    pages = _spec((n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
                  jnp.bfloat16, one_chip)
    table = _spec((SLOTS, w), jnp.int32, one_chip)
    pos = _spec((SLOTS,), jnp.int32, one_chip)
    _compile(paged_attention_pallas, q, pages, pages, table, pos)


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
