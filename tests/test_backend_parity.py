"""Cross-backend kernel parity harness.

Every entry of ``execution.BACKENDS`` — present and future — is run
against a pure-jnp oracle over a grid of shapes and dtypes (f32, bf16)
with per-dtype tolerances; the grid is chosen per *op family*
(``execution.BACKEND_OPS``): GEMM backends against ``kernels/ref.gemm_
ref`` over ragged/non-multiple-of-block/1-row/1-col edges, paged-
attention backends against ``kernels/ref.paged_attention_ref`` over
GQA/MHA head layouts, page sizes, sentinel-holding tables, and ring-
wrapped positions.  The parametrizations iterate the dispatch table
itself, so **adding a backend automatically adds its parity coverage**:
a new entry that lacks an interpret twin (the CPU route,
``execution.INTERPRET_TWIN``) fails ``test_every_backend_has_a_
cpu_route`` before it can ship untested, and an entry missing from
``BACKEND_OPS`` fails the vocabulary test in tests/test_execution.py.

Pallas variants execute through their interpret twins (the kernel *body*
is identical; Mosaic compilation is the only thing interpret mode skips),
which is how this suite runs on the CPU-only CI host.  A hypothesis sweep
(marked ``slow``; the CI parity lane raises its example count via
``$REPRO_PARITY_EXAMPLES``) fuzzes shapes and block configs beyond the
fixed grid.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import execution as X
from repro.core.blocking import TPU_V5E, BlockConfig, derive_block_config
from repro.kernels import ref

RNG = np.random.default_rng(11)

# Ragged, non-multiple-of-block, and degenerate 1-row/1-col problems.
SHAPES = [
    (128, 128, 128),     # exact single block
    (256, 512, 128),     # multi-block, exact
    (300, 200, 180),     # ragged in all dims
    (64, 1024, 96),      # sub-block m/n, long k
    (1, 384, 128),       # 1-row edge
    (128, 256, 1),       # 1-col edge
    (1, 128, 1),         # 1x1 output
    (257, 129, 131),     # off-by-one past block boundaries
]

# allclose tolerance per accumulation dtype: fp32 accumulators everywhere,
# but bf16 operands quantize the inputs.
TOLS = {jnp.float32: dict(rtol=1e-4, atol=1e-4), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}

DTYPES = sorted(TOLS, key=str)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _run_backend(backend, a, b, cfg):
    """Dispatch through the table via the backend's CPU-runnable twin."""

    return X.BACKENDS[X.interpret_twin(backend)](a, b, cfg, a.dtype)


def test_every_backend_has_a_cpu_route():
    """The growth guard: a BACKENDS entry without a registered interpret
    twin cannot be parity-tested and must not exist."""

    for name in X.BACKENDS:
        twin = X.interpret_twin(name)  # raises on a missing registration
        assert twin in X.BACKENDS
    # And the twin map carries no stale names for removed backends.
    assert set(X.INTERPRET_TWIN) == set(X.BACKENDS)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("backend", sorted(X.GEMM_BACKEND_NAMES))
def test_backend_matches_oracle(backend, shape, dtype):
    m, k, n = shape
    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    # A fixed single-tile config exercises the padding paths on every
    # ragged/edge shape; XLA ignores it.
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=a.dtype.itemsize)
    out = _run_backend(backend, a, b, cfg)
    expect = ref.gemm_ref(a, b)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


@pytest.mark.parametrize("backend", sorted(X.GEMM_BACKEND_NAMES))
def test_backend_default_config_resolution(backend):
    """cfg=None resolves per backend (lean derives single-buffered) and
    still matches the oracle."""

    a, b = _rand((130, 70), jnp.float32), _rand((70, 50), jnp.float32)
    out = X.BACKENDS[X.interpret_twin(backend)](a, b, None, a.dtype)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.gemm_ref(a, b)), rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# Paged-attention parity (op family "paged_attn")
# ---------------------------------------------------------------------------

PAGED_BACKENDS = sorted(n for n, op in X.BACKEND_OPS.items() if op == "paged_attn")

# (batch, Hq, Hkv, Dh, page_size, table_width, arena_pages): GQA and MHA
# head layouts, single- and multi-page lanes, arenas larger than any one
# row needs (so tables hold genuinely scattered page ids).
PAGED_CASES = [
    (3, 4, 2, 16, 8, 4, 16),
    (5, 8, 8, 32, 16, 2, 12),
    (2, 4, 1, 8, 4, 8, 40),
    (4, 2, 2, 64, 32, 1, 6),
]

# Every case reads the last layer of a stacked arena whose layers hold
# different values: a backend that ignored the layer (or offset it
# wrongly) would read another layer's keys.
ARENA_LAYERS = 3


def _paged_case(b, hq, hkv, dh, ps, w, pages, dtype, seed=0):
    """Random stacked head-major arena ``(L, P, Hkv, ps, Dh)`` + per-row
    tables: allocated prefix pages, SENTINEL beyond, positions drawn past
    ``s_cache`` too (the ring-wrapped row attends its whole logical
    cache)."""

    from repro.runtime.paging import SENTINEL

    rng = np.random.default_rng(seed + b * 131 + hq * 17 + ps)
    shape = (ARENA_LAYERS, pages, hkv, ps, dh)
    q = jnp.asarray(rng.normal(size=(b, hq, dh)), dtype)
    pk = jnp.asarray(rng.normal(size=shape), dtype)
    pv = jnp.asarray(rng.normal(size=shape), dtype)
    pos = rng.integers(0, w * ps + ps, size=(b,))
    table = np.full((b, w), SENTINEL, np.int32)
    for r in range(b):
        need = min(int(pos[r]) // ps + 1, w)
        table[r, :need] = rng.choice(pages, size=need, replace=False)
    return q, pk, pv, jnp.asarray(table), jnp.asarray(pos, jnp.int32)


def _paged_numpy(q, pk, pv, table, pos, layer):
    """The layout spelled out element by element: row ``b``'s key ``t``
    is ``arena[layer, table[b, t // ps], h, t % ps]``; plain float64
    softmax attention over its valid prefix."""

    q, pk, pv = (np.asarray(a, np.float64) for a in (q, pk, pv))
    table, pos = np.asarray(table), np.asarray(pos)
    b, hq, d = q.shape
    _, _, hkv, ps, _ = pk.shape
    s_cache = table.shape[1] * ps
    out = np.zeros((b, hq, d))
    for r in range(b):
        n = min(int(pos[r]) + 1, s_cache)
        keys = np.stack([pk[layer, table[r, t // ps], :, t % ps] for t in range(n)])
        vals = np.stack([pv[layer, table[r, t // ps], :, t % ps] for t in range(n)])
        for h in range(hq):
            kv = h // (hq // hkv)
            sc = keys[:, kv] @ q[r, h] / np.sqrt(d)
            pr = np.exp(sc - sc.max())
            out[r, h] = (pr / pr.sum()) @ vals[:, kv]
    return out


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize(
    "case", PAGED_CASES, ids=lambda c: "b{}h{}kv{}d{}ps{}w{}p{}".format(*c)
)
@pytest.mark.parametrize("backend", PAGED_BACKENDS)
def test_paged_attention_matches_oracle(backend, case, dtype):
    q, pk, pv, table, pos = _paged_case(*case, dtype)
    layer = ARENA_LAYERS - 1
    out = X.BACKENDS[X.interpret_twin(backend)](q, pk, pv, table, pos, layer)
    expect = ref.paged_attention_ref(q, pk, pv, table, pos, layer)
    assert out.shape == expect.shape and out.dtype == expect.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **TOLS[dtype]
    )


PAGED_ROUTES = {
    **{name: X.BACKENDS[X.interpret_twin(name)] for name in PAGED_BACKENDS},
    "oracle": ref.paged_attention_ref,
}


@pytest.mark.parametrize("layer", range(ARENA_LAYERS))
@pytest.mark.parametrize("route", sorted(PAGED_ROUTES))
def test_paged_attention_reads_its_layer(route, layer):
    """Each backend — and the oracle itself — against the layout spelled
    out element by element, at every layer of the stacked arena, with the
    layer passed as a traced scalar as the decode loop passes it."""

    q, pk, pv, table, pos = _paged_case(*PAGED_CASES[0], jnp.float32)
    out = jax.jit(PAGED_ROUTES[route])(q, pk, pv, table, pos, jnp.int32(layer))
    expect = _paged_numpy(q, pk, pv, table, pos, layer)
    np.testing.assert_allclose(np.asarray(out, np.float64), expect,
                               rtol=1e-4, atol=1e-4)
    if layer:  # the layers differ, so reading the wrong one would show
        other = _paged_numpy(q, pk, pv, table, pos, 0)
        assert not np.allclose(expect, other, rtol=1e-2, atol=1e-2)


def test_paged_dispatch_routes_by_op_family():
    """The funnel: auto resolves inside the paged_attn family, and the
    result matches the oracle (tolerance — auto may pick either route)."""

    q, pk, pv, table, pos = _paged_case(*PAGED_CASES[0], jnp.float32)
    out = X.dispatch_paged_attention(q, pk, pv, table, pos, 1, backend="auto")
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.paged_attention_ref(q, pk, pv, table, pos, 1), np.float32),
        rtol=1e-4, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# Hypothesis sweep (the CI parity lane: pytest -m slow tests/test_backend_parity.py)
# ---------------------------------------------------------------------------

# Only the fuzz sweep needs hypothesis; the fixed grid above must keep
# running without it (so no module-level importorskip).
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is an optional dep
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _EXAMPLES = int(os.environ.get("REPRO_PARITY_EXAMPLES", "10"))

    dims = st.integers(min_value=1, max_value=300)
    blocks = st.sampled_from([64, 128, 256])

    @pytest.mark.slow
    @settings(
        max_examples=_EXAMPLES,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    @given(m=dims, k=dims, n=dims, bm=blocks, bk=blocks, bn=blocks, data=st.data())
    def test_backend_parity_fuzz(m, k, n, bm, bk, bn, data):
        """Random (shape, block, backend, dtype): every backend agrees
        with the oracle whenever the config passes shape validation."""

        backend = data.draw(
            st.sampled_from(sorted(X.GEMM_BACKEND_NAMES)), label="backend"
        )
        dtype = data.draw(st.sampled_from(DTYPES), label="dtype")
        # Deterministic data per drawn example (hypothesis replays shrink
        # candidates; a shared advancing RNG would make failures flaky).
        rng = np.random.default_rng(m * 7919 + k * 104729 + n)
        a = jnp.asarray(rng.normal(size=(m, k)), dtype)
        b = jnp.asarray(rng.normal(size=(k, n)), dtype)
        cfg = BlockConfig(bm=bm, bk=bk, bn=bn, dtype_bytes=a.dtype.itemsize)
        from repro.kernels.gemm import validate_block_config

        try:
            validate_block_config(m, k, n, cfg)
        except ValueError:
            # Oversized blocks are a loud error by contract (the bugfix);
            # parity only covers valid configs.
            return
        out = _run_backend(backend, a, b, cfg)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(ref.gemm_ref(a, b), np.float32),
            **TOLS[dtype],
        )

    @pytest.mark.slow
    @settings(max_examples=max(5, _EXAMPLES // 2), deadline=None)
    @given(
        m=st.integers(min_value=1, max_value=600),
        k=st.integers(min_value=1, max_value=600),
        n=st.integers(min_value=1, max_value=600),
    )
    def test_lean_bitwise_matches_pipelined(m, k, n):
        """The lean kernel is a *scheduling* change, not a numeric one:
        same blocks, same accumulation order, bit-identical to the
        default kernel."""

        from repro.kernels.gemm import gemm_pallas, gemm_pallas_lean

        rng = np.random.default_rng(m * 7919 + k * 104729 + n)
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        cfg = derive_block_config(m, k, n, spec=TPU_V5E, dtype_bytes=4)
        assert np.array_equal(
            np.asarray(gemm_pallas(a, b, cfg, interpret=True)),
            np.asarray(gemm_pallas_lean(a, b, cfg, interpret=True)),
        )
