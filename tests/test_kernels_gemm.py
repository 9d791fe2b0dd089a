"""Pallas kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocking import BlockConfig, PAPER_A15, PAPER_A7, GotoBlocking
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm_pallas, gemm_pallas_lean, validate_block_config
from repro.kernels.ops import gemm, linear

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


GEMM_SHAPES = [
    (128, 128, 128),
    (256, 512, 128),
    (300, 200, 180),   # ragged: exercises padding
    (64, 1024, 96),
    (512, 128, 512),
]


@pytest.mark.parametrize("shape", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_pallas_matches_oracle(shape, dtype):
    m, k, n = shape
    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=a.dtype.itemsize)
    out = gemm_pallas(a, b, cfg, interpret=True)
    expect = ref.gemm_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2  # f32: blocked-K rounding
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("blocks", [(128, 128, 128), (256, 128, 128), (128, 256, 256)])
def test_gemm_block_shape_invariance(blocks):
    bm, bk, bn = blocks
    a, b = _rand((384, 384), jnp.float32), _rand((384, 384), jnp.float32)
    cfg = BlockConfig(bm=bm, bk=bk, bn=bn, dtype_bytes=4)
    out = gemm_pallas(a, b, cfg, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.gemm_ref(a, b)), rtol=1e-5, atol=1e-4
    )


# ---------------------------------------------------------------------------
# VMEM-lean k-streaming variant (the TPU_LITTLE micro-kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_pallas_lean_matches_oracle(shape, dtype):
    m, k, n = shape
    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=a.dtype.itemsize)
    out = gemm_pallas_lean(a, b, cfg, interpret=True)
    expect = ref.gemm_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=tol, atol=tol
    )


def test_gemm_pallas_lean_bitwise_matches_default():
    """Same blocks, same fp32 accumulation order — the lean variant is a
    scheduling/footprint change, not a numeric one."""

    a, b = _rand((384, 300), jnp.float32), _rand((300, 200), jnp.float32)
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
    assert np.array_equal(
        np.asarray(gemm_pallas(a, b, cfg, interpret=True)),
        np.asarray(gemm_pallas_lean(a, b, cfg, interpret=True)),
    )


def test_gemm_pallas_lean_single_buffer_fit_admits_bigger_panels():
    """The point of the variant: a config that only fits single-buffered
    (lean VMEM model) runs correctly through the lean kernel."""

    from repro.core.blocking import TPU_LITTLE

    # (512, 640, 1024) bf16: 6.5 MiB single-buffered working set vs
    # 8.4 MiB double-buffered — lean-only inside little's 7.2 MiB budget,
    # exactly the panel the control trees keep for little.
    cfg = BlockConfig(bm=512, bk=640, bn=1024, dtype_bytes=2)
    assert not cfg.fits(TPU_LITTLE)
    assert cfg.fits(TPU_LITTLE, double_buffer=False)
    a, b = _rand((512, 1280), jnp.bfloat16), _rand((1280, 1024), jnp.bfloat16)
    out = gemm_pallas_lean(a, b, cfg, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.gemm_ref(a, b), np.float32),
        rtol=2e-2, atol=2e-2,
    )


# ---------------------------------------------------------------------------
# Config-vs-shape validation (regression: oversized bk was silent)
# ---------------------------------------------------------------------------


class TestBlockConfigValidation:
    def test_bk_exceeding_padded_k_raises(self):
        """Regression: bk=256 against K=100 (pads to 128) used to be
        silently accepted — padding K all the way to 256 and more than
        doubling every grid step's FLOPs."""

        a, b = _rand((128, 100), jnp.float32), _rand((100, 128), jnp.float32)
        cfg = BlockConfig(bm=128, bk=256, bn=128, dtype_bytes=4)
        with pytest.raises(ValueError, match=r"bk=256 exceeds padded K=128"):
            gemm_pallas(a, b, cfg, interpret=True)
        with pytest.raises(ValueError, match=r"bk=256 exceeds padded K=128"):
            gemm_pallas_lean(a, b, cfg, interpret=True)

    @pytest.mark.parametrize(
        "cfg_dims,match",
        [((512, 128, 128), "bm=512 exceeds padded M"),
         ((128, 128, 512), "bn=512 exceeds padded N")],
    )
    def test_bm_bn_also_validated(self, cfg_dims, match):
        bm, bk, bn = cfg_dims
        a, b = _rand((100, 128), jnp.float32), _rand((128, 100), jnp.float32)
        with pytest.raises(ValueError, match=match):
            gemm_pallas(a, b, BlockConfig(bm=bm, bk=bk, bn=bn, dtype_bytes=4),
                        interpret=True)

    def test_blocks_up_to_lane_padding_still_accepted(self):
        # A block equal to the lane-padded dim is the legitimate way to
        # run a sub-128 problem; sub-block dims stay fine too.
        validate_block_config(100, 100, 100, BlockConfig(128, 128, 128, dtype_bytes=4))
        validate_block_config(300, 200, 180, BlockConfig(128, 256, 128, dtype_bytes=4))
        validate_block_config(128, 128, 128, BlockConfig(64, 64, 64, dtype_bytes=4))


def test_blocked_ref_matches_paper_loop_structure():
    """The Figure-1 five-loop reference agrees with plain matmul for both
    published cache configs (and a deliberately ragged one)."""

    a = RNG.normal(size=(300, 1100)).astype(np.float32)
    b = RNG.normal(size=(1100, 200)).astype(np.float32)
    for cfg in (PAPER_A15, PAPER_A7, GotoBlocking(mc=32, kc=952, nc=64)):
        out = ref.blocked_gemm_ref(a, b, cfg)
        np.testing.assert_allclose(out, a @ b, rtol=1e-4, atol=1e-3)


def test_blocked_tpu_ref_matches():
    a, b = _rand((256, 512), jnp.float32), _rand((512, 256), jnp.float32)
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
    np.testing.assert_allclose(
        np.asarray(ref.blocked_gemm_tpu_ref(a, b, cfg)),
        np.asarray(ref.gemm_ref(a, b)),
        rtol=1e-5,
        atol=1e-4,
    )


def test_ops_gemm_leading_dims():
    a = _rand((2, 3, 64), jnp.float32)
    b = _rand((64, 32), jnp.float32)
    out = gemm(a, b, backend="xla")
    assert out.shape == (2, 3, 32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.einsum("bsd,df->bsf", a, b)), rtol=1e-5
    )


def test_ops_backends_agree():
    a, b = _rand((130, 70), jnp.float32), _rand((70, 50), jnp.float32)
    cfg = BlockConfig(bm=128, bk=128, bn=128, dtype_bytes=4)
    x = gemm(a, b, backend="xla")
    p = gemm(a, b, backend="pallas_interpret", config=cfg)
    np.testing.assert_allclose(np.asarray(x), np.asarray(p), rtol=1e-5, atol=1e-4)


def test_linear_bias():
    a, w = _rand((4, 16), jnp.float32), _rand((16, 8), jnp.float32)
    b = _rand((8,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(linear(a, w, b)), np.asarray(a @ w + b), rtol=1e-5, atol=1e-5
    )


ATTN_CASES = [
    # (B, Sq, Sk, H, D, causal, window)
    (2, 128, 128, 2, 64, True, None),
    (1, 100, 100, 1, 64, True, None),     # ragged padding
    (1, 64, 192, 2, 64, True, None),      # query suffix (decode-ish)
    (2, 128, 128, 2, 64, False, None),    # bidirectional (whisper encoder)
    (1, 256, 256, 1, 64, True, 64),       # sliding window (mixtral)
    (1, 128, 128, 2, 128, True, None),    # head dim 128
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_oracle(case):
    b, sq, sk, h, d, causal, window = case
    q = _rand((b, sq, h, d), jnp.float32)
    k = _rand((b, sk, h, d), jnp.float32)
    v = _rand((b, sk, h, d), jnp.float32)
    out = flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64, interpret=True
    )
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    q = _rand((2, 128, 2, 64), jnp.bfloat16)
    k = _rand((2, 128, 2, 64), jnp.bfloat16)
    v = _rand((2, 128, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), rtol=3e-2, atol=3e-2
    )


def test_chunked_attention_matches_oracle():
    from repro.models.layers import chunked_attention

    q = _rand((2, 96, 4, 32), jnp.float32)
    k = _rand((2, 96, 4, 32), jnp.float32)
    v = _rand((2, 96, 4, 32), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, q_chunk=32)
    expect = ref.attention_ref(q, k, v, causal=True)
    # chunked_attention computes in COMPUTE_DTYPE (bf16) — tolerance to match
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-2, atol=2e-2)


def test_chunked_attention_window():
    from repro.models.layers import chunked_attention

    q = _rand((1, 128, 2, 32), jnp.float32)
    k = _rand((1, 128, 2, 32), jnp.float32)
    v = _rand((1, 128, 2, 32), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, window=32, q_chunk=64)
    expect = ref.attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-2, atol=2e-2)
