"""The plain reference against the program's own prefill and cache decode,
at a reduced size on the CPU, for both configurations' families: grouped
KV heads (internlm2) and one KV head per query head (deepseek)."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as R
from chipbench.model import Dims, arch_config, make_params
from repro.models import model_zoo as Z


def _config(arch: str, kv_heads: int) -> dict:
    return {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
            "num_attention_heads": 4, "num_key_value_heads": kv_heads, "head_dim": 16,
            "vocab_size": 256, "hidden_act": "silu", "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "chipbench": {"arch": arch}}


def _program_logits(cfg, params, tokens, n_prompt):
    """Bulk prefill of the prompt, then one cache decode step per token:
    the logits that predict tokens ``n_prompt .. len(tokens) - 1``."""

    decode = Z.make_decode_fn(cfg)
    state = Z.init_decode_state(cfg, 1, len(tokens))
    lg, state = Z.bulk_prefill_from_decode(decode)(
        params, {"tokens": jnp.asarray(tokens[None, :n_prompt])}, state, 0)
    out = [lg[0, -1]]
    for t in range(n_prompt, len(tokens) - 1):
        lg, state = decode(params, {"tokens": jnp.asarray(tokens[None, t:t + 1])}, state,
                           jnp.int32(t))
        out.append(lg[0, -1])
    return np.asarray(jnp.stack(out), np.float32)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("arch,kv_heads", [("internlm2-1.8b", 2), ("deepseek-7b", 4)])
def test_reference_matches_prefill_then_decode(arch, kv_heads):
    c = _config(arch, kv_heads)
    dims, cfg = Dims.from_config(c), arch_config(c)
    params = make_params(3, dims)
    tokens = np.random.default_rng(0).integers(0, dims.vocab, 48).astype(np.int32)
    n_prompt = 20
    got = _program_logits(cfg, params, tokens, n_prompt)
    ref = np.asarray(R.logits(params, R.hidden(params, dims, jnp.asarray(tokens), q_chunk=16)))
    ref = ref[n_prompt - 1: len(tokens) - 1]
    # The program computes in bfloat16 (unit roundoff 2^-8) through three
    # layers; the reference in float32 at highest precision.
    assert _rel(got, ref) < 2e-2
    assert (got.argmax(-1) == ref.argmax(-1)).mean() > 0.9
    # The tolerance has teeth: rotary embeddings at another theta, or the
    # norms' epsilon at 1e-2 instead, fail it.
    for wrong in (dataclasses.replace(dims, rope_theta=500.0),
                  dataclasses.replace(dims, norm_eps=1e-1)):
        bad = np.asarray(R.logits(params, R.hidden(params, wrong, jnp.asarray(tokens), q_chunk=16)))
        assert _rel(got, bad[n_prompt - 1: len(tokens) - 1]) > 5e-2


def test_mantissa_rounding():
    x = jnp.asarray([1.0, 1.0625, 1.1, -3.3, 1e-20, 7e20, 0.0], jnp.float32)
    r = np.asarray(R.round_mantissa(x, 3))
    # Four significant bits: relative error at most 2^-4, values kept where
    # they already fit.
    assert r[0] == 1.0 and r[6] == 0.0
    assert np.all(np.abs(r - np.asarray(x)) <= 2.0**-4 * np.abs(np.asarray(x)))
    assert r[2] == 1.125 and r[3] == -3.25
    assert np.all(np.asarray(R.round_mantissa(x, None)) == np.asarray(x))
