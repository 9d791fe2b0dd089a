"""A throwaway cell at a size a CPU test run holds: a configuration, a mix
and a metric (and, if asked, a model family) added as files and
``BENCHMARK.json`` entries, in a copy of the benchmark, without editing any
file the benchmark already has."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "hidden_act": "silu", "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "chipbench": {"arch": "internlm2-1.8b", "name": "tiny", "reduced": [],
                  "engine": {"pods": 2, "slots_per_pod": 2, "lane": 128, "paged": "off"},
                  "limits": {"widest_gap": 0.05}},
}
MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 20,
                    "prompt_len": {"values": [8, 16], "weights": [0.5, 0.5]},
                    "output_len": {"lognormal": {"median": 8, "sigma": 0.8}, "min": 2, "max": 40}}}
METRIC = '''"""Throwaway: requests that got a first token in the window."""


def read(run):
    return float(len(run.record.token_times))
'''
# A family of its own: the dense decoder with q, k and v biases, served
# through the registry's qwen2.5-32b (``qkv_bias``).  Its reference adds
# the biases unless ``REFERENCE_BIASES`` is off.
QKV_BIAS_FAMILY = '''"""Throwaway family: a dense decoder with q, k and v biases.  All that the
biases do not touch is the dense family's, loaded from beside this file."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench import spec
from chipbench.reference import _mm, attention, rms_norm, rope

dense = spec.load_family("dense", Path(__file__).resolve().parents[1])
REFERENCE_BIASES = True

Dims, dims, logits = dense.Dims, dense.dims, dense.logits
# A bias is an addition, not a product: the dense counts stand.
step_gemms, attn_roofline_s = dense.step_gemms, dense.attn_roofline_s
token_flops, prompt_flops = dense.token_flops, dense.prompt_flops


def arch_config(c, d):
    return dataclasses.replace(dense.arch_config(c, d), qkv_bias=True)


def _widths(d):
    return {"bq": d.n_heads * d.d_head, "bk": d.n_kv_heads * d.d_head,
            "bv": d.n_kv_heads * d.d_head}


def param_shapes(d):
    shapes = dense.param_shapes(d)
    shapes["blocks"]["attn"].update({b: (d.n_layers, w) for b, w in _widths(d).items()})
    return shapes


def init(key, d):
    key, kb = jax.random.split(key)
    params = dense.init(key, d)
    for k, (b, w) in zip(jax.random.split(kb, 3), _widths(d).items()):
        params["blocks"]["attn"][b] = 0.5 * jax.random.normal(k, (d.n_layers, w), jnp.float32)
    return params


def param_count(d, *, embed=True):
    return dense.param_count(d, embed=embed) + d.n_layers * sum(_widths(d).values())


def hidden(params, dims, tokens, *, bits=None, q_chunk=256):
    s = tokens.shape[0]
    q_chunk = min(q_chunk, s)
    x = params["embed"][tokens].astype(jnp.float32)

    def proj(n, a, w):
        y = _mm(n, a["w" + w], bits)
        return y + a["b" + w] if REFERENCE_BIASES else y

    def layer(x, p):
        a = p["attn"]
        n1 = rms_norm(x, p["ln1"], dims.norm_eps)
        q = proj(n1, a, "q").reshape(s, dims.n_heads, dims.d_head)
        k = proj(n1, a, "k").reshape(s, dims.n_kv_heads, dims.d_head)
        v = proj(n1, a, "v").reshape(s, dims.n_kv_heads, dims.d_head)
        o = attention(rope(q, dims.rope_theta), rope(k, dims.rope_theta), v, q_chunk=q_chunk)
        x = x + _mm(o, a["wo"], bits)
        m = p["mlp"]
        n2 = rms_norm(x, p["ln2"], dims.norm_eps)
        x = x + _mm(jax.nn.silu(_mm(n2, m["w1"], bits)) * _mm(n2, m["w3"], bits), m["w2"], bits)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms_norm(x, params["final_norm"], dims.norm_eps)
'''


def make(tmp: Path, *, paged: str = "off", qkv_bias: bool = False,
         reference_biases: bool = True) -> Path:
    """A copy of the benchmark under ``tmp`` with the throwaway cell
    ``tiny.burst`` added; returns the copy's root.  With ``qkv_bias`` the
    cell's configuration names the throwaway family ``qkv_bias``, added as
    ``families/qkv_bias.py``, whose reference leaves the biases out unless
    ``reference_biases``."""

    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads(json.dumps(CONFIG))
    cfg["chipbench"]["engine"]["paged"] = paged
    if qkv_bias:
        cfg["chipbench"].update(family="qkv_bias", arch="qwen2.5-32b")
        (root / "chipbench/families/qkv_bias.py").write_text(QKV_BIAS_FAMILY.replace(
            "REFERENCE_BIASES = True", f"REFERENCE_BIASES = {reference_biases}"))
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/burst.json").write_text(json.dumps(MIX))
    (root / "chipbench/metrics/requests_started.tiny.py").write_text(METRIC)
    bench["configs"].append({"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_started.tiny", "unit": "requests",
                               "better": "higher", "source": "program_counter", "layer": "engine",
                               "moves": "output_tok_s", "workloads": ["tiny.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
