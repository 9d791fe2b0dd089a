"""A throwaway cell at a size a CPU test run holds: a configuration, a mix
and a metric added as files and ``BENCHMARK.json`` entries, in a copy of
the benchmark, without editing any file the benchmark already has."""

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


def make(tmp: Path, *, paged: str = "off") -> Path:
    """A copy of the benchmark under ``tmp`` with the throwaway cell
    ``tiny.burst`` added; returns the copy's root."""

    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads(json.dumps(CONFIG))
    cfg["chipbench"]["engine"]["paged"] = paged
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
