"""Architecture configuration registry.

One module per assigned architecture (``src/repro/configs/<id>.py``), each
exporting ``CONFIG: ArchConfig`` with the exact published dimensions.
``get_config(name)`` resolves either the registry id (e.g.
``"qwen2.5-32b"``) or the module name (``"qwen2p5_32b"``).

Every config also knows how to produce a *reduced* variant
(:meth:`ArchConfig.reduced`) for the CPU smoke tests — same family and
block structure, tiny dims.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

from repro.models.layers import MLAConfig
from repro.models.moe import MoEConfig
from repro.models.ssm import SSMConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned (input-shape) cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# The assigned LM shape set (identical across the ten architectures).
LM_SHAPES = (
    ShapeSpec("train_4k", 4096, 256, "train"),
    ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    ShapeSpec("decode_32k", 32768, 128, "decode"),
    ShapeSpec("long_500k", 524288, 1, "decode"),
)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None
    qkv_bias: bool = False
    swa_window: Optional[int] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    norm: str = "rms"           # rms | layer
    embed_inputs: bool = False  # pixtral: backbone consumes patch embeddings
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA (DeepSeek-V2)
    first_k_dense: int = 0      # leading layers with a dense MLP (d_ff) before MoE
    shared_attn_every: int = 0  # zamba2: shared attn+mlp block cadence
    enc_layers: int = 0         # whisper: encoder depth (decoder = n_layers)
    enc_frames: int = 1500      # whisper: cross-attention KV length at decode
    notes: str = ""

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k: SSM state / hybrid / sliding window."""

        return self.family in ("ssm", "hybrid") or self.swa_window is not None

    def shapes(self, include_skipped: bool = False):
        out = []
        for s in LM_SHAPES:
            if s.name == "long_500k" and not self.subquadratic and not include_skipped:
                continue
            out.append(s)
        return out

    def param_count(self) -> int:
        """Analytic total parameter count (used by MODEL_FLOPS)."""

        d, f, v, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        attn = self._attn_params()
        glu = 3 * d * f
        n = 0
        if not self.embed_inputs:
            n += v * d
        n += d * v  # lm head
        if self.family == "dense":
            n += L * (attn + glu + 2 * d)
        elif self.family == "moe":
            m = self.moe
            per = attn + 2 * d + d * m.router_width + 3 * m.n_experts * d * m.d_ff_expert
            if m.d_ff_shared:
                per += 3 * d * m.d_ff_shared + d * m.shared_gate
            kd = self.first_k_dense
            n += (L - kd) * per + kd * (attn + glu + 2 * d)
        elif self.family == "ssm":
            n += L * self._mamba_params()
        elif self.family == "hybrid":
            n += L * self._mamba_params()
            n += attn + glu + 2 * d  # one shared block
        elif self.family == "encdec":
            mlp = 2 * d * f
            n += self.enc_layers * (attn + mlp + 2 * d)
            n += L * (attn + (d * hkv * 2 + d * hq + hq * d) + mlp + 3 * d)
        return n

    def _attn_params(self) -> int:
        d = self.d_model
        if self.mla is not None:
            a = self.mla
            h = a.n_heads
            return (d * h * (a.qk_nope_head_dim + a.qk_rope_head_dim) + d * a.latent
                    + a.kv_lora_rank + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)
                    + h * a.v_head_dim * d)
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        return d * hq + 2 * d * hkv + hq * d

    def _mamba_params(self) -> int:
        s = self.ssm
        di = s.d_inner
        gn2 = 2 * s.n_groups * s.d_state
        return (
            2 * self.d_model * di          # wz, wx
            + self.d_model * gn2           # wbc
            + self.d_model * s.n_heads     # wdt
            + s.d_conv * (di + gn2)        # convs
            + 3 * s.n_heads + di           # dt_bias, A_log, D, norm
            + di * self.d_model            # out_proj
        )

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k only)."""

        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d, L = self.d_model, self.n_layers
        attn = self._attn_params()
        per = attn + 2 * d + d * m.router_width + 3 * m.top_k * d * m.d_ff_expert
        if m.d_ff_shared:
            per += 3 * d * m.d_ff_shared + d * m.shared_gate
        kd = self.first_k_dense
        return self.vocab * d * 2 + (L - kd) * per + kd * (attn + 3 * d * self.d_ff + 2 * d)

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""

        kw = dict(
            name=self.name + "-smoke",
            family=self.family,
            n_layers=min(self.n_layers, 4 if self.family != "hybrid" else 4),
            d_model=64,
            n_heads=4,
            n_kv_heads=2 if self.n_kv_heads < self.n_heads else 4,
            d_ff=128,
            vocab=256,
            d_head=16,
            qkv_bias=self.qkv_bias,
            swa_window=8 if self.swa_window else None,
            embed_inputs=self.embed_inputs,
            norm=self.norm,
            shared_attn_every=2 if self.shared_attn_every else 0,
            enc_layers=2 if self.enc_layers else 0,
            enc_frames=16,
        )
        if self.moe is not None:
            kw["moe"] = MoEConfig(
                d_model=64,
                n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_ff_expert=32,
                d_ff_shared=64 if self.moe.d_ff_shared else 0,
                norm_topk_prob=self.moe.norm_topk_prob,
                shared_gate=self.moe.shared_gate,
            )
        if self.mla is not None:
            kw["mla"] = dataclasses.replace(
                self.mla, d_model=64, n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16)
            kw["first_k_dense"] = min(self.first_k_dense, 1)
        if self.ssm is not None:
            kw["ssm"] = SSMConfig(d_model=64, d_state=16, headdim=16, expand=2, chunk=8)
        return ArchConfig(**kw)


_REGISTRY = {
    "pixtral-12b": "pixtral_12b",
    "zamba2-2.7b": "zamba2_2p7b",
    "whisper-small": "whisper_small",
    "internlm2-1.8b": "internlm2_1p8b",
    "qwen2.5-32b": "qwen2p5_32b",
    "minitron-4b": "minitron_4b",
    "deepseek-7b": "deepseek_7b",
    "mixtral-8x7b": "mixtral_8x7b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2p7b",
    "mamba2-1.3b": "mamba2_1p3b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def list_configs() -> list[str]:
    return sorted(_REGISTRY)


def get_config(name: str) -> ArchConfig:
    mod_name = _REGISTRY.get(name, name.replace("-", "_").replace(".", "p"))
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.CONFIG


__all__ = ["ArchConfig", "ShapeSpec", "LM_SHAPES", "get_config", "list_configs"]
