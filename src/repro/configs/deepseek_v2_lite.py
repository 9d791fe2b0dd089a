"""DeepSeek-V2-Lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27 layers of width 2048, all with multi-head latent attention (16 heads,
no query compression, keys 128 + 64 rope, values 128, ``kv_lora_rank``
512, YaRN rope x40 over 4096 positions).  Layer 0 has a dense SwiGLU MLP
of width 10944; layers 1-26 have 64 routed experts of width 1408 (top-6
of a softmax router, weights not renormalised, scale 1) and 2 shared
experts (one ungated SwiGLU of width 2 x 1408).
"""

from repro.configs import ArchConfig
from repro.models.layers import MLAConfig, Yarn
from repro.models.moe import MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=10944,
    vocab=102400,
    norm_eps=1e-6,
    first_k_dense=1,
    mla=MLAConfig(
        d_model=2048, n_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0, norm_eps=1e-6,
        yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
                  mscale=0.707, mscale_all_dim=0.707),
    ),
    moe=MoEConfig(d_model=2048, n_experts=64, top_k=6, d_ff_expert=1408,
                  d_ff_shared=2 * 1408, norm_topk_prob=False, shared_gate=False,
                  rs_output=False),
    notes="full attention -> long_500k skipped",
)
