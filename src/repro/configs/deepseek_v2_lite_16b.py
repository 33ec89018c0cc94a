"""deepseek-v2-lite-16b [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

27 layers, d_model 2048, 16 heads, vocab 102400, untied head.  MLA with
kv_lora_rank 512 and no q-LoRA (qk_nope 128, qk_rope 64, v 128), YaRN rope
(factor 40, mscale = mscale_all_dim = 0.707, beta_fast 32, beta_slow 1,
original context 4096).  Layer 0 is dense (first_k_dense_replace 1, d_ff
10944); layers 1-26 are MoE: 64 routed experts of width 1408, top-6,
softmax scoring without renormalising the top-6 (norm_topk_prob false),
routed_scaling_factor 1, and 2 shared experts.
"""

import dataclasses

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    vocab=102_400,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,            # nominal (MLA path does not use it)
    use_mla=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_dense_layers=1,
    d_ff=10_944,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    d_ff_expert=1408,
    norm_topk_prob=False,                # routed_scaling_factor is 1
    mlp_act="silu",
    rope_theta=10_000.0,
    rope_yarn_factor=40.0,
    rope_yarn_orig_max_pos=4096,
    rope_yarn_beta_fast=32.0,
    rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707,
    rope_yarn_mscale_all_dim=0.707,
)


def smoke_config() -> ModelConfig:
    # a short original context puts the smoke positions on YaRN's ramp
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, vocab=256, n_heads=4, n_kv_heads=4,
        head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, d_ff=96, n_experts=8, top_k=2, n_shared_experts=1,
        d_ff_expert=48, rope_yarn_factor=4.0, rope_yarn_orig_max_pos=32,
    )
