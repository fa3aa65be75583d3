"""DeepSeek-V2 236B — MLA (kv_lora=512) + MoE 160 experts top-6, 2 shared.

[arXiv:2405.04434] 60L d_model=5120 128H d_ff_expert=1536 vocab=102400,
first layer dense (d_ff=12288). Routing and positions as the published
config.json: softmax scores, group-limited greedy top-6 from 3 of 8
groups, weights scaled by 16 and not renormalised; YaRN rope (factor 40
over 4096 positions).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,
    d_ff=12288,                      # dense layers
    vocab_size=102400,
    pos_kind="rope",
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    act="swiglu",
    norm="rmsnorm",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=160, top_k=6, d_ff_expert=1536,
                  num_shared_experts=2, first_dense_layers=1,
                  n_group=8, topk_group=3, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
)
