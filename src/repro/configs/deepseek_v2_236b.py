"""deepseek-v2-236b [moe] — MLA kv_lora=512, 2 shared + 160 routed top-6 [arXiv:2405.04434].

Routing, rope and norm as in the model's config.json: group-limited greedy
top-6 (3 of 8 groups), weights not renormalised but scaled by 16, YaRN rope
(factor 40 over 4096 positions), RMSNorm eps 1e-6. No sliding window.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b", family="moe", source="arXiv:2405.04434 (DeepSeek-V2)",
    num_layers=60, d_model=5120, num_heads=128, num_kv_heads=128,
    d_ff=12288,                  # dense-FFN width for the first (non-MoE) layer
    vocab_size=102400,
    num_experts=160, top_k=6, d_ff_expert=1536, num_shared_experts=2,
    first_dense_layers=1,
    use_mla=True, kv_lora_rank=512, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    n_group=8, topk_group=3, norm_topk_prob=False, routed_scaling_factor=16.0,
    rope_theta=10000.0, act="silu", norm="rmsnorm", norm_eps=1e-6,
    rope_factor=40.0, rope_original_max_positions=4096, rope_beta_fast=32.0,
    rope_beta_slow=1.0, rope_mscale_all_dim=0.707,
    long_context="skip",
)
