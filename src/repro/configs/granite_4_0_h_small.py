"""granite-4.0-h-small [hybrid] — Mamba-2 + NoPE GQA attention, 72 experts top-10.

Granite 4.0-H Small (32B-A9B), as in the model's config.json
(``granitemoehybrid``): 40 layers repeating a period of ten, five Mamba-2
layers, one attention layer, four Mamba-2 layers (36 + 4). Mamba-2: 128
heads of 64, state 128, one group, conv 4 with a bias, chunk 256. GQA
32/8 heads of 128 with no position embedding and softmax scale
``attention_multiplier`` 1/128. Every layer's FFN is an MoE of 72 SwiGLU
experts of width 768, top-10 with a softmax over the ten chosen logits,
plus one shared SwiGLU of width 1536 (two experts' width). Embeddings
times 12, each residual branch times 0.22, logits divided by 16; tied
vocabulary of 100352; RMSNorm eps 1e-5.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    source="https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json",
    num_layers=40, d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    d_ff=0, vocab_size=100352,
    block_pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4, local_window=0,
    rope_theta=0.0, attention_multiplier=0.0078125,
    ssm_state=128, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
    ssm_ngroups=1, ssm_conv_bias=True,
    num_experts=72, top_k=10, d_ff_expert=768, num_shared_experts=2,
    norm_topk_prob=True,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    act="silu", norm="rmsnorm", norm_eps=1e-5, tie_embeddings=True,
    long_context="skip",       # global attention layers: the k/v cache grows with context
)
