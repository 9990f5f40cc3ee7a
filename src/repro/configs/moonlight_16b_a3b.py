"""Moonlight-16B-A3B — MLA + MoE decoder (DeepSeek-V3 layout).
[hf:moonshotai/Moonlight-16B-A3B config.json]

Published: 27L d_model=2048, 16 heads of latent attention (MLA) with a
plain q projection (q_lora_rank null) to 16 x 192, kv_lora_rank 512 and
a 64-wide roped key shared by all heads, so each position caches 576
values a layer; qk_nope/v head dims 128/128; rope_theta 50000, no rope
scaling. Layer 0 dense (first_k_dense_replace 1, width 11264), layers
1-26 with 64 routed experts of width 1408, 6 per token, sigmoid scores
with a score-correction bias (noaux_tc, n_group = topk_group = 1),
renormalised and scaled by 2.446, and 2 shared experts (one SwiGLU MLP
of width 2816); untied vocabulary 163840; context 8192.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    vocab_size=163_840,
    num_heads=16,
    num_kv_heads=16,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    d_ff=11_264,
    first_k_dense=1,
    num_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    router_scoring="sigmoid",
    routed_scaling_factor=2.446,
    n_shared_experts=2,
    norm_eps=1e-5,
    param_dtype="bfloat16",
    source="hf:moonshotai/Moonlight-16B-A3B config.json",
)
