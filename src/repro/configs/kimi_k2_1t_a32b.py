"""Kimi K2 — trillion-parameter MLA + MoE decoder (DeepSeek-V3 layout).
[hf:moonshotai/Kimi-K2-Instruct config.json]

Published: 61L d_model=7168, 64 heads of latent attention (MLA) with a
low-rank query (q_lora_rank 1536 -> q_a_proj, q_a_layernorm, q_b_proj),
kv_lora_rank 512, qk_nope/qk_rope/v head dims 128/64/128; layer 0 dense
(first_k_dense_replace 1, width 18432), layers 1-60 with 384 routed
experts of width 2048, 8 per token, sigmoid scores with a
score-correction bias (noaux_tc, one group), renormalised and scaled by
2.827, and 1 shared expert; untied vocabulary 163840, rope_theta 50000.

Not implemented: the config's YaRN rope scaling (factor 32 over 4096
original positions, mscale_all_dim 1), which stretches the rope
frequencies and raises the softmax scale; positions below 4096 see the
unscaled rope here.

Scale note: ~1.03T total params / ~32B active. fp32 AdamW state (12 B/param)
would need ~12.5 TB — beyond a 256-chip v5e pod (4 TB HBM). The default
TrainConfig for this arch therefore uses Adafactor with bf16 parameters,
which is how 1T-class models are actually trained on 16 GB-HBM parts.
"""
from repro.configs.base import ModelConfig, TrainConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    vocab_size=163_840,
    num_heads=64,
    num_kv_heads=64,
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    d_ff=18_432,
    first_k_dense=1,
    num_experts=384,
    experts_per_token=8,
    moe_d_ff=2048,
    router_scoring="sigmoid",
    routed_scaling_factor=2.827,
    n_shared_experts=1,
    norm_eps=1e-6,
    param_dtype="bfloat16",
    source="hf:moonshotai/Kimi-K2-Instruct config.json",
)

TRAIN = TrainConfig(
    optimizer="adafactor",
    num_microbatches=8,
    grad_accum_dtype="bfloat16",
    remat_policy="full",
)
