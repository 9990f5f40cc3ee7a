"""Chameleon-34B — early-fusion VLM, VQ image tokens. [arXiv:2405.09818; unverified]

Assignment table: 48L d_model=8192 64H (GQA kv=8) d_ff=22016 vocab=65536.

Early fusion means image patches are VQ-quantized into ordinary token ids
inside the 65536 vocab; the transformer backbone is a plain decoder-only
LM. Per the assignment, the VQ frontend is a STUB: the caller
provides precomputed token ids (text + image-token spans interleaved).
Chameleon uses qk-norm for training stability.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="chameleon-34b",
    family="vlm",
    num_layers=48,
    d_model=8192,
    vocab_size=65_536,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    d_ff=22_016,
    qk_norm=True,
    frontend="vq_stub",
    source="arXiv:2405.09818; unverified",
)
