"""Whisper-large-v3 — encoder-decoder, conv frontend (STUB). [arXiv:2212.04356; unverified]

Assignment table: 32L d_model=1280 20H (kv=20, i.e. MHA) d_ff=5120
vocab=51866. Encoder and decoder are both 32 layers; the mel->conv
frontend is a STUB per the assignment — the caller provides
precomputed frame embeddings [B, 1500, 1280].
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="whisper-large-v3",
    family="encdec",
    num_layers=32,
    d_model=1280,
    vocab_size=51_866,
    num_heads=20,
    num_kv_heads=20,
    head_dim=64,
    d_ff=5120,
    is_encoder_decoder=True,
    num_encoder_layers=32,
    encoder_seq=1500,
    frontend="audio_stub",
    source="arXiv:2212.04356; unverified",
)
