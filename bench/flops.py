"""Least work of the configurations' arithmetic, counted from shapes.

Counts are what the mathematics needs, whatever implements it: a
recomputed forward (remat) is not counted, weights are counted at the
compute dtype, and a KV cache only up to its live position. A share of
a peak built from these can therefore rise towards 100% by a faster
program but never pass it.

``m`` is a model-size dict as ``harness.model_sizes`` gives it: layers,
d_model, heads, kv_heads, head_dim, d_ff, vocab.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def matmul_params(m: dict) -> int:
    """Parameters that enter a matmul: q/k/v/o and the SwiGLU
    projections of every layer, plus the tied embedding once, as the
    unembedding. Norm scales are not matmul weights."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["heads"] + 2 * m["kv_heads"])
    mlp = 3 * d * m["d_ff"]
    return m["layers"] * (attn + mlp) + m["vocab"] * d


def norm_params(m: dict) -> int:
    per_layer = 2 * m["d_model"] + (2 * m["head_dim"] if m.get("qk_norm") else 0)
    return m["layers"] * per_layer + m["d_model"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 x matmul parameters, plus 6 L S H hd for causal attention
    (half of PaLM appendix B's 12 L H Q T: the mask halves the work)."""
    attn = 6 * m["layers"] * seq * m["heads"] * m["head_dim"]
    return 6.0 * matmul_params(m) + attn


def decode_step_flops(m: dict, batch: int, live: float) -> float:
    """One decode step for ``batch`` sequences that attend to ``live``
    cached positions each: 2 flops per matmul weight per token, and
    q.k plus p.v over the live positions."""
    attn = 4 * m["layers"] * m["heads"] * m["head_dim"] * live
    return batch * (2.0 * matmul_params(m) + attn)


def decode_step_bytes(m: dict, batch: int, live: float) -> float:
    """Weights once at bf16, the K and V cache read up to ``live``
    positions and the new position written, at bf16, and the fp32 logits
    written."""
    weights = (matmul_params(m) + norm_params(m)) * BF16
    kv_row = 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * BF16
    kv = batch * kv_row * (live + 1)
    logits = batch * m["vocab"] * F32
    return float(weights + kv + logits)


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of the compute and the memory time,
    and which of the two it is."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
