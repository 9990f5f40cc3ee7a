"""Least work of a latent-attention MoE decode step, counted from shapes.

As in ``flops.py``: counts are what the arithmetic needs whatever
implements it, weights at the compute dtype (bf16) except the float32
router, the latent cache only up to the position each step attends to,
so that a share of a peak built from them can rise towards 100 % but not
pass it.

``m`` is the size dict of ``drivers/serve_session.sizes``: layers,
dense_layers, d_model, heads, q_lora_rank, kv_lora_rank,
qk_nope_head_dim, qk_rope_head_dim, v_head_dim, d_ff, experts,
experts_per_token, moe_d_ff, shared_experts, vocab.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def latent_width(m: dict) -> int:
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attn_params(m: dict) -> int:
    """Matmul weights of one latent-attention layer: the query (plain or
    low-rank), the latent projection, W_UK, W_UV and the output."""
    d, H, r = m["d_model"], m["heads"], m["kv_lora_rank"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    qr = m["q_lora_rank"]
    q = d * qr + qr * H * qk if qr else d * H * qk
    return (q + d * latent_width(m) + r * H * m["qk_nope_head_dim"]
            + r * H * m["v_head_dim"] + H * m["v_head_dim"] * d)


def attn_norm_params(m: dict) -> int:
    return m["kv_lora_rank"] + m["q_lora_rank"]


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU weights."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def shared_params(m: dict) -> int:
    return 3 * m["d_model"] * m["shared_experts"] * m["moe_d_ff"]


def router_bytes(m: dict) -> int:
    """The float32 router and its score-correction bias, one layer."""
    return (m["d_model"] + 1) * m["experts"] * F32


def moe_layers(m: dict) -> int:
    return m["layers"] - m["dense_layers"]


def active_params(m: dict) -> int:
    """Matmul weights one token multiplies: every layer's attention, the
    dense layers' MLP, each MoE layer's chosen experts, shared experts
    and router, and the untied head (the embedding is a lookup)."""
    dense = m["dense_layers"] * 3 * m["d_model"] * m["d_ff"]
    moe = moe_layers(m) * (m["experts_per_token"] * expert_params(m)
                           + shared_params(m)
                           + m["d_model"] * m["experts"])
    return m["layers"] * attn_params(m) + dense + moe \
        + m["vocab"] * m["d_model"]


def attn_flops_per_token(m: dict, live: float) -> float:
    """Absorbed attention of one token in one layer over ``live``
    positions: scores over the whole latent width, then the weighted sum
    of c_kv, per head."""
    return 2.0 * m["heads"] * live * (latent_width(m) + m["kv_lora_rank"])


def decode_step_flops(m: dict, batch: int, live: float) -> float:
    """One decode step for ``batch`` rows attending ``live`` positions:
    2 flops per active weight per token plus the absorbed attention."""
    return batch * (2.0 * active_params(m)
                    + m["layers"] * attn_flops_per_token(m, live))


def mla_decode_bytes(m: dict, batch: int, live: float) -> float:
    """What the absorbed attention of one decode step must move, all
    layers: each row's ``live`` latent positions read (the new one
    included) and the new one written, and the layers' attention
    weights."""
    cache = m["layers"] * batch * (live + 1) * latent_width(m) * BF16
    w = m["layers"] * (attn_params(m) + attn_norm_params(m)) * BF16
    return float(cache + w)


def moe_decode_bytes(m: dict, distinct: float) -> float:
    """What the MoE layers of one decode step must move: the weights of
    the ``distinct`` routed experts the step's routing hit (summed over
    the MoE layers), each layer's shared experts and router."""
    return float(distinct * expert_params(m) * BF16
                 + moe_layers(m) * (shared_params(m) * BF16 + router_bytes(m)))
