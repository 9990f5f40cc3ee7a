"""Plain reference of the latent-attention MoE decoder (DeepSeek-V3).

Written from the DeepSeek-V3 layer equations (the ``deepseek_v3``
modelling code that Moonlight-16B-A3B's config names) in float32
``jax.numpy`` with every matmul at ``precision="highest"``, with no
cache, no absorption of ``W_UK`` / ``W_UV``, no kernel and no batching,
and with nothing imported from the program under test.

A layer, on one sequence x [S, d] (pre-norm; eps the config's, the q_a
and kv_a norms RMSNorm's default 1e-6 as the modelling code builds them):

* query: ``q = h W_q`` [S, H, nope + rope] (or, with a q LoRA rank,
  ``q = rms(h W_q_a) W_q_b``); ``q_pe`` its last ``rope`` values.
* latent: ``[c_kv | k_pe] = h W_kv_a``, ``c_kv = rms(c_kv)``;
  ``k_nope = c_kv W_UK`` and ``v = c_kv W_UV`` per head; ``k_pe`` one
  head shared by all.
* RoPE on ``q_pe`` and ``k_pe`` in the published layout: the pairs
  (x0, x1), (x2, x3), ... are de-interleaved into halves
  (``view(d/2, 2).transpose``) and the halves rotated, theta
  ``rope_theta``, no scaling.
* causal softmax attention of ``[q_nope | q_pe]`` against
  ``[k_nope | k_pe]`` with scale (nope + rope)^-1/2, then ``o W_o``.
* MLP: SwiGLU of width ``d_ff`` for the leading dense layers; else the
  MoE: sigmoid scores of ``h W_router``, the top ``k`` experts chosen by
  score plus the score-correction bias (one group), weighted by their
  scores without the bias, renormalised (``norm_topk_prob``) and scaled
  by ``routed_scaling_factor``, each a SwiGLU of width ``moe_d_ff``; plus
  the shared experts, one SwiGLU of width n_shared * moe_d_ff.

Parameters come in the checkpoint layout the program stores: ``embed``,
``unembed`` [V, d]; ``final_norm`` [d]; ``dense_layers`` (the leading
dense layers) and ``layers`` (the MoE layers), each with per-layer
stacks ``norm1``, ``norm2`` [L, d], ``attn`` {``wq`` [L, d, H, nope +
rope] (or ``wq_a`` [L, d, qr], ``q_a_norm`` [L, qr], ``wq_b`` [L, qr, H,
nope + rope]), ``wkv_a`` [L, d, r + rope], ``kv_a_norm`` [L, r], ``wk_b``
[L, r, H, nope], ``wv_b`` [L, r, H, v], ``wo`` [L, H, v, d]} and ``mlp``
{``w_gate``, ``w_up`` [L, d, ff], ``w_down`` [L, ff, d]} or ``moe``
{``router`` [L, d, E], ``router_bias`` [L, E], ``w_gate``, ``w_up`` [L,
E, d, ff], ``w_down`` [L, E, ff, d], ``shared`` {as ``mlp``}}. ``wk_b``
and ``wv_b`` are the published ``kv_b_proj`` split into its per-head key
and value parts.

One departure from the published model, taken over because the program
computes it: the token embedding is scaled by sqrt(d_model) before the
first layer (with weights drawn from a seed, the same as an embedding
drawn at sqrt(d_model) times the spread).

For one sequence of 8k positions the attention is computed in blocks of
queries and the experts in blocks of tokens, so that the reference fits
one chip beside the weights; blocking changes no arithmetic.

``mode="fp8"`` is the control: every matmul operand, weights and
activations alike, and the residual stream after every add, is rounded
to float8 e4m3 with one absmax scale per tensor, where the program keeps
them in bf16: the precision step below the bf16 that the configuration
states for compute.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
LATENT_EPS = 1e-6
BLOCK = 512           # queries per attention block, tokens per expert block


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, a, b, mode: str = "f32"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def keep(x, mode: str):
    return _fp8(x) if mode == "fp8" else x


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, positions, theta):
    """The modelling code's ``apply_rotary_pos_emb`` on x [S, H, d]."""
    S, H, d = x.shape
    x = x.reshape(S, H, d // 2, 2).swapaxes(-1, -2).reshape(S, H, d)
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    freqs = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def swiglu(h, p, mode):
    g = mm("sd,df->sf", h, p["w_gate"], mode)
    u = mm("sd,df->sf", h, p["w_up"], mode)
    return mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], mode)


def attention(h, a: Dict, m: Dict, mode: str):
    """Non-absorbed latent attention of one sequence h [S, d]."""
    S = h.shape[0]
    pos = jnp.arange(S)
    nope, rp, r = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["kv_lora_rank"]
    if "wq_a" in a:
        qa = rms(mm("sd,dr->sr", h, a["wq_a"], mode), a["q_a_norm"],
                 LATENT_EPS)
        q = mm("sr,rhk->shk", qa, a["wq_b"], mode)
    else:
        q = mm("sd,dhk->shk", h, a["wq"], mode)
    kv = mm("sd,dc->sc", h, a["wkv_a"], mode)
    c = rms(kv[:, :r], a["kv_a_norm"], LATENT_EPS)
    k_pe = rope(kv[:, None, r:], pos, m["rope_theta"])           # [S, 1, rp]
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, m["rope_theta"])], -1)
    k_nope = mm("sr,rhk->shk", c, a["wk_b"], mode)
    v = mm("sr,rhk->shk", c, a["wv_b"], mode)
    H = q.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rp))], -1)
    scale = 1.0 / math.sqrt(nope + rp)

    def block(qb, q_pos):
        s = mm("qhk,thk->hqt", qb, k, mode) * scale
        s = jnp.where(q_pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return mm("hqt,thv->qhv", jax.nn.softmax(s, axis=-1), v, mode)

    nb = S // BLOCK
    o = jax.lax.map(lambda i: block(
        jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK),
        i * BLOCK + jnp.arange(BLOCK)), jnp.arange(nb))
    o = o.reshape(S, H, -1)
    return mm("shv,hvd->sd", o, a["wo"], mode)


def moe(h, p: Dict, m: Dict, mode: str):
    """Sigmoid-routed experts (every expert computed, the chosen ones
    weighted) and the shared experts, token block by token block."""
    k = m["experts_per_token"]

    def block(hb):
        scores = jax.nn.sigmoid(mm("sd,de->se", hb, p["router"], mode))
        _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                               k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * m["routed_scaling_factor"]
        gate = jnp.zeros_like(scores).at[
            jnp.arange(hb.shape[0])[:, None], idx].set(w)          # [s, E]
        g = mm("sd,edf->sef", hb, p["w_gate"], mode)
        u = mm("sd,edf->sef", hb, p["w_up"], mode)
        y = mm("sef,efd->sed", jax.nn.silu(g) * u, p["w_down"], mode)
        return jnp.einsum("se,sed->sd", gate, y, precision=HIGHEST) \
            + swiglu(hb, p["shared"], mode)

    S, d = h.shape
    out = jax.lax.map(block, h.reshape(S // BLOCK, BLOCK, d))
    return out.reshape(S, d)


def layer(x, lp: Dict, m: Dict, mode: str, dense: bool):
    eps = m["eps"]
    x = keep(x + attention(rms(x, lp["norm1"], eps), lp["attn"], m, mode),
             mode)
    h2 = rms(x, lp["norm2"], eps)
    y = swiglu(h2, lp["mlp"], mode) if dense else moe(h2, lp["moe"], m, mode)
    return keep(x + y, mode)


def hidden(params, tokens, m: Dict, mode: str = "f32"):
    """tokens [S] (S a multiple of ``BLOCK``) -> final-normed hidden
    states [S, d] f32."""
    table = params["embed"].astype(jnp.float32)
    x = keep(table[tokens] * math.sqrt(table.shape[1]), mode)
    for key, dense in (("dense_layers", True), ("layers", False)):
        if key in params:
            x, _ = jax.lax.scan(
                lambda x, lp, dense=dense: (layer(x, lp, m, mode, dense),
                                            None), x, params[key])
    return rms(x, params["final_norm"], m["eps"])


@functools.lru_cache(maxsize=None)
def _hidden_fn(fm: tuple, mode: str):
    m = dict(fm)
    return jax.jit(lambda p, t: hidden(p, t, m, mode))


@functools.lru_cache(maxsize=None)
def _unembed_fn(mode: str):
    return jax.jit(lambda x, e: mm("sd,vd->sv", x, e, mode))


def logits(params, tokens, m: Dict, mode: str = "f32", rows=None):
    """Logits [S or len(rows), V] f32 of one sequence."""
    S = len(tokens)
    padded = jnp.asarray(np.pad(np.asarray(tokens), (0, -S % BLOCK)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _hidden_fn(tuple(sorted(m.items())), mode)(params, padded)[:S]
        if rows is not None:
            h = h[np.asarray(rows)]
        return _unembed_fn(mode)(h, params["unembed"])


def served_gaps(params, prompt, served, m: Dict, mode: str = "f32",
                block: int = 256) -> np.ndarray:
    """How far each served token's logit lies below the best logit.

    ``prompt`` [P] and ``served`` [N] ids of one sequence; position
    P - 1 + i predicts served[i]. The sequence is padded at its end to a
    multiple of ``BLOCK`` (causal: no real position sees the padding).
    With ``mode="f32"`` the gaps are those of the served tokens; any
    other mode is the control: the token that ``mode`` puts first at
    each position, its gap read under the f32 reference."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    S, P = len(seq), len(prompt)
    padded = jnp.asarray(np.pad(seq, (0, -S % BLOCK)), jnp.int32)
    fm = tuple(sorted(m.items()))
    out = []
    with jax.default_matmul_precision("highest"):
        h = _hidden_fn(fm, "f32")(params, padded)
        hc = None if mode == "f32" else _hidden_fn(fm, mode)(params, padded)
        un, unc = _unembed_fn("f32"), _unembed_fn(mode)
        for lo in range(0, len(served), block):
            rows = np.arange(P - 1 + lo, P - 1 + min(lo + block, len(served)))
            ref = un(h[rows], params["unembed"])
            if mode == "f32":
                pick = jnp.asarray(served[lo:lo + len(rows)], jnp.int32)
            else:
                pick = jnp.argmax(unc(hc[rows], params["unembed"]), axis=-1)
            got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            out.append(np.asarray(jnp.max(ref, axis=-1) - got))
    return np.concatenate(out)
