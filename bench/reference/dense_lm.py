"""Plain reference of the dense decoder both configurations use.

Written from the published Llama / Qwen3 layer equations in float32
``jax.numpy`` with every matmul at ``precision="highest"``, with no
kernel, cache or batching, and with nothing imported from the program
under test. Parameters come in the checkpoint layout the program
stores: ``embed`` [V, d]; ``layers`` holding per-layer stacks
``norm1``, ``norm2`` [L, d], ``attn`` ``wq`` [L, d, H, hd], ``wk`` /
``wv`` [L, d, Hkv, hd], ``wo`` [L, H, hd, d], optional ``q_norm`` /
``k_norm`` [L, hd], ``mlp`` ``w_gate`` / ``w_up`` [L, d, ff],
``w_down`` [L, ff, d]; ``final_norm`` [d].

One departure from the published models, taken over because the
program computes it: the token embedding is scaled by sqrt(d_model)
before the first layer.

``mode="fp8"`` is the control: every matmul operand, weights and
activations alike, and the residual stream after every add, is rounded
to float8 e4m3 with one absmax scale per tensor, where the program
keeps them in bf16: the precision step below the bf16 that the
configurations state for compute.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    """Round to float8 e4m3 with one absmax scale; the backward pass
    sees the identity (straight-through)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(spec: str, a, b, mode: str = "f32"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def keep(x, mode: str):
    """An activation as the configuration keeps it between operations:
    f32 in the reference, float8 in the control."""
    return _fp8(x) if mode == "fp8" else x


def rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotate-half RoPE; x [S, H, hd]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer(x, lp: Dict, m: Dict, mode: str):
    """One pre-norm block on one sequence; x [S, d] f32."""
    S = x.shape[0]
    eps = m["eps"]
    pos = jnp.arange(S)
    h = rms(x, lp["norm1"], eps)
    q = mm("sd,dhk->shk", h, lp["attn"]["wq"], mode)
    k = mm("sd,dhk->shk", h, lp["attn"]["wk"], mode)
    v = mm("sd,dhk->shk", h, lp["attn"]["wv"], mode)
    if "q_norm" in lp["attn"]:
        q = rms(q, lp["attn"]["q_norm"], eps)
        k = rms(k, lp["attn"]["k_norm"], eps)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)          # head h reads kv head h // group
    v = jnp.repeat(v, group, axis=1)
    s = mm("qhk,thk->hqt", q, k, mode) / math.sqrt(q.shape[-1])
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqt,thk->qhk", p, v, mode)
    x = keep(x + mm("shk,hkd->sd", o, lp["attn"]["wo"], mode), mode)
    h2 = rms(x, lp["norm2"], eps)
    g = mm("sd,df->sf", h2, lp["mlp"]["w_gate"], mode)
    u = mm("sd,df->sf", h2, lp["mlp"]["w_up"], mode)
    return keep(x + mm("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp"]["w_down"],
                       mode), mode)


def hidden(params, tokens, m: Dict, mode: str = "f32", remat: bool = False):
    """tokens [S] -> final-normed hidden states [S, d] f32."""
    table = params["embed"].astype(jnp.float32)
    x = keep(table[tokens] * math.sqrt(table.shape[1]), mode)
    body = (lambda x, lp: (layer(x, lp, m, mode), None))
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms(x, params["final_norm"], m["eps"])


def logits(params, tokens, m: Dict, mode: str = "f32", rows=None):
    """Logits [S or len(rows), V] f32 of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, m, mode)
    if rows is not None:
        x = x[rows]
    return mm("sd,vd->sv", x, params["embed"], mode)


# ---------------------------------------------------------------------------
# Training: loss, gradients and AdamW, as the configuration states them
# ---------------------------------------------------------------------------

def row_loss_sum(params, tokens, labels, m: Dict, z_coef: float,
                 mode: str = "f32"):
    """Summed token cross entropy (+ z_coef * logZ^2) of one sequence."""
    x = hidden(params, tokens, m, mode, remat=True)
    lg = mm("sd,vd->sv", x, params["embed"], mode)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold) + z_coef * jnp.sum(logz * logz)


def lr_at(step: int, hp: Dict) -> float:
    """Linear warmup to the peak, then cosine to final_lr_fraction."""
    base, warm, total = hp["learning_rate"], hp["warmup_steps"], hp["total_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    f = hp["final_lr_fraction"]
    return base * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(v.astype(jnp.float32)))
            for k, v in flat}


def train_steps(params0, batches: Sequence[Dict[str, np.ndarray]], m: Dict,
                hp: Dict, z_coef: float = 0.0, mode: str = "f32",
                rows_used=None) -> Dict:
    """AdamW steps from ``params0`` on ``batches`` (one per step).

    Returns each step's loss, the per-leaf norms of the first step's
    gradient after clipping (what the optimizer is handed), and the
    per-leaf norms of the parameters' change after the last step.
    ``rows_used`` takes only those rows of each batch (a planted fault:
    part of the batch left out, the mean taken over the rest)."""
    with jax.default_matmul_precision("highest"):
        grad_row = jax.jit(jax.value_and_grad(
            lambda p, t, l: row_loss_sum(p, t, l, m, z_coef, mode)))
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params0)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        b1, b2, eps = hp["beta1"], hp["beta2"], hp["adam_eps"]
        losses, first_grad = [], None
        for step, batch in enumerate(batches):
            rows = range(batch["tokens"].shape[0]) if rows_used is None \
                else rows_used
            total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
            for r in rows:
                lr_sum, g = grad_row(params, jnp.asarray(batch["tokens"][r]),
                                     jnp.asarray(batch["labels"][r]))
                total = total + lr_sum
                grads = jax.tree.map(jnp.add, grads, g)
            n_tok = len(rows) * batch["tokens"].shape[1]
            grads = jax.tree.map(lambda g: g / n_tok, grads)
            losses.append(float(total) / n_tok)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
            if first_grad is None:
                first_grad = leaf_norms(grads)
            c = step + 1
            lr = lr_at(step, hp)
            mu = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mu, grads)
            nu = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, nu, grads)
            params = jax.tree.map(
                lambda p, a, b: p - lr * ((a / (1 - b1 ** c))
                                          / (jnp.sqrt(b / (1 - b2 ** c)) + eps)
                                          + hp["weight_decay"] * p),
                params, mu, nu)
        change = leaf_norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), params, params0))
    return {"losses": losses, "first_grad": first_grad, "change": change}


@functools.lru_cache(maxsize=None)
def _hidden_fn(fm: tuple, mode: str):
    """``hidden`` jitted once per model and mode, so that every sampled
    row after the first finds its program traced and compiled."""
    m = dict(fm)
    return jax.jit(lambda p, t: hidden(p, t, m, mode))


@functools.lru_cache(maxsize=None)
def _unembed_fn(mode: str):
    return jax.jit(lambda x, e: mm("sd,vd->sv", x, e, mode))


def served_gaps(params, prompt, served, m: Dict, mode: str = "f32",
                ref_params=None, block: int = 256) -> np.ndarray:
    """How far each served token's logit lies below the best logit.

    ``prompt`` [P] and ``served`` [N] ids of one sequence; position
    P - 1 + i predicts served[i]. With ``mode="f32"`` the gaps are those
    of the served tokens. Any other mode is the control: at each position
    the token that ``mode`` puts first is taken, and its gap is read
    under the f32 reference."""
    seq = jnp.asarray(np.concatenate([prompt, served[:-1]]), jnp.int32)
    P = len(prompt)
    out = []
    fm = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        h = _hidden_fn(fm, "f32")(params, seq)
        hc = None if mode == "f32" else _hidden_fn(fm, mode)(params, seq)
        un, unc = _unembed_fn("f32"), _unembed_fn(mode)
        for lo in range(0, len(served), block):
            rows = np.arange(P - 1 + lo, P - 1 + min(lo + block, len(served)))
            ref = un(h[rows], params["embed"])
            if mode == "f32":
                pick = jnp.asarray(served[lo:lo + len(rows)], jnp.int32)
            else:
                pick = jnp.argmax(unc(hc[rows], params["embed"]), axis=-1)
            got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            out.append(np.asarray(jnp.max(ref, axis=-1) - got))
    return np.concatenate(out)
