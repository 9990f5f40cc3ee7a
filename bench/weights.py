"""Weights and token streams drawn from a run's seed, by the benchmark.

The program under test is handed these; the reference draws the same
ones again after the window. Both follow the checkpoint layout written
out in ``shapes``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from any non-negative integer seed."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def shapes(m: Dict) -> Dict:
    """The parameter layout of the dense decoder: name -> shape."""
    L, d, H, K, hd, ff, V = (m["layers"], m["d_model"], m["heads"],
                             m["kv_heads"], m["head_dim"], m["d_ff"], m["vocab"])
    attn = {"wq": (L, d, H, hd), "wk": (L, d, K, hd), "wv": (L, d, K, hd),
            "wo": (L, H, hd, d)}
    if m["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    return {"embed": (V, d), "final_norm": (d,),
            "layers": {"norm1": (L, d), "norm2": (L, d), "attn": attn,
                       "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)}}}


def _std(name: str, shape) -> float:
    """Fan-in scaling, so that activations and logits stay O(1)."""
    if name == "embed":
        return shape[1] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5          # [L, fan_in, ...]


def _key(seed: int):
    return jax.random.PRNGKey(int(seed_words(seed, 1)[0]))


def make_params(seed: int, m: Dict, embed_scale: float = 1.0) -> Dict:
    """All parameters in float32, made on the default device in one
    jitted call. Norm scales are 1 + N(0, 0.1^2), so that a scale
    applied to the wrong tensor shows. ``embed_scale`` multiplies the
    tied embedding's standard deviation: below 1 the input token's own
    row no longer dominates the next token's logits."""
    return _drawer(_frozen(m), float(embed_scale))(_key(seed))


def _frozen(m: Dict) -> tuple:
    return tuple(sorted(m.items()))


@functools.lru_cache(maxsize=None)
def _drawer(fm: tuple, embed_scale: float = 1.0):
    m = dict(fm)
    tree = shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    names = [k[-1].key for k, _ in flat]
    shps = [s for _, s in flat]

    def draw(key):
        out = []
        for i, (name, shp) in enumerate(zip(names, shps)):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shp, jnp.float32)
            x = 1.0 + 0.1 * z if "norm" in name else z * _std(name, shp)
            if name == "embed":
                x = x * embed_scale
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)


def _names(tree, values) -> Dict[str, float]:
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    return dict(zip(paths, (float(v) for v in values)))


@jax.jit
def _norms(tree):
    return [jnp.linalg.norm(a.astype(jnp.float32)) for a in jax.tree.leaves(tree)]


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norms, by the leaf's path, in one jitted call."""
    return _names(tree, _norms(tree))


def change_norms(params, seed: int, m: Dict) -> Dict[str, float]:
    """Per-leaf norms of ``params`` minus the seed's initial parameters,
    drawn again on the device."""
    @jax.jit
    def diff(p, key):
        p0 = _drawer(_frozen(m))(key)
        return [jnp.linalg.norm(a.astype(jnp.float32) - b)
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]
    return _names(params, diff(params, _key(seed)))


def affine_chain_rows(vocab: int, seq: int, batch: int, seed: int, step: int,
                      noise: float = 0.02) -> Dict[str, np.ndarray]:
    """Rows of the program's synthetic language task, written out again
    here for the reference: a Markov chain x <- (a x + b) mod V with
    per-seed (a, b) and a fraction ``noise`` of ids replaced by uniform
    draws. The program's ``repro.data.synthetic.batch_at`` feeds the
    same rows to the training loop."""
    rng = np.random.default_rng(seed)
    a = int(rng.integers(3, 131)) * 2 + 1
    b = int(rng.integers(1, vocab - 1))
    rng = np.random.default_rng((seed * 1_000_003 + step) % (1 << 63))
    x = rng.integers(0, vocab, size=batch)
    rows = np.empty((batch, seq + 1), np.int64)
    for t in range(seq + 1):
        rows[:, t] = x
        x = (a * x + b) % vocab
    hit = rng.random((batch, seq + 1)) < noise
    rows[hit] = rng.integers(0, vocab, size=int(hit.sum()))
    rows = rows.astype(np.int32)
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
