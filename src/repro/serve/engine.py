"""Batched prefill + decode engine.

``serve_step`` (one token for the whole batch against the KV cache) is
the program a decode step runs. ``generate`` is ``prefill``
then the decode loop; ``decode`` runs the same loop from a cache and a
position the caller keeps, so that further turns reuse a resident,
prefilled cache (``prefill`` can fill a chunk of its rows in place).

The sampler — logits [B,V] + key -> token ids [B] — is an active-code
slot: an analyst can deploy a new sampling rule (temperature change,
top-k, logit bias) between decode steps of an *ongoing* generation, the
serving analogue of the paper's mid-assignment algorithm swap.
Executables are cached per sampler fingerprint exactly like the train
step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.core.registry import Binding, LocalDeployment
from repro.core.telemetry import Metrics, StepArrays, rebuild_span, timed
from repro.core.tracing import SpanRecorder
from repro.models.blocks import ModelCtx
from repro.train.step import build_ctx


def default_sampler(logits: jax.Array, key: jax.Array) -> jax.Array:
    """Greedy (temperature 0)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sampler(temp: float) -> Callable:
    def sample(logits, key):
        if temp <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temp).astype(jnp.int32)
    return sample


def make_serve_step(model, ctx: ModelCtx, sampler: Callable) -> Callable:
    """(params, token [B], cache, pos, key) ->
    (next_token [B], new_cache, new_pos, new_key)."""

    def serve_step(params, token, cache, pos, key):
        logits, new_cache = model.decode_step(params, token, cache, pos, ctx)
        key, sub = jax.random.split(key)
        with jax.named_scope("sampler"):
            nxt = sampler(logits, sub)
        return nxt, new_cache, pos + 1, key

    return serve_step


class ServeEngine:
    """``metrics`` holds the ``serve.*`` counters and span histograms;
    ``spans`` keeps one ``serve.rebuild`` record per decode step built,
    with the sampler's md5 and its trace, lower and backend-compile
    seconds; ``expert_load``, while on, keeps each decode step's tokens
    routed to each expert ([MoE layers, E], a MoE model only)."""

    def __init__(self, model, cfg: RunConfig, *,
                 sampler_binding: Optional[Binding] = None,
                 mesh=None, rules=None, max_seq: Optional[int] = None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.ctx = build_ctx(cfg, mesh=mesh, rules=rules, decode=True)
        self.sampler_binding = sampler_binding
        self.max_seq = max_seq or cfg.shape.seq_len
        self._cache: Dict[Tuple, Callable] = {}
        self._prefill_jit = None
        self._fill_jit = None
        self.metrics = Metrics()
        self.spans = SpanRecorder("serve")
        self.expert_load = StepArrays()

    @property
    def rebuilds(self) -> int:
        return int(self.metrics.counter("serve.rebuilds"))

    # ------------------------------------------------------------------
    def deploy_sampler(self, source: str) -> LocalDeployment:
        """Versioned sampler swap between decode steps of an ongoing
        generation — same deployment surface as the fleet's
        ``deploy_code`` (``version``/``md5``/``rollback()``), backed by
        this engine's sampler binding."""
        if self.sampler_binding is None:
            raise RuntimeError("engine has no sampler binding to deploy into")
        return self.sampler_binding.deploy(source)

    def _resolve_sampler(self) -> Tuple[Tuple, Callable, str]:
        with timed(self.metrics, "serve.resolve"):
            b = self.sampler_binding
            if b is None or (b.default is None and b.registry.resolve(
                    b.user_id, b.slot) is None):
                return ("sampler", "builtin", 0), default_sampler, "builtin"
            r = b.current()
            return r.fingerprint, (r.fn if not r.is_default
                                   else default_sampler), r.md5

    def _step(self, fp, sampler, md5, params, tok, cache, pos, key):
        """One decode step on the executable for ``fp``; the first call of
        a new one (trace, lower, compile) is a ``serve.rebuild``."""
        ex = self._cache.get(fp)
        if ex is not None:
            return ex(params, tok, cache, pos, key)
        with rebuild_span(self.metrics, self.spans, "serve.rebuild",
                          {"sampler": md5}):
            ex = jax.jit(make_serve_step(self.model, self.ctx, sampler),
                         donate_argnums=(2,))
            out = ex(params, tok, cache, pos, key)
        self._cache[fp] = ex
        self.metrics.inc("serve.rebuilds")
        return out

    # ------------------------------------------------------------------
    def prefill(self, params, prompt: jax.Array,
                frames: Optional[jax.Array] = None, *, cache=None,
                row: int = 0):
        """(last-position logits, cache, next position) of ``prompt``
        [B, S]. Without ``cache`` into a new cache of B rows; with one
        (donated), into its rows [row, row + B), the rest kept."""
        if cache is not None:
            return self._fill(params, prompt, cache, row)
        if self._prefill_jit is None:
            model, ctx = self.model, self.ctx
            if model.cfg.is_encoder_decoder:
                def prefill(p, t, f, c):
                    return model.prefill(p, t, f, c, ctx)
            else:
                def prefill(p, t, c):
                    return model.prefill(p, t, c, ctx)
            self._prefill_jit = jax.jit(prefill)
        with timed(self.metrics, "serve.prefill"):
            cache = self.model.init_cache(prompt.shape[0], self.max_seq,
                                          self.ctx)
            if self.model.cfg.is_encoder_decoder:
                return self._prefill_jit(params, prompt, frames, cache)
            return self._prefill_jit(params, prompt, cache)

    def fill_program(self):
        """The jitted prefill of a chunk of rows into a larger cache (the
        cache donated): the rows are sliced out along each leaf's batch
        axis, prefilled and written back, in one program."""
        if self._fill_jit is None:
            model, ctx = self.model, self.ctx
            is_axes = (lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
            bax = [a.index("batch") if "batch" in a else None
                   for a in jax.tree.leaves(model.cache_axes(),
                                            is_leaf=is_axes)]

            def fill(p, t, c, row):
                leaves, tree = jax.tree.flatten(c)
                sub = [x if b is None else jax.lax.dynamic_slice_in_dim(
                    x, row, t.shape[0], axis=b) for x, b in zip(leaves, bax)]
                logits, sub, pos = model.prefill(
                    p, t, jax.tree.unflatten(tree, sub), ctx)
                out = [s if b is None else jax.lax.dynamic_update_slice_in_dim(
                    x, s, row, axis=b)
                    for x, s, b in zip(leaves, jax.tree.leaves(sub), bax)]
                return logits, jax.tree.unflatten(tree, out), pos
            self._fill_jit = jax.jit(fill, donate_argnums=(2,))
        return self._fill_jit

    def _fill(self, params, prompt, cache, row):
        fill = self.fill_program()
        with timed(self.metrics, "serve.prefill"):
            return fill(params, prompt, cache, jnp.asarray(row, jnp.int32))

    def generate(self, params, prompt: jax.Array, n_tokens: int, *,
                 frames: Optional[jax.Array] = None, seed: int = 0,
                 on_token: Optional[Callable[[int, jax.Array], None]] = None
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Prefill, then the decode loop with per-token sampler rebinding
        (hot-swap point)."""
        logits, cache, pos = self.prefill(params, prompt, frames=frames)
        key = jax.random.PRNGKey(seed)
        fp, sampler, md5 = self._resolve_sampler()
        with timed(self.metrics, "serve.first_token"), \
                jax.named_scope("sampler"):
            tok = sampler(logits, key).astype(jnp.int32)
        out, md5s, *_ = self._loop(params, tok, cache, pos, key,
                                   n_tokens - 1, on_token)
        return jnp.stack([tok] + out, axis=1), {
            "sampler_md5s": [md5] + md5s, "rebuilds": self.rebuilds}

    def decode(self, params, token: jax.Array, cache, pos, n_tokens: int, *,
               seed: int = 0,
               on_token: Optional[Callable[[int, jax.Array], None]] = None):
        """``n_tokens`` steps of the decode loop from ``token`` [B] at
        position ``pos`` in ``cache`` (donated): each step writes the
        token's row at its position and samples the next token. Returns
        (tokens [B, n_tokens], cache, next position, info)."""
        out, md5s, cache, pos = self._loop(
            params, token, cache, jnp.asarray(pos, jnp.int32),
            jax.random.PRNGKey(seed), n_tokens, on_token)
        return jnp.stack(out, axis=1), cache, pos, {
            "sampler_md5s": md5s, "rebuilds": self.rebuilds}

    def _loop(self, params, tok, cache, pos, key, n: int, on_token):
        """The decode loop both entry points share."""
        out, md5s = [], []
        load = getattr(self.model, "expert_load", None)
        for i in range(n):
            fp, sampler, md5 = self._resolve_sampler()   # swap boundary
            tok, cache, pos, key = self._step(fp, sampler, md5, params, tok,
                                              cache, pos, key)
            out.append(tok)
            md5s.append(md5)
            if self.expert_load.on and load is not None:
                self.expert_load.record(load(cache))
            if on_token is not None:
                on_token(i, tok)
        return out, md5s, cache, pos
