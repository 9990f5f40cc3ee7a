"""Train-step builder with active-code slots.

The paper's "custom on-board method" maps to pure-function *slots*
inside the jitted step: ``train_loss``, ``train_metrics``, and
``grad_transform``. Slots resolve through `core.registry.Binding`s; the
step builder keys a jit-executable cache on the tuple of slot
fingerprints (slot, md5, version):

* unchanged code => one integer/string compare per iteration, zero
  recompile (cheaper than the paper, which re-reads the module file);
* changed code   => rebuild the closure and re-jit *only this step*;
  every previously-seen version stays in the cache, so A/B flip-flops
  re-jit nothing after first use.

Every step's metrics carry the md5s of the code that produced them
(``code_md5`` field) — the fleet-level majority filter consumes these.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.core.registry import Binding
from repro.core.telemetry import Metrics, rebuild_span, timed
from repro.core.tracing import SpanRecorder
from repro.models.blocks import ModelCtx
from repro.optim.api import Optimizer
from repro.optim.clip import clip_by_global_norm
from repro.optim.compression import build_compressor
from repro.sharding.auto import run_rules
from repro.train.state import TrainState

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Default slot implementations (the pre-deployed "library of methods")
# ---------------------------------------------------------------------------

def default_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean cross entropy; logits fp32 [B,S,V], labels int32 [B,S]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    return jnp.mean(logz - gold)


def default_metrics(logits: jax.Array, labels: jax.Array
                    ) -> Dict[str, jax.Array]:
    pred = jnp.argmax(logits, axis=-1)
    return {"accuracy": jnp.mean((pred == labels).astype(jnp.float32))}


# ---------------------------------------------------------------------------
# Context / forward adapters
# ---------------------------------------------------------------------------

def build_ctx(cfg: RunConfig, mesh=None, rules=None,
              decode: bool = False) -> ModelCtx:
    """``ModelCtx`` for a run: its mesh, rules and axes, and for
    ``decode`` the decode step's kernels."""
    if rules is None and mesh is not None:
        rules = run_rules(cfg)
    kernels = {}
    if decode:
        kernels = dict(ssd_impl="auto", gmm_impl="auto")
        if mesh is not None and cfg.shape.kind == "decode":
            kernels["decode_attn_impl"] = "seqshard"
    return ModelCtx(mesh=mesh, rules=rules, tp_axis=cfg.sharding.tp_axis,
                    batch_axes=cfg.sharding.batch_axes,
                    remat_policy=cfg.train.remat_policy, **kernels)


def model_forward(model, params, batch: Dict[str, jax.Array], ctx: ModelCtx
                  ) -> Tuple[jax.Array, jax.Array]:
    if model.cfg.is_encoder_decoder:
        return model.forward(params, batch["tokens"], batch["frames"], ctx)
    return model.forward(params, batch["tokens"], ctx)


# ---------------------------------------------------------------------------
# Step factory
# ---------------------------------------------------------------------------

def make_train_step(
    model, cfg: RunConfig, optimizer: Optimizer, ctx: ModelCtx, *,
    loss_fn: Callable = default_loss,
    metrics_fn: Callable = default_metrics,
    grad_tx: Optional[Callable] = None,
    mesh=None,
) -> Callable[[TrainState, Dict[str, jax.Array]],
              Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build an (unjitted) train_step closure over the given slot fns."""
    tc = cfg.train
    M = tc.num_microbatches
    acc_dtype = jnp.dtype(tc.grad_accum_dtype)
    compressor = grad_tx if grad_tx is not None else build_compressor(
        tc.grad_compression)

    def loss_and_metrics(params, mb):
        logits, aux = model_forward(model, params, mb, ctx)
        with jax.named_scope("loss"):
            loss = loss_fn(logits, mb["labels"])
        total = loss + AUX_LOSS_WEIGHT * aux
        mets = metrics_fn(logits, mb["labels"])
        return total, (loss, aux, mets)

    grad_fn = jax.value_and_grad(loss_and_metrics, has_aux=True)

    def one_microbatch(params, mb):
        (_, (loss, aux, mets)), grads = grad_fn(params, mb)
        return grads, loss, aux, mets

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state.params
        if M <= 1:
            grads, loss, aux, mets = one_microbatch(params, batch)
        else:
            if batch["tokens"].ndim == 3:
                mbs = batch        # already [M, B/M, ...] (launch path)
            else:
                mbs = jax.tree.map(
                    lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]),
                    batch)

            def scan_body(acc, mb):
                g, l, a, m = one_microbatch(params, mb)
                acc = jax.tree.map(
                    lambda s, gi: s + gi.astype(acc_dtype), acc, g)
                return acc, (l, a, m)

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            gsum, (ls, auxs, ms) = jax.lax.scan(scan_body, zero, mbs)
            grads = jax.tree.map(lambda g: (g / M).astype(jnp.float32), gsum)
            loss, aux = ls.mean(), auxs.mean()
            mets = jax.tree.map(lambda m: m.mean(), ms)

        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)

            comp_state = state.comp_state
            if compressor is not None:
                grads, comp_state = compressor(grads, comp_state)

            lr = optimizer.schedule(state.step)
            new_params, new_opt = optimizer.update(grads, state.opt_state,
                                                   params, lr)
        metrics = {"loss": loss, "aux_loss": aux, "grad_norm": gnorm,
                   "lr": lr, **mets}
        return TrainState(new_params, new_opt, comp_state,
                          state.step + 1), metrics

    return train_step


# ---------------------------------------------------------------------------
# Hot-swap wrapper (the paper's mechanism at the training layer)
# ---------------------------------------------------------------------------

class HotSwapTrainStep:
    """Per-iteration slot rebinding around a jit cache.

    ``bindings`` maps slot name -> core.registry.Binding. The executable
    for a fingerprint tuple is built/jitted at most once.

    ``async_compile=True`` enables **zero-stall swap** (beyond-paper):
    when a deploy changes a slot, the new executable is AOT-compiled on
    a background thread while steps keep running the previous version;
    the loop cuts over at the first step boundary after compilation
    finishes. A code deploy then *never* stalls training — the paper's
    "does not require interrupting ongoing assignments", strengthened to
    cover compilation too. (One-version lag during the compile window;
    the metrics' md5 tags always tell which version a step ran.)

    ``metrics`` holds the ``train.*`` counters and span histograms (a
    ``TrainLoop`` over this step adds to the same one); ``spans`` keeps
    one ``train.rebuild`` record per executable built, with the new
    code's md5s and its trace, lower and backend-compile seconds.
    """

    SLOTS = ("train_loss", "train_metrics", "grad_transform")

    def __init__(self, model, cfg: RunConfig, optimizer: Optimizer,
                 bindings: Dict[str, Binding], *, mesh=None, rules=None,
                 donate: bool = True, async_compile: bool = False,
                 in_shardings=None, out_shardings=None):
        self.model = model
        self.cfg = cfg
        self.optimizer = optimizer
        self.bindings = bindings
        self.mesh = mesh
        self.ctx = build_ctx(cfg, mesh=mesh, rules=rules)
        self.donate = donate
        self.async_compile = async_compile
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self._cache: Dict[Tuple, Callable] = {}
        self._md5s: Dict[Tuple, Dict[str, str]] = {}
        self._compiling: Dict[Tuple, threading.Thread] = {}
        self._compile_errors: Dict[Tuple, Exception] = {}
        self._lock = threading.Lock()
        self.last_fingerprint: Optional[Tuple] = None
        self.active_fingerprint: Optional[Tuple] = None
        self.metrics = Metrics()
        self.spans = SpanRecorder("train")

    @property
    def swap_events(self) -> int:
        return int(self.metrics.counter("train.swap_events"))

    @property
    def rebuilds(self) -> int:
        return int(self.metrics.counter("train.rebuilds"))

    @property
    def stall_free_steps(self) -> int:
        """Steps served by the old version while the new one compiled
        in the background."""
        return int(self.metrics.counter("train.stall_free_steps"))

    def _resolve(self):
        fp, fns, md5s = [], {}, {}
        for slot in self.SLOTS:
            b = self.bindings.get(slot)
            if b is None or (b.default is None
                             and b.registry.resolve(b.user_id, slot) is None):
                # nothing deployed and no default: use the built-in method
                fp.append((slot, "unset", 0))
                fns[slot] = None
                md5s[slot] = "builtin"
                continue
            r = b.current()
            fp.append(r.fingerprint)
            fns[slot] = r.fn if not r.is_default else None
            md5s[slot] = r.md5
        fpt = tuple(fp)
        self._md5s[fpt] = md5s
        return fpt, fns, md5s

    def _build(self, fns) -> Callable:
        step = make_train_step(
            self.model, self.cfg, self.optimizer, self.ctx,
            loss_fn=fns["train_loss"] or default_loss,
            metrics_fn=fns["train_metrics"] or default_metrics,
            grad_tx=fns["grad_transform"],
            mesh=self.mesh)
        kw = {}
        if self.in_shardings is not None:
            kw["in_shardings"] = self.in_shardings
        if self.out_shardings is not None:
            kw["out_shardings"] = self.out_shardings
        if self.donate:
            kw["donate_argnums"] = (0,)
        return jax.jit(step, **kw)

    def _rebuild_span(self, md5s):
        return rebuild_span(self.metrics, self.spans, "train.rebuild", md5s)

    def _start_background_compile(self, fp, fns, md5s, state, batch) -> None:
        sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x),
                sharding=getattr(x, "sharding", None)),
            (state, batch))

        def work():
            # AOT warm-up compile against the live shapes so the cutover
            # step pays dispatch cost only. A compile error is kept and
            # raised at cutover, on the caller's thread.
            try:
                with self._rebuild_span(md5s):
                    ex = self._build(fns)
                    ex.lower(*sds).compile()
            except Exception as e:   # noqa: BLE001 - re-raised at cutover
                with self._lock:
                    self._compile_errors[fp] = e
                    self._compiling.pop(fp, None)
                return
            with self._lock:
                self._cache[fp] = ex
                self._compiling.pop(fp, None)
            self.metrics.inc("train.rebuilds")

        t = threading.Thread(target=work, daemon=True)
        self._compiling[fp] = t
        t.start()

    def __call__(self, state: TrainState, batch
                 ) -> Tuple[TrainState, Dict[str, Any]]:
        with timed(self.metrics, "train.resolve"):
            fp, fns, md5s = self._resolve()
        if fp != self.last_fingerprint and self.last_fingerprint is not None:
            self.metrics.inc("train.swap_events")
        self.last_fingerprint = fp
        with self._lock:
            ex = self._cache.get(fp)
            compiling = fp in self._compiling
            error = self._compile_errors.pop(fp, None)
        if error is not None:
            raise error
        if ex is None and (self.async_compile
                           and self.active_fingerprint is not None
                           and self.active_fingerprint in self._cache):
            # zero-stall: keep stepping the active version while the
            # new one compiles in the background
            if not compiling:
                with self._lock:
                    if fp not in self._compiling:
                        self._start_background_compile(
                            fp, fns, md5s, state, batch)
            fp_run = self.active_fingerprint
            ex = self._cache[fp_run]
            self.metrics.inc("train.stall_free_steps")
            # tag metrics with the md5s of the EXECUTED version —
            # the consistency filter must see what actually ran
            md5s = dict(self._md5s.get(fp_run, md5s))
            md5s["_pending_swap"] = True
        elif ex is None:
            # the first call traces, lowers and compiles the new step
            with self._rebuild_span(md5s):
                ex = self._build(fns)
                new_state, metrics = ex(state, batch)
            with self._lock:
                self._cache[fp] = ex
            self.metrics.inc("train.rebuilds")
            self.active_fingerprint = fp
            metrics["code_md5"] = md5s
            return new_state, metrics
        else:
            self.active_fingerprint = fp
        new_state, metrics = ex(state, batch)
        metrics["code_md5"] = md5s
        return new_state, metrics
