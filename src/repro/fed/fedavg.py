"""FedAvg as a first-class fleet workload, riding the assignment/task
machinery.

The paper (§3) points out that active-code replacement makes "even the
most complex OODIDA use cases", federated learning included, expressible
as ad-hoc custom code. We reproduce that literally — and, since PR 10,
*deployably*: nothing federated lives as an in-proc closure, so the same
session drives in-proc fleets and the sharded multi-process TCP fleet.

* the **round driver** is an active-code slot (``federated_round``):
  a context-aware module (``run(window, ctx)``) deployed through the
  normal code-replacement path. Each client synthesizes its supervised
  data from its own telemetry window plus a shift derived
  deterministically from ``client_id`` (stable under churn/re-homing)
  and the ``model_seed`` shipped in ``task.params`` — no cross-process
  state;
* the **client update rule** is an active-code slot (``client_update``):
  ``run(flat_params, xs, ys)`` -> updated flat params — swappable
  **between rounds** of an ongoing federated assignment, per cohort
  (the paper's A/B use case: ``FederatedSession.run_ab``);
* the **aggregator** is a cloud-side slot (``fed_aggregate``), default
  FedAvg (mean); deployed with ``Target.CLOUD`` it installs on the
  shard/router path, so sharded fleets aggregate at the router after
  the exact cross-shard merge;
* every client's round result is tagged with the md5 of the *update
  rule* that produced it (the round driver re-tags via the context
  envelope); the round commits through the majority filter, so a round
  never mixes updates computed by different rules (the paper's
  consistency guarantee, applied to FL), and carries the local training
  loss as ``TaggedResult.metric`` so ``IterationEvent.arm_stats``
  accumulates per-arm loss traces that merge exactly across shard legs.

The model here is a linear-regression-with-features head (flat
parameter vector) — deliberately small so a fleet round is
milliseconds; the LM path lives in train/ and launch/.
"""
from __future__ import annotations

import inspect
import queue
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.core.assignment import IterationEvent, Status, Target
from repro.core.fleet import ClientApp, Fleet
from repro.core.rollout import ArmStats, select_cohorts
from repro.core.validation import SlotSpec

DIM = 8   # feature dim of the toy federated model


# ---------------------------------------------------------------------------
# The federated math. Everything below until the slot specs is written
# against the active-code sandbox (numpy only, whitelisted builtins): the
# deployable module sources are assembled from these functions'
# *source text* via ``inspect.getsource``, so host-side helpers, the
# shipped round driver, and the tests all share one implementation.
# ---------------------------------------------------------------------------


def _features(xs):
    """Deterministic nonlinear features of a scalar stream [n] -> [n, DIM].
    Inputs are squashed to [-1, 1] first so powers stay bounded."""
    z = np.tanh(xs)
    t = np.stack([z ** i for i in range(1, DIM // 2 + 1)], axis=-1)
    return np.concatenate([t, np.sin(np.pi * t[:, :DIM - DIM // 2])], axis=-1)


def client_shift(client_id):
    """Per-client non-IID label shift, derived from the client's
    *identity* (FNV-1a over the id string), never from enumeration
    order — a client that drops and re-homes keeps its distribution."""
    h = 2166136261
    for b in client_id.encode("utf-8"):
        h = ((h ^ b) * 16777619) % 4294967296
    return 0.05 * (h % 8)


def true_model(seed):
    """The ground-truth weights every client's labels are generated
    from; pure function of the session seed shipped in ``task.params``
    (``model_seed``), so no closure has to cross the process boundary."""
    return np.random.default_rng(int(seed)).normal(size=DIM) * 0.5


def default_client_update(w, xs, ys, lr=0.05, epochs=5):
    """Local SGD on squared loss (the built-in fallback rule)."""
    f = _features(xs)
    w = np.asarray(w, dtype=np.float64)
    for _ in range(epochs):
        pred = f @ w
        grad = f.T @ (pred - ys) / len(ys)
        w = w - lr * grad
    return w


def _topk_keep(g, frac):
    """Indices of the ``max(1, int(n * frac))`` largest-|magnitude|
    coordinates (numpy mirror of ``optim.compression.topk_mask``,
    made exact-k so payload sizes are deterministic)."""
    k = max(1, int(g.shape[0] * frac))
    order = np.argsort(-np.abs(g), kind="stable")
    return np.sort(order[:k]).astype(np.int32)


def _decode_payload(p):
    """Reconstruct a weight vector from a round payload: plain list,
    ``int8_ef`` dict, or ``topk_ef`` dict."""
    if isinstance(p, dict):
        kind = p.get("kind")
        if kind == "int8_ef":
            return np.asarray(p["q"], dtype=np.float64) * float(p["scale"])
        if kind == "topk_ef":
            w = np.zeros(int(p["dim"]))
            idx = np.asarray(p["idx"], dtype=np.int64)
            w[idx] = np.asarray(p["val"], dtype=np.float64)
            return w
        raise ValueError(f"unknown payload kind {kind!r}")
    return np.asarray(p, dtype=np.float64)


def _round_payload(state, w_out, comp, frac):
    """Semantic (lossy) compression of the round payload with per-client
    error feedback: the residual is computed against ``_decode_payload``
    of the payload *actually shipped* (int8 dequantization, or the
    float32-round-tripped top-k values — shipping float32 but keeping a
    float64 residual is exactly the bias error feedback exists to kill),
    kept in ``state`` and added back next round. Composes with frame
    compression: these dicts ride the negotiated binary+zlib/zstd wire."""
    r = state.get("residual")
    gf = np.asarray(w_out, dtype=np.float64) + (r if r is not None else 0.0)
    if comp in ("int8", "int8_ef"):
        scale = max(float(np.max(np.abs(gf))), 1e-12) / 127.0
        q = np.clip(np.round(gf / scale), -127, 127).astype(np.int8)
        payload = {"kind": "int8_ef", "q": q, "scale": float(scale)}
    elif comp in ("topk", "topk_ef"):
        idx = _topk_keep(gf, frac)
        payload = {"kind": "topk_ef", "dim": int(gf.shape[0]),
                   "idx": idx, "val": gf[idx].astype(np.float32)}
    else:
        raise ValueError(f"unknown weight compression {comp!r}; "
                         f"use 'int8_ef' or 'topk_ef'")
    # residual against what the cloud will actually reconstruct
    state["residual"] = gf - _decode_payload(payload)
    return payload


def fedavg_aggregate(stacked: np.ndarray) -> np.ndarray:
    """[n_clients, DIM] -> [DIM] (unweighted FedAvg)."""
    return np.mean(np.asarray(stacked, dtype=np.float64), axis=0)


# -- deployable module sources ----------------------------------------------

_SANDBOX_HEADER = "import numpy as np\n\nDIM = 8\n\n"


def _sources(*fns) -> str:
    return "\n\n".join(inspect.getsource(f).rstrip() for f in fns) + "\n"


#: The ``federated_round`` driver, shipped through the code-replacement
#: path like any other analyst module. ``run(window, ctx)`` opts into
#: the task context (identity, params, per-method state, slot resolver)
#: and returns a tagged envelope: the payload is the (optionally
#: compressed) updated weights, the code hash is the *optimizer rule's*
#: md5 (so the majority filter keys on the rule, and a round never mixes
#: rules), and the metric is the post-update local training loss.
FEDERATED_ROUND_SOURCE = (
    _SANDBOX_HEADER
    + _sources(_features, client_shift, true_model, default_client_update,
               _topk_keep, _decode_payload, _round_payload)
    + '''

def run(xs, ctx):
    p = ctx["params"]
    w_in = np.asarray(p["weights"], dtype=np.float64)
    ys = _features(xs) @ true_model(p.get("model_seed", 0)) \\
        + client_shift(ctx["client_id"])
    rule = ctx["resolve"]("client_update")
    if rule is not None:
        fn, md5 = rule
        w_out = np.asarray(fn(w_in, xs, ys), dtype=np.float64)
    else:
        w_out = default_client_update(w_in, xs, ys)
        md5 = "builtin:client_update"
    loss = float(np.mean((_features(xs) @ w_out - ys) ** 2))
    comp = p.get("compression")
    payload = (_round_payload(ctx["state"], w_out, comp,
                              float(p.get("compression_frac", 0.25)))
               if comp else w_out.tolist())
    return {"__tagged__": True, "code_md5": md5, "payload": payload,
            "metric": loss}
''')


#: Arm-A / incumbent optimizer rule: plain local SGD, identical math to
#: ``default_client_update`` but deployed (distinct md5 from the builtin
#: tag, so hot-swaps and rollbacks are observable in ``winning_md5``).
SGD_UPDATE_SOURCE = (
    _SANDBOX_HEADER + _sources(_features) + '''

def run(w, xs, ys):
    """Local SGD on squared loss (incumbent rule)."""
    f = _features(xs)
    w = np.asarray(w, dtype=np.float64)
    for _ in range(5):
        grad = f.T @ (f @ w - ys) / len(ys)
        w = w - 0.05 * grad
    return w
''')


#: Arm-B / challenger rule: AdamW-style per-coordinate adaptive step
#: with decoupled weight decay (``optim/adamw.py``'s update rule,
#: restated in sandbox numpy), same 5 local epochs.
ADAM_UPDATE_SOURCE = (
    _SANDBOX_HEADER + _sources(_features) + '''

def run(w, xs, ys):
    """AdamW-style local update (challenger rule)."""
    f = _features(xs)
    w = np.asarray(w, dtype=np.float64)
    m = np.zeros(w.shape[0])
    v = np.zeros(w.shape[0])
    b1, b2, lr, wd = 0.9, 0.999, 0.1, 0.001
    for t in range(1, 6):
        grad = f.T @ (f @ w - ys) / len(ys)
        m = b1 * m + (1.0 - b1) * grad
        v = b2 * v + (1.0 - b2) * grad * grad
        mhat = m / (1.0 - b1 ** t)
        vhat = v / (1.0 - b2 ** t)
        w = w - lr * (mhat / (np.sqrt(vhat) + 1e-8) + wd * w)
    return w
''')


#: The cloud-side aggregator. Deployed with ``Target.CLOUD`` it installs
#: into the cloud app that actually aggregates: the flat ``CloudNode``'s
#: when unsharded, the *router's* when sharded (legs strip
#: ``cloud_method``; aggregation runs once, after the exact merge).
FED_AGGREGATE_SOURCE = '''
import numpy as np

def run(stacked):
    """Unweighted FedAvg: stacked [n, DIM] client weights -> [DIM]."""
    return np.mean(np.asarray(stacked, dtype=np.float64), axis=0)
'''


def client_update_slot() -> SlotSpec:
    import jax.numpy as jnp

    def probe():
        return (jnp.zeros((DIM,)), jnp.zeros((16,)), jnp.zeros((16,)))

    def check(out) -> Optional[str]:
        if getattr(out, "shape", None) != (DIM,):
            return f"client_update must return shape ({DIM},), got " \
                   f"{getattr(out, 'shape', None)}"
        return None

    return SlotSpec(name="client_update", probe_args=probe,
                    check_output=check,
                    doc="run(w [DIM], xs [n], ys [n]) -> w' [DIM]")


def fed_aggregate_slot() -> SlotSpec:
    import jax.numpy as jnp

    def probe():
        return (jnp.zeros((3, DIM)),)

    def check(out) -> Optional[str]:
        if getattr(out, "shape", None) != (DIM,):
            return f"fed_aggregate must return shape ({DIM},)"
        return None

    return SlotSpec(name="fed_aggregate", probe_args=probe,
                    check_output=check,
                    doc="run(stacked [n,DIM]) -> w [DIM]")


class FederatedRoundError(RuntimeError):
    """A federated round failed to commit exactly one iteration: the
    handle timed out, the assignment terminated abnormally, or the
    iteration count was wrong. Carries what is known about the round so
    the failure names itself instead of surfacing as a bare unpack
    ``ValueError``."""

    def __init__(self, round_ix: int, detail: str,
                 n_accepted: int = 0, n_dropped: int = 0):
        super().__init__(
            f"federated round {round_ix} failed: {detail} "
            f"(accepted={n_accepted}, dropped={n_dropped})")
        self.round_ix = round_ix
        self.n_accepted = n_accepted
        self.n_dropped = n_dropped


@dataclass
class FederatedSession:
    """Runs FedAvg rounds over a Fleet; the target fn is a per-client
    regression ys = g(xs) + shift with a client-identity-derived shift
    (non-IID). Works identically over in-proc and TCP fleets: all
    federated code reaches the clients as deployed active modules."""

    fleet: Optional[Fleet]
    user_id: str = "analyst"
    seed: int = 0
    w: np.ndarray = field(default_factory=lambda: np.zeros(DIM))
    round_log: List[Dict[str, Any]] = field(default_factory=list)
    ab_log: List[Dict[str, Any]] = field(default_factory=list)
    round_timeout_s: float = 30.0

    def __post_init__(self):
        self.true_w = true_model(self.seed)
        self._round_module_ready = False
        self._cloud_aggregate_ready = False

    # -- payload helpers (shared with the deployed module) -------------------
    @staticmethod
    def _compress_payload(app: ClientApp, w_out: np.ndarray, comp: str,
                          frac: float) -> Dict[str, Any]:
        """Host-side wrapper over the module's ``_round_payload`` (same
        source text ships to the clients); error-feedback state lives on
        ``app.fed_state``."""
        state = getattr(app, "fed_state", None)
        if state is None:
            state = app.fed_state = {}
        return _round_payload(state, w_out, comp, frac)

    @staticmethod
    def decode_payload(p: Any) -> np.ndarray:
        """Inverse of ``_compress_payload`` (identity for plain lists)."""
        return _decode_payload(p)

    # -- module deployment ---------------------------------------------------
    def ensure_round_module(self, frontend,
                            client_ids: Sequence[str] = ()) -> None:
        """Deploy the ``federated_round`` driver (idempotent per
        session); every round thereafter resolves it client-side with
        reload-per-iteration semantics."""
        if self._round_module_ready:
            return
        dep = frontend.deploy_code("federated_round", FEDERATED_ROUND_SOURCE,
                                   client_ids=client_ids)
        dep.result(timeout=self.round_timeout_s)
        self._round_module_ready = True

    def ensure_cloud_aggregate(self, frontend) -> None:
        """Deploy ``fed_aggregate`` to the cloud side (router when
        sharded), idempotently."""
        if self._cloud_aggregate_ready:
            return
        dep = frontend.deploy_code("fed_aggregate", FED_AGGREGATE_SOURCE,
                                   target=Target.CLOUD)
        dep.result(timeout=self.round_timeout_s)
        self._cloud_aggregate_ready = True

    # -- round plumbing ------------------------------------------------------
    def _round_params(self, weights: np.ndarray,
                      compression: Optional[str],
                      compression_frac: float,
                      cloud_aggregate: bool) -> Dict[str, Any]:
        params: Dict[str, Any] = {"weights": np.asarray(weights).tolist(),
                                  "n_values": 64,
                                  "code_user": self.user_id,
                                  "model_seed": self.seed}
        if compression is not None:
            params["compression"] = compression
            params["compression_frac"] = compression_frac
        if cloud_aggregate:
            params["cloud_method"] = "fed_aggregate"
        return params

    def _commit_round(self, handle, round_ix: int) -> IterationEvent:
        """Drive one round's handle to completion; clear failure beats
        a bare unpack ``ValueError`` when the fleet overruns the window
        (e.g. a shard re-home) or the assignment dies."""
        try:
            results, done = handle.result(timeout=self.round_timeout_s)
        except queue.Empty:
            raise FederatedRoundError(
                round_ix, f"no DoneEvent within {self.round_timeout_s:.1f}s "
                          f"(fleet did not commit the iteration in time)"
            ) from None
        last = results[-1] if results else None
        n_acc = last.n_accepted if last is not None else 0
        n_drop = last.n_dropped if last is not None else 0
        if done.status is not Status.DONE:
            raise FederatedRoundError(
                round_ix, f"assignment ended {done.status.value!r} "
                          f"({done.detail or 'no detail'})", n_acc, n_drop)
        if len(results) != 1:
            raise FederatedRoundError(
                round_ix, f"expected exactly 1 committed iteration, "
                          f"got {len(results)}", n_acc, n_drop)
        return results[0]

    def _aggregate_value(self, vals: Any) -> np.ndarray:
        """Turn one committed iteration's value into the new global
        weights. Decodes *per element* — a mid-session module swap may
        legally mix plain-list and compressed-dict payloads in one
        round — and aggregates unless the cloud slot already did."""
        if isinstance(vals, list) and vals \
                and isinstance(vals[0], (dict, list)):
            stacked = np.stack([_decode_payload(p) for p in vals])
        else:
            stacked = np.asarray(vals, dtype=np.float64)
        if stacked.ndim == 2:            # raw per-client list: aggregate
            agg = None
            if self.fleet is not None and self.fleet.cloud_app is not None:
                agg = self.fleet.cloud_app.registry.resolve(
                    self.user_id, "fed_aggregate")
            return (np.asarray(agg.fn(stacked), dtype=np.float64)
                    if agg is not None else fedavg_aggregate(stacked))
        return stacked                   # aggregated by the cloud slot

    # -- round loop ----------------------------------------------------------
    def run_rounds(self, frontend, n_rounds: int,
                   client_ids: Sequence[str] = (), *,
                   compression: Optional[str] = None,
                   compression_frac: float = 0.25,
                   cloud_aggregate: bool = False) -> np.ndarray:
        """Each round is one assignment driven through its handle; the
        per-round handle is the same control surface every other
        submission path uses (cancel/status/typed events included).

        ``compression`` turns on semantic weight-payload compression on
        the clients (``"int8_ef"`` or ``"topk_ef"`` with keep-fraction
        ``compression_frac``, both error-feedback corrected across
        rounds); the compressed payloads are decoded here before
        aggregation. ``cloud_aggregate`` instead runs the deployed
        ``fed_aggregate`` slot on the cloud/router path (uncompressed
        payloads only — the cloud slot stacks raw weight vectors)."""
        if cloud_aggregate and compression is not None:
            raise ValueError("cloud_aggregate requires uncompressed "
                             "payloads (the cloud slot stacks raw vectors)")
        self.ensure_round_module(frontend, client_ids)
        if cloud_aggregate:
            self.ensure_cloud_aggregate(frontend)
        for _ in range(n_rounds):
            handle = frontend.submit_analytics(
                "federated_round", iterations=1, client_ids=client_ids,
                params=self._round_params(self.w, compression,
                                          compression_frac, cloud_aggregate))
            it = self._commit_round(handle, len(self.round_log))
            self.w = self._aggregate_value(it.value)
            err = float(np.linalg.norm(self.w - self.true_w))
            self.round_log.append({
                "round": len(self.round_log), "err": err,
                "winning_md5": it.winning_md5,
                "n_accepted": it.n_accepted,
                "n_dropped": it.n_dropped,
                "compression": compression,
            })
        return self.w

    # -- live A/B of optimizer rules -----------------------------------------
    def run_ab(self, frontend, n_rounds: int,
               client_ids: Sequence[str] = (), *,
               swap_round: Optional[int] = None,
               fraction: float = 0.5,
               initial_rule: str = SGD_UPDATE_SOURCE,
               swap_rule: str = ADAM_UPDATE_SOURCE,
               compression: Optional[str] = None,
               compression_frac: float = 0.25,
               cloud_aggregate: bool = False) -> List[Dict[str, Any]]:
        """The paper's headline use case, live on the fleet: one ongoing
        federated session, split 50/50 (``select_cohorts``, churn-stable)
        into arms A (control) and B (canary); at ``swap_round`` the B
        cohort's ``client_update`` rule is hot-swapped via a
        subset-targeted deploy *between rounds*. Each arm trains its own
        model in its own per-round assignment (so the majority filter
        guards rule consistency *within* an arm instead of letting one
        arm's results evict the other's), results are arm-stamped via
        ``params["arms"]``, and per-round per-arm rows — convergence
        error, mean local loss from ``arm_stats``, ``winning_md5`` — are
        appended to ``ab_log``."""
        ids = tuple(client_ids)
        if not ids and self.fleet is not None:
            ids = tuple(self.fleet.client_ids())
        if len(ids) < 2:
            raise ValueError("run_ab needs at least 2 clients to split")
        if swap_round is None:
            swap_round = n_rounds // 2
        split = select_cohorts(ids, fraction, seed=self.seed)
        members = {"A": split.control, "B": split.canary}

        self.ensure_round_module(frontend, ids)
        if cloud_aggregate:
            self.ensure_cloud_aggregate(frontend)
        dep = frontend.deploy_code("client_update", initial_rule,
                                   client_ids=ids)
        dep.result(timeout=self.round_timeout_s)

        weights = {arm: np.array(self.w, dtype=np.float64)
                   for arm in members}
        for r in range(n_rounds):
            if r == swap_round:
                dep_b = frontend.deploy_code("client_update", swap_rule,
                                             client_ids=members["B"])
                dep_b.result(timeout=self.round_timeout_s)
            handles = {}
            for arm, cohort in members.items():
                params = self._round_params(weights[arm], compression,
                                            compression_frac,
                                            cloud_aggregate)
                params["arms"] = {cid: arm for cid in cohort}
                handles[arm] = frontend.submit_analytics(
                    "federated_round", iterations=1,
                    client_ids=cohort, params=params)
            for arm in members:
                it = self._commit_round(handles[arm], r)
                weights[arm] = self._aggregate_value(it.value)
                stats = ArmStats.from_report((it.arm_stats or {}).get(arm))
                self.ab_log.append({
                    "round": r, "arm": arm,
                    "err": float(np.linalg.norm(weights[arm] - self.true_w)),
                    "loss": stats.metric_mean,
                    "winning_md5": it.winning_md5,
                    "n_accepted": it.n_accepted,
                    "n_dropped": it.n_dropped,
                })
        self.ab_weights = weights
        return self.ab_log
