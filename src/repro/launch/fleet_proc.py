"""Multi-process OODIDA fleet launcher: real processes, real sockets.

The paper's deployment is one Erlang node per machine; this launcher is
the closest a laptop gets: the user frontend (and the router, when
sharded) stay in the calling process, and **every client node — and
every CloudNode shard — is a spawned child process** speaking
length-prefixed TCP frames. Nothing is shared — code modules, tasks,
and results exist on a client only after crossing the wire, exactly
like production.

Two entry points:

* ``spawn_tcp_fleet(n, shards=k)`` — programmatic; what
  ``Fleet.create(n, topology="tcp", shards=k)`` calls;
* ``python -m repro.launch.fleet_proc --clients 4 --shards 2 --churn``
  — CLI smoke: one deploy -> iterate -> redeploy -> rollback round
  across child processes, optionally killing one client mid-run to
  exercise eviction + straggler handling; exit code 0 on success (the
  CI jobs).

Children are started with the multiprocessing *spawn* context (never
fork: the parent runs dozens of actor threads) and are daemonic, so an
abandoned parent cannot leak them.

Placement: one process holds the accelerator. That is the calling
process, which runs the user/cloud side (and, beside the fleet,
training and serving). Clients stand in for vehicles' on-board
computers and shards for fabric nodes, so every child is placed on the
host CPU: ``JAX_PLATFORMS=cpu`` is in its environment from the moment
it is spawned, and a deployed ``jax.numpy`` module runs there on the
CPU backend. This is a stated placement, not a fallback: a child never
opens the chip that its parent holds.
"""
from __future__ import annotations

import argparse
import contextlib
import multiprocessing as mp
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence


def _env_json_clients() -> tuple:
    """Client ids pinned to legacy JSON framing via the
    ``REPRO_WIRE_JSON_CLIENTS`` env knob (comma-separated, e.g.
    ``REPRO_WIRE_JSON_CLIENTS=c000``). Read in the *parent* so only the
    named children are pinned — unlike ``REPRO_WIRE_ENCODING=json``,
    which children inherit and which would pin the whole fleet."""
    raw = os.environ.get("REPRO_WIRE_JSON_CLIENTS", "")
    return tuple(c.strip() for c in raw.split(",") if c.strip())


@contextlib.contextmanager
def _children_on_host_cpu():
    """Children spawned inside this block start with ``JAX_PLATFORMS=cpu``
    (see the module docstring); the parent's own backend is already
    chosen or is chosen by its own environment once the block exits."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


# ---------------------------------------------------------------------------
# Child process entry points
# ---------------------------------------------------------------------------


def _client_main(cfg: Dict[str, Any]) -> None:
    """Runs inside a spawned client process: build the client app, listen
    on TCP, register (the ClientNode actor does the handshake and, if
    configured, heartbeats its owning cloud/shard), serve tasks until
    StopNode."""
    import numpy as np

    from repro.core import wirefmt
    from repro.core.fleet import ClientApp, ClientNode
    from repro.core.registry import ActiveCodeRegistry
    from repro.core.telemetry import NodeTelemetry
    from repro.core.transport import Node, TcpTransport

    rng = np.random.default_rng(cfg["seed"])
    data = rng.normal(loc=cfg["loc"], scale=1.0, size=cfg["n_values"])
    registry = ActiveCodeRegistry(store_root=cfg.get("store_root"))
    app = ClientApp(cfg["client_id"], data, registry=registry)

    transport = TcpTransport()
    tel = (NodeTelemetry(cfg["node_id"])
           if cfg.get("telemetry", True) else None)
    # a JSON-pinned client advertises nothing but the mandatory fallback,
    # so the handshake settles every conversation with it on legacy JSON
    wire = (wirefmt.WireState(node_id=cfg["node_id"],
                              encodings=("json",), compressions=())
            if cfg.get("wire_json_only") else None)
    node = Node(cfg["node_id"], transport, telemetry=tel, wire=wire)
    transport.add_peer(cfg["cloud_node_id"], cfg["cloud_endpoint"])
    # dial the entry node + fire the wire Hello before the first
    # registration frame needs them
    node.prewarm_peer(cfg["cloud_node_id"])

    stop = threading.Event()
    actor = ClientNode(
        f"client.{cfg['client_id']}", app, stop_event=stop,
        register_with=cfg["cloud_addr"],
        endpoint=transport.endpoint,
        heartbeat_interval_s=cfg.get("heartbeat_interval_s"),
        heartbeat_miss_limit=cfg.get("heartbeat_miss_limit", 3))
    node.spawn(actor)
    stop.wait()
    node.close()


def _shard_main(cfg: Dict[str, Any]) -> None:
    """Runs inside a spawned shard process: one CloudNode shard that
    announces itself to the router, owns the clients the ring assigns it,
    and evicts the ones whose heartbeats stop."""
    from repro.core.fleet import CloudApp, CloudNode, RegisterShard
    from repro.core.registry import ActiveCodeRegistry
    from repro.core.telemetry import NodeTelemetry
    from repro.core.transport import Node, TcpTransport

    registry = ActiveCodeRegistry(store_root=cfg.get("store_root"))
    transport = TcpTransport()
    tel = (NodeTelemetry(cfg["shard_id"])
           if cfg.get("telemetry", True) else None)
    node = Node(cfg["shard_id"], transport, telemetry=tel)
    transport.add_peer(cfg["router_node_id"], cfg["router_endpoint"])
    # warm the shard->router connection ahead of RegisterShard
    node.prewarm_peer(cfg["router_node_id"])

    stop = threading.Event()
    cloud = CloudNode(
        "cloud", {}, CloudApp(registry), cfg["policy"],
        max_concurrent_assignments=cfg.get("max_concurrent_assignments"),
        heartbeat_timeout_s=cfg.get("eviction_timeout_s"),
        sweep_interval_s=cfg.get("sweep_interval_s"),
        straggler_grace_s=cfg.get("straggler_grace_s", 0.25),
        shard_heartbeat_interval_s=cfg.get("shard_heartbeat_interval_s"),
        router_addr=cfg["router_addr"],
        stop_event=stop)
    node.spawn(cloud)
    node.route(cfg["router_addr"],
               RegisterShard(cfg["shard_id"], node.address("cloud"),
                             transport.endpoint),
               sender="cloud")
    stop.wait()
    node.close()


# ---------------------------------------------------------------------------
# Parent-side launcher
# ---------------------------------------------------------------------------


def _fail_fast(procs: List[Any], nodes: List[Any], why: str,
               exc: type = RuntimeError) -> None:
    """Startup failed: reap every child, close the parent-side nodes,
    raise. The single teardown path for all launcher failure modes."""
    for p in procs:
        p.terminate()
    for n in nodes:
        n.close()
    raise exc(why)


def spawn_tcp_fleet(n_clients: int, *, shards: int = 1, seed: int = 0,
                    policy: Optional[Any] = None,
                    data_per_client: int = 4096,
                    store_root: Optional[str] = None,
                    max_concurrent_assignments: Optional[int] = None,
                    heartbeat_interval_s: Optional[float] = None,
                    eviction_timeout_s: Optional[float] = None,
                    sweep_interval_s: Optional[float] = None,
                    heartbeat_miss_limit: int = 3,
                    straggler_grace_s: float = 0.25,
                    shard_heartbeat_interval_s: Optional[float] = None,
                    shard_eviction_timeout_s: Optional[float] = None,
                    rehome_grace_s: float = 2.0,
                    ready_timeout_s: float = 120.0,
                    telemetry: bool = True,
                    json_clients: Sequence[str] = ()):
    """Build a ``Fleet`` whose client nodes — and, for ``shards > 1``,
    whose CloudNode shards — are child processes on TCP.

    ``json_clients`` (default: the ``REPRO_WIRE_JSON_CLIENTS`` env knob)
    names client ids pinned to legacy JSON framing — they advertise only
    the mandatory fallback in the wire-format handshake, so the rest of
    the fleet can negotiate binary while these peers stay readable by
    down-rev tooling (the mixed-encoding compatibility scenario).

    Blocks until every shard has completed the ``RegisterShard``
    handshake and every client the ``RegisterClient`` handshake
    (children pay their interpreter + jax import on this path) or raises
    ``TimeoutError`` after ``ready_timeout_s``, cleaning up the children.
    """
    from repro.core.consistency import QuorumPolicy
    from repro.core.fleet import CloudApp, CloudNode, Fleet, RouterNode
    from repro.core.registry import ActiveCodeRegistry
    from repro.core.telemetry import NodeTelemetry
    from repro.core.transport import Node, TcpTransport

    policy = policy or QuorumPolicy()
    json_pinned = frozenset(json_clients or _env_json_clients())
    ctx = mp.get_context("spawn")

    def make_tel(node_id: str):
        return NodeTelemetry(node_id) if telemetry else None

    user_transport = TcpTransport()
    user_node = Node("user", user_transport, telemetry=make_tel("user"))

    if shards == 1:
        server_transport = TcpTransport()
        server_node = Node("cloud", server_transport,
                           telemetry=make_tel("cloud"))
        cloud_reg = ActiveCodeRegistry(
            store_root=f"{store_root}/cloud" if store_root else None)
        cloud_app = CloudApp(cloud_reg)
        server: Any = CloudNode(
            "cloud", {}, cloud_app, policy,
            max_concurrent_assignments=max_concurrent_assignments,
            heartbeat_timeout_s=eviction_timeout_s,
            sweep_interval_s=sweep_interval_s,
            straggler_grace_s=straggler_grace_s)
        server_node.spawn(server)
        shard_procs: List[Any] = []
    else:
        server_transport = TcpTransport()
        server_node = Node("router", server_transport,
                           telemetry=make_tel("router"))
        router_reg = ActiveCodeRegistry(
            store_root=f"{store_root}/router" if store_root else None)
        cloud_app = CloudApp(router_reg)
        server = RouterNode(
            "router", {}, cloud_app,
            shard_eviction_timeout_s=shard_eviction_timeout_s,
            rehome_grace_s=rehome_grace_s)
        server_node.spawn(server)
        server_addr = server_node.address(server.name)
        shard_procs = []
        for j in range(shards):
            sid = f"shard{j}"
            cfg = {
                "shard_id": sid,
                "router_node_id": server_node.node_id,
                "router_endpoint": server_transport.endpoint,
                "router_addr": server_addr,
                "policy": policy,
                "max_concurrent_assignments": max_concurrent_assignments,
                "eviction_timeout_s": eviction_timeout_s,
                "sweep_interval_s": sweep_interval_s,
                "straggler_grace_s": straggler_grace_s,
                "shard_heartbeat_interval_s": shard_heartbeat_interval_s,
                "store_root": f"{store_root}/{sid}" if store_root else None,
                "telemetry": telemetry,
            }
            p = ctx.Process(target=_shard_main, args=(cfg,), daemon=True,
                            name=f"fleet-{sid}")
            with _children_on_host_cpu():
                p.start()
            shard_procs.append(p)
        deadline = time.time() + ready_timeout_s
        while server.n_shards < shards:
            if time.time() > deadline:
                _fail_fast(shard_procs, [server_node, user_node],
                           f"only {server.n_shards}/{shards} shards "
                           f"registered within {ready_timeout_s:.0f}s",
                           exc=TimeoutError)
            if any(p.exitcode not in (None, 0) for p in shard_procs):
                _fail_fast(shard_procs, [server_node, user_node],
                           "a shard process died during startup")
            time.sleep(0.02)

    server_addr = server_node.address(server.name)
    user_transport.add_peer(server_node.node_id, server_transport.endpoint)
    server_transport.add_peer("user", user_transport.endpoint)
    # both directions of the user<->server pair are known now: warm them
    # so the first submission and its first event reply skip the dial
    user_node.prewarm_peer(server_node.node_id)
    server_node.prewarm_peer("user")

    procs = []
    for i in range(n_clients):
        cid = f"c{i:03d}"
        cfg = {
            "client_id": cid,
            "node_id": cid,
            "seed": [seed, i],
            "loc": float(i),
            "n_values": data_per_client,
            "store_root": f"{store_root}/{cid}" if store_root else None,
            "cloud_node_id": server_node.node_id,
            "cloud_endpoint": server_transport.endpoint,
            "cloud_addr": server_addr,
            "heartbeat_interval_s": heartbeat_interval_s,
            "heartbeat_miss_limit": heartbeat_miss_limit,
            "telemetry": telemetry,
            "wire_json_only": cid in json_pinned,
        }
        p = ctx.Process(target=_client_main, args=(cfg,), daemon=True,
                        name=f"fleet-client-{cid}")
        with _children_on_host_cpu():
            p.start()
        procs.append(p)

    deadline = time.time() + ready_timeout_s
    while server.n_clients < n_clients:
        if time.time() > deadline:
            _fail_fast(procs + shard_procs, [server_node, user_node],
                       f"only {server.n_clients}/{n_clients} clients "
                       f"registered within {ready_timeout_s:.0f}s",
                       exc=TimeoutError)
        if any(p.exitcode not in (None, 0) for p in procs + shard_procs):
            _fail_fast(procs + shard_procs, [server_node, user_node],
                       "a child process died during startup")
        time.sleep(0.02)

    client_addrs = (dict(server.client_nodes) if shards == 1 else {})
    shard_addrs = (dict(server.shard_addrs) if shards > 1 else {})
    return Fleet(user_node=user_node, cloud_node=server_node,
                 cloud_addr=server_addr,
                 cloud_app=cloud_app, client_apps={},
                 client_nodes=[], client_addrs=client_addrs,
                 procs=procs, topology="tcp", shards=shards,
                 shard_addrs=shard_addrs, shard_procs=shard_procs,
                 server=server, telemetry=telemetry)


# ---------------------------------------------------------------------------
# CLI smoke: deploy -> iterate -> (kill a client) -> redeploy -> rollback
# ---------------------------------------------------------------------------

_V1 = """
import jax.numpy as jnp
def run(xs):
    return jnp.mean(xs) * 2.0
"""

_V2 = """
import jax.numpy as jnp
def run(xs):
    return jnp.mean(xs) * 4.0
"""

# a deliberately slow (~ms) jax-free mean: keeps an assignment in flight
# long enough for the shard-failover scenario to kill a shard mid-iteration
_SLOW_MEAN = """
import math
def run(xs):
    acc = 0.0
    for i in range(20000):
        acc += math.sin(i * 1e-3)
    return float(sum(float(x) for x in xs) / len(xs)) + acc * 1e-12
"""


def run_shard_failover_smoke(n_clients: int = 6, shards: int = 3,
                             iterations: int = 400,
                             verbose: bool = True) -> int:
    """The shard-liveness acceptance scenario over real processes: kill a
    CloudNode shard process mid-iteration and require the in-flight
    ``AssignmentHandle`` to reach ``DoneEvent`` (not a timeout), with the
    dead shard's clients re-homed onto survivors and counted in the
    committed iterations. Returns 0 on success (the CI smoke contract)."""
    from repro.core.assignment import Status

    def say(msg: str) -> None:
        if verbose:
            print(f"[fleet_proc] {msg}", flush=True)

    fleet = spawn_tcp_fleet(
        n_clients, shards=shards,
        heartbeat_interval_s=0.25, eviction_timeout_s=1.5,
        shard_heartbeat_interval_s=0.25, shard_eviction_timeout_s=1.5,
        rehome_grace_s=20.0)
    say(f"{n_clients} client processes across {shards} shard processes")
    try:
        fe = fleet.frontend("ci")
        v1 = fe.deploy_code("failover_mean", _SLOW_MEAN)
        _, done = v1.result(timeout=120.0)
        assert done.status == Status.DONE, f"deploy failed: {done.detail}"

        handle = fe.submit_analytics("failover_mean", iterations=iterations,
                                     params={"n_values": 16})
        stream = handle.events()
        first = next(stream)
        assert first.n_accepted == n_clients

        # pick a victim shard that owns clients, then kill its process
        owners = dict(fleet.server.clients)        # client_id -> shard id
        victim_sid = next(sid for sid in fleet.server.shard_addrs
                          if sid in owners.values())
        n_victim_clients = sum(1 for s in owners.values()
                               if s == victim_sid)
        victim = fleet.shard_procs[int(victim_sid.removeprefix("shard"))]
        victim.terminate()
        victim.join(timeout=10.0)
        say(f"killed {victim_sid} mid-iteration "
            f"({n_victim_clients} clients orphaned)")

        deadline = time.time() + 60.0
        while fleet.server.n_shards > shards - 1:
            if time.time() > deadline:
                raise AssertionError("router never evicted the dead shard")
            time.sleep(0.05)
        say(f"router evicted {victim_sid}; waiting for re-homing")

        results, done = handle.result(timeout=300.0)
        assert done.status == Status.DONE, \
            f"handle did not complete cleanly: {done.status} {done.detail}"
        assert len(results) == iterations
        # every committed iteration accounts for the whole fleet, and by
        # the end the orphans are re-homed and counted again
        assert all(r.n_accepted + r.n_dropped + r.n_stragglers == n_clients
                   for r in results)
        assert results[-1].n_accepted == n_clients, \
            f"re-homed clients missing: {results[-1]}"
        assert fleet.server.n_clients == n_clients
        say(f"assignment completed all {iterations} iterations; "
            f"{n_victim_clients} clients re-homed and counted")

        # the healed fleet is fully deployable: v2 reaches every client
        v2 = fe.deploy_code("failover_mean", _V2)
        _, done = v2.result(timeout=120.0)
        assert done.status == Status.DONE, f"redeploy failed: {done.detail}"
        assert f"{n_clients}/{n_clients}" in done.detail, done.detail
        say("shard failover verified across processes: PASS")
        return 0
    finally:
        fleet.shutdown()


_ROLLOUT_V1 = """
def run(xs):
    return 1.0
"""

# identical math, different md5 — the healthy canary candidate
_ROLLOUT_V2 = """
def run(xs):
    # tuned build, identical output
    return 1.0
"""

_ROLLOUT_BAD = """
def run(xs):
    raise RuntimeError('canary build is broken')
"""


def run_rollout_smoke(n_clients: int = 6, shards: int = 2,
                      verbose: bool = True) -> int:
    """The staged-rollout acceptance scenario over real processes: on a
    router + shard-process fleet of TCP clients, (a) canary an unhealthy
    build and require auto-rollback to leave every client on the
    incumbent, then (b) canary a healthy build and require promotion to
    land it fleet-wide. Returns 0 on success (the CI
    ``canary-rollout-smoke`` contract)."""
    from repro.core.assignment import Status
    from repro.core.rollout import GateDecision, HealthPolicy

    def say(msg: str) -> None:
        if verbose:
            print(f"[fleet_proc] {msg}", flush=True)

    fleet = spawn_tcp_fleet(n_clients, shards=shards)
    say(f"{n_clients} client processes across {shards} shard processes")
    try:
        fe = fleet.frontend("ci")
        v1 = fe.deploy_code("rollout_mean", _ROLLOUT_V1)
        _, done = v1.result(timeout=120.0)
        assert done.status == Status.DONE, f"deploy failed: {done.detail}"
        assert f"{n_clients}/{n_clients}" in done.detail, done.detail
        say(f"incumbent v1 ({v1.md5[:8]}) on all {n_clients} clients")

        # (a) unhealthy canary: errors trip the gate, auto-rollback
        bad = fe.start_rollout("rollout_mean", _ROLLOUT_BAD, fraction=0.34,
                               seed=7, health=HealthPolicy(window=2))
        say(f"canarying broken build to {len(bad.canary)} clients "
            f"({', '.join(bad.canary)})")
        decision = bad.run(timeout=120.0)
        assert decision is GateDecision.ROLLBACK, \
            f"broken canary was not rolled back: {decision}"
        kinds = [e.kind for e in bad.events]
        assert "canary_unhealthy" in kinds and kinds[-1] == "rolled_back", \
            f"unexpected rollout events: {kinds}"
        results, done = fe.submit_analytics(
            "rollout_mean", iterations=1,
            params={"n_values": 16}).result(timeout=120.0)
        assert done.status == Status.DONE, done.detail
        assert results[0].winning_md5 == v1.md5, \
            "fleet not restored to the incumbent after auto-rollback"
        assert results[0].n_accepted == n_clients, results[0]
        say(f"auto-rollback verified: all {n_clients} clients back on "
            f"v1 ({v1.md5[:8]})")

        # (b) healthy canary: the gate fills its window, then promotes
        good = fe.start_rollout("rollout_mean", _ROLLOUT_V2, fraction=0.34,
                                seed=7, health=HealthPolicy(window=2))
        decision = good.run(timeout=120.0)
        assert decision is GateDecision.PROMOTE, \
            f"healthy canary was not promoted: {decision}"
        kinds = [e.kind for e in good.events]
        assert kinds[-1] == "promoted" and "canary_unhealthy" not in kinds, \
            f"unexpected rollout events: {kinds}"
        results, done = fe.submit_analytics(
            "rollout_mean", iterations=1,
            params={"n_values": 16}).result(timeout=120.0)
        assert done.status == Status.DONE, done.detail
        assert results[0].winning_md5 == good.deployment.md5, \
            "promotion did not land fleet-wide"
        assert results[0].n_accepted == n_clients, results[0]
        say(f"promotion verified: all {n_clients} clients on "
            f"v2 ({good.deployment.md5[:8]})")
        say("staged rollout (auto-rollback + promote) over TCP: PASS")
        return 0
    finally:
        fleet.shutdown()


def run_fed_ab_smoke(n_clients: int = 8, shards: int = 2,
                     n_rounds: int = 12, swap_round: int = 6,
                     verbose: bool = True) -> int:
    """The federated-A/B acceptance scenario over real processes (the CI
    ``fed-ab-smoke`` contract): a sharded TCP fleet runs one ongoing
    ``FederatedSession.run_ab`` — deployable ``federated_round`` driver,
    cloud-side ``fed_aggregate`` on the router path, arm B's optimizer
    rule hot-swapped on a 50% cohort *between rounds* — and the smoke
    asserts both arms' loss traces are complete and no round ever mixed
    rules. A short compressed ``run_rounds`` tail exercises the
    compressed-weight payloads on the same fleet. Returns 0 on success."""
    from repro.fed.fedavg import FederatedSession

    def say(msg: str) -> None:
        if verbose:
            print(f"[fleet_proc] {msg}", flush=True)

    fleet = spawn_tcp_fleet(n_clients, shards=shards)
    say(f"{n_clients} client processes across {shards} shard processes")
    try:
        sess = FederatedSession(fleet, seed=3)
        fe = fleet.frontend(sess.user_id)
        log = sess.run_ab(fe, n_rounds=n_rounds, swap_round=swap_round,
                          cloud_aggregate=True)
        by_arm: Dict[str, list] = {}
        for row in log:
            by_arm.setdefault(row["arm"], []).append(row)
        assert sorted(by_arm) == ["A", "B"], sorted(by_arm)
        for arm, rows in by_arm.items():
            # trace completeness: every round contributed a row with a
            # convergence err and a mean local loss from arm_stats
            assert [r["round"] for r in rows] == list(range(n_rounds)), rows
            missing = [r["round"] for r in rows if r["loss"] is None]
            assert not missing, f"arm {arm} loss trace has holes: {missing}"
            # rule consistency: nothing dropped by the majority filter,
            # and winning_md5 single-valued per arm on each side of the
            # swap (arm A forever on the incumbent; arm B flips once)
            assert all(r["n_dropped"] == 0 for r in rows), rows
            md5s = [r["winning_md5"] for r in rows]
            assert len(set(md5s if arm == "A" else md5s[:swap_round])) == 1
            if arm == "B":
                assert len(set(md5s[swap_round:])) == 1
                assert md5s[0] != md5s[-1], \
                    "arm B's rule swap never took effect"
        assert by_arm["A"][-1]["winning_md5"] != \
            by_arm["B"][-1]["winning_md5"], "arms converged to one rule"
        say(f"A/B over {n_rounds} rounds: arm A on "
            f"{by_arm['A'][-1]['winning_md5'][:8]} throughout, arm B "
            f"hot-swapped to {by_arm['B'][-1]['winning_md5'][:8]} at "
            f"round {swap_round}, zero mixed-rule results")
        say(f"final err A={by_arm['A'][-1]['err']:.3f} "
            f"B={by_arm['B'][-1]['err']:.3f}; mean loss "
            f"A={by_arm['A'][-1]['loss']:.4f} B={by_arm['B'][-1]['loss']:.4f}")

        # compressed payloads riding the same binary wire
        sess.run_rounds(fe, 2, compression="topk_ef", compression_frac=0.5)
        assert len(sess.round_log) == 2, sess.round_log
        assert all(r["n_accepted"] >= n_clients // 2
                   for r in sess.round_log), sess.round_log
        say("2 topk_ef-compressed rounds on the same fleet: "
            f"err {sess.round_log[-1]['err']:.3f}")
        say("federated A/B with live optimizer hot-swap over TCP: PASS")
        return 0
    finally:
        fleet.shutdown()


def run_smoke(n_clients: int = 3, iterations: int = 3, shards: int = 1,
              churn: bool = False, verbose: bool = True,
              json_clients: Sequence[str] = ()) -> int:
    """One full active-code round over spawned processes; with ``churn``
    a client process is killed mid-run and the fleet must evict it,
    complete the round, and redeploy to the survivors. ``json_clients``
    (or ``REPRO_WIRE_JSON_CLIENTS``) pins the named clients to legacy
    JSON framing and the smoke additionally verifies the fleet really
    ran mixed-encoding: the rest spoke binary while the pinned peers
    never saw a binary frame. Returns 0 on success (the CI smoke
    contract)."""
    from repro.core.assignment import Status

    def say(msg: str) -> None:
        if verbose:
            print(f"[fleet_proc] {msg}", flush=True)

    pinned = tuple(json_clients) or _env_json_clients()
    hb, evict = (0.25, 1.5) if churn else (None, None)
    fleet = spawn_tcp_fleet(n_clients, shards=shards,
                            heartbeat_interval_s=hb,
                            eviction_timeout_s=evict,
                            json_clients=pinned)
    say(f"{n_clients} client processes registered"
        + (f" across {shards} shard processes" if shards > 1 else "")
        + (f"; {', '.join(pinned)} pinned to JSON framing" if pinned else ""))
    try:
        fe = fleet.frontend("ci")
        v1 = fe.deploy_code("smoke_mean", _V1)
        _, done = v1.result(timeout=120.0)
        assert done.status == Status.DONE, f"deploy failed: {done.detail}"
        assert f"{n_clients}/{n_clients}" in done.detail, done.detail
        say(f"deployed v1 ({v1.md5[:8]}) to {n_clients} processes")

        handle = fe.submit_analytics("smoke_mean", iterations=iterations,
                                     params={"n_values": 16})
        results, done = handle.result(timeout=120.0)
        assert done.status == Status.DONE, f"analytics failed: {done.detail}"
        assert len(results) == iterations
        assert all(r.winning_md5 == v1.md5 for r in results)
        say(f"{iterations} iterations committed on v1")

        survivors = n_clients
        if churn:
            victim = fleet.procs[0]
            victim.terminate()
            victim.join(timeout=10.0)
            say("killed client c000 mid-run; waiting for eviction")
            deadline = time.time() + 60.0
            while fleet.server.n_clients > n_clients - 1:
                if time.time() > deadline:
                    raise AssertionError(
                        f"eviction did not happen: still "
                        f"{fleet.server.n_clients} clients registered")
                time.sleep(0.05)
            survivors = n_clients - 1
            say(f"c000 evicted; {survivors} clients remain")

        v2 = fe.deploy_code("smoke_mean", _V2)
        _, done = v2.result(timeout=120.0)
        assert done.status == Status.DONE, f"redeploy failed: {done.detail}"
        assert f"{survivors}/{survivors}" in done.detail, done.detail
        say(f"redeployed v2 to {survivors} survivors")
        rb = v2.rollback()
        _, done = rb.result(timeout=120.0)
        assert done.status == Status.DONE, f"rollback failed: {done.detail}"
        assert rb.md5 == v1.md5

        results, done = fe.submit_analytics(
            "smoke_mean", iterations=1,
            params={"n_values": 16}).result(timeout=120.0)
        assert done.status == Status.DONE
        assert results[0].winning_md5 == v1.md5, \
            "post-rollback iteration did not run v1"
        assert results[0].n_accepted == survivors
        if pinned and fleet.telemetry and not churn:
            # the whole round must have been genuinely mixed-encoding:
            # somebody un-pinned spoke binary, and the pinned peers'
            # frame counters show JSON only (negotiation never escalated
            # a conversation with them past the mandatory fallback)
            metrics = fleet.metrics(timeout=30.0)
            binary_tx = {n for n, t in metrics.items()
                         if any(k.startswith("frames_out.binary")
                                for k in t)}
            assert binary_tx - set(pinned), \
                "no node sent binary frames; the fleet was not mixed"
            for cid in pinned:
                tbl = metrics.get(cid, {})
                leaked = [k for k in tbl
                          if k.startswith(("frames_in.binary",
                                           "frames_out.binary"))]
                assert not leaked, \
                    f"JSON-pinned {cid} saw binary frames: {leaked}"
            say(f"mixed encoding verified: {sorted(binary_tx)} spoke "
                f"binary, {', '.join(pinned)} stayed JSON end to end")
        say("redeploy + rollback verified across processes: PASS")
        return 0
    finally:
        fleet.shutdown()


def run_telemetry_smoke(n_clients: int = 4, shards: int = 2,
                        iterations: int = 2, trace_dump: bool = True,
                        metrics_dump: bool = True,
                        verbose: bool = True) -> int:
    """The observability acceptance scenario over real processes: one
    deploy + analytics round over TCP, then pull telemetry from every
    node over the wire and require (a) a non-empty assembled deploy
    trace and (b) a metrics dump in which every wire tag seen leaving a
    node was also seen arriving somewhere. Returns 0 on success (the CI
    ``telemetry-smoke`` contract)."""
    import json as _json

    from repro.core.assignment import Status

    def say(msg: str) -> None:
        if verbose:
            print(f"[fleet_proc] {msg}", flush=True)

    fleet = spawn_tcp_fleet(n_clients, shards=shards)
    say(f"{n_clients} client processes"
        + (f" across {shards} shard processes" if shards > 1 else "")
        + ", telemetry on")
    try:
        fe = fleet.frontend("ci")
        v1 = fe.deploy_code("telemetry_mean", _V1)
        _, done = v1.result(timeout=120.0)
        assert done.status == Status.DONE, f"deploy failed: {done.detail}"

        handle = fe.submit_analytics("telemetry_mean",
                                     iterations=iterations,
                                     params={"n_values": 16})
        results, done = handle.result(timeout=120.0)
        assert done.status == Status.DONE, f"analytics failed: {done.detail}"
        assert len(results) == iterations

        if trace_dump:
            tree = v1.trace(timeout=30.0)
            assert tree.spans, "assembled deploy trace is empty"
            assert tree.is_connected, \
                f"deploy trace is not a connected tree: {tree.to_dict()}"
            segments = tree.segments()
            say(f"deploy trace: {len(tree.spans)} spans, "
                f"{tree.duration_us / 1e3:.2f} ms, connected")
            print(tree.render(), flush=True)
            print(_json.dumps({"trace_segments": segments}, sort_keys=True),
                  flush=True)

        if metrics_dump:
            metrics = fleet.metrics(timeout=30.0)
            assert metrics, "metrics pull returned no nodes"
            tags_out = {k.split(".", 1)[1] for t in metrics.values()
                        for k in t if k.startswith("msgs_out.")}
            tags_in = {k.split(".", 1)[1] for t in metrics.values()
                       for k in t if k.startswith("msgs_in.")}
            assert tags_out, "no msgs_out counters in the metrics dump"
            # every tag that left a node arrived somewhere (no faults are
            # injected here; snapshots still in flight during the pull are
            # the one tag allowed to be asymmetric)
            missing = tags_out - tags_in - {"telemetry_snapshot"}
            assert not missing, \
                f"tags sent but never received anywhere: {sorted(missing)}"
            for tag in ("submit_assignment", "new_task", "task_done",
                        "register_client", "telemetry_pull"):
                assert tag in tags_out, f"expected wire tag {tag!r} missing"
            say(f"metrics dump: {len(metrics)} nodes, "
                f"{len(tags_out)} wire tags")
            print(_json.dumps({"fleet_metrics": metrics}, sort_keys=True),
                  flush=True)

        say("telemetry plane verified across processes: PASS")
        return 0
    finally:
        fleet.shutdown()


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Spawn a multi-process TCP fleet and run one "
                    "deploy -> iterate -> redeploy -> rollback round; "
                    "--shards puts a router in front of k CloudNode shard "
                    "processes, --churn kills a client mid-run, "
                    "--shard-churn kills a whole shard process "
                    "mid-iteration and requires clean recovery.")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--churn", action="store_true")
    ap.add_argument("--shard-churn", action="store_true")
    ap.add_argument("--rollout", action="store_true",
                    help="run the staged-rollout scenario: an unhealthy "
                         "canary auto-rolls-back, then a healthy canary "
                         "promotes fleet-wide")
    ap.add_argument("--fed-ab", action="store_true",
                    help="run the federated A/B scenario: a sharded TCP "
                         "fleet drives a FedAvg session with arm B's "
                         "optimizer rule hot-swapped mid-session on a "
                         "50%% cohort")
    ap.add_argument("--trace-dump", action="store_true",
                    help="deploy over TCP, then assemble and print the "
                         "deploy trace pulled from every node")
    ap.add_argument("--metrics-dump", action="store_true",
                    help="print the fleet-wide per-node metrics tables "
                         "after one deploy + analytics round")
    ap.add_argument("--pin-json", action="append", default=[],
                    metavar="CLIENT_ID",
                    help="pin a client to legacy JSON framing (repeatable; "
                         "also settable via REPRO_WIRE_JSON_CLIENTS) and "
                         "verify the round ran mixed-encoding")
    args = ap.parse_args(argv)
    if args.shard_churn:
        return run_shard_failover_smoke(args.clients, shards=args.shards)
    if args.rollout:
        return run_rollout_smoke(max(args.clients, 4), shards=args.shards)
    if args.fed_ab:
        return run_fed_ab_smoke(max(args.clients, 8),
                                shards=max(args.shards, 2))
    if args.trace_dump or args.metrics_dump:
        return run_telemetry_smoke(
            max(args.clients, 4), shards=args.shards,
            iterations=args.iterations,
            trace_dump=args.trace_dump, metrics_dump=args.metrics_dump)
    return run_smoke(args.clients, args.iterations, shards=args.shards,
                     churn=args.churn, json_clients=args.pin_json)


if __name__ == "__main__":
    sys.exit(main())
