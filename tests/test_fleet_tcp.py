"""The acceptance scenario on both fabric topologies: deploy -> 3
iterations -> mid-assignment redeploy -> rollback, with every message
crossing the wire codec — in-proc loopback and real spawned-process TCP."""
import os
import time

import pytest

from repro.core import Status
from repro.core.fleet import Fleet

V1 = """
import jax.numpy as jnp
def run(xs):
    return jnp.mean(xs) * 2.0
"""

V2 = """
import jax.numpy as jnp
def run(xs):
    return jnp.mean(xs) * 4.0
"""


def _full_scenario(fleet, n_clients: int, timeout: float) -> None:
    fe = fleet.frontend("u1")

    # deploy v1 to every client over the fabric
    v1 = fe.deploy_code("t_mean", V1)
    _, done = v1.result(timeout=timeout)
    assert done.status == Status.DONE
    assert f"{n_clients}/{n_clients}" in done.detail

    # 3 committed iterations, all on v1
    handle = fe.submit_analytics("t_mean", iterations=3,
                                 params={"n_values": 16})
    results, done = handle.result(timeout=timeout)
    assert done.status == Status.DONE
    assert len(results) == 3
    assert all(r.winning_md5 == v1.md5 for r in results)
    assert all(r.n_accepted == n_clients for r in results)

    # mid-assignment redeploy: a long assignment picks up v2 mid-flight
    long = fe.submit_analytics("t_mean", iterations=8,
                               params={"n_values": 16})
    stream = long.events()
    first = next(stream)
    assert first.winning_md5 == v1.md5
    v2 = fe.deploy_code("t_mean", V2)
    _, done = v2.result(timeout=timeout)
    assert done.status == Status.DONE

    # rollback before the long assignment finishes: back on v1
    rb = v2.rollback()
    _, done = rb.result(timeout=timeout)
    assert done.status == Status.DONE
    assert rb.md5 == v1.md5

    results, done = long.result(timeout=timeout)
    assert done.status == Status.DONE
    seen = {r.winning_md5 for r in results}
    assert v1.md5 in seen                      # started on v1
    assert seen <= {v1.md5, v2.md5}            # only deployed versions win
    # during a swap window a round may mix versions; dissenting clients
    # count as drops, never as silently merged results — every client
    # is accounted for either way
    assert all(r.n_accepted + r.n_dropped + r.n_stragglers == n_clients
               for r in results)

    # rollback took effect fleet-wide: deploys never block in-flight
    # rounds, so the long assignment's final round may legitimately
    # still commit v2 — but a round dispatched strictly after every
    # client acked the rollback install must commit v1
    post = fe.submit_analytics("t_mean", iterations=1,
                               params={"n_values": 16})
    results, done = post.result(timeout=timeout)
    assert done.status == Status.DONE
    assert all(r.winning_md5 == v1.md5 for r in results)


def test_scenario_inproc_topology():
    fleet = Fleet.create(4, seed=11)
    assert fleet.topology == "inproc"
    try:
        _full_scenario(fleet, n_clients=4, timeout=30.0)
    finally:
        fleet.shutdown()


@pytest.mark.slow
def test_scenario_tcp_spawned_processes():
    """Client nodes are real child processes; code, tasks and results
    exist there only after crossing TCP frames."""
    fleet = Fleet.create(3, topology="tcp")
    assert fleet.topology == "tcp"
    assert fleet.client_apps == {}             # client state is remote
    assert len(fleet.procs) == 3
    assert all(p.is_alive() for p in fleet.procs)
    try:
        _full_scenario(fleet, n_clients=3, timeout=120.0)
    finally:
        fleet.shutdown()
    deadline = time.time() + 10.0
    while time.time() < deadline and any(p.is_alive() for p in fleet.procs):
        time.sleep(0.05)
    assert not any(p.is_alive() for p in fleet.procs)  # clean child exit


def test_tcp_topology_rejects_unshippable_callables():
    with pytest.raises(ValueError, match="cannot cross a process"):
        Fleet.create(2, topology="tcp", delay_fns={"c000": lambda t: 0.1})


def test_unknown_topology_rejected():
    with pytest.raises(ValueError, match="unknown topology"):
        Fleet.create(2, topology="quantum")


BACKEND = """
import jax
def run(xs):
    return jax.default_backend()
"""


def test_tcp_children_are_placed_on_host_cpu(monkeypatch):
    """Spawned clients run deployed JAX code on the host CPU, whatever
    platform the parent's environment names: the parent may hold the
    chip, and a child must never reach for it."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    fleet = Fleet.create(2, topology="tcp")
    try:
        fe = fleet.frontend("u1")
        _, done = fe.deploy_code("where", BACKEND).result(timeout=120.0)
        assert done.status == Status.DONE
        results, done = fe.submit_analytics(
            "where", iterations=1, params={"n_values": 4}
        ).result(timeout=120.0)
        assert done.status == Status.DONE
        assert results[0].value == ["cpu", "cpu"]
    finally:
        fleet.shutdown()
    assert os.environ["JAX_PLATFORMS"] == "tpu"   # the parent's is kept
