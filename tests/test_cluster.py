"""Cluster membership tests (mirrors reference cluster_test.go).

The reference ran a genuine 4-member raft cluster in one process
(cluster_test.go:47-167); the analog here is several joins sharing one
in-process coordination state, plus a real TCP seed topology.
"""

import time

import pytest

from ptype_tpu.actor import ActorServer
from ptype_tpu.cluster import get_ip, join
from ptype_tpu.config import Config, PlatformConfig
from ptype_tpu.errors import ClusterError
from ptype_tpu.rpc import ConnConfig


def local_cfg(service, node, port=0, cluster_name="testcluster", **platform_kw):
    platform_kw.setdefault("lease_ttl", 0.5)
    return Config(
        service_name=service,
        node_name=node,
        port=port,
        platform=PlatformConfig(
            name=node,
            coordinator_address=f"local:{cluster_name}",
            **platform_kw,
        ),
    )


def conn_cfg(**kw):
    kw.setdefault("initial_node_timeout", 2.0)
    kw.setdefault("debounce_time", 0.1)
    kw.setdefault("retries", 1)
    return ConnConfig(**kw)


class Calculator:
    def Multiply(self, a, b):
        return a * b


def test_join_and_member_list():
    c1 = join(local_cfg("calc", "n1", 9001))
    c2 = join(local_cfg("calc", "n2", 9002))
    try:
        names = [m.name for m in c1.member_list()]
        assert names == ["n1", "n2"]
        # Registered under its service with its advertised address
        nodes = c1.registry.services()["calc"]
        assert {n.port for n in nodes} == {9001, 9002}
    finally:
        c1.close()
        c2.close()


def test_close_removes_member_and_registration():
    c1 = join(local_cfg("calc", "n1", 9001))
    c2 = join(local_cfg("calc", "n2", 9002))
    try:
        c2.close()
        assert [m.name for m in c1.member_list()] == ["n1"]
        assert {n.port for n in c1.registry.services().get("calc", [])} == {9001}
    finally:
        c1.close()


def test_store_shared_between_members():
    c1 = join(local_cfg("calc", "n1"))
    c2 = join(local_cfg("calc", "n2"))
    try:
        c1.store.put("lr", "3e-4")
        assert c2.store.get_one("lr") == "3e-4"
    finally:
        c1.close()
        c2.close()


def test_end_to_end_calculator_rpc():
    """The reference's calculator flow (server.go + client.go) end to end:
    register handler -> join -> serve; join -> new_client -> call."""
    server = ActorServer(get_ip(), 0)
    server.register(Calculator())
    server.serve()
    c_server = join(local_cfg("calc", "server-node", server.port))
    c_client = join(local_cfg("calc_client", "client-node"))
    try:
        client = c_client.new_client("calc", conn_cfg())
        assert client.call("Calculator.Multiply", 6, 7) == 42
        client.close()
    finally:
        c_server.close()
        c_client.close()
        server.close()


def test_tcp_seed_topology():
    """Seed hosts the coordination service over TCP; a second member joins
    via initial_cluster_client_urls (ref: joinExistingCluster path)."""
    seed_cfg = Config(
        service_name="calc", node_name="seed", port=9001,
        platform=PlatformConfig(
            name="seed", coordinator_address="127.0.0.1:0",
            is_coordinator=True, lease_ttl=0.5,
        ),
    )
    seed = join(seed_cfg)
    coord_addr = seed._owned_server.address
    joiner_cfg = Config(
        service_name="calc", node_name="joiner", port=9002,
        initial_cluster_client_urls=[coord_addr],
        platform=PlatformConfig(
            name="joiner", coordinator_address=coord_addr, lease_ttl=0.5,
        ),
    )
    joiner = join(joiner_cfg)
    try:
        assert [m.name for m in seed.member_list()] == ["seed", "joiner"]
        assert [m.name for m in joiner.member_list()] == ["seed", "joiner"]
        joiner.store.put("k", "v")
        assert seed.store.get_one("k") == "v"
    finally:
        joiner.close()
        seed.close()


def test_join_unreachable_coordinator_fails():
    cfg = Config(
        service_name="s", node_name="n", port=1,
        initial_cluster_client_urls=["127.0.0.1:1"],
        platform=PlatformConfig(
            name="n", coordinator_address="127.0.0.1:1", dial_timeout=0.3,
        ),
    )
    with pytest.raises(ClusterError, match="failed to reach"):
        join(cfg)


def test_dead_member_does_not_block_join():
    """Join works with a dead (lease-expired) member hanging around
    (ref: cluster_test.go:133-165 dead-member join)."""
    c1 = join(local_cfg("calc", "n1", 9001))
    c2 = join(local_cfg("calc", "n2", 9002))
    # Simulate n2 crashing: abandon without revoking
    c2.registration.close(revoke=False)
    time.sleep(1.2)  # > lease_ttl: registration gone
    c3 = join(local_cfg("calc", "n3", 9003))
    try:
        services = c3.registry.services()
        ports = {n.port for n in services["calc"]}
        assert 9002 not in ports
        assert {9001, 9003} <= ports
    finally:
        c1.close()
        c3.close()


def test_mesh_member_whose_backend_is_down_fails_its_join(monkeypatch):
    """A platform config with mesh_axes names a mesh member: if its JAX
    backend does not come up, join raises — it used to register the
    member with no devices and carry on."""
    import jax

    def down():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "local_devices", down)
    with pytest.raises(ClusterError, match="backend did not come up"):
        join(local_cfg("trainer", "t0", mesh_axes={"data": 1}))
    # A control-plane process (no axes) never asks the backend.
    join(local_cfg("gateway", "g0")).close()


def test_mesh_defaults_to_every_device_and_names_what_it_leaves_out(
        caplog):
    """No mesh_axes: every visible device on ``data`` (the documented
    trainer command used to take one chip of four without a word). An
    explicit smaller layout logs the devices it leaves idle."""
    import logging

    import jax

    c = join(local_cfg("trainer", "t0"))
    try:
        mesh = c.mesh()
        assert dict(mesh.shape) == {"data": jax.device_count()}
    finally:
        c.close()
    c = join(local_cfg("trainer", "t1", mesh_axes={"data": 2}))
    lg = logging.getLogger("ptype_tpu.cluster")
    lg.addHandler(caplog.handler)  # the package logger does not propagate
    try:
        with caplog.at_level(logging.WARNING, logger="ptype_tpu.cluster"):
            mesh = c.mesh()
        assert mesh.devices.size == 2
        assert any("leaves devices out" in r.getMessage()
                   for r in caplog.records)
    finally:
        lg.removeHandler(caplog.handler)
        c.close()
