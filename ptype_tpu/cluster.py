"""Cluster membership: join, members, close, new_client.

Capability parity with the reference's L2 (cluster/cluster.go:20-103):
``join(cfg)`` wires up the coordination backend, registry, and store,
self-registers this node, and returns a :class:`Cluster`. Where the
reference started an embedded raft member in every process
(cluster.go:161-196), the TPU-native model is seed-hosts-coordination:
the process whose platform config says ``is_coordinator: true`` serves
:class:`CoordServer`; everyone (including the seed) speaks the same
:class:`CoordBackend` interface. ``local:<name>`` coordinator addresses
select the in-process backend — the embedded-etcd-style test tier.

TPU wiring: when the platform config declares mesh axes, join discovers
this process's JAX devices and publishes their ordinals on the member
record and every service registration, making the registry the pod's
mesh map (north star, BASELINE.json).
"""

from __future__ import annotations

import socket
import threading

from ptype_tpu import logs
from ptype_tpu.config import Config
from ptype_tpu.coord.api import CoordBackend, connect
from ptype_tpu.coord.core import Member
from ptype_tpu.coord.local import local_coord
from ptype_tpu.coord.service import CoordServer
from ptype_tpu.errors import ClusterError, CoordinationError
from ptype_tpu.registry import CoordRegistry, Registration, Registry
from ptype_tpu.rpc import Client, ConnConfig
from ptype_tpu.store import KVStore

log = logs.get_logger("cluster")

# Coordination servers owned by this process, keyed by listen address —
# lets several in-process joins share one server (test topology parity
# with the reference's in-process multi-member suites, cluster_test.go).
_servers: dict[str, CoordServer] = {}
_servers_lock = threading.Lock()


def get_ip() -> str:
    """First non-loopback IPv4 of this host (ref: cluster.go:198-213)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            # connect() on UDP sends no packets; it just resolves routing.
            s.connect(("10.255.255.255", 1))
            ip = s.getsockname()[0]
            if not ip.startswith("127."):
                return ip
    except OSError:
        pass
    try:
        for info in socket.getaddrinfo(socket.gethostname(), None,
                                       socket.AF_INET):
            ip = info[4][0]
            if not ip.startswith("127."):
                return ip
    except OSError:
        pass
    return "127.0.0.1"


def _local_device_ordinals() -> tuple[int, ...]:
    """Global ids of this process's JAX devices. Only called for a
    platform config that declares ``mesh_axes``: such a process is a
    mesh member, and one whose backend does not come up must fail its
    join rather than register with no devices."""
    import jax

    try:
        return tuple(d.id for d in jax.local_devices())
    except RuntimeError as e:
        raise ClusterError(
            "join: the platform config declares mesh_axes but the JAX "
            f"backend did not come up: {e}") from e


class Cluster:
    """A joined cluster member (ref: cluster.go:20-26)."""

    def __init__(self, cfg: Config, coord: CoordBackend,
                 registry: Registry, store: KVStore,
                 member: Member, registration: Registration | None,
                 owned_server: CoordServer | None,
                 advertise_host: str,
                 device_ordinals: tuple[int, ...]):
        self.cfg = cfg
        self.coord = coord
        self.registry = registry
        self.store = store
        self.member = member
        self.registration = registration
        self.advertise_host = advertise_host
        self.device_ordinals = device_ordinals
        self._owned_server = owned_server
        self._closed = False

    def member_list(self) -> list[Member]:
        """Ref: cluster.go:86-93."""
        return self.coord.member_list()

    def new_client(self, service_name: str,
                   cfg: ConnConfig | None = None) -> Client:
        """Load-balanced client for a service (ref: cluster.go:101-103)."""
        return Client(self.advertise_host, service_name, self.registry, cfg)

    def mesh(self, axis_names: tuple[str, ...] | None = None):
        """Device mesh from the platform config's axes — the registry-as-
        mesh-map lowering. See ptype_tpu.parallel.mesh. A config that
        gives no axes gets every visible device on the ``data`` axis;
        one whose axes cover fewer devices than the host shows says
        which it left out."""
        import jax

        from ptype_tpu.parallel.mesh import build_mesh
        from ptype_tpu.parallel.topology import DATA_AXIS

        axes = (self.cfg.platform.mesh_axes
                or {DATA_AXIS: jax.device_count()})
        mesh = build_mesh(axes, axis_names)
        used = {d.id for d in mesh.devices.flat}
        idle = [d.id for d in jax.devices() if d.id not in used]
        if idle:
            log.warning("mesh leaves devices out",
                        kv={"axes": dict(mesh.shape), "unused": idle})
        return mesh

    def close(self) -> None:
        """Leave the cluster (ref: cluster.go:95-99 — plus prompt
        deregistration, which the reference skipped; SURVEY.md §2)."""
        if self._closed:
            return
        self._closed = True
        if self.registration is not None:
            self.registration.close(revoke=True)
        try:
            self.coord.member_remove(self.member.id)
        except CoordinationError:
            pass
        self.coord.close()
        if self._owned_server is not None:
            with _servers_lock:
                addr = self._owned_server.address
                if _servers.get(addr) is self._owned_server:
                    del _servers[addr]
            self._owned_server.close()
        log.info("left cluster", kv={"node": self.cfg.node_name})


def _init_jax_distributed(platform) -> None:
    """Initialize the multi-controller JAX runtime as part of join —
    Join does *everything* in the reference (cluster.go:28-84); the TPU
    translation is "Join ≈ jax.distributed.initialize + mesh
    construction" (SURVEY §3.1). No-op when already initialized (e.g.
    the launcher did it) so join stays idempotent."""
    import jax

    if jax.distributed.is_initialized():
        log.debug("jax.distributed already initialized")
        return
    addr = platform.jax_coordinator_address
    if not addr:
        host, _, port = platform.coordinator_address.rpartition(":")
        addr = f"{host}:{int(port) + 1}"
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=platform.num_processes,
        process_id=platform.process_id,
    )
    log.info("jax distributed initialized",
             kv={"addr": addr, "process": platform.process_id,
                 "n": platform.num_processes})


def join(cfg: Config) -> Cluster:
    """Join (or seed) the cluster described by ``cfg`` (ref: cluster.go:28-84)."""
    logs.set_debug(cfg.debug)
    platform = cfg.platform

    owned_server: CoordServer | None = None
    coord_addr = platform.coordinator_address

    # Control plane FIRST, JAX runtime second: the seed must be
    # dialable before it blocks in jax.distributed.initialize, and
    # joiners must keep retrying within dial_timeout — simultaneous
    # process launch otherwise races join into "connection refused"
    # (observed: a joiner dialing in the ms between the seed's jax init
    # and its server bind).
    if coord_addr.startswith("local:"):
        coord: CoordBackend = local_coord(coord_addr.split(":", 1)[1])
    elif platform.is_coordinator:
        with _servers_lock:
            server = _servers.get(coord_addr)
            if server is None:
                import os as _os

                # Durable control plane (ref: etcd data-dir): the seed
                # WALs its CoordState so registry/store survive restart.
                server = CoordServer(
                    coord_addr,
                    data_dir=(_os.path.join(platform.data_dir, "coord")
                              if platform.data_dir else None),
                    fsync=platform.wal_fsync,
                    witness_addr=platform.witness_address or None,
                    witness_ttl=platform.witness_ttl,
                )
                _servers[server.address] = server
                owned_server = server
        # The seed talks to its own state in-process — no self-dial.
        from ptype_tpu.coord.local import LocalCoord

        coord = LocalCoord(server.state)
        log.debug("seeded coordination service", kv={"addr": server.address})
    else:
        # Join an existing cluster through any known client URL
        # (ref: joinExistingCluster, cluster.go:105-118), retrying the
        # endpoint list until dial_timeout: cluster launchers start the
        # seed and joiners at the same instant.
        import time as _time

        from ptype_tpu import retry as _retry

        endpoints = cfg.initial_cluster_client_urls or [coord_addr]
        deadline = _time.monotonic() + platform.dial_timeout
        last: Exception | None = None
        coord = None  # type: ignore[assignment]
        join_bo = _retry.Backoff(base=0.2, cap=1.0)
        while coord is None:
            per_dial = max(0.5, deadline - _time.monotonic())
            try:
                # The FULL endpoint list goes to the client: on a later
                # connection loss it fails over to any standby
                # (coord.standby) in the list, not just the seed —
                # and discovery extends the list with promote-eligible
                # standbys attached after this process joined.
                coord = connect(endpoints, dial_timeout=per_dial,
                                discovery_interval=5.0)
            except CoordinationError as e:
                last = e
                if _time.monotonic() >= deadline:
                    raise ClusterError(
                        f"failed to reach coordination service via "
                        f"{endpoints}: {last}"
                    ) from e
                join_bo.sleep()

    if platform.num_processes > 1:
        _init_jax_distributed(platform)

    device_ordinals = (
        _local_device_ordinals() if platform.mesh_axes else ()
    )
    advertise_host = get_ip()

    member = coord.member_add(
        cfg.node_name,
        f"{advertise_host}:{cfg.port}",
        metadata={
            "service": cfg.service_name,
            "process_id": platform.process_id,
            "device_ordinals": list(device_ordinals),
        },
    )

    registry = CoordRegistry(coord, lease_ttl=platform.lease_ttl)
    store = KVStore(coord)

    registration = None
    if cfg.service_name:
        # Self-register (ref: cluster.go:69-73). Registration is always on:
        # a node that serves nothing is still discoverable for liveness.
        registration = registry.register(
            cfg.service_name, cfg.node_name, advertise_host, cfg.port,
            process_id=platform.process_id,
            device_ordinals=device_ordinals,
        )

    log.info("joined cluster",
             kv={"service": cfg.service_name, "node": cfg.node_name,
                 "member_id": member.id, "devices": list(device_ordinals)})
    return Cluster(cfg, coord, registry, store, member, registration,
                   owned_server, advertise_host, device_ordinals)
