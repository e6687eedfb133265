"""ptype_tpu — a TPU-native actor-cluster framework.

Capability surface of edegens/ptype (see /root/reference and SURVEY.md),
re-designed TPU-first:

- ``join(config)``       -> Cluster membership over a coordination service
                            (the JAX-style single-coordinator model rather
                            than embedded raft; ref: cluster/cluster.go:28-84).
- ``Cluster.registry``   -> lease-backed service discovery with watch streams
                            (ref: cluster/registry.go:17-21), where nodes carry
                            TPU device ordinals so the cluster topology *is*
                            the pod mesh.
- ``Cluster.store``      -> replicated KV metadata tier (ref: cluster/store.go)
                            plus a tensor tier (``ptype_tpu.parallel``) whose
                            push/pull lowers to XLA collectives over ICI.
- ``Cluster.new_client`` -> load-balanced sync/async actor RPC with bounded
                            retries and a watch-driven connection balancer
                            (ref: cluster/rpc.go).

The compute path is JAX/XLA/pjit/shard_map/Pallas; the host-side runtime is
pure-Python threads + sockets (the reference's runtime was pure Go + TCP).
"""

from ptype_tpu.config import (
    Config,
    ConfigError,
    PlatformConfig,
    config_from_env,
    config_from_file,
)
from ptype_tpu.errors import (
    ClusterError,
    ErrNoClientAvailable,
    ErrNoKey,
    NoClientAvailableError,
    NoKeyError,
    RPCError,
)
from ptype_tpu.registry import Node, Registry
from ptype_tpu.store import KVStore
from ptype_tpu.rpc import Client, ConnConfig, DEFAULT_CONN_CONFIG
from ptype_tpu.actor import ActorServer
from ptype_tpu.cluster import Cluster, join

__version__ = "0.1.0"

__all__ = [
    "ActorServer",
    "Client",
    "Cluster",
    "ClusterError",
    "Config",
    "ConfigError",
    "ConnConfig",
    "DEFAULT_CONN_CONFIG",
    "ErrNoClientAvailable",
    "ErrNoKey",
    "KVStore",
    "Node",
    "NoClientAvailableError",
    "NoKeyError",
    "PlatformConfig",
    "RPCError",
    "Registry",
    "join",
    "config_from_env",
    "config_from_file",
]
