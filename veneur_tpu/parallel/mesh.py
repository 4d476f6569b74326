"""Device mesh construction for the sharded aggregation tier.

The reference scales with hash-sharded workers in one process
(`worker.go:34-50`, P2 in SURVEY.md §2.10) and a consistent-hash proxy tier
across processes (P4).  The TPU-native analog is a 2-D mesh:

  - axis "shard": partitions the metric-key space — each device owns
    K/n_shards rows of every arena (the pjit analog of fnv1a % num_workers
    and of the proxy's hash ring);
  - axis "replica": parallel ingest lanes — each replica holds partial
    sketches for the same keys (e.g. digests forwarded by a subset of local
    instances), reduced at flush time with XLA collectives over ICI
    (all_gather + compress for t-digests, pmax for HLL registers, psum for
    counters) — the map-reduce of flusher.go:516-591 / worker.go:402-459
    as a device collective.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"
REPLICA_AXIS = "replica"


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with the replication check disabled — the flush
    body's collectives are hand-placed and the checker rejects the
    axis-size-1 specialization it cannot see through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int | None = None,
              replicas: int | None = None) -> Mesh:
    """A (shard, replica) mesh over the first n devices.

    replicas defaults to 2 when the device count allows, else 1 — key
    sharding is the primary scaling axis.
    """
    devices = jax.devices()
    n = n_devices if n_devices is not None else len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} devices, have {len(devices)}")
    if replicas is None:
        replicas = 2 if n % 2 == 0 and n >= 2 else 1
    if n % replicas != 0:
        raise ValueError(f"{n} devices not divisible into {replicas} replicas")
    if jax.process_count() > 1:
        per_host = len(jax.local_devices())
        if per_host and per_host % replicas != 0:
            import logging
            logging.getLogger("veneur_tpu.parallel.mesh").warning(
                "mesh_replicas=%d does not divide the per-host device "
                "count %d: replica groups will straddle hosts and the "
                "flush all_gather will ride DCN instead of ICI",
                replicas, per_host)
    shards = n // replicas
    dev_array = np.asarray(devices[:n]).reshape(shards, replicas)
    return Mesh(dev_array, (SHARD_AXIS, REPLICA_AXIS))


def key_sharding(mesh: Mesh) -> NamedSharding:
    """Arrays whose leading axis is the key axis: sharded over 'shard',
    replicated over 'replica'."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replica_key_sharding(mesh: Mesh) -> NamedSharding:
    """Staged partials [R, K, ...]: replica-sharded leading axis, key-sharded
    second axis."""
    return NamedSharding(mesh, P(REPLICA_AXIS, SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, mult: int) -> int:
    return int(math.ceil(n / mult)) * mult
