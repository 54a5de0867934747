"""Device-mesh construction and canonical shardings.

The reference's parallel topology is hand-built: one worker thread per GPU,
NCCL rings intra-node, boxps SyncDense/MPI inter-node (SURVEY.md §2.3). Here
the topology is a `jax.sharding.Mesh` with up to two axes:

- ``"node"`` — the DCN axis (hosts); present only multi-host.
- ``"dp"``   — the ICI axis (chips per host); data parallelism AND the
  embedding-table shard axis ride this (the reference likewise shards the
  embedding across the same GPUs that run data-parallel training).

A 2D (node, dp) psum gives the reference's hierarchical
reduce-scatter → inter-node sync → all-gather (boxps_worker.cc:497-511) for
free — XLA emits exactly that decomposition for multi-axis collectives.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names
NODE_AXIS = "node"
DP_AXIS = "dp"


def make_mesh(num_devices: int | None = None,
              num_nodes: int = 1,
              devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the (node, dp) mesh.

    Single-host: a 1D ("dp",) mesh over local devices. Multi-host (or
    simulated multi-node): 2D ("node", "dp").
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if num_devices is not None:
        devs = devs[:num_devices]
    n = len(devs)
    if num_nodes > 1:
        if n % num_nodes:
            raise ValueError(f"{n} devices not divisible by {num_nodes} nodes")
        arr = np.array(devs).reshape(num_nodes, n // num_nodes)
        return Mesh(arr, (NODE_AXIS, DP_AXIS))
    return Mesh(np.array(devs), (DP_AXIS,))


def shard_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes, in order — the embedding table shards over the product."""
    return tuple(mesh.axis_names)


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Embedding working-set table: rows contiguously sharded over all axes."""
    return NamedSharding(mesh, P(shard_axes(mesh)))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Per-example batch arrays: leading dim sharded over all axes (pure DP)."""
    return NamedSharding(mesh, P(shard_axes(mesh)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def num_shards(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))
