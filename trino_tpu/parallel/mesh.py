"""Device mesh runtime.

Reference: Trino's distribution machinery — NodePartitioningManager maps
partitions to worker nodes (sql/planner/NodePartitioningManager.java:60) and
stages run as tasks per node (SURVEY.md §2.8). Here the "worker fleet" is a
`jax.sharding.Mesh`; a stage is one jitted SPMD program laid over it with
`shard_map`, and inter-"task" data movement is an XLA collective over ICI
instead of HTTP page shuttling.

Axis naming: a single "workers" axis for row-sharded (DP-style) execution.
Multi-axis meshes (host x chip) layer on when multi-host lands.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..batch import Batch, Column

AXIS = "workers"


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication checker off: stage programs
    are collective-carrying bodies with manually asserted out_specs,
    exactly the case the checker rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pad_to_multiple(batch: Batch, multiple: int) -> Batch:
    """Grow a batch's capacity to the next multiple of `multiple` with
    dead rows (live=False, valid=False) so row-sharding divides evenly.
    Dead padding is invisible to every kernel (the live mask gates all
    semantics), so this is pure layout."""
    cap = batch.capacity
    pad = (-cap) % multiple
    if pad == 0:
        return batch
    cols = tuple(
        Column(data=jnp.pad(c.data, [(0, pad)] + [(0, 0)] *
                            (c.data.ndim - 1)),
               valid=jnp.pad(c.valid, (0, pad)))
        for c in batch.columns)
    return Batch(columns=cols, live=jnp.pad(batch.live, (0, pad)))


def make_mesh(n_devices: Optional[int] = None, axis: str = AXIS) -> Mesh:
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis,))


def make_mesh_2d(n_hosts: int, chips_per_host: int,
                 axes=("hosts", "chips")) -> Mesh:
    """Two-axis mesh for multi-host topologies: the outer axis spans DCN
    (hosts), the inner axis ICI (chips within a host). Shardings laid out
    as P(('hosts','chips')) keep the heavy collectives on the inner axis —
    the scaling-book layout recipe, and the analog of Trino's node-level
    vs task-level parallelism split (SURVEY.md §2.8)."""
    devs = jax.devices()
    n = n_hosts * chips_per_host
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]).reshape(n_hosts, chips_per_host), axes)


def shard_rows(batch: Batch, mesh: Mesh, axis: Optional[str] = None) -> Batch:
    """Place a host-built batch row-sharded across the mesh (the split
    assignment step: SourcePartitionedScheduler.assignSplits:378 analog).
    Multi-axis meshes shard rows over ALL axes (hosts x chips). Capacity
    must divide evenly — batch_from_numpy pads to 1024-multiples, so
    pad_multiple must be a multiple of mesh size * 8."""
    axes = (axis,) if axis is not None else tuple(mesh.axis_names)
    spec = NamedSharding(mesh, P(axes))

    def put(x):
        return jax.device_put(x, spec)

    return jax.tree_util.tree_map(put, batch)


def replicate(batch: Batch, mesh: Mesh) -> Batch:
    """Broadcast a (small) batch to every device — the
    FIXED_BROADCAST_DISTRIBUTION / BroadcastOutputBuffer path
    (execution/buffer/BroadcastOutputBuffer.java:56)."""
    spec = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, spec), batch)
