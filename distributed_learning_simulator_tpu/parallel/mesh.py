"""Device mesh + sharding helpers for the client axis.

The reference's "distributed communication backend" is an in-process blocking
queue with broadcast (reference servers/server.py:10-17, fed_server.py:19-24,
88-91) plus a dormant multi-process path (simulator.py:56 hard-codes it off).
The TPU-native equivalent: simulated clients are a *mesh axis*. Client-stacked
arrays get ``PartitionSpec("clients", ...)``; every reduction over that axis
(FedAvg weighted mean, SignSGD vote) is lowered by XLA to an ICI collective,
and the broadcast back is just the replicated output sharding. Multi-host
(DCN) uses the same program after ``jax.distributed.initialize`` — the mesh
spans all processes' devices.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

CLIENT_AXIS = "clients"


def make_mesh(num_devices: int | None = None, axis_name: str = CLIENT_AXIS) -> Mesh:
    """1-D mesh over local (or all, under multi-host) devices.

    ``num_devices=None`` uses every visible device. The client axis is sharded
    over this mesh; n_clients must be a multiple of the mesh size.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} mesh devices but only "
                f"{len(devices)} visible on platform "
                f"{devices[0].platform!r}"
            )
        devices = devices[:num_devices]
    return Mesh(np.array(devices), (axis_name,))


def client_sharding(mesh: Mesh, ndim_tail: int = 0) -> NamedSharding:
    """Sharding for an array whose LEADING axis is the client axis."""
    spec = PartitionSpec(mesh.axis_names[0], *([None] * ndim_tail))
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (global params, test set)."""
    return NamedSharding(mesh, PartitionSpec())


def shard_client_data(tree, mesh: Mesh):
    """device_put every leaf with its leading (client) axis over the mesh."""
    spec = PartitionSpec(mesh.axis_names[0])
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def replicate(tree, mesh: Mesh):
    """device_put every leaf fully replicated over the mesh."""
    sharding = replicated_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)
