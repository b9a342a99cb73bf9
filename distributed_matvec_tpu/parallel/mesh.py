"""Device-mesh helpers for the hash-sharded engine.

The reference runs one Chapel locale per node over GASNet
(``env/setup-env.sh``); devices here are TPU chips in a 1-D
``jax.sharding.Mesh`` whose single axis shards the Hilbert dimension.
Multi-host extension: initialise ``jax.distributed`` first, then build the
mesh over ``jax.devices()`` — the collectives ride ICI within a slice and
DCN across hosts.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["SHARD_AXIS", "make_mesh", "shard_spec", "init_distributed"]

SHARD_AXIS = "shards"


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bring-up — the DCN analog of the reference's GASNet
    substrate env scripts (``env/chpl-env-*.sh``: smp/mpi/ibv/ofi).

    Call once per host *before* any device use; afterwards ``jax.devices()``
    spans the whole slice, ``make_mesh()`` covers it, and the engine's
    collectives ride ICI within a slice and DCN across hosts.  Arguments
    default to cluster auto-detection (Slurm/GKE — the role the reference's
    Slurm launcher plays, env/chpl-env-snellius.sh).
    """
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over ``n_devices`` (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} present"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_spec(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Sharding that splits axis 0 over the mesh, replicating the rest."""
    return NamedSharding(mesh, PartitionSpec(SHARD_AXIS, *([None] * (ndim - 1))))
