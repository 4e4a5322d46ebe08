"""Device mesh construction + sharding helpers.

The parallelism strategies the reference gets from Spark (SURVEY.md §2.6)
map onto two mesh axes:

- ``data``  — RDD-partition data parallelism → batch/interaction sharding
- ``model`` — MLlib ALS block partitioning  → factor/feature sharding

Arrays are placed with `NamedSharding`s; XLA inserts the ICI/DCN
collectives (psum / all_gather / reduce_scatter) that replace Spark
shuffle. Multi-host entry is `init_distributed` (the reference's
driver↔executor control plane analogue, SURVEY.md §2.7).
"""

from __future__ import annotations

import logging
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"


def describe_devices(devices: Sequence[jax.Device]) -> str:
    """The devices a computation was placed on, as JAX reports them.
    Every verb that touches the chip logs this line where it places its
    work, so a run that landed on the CPU cannot pass for a device run."""
    d = devices[0]
    return (f"platform={d.platform} device_kind={d.device_kind!r} "
            f"devices={len(devices)}")


def make_mesh(
    mesh_shape: Optional[dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, model) mesh.

    Default: all local devices on the ``data`` axis, ``model`` axis of 1 —
    the right shape for every reference workload up to config 4; config 5
    (rank-128 ALS on v5e-64) wants e.g. ``{"data": 16, "model": 4}``.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if mesh_shape is None:
        mesh_shape = {DATA_AXIS: n, MODEL_AXIS: 1}
    axis_names = tuple(mesh_shape.keys())
    sizes = tuple(mesh_shape.values())
    want = math.prod(sizes)
    if want > n:
        raise ValueError(f"mesh_shape {mesh_shape} needs {want} devices, have {n}")
    dev_array = np.asarray(devices[:want]).reshape(sizes)
    log.info("mesh %s on %s", dict(zip(axis_names, sizes)),
             describe_devices(devices[:want]))
    return Mesh(dev_array, axis_names)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """`named_sharding(mesh, "data", None)` → rows sharded over `data`."""
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def host_shard(mesh: Mesh, array, axis_name: str = DATA_AXIS):
    """Place a host array onto the mesh, sharded along its leading dim.

    The leading dim must divide by the axis size (callers pad — ALS blocks
    are already padded to tile boundaries). This is the rebuild's
    HBase-scan→RDD ingest analogue: host loader → device shards
    (SURVEY.md §2.7 'Storage I/O').
    """
    import jax.numpy as jnp

    spec = [None] * array.ndim
    spec[0] = axis_name
    return jax.device_put(jnp.asarray(array), NamedSharding(mesh, P(*spec)))


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host control-plane init (`jax.distributed.initialize`).

    Replaces the reference's Spark driver↔executor RPC bootstrapping
    (SURVEY.md §2.7). No-op when args are absent and env vars aren't set —
    single-host runs never need it.
    """
    import os

    if coordinator_address is None and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        log.debug("init_distributed: single-host run, skipping")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    log.info(
        "jax.distributed initialized: process %d/%d",
        jax.process_index(),
        jax.process_count(),
    )
