"""Multi-host control plane + host-sharded data feeding.

The reference's control plane is the Spark driver↔executor bootstrap
(Akka/netty RPC under YARN — SURVEY.md §2.7); the TPU-native equivalent is
`jax.distributed.initialize`: one coordinator, N host processes, global
device view over ICI/DCN. This module wraps it with env-driven
configuration so `pio-tpu train` works unchanged from single-host dev to a
multi-host pod slice:

    PIO_COORDINATOR_ADDRESS  host:port of process 0 (absent → single host)
    PIO_NUM_PROCESSES        total host processes
    PIO_PROCESS_ID           this process's rank
    PIO_MESH_SHAPE           e.g. "data=16,model=4" (global mesh)

Storage I/O becomes host-side loading (SURVEY.md §2.7 'Storage I/O'): each
host reads its row range from the event store and
`make_global_array` assembles the sharded global array
(`jax.make_array_from_process_local_data` under the hood) — the HBase
TableInputFormat-scan→RDD analogue.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)


def parse_mesh_shape(spec: str) -> dict[str, int]:
    """'data=16,model=4' → {"data": 16, "model": 4} (axis order kept)."""
    out: dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad PIO_MESH_SHAPE segment {part!r}")
        k, v = part.split("=", 1)
        out[k.strip()] = int(v)
    if not out:
        raise ValueError(f"empty mesh shape spec {spec!r}")
    return out


def persist_rank() -> int:
    """The rank that persists models/instances and writes checkpoints
    (`PIO_PERSIST_RANK`, default 0). Decouples the PERSISTER from the
    COORDINATOR: jax.distributed pins the coordination service to
    process 0, but the host with fast storage access need not be the
    coordinator host — e.g. rank 0 on a control node, models written by
    the rank colocated with the database. Every rank still trains (SPMD)
    and joins the pre-persist host-gather collectives; only this rank
    writes. Single-process runs ignore the variable entirely (a stale
    multi-host env file must not break a local train); multi-process
    worlds validate it loudly — at workflow entry, before any epoch."""
    import jax

    n = jax.process_count()
    if n == 1:
        return 0
    r = int(os.environ.get("PIO_PERSIST_RANK", "0"))
    if not 0 <= r < n:
        raise ValueError(
            f"PIO_PERSIST_RANK={r} out of range for a {n}-process world")
    return r


def initialize_from_env() -> bool:
    """Bring up `jax.distributed` when the PIO_* env says this is a
    multi-host run; no-op (False) otherwise. Idempotent."""
    import jax

    addr = os.environ.get("PIO_COORDINATOR_ADDRESS")
    if not addr:
        return False
    num = int(os.environ["PIO_NUM_PROCESSES"])
    pid = int(os.environ["PIO_PROCESS_ID"])
    kwargs = {}
    timeout_s = os.environ.get("PIO_COORDINATOR_TIMEOUT_S")
    if timeout_s:
        # bounded failure detection at bootstrap (SURVEY.md §5): a rank
        # that never shows up should fail the job in timeout_s, not hang
        # the surviving ranks on jax's (much longer) default
        kwargs["initialization_timeout"] = int(timeout_s)
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=num, process_id=pid,
        **kwargs
    )
    log.info("jax.distributed up: process %d/%d, %d global devices",
             jax.process_index(), jax.process_count(), jax.device_count())
    return True


def global_mesh(mesh_shape: Optional[dict[str, int]] = None):
    """Build the global (all-hosts) mesh; shape from PIO_MESH_SHAPE or all
    devices on the data axis. THE mesh-shape resolution — WorkflowContext
    delegates here so the env contract lives in one place."""
    from predictionio_tpu.parallel.mesh import make_mesh

    if mesh_shape is None:
        spec = os.environ.get("PIO_MESH_SHAPE")
        if spec:
            mesh_shape = parse_mesh_shape(spec)
    import jax

    return make_mesh(mesh_shape, devices=jax.devices())


def process_row_range(n_rows: int) -> tuple[int, int]:
    """[start, end) of the rows THIS host should load — contiguous
    process-striped split, the per-executor scan-range analogue."""
    import jax

    p, n = jax.process_index(), jax.process_count()
    per = -(-n_rows // n)
    return min(p * per, n_rows), min((p + 1) * per, n_rows)


def make_global_array(mesh, local_rows: np.ndarray, axis_name: str = "data"):
    """Assemble a globally row-sharded array from this host's row block.

    Single-process: a plain `device_put` with the row sharding (the fast
    path every unit test takes). Multi-process: delegates to
    `jax.make_array_from_process_local_data`, which wires each host's
    block into the global sharded array without gathering anywhere.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [None] * local_rows.ndim
    spec[0] = axis_name
    sharding = NamedSharding(mesh, P(*spec))
    if jax.process_count() == 1:
        return jax.device_put(local_rows, sharding)
    return jax.make_array_from_process_local_data(sharding, local_rows)
