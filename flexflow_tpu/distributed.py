"""Multi-host (multi-controller) SPMD execution.

TPU re-design of the reference's multi-node runtime: where the reference
launches one Legion process per node over GASNet/MPI conduits
(reference CMakeLists.txt:47-49, tests/multinode_helpers/mpi_wrapper1.sh)
and syncs parameters with NCCL, the TPU framework runs one JAX process
per host in multi-controller SPMD: every process executes the same
program over one global `jax.sharding.Mesh` spanning all hosts, XLA
inserts the ICI/DCN collectives, and each host feeds only the batch rows
its own devices hold (`jax.make_array_from_process_local_data`).

Entry points:
  * `initialize(...)` / `initialize_from_config(cfg)` — wire the JAX
    distributed runtime (coordinator rendezvous). On a real TPU pod all
    arguments are auto-detected; on CPU (tests / dryrun) the caller
    passes coordinator/rank and gloo collectives are enabled.
  * `stage_local_batch(local, sharding)` — build the global batch array
    from this process's rows.
  * `local_batch_rows(sharding, global_rows)` — how many of a
    `global_rows` batch this process feeds, and at which offset.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import numpy as np


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> None:
    """Initialize the JAX distributed runtime (idempotent).

    On TPU pods, all arguments are optional (auto-detected from the
    metadata server). On CPU, pass coordinator/num_processes/process_id
    explicitly; cross-process CPU collectives use gloo.
    """
    import jax

    if is_initialized():
        return
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        # must be set before the backend exists; harmless on TPU where
        # the flag is ignored
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = local_device_ids
    jax.distributed.initialize(**kwargs)


def initialize_from_config(cfg) -> None:
    """Driver hook: start the distributed runtime when the run is
    multi-node (--nodes N > 1, or FLEXFLOW_COORDINATOR set).

    Rank/coordinator come from flags when given, else from the
    environment (FLEXFLOW_COORDINATOR / FLEXFLOW_NODE_RANK), else are
    auto-detected (TPU pod metadata)."""
    num_nodes = getattr(cfg, "num_nodes", 1)
    if num_nodes <= 1:
        num_nodes = int(os.environ.get("FLEXFLOW_NUM_NODES", "1"))
    coord = (getattr(cfg, "coordinator_address", None)
             or os.environ.get("FLEXFLOW_COORDINATOR") or None)
    if num_nodes <= 1 and coord is None:
        return
    if coord is not None and num_nodes <= 1:
        raise ValueError(
            "multi-node launch: a coordinator address was given but the "
            "process count is unknown — pass --nodes N or set "
            "FLEXFLOW_NUM_NODES")
    rank = getattr(cfg, "node_rank", -1)
    if rank < 0:
        rank = int(os.environ.get("FLEXFLOW_NODE_RANK", "-1"))
    initialize(coordinator_address=coord,
               num_processes=num_nodes if num_nodes > 1 else None,
               process_id=rank if rank >= 0 else None)


def is_initialized() -> bool:
    import jax

    return jax.distributed.is_initialized()


def process_count() -> int:
    import jax

    return jax.process_count()


def process_index() -> int:
    import jax

    return jax.process_index()


def allgather_value(value: int):
    """Every process's copy of a host-side scalar (one collective).

    The agreement check that turns a would-be deadlock into a
    diagnosis: loop counts derived from per-host data (dataloader
    ``num_batches``) must match across processes BEFORE anyone enters a
    per-batch collective, or the job hangs with no message. Single
    process returns ``[value]`` without touching the backend."""
    import jax

    if jax.process_count() <= 1:
        return [int(value)]
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray([int(value)]), tiled=True)
    return [int(v) for v in np.asarray(gathered).ravel()]


def ranks_agree(value: int) -> Tuple[list, bool]:
    """(per-rank values, all-equal?) for a host-side scalar — the
    checkpoint fail-fast primitive (ADVICE r5): decisions derived from
    per-host filesystem state (is the checkpoint visible? which step is
    newest?) must be compared across ranks BEFORE anyone enters the
    load's collectives, or a non-shared filesystem turns into a silent
    deadlock. Single process: ([value], True)."""
    vals = allgather_value(value)
    return vals, len(set(vals)) == 1


# ---------------------------------------------------------------------------
# per-host batch staging


def _batch_partitions(sharding) -> int:
    """Number of partitions of the batch (leading) dim under `sharding`."""
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) == 0 or spec[0] is None:
        return 1
    axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    n = 1
    for a in axes:
        if a is not None:
            n *= sharding.mesh.shape[a]
    return n


def local_batch_rows(sharding, global_rows: int) -> Tuple[int, int]:
    """(rows, offset) of the contiguous block of a `global_rows`-row batch
    that THIS process feeds under `sharding`.

    Single-process (or batch replicated across hosts): (global_rows, 0).
    """
    import jax

    if jax.process_count() <= 1:
        return global_rows, 0
    parts = _batch_partitions(sharding)
    if global_rows % parts != 0:
        raise ValueError(
            f"batch of {global_rows} rows cannot split over {parts} "
            f"mesh shards")
    # probe shape: one row per partition -> device index map gives each
    # device's partition id along dim 0
    imap = sharding.devices_indices_map((parts,))
    mine = sorted({
        (imap[d][0].start or 0)
        for d in sharding.addressable_devices
    })
    if not mine:
        raise RuntimeError("process holds no shard of the batch dim")
    lo, hi = mine[0], mine[-1]
    if mine != list(range(lo, hi + 1)):
        raise ValueError(
            f"process's batch partitions {mine} are not contiguous — "
            f"reorder the mesh so the data axis is host-major")
    rows_per_part = global_rows // parts
    return rows_per_part * len(mine), rows_per_part * lo


def stage_local_batch(local: np.ndarray, sharding,
                      global_rows: Optional[int] = None):
    """Assemble the global batch array from this process's rows.

    `local` holds the rows this process feeds (its contiguous block of
    the global batch). `global_rows` defaults to
    local_rows * (hosts spanned by the batch axis)."""
    import jax

    if jax.process_count() <= 1:
        return jax.device_put(local, sharding)
    if global_rows is None:
        parts = _batch_partitions(sharding)
        imap = sharding.devices_indices_map((parts,))
        mine = {(imap[d][0].start or 0)
                for d in sharding.addressable_devices}
        if len(mine) == 0 or parts % len(mine) != 0:
            raise RuntimeError("cannot infer global batch size")
        global_rows = local.shape[0] * (parts // len(mine))
    global_shape = (global_rows,) + tuple(local.shape[1:])
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local), global_shape)


def all_gather_host(arr) -> np.ndarray:
    """Gather a (possibly non-fully-addressable) global array to every
    host as numpy — predict()/get_parameter() escape hatch."""
    import jax

    if jax.process_count() <= 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
