"""Device-mesh construction for the pod tier.

The reference's scaling axis is peer count over a TCP tree (SURVEY.md §2.3);
the TPU-native equivalent runs peers *inside* one process as devices on a
`jax.sharding.Mesh` axis, exchanging compressed deltas over ICI instead of
sockets (BASELINE.json north star). Two axes:

- ``peer``: each device along this axis is an independent async-DP peer with
  its own replica of the shared table (the reference's "node").
- ``shard``: the flat table buffer is additionally sharded along this axis, so
  tables far larger than one device's HBM still sync at ICI speed (the
  reference crashes at ~60 Mi elements, quirk Q6; SURVEY.md §5.7).

Tests run this on an 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``); the same code runs unmodified
on a real v5e-8 (SURVEY.md §4.2 tier 2).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..config import MeshConfig


def make_mesh(
    n_peer: Optional[int] = None,
    n_shard: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    config: MeshConfig | None = None,
) -> Mesh:
    """A (peer, shard) mesh over ``n_peer * n_shard`` devices.

    ``n_peer=None`` uses all remaining devices. On real hardware, pass devices
    ordered so that the shard axis is innermost (contiguous ICI neighbors) —
    scale reductions ride the shard axis every frame, while peer exchange is
    one all-gather per frame.
    """
    cfg = config or MeshConfig()
    devs = list(devices if devices is not None else jax.devices())
    if n_peer is None:
        n_peer = len(devs) // n_shard
    need = n_peer * n_shard
    if need > len(devs):
        raise ValueError(
            f"mesh ({n_peer} peers x {n_shard} shards) needs {need} devices, "
            f"have {len(devs)}"
        )
    grid = np.array(devs[:need]).reshape(n_peer, n_shard)
    return Mesh(grid, (cfg.peer_axis, cfg.shard_axis))


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> int:
    """Initialize a MULTI-HOST pod: every host process calls this, then
    builds the same mesh with :func:`make_mesh` over ``jax.devices()`` (the
    global device list). XLA then routes the sync step's collectives over
    ICI within a slice and DCN between hosts automatically — one pod can
    span hosts with no code change in the sync path.

    This is the GSPMD tier of the multi-host story; the alternative tier is
    one HierarchicalTrainer per host pod bridged over the TCP tree
    (train/hierarchical.py), which tolerates asynchrony between hosts the
    way the reference's cross-machine peers do (README.md:26). Use this one
    when hosts are tightly coupled (same pod/DCN domain), the hierarchical
    tier when they are not.

    Arguments default to the standard JAX env vars (cluster auto-detection).
    Returns this process's index. No-ops safely if already initialized."""
    import jax.distributed

    if jax.distributed.is_initialized():
        return jax.process_index()  # idempotent use in notebooks/tests
    # Any RuntimeError here (bad coordinator address, mismatched
    # num_processes/process_id) propagates: swallowing it would let a broken
    # multi-host launch proceed as a confusing single-process mesh.
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_index()


def rows_per_shard(total: int, n_shard: int, lanes: int = 128) -> int:
    """Rows of the (rows, 128) view each shard owns; validates divisibility.

    ``total`` is always a multiple of 1024 (= 8 rows, ops/packing.py TILE), so
    any power-of-two ``n_shard`` <= 8 divides evenly; larger shard counts may
    need the caller to grow the table padding.
    """
    rows = total // lanes
    if rows % n_shard:
        raise ValueError(
            f"{rows} rows not divisible by {n_shard} shards; "
            f"pad the table to a multiple of {n_shard * lanes * 8} elements"
        )
    return rows // n_shard
