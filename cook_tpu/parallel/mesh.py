"""Device mesh helpers for pool-sharded scheduling.

The TPU-build equivalent of the reference's per-pool concurrency (reference:
per-pool handlers round-robin triggered, scheduler.clj:2491-2517): pools
shard across a 1-D "pool" mesh axis; cross-pool reconciliation (quota groups,
global DRU telemetry) rides ICI collectives (SURVEY.md section 2.7).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh

POOL_AXIS = "pool"
DCN_AXIS = "dcn"


class ShardAlignmentError(ValueError):
    """The PartitionMap's pool groups and the mesh pool-shard layout
    disagree: a pool's write-plane partition and its resident-buffer
    shard would be owned by DIFFERENT controller processes (double-owned
    or orphaned resident state).  Raised at daemon boot — a config
    error, never a silent split-brain."""


def shard_of_partition(partition: int, count: int, n_shards: int) -> int:
    """Which controller shard owns write-plane ``partition``: partitions
    map onto shards in contiguous blocks, so a shard's pools are also a
    contiguous block of the pool-stacked [P, ...] mesh arrays — the same
    slice ``parallel.mesh.pool_sharding`` commits to that shard's
    devices.  ``count`` must divide evenly into ``n_shards`` blocks."""
    if n_shards < 1:
        raise ShardAlignmentError(f"shards must be >= 1, got {n_shards}")
    if count % n_shards != 0:
        raise ShardAlignmentError(
            f"{count} write-plane partitions do not divide over "
            f"{n_shards} controller shards; partition blocks must be "
            "equal so every shard's resident slice has one owner")
    if not 0 <= partition < count:
        raise ShardAlignmentError(
            f"partition {partition} out of range [0, {count})")
    return partition // (count // n_shards)


def shard_of_pool(pmap, pool: str, n_shards: int) -> int:
    """Controller shard owning ``pool``: its PartitionMap partition's
    contiguous block (``pmap`` is a state.partition.PartitionMap)."""
    return shard_of_partition(pmap.partition_of(pool), pmap.count, n_shards)


def validate_shard_alignment(pmap, n_shards: int,
                             declared: Optional[Dict[str, int]] = None
                             ) -> Dict[int, List[str]]:
    """Boot-time cross-check (ISSUE 19 satellite): the PartitionMap's
    pool groups and the mesh ``pool_sharding`` layout must be the SAME
    partition.  ``declared`` is the operator's explicit pool -> mesh
    shard table (config ``partitions.shard_pools``); every declared pool
    must land on the shard its write-plane partition routes to, and
    every declared shard index must exist.  Returns the validated
    shard -> sorted pool names layout (explicit pools only; hash-routed
    pools follow their partition block by construction).  Raises
    :class:`ShardAlignmentError` with the offending pool on mismatch —
    a mismatched declaration would silently double-own or orphan the
    pool's resident buffers."""
    layout: Dict[int, List[str]] = {s: [] for s in range(n_shards)}
    for pool in sorted(getattr(pmap, "pools", {}) or {}):
        layout[shard_of_pool(pmap, pool, n_shards)].append(pool)
    for pool, shard in sorted((declared or {}).items()):
        if not 0 <= int(shard) < n_shards:
            raise ShardAlignmentError(
                f"shard_pools[{pool!r}] = {shard} but only shards "
                f"[0, {n_shards}) exist")
        owner = shard_of_pool(pmap, pool, n_shards)
        if int(shard) != owner:
            raise ShardAlignmentError(
                f"pool {pool!r} is declared on mesh shard {shard} but "
                f"its write-plane partition {pmap.partition_of(pool)} "
                f"belongs to controller shard {owner}: the partition "
                "map and the mesh pool_sharding layout must agree "
                "(one partition = one process = one mesh shard)")
        if pool not in layout[owner]:
            layout[owner].append(pool)
            layout[owner].sort()
    return layout


def validate_pool_mesh(mesh_devices: int, shards: int = 0,
                       shard_id: Optional[int] = None,
                       local_devices: Optional[int] = None) -> None:
    """Boot-time check of ``pipeline.mesh_devices`` (docs/DEPLOY.md
    "pool mesh").  A mesh over one device and controller shards are
    exclusive layouts: a shard process commits resident buffers for ITS
    pools only, and a pool mesh under it would commit them for pools
    other processes own — double-owned device state, the split-brain
    :func:`validate_shard_alignment` exists to refuse.  And a mesh of
    more devices than the process has must fail the boot: a cycle that
    quietly ran on fewer chips than the deployment states would read as
    an ordinary, slower one.  ``local_devices`` None skips the second
    check (the daemon validates its configuration before any process of
    it touches JAX)."""
    if mesh_devices > 1 and (shards > 0 or shard_id is not None):
        who = (f"controller shard {shard_id}" if shard_id is not None
               else f"partitions.shards = {shards}")
        raise ShardAlignmentError(
            f"pipeline.mesh_devices = {mesh_devices} with {who}: a shard "
            "process commits resident buffers for ITS pools only, so its "
            "cycle runs on one device; use partitions.shards (one process "
            "a shard) or pipeline.mesh_devices (one process, pools split "
            "over its devices), not both")
    if local_devices is not None and mesh_devices > max(local_devices, 1):
        raise ValueError(
            f"pipeline.mesh_devices = {mesh_devices} but this process has "
            f"{local_devices} local device(s): the cycle's pool mesh "
            "cannot be built, and nothing falls back to fewer chips")


def pool_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the pool axis; single-slice, collectives ride ICI."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (POOL_AXIS,))


def multislice_pool_mesh(n_slices: int,
                         devices_per_slice: Optional[int] = None) -> Mesh:
    """2-D ("dcn", "pool") mesh for multi-slice topologies: pools shard over
    BOTH axes (each slice owns an independent pool block — pool cycles never
    communicate within a cycle except reconciliation), so the only
    cross-slice traffic is the small matched-usage all-gather / placement
    psum, which is exactly what belongs on DCN; everything bandwidth-heavy
    stays slice-local on ICI (SURVEY.md section 5 distributed-backend
    mapping)."""
    devices = jax.devices()
    if devices_per_slice is None:
        if len(devices) % n_slices != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible into {n_slices} "
                "slices; pass devices_per_slice explicitly")
        devices_per_slice = len(devices) // n_slices
    need = n_slices * devices_per_slice
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.array(devices[:need]).reshape(n_slices, devices_per_slice)
    return Mesh(grid, (DCN_AXIS, POOL_AXIS))


def pool_sharding(mesh: Mesh):
    """NamedSharding that splits a [P, ...] pool-stacked array over every
    mesh axis — the committed placement for DEVICE-RESIDENT cycle state
    (sched/fused.py resident pack): each pool shard owns its own slice of
    the resident rows/flags buffers, so the per-cycle delta scatter and
    the fused cycle's shard_map read the same owner-local memory instead
    of resharding an uncommitted host upload every dispatch."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(mesh.axis_names))
