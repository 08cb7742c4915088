"""Device mesh helpers.

The reference delegates distribution to the Spark cluster (driver/executor
split, SURVEY §2.12); here the cluster is a `jax.sharding.Mesh` over TPU
chips — ICI within a slice, DCN across slices — and data movement is XLA
collectives, not a block-shuffle service.

Mesh shapes: single-slice deployments use a 1-axis `(shard,)` mesh.
Multi-host deployments use a 2-axis `(dcn, shard)` mesh — `shard` is the
INNER axis (devices within a slice, connected by ICI), `dcn` the outer
axis (one row per slice, connected by datacenter network). Collectives
issued over one named axis are confined to its device groups, so the
build's heavy within-slice re-bucket rides ICI and only the cross-slice
stage touches DCN (SURVEY §2.12: "DCN only across slices").

Bucket <-> shard ownership: flat shard `s` of an `n`-total-shard mesh owns
the CONTIGUOUS bucket range `[ceil(s*B/n), ceil((s+1)*B/n))` —
`bucket_owner(b) = b*n // B`; on a 2-axis mesh flat order is row-major
(dcn, shard), i.e. `s = d * n_ici + i`. The build (all_to_all routing),
the born-sharded parquet shard layout recorded in the index log entry,
the per-device segment-cache fills, and the SPMD co-sharded join all rely
on this ONE mapping (`bucket_ranges` / `bucket_owner` below), which is
also why equal bucket counts join with ZERO inter-chip traffic (the
ranker's preference, reference `index/rankers/JoinIndexRanker.scala:40-55`).
Contiguous ranges — rather than the former `b % n` stripes — are what let
a bucket-ordered on-disk layout slice straight into per-device shards: a
device's bucket range is one contiguous run of rows/files, so a born-
sharded read fills each device's HBM from its own files with no
interleaving gather.

This module is also THE layout-spec seam: every `NamedSharding` /
`PartitionSpec` / `shard_map` the package constructs comes from the
helpers here (`row_spec`, `shard_rows`, `replicated`, `device_of_shard`,
`compat_shard_map`), so layouts cannot drift between operators —
`scripts/check_metrics_coverage.py` bans raw construction elsewhere.
"""

from __future__ import annotations

import math
from typing import Optional

import hyperspace_tpu._jax_config  # noqa: F401

SHARD_AXIS = "shard"
DCN_AXIS = "dcn"


def compat_shard_map(body, mesh, in_specs, out_specs,
                     check_vma: bool = False):
    """`jax.shard_map`, constructed HERE only: the one seam every mesh
    kernel's layout goes through (the coverage lint bans raw
    construction elsewhere)."""
    from jax import shard_map
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=check_vma)


def make_mesh(num_devices: Optional[int] = None,
              dcn_size: Optional[int] = None):
    """1-axis `(shard,)` mesh, or — with `dcn_size` > 1 — a 2-axis
    `(dcn, shard)` mesh of dcn_size slices."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if num_devices is not None:
        if len(devices) < num_devices:
            raise ValueError(
                f"Requested {num_devices} devices, have {len(devices)}.")
        devices = devices[:num_devices]
    from hyperspace_tpu import telemetry
    telemetry.get_registry().gauge("mesh.devices").set(len(devices))
    import numpy as np
    if dcn_size is not None and dcn_size > 1:
        if len(devices) % dcn_size != 0:
            raise ValueError(
                f"dcn size {dcn_size} must divide device count "
                f"{len(devices)}.")
        grid = np.array(devices).reshape(dcn_size, -1)
        return Mesh(grid, (DCN_AXIS, SHARD_AXIS))
    return Mesh(np.array(devices), (SHARD_AXIS,))


def row_axes(mesh):
    """The mesh axis names the ROW dimension shards over — every axis,
    outer (dcn) first, so flat shard order is row-major (dcn, shard)."""
    return tuple(mesh.axis_names)


def total_shards(mesh) -> int:
    return math.prod(mesh.shape.values())


def dcn_size(mesh) -> int:
    """Number of slices (1 on a flat single-axis mesh)."""
    return mesh.shape.get(DCN_AXIS, 1)


def ici_size(mesh) -> int:
    """Devices per slice (the inner ICI axis; the whole mesh when
    flat)."""
    return mesh.shape.get(SHARD_AXIS, total_shards(mesh))


def slice_of_shard(shard: int, n_ici: int) -> int:
    """Owning slice of flat shard `shard` under row-major (dcn, shard)
    flat order."""
    return shard // n_ici


def slice_submesh(mesh, idx: int):
    """Flat 1-axis submesh over slice `idx`'s devices — THE replica
    execution mesh: with replication on, a query routed to slice `idx`
    runs the whole born-sharded pipeline over this submesh exactly as a
    single-slice deployment would (`bucket_ranges(B, n_ici)` over the
    slice's devices), so replica execution is the degenerate flat case
    by construction. On a flat mesh only slice 0 exists and the mesh is
    returned as-is."""
    import numpy as np
    from jax.sharding import Mesh

    grid = np.asarray(mesh.devices)
    if grid.ndim == 1:
        if idx != 0:
            raise ValueError(f"flat mesh has one slice; asked for {idx}")
        return mesh
    if not 0 <= idx < grid.shape[0]:
        raise ValueError(
            f"slice {idx} out of range for a {grid.shape[0]}-slice mesh")
    return Mesh(grid[idx], (SHARD_AXIS,))


def mesh_device_tag(mesh) -> tuple:
    """Stable identity of the mesh's device set in flat shard order —
    the replica discriminator in per-device segment-cache keys: two
    slices of one topology hold the SAME bucket ranges on DIFFERENT
    devices, and their cached shards must never alias."""
    return tuple(int(getattr(d, "id", i))
                 for i, d in enumerate(mesh_device_list(mesh)))


def row_spec(mesh):
    """PartitionSpec splitting axis 0 across ALL mesh axes — THE row
    sharding used by every parallel operator (build/join/aggregate/scan)."""
    from jax.sharding import PartitionSpec
    return PartitionSpec(row_axes(mesh))


def shard_rows(mesh):
    """Sharding spec: rows (axis 0) split across ALL mesh devices."""
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, row_spec(mesh))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


# -- contiguous bucket-range ownership --------------------------------------
#
# THE bucket <-> shard map (module docstring). Every consumer — the build's
# all_to_all routing, the born-sharded parquet writer, the per-device
# segment-cache fills, and the SPMD join/aggregate — derives ownership from
# these two functions so the on-disk shard layout, the HBM residency, and
# the collective routing can never disagree.


def bucket_ranges(num_buckets: int, n_shards: int):
    """[(lo, hi)) bucket range per flat shard: shard s owns
    `[ceil(s*B/n), ceil((s+1)*B/n))` — contiguous, balanced to within one
    bucket, exact `B/n`-sized when `n_shards` divides `num_buckets`."""
    return [((s * num_buckets + n_shards - 1) // n_shards,
             ((s + 1) * num_buckets + n_shards - 1) // n_shards)
            for s in range(n_shards)]


def bucket_owner(bucket, num_buckets: int, n_shards: int):
    """Owning flat shard of `bucket` (scalar, numpy, or traced jax array)
    under the contiguous-range map — the exact inverse of
    `bucket_ranges`."""
    return bucket * n_shards // num_buckets


def slice_bucket_ranges(num_buckets: int, n_slices: int, n_ici: int):
    """[(lo, hi)) bucket range per SLICE of an (n_slices x n_ici)
    topology. The hierarchy nests exactly: because flat shard
    `s = d * n_ici + i` owns `[ceil(s*B/n), ...)` with
    `n = n_slices * n_ici`, slice d's union of its shards' ranges is
    `[ceil(d*B/n_slices), ceil((d+1)*B/n_slices))` — i.e. the slice-level
    map IS `bucket_ranges(B, n_slices)`, so a slice-granular record
    (layout v3, replica residency) and the flat shard map can never
    disagree."""
    del n_ici  # the identity above makes the inner size irrelevant
    return bucket_ranges(num_buckets, n_slices)


def shard_row_segments(lengths, n_shards: int):
    """Per-shard (row_start, row_end) into a bucket-ordered row space:
    shard s's rows are exactly its bucket range's rows — the property
    that makes a bucket-ordered table sliceable into per-device shards
    with no gather. `lengths` is the [num_buckets] per-bucket row-count
    vector."""
    import numpy as np
    lengths = np.asarray(lengths, dtype=np.int64)
    cum = np.concatenate([[0], np.cumsum(lengths)])
    return [(int(cum[lo]), int(cum[hi]))
            for lo, hi in bucket_ranges(len(lengths), n_shards)]


def mesh_device_list(mesh):
    """The mesh's devices in FLAT shard order (row-major over the axes) —
    the order `shard_rows` places shard s of a [S*C] row-sharded array on
    device s. Per-device segment-cache fills target these."""
    import numpy as np
    return list(np.asarray(mesh.devices).reshape(-1))


def device_of_shard(mesh, shard: int):
    """The device owning flat shard `shard` (per-device cache fills and
    born-sharded placements target it)."""
    return mesh_device_list(mesh)[shard]


def assemble_sharded_rows(mesh, per_device_arrays):
    """Build ONE globally row-sharded array from per-device single-shard
    arrays (equal first-dim length, array i resident on flat-shard device
    i) with ZERO data movement — the warm-path assembly of born-sharded
    reads: each device's segment-cache entry becomes its shard of the
    global array, and no byte crosses a link."""
    import jax
    total = sum(int(a.shape[0]) for a in per_device_arrays)
    shape = (total,) + tuple(per_device_arrays[0].shape[1:])
    return jax.make_array_from_single_device_arrays(
        shape, shard_rows(mesh), list(per_device_arrays))
