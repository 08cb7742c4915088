"""Mesh-sharded index build: the TPU-native replacement for the build-time
shuffle.

Reference equivalent: `df.repartition(numBuckets, indexedCols)` — a Spark
block-shuffle exchange (`actions/CreateActionBase.scala:110-111`). Here the
exchange is ONE `lax.all_to_all` over the mesh's ICI links inside
`shard_map`, with MoE-style fixed per-peer capacity (XLA needs static
shapes; ragged routing is expressed as capacity + validity masks, and
overflow is detected exactly and retried with a larger capacity factor):

per shard (local rows [Ls]):
1. bucket id = murmur-mix(keys) % num_buckets       (32-bit lanes)
2. dest shard = bucket * n_shards // num_buckets    (contiguous-range map)
3. one local stable sort by dest groups rows per peer
4. rows scatter into a [n_shards, capacity] send buffer; overflow beyond
   capacity is counted (never silently dropped: the host retries)
5. lax.all_to_all swaps peer slabs across the mesh -> each shard holds
   exactly the rows of its buckets
6. one local stable sort by (bucket, keys) orders every bucket run

The host then writes each shard's buckets as bucketed parquet, identical
layout to the single-chip path.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import (ColumnBatch, batch_to_tree,
                                        tree_to_batch)
from hyperspace_tpu.ops import keys as keymod
from hyperspace_tpu.ops.build import _entry_sort_lanes, _tree_hash_lanes
from hyperspace_tpu.parallel.mesh import SHARD_AXIS


def _route_stage(tree, row_valid, bucket, dest, axis: str, n_peers: int,
                 capacity: int):
    """One routing exchange: sort local rows by `dest` peer, scatter into
    a [n_peers, capacity] send buffer, all_to_all over the named mesh
    `axis` (the collective is CONFINED to that axis's device groups).
    Returns (routed tree, routed valid, routed bucket, overflow count) —
    overflow rows are counted exactly, never silently dropped."""
    import jax
    import jax.numpy as jnp

    n_local = dest.shape[0]
    iota = jnp.arange(n_local, dtype=jnp.int32)
    dest_sorted, perm = jax.lax.sort([dest, iota], num_keys=1, is_stable=True)

    # Slot within the destination segment.
    seg_start = jnp.searchsorted(
        dest_sorted, jnp.arange(n_peers + 1, dtype=jnp.int32), side="left")
    offset = jnp.arange(n_local, dtype=jnp.int32) - jnp.take(
        seg_start, jnp.clip(dest_sorted, 0, n_peers))
    keep = (offset < capacity) & (dest_sorted < n_peers)
    overflow = jnp.sum((offset >= capacity) & (dest_sorted < n_peers))
    slot = jnp.where(keep, dest_sorted * capacity + offset,
                     n_peers * capacity)

    def route(arr):
        src = jnp.take(arr, perm, axis=0)
        buf_shape = (n_peers * capacity + 1,) + src.shape[1:]
        buf = jnp.zeros(buf_shape, dtype=src.dtype)
        buf = buf.at[slot].set(src, mode="drop")
        send = buf[:n_peers * capacity].reshape(
            (n_peers, capacity) + src.shape[1:])
        return jax.lax.all_to_all(send, axis, split_axis=0,
                                  concat_axis=0, tiled=False)

    routed = {}
    for name, entry in tree.items():
        out = dict(entry)
        out["data"] = route(entry["data"]).reshape(
            -1, *entry["data"].shape[1:])
        if "validity" in entry:
            out["validity"] = route(entry["validity"]).reshape(-1)
        routed[name] = out
    # Unwritten send slots keep their zero-init => validity defaults False,
    # so routing the raw validity/bucket arrays is sufficient (route()
    # applies the dest-sort permutation internally).
    recv_valid = route(row_valid).reshape(-1)
    recv_bucket = route(bucket).reshape(-1)
    return routed, recv_valid, recv_bucket, overflow


def _stage_capacity(local_rows: int, n_peers: int,
                    capacity_factor: float) -> int:
    return max(16, int(local_rows / n_peers * capacity_factor))


def _shard_step(tree, key_names: Tuple[str, ...], num_buckets: int,
                n_ici: int, n_dcn: int, capacity_factor: float):
    """The per-shard body (runs under shard_map; local shapes).

    1-axis mesh (n_dcn == 1): one all_to_all routes each row to its
    bucket's owner shard. 2-axis mesh: HIERARCHICAL routing — stage 1
    moves rows to the owner's ICI position within the source slice
    (all_to_all over the inner `shard` axis: rides ICI), stage 2 moves
    them to the owner's slice (all_to_all over the outer `dcn` axis);
    each stage changes exactly one mesh coordinate, so the flat owner
    `bucket % (n_dcn * n_ici) = d * n_ici + i` is reached in two
    axis-confined hops instead of one flat exchange."""
    import jax.numpy as jnp
    from hyperspace_tpu.ops.hash_partition import flat_hash32

    row_valid = tree["__valid__"]
    data_tree = {k: v for k, v in tree.items() if k != "__valid__"}
    lanes = []
    for name in key_names:
        lanes.extend(_tree_hash_lanes(tree[name]))
    h = flat_hash32(lanes)  # the one shared hash identity
    bucket = (h % jnp.uint32(num_buckets)).astype(jnp.int32)

    n_total = n_ici * n_dcn
    # Contiguous-range ownership (mesh.bucket_owner): shard s receives the
    # bucket range [ceil(s*B/n), ceil((s+1)*B/n)) — the same map the
    # born-sharded parquet writer and the per-device cache fills use. The
    # int64 intermediate keeps bucket * n_total exact for large bucket
    # counts before the narrowing divide.
    owner = ((bucket.astype(jnp.int64) * n_total)
             // num_buckets).astype(jnp.int32)
    overflow = jnp.zeros((), dtype=jnp.int32)

    # Stage 1 (ICI): to the owner's position within THIS slice.
    dest1 = jnp.where(row_valid, owner % n_ici, jnp.int32(n_ici))
    cap1 = _stage_capacity(dest1.shape[0], n_ici, capacity_factor)
    data_tree, row_valid, bucket, ov = _route_stage(
        data_tree, row_valid, bucket, dest1, SHARD_AXIS, n_ici, cap1)
    overflow = overflow + ov

    if n_dcn > 1:
        # Stage 2 (DCN): to the owner slice, ICI position already final.
        # Ownership re-derives from the ROUTED bucket ids (the data moved
        # in stage 1) through the same contiguous-range map.
        from hyperspace_tpu.parallel.mesh import DCN_AXIS
        owner2 = ((bucket.astype(jnp.int64) * n_total)
                  // num_buckets).astype(jnp.int32) // n_ici
        dest2 = jnp.where(row_valid, owner2, jnp.int32(n_dcn))
        cap2 = _stage_capacity(dest2.shape[0], n_dcn, capacity_factor)
        data_tree, row_valid, bucket, ov2 = _route_stage(
            data_tree, row_valid, bucket, dest2, DCN_AXIS, n_dcn, cap2)
        overflow = overflow + ov2

    recv_bucket = jnp.where(row_valid, bucket, num_buckets)

    # Local order: (bucket, keys); invalid rows (bucket=num_buckets) last.
    operands = [recv_bucket]
    for name in key_names:
        operands.extend(_entry_sort_lanes(data_tree[name]))
    m = recv_bucket.shape[0]
    iota2 = jnp.arange(m, dtype=jnp.int32)
    import jax
    results = jax.lax.sort([*operands, iota2], num_keys=len(operands),
                           is_stable=True)
    perm2 = results[-1]
    sorted_bucket = results[0]
    out_tree = {}
    for name, entry in data_tree.items():
        out = dict(entry)
        out["data"] = jnp.take(entry["data"], perm2, axis=0)
        if "validity" in entry:
            out["validity"] = jnp.take(entry["validity"], perm2, axis=0)
        out_tree[name] = out
    out_tree["__valid__"] = {"data": jnp.take(row_valid, perm2)}
    out_tree["__bucket__"] = {"data": sorted_bucket}
    out_tree["__overflow__"] = {"data": overflow.reshape(1)}
    return out_tree


def make_distributed_build_step(mesh, key_names: Tuple[str, ...],
                                num_buckets: int, capacity_factor: float):
    """Compile the full mesh-sharded build step (jit of shard_map). On a
    2-axis (dcn, shard) mesh the row axis shards over BOTH axes and the
    body runs the hierarchical two-stage exchange."""
    import jax

    from hyperspace_tpu.parallel.mesh import (compat_shard_map, dcn_size,
                                              row_spec)

    n_ici = mesh.shape[SHARD_AXIS]
    n_dcn = dcn_size(mesh)
    rows_spec = row_spec(mesh)

    def spec_like(tree):
        return jax.tree_util.tree_map(lambda _: rows_spec, tree)

    def step(tree):
        body = partial(_shard_step, key_names=key_names,
                       num_buckets=num_buckets, n_ici=n_ici, n_dcn=n_dcn,
                       capacity_factor=capacity_factor)
        sharded = compat_shard_map(body, mesh=mesh,
                                   in_specs=(spec_like(tree),),
                                   out_specs=rows_spec,
                                   check_vma=False)
        return sharded(tree)

    # A fresh jit per call means every dispatch traces; the compile
    # tracker makes that cost (and any future retrace storm here)
    # visible as compile.mesh.build_step.traces instead of silent wall.
    from hyperspace_tpu.telemetry import instrumented_jit
    return instrumented_jit("mesh.build_step", step, scope="hs.mesh.build")


def distributed_build(batch: ColumnBatch, key_columns: Sequence[str],
                      num_buckets: int, mesh,
                      capacity_factor: float = 2.0):
    """Run the mesh-sharded build. Returns (sorted ColumnBatch of valid rows
    in (shard, bucket, keys) order, per-bucket lengths np[num_buckets]).

    Hash tables / dictionaries are replicated; row data is sharded on entry
    (XLA moves the host arrays to the right chips). Exact overflow recovery:
    if any shard overflowed its per-peer capacity, retry with 2x capacity.
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.parallel.mesh import shard_rows, total_shards

    n_shards = total_shards(mesh)
    key_names = tuple(batch.schema.field(c).name for c in key_columns)
    n = batch.num_rows
    local = -(-n // n_shards)  # ceil
    padded = local * n_shards

    tracer = telemetry.tracer()
    reg = telemetry.get_registry()
    span_ts = tracer.now_us() if tracer is not None else 0.0

    # Payload rides the exchange in its carried form (float64 as int64
    # bits: exact through the all_to_all); only the keys are computed on.
    tree, aux = batch_to_tree(batch, computes_on=key_names)
    # Host-resident sources build the padded tree in numpy and place
    # every leaf with the row sharding DIRECTLY (pipelined transfer
    # engine, all shards' puts issued before the first block) — each
    # device receives only its slice, instead of the whole table
    # round-tripping through the default device before the exchange.
    host_input = all(isinstance(entry["data"], np.ndarray)
                     for entry in tree.values())
    xp = np if host_input else jnp

    # Pad rows to a multiple of the shard count; padding rows are invalid.
    def pad(arr):
        pad_width = [(0, padded - n)] + [(0, 0)] * (arr.ndim - 1)
        return xp.pad(arr, pad_width)

    in_tree: Dict = {}
    for name, entry in tree.items():
        out = dict(entry)
        out["data"] = pad(entry["data"])
        if "validity" in entry:
            out["validity"] = pad(entry["validity"])
        # hash tables stay replicated: broadcast to per-shard copies
        if "hash_hi" in entry:
            out["hash_hi"] = xp.tile(entry["hash_hi"], (n_shards, 1)).reshape(
                n_shards * entry["hash_hi"].shape[0])
            out["hash_lo"] = xp.tile(entry["hash_lo"], (n_shards, 1)).reshape(
                n_shards * entry["hash_lo"].shape[0])
        in_tree[name] = out
    in_tree["__valid__"] = xp.concatenate(
        [xp.ones(n, dtype=bool), xp.zeros(padded - n, dtype=bool)])
    if host_input:
        from hyperspace_tpu.io import transfer

        engine = transfer.get_engine()
        sharding = shard_rows(mesh)
        in_tree = jax.tree_util.tree_map(
            lambda a: (engine.put(a, device=sharding)
                       if isinstance(a, np.ndarray) else a), in_tree)

    factor = capacity_factor
    while True:
        step = make_distributed_build_step(mesh, key_names, num_buckets,
                                           factor)
        t0 = _time.perf_counter()
        with telemetry.span("hs.mesh.build.dispatch", "mesh",
                            shards=n_shards, rows=n):
            out = step(in_tree)
        reg.counter("mesh.build.dispatch_s").inc(
            _time.perf_counter() - t0)
        t0 = _time.perf_counter()
        overflow = int(jnp.sum(out["__overflow__"]["data"]))  # host sync
        sync_s = _time.perf_counter() - t0
        reg.counter("mesh.build.sync_s").inc(sync_s)
        telemetry.add_seconds("mesh.sync_s", sync_s)
        if overflow == 0:
            break
        reg.counter("mesh.build.overflow_retries").inc()
        factor *= 2  # exact recovery: nothing was lost, rerun wider

    result_tree = {}
    for name, entry in out.items():
        if name.startswith("__"):
            continue
        cleaned = dict(entry)
        if "hash_hi" in cleaned:
            # restore single replicated hash tables
            cleaned["hash_hi"] = tree[name]["hash_hi"]
            cleaned["hash_lo"] = tree[name]["hash_lo"]
        result_tree[name] = cleaned
    full = tree_to_batch(result_tree, batch.schema, aux)

    # Compact + globally order ON DEVICE: invalid rows carry bucket id
    # num_buckets, and every bucket lives on exactly one shard (the
    # contiguous-range map — shard s's buckets all precede shard s+1's),
    # so ONE stable argsort by bucket yields global (bucket, keys) order
    # with invalid rows at the tail — the per-shard key order within each
    # bucket is preserved, and under range ownership the sort is nearly
    # shard-local (rows only compact within their shard's run). The only
    # host traffic is the [num_buckets] length vector, which also sizes
    # the final slice.
    buckets_dev = out["__bucket__"]["data"]
    valid_dev = out["__valid__"]["data"]
    order = jnp.argsort(buckets_dev, stable=True)
    lengths = np.asarray(jax.ops.segment_sum(
        valid_dev.astype(jnp.int32), buckets_dev.astype(jnp.int32),
        num_segments=num_buckets + 1))[:num_buckets].astype(np.int64)
    total = int(lengths.sum())
    final = full.take(order[:total])
    # Per-device attribution: flat shard s owns the contiguous bucket
    # range (mesh.bucket_ranges), so the length vector yields each chip's
    # row load exactly — the histogram + device-track spans are where
    # multi-chip skew becomes visible.
    from hyperspace_tpu.parallel.mesh import bucket_ranges
    shard_rows = [int(lengths[lo:hi].sum())
                  for lo, hi in bucket_ranges(num_buckets, n_shards)]
    for rows in shard_rows:
        reg.histogram("mesh.build.shard_rows").observe(rows)
    reg.counter("mesh.build.execs").inc()
    telemetry.event("mesh", "build", shards=n_shards, rows=n,
                    buckets=num_buckets, shard_rows=shard_rows)
    if tracer is not None:
        tracer.device_spans("build", span_ts, shard_rows,
                            buckets=num_buckets)
    return final, lengths
