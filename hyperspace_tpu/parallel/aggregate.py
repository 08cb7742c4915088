"""Mesh-sharded group-by aggregation: partial aggregation per shard, one
small combine on the host.

The reference delegates aggregation to Spark's partial/final aggregate
pairs over the cluster; the TPU equivalent is SPMD partial aggregation
under `shard_map` — each chip sorts ITS rows by the group key lanes and
segment-reduces into a fixed-capacity [G] slot table (XLA needs static
shapes; ragged group counts are expressed as capacity + validity, with
exact overflow detection and a wider retry, like the build's all_to_all).
Only the [n_shards, G] partials cross to the host, where numpy merges
them by key — combinable forms: count/sum -> sum, min/max -> min/max,
avg -> (sum, count), stddev -> (count, sum, M2) merged by the exact
variance decomposition  M2_tot = sum M2_i + sum cnt_i (mean_i - anchor)^2
with the anchor at the global mean (per-shard deviations stay centered,
so no catastrophic cancellation).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import (ColumnBatch, DeviceColumn,
                                        fetched)
from hyperspace_tpu.parallel.mesh import total_shards
from hyperspace_tpu.parallel.scan import shard_batch
from hyperspace_tpu.plan.nodes import AggSpec
from hyperspace_tpu.plan.schema import Schema


def _shard_partials(tree, num_lanes: int, specs_meta: Tuple[Tuple[str, bool],
                                                            ...],
                    capacity: int):
    """Per-shard body. `tree` carries: "lane<i>" group-key lanes,
    "valid" row mask, and per-spec "v<j>" value / "m<j>" value-validity
    arrays. Returns slot tables of size [G]."""
    import jax
    import jax.numpy as jnp

    lanes = [tree[f"lane{i}"] for i in range(num_lanes)]
    row_valid = tree["valid"]
    n = row_valid.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # Invalid (padding) rows sort last via a leading validity key.
    sorted_ops = jax.lax.sort([~row_valid, *lanes, iota],
                              num_keys=1 + len(lanes), is_stable=True)
    perm = sorted_ops[-1]
    inv_sorted = sorted_ops[0]
    lanes_sorted = sorted_ops[1:-1]
    differs = jnp.zeros(n, dtype=jnp.int32)
    for k in (inv_sorted, *lanes_sorted):
        differs = differs | jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32),
             (k[1:] != k[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(differs, dtype=jnp.int32)
    valid_sorted = jnp.take(row_valid, perm)
    num_groups = jnp.max(jnp.where(valid_sorted, seg, -1)) + 1
    overflow = jnp.maximum(num_groups - capacity, 0)
    slot = jnp.where(valid_sorted & (seg < capacity), seg, capacity)

    def seg_sum(x):
        return jax.ops.segment_sum(x, slot, num_segments=capacity + 1
                                   )[:capacity]

    out = {"overflow": overflow.reshape(1)}
    # Group identity: first sorted row of each local segment.
    firsts = jnp.searchsorted(seg, jnp.arange(capacity, dtype=jnp.int32),
                              side="left")
    firsts = jnp.clip(firsts, 0, n - 1)
    for i, lane in enumerate(lanes_sorted):
        out[f"key{i}"] = jnp.take(lane, firsts)
    out["rows"] = seg_sum(valid_sorted.astype(jnp.int64))
    out["first_perm"] = jnp.take(perm, firsts)

    for j, (func, _nullable) in enumerate(specs_meta):
        if func == "count_star":
            continue  # rows covers it
        v = jnp.take(tree[f"v{j}"], perm)
        m = jnp.take(tree[f"m{j}"], perm) & valid_sorted
        cnt = seg_sum(m.astype(jnp.int64))
        out[f"cnt{j}"] = cnt
        if func == "count":
            continue
        # Integer aggregates accumulate in int64 — float64 would silently
        # lose exactness past 2^53, diverging from the single-chip path.
        is_float = jnp.issubdtype(v.dtype, jnp.floating)
        acc_dtype = jnp.float64 if is_float else jnp.int64
        if func in ("sum", "avg"):
            out[f"s1{j}"] = seg_sum(jnp.where(m, v, 0).astype(acc_dtype))
        elif func == "min":
            sentinel = jnp.inf if is_float else jnp.iinfo(jnp.int64).max
            big = jnp.where(m, v.astype(acc_dtype), sentinel)
            out[f"mn{j}"] = jax.ops.segment_min(
                big, slot, num_segments=capacity + 1)[:capacity]
        elif func == "max":
            sentinel = -jnp.inf if is_float else jnp.iinfo(jnp.int64).min
            small = jnp.where(m, v.astype(acc_dtype), sentinel)
            out[f"mx{j}"] = jax.ops.segment_max(
                small, slot, num_segments=capacity + 1)[:capacity]
        elif func == "stddev":
            x = jnp.where(m, v, 0).astype(jnp.float64)
            s1 = seg_sum(x)
            mu = s1 / jnp.maximum(cnt.astype(jnp.float64), 1)
            dev = jnp.where(m, x - jnp.take(mu, jnp.clip(slot, 0, capacity - 1)),
                            0.0)
            out[f"s1{j}"] = s1
            out[f"m2{j}"] = seg_sum(dev * dev)
    return out


def make_partial_step(mesh, num_lanes: int, specs_meta, capacity: int):
    """The partial-aggregation program (`jit_aggregate_step`, its ops
    under the device scope `hs.mesh.aggregate`), kept with the other
    SPMD programs per (mesh, lanes, specs, capacity): a warm repeat
    dispatches the compiled program instead of retracing a fresh jit."""
    from hyperspace_tpu.parallel.spmd import _cached_program

    def build():
        import jax

        from hyperspace_tpu.parallel.mesh import (compat_shard_map,
                                                  row_spec)
        from hyperspace_tpu.telemetry import instrumented_jit
        rows_spec = row_spec(mesh)

        def shard_partials(tree):
            return _shard_partials(tree, num_lanes=num_lanes,
                                   specs_meta=specs_meta,
                                   capacity=capacity)

        def aggregate_step(tree):
            return compat_shard_map(
                shard_partials, mesh=mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: rows_spec,
                                                 tree),),
                out_specs=rows_spec, check_vma=False)(tree)

        return instrumented_jit("mesh.aggregate_step", aggregate_step,
                                scope="hs.mesh.aggregate")

    return _cached_program(
        ("aggregate", mesh, num_lanes, specs_meta, capacity), build)


def distributed_group_aggregate(batch: ColumnBatch,
                                group_columns: Sequence[str],
                                aggregates: Sequence[AggSpec],
                                out_schema: Schema, mesh,
                                pre_sharded=None) -> ColumnBatch:
    """SPMD partial aggregation over the mesh + host combine. Requires at
    least one group column (global aggregates are cheap single-chip).

    `pre_sharded` = (flat sharded batch, row_valid) skips the placement
    step entirely for BORN-SHARDED inputs (`parallel/spmd.py`): the
    partial program consumes the already-resident [S*C] layout, so a
    join -> aggregate pipeline stays device-resident stage to stage."""
    if not group_columns:
        raise HyperspaceException(
            "distributed aggregation requires group columns")
    from hyperspace_tpu import telemetry
    n_shards = total_shards(mesh)
    reg = telemetry.get_registry()
    reg.counter("mesh.aggregate.execs").inc()
    telemetry.event("mesh", "aggregate", shards=n_shards,
                    rows=batch.num_rows, groups=len(group_columns))
    with telemetry.span("hs.mesh.aggregate", "mesh", rows=batch.num_rows,
                        shards=n_shards):
        return _distributed_group_aggregate(
            batch, group_columns, aggregates, out_schema, mesh, n_shards,
            reg, pre_sharded=pre_sharded)


def _distributed_group_aggregate(batch, group_columns, aggregates,
                                 out_schema, mesh, n_shards, reg,
                                 pre_sharded=None):
    import jax.numpy as jnp
    import time as _time

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.ops.keys import column_sort_lanes

    if pre_sharded is not None:
        # Born-sharded input: already resident under the canonical row
        # sharding with its own validity mask — zero placement work, and
        # the representative-row gather below indexes the SAME padded
        # layout (first_perm's shard-local positions are global
        # s*C + i here too).
        sharded, row_valid = pre_sharded
        batch = sharded
    else:
        sharded, row_valid = shard_batch(batch, mesh)

    tree = {"valid": row_valid}
    lane_cols: List = []
    for name in group_columns:
        lane_cols.extend(column_sort_lanes(sharded.column(name)))
    for i, lane in enumerate(lane_cols):
        tree[f"lane{i}"] = lane
    specs_meta = []
    for j, spec in enumerate(aggregates):
        if spec.func == "count" and spec.column == "*":
            specs_meta.append(("count_star", False))
            continue
        col = sharded.column(spec.column)
        if col.is_string and spec.func != "count":
            raise HyperspaceException(
                f"Aggregate {spec.func} over string column {spec.column}")
        specs_meta.append((spec.func, col.validity is not None))
        tree[f"v{j}"] = col.data
        tree[f"m{j}"] = (col.validity if col.validity is not None
                         else jnp.ones(col.data.shape[0], dtype=bool))

    local = row_valid.shape[0] // n_shards
    capacity = max(64, min(local, 1 << 14))
    while True:
        step = make_partial_step(mesh, len(lane_cols), tuple(specs_meta),
                                 capacity)
        out = step(tree)
        t0 = _time.perf_counter()
        overflowed = int(np.asarray(out["overflow"]).sum())  # host sync
        sync_s = _time.perf_counter() - t0
        reg.counter("mesh.aggregate.sync_s").inc(sync_s)
        telemetry.add_seconds("mesh.sync_s", sync_s)
        if overflowed == 0:
            break
        reg.counter("mesh.aggregate.overflow_retries").inc()
        capacity *= 2  # exact recovery: rerun wider

    return _combine_partials(batch, out, group_columns, aggregates,
                             specs_meta, out_schema, len(lane_cols),
                             n_shards, capacity, sharded, row_valid)


def _combine_partials(batch, out, group_columns, aggregates, specs_meta,
                      out_schema, num_lanes, n_shards, capacity,
                      sharded, row_valid):
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.ops.keys import host_dense_group_ids

    # The [n_shards, G] partial tables cross here, once: accounted as
    # the link crossing they are (the host combine reads numpy below).
    tables = {k: v for k, v in out.items() if k != "overflow"}
    with telemetry.link_transfer(
            "d2h", sum(int(v.nbytes) for v in tables.values())):
        out = {k: np.asarray(v) for k, v in tables.items()}
    rows = out["rows"].reshape(-1)
    used = rows > 0  # empty slots carry no group
    keys = [np.asarray(out[f"key{i}"]).reshape(-1)[used]
            for i in range(num_lanes)]
    perm, seg_sorted = host_dense_group_ids(keys)
    order = perm
    seg = seg_sorted
    num_groups = int(seg[-1]) + 1 if len(seg) else 0
    starts = np.searchsorted(seg, np.arange(num_groups), side="left")

    def fold(name, default=0.0):
        vals = np.asarray(out[name]).reshape(-1)[used][order]
        return vals, starts

    # Representative original row per group (for the group-key VALUES):
    # first_perm holds, per slot, the LOCAL sorted position's original
    # global row index — valid because shard_batch row-shards the global
    # arrays in order, so shard s's local index i is global s*local + i.
    first_perm = np.asarray(out["first_perm"]).reshape(n_shards, capacity)
    local = row_valid.shape[0] // n_shards
    first_global = (first_perm
                    + (np.arange(n_shards, dtype=np.int64)[:, None] * local))
    first_global = first_global.reshape(-1)[used][order]
    group_first = first_global[starts]

    import jax.numpy as jnp
    rep = batch.take(jnp.asarray(np.minimum(group_first,
                                            batch.num_rows - 1)
                                 .astype(np.int32)))

    columns = {}
    for name in group_columns:
        src = rep.column(name)
        f = batch.schema.field(name)
        columns[f.name] = DeviceColumn(
            data=fetched(np.asarray(src.raw), src.dtype), dtype=src.dtype,
            validity=(np.asarray(src.validity)
                      if src.validity is not None else None),
            dictionary=src.dictionary, dict_hashes=src.dict_hashes)

    from hyperspace_tpu.io.columnar import HOST_NP_DTYPES as _HOST_NP
    rows_sorted = rows[used][order]
    for j, spec in enumerate(aggregates):
        out_field = out_schema.field(spec.alias)
        if specs_meta[j][0] == "count_star":
            data = np.add.reduceat(rows_sorted, starts).astype(np.int64)
            columns[out_field.name] = DeviceColumn(data, "int64")
            continue
        cnt, _ = fold(f"cnt{j}")
        cnt_tot = np.add.reduceat(cnt, starts).astype(np.int64)
        if spec.func == "count":
            columns[out_field.name] = DeviceColumn(cnt_tot, "int64")
            continue
        validity_out = cnt_tot > 0
        safe_cnt = np.maximum(cnt_tot.astype(np.float64), 1)
        if spec.func in ("sum", "avg"):
            s1, _ = fold(f"s1{j}")
            s1_tot = np.add.reduceat(s1, starts)
            data = (s1_tot if spec.func == "sum"
                    else s1_tot / safe_cnt)
        elif spec.func == "min":
            mn, _ = fold(f"mn{j}")
            data = np.minimum.reduceat(mn, starts)
        elif spec.func == "max":
            mx, _ = fold(f"mx{j}")
            data = np.maximum.reduceat(mx, starts)
        else:  # stddev: exact variance decomposition around the global mean
            s1, _ = fold(f"s1{j}")
            m2, _ = fold(f"m2{j}")
            s1_tot = np.add.reduceat(s1, starts)
            anchor = s1_tot / safe_cnt
            cnt_f = cnt.astype(np.float64)
            shard_mean = np.divide(s1, np.maximum(cnt_f, 1))
            shift = cnt_f * (shard_mean
                             - np.repeat(anchor, np.diff(
                                 np.append(starts, len(s1))))) ** 2
            m2_tot = np.add.reduceat(m2 + shift, starts)
            data = np.sqrt(np.maximum(
                m2_tot / np.maximum(safe_cnt - 1, 1), 0.0))
            validity_out = cnt_tot > 1
        columns[out_field.name] = DeviceColumn(
            data.astype(_HOST_NP[out_field.dtype]), out_field.dtype,
            validity=validity_out)
    return ColumnBatch(out_schema, columns)
