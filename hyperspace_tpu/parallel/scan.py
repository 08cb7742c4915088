"""Mesh-sharded predicate scan.

Reference rationale: `FilterIndexRule.scala:112-120` replaces the relation
with NO BucketSpec precisely so the engine parallelizes the scan freely —
the filter path's parallelism axis is rows, not buckets (SURVEY §2.12 row
4). Here rows are sharded over the mesh and the compiled predicate runs
SPMD: each chip evaluates the mask over its shard; only the compaction
gather crosses chips.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from hyperspace_tpu import telemetry
from hyperspace_tpu.io.columnar import ColumnBatch, DeviceColumn
from hyperspace_tpu.parallel.mesh import shard_rows, total_shards


def shard_batch(batch: ColumnBatch, mesh):
    """Pad rows to a multiple of the mesh size and place every column
    row-sharded. Returns (sharded batch, row_valid mask) — padding rows are
    marked invalid and must be excluded by the caller.

    Host-resident columns pad in numpy and cross the link through the
    transfer engine (each device pulls only its slice of the sharded
    put; every column's put is issued before the first block); device
    columns only re-lay out."""
    import jax.numpy as jnp

    from hyperspace_tpu.io import transfer

    n = batch.num_rows
    n_shards = total_shards(mesh)
    padded = -(-n // n_shards) * n_shards
    pad = padded - n
    sharding = shard_rows(mesh)
    engine = transfer.get_engine()

    def place(arr, fill):
        if isinstance(arr, np.ndarray):
            if pad:
                arr = np.concatenate(
                    [arr, np.full((pad,) + arr.shape[1:], fill,
                                  arr.dtype)])
            return engine.put(arr, device=sharding)
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)])
        return engine.put(arr, device=sharding)

    # The engine records each host column's link crossing; the span
    # keeps the placement visible as one mesh stage in traces.
    with telemetry.span("hs.mesh.place", "mesh", rows=n, shards=n_shards):
        columns: Dict[str, DeviceColumn] = {}
        for name, col in batch.columns.items():
            columns[name] = col.with_raw(
                place(col.carry, 0),
                (place(col.validity, False)
                 if col.validity is not None else None))
        # made on the device: the mask is no payload to send over the link
        row_valid = engine.put(jnp.arange(padded, dtype=jnp.int32) < n,
                               device=sharding)
    return ColumnBatch(batch.schema, columns), row_valid


def distributed_filter(batch: ColumnBatch, expression, mesh) -> ColumnBatch:
    """Filter `batch` on the mesh; result equals the single-chip
    `engine.compiler.apply_filter` bit for bit. The predicate (the FLOPs)
    runs shard-locally; the compaction gather is the only cross-chip step."""
    import jax.numpy as jnp

    from hyperspace_tpu.engine.compiler import compile_predicate

    n_shards = total_shards(mesh)
    reg = telemetry.get_registry()
    with telemetry.span("hs.mesh.filter", "mesh", rows=batch.num_rows,
                        shards=n_shards):
        sharded, row_valid = shard_batch(batch, mesh)
        mask = compile_predicate(expression, sharded) & row_valid
        t0 = time.perf_counter()
        count = int(jnp.sum(mask))  # host sync — sizes the output
        sync_s = time.perf_counter() - t0
        reg.counter("mesh.filter.execs").inc()
        reg.counter("mesh.filter.sync_s").inc(sync_s)
        telemetry.add_seconds("mesh.sync_s", sync_s)
        telemetry.event("mesh", "filter", shards=n_shards,
                        rows=batch.num_rows, selected=count)
        (indices,) = jnp.nonzero(mask, size=count, fill_value=0)
        return sharded.take(indices)
