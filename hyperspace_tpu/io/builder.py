"""The index build pipeline — the framework's hot data path.

Reference equivalent: `CreateActionBase.write` =
`df.select(indexed++included).repartition(numBuckets, indexedCols)
.write.saveWithBuckets(...)` (`actions/CreateActionBase.scala:99-120`,
`index/DataFrameWriterExtensions.scala:49-78`) — a distributed JVM shuffle +
per-bucket sort + parquet encode.

TPU-native pipeline (single device; the mesh-sharded variant lives in
`parallel/build.py`):
1. execute the source plan projected to indexed+included columns ->
   HBM-resident ColumnBatch;
2. murmur-mix bucket ids on device (`ops/hash_partition.py`);
3. ONE stable `lax.sort` keyed (bucket_id, *indexed columns) — this both
   groups rows by bucket and sorts within buckets in a single XLA sort
   (the reference needs a shuffle THEN a per-bucket sort);
4. bucket boundaries via two searchsorted calls;
5. slice per bucket -> Arrow -> one parquet file per bucket.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence

import numpy as np

import hyperspace_tpu.engine  # noqa: F401  (x64 config)
from hyperspace_tpu import telemetry
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io import columnar, parquet
from hyperspace_tpu.plan.nodes import BucketSpec


def _write_file(table, out: str) -> None:
    """One bucket file's encode + write, on the writer thread."""
    with telemetry.span("hs.build.write.file", "build",
                        rows=table.num_rows):
        parquet.write_table(table, out)


def _write_sorted_runs(table, perm_chunks, starts, ends, path: str,
                       file_suffix: Optional[str]) -> List[str]:
    """Apply the device-computed permutation chunk by chunk on the host and
    stream bucket files out — the tail of the pipelined build.

    `perm_chunks` are contiguous slices of the global (bucket, *keys) sort
    permutation, still device-resident: their D2H copies are issued
    asynchronously up front (transfer-engine prefetch; failures land in
    `link.d2h.prefetch_errors` instead of silently degrading to the
    serial path), so chunk i+1 is in flight over the link while chunk i
    is being gathered (Arrow `take`) and parquet-encoded — and chunk i's
    parquet ENCODE runs on the writer thread while chunk i+1's fetch +
    gather proceed (one chunk of write depth, so fault/ordering
    semantics stay deterministic). A bucket whose rows span a chunk
    boundary is written as multiple run files (`part-NNNNN-cKK.parquet`);
    runs are contiguous in sort order, so their name-ordered
    concatenation stays fully sorted — the same multi-run layout the
    incremental-refresh deltas already use.
    """
    with telemetry.span("hs.build.write", "build",
                        rows=table.num_rows) as sp:
        written = _write_runs(table, perm_chunks, starts, ends, path,
                              file_suffix)
        sp.set(files=len(written))
    return written


def _write_runs(table, perm_chunks, starts, ends, path: str,
                file_suffix: Optional[str]) -> List[str]:
    import pyarrow as pa

    from hyperspace_tpu.io import transfer

    engine = transfer.get_engine()
    # Order matters: issue every chunk's DMA before the first blocking
    # fetch (starts/ends below) so the transfers run during the
    # device-sort sync instead of after it.
    engine.prefetch(*perm_chunks)
    starts, ends = np.asarray(starts), np.asarray(ends)
    written: List[str] = []
    from hyperspace_tpu.utils import file_utils
    file_utils.create_directory(path)
    multi = len(perm_chunks) > 1
    offset = 0
    pending: List = []  # last chunk's in-flight write futures

    def drain():
        for fut in pending:
            fut.result()
        pending.clear()

    # The writer thread's spans carry the caller's query identifier.
    write_file = telemetry.propagating(_write_file)
    try:
        for ci, chunk in enumerate(perm_chunks):
            # Chunk-boundary cancellation checkpoint: a cancelled query
            # (or a deadline-capped maintenance caller) stops WITHOUT
            # queueing further writes — the finally drain below leaves
            # already-submitted files landed, same partial-dir story
            # the `_committed` marker already makes crash-safe.
            telemetry.check_deadline("write")
            # Device-resident permutation chunk: engine.fetch IS the D2H
            # link crossing (the async prefetch above may have already
            # landed it — the histogram then shows a near-zero wall for
            # the same bytes, which is the overlap working).
            perm_np = engine.fetch(chunk)
            m = len(perm_np)
            if m == 0:
                continue
            chunk_table = table.take(pa.array(perm_np))
            # Previous chunk's encodes must land before this chunk's are
            # queued: single-writer FIFO keeps write (and injected
            # fault) order identical to the serial path.
            drain()
            # Buckets intersecting sorted-row range [offset, offset + m).
            b_lo = int(np.searchsorted(ends, offset, side="right"))
            b_hi = int(np.searchsorted(starts, offset + m, side="left"))
            for b in range(b_lo, b_hi):
                s = max(int(starts[b]), offset)
                e = min(int(ends[b]), offset + m)
                if e <= s:
                    continue  # empty bucket -> no file (Spark parity)
                suffix = file_suffix
                if multi and (int(starts[b]) < offset
                              or int(ends[b]) > offset + m):
                    # Partial run of a chunk-spanning bucket: unique,
                    # ordered name.
                    suffix = f"{file_suffix or ''}c{ci:02d}"
                out = os.path.join(path, parquet.bucket_file_name(b, suffix))
                pending.append(_writer_pool().submit(
                    write_file, chunk_table.slice(s - offset, e - s), out))
                written.append(out)
            offset += m
    finally:
        drain()
    return written


# Single-worker writer behind `_write_sorted_runs`: ONE lane keeps file
# writes (and injected write faults) in deterministic submission order
# while still overlapping chunk i's parquet encode with chunk i+1's
# permutation fetch + Arrow gather. Lazy module-level pool — a
# per-build executor would churn a thread per maintenance action.
_writer = None
_writer_lock = threading.Lock()


def _writer_pool():
    global _writer
    if _writer is None:
        with _writer_lock:
            if _writer is None:
                from concurrent.futures import ThreadPoolExecutor
                _writer = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="hs-bucket-writer")
    return _writer


def shutdown_writer_pool(wait: bool = True) -> None:
    """Drain + stop the single-lane bucket writer (idempotent, lazily
    re-created; atexit hook — a queued parquet encode must land before
    interpreter teardown, the build already returned its file list)."""
    global _writer
    with _writer_lock:
        pool, _writer = _writer, None
    if pool is not None:
        pool.shutdown(wait=wait)


import atexit as _atexit  # noqa: E402

_atexit.register(shutdown_writer_pool)


# Below this row count the build permutation is computed on the host
# (numpy): a novel table shape costs a fresh XLA compile (~tens of
# seconds) that small builds can never amortize, and warm device builds
# only overtake host lexsort in the ~1M-row range. Bucket assignment uses
# the host mirror of THE hash identity, so the on-disk layout is
# indistinguishable from a device build.
BUILD_MIN_DEVICE_ROWS = 1_000_000


def build_lane(rows: int) -> str:
    """Which permutation engine a HOST-resident build of `rows` rows
    takes: "native-host" (C++ radix — no device link traffic,
    link-independent cost), "host-lexsort" (small build; an XLA compile
    could never amortize), or "device" (no native library and the size
    justifies the on-chip sort). THE routing predicate.
    Device/mesh-resident batches are routed by residency before this is
    consulted (`write_bucketed_batch`, `parallel/build.py`). Above 2^31
    rows the native lane's int32 permutation would wrap
    (`native.bucket_key_sort_perm` declines), so sizing routes to the
    int64-permutation lanes instead."""
    from hyperspace_tpu import native
    if rows < BUILD_MIN_DEVICE_ROWS:
        return "host-lexsort"
    if rows < 1 << 31 and native.get_lib() is not None:
        return "native-host"
    return "device"


def _host_lane_preferred(rows: int) -> bool:
    return build_lane(rows) != "device"


def _host_build_permutation(table, names: Sequence[str], num_buckets: int):
    """Host (bucket, *keys) stable sort permutation + bucket boundaries,
    mirroring the device program's layout semantics. The sort itself runs
    in the native C++ radix lane (`native.bucket_key_sort_perm`) when the
    library is available — no device link traffic, ~radix-speed on the
    1-core host — with np.lexsort as the always-correct fallback."""
    from hyperspace_tpu import native
    from hyperspace_tpu.ops.host_hash import (host_column_hash_lanes,
                                              host_flat_hash32)
    from hyperspace_tpu.ops.keys import host_column_sort_lanes

    batch = columnar.from_arrow(table.select(names), device=False)
    hash_lanes: List = []
    for name in names:
        hash_lanes.extend(host_column_hash_lanes(batch.column(name)))
    bucket = (host_flat_hash32(hash_lanes)
              % np.uint32(num_buckets)).astype(np.int32)
    sort_lanes: List = []
    for name in names:
        sort_lanes.extend(host_column_sort_lanes(batch.column(name)))
    nat = native.bucket_key_sort_perm(bucket, num_buckets, sort_lanes)
    if nat is not None:
        perm, starts, ends = nat
        return [perm], starts, ends
    perm = np.lexsort(tuple(reversed([bucket] + sort_lanes)))
    sorted_bucket = bucket[perm]
    starts = np.searchsorted(sorted_bucket, np.arange(num_buckets), "left")
    ends = np.searchsorted(sorted_bucket, np.arange(num_buckets), "right")
    return [perm.astype(np.int64)], starts, ends


def _stage_key_tree(table, names: Sequence[str]):
    """Stage the key columns of a host Arrow table as a device key tree
    for `ops.build.permutation_from_tree`, with narrow transport: a
    null-free int64 column whose values fit uint32 (host range check over
    data already in cache) ships HALF the bytes as a single `lo32` lane —
    hash identity and sort order are unchanged (`ops/build.py`). All H2D
    rides the pipelined transfer engine: the narrow lane ships as
    byte-budgeted chunks (whether several concurrent streams beat one
    big transfer is unmeasured on an attached chip; the compiled program
    concatenates —
    `_entry_assemble`), cast into reused staging buffers instead of a
    fresh `astype` materialisation per column."""
    import pyarrow as pa

    from hyperspace_tpu.io import transfer

    engine = transfer.get_engine()
    tree = {}
    wide = []
    for name in names:
        arr = table.column(name)
        chunk = (arr.combine_chunks() if hasattr(arr, "combine_chunks")
                 else arr)
        if pa.types.is_int64(chunk.type) and chunk.null_count == 0:
            vals = chunk.to_numpy(zero_copy_only=False)
            if len(vals) and vals.min() >= 0 and vals.max() < 1 << 32:
                parts = engine.put_chunks(
                    transfer.HostCast(vals, np.uint32))
                if len(parts) > 1:
                    tree[name] = {"lo32_chunks": parts}
                else:
                    tree[name] = {"lo32": parts[0]}
                continue
        wide.append(name)
    if wide:
        batch = columnar.from_arrow(table.select(wide))
        staged, _aux = columnar.batch_to_tree(batch)
        tree.update(staged)
    return tree


def write_bucketed_table(table, indexed_columns: Sequence[str],
                         num_buckets: int, path: str,
                         file_suffix: Optional[str] = None,
                         key_batch: Optional[columnar.ColumnBatch] = None
                         ) -> List[str]:
    """Bucketed build from a HOST Arrow table: only the key columns touch
    the device (hash + sort -> permutation); payload rows never cross the
    link. `key_batch` may pass an already-staged device batch containing
    the key columns (any extra columns are ignored)."""
    from hyperspace_tpu.ops.build import (build_permutation,
                                          permutation_from_tree)

    if table.num_rows == 0:
        from hyperspace_tpu.utils import file_utils
        file_utils.create_directory(path)
        return []
    if key_batch is None:
        by_lower = {n.lower(): n for n in table.column_names}
        missing = [c for c in indexed_columns if c.lower() not in by_lower]
        if missing:
            raise HyperspaceException(
                f"Column not found in table: {', '.join(missing)}")
        names = [by_lower[c.lower()] for c in indexed_columns]
        with telemetry.span("hs.build.sort", "build", rows=table.num_rows,
                            lane=build_lane(table.num_rows)):
            if _host_lane_preferred(table.num_rows):
                chunks, starts, ends = _host_build_permutation(
                    table, names, num_buckets)
            else:
                tree = _stage_key_tree(table, names)
                chunks, starts, ends = permutation_from_tree(
                    tree, names, table.num_rows, num_buckets)
    else:
        if key_batch.num_rows != table.num_rows:
            raise HyperspaceException(
                f"key_batch rows ({key_batch.num_rows}) != table rows "
                f"({table.num_rows}); the permutation would silently drop "
                f"rows.")
        with telemetry.span("hs.build.sort", "build",
                            rows=table.num_rows, lane="device"):
            chunks, starts, ends = build_permutation(
                key_batch, indexed_columns, num_buckets)
    return _write_sorted_runs(table, chunks, starts, ends, path, file_suffix)


def write_bucketed_from_files(files: Sequence[str],
                              column_names: Sequence[str],
                              key_names: Sequence[str], num_buckets: int,
                              path: str, lineage_ids=None,
                              file_suffix: Optional[str] = None
                              ) -> List[str]:
    """PIPELINED build straight from parquet files (the plain-scan create
    path): the payload-column decode is kicked off on a background
    thread FIRST, then the key columns decode, stage over the link
    (chunked H2D through the transfer engine), and the device
    permutation dispatches (async — jax returns before the sort
    finishes). Payload decode thus overlaps key decode + key H2D +
    device sort, and `_write_sorted_runs` overlaps perm D2H, Arrow
    gather, and parquet encode — every stage of
    decode -> stage -> sort -> fetch -> take -> write has a partner to
    hide behind. Below the device-amortization row count this degrades
    to the single-read host path."""
    import pyarrow as pa

    from hyperspace_tpu.ops.build import permutation_from_tree

    n = sum(parquet.file_row_counts(files))  # footers only, no decode
    if _host_lane_preferred(n):
        with telemetry.span("hs.build.read", "build", files=len(files),
                            rows=n):
            table = parquet.read_table(files, columns=list(column_names))
            if lineage_ids is not None:
                table = append_lineage_column(table, files, lineage_ids)
        return write_bucketed_table(table, list(key_names), num_buckets,
                                    path, file_suffix=file_suffix)
    payload_names = [c for c in column_names if c not in key_names]
    payload: dict = {}
    payload_thread = None
    if payload_names:
        # Decoded while the keys decode/stage and the device sorts
        # (pyarrow releases the GIL for the column decode).
        def _decode_payload():
            try:
                with telemetry.span("hs.build.read", "build",
                                    files=len(files), rows=n,
                                    part="payload"):
                    payload["table"] = parquet.read_table(
                        files, columns=payload_names)
            except BaseException as exc:  # surfaces at join below
                payload["error"] = exc

        payload_thread = threading.Thread(
            target=telemetry.propagating(_decode_payload),
            name="hs-payload-decode", daemon=True)
        payload_thread.start()
    with telemetry.span("hs.build.read", "build", files=len(files),
                        rows=n, part="keys"):
        key_table = parquet.read_table(files, columns=list(key_names))
    with telemetry.span("hs.build.sort", "build", rows=n, lane="device"):
        tree = _stage_key_tree(key_table, key_names)
        chunks, starts, ends = permutation_from_tree(tree, key_names, n,
                                                     num_buckets)
    if payload_thread is not None:
        payload_thread.join()
        if "error" in payload:
            raise payload["error"]
        ptable = payload["table"]
        table = pa.table({c: (key_table.column(c) if c in key_names
                              else ptable.column(c))
                          for c in column_names})
    else:
        table = key_table.select(list(column_names))
    if lineage_ids is not None:
        table = append_lineage_column(table, files, lineage_ids)
    return _write_sorted_runs(table, chunks, starts, ends, path,
                              file_suffix)


def write_bucketed_batch(batch: columnar.ColumnBatch,
                         indexed_columns: Sequence[str],
                         num_buckets: int, path: str,
                         file_suffix: Optional[str] = None) -> List[str]:
    """Bucketed build from a DEVICE-resident batch (post-filter/plan data).

    The permutation program and the unsorted payload's D2H copies are
    dispatched together so the payload transfer overlaps the device sort;
    the permutation is then applied host-side per chunk. This replaces the
    old device payload gather + sorted-payload transfer, which serialized
    the big D2H behind the sort."""
    from hyperspace_tpu.ops.build import build_permutation

    if batch.num_rows == 0:
        from hyperspace_tpu.utils import file_utils
        file_utils.create_directory(path)
        return []
    with telemetry.span("hs.build.sort", "build", rows=batch.num_rows,
                        lane="device"):
        chunks, starts, ends = build_permutation(batch, indexed_columns,
                                                 num_buckets)
    table = columnar.to_arrow(batch)  # async copies overlap the sort
    return _write_sorted_runs(table, chunks, starts, ends, path, file_suffix)


def _plain_scan_source(plan) -> Optional[tuple]:
    """If the plan is just Project*(Scan) — the shape CreateAction.validate
    admits (reference `CreateAction.scala:42-62`) — return (files, scan
    schema); else None. Lets the build read payload straight from parquet
    on the host instead of round-tripping every column through HBM."""
    from hyperspace_tpu.plan.nodes import Project, Scan

    node = plan
    while isinstance(node, Project):
        node = node.child
    if isinstance(node, Scan) and node.bucket_spec is None:
        files = node.files()
        if files:
            return files, node.schema
    return None


SHARD_LAYOUT_FILE = "_shard_layout.json"


def write_shard_layout(path: str, num_buckets: int, n_shards: int,
                       dictionaries=None, n_slices: int = 1) -> dict:
    """Persist the born-sharded layout record next to the bucket spec:
    which contiguous bucket range each device shard owns (THE map,
    `parallel/mesh.bucket_ranges`) and — for string columns — each
    range's sorted local dictionary (`dictionaries`: {column: [values
    per shard | None]}; None marks a range past the
    `distribution.dictionary.max.entries` cap, which the reader derives
    from parquet instead). Version 3 records the (slice, device)
    HIERARCHY of multi-slice builds: `numSlices` and the slice-level
    `sliceBucketRanges` (which nest exactly over the flat shard map,
    `parallel/mesh.slice_bucket_ranges`), so a reader can route
    per-slice replica fills or cross-slice repartitions without
    rederiving the topology; a flat build records the degenerate
    1-slice hierarchy. `stamp_stats` lifts the record (dictionaries
    summarized to entry counts) into the index log entry so a reader
    knows the build's shard shape without walking the data dir."""
    import json

    from hyperspace_tpu.parallel.mesh import (bucket_ranges,
                                              slice_bucket_ranges)
    from hyperspace_tpu.utils import file_utils, storage

    n_slices = max(1, int(n_slices))
    layout = {
        "version": 3,
        "numBuckets": num_buckets,
        "numShards": n_shards,
        "numSlices": n_slices,
        "bucketRanges": [[lo, hi]
                         for lo, hi in bucket_ranges(num_buckets,
                                                     n_shards)],
        "sliceBucketRanges": [
            [lo, hi] for lo, hi in slice_bucket_ranges(
                num_buckets, n_slices, n_shards // n_slices)],
    }
    if dictionaries:
        layout["dictionaries"] = dictionaries
    file_utils.create_file(storage.join(path, SHARD_LAYOUT_FILE),
                           json.dumps(layout, indent=2))
    return layout


def summarize_shard_layout(layout):
    """The log-entry form of a shard-layout record: per-range
    dictionary VALUES stay in `_shard_layout.json` (they can be large);
    the entry carries only per-range entry COUNTS (-1 = over-cap range
    recorded as null)."""
    if not layout or "dictionaries" not in layout:
        return layout
    out = dict(layout)
    out["dictionaryEntries"] = {
        col: [len(r) if r is not None else -1 for r in ranges]
        for col, ranges in layout["dictionaries"].items()}
    del out["dictionaries"]
    return out


def _range_dictionaries(table, schema, lengths, num_buckets: int,
                        n_shards: int, max_entries: int):
    """{string column: [sorted per-range value list | None]} over the
    bucket-ordered arrow table — the build-time half of the born-sharded
    string story: each device range's dictionary recorded so query-time
    global resolution is pure JSON. A range whose distinct count
    exceeds `max_entries` records None (reader falls back to the
    files)."""
    import numpy as np

    from hyperspace_tpu.parallel.mesh import (bucket_ranges,
                                              shard_row_segments)

    str_fields = [f.name for f in schema.fields if f.dtype == "string"]
    if not str_fields or max_entries <= 0:
        return None
    segs = shard_row_segments(np.asarray(lengths, dtype=np.int64),
                              n_shards)
    out = {}
    for name in str_fields:
        col = table.column(name)
        ranges = []
        for lo, hi in segs:
            chunk = col.slice(lo, hi - lo).drop_null()
            values = np.unique(np.asarray(
                chunk.to_numpy(zero_copy_only=False), dtype=str))
            ranges.append([str(v) for v in values]
                          if len(values) <= max_entries else None)
        out[name] = ranges
    return out


def read_shard_layout(path: str) -> Optional[dict]:
    """The layout record of a born-sharded version dir, or None for a
    single-device build."""
    import json

    from hyperspace_tpu.utils import file_utils, storage

    p = storage.join(path, SHARD_LAYOUT_FILE)
    if not file_utils.exists(p):
        return None
    try:
        return json.loads(file_utils.read_contents(p))
    except (ValueError, OSError):
        return None


def write_bucket_ordered(batch: columnar.ColumnBatch, lengths,
                         num_buckets: int, path: str,
                         file_suffix: Optional[str] = None,
                         mesh=None,
                         dict_max_entries: Optional[int] = None
                         ) -> List[str]:
    """Write a batch already concatenated in bucket order (the distributed
    build's output shape) as bucketed parquet files.

    With `mesh`, the index is BORN SHARDED: each flat shard's contiguous
    bucket range writes as that device's parquet shard — files carry the
    owning shard in their suffix (`part-00003-s01.parquet`), the
    `_shard_layout.json` record pins the range map PLUS each range's
    sorted local string dictionaries (capped per
    `distribution.dictionary.max.entries`; the query-time global
    dictionary then resolves from pure JSON), and because ownership is
    contiguous, shard s's files are exactly the rows its device held
    after the build exchange (and exactly what its device re-fills on a
    born-sharded read)."""
    table = columnar.to_arrow(batch)
    written: List[str] = []
    from hyperspace_tpu.utils import file_utils
    file_utils.create_directory(path)

    def write_range(bucket_lo: int, bucket_hi: int, offset: int,
                    suffix: Optional[str]) -> int:
        for b in range(bucket_lo, bucket_hi):
            count = int(lengths[b])
            if count > 0:
                out = os.path.join(path, parquet.bucket_file_name(b,
                                                                  suffix))
                parquet.write_table(table.slice(offset, count), out)
                written.append(out)
            offset += count
        return offset

    if mesh is None:
        write_range(0, num_buckets, 0, file_suffix)
        return written

    from hyperspace_tpu.parallel.mesh import (bucket_ranges, dcn_size,
                                              total_shards)

    n_shards = total_shards(mesh)
    offset = 0
    for s, (lo, hi) in enumerate(bucket_ranges(num_buckets, n_shards)):
        suffix = f"{file_suffix or ''}s{s:02d}"
        offset = write_range(lo, hi, offset, suffix)
    from hyperspace_tpu.constants import (
        DISTRIBUTION_DICT_MAX_ENTRIES_DEFAULT)
    cap = (dict_max_entries if dict_max_entries is not None
           else DISTRIBUTION_DICT_MAX_ENTRIES_DEFAULT)
    dictionaries = _range_dictionaries(table, batch.schema, lengths,
                                       num_buckets, n_shards, cap)
    write_shard_layout(path, num_buckets, n_shards,
                       dictionaries=dictionaries,
                       n_slices=dcn_size(mesh))
    return written


def lineage_schema(schema):
    """`schema` extended with the non-nullable int64 lineage column.
    Paired with `append_lineage_column` (below) so the LOGGED index schema
    and the WRITTEN data can never disagree on the column's shape."""
    from hyperspace_tpu.constants import LINEAGE_COLUMN
    from hyperspace_tpu.plan.schema import Field, Schema

    return Schema(list(schema.fields)
                  + [Field(LINEAGE_COLUMN, "int64", False)])


def append_lineage_column(table, files: Sequence[str], lineage_ids: dict):
    """Append the per-row `_hs_file_id` column to an Arrow table read by
    concatenating `files` in order: rows from file F carry lineage_ids[F].
    THE one materialization of row lineage — create, full refresh, and
    incremental refresh all route through it, so id-to-row assignment can
    never diverge between build paths."""
    import pyarrow as pa

    from hyperspace_tpu.constants import LINEAGE_COLUMN

    counts = parquet.file_row_counts(files)
    col = np.repeat(np.asarray([lineage_ids[f] for f in files],
                               dtype=np.int64), counts)
    return table.append_column(LINEAGE_COLUMN,
                               pa.array(col, type=pa.int64()))


def write_index(df, indexed_columns: Sequence[str],
                included_columns: Sequence[str], num_buckets: int,
                path: str, conf=None, lineage_ids=None) -> List[str]:
    """THE index build job (reference `CreateActionBase.scala:99-120`).

    With a multi-device mesh active (`parallel/context.py`) the build runs
    the mesh-sharded all_to_all pipeline — the reference's cluster-wide
    `repartition(numBuckets, indexedCols)` shuffle
    (`CreateActionBase.scala:110-111`) expressed as XLA collectives.

    `lineage_ids` ({source file path: id}, lineage-enabled builds) appends
    the per-row `_hs_file_id` column: rows read from file F carry
    lineage_ids[F]. Payload-only — bucket hash and sort keys are untouched.
    """
    from hyperspace_tpu.engine.executor import execute_plan
    from hyperspace_tpu.io import transfer
    from hyperspace_tpu.parallel.context import should_distribute

    transfer.configure(conf)  # session knobs -> process engine

    def build_distributed(mesh, batch):
        from hyperspace_tpu.parallel.build import distributed_build

        built, lengths = distributed_build(batch, indexed_columns,
                                           num_buckets, mesh)
        # Born sharded: per-device parquet shards over the contiguous
        # bucket-range map, with the layout record (incl. per-range
        # string dictionaries) next to the bucket spec (lifted into the
        # log entry by `stamp_stats`).
        return write_bucket_ordered(
            built, lengths, num_buckets, path, mesh=mesh,
            dict_max_entries=(conf.distribution_dict_max_entries
                              if conf is not None else None))

    columns = list(indexed_columns) + list(included_columns)
    source = _plain_scan_source(df.plan)
    if source is None and lineage_ids is not None:
        # CreateAction.validate admits only plain file scans, so this is a
        # programming error, not a user-reachable state.
        raise HyperspaceException(
            "Lineage requires a plain file-scan source.")
    if source is not None:
        files, scan_schema = source
        names = [scan_schema.field(c).name for c in columns]
        key_names = [scan_schema.field(c).name for c in indexed_columns]
        schema = scan_schema.select(columns)
        if lineage_ids is not None:
            schema = lineage_schema(schema)
        rows = sum(parquet.file_row_counts(files))  # footers only
        mesh = should_distribute(conf, rows)
        if mesh is not None:
            with telemetry.span("hs.build.read", "build",
                                files=len(files), rows=rows):
                table = parquet.read_table(files, columns=names)
                if lineage_ids is not None:
                    table = append_lineage_column(table, files,
                                                  lineage_ids)
            # Host batch: `distributed_build` places each device's shard
            # straight from host memory (concurrent sharded puts through
            # the transfer engine) instead of round-tripping the whole
            # table through the default device first.
            written = build_distributed(
                mesh, columnar.from_arrow(table, schema, device=False))
        else:
            # Pipelined: key decode -> async device sort -> payload
            # decode overlapping the sort -> streamed bucket writes.
            written = write_bucketed_from_files(
                files, names, key_names, num_buckets, path,
                lineage_ids=lineage_ids)
    else:
        batch = execute_plan(df.plan, projection=columns, conf=conf)
        schema = batch.schema
        mesh = should_distribute(conf, batch.num_rows)
        if mesh is not None:
            written = build_distributed(mesh, batch)
        else:
            written = write_bucketed_batch(batch, indexed_columns,
                                           num_buckets, path)
    spec = BucketSpec(num_buckets, tuple(indexed_columns),
                      tuple(indexed_columns))
    parquet.write_bucket_spec(path, spec, schema)
    return written


_MERGE_KEY_DTYPES = ("int64", "int32", "int16", "int8", "date32",
                     "timestamp", "bool")


def _merge_path_permutation(table, ordered, counts, names, schema,
                            num_buckets):
    """The compaction fast path: single null-free integer key -> a TRUE
    merge of each bucket's sorted runs (no re-sort of the base run,
    `ops/merge.host_merge_runs_permutation`). None when the shape doesn't
    qualify (multi-key, strings, floats — float lane order differs from
    raw order — or a nullable key); callers fall back to the batched
    sort."""
    if len(names) != 1 or schema.field(names[0]).dtype not in \
            _MERGE_KEY_DTYPES:
        return None
    col = table.column(names[0])
    if col.null_count:
        return None
    from hyperspace_tpu.ops.merge import host_merge_runs_permutation
    key = col.to_numpy(zero_copy_only=False)
    # run_bounds indexed by BUCKET ID (empty list for absent buckets) so
    # the writer's starts/ends line up with bucket file numbering.
    run_bounds = [[] for _ in range(num_buckets)]
    offset = 0
    for (b, _), c in zip(ordered, counts):
        run_bounds[b].append((offset, offset + c))
        offset += c
    return host_merge_runs_permutation(key, run_bounds)


def compact_index(prev_entry, data_manager, out_path: str) -> List[str]:
    """Merge-compact the current data version's runs (base + incremental
    delta runs living side by side in one `v__=N` dir) into one
    fully-sorted file per bucket at `out_path` (OptimizeAction's op; the
    reference has no compaction — its roadmap item,
    `/root/reference/ROADMAP.md:66-75`, exceeded here).

    All buckets compact through ONE compiled program (`ops/merge.py`):
    every bucket's runs are batch-sorted on a padded [B, L] bucket axis,
    only key lanes cross the link, and the host streams the permuted
    payload out per bucket — no per-bucket compile, no per-bucket sync.
    Below the device-amortization row count the permutation comes from a
    host lexsort with identical layout semantics.
    """
    from hyperspace_tpu.ops.merge import (bucket_sort_permutation,
                                          host_bucket_sort_permutation)

    indexed = prev_entry.indexed_columns
    num_buckets = prev_entry.num_buckets
    per_bucket = dict(parquet.bucket_files(prev_entry.content.root))
    if not per_bucket:
        raise HyperspaceException("No index data files found to compact.")
    # ONE ordered read of every run, bucket-major, VERSION order within a
    # bucket: base runs (no delta suffix, chunk suffixes keep name order)
    # then delta runs by delta number — so equal keys keep their append
    # order and the stable sort reproduces the tie order a full rebuild
    # over (base files + appended files) produces.
    import re as _re

    def _run_order(path: str):
        name = os.path.basename(path)
        m = _re.search(r"-delta(\d+)", name)
        return (int(m.group(1)) if m else 0, name)

    ordered = [(b, f) for b in sorted(per_bucket)
               for f in sorted(per_bucket[b], key=_run_order)]
    counts = parquet.file_row_counts([f for _, f in ordered])
    lengths = np.zeros(num_buckets, dtype=np.int64)
    for (b, _), c in zip(ordered, counts):
        lengths[b] += c
    table = parquet.read_table([f for _, f in ordered])
    from hyperspace_tpu.plan.schema import Schema
    schema = Schema.from_arrow(table.schema)

    names = [schema.field(c).name for c in indexed]
    merge_perm = _merge_path_permutation(table, ordered, counts, names,
                                         schema, num_buckets)
    if merge_perm is not None:
        chunks, starts, ends = merge_perm
    elif _host_lane_preferred(table.num_rows):
        key_batch = columnar.from_arrow(table.select(names), device=False)
        chunks, starts, ends = host_bucket_sort_permutation(
            key_batch, names, lengths)
    else:
        key_batch = columnar.from_arrow(table.select(names))
        chunks, starts, ends = bucket_sort_permutation(key_batch, names,
                                                       lengths)
    written = _write_sorted_runs(table, chunks, starts, ends, out_path,
                                 file_suffix=None)
    spec = BucketSpec(num_buckets, tuple(indexed), tuple(indexed))
    parquet.write_bucket_spec(out_path, spec, schema)
    return written
