"""Parquet IO, including the bucketed index-data layout.

Layout parity with the reference's bucketed write
(`index/DataFrameWriterExtensions.scala:49-78`): one parquet file (set) per
bucket, hash-partitioned by the indexed columns and sorted within buckets.
Bucket id is encoded in the file name (`part-<bucket 5 digits>.parquet`) —
the read side maps file -> bucket from the name, like Spark's bucketed
tables — and a `_bucket_spec.json` sidecar makes index data dirs
self-describing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Sequence

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.utils import storage
from hyperspace_tpu.plan.nodes import BucketSpec
from hyperspace_tpu.plan.schema import Schema

BUCKET_FILE_RE = re.compile(r"part-(\d{5})(?:-[A-Za-z0-9]+)?\.parquet$")
BUCKET_SPEC_FILE = "_bucket_spec.json"

# Version of THE bucket hash identity (`ops/hash_partition` + float-lane
# normalization in `ops/keys.py`). Bumped whenever the row -> bucket map
# of existing layouts would change (v2: -0.0/NaN float normalization). A
# data dir written under a different version reports no bucket spec, so
# readers treat it as unbucketed (correct, just unaccelerated) instead of
# silently mis-bucketing point lookups and co-partitioned joins.
BUCKET_HASH_VERSION = 2


def bucket_file_name(bucket: int, suffix: Optional[str] = None) -> str:
    tag = f"-{suffix}" if suffix else ""
    return f"part-{bucket:05d}{tag}.parquet"


def bucket_of_file(path: str) -> Optional[int]:
    m = BUCKET_FILE_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def _read_one(path: str, cols):
    import pyarrow.parquet as pq

    from hyperspace_tpu.utils import faults, retry

    # partitioning=None: the index layout's `v__=N` version directories
    # LOOK like hive partitions, and newer pyarrow infers a synthetic
    # `v__` dictionary column from the path (even for single-file
    # reads) — which is not data, collides with files that were written
    # while such inference was active, and must never enter a batch.
    def read():
        faults.fire("parquet.read", path)
        if storage.is_url(path):
            fs, real = storage.get_fs(path)
            return pq.read_table(real, columns=cols, filesystem=fs,
                                 partitioning=None)
        return pq.read_table(path, columns=cols, partitioning=None)

    # Transient storage failures (connection resets, 5xx from object
    # stores) retry per the io.retry policy; a corrupt file or missing
    # path is permanent and raises through (index scans convert it into
    # graceful degradation upstream).
    return retry.call(read, operation=f"parquet.read:{path}")


# Decoded-read cache: query trees that reference the same relation more
# than once (q64 joins a year-over-year aggregate to itself, so every
# underlying index is read twice) would otherwise re-decode identical
# parquet bytes. Entries are keyed on (files, columns) and VALIDATED by
# each file's (size, mtime) captured at read time — a refreshed or
# rewritten file misses. LRU-bounded by decoded bytes.
READ_CACHE_BYTES = int(os.environ.get(
    "HYPERSPACE_READ_CACHE_BYTES", 256 * 1024 * 1024))
import threading  # noqa: E402
from collections import OrderedDict as _OrderedDict  # noqa: E402
_read_cache: "_OrderedDict" = _OrderedDict()
# The bucketed join reads its two sides concurrently; all cache map
# mutations (touch, insert, evict) take this lock. File reads and decode
# run outside it.
_read_cache_lock = threading.Lock()

# ONE shared IO executor for concurrent per-file reads and footer
# fetches (lazily created): the previous per-call
# ThreadPoolExecutor(8) spun up and tore down 8 threads on EVERY
# multi-file read — per-query thread churn on the hot scan path.
# Tasks never submit sub-tasks, so sharing cannot deadlock.
_io_pool = None
_io_pool_lock = threading.Lock()


def io_executor():
    global _io_pool
    if _io_pool is None:
        with _io_pool_lock:
            if _io_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                _io_pool = ThreadPoolExecutor(max_workers=8,
                                              thread_name_prefix="hs-io")
    return _io_pool


def shutdown_io_executor(wait: bool = True) -> None:
    """Tear the shared IO pool down (idempotent; lazily re-created by
    the next `io_executor()` call, so tests survive a mid-run
    shutdown). Registered atexit: before this, interpreter teardown
    left 8 idle `hs-io` threads to be reaped by the futures module's
    own exit hook with any queued work's ordering unobserved — now the
    pool drains deterministically."""
    global _io_pool
    with _io_pool_lock:
        pool, _io_pool = _io_pool, None
    if pool is not None:
        pool.shutdown(wait=wait)


import atexit as _atexit  # noqa: E402

_atexit.register(shutdown_io_executor)


def _file_stamp(path: str, fresh: bool = False):
    """(size, mtime) of a FILE, or None when the path is a directory or
    the backend exposes no modification time — both must disable caching
    (a directory's own stamp does not change when a member file is
    rewritten in place; without mtime a same-size rewrite would collide).
    A local file is stat'ed once per query (`storage.stat`) unless
    `fresh`."""
    if storage.is_url(path):
        fs, real = storage.get_fs(path)
        info = fs.info(real)
        if (info.get("type") == "directory") or fs.isdir(real):
            return None
        mtime = (info.get("mtime") or info.get("updated")
                 or info.get("last_modified") or info.get("LastModified")
                 or info.get("created"))
        if not mtime:
            return None
        return (info.get("size", 0) or 0, str(mtime))
    st = storage.stat(path, fresh)
    import stat as _stat
    if _stat.S_ISDIR(st.st_mode):
        return None
    return (st.st_size, st.st_mtime_ns)


def _stamps(paths: Sequence[str], fresh: bool = False):
    """Tuple of per-file stamps, or None when any file is unstampable
    (directory, no mtime, stat failure) — which disables caching.
    `fresh`: stat each file now (the re-stat after a read)."""
    try:
        stamps = tuple(_file_stamp(p, fresh) for p in paths)
    except OSError:
        return None
    return None if any(st is None for st in stamps) else stamps


def clear_read_cache() -> None:
    with _read_cache_lock:
        _read_cache.clear()
    _count_cache.clear()
    clear_batch_cache()
    clear_device_cache()


def invalidate_paths(prefix: str) -> None:
    """Drop every host-cache entry (read / decoded-batch / footer-count)
    whose key touches a path under `prefix` — the index-FSM
    invalidation hook (`io/segcache.py`). Stamp validation alone cannot
    close the mid-commit window: a racing query can stat, validate, and
    serve bytes the committing action is replacing; an explicit sweep
    at the commit boundary can."""
    prefix = prefix.rstrip("/\\")

    def under(path: str) -> bool:
        return path == prefix or path.startswith(prefix + "/") \
            or path.startswith(prefix + os.sep)

    with _read_cache_lock:
        for key in [k for k in _read_cache if any(under(p)
                                                  for p in k[0])]:
            del _read_cache[key]
    with _batch_cache_lock:
        for key in [k for k in _batch_cache if any(under(p)
                                                   for p in k[0])]:
            del _batch_cache[key]
    for path in [p for p in _count_cache if under(p)]:
        _count_cache.pop(path, None)


def read_table(paths: Sequence[str], columns: Optional[Sequence[str]] = None):
    """Read one or more parquet files/dirs into a single Arrow table, in
    path order. Files are read concurrently (pyarrow releases the GIL);
    order is preserved by the map. `scheme://` paths read through their
    fsspec filesystem. Results are served from the stamped read cache
    when every file is unchanged."""
    import pyarrow as pa

    from hyperspace_tpu.telemetry import memory as _mem

    if not paths:
        raise HyperspaceException("No parquet inputs to read.")
    cols = list(columns) if columns else None
    key = (tuple(paths), tuple(cols) if cols else None)
    stamps = _stamps(paths)
    if stamps is not None and READ_CACHE_BYTES > 0:
        with _read_cache_lock:
            hit = _read_cache.get(key)
            if hit is not None and hit[0] == stamps:
                _read_cache.move_to_end(key)  # LRU touch
                _mem.cache_hit("parquet_read")
                return hit[1]
    _mem.cache_miss("parquet_read")

    if len(paths) == 1:
        table = _read_one(paths[0], cols)
    else:
        tables = list(io_executor().map(lambda p: _read_one(p, cols),
                                        paths))
        table = pa.concat_tables(tables, promote_options="default")

    if stamps is not None and READ_CACHE_BYTES > 0:
        # Re-stat after the read: a file rewritten DURING the read would
        # otherwise cache new (or torn, for multi-file concat) bytes under
        # the old stamp, and the stale entry would keep validating until
        # the file changed again. Insert only when nothing moved.
        if _stamps(paths, fresh=True) != stamps:
            return table
        with _read_cache_lock:
            _read_cache[key] = (stamps, table)
            total = sum(t.nbytes for _, t in _read_cache.values())
            evictions = 0
            while total > READ_CACHE_BYTES and len(_read_cache) > 1:
                _, (_, evicted) = _read_cache.popitem(last=False)
                total -= evicted.nbytes
                evictions += 1
            entries = len(_read_cache)
        _mem.cache_eviction("parquet_read", evictions)
        _mem.cache_stats("parquet_read", total, entries)
    return table


_count_cache: dict = {}


def file_row_counts(paths: Sequence[str]) -> List[int]:
    """Per-file row counts from parquet footers (no data read); stamped
    per-file cache (index data files are immutable, and the bucketed read
    path asks for the same footers on every warm query)."""
    import pyarrow.parquet as pq

    def meta_rows(p):
        try:
            stamp = _file_stamp(p)
        except OSError:
            stamp = None
        if stamp is not None:
            hit = _count_cache.get(p)
            if hit is not None and hit[0] == stamp:
                return hit[1]
        if storage.is_url(p):
            fs, real = storage.get_fs(p)
            with fs.open(real, "rb") as f:
                rows = pq.read_metadata(f).num_rows
        else:
            rows = pq.read_metadata(p).num_rows
        if stamp is not None:
            if len(_count_cache) > 65536:
                _count_cache.clear()
            _count_cache[p] = (stamp, rows)
        return rows

    if len(paths) <= 1:
        return [meta_rows(p) for p in paths]
    return list(io_executor().map(meta_rows, paths))


# Decoded host-batch cache: the read cache (above) keeps Arrow bytes, but
# a warm query still re-derives numpy-backed ColumnBatches from them every
# execution (~50 ms at 4M rows). Batches are immutable downstream (every
# operator gathers into new arrays), and the numpy columns mostly alias
# the cached Arrow buffers, so caching the decoded form costs little extra
# memory. Same stamp validation as the read cache.
_batch_cache: "_OrderedDict" = _OrderedDict()
_batch_cache_lock = threading.Lock()


def clear_batch_cache() -> None:
    with _batch_cache_lock:
        _batch_cache.clear()


def _stamped_batch_read(paths: Sequence[str],
                        columns: Optional[Sequence[str]], schema,
                        cache: "_OrderedDict", lock, budget: int):
    """Stamped-LRU read for the HOST decoded-batch cache: get with
    stamp validation, decode on miss, insert with re-stat (a file
    rewritten during the read must not cache under the old stamp),
    evict LRU entries until within budget. Hit/miss/eviction/bytes-held
    series land as `cache.host_batch.*`. (The DEVICE lane lives in
    `io/segcache.py` — version-keyed HBM residency, single-flight
    fills, index-FSM invalidation.)"""
    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.telemetry import memory as _mem

    name = "host_batch"
    key = (tuple(paths), tuple(columns) if columns is not None else None,
           schema.to_json() if schema is not None else None)
    # Enforce the effective budget on ENTRY, not only on insert: a budget
    # lowered mid-session (the documented OOM remedy — conf
    # `cache.device.bytes`) must actually release already-resident
    # batches, and budget 0 must empty the cache, or the memory being
    # tuned away stays pinned.
    with lock:
        evictions = 0
        if budget <= 0:
            evictions = len(cache)
            cache.clear()
            total = 0
        else:
            total = sum(b for _, _, b in cache.values())
            while total > budget and cache:
                _, (_, _, evicted) = cache.popitem(last=False)
                total -= evicted
                evictions += 1
        entries = len(cache)
    _mem.cache_eviction(name, evictions)
    _mem.cache_stats(name, total, entries)
    stamps = _stamps(paths)
    if stamps is not None and budget > 0:
        with lock:
            hit = cache.get(key)
            if hit is not None and hit[0] == stamps:
                cache.move_to_end(key)
                _mem.cache_hit(name)
                return hit[1]
            if hit is not None:
                del cache[key]
    _mem.cache_miss(name)
    table = read_table(paths, columns=columns)
    batch = columnar.from_arrow(table, schema, device=False)
    if stamps is not None and budget > 0:
        if _stamps(paths, fresh=True) != stamps:
            return batch
        nbytes = _batch_nbytes(batch)
        if nbytes <= budget:
            with lock:
                cache[key] = (stamps, batch, nbytes)
                total = sum(b for _, _, b in cache.values())
                evictions = 0
                while total > budget and len(cache) > 1:
                    _, (_, _, evicted) = cache.popitem(last=False)
                    total -= evicted
                    evictions += 1
                entries = len(cache)
            _mem.cache_eviction(name, evictions)
            _mem.cache_stats(name, total, entries)
    return batch


def read_host_batch(paths: Sequence[str],
                    columns: Optional[Sequence[str]], schema,
                    budget: Optional[int] = None):
    """Read parquet files into a HOST-lane ColumnBatch through the stamped
    decoded-batch cache. `budget` (session conf) overrides the env-default
    cache bound."""
    return _stamped_batch_read(paths, columns, schema, _batch_cache,
                               _batch_cache_lock,
                               READ_CACHE_BYTES if budget is None else budget)


def clear_device_cache() -> None:
    """Empty the HBM segment cache (`io/segcache.py` owns the device
    lane now; this name survives for the cold-cache callers —
    `clear_read_cache`, tests)."""
    from hyperspace_tpu.io import segcache
    segcache.clear()


def _batch_nbytes(batch) -> int:
    """Approximate resident bytes of a host batch (column payloads +
    validity; dictionaries are shared and small)."""
    total = 0
    for col in batch.columns.values():
        total += getattr(col.raw, "nbytes", 0)
        if col.validity is not None:
            total += getattr(col.validity, "nbytes", 0)
    return total


def write_table(table, path: str) -> None:
    """Write an index data file. Numeric columns skip parquet's
    dictionary-encoding attempt, and statistics are disabled for ALL
    columns: index rows are pre-sorted runs, the bucket layout (not page
    stats) prunes reads, and dropping both measured ~3x faster encodes
    with smaller files and ~25% faster reads. String columns keep
    dictionary encoding — they compress well and decode to the same Arrow
    dictionaries the device encoding consumes."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.utils import faults, retry

    string_cols = [f.name for f in table.schema
                   if pa.types.is_string(f.type) or pa.types.is_large_string(f.type)
                   or pa.types.is_dictionary(f.type)]
    kwargs = dict(use_dictionary=string_cols or False,
                  write_statistics=False, compression="snappy")

    def write():
        faults.fire("parquet.write", path)
        if storage.is_url(path):
            fs, real = storage.get_fs(path)
            fs.makedirs(os.path.dirname(real), exist_ok=True)
            pq.write_table(table, real, filesystem=fs, **kwargs)
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        pq.write_table(table, path, **kwargs)

    # A retried attempt rewrites the whole file — safe: version dirs are
    # private to their writing action until the commit marker lands.
    retry.call(write, operation=f"parquet.write:{path}")


def write_bucket_spec(directory: str, spec: BucketSpec, schema: Schema) -> None:
    from hyperspace_tpu.utils import file_utils
    payload = json.dumps({"bucketSpec": spec.to_dict(),
                          "hashVersion": BUCKET_HASH_VERSION,
                          "schema": [fld.to_dict() for fld in schema.fields]},
                         indent=2)
    file_utils.create_file(storage.join(directory, BUCKET_SPEC_FILE), payload)


def read_bucket_spec(directory: str) -> Optional[BucketSpec]:
    from hyperspace_tpu.utils import file_utils
    path = storage.join(directory, BUCKET_SPEC_FILE)
    if not file_utils.exists(path):
        return None
    payload = json.loads(file_utils.read_contents(path))
    if payload.get("hashVersion", 1) != BUCKET_HASH_VERSION:
        # Layout written under a different hash identity: expose it as
        # unbucketed so reads stay correct (no pruning/co-partitioning).
        return None
    return BucketSpec.from_dict(payload["bucketSpec"])


def bucket_map(files: Sequence[str]) -> Dict[int, List[str]]:
    """Group an EXPLICIT file listing by bucket id (files not carrying
    the bucket naming pattern are dropped). The snapshot-pinned scan
    path (`engine/physical.ScanExec._per_bucket_files`) derives bucket
    maps from its plan-time-frozen listing through this instead of
    re-listing the directory at execution."""
    out: Dict[int, List[str]] = {}
    for path in sorted(files, key=os.path.basename):
        bucket = bucket_of_file(path)
        if bucket is not None:
            out.setdefault(bucket, []).append(path)
    return out


def bucket_files(directory: str) -> Dict[int, List[str]]:
    """Map bucket id -> parquet files in a bucketed data dir (empty buckets
    have no files)."""
    out: Dict[int, List[str]] = {}
    from hyperspace_tpu.utils import file_utils
    if not file_utils.is_dir(directory):
        return out
    for name in sorted(storage.listdir_names(directory)):
        bucket = bucket_of_file(name)
        if bucket is not None:
            out.setdefault(bucket, []).append(storage.join(directory, name))
    return out
