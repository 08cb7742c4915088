"""Born-sharded SPMD query execution: device-resident, bucket-range-
sharded inputs flowing stage to stage as single jitted programs.

The deleted legacy `parallel/join.py` path parallelized the BATCH:
every query re-gathered key lanes on the host, re-placed a fresh [S, C]
layout onto the mesh, and synced to the host between stages to size
outputs. This module — now the ONE distributed join architecture —
parallelizes the INDEX, the way the paper's bucketed
layout intends: a committed covering index is *born sharded* — the build
writes per-device parquet shards over the contiguous bucket-range map
(`parallel/mesh.bucket_ranges`), the per-device segment cache holds each
device's bucket range (warm reads assemble the global arrays from HBM
with ZERO link traffic, `mesh.assemble_sharded_rows`), and the
shuffle-free sort-merge join, predicate scan, and group-by aggregate
execute as single jitted SPMD programs under the canonical row sharding:

- **a join is two programs and one readback**: key-lane decomposition
  and the counting match are one `instrumented_jit` dispatch whose
  shapes depend on the inputs alone; the host reads the per-shard
  totals (with the unmatched-right counts and the route-overflow
  scalar) ONCE; the pair expansion is a second dispatch over the
  power-of-two rung just above the largest total (`_expand_rung`). An
  expansion sized by what the match found cannot overflow, so there is
  no capacity to guess, double or remember, and its cost follows the
  answer, not the inputs (a static capacity of 2x the input rows spent
  98% of a four-chip Q12 placing 23,438 pairs in 11.3 M slots: PERF.md
  section 6, PR 33).
- **ICI repartition in-program**: when the two sides' bucket counts
  mismatch (the ranker's fallback), the smaller-bucket side's key lanes
  re-bucket to the larger count through a `shard_map` all_to_all *inside
  the match program* — row payload never routes (the expansion
  carries routed original-row ids and the output gather reaches across
  shards), and nothing crosses through the host.
- **stage-to-stage residency**: join output stays a device-resident
  ColumnBatch; `repartition_sharded` re-buckets it over ICI into a new
  born-sharded layout for the next join, and `sharded_group_aggregate` /
  `sharded_filter` consume the sharded layout directly — a warm
  multi-stage plan records zero D2H link crossings between stages
  (`link.d2h.*` stays flat until result materialization).

Layout contract (`ShardedBatch`): every column is a flat `[S*C]` jax
array under `mesh.shard_rows` — shard s's slice holds the rows of its
bucket range, padded to the common per-shard capacity C with
`row_valid=False` tail rows. Because ownership is a CONTIGUOUS bucket
range, same-key rows co-locate on one shard by construction and the
counting match needs no bucket lane: equal keys hash to one bucket, one
bucket lives on one shard.

String columns are FIRST-CLASS in this layout. Each device's bucket
range carries its own sorted local dictionary (written next to the
parquet shards and recorded in `_shard_layout.json` by mesh builds); a
born-sharded read unifies the ranges into ONE global sorted dictionary
(host metadata, cached version-keyed in the segment cache) and remaps
each shard's codes into it on the host before placement, so the cached
device payload is globally comparable int32 code lanes riding the same
[S*C] row sharding as every numeric column — string BYTES never cross
the link at query time, and a warm read is as link-free as a numeric
one. Joins whose two sides carry different dictionaries unify IN-PROGRAM
through compact rank-remap tables (`string_remap_tables`, THE
lint-enforced remap seam): the int32 local-code -> pair-merged-rank
tables are built once on the host from the dictionaries (derived from
the same precomputed value-hash identity the bucket layout uses), cached
content-keyed in the segment cache, and replicated into the jitted
match program over ICI — warm repeats serve them straight from HBM
(`spmd.strings.remap_cache_hits`) and ship zero string bytes. String
predicates compile to code-space range tests against the global
dictionary (`engine/compiler.py`), so the jitted filter program never
touches bytes either.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import ColumnBatch, DeviceColumn
from hyperspace_tpu.ops import keys as keymod
from hyperspace_tpu.ops.bucketed_join import next_pow2
from hyperspace_tpu.ops.join import run_brackets
from hyperspace_tpu.parallel.mesh import (DCN_AXIS, SHARD_AXIS,
                                          assemble_sharded_rows,
                                          bucket_owner, bucket_ranges,
                                          compat_shard_map, dcn_size,
                                          ici_size, mesh_device_list,
                                          mesh_device_tag, row_spec,
                                          shard_row_segments, shard_rows,
                                          total_shards)

# Route-slab discipline: the first attempt of a repartition sizes each
# per-peer slab at CAPACITY_FACTOR x the even share of the per-shard
# rows; on-device overflow detection doubles it until every row fits
# (exact — nothing is ever silently dropped). The join's pair expansion
# is NOT sized this way: it is sized by what its match found
# (`_expand_rung`).
CAPACITY_FACTOR = 2.0

# Born-sharded skew guard: when the padded [S, C] layout would out-size
# the true rows by more than this, the caller should fall back to the
# single-chip counting join (whose memory is bounded by the true rows).
PAD_BLOWUP_FACTOR = 4


@dataclass
class ShardedBatch:
    """A born-sharded, device-resident batch: flat [S*C] columns under
    the canonical row sharding, shard s holding its contiguous bucket
    range's rows with invalid padding rows at each shard's tail.
    `lengths` (per-bucket row counts) is layout metadata — None for
    repartitioned intermediates whose per-bucket histogram never
    touched the host."""

    batch: ColumnBatch          # flat [S*C] device columns
    row_valid: object           # [S*C] bool, sharded
    mesh: object
    rows_per_shard: int         # C
    num_buckets: int
    lengths: Optional[np.ndarray] = None
    # Virtual sub-shards (hot-bucket skew): set when the layout was
    # row-balanced INSIDE hot buckets instead of bucket-aligned — keys
    # no longer co-locate per shard, so a join over this side must read
    # its other side ALIGNED to this plan (hot buckets replicated onto
    # every covering shard). None = the canonical bucket-range layout.
    split_plan: Optional["SubshardPlan"] = None

    @property
    def n_shards(self) -> int:
        return total_shards(self.mesh)

    @property
    def num_rows(self) -> int:
        """TRUE row count (padding excluded) when lengths are known."""
        if self.lengths is not None:
            return int(self.lengths.sum())
        import jax.numpy as jnp
        return int(jnp.sum(self.row_valid))


def supports_sharded(schema, key_columns: Sequence[str] = ()) -> bool:
    """Whether a schema fits the born-sharded layout. Strings are
    first-class (per-range dictionaries, module docstring); only a dtype
    outside the engine's host-lane map declines."""
    from hyperspace_tpu.io.columnar import HOST_NP_DTYPES
    try:
        for f in schema.fields:
            if f.dtype not in HOST_NP_DTYPES:
                return False
        for c in key_columns:
            schema.field(c)
    except Exception:
        return False
    return True


def spmd_fallback(reason: str) -> None:
    """Record a decline of the born-sharded SPMD lane while a mesh was
    AVAILABLE (`spmd.fallbacks` + a query event). The counter is the
    one-architecture contract: `tests/test_spmd.py` and
    `tests/test_q12_mesh.py` pin it at 0 on the lanes they drive, and
    the benchmark's four-chip cell reports it as
    `compared.spmd_fallbacks`."""
    from hyperspace_tpu import telemetry
    telemetry.get_registry().counter("spmd.fallbacks").inc()
    telemetry.event("spmd", "fallback", reason=reason)


def count_string_predicate_lookups(expression, batch: ColumnBatch) -> None:
    """`spmd.strings.dict_lookups`: one per string column a predicate
    resolves literals against on the SPMD lane (the compiler's
    code-space binary searches, `engine/compiler._string_literal_compare`
    — the jitted program itself never touches bytes)."""
    from hyperspace_tpu import telemetry
    try:
        refs = expression.references()
    except Exception:
        return
    n = 0
    for r in refs:
        try:
            if batch.column(r).is_string:
                n += 1
        except Exception:
            continue
    if n:
        telemetry.get_registry().counter(
            "spmd.strings.dict_lookups").inc(n)


def pad_blowup(lengths, n_shards: int) -> bool:
    """True when per-shard padding to the hottest shard's row count
    would blow the [S*C] layout far past the true rows (the caller
    splits the hot range into virtual sub-shards — `subshard_plan` —
    or falls back to the single-chip counting join)."""
    segs = shard_row_segments(lengths, n_shards)
    C = max(1, max(e - s for s, e in segs))
    rows = int(np.asarray(lengths).sum())
    return C * n_shards > max(PAD_BLOWUP_FACTOR * rows, 1 << 16)


# ---------------------------------------------------------------------------
# Virtual sub-shards: hot-bucket skew without leaving the SPMD lane
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SubshardPlan:
    """Row-balanced virtual sub-shards over a skewed bucket histogram.

    When one bucket range is hot enough that whole-bucket ownership
    would pad the [S*C] layout past `PAD_BLOWUP_FACTOR`x the true rows,
    the skewed side's bucket-ordered row space is cut into EQUAL row
    segments instead — cuts may fall inside a hot bucket, so a hot
    bucket's rows span several consecutive shards (the hierarchical
    range map makes this representation free: segments are just row
    intervals, exactly like `shard_row_segments`' output).

    Splitting breaks per-shard key co-location, so a join over the
    split side reads its OTHER side aligned to this plan:
    `bucket_spans[s]` is the contiguous bucket interval intersecting
    shard s's row segment, and the aligned read places ALL of those
    buckets' rows on shard s — a split bucket's other-side rows are
    REPLICATED onto every shard covering part of it. Each split-side
    row then meets every matching row locally and lives on exactly one
    shard, so inner/left_outer/semi/anti results are bit-identical to
    the unsplit join (full_outer needs unmatched-RIGHT uniqueness and
    stays off this lane)."""

    num_buckets: int
    n_shards: int
    segments: tuple      # per-shard (row_lo, row_hi) into the row space
    bucket_spans: tuple  # per-shard (b_lo, b_hi) intersecting buckets


def subshard_plan(lengths, n_shards: int) -> SubshardPlan:
    """The deterministic split plan for a skewed histogram: equal row
    segments (±1) with their covering bucket intervals."""
    lengths = np.asarray(lengths, dtype=np.int64)
    total = int(lengths.sum())
    per = -(-max(total, 1) // n_shards)
    cum = np.concatenate([[0], np.cumsum(lengths)])
    segments = []
    spans = []
    for s in range(n_shards):
        lo, hi = min(s * per, total), min((s + 1) * per, total)
        segments.append((lo, hi))
        if hi <= lo:
            spans.append((0, 0))
            continue
        # buckets b with cum[b] < hi and cum[b+1] > lo
        b_lo = int(np.searchsorted(cum, lo, side="right")) - 1
        b_hi = int(np.searchsorted(cum, hi, side="left"))
        spans.append((max(b_lo, 0), min(b_hi, len(lengths))))
    return SubshardPlan(len(lengths), n_shards, tuple(segments),
                        tuple(spans))


def _file_cuts(per_bucket: dict, num_buckets: int, counts=None):
    """Ordered (bucket, file, rows) over the bucket-ordered file list
    plus the cumulative row offsets — the geometry both sub-shard read
    planners slice against. Row counts are the caller's (`counts`, in
    that order: the scan's resolved facts) or come from parquet
    footers."""
    from hyperspace_tpu.io import parquet

    ordered = [(b, f) for b in range(num_buckets)
               for f in per_bucket.get(b, [])]
    if counts is None:
        counts = parquet.file_row_counts([f for _, f in ordered])
    cum = np.concatenate([[0], np.cumsum(np.asarray(counts,
                                                    dtype=np.int64))])
    return ordered, counts, cum


def plan_skew_read(per_bucket: dict, lengths, n_shards: int,
                   counts=None):
    """(plan, shard_specs) for the SKEWED side: each shard s reads rows
    [lo, hi) of the bucket-ordered file list — the covering files plus
    a (skip, take) window so a file holding a cut boundary decodes once
    per touching shard but ships only its slice."""
    lengths = np.asarray(lengths, dtype=np.int64)
    plan = subshard_plan(lengths, n_shards)
    ordered, counts, cum = _file_cuts(per_bucket, len(lengths), counts)
    specs = []
    for lo, hi in plan.segments:
        if hi <= lo:
            specs.append(((), 0, 0))
            continue
        f_lo = int(np.searchsorted(cum, lo, side="right")) - 1
        f_hi = int(np.searchsorted(cum, hi, side="left"))
        files = tuple(f for _b, f in ordered[f_lo:f_hi])
        specs.append((files, lo - int(cum[f_lo]), hi - lo))
    return plan, specs


def plan_aligned_read(per_bucket: dict, lengths, plan: SubshardPlan):
    """shard_specs for the side ALIGNED to a split plan: shard s holds
    every row of the buckets intersecting the plan's shard-s segment —
    buckets on a cut boundary are replicated onto each covering
    shard."""
    lengths = np.asarray(lengths, dtype=np.int64)
    cum = np.concatenate([[0], np.cumsum(lengths)])
    specs = []
    for b_lo, b_hi in plan.bucket_spans:
        files = tuple(f for b in range(b_lo, b_hi)
                      for f in per_bucket.get(b, []))
        specs.append((files, 0, int(cum[b_hi] - cum[b_lo])))
    return specs


# ---------------------------------------------------------------------------
# Layout construction
# ---------------------------------------------------------------------------


def shard_bucket_ordered(batch: ColumnBatch, lengths, mesh) -> ShardedBatch:
    """Place a bucket-ordered batch into the born-sharded layout. HOST
    batches pad per shard in numpy and cross the link ONCE through the
    transfer engine's sharded put (each device receives only its range's
    rows); DEVICE batches re-lay out with an on-device gather (the
    per-shard segment boundaries are host metadata, the rows never leave
    the device)."""
    import jax.numpy as jnp

    from hyperspace_tpu.io import transfer

    lengths = np.asarray(lengths, dtype=np.int64)
    n_shards = total_shards(mesh)
    segs = shard_row_segments(lengths, n_shards)
    C = max(1, max(e - s for s, e in segs))
    n = batch.num_rows
    sharding = shard_rows(mesh)
    engine = transfer.get_engine()

    # [S*C] gather index + validity, from the host-side segment map.
    idx = np.zeros(n_shards * C, dtype=np.int64)
    valid = np.zeros(n_shards * C, dtype=bool)
    for s, (lo, hi) in enumerate(segs):
        rows = hi - lo
        idx[s * C:s * C + rows] = np.arange(lo, hi)
        valid[s * C:s * C + rows] = True

    columns = {}
    if batch.is_host:
        for name, col in batch.columns.items():
            src = col.carry
            data = np.zeros((n_shards * C,) + src.shape[1:],
                            dtype=src.dtype)
            data[valid] = src
            v = None
            if col.validity is not None:
                v = np.zeros(n_shards * C, dtype=bool)
                v[valid] = col.validity
                v = engine.put(v, device=sharding)
            columns[name] = col.with_raw(
                engine.put(data, device=sharding), v)
        row_valid = engine.put(valid, device=sharding)
    else:
        idx_dev = engine.put(np.minimum(idx, max(n - 1, 0)),
                             device=sharding)
        row_valid = engine.put(valid, device=sharding)
        for name, col in batch.columns.items():
            src = jnp.asarray(col.carry)
            data = jnp.where(
                _expand_mask(row_valid, src.ndim),
                jnp.take(src, idx_dev, axis=0), 0)
            v = None
            if col.validity is not None:
                v = jnp.take(jnp.asarray(col.validity), idx_dev) & row_valid
            columns[name] = col.with_raw(
                engine.put(data, device=sharding),
                (engine.put(v, device=sharding)
                 if v is not None else None))
    flat = ColumnBatch(batch.schema, columns)
    return ShardedBatch(flat, row_valid, mesh, C, len(lengths),
                        lengths=lengths)


def _expand_mask(mask, ndim: int):
    import jax.numpy as jnp
    out = jnp.asarray(mask)
    for _ in range(ndim - 1):
        out = out[..., None]
    return out


def _build_global_dicts(files: List[str], str_fields: Sequence[str],
                        schema) -> dict:
    """The GLOBAL sorted dictionary (+ precomputed value hashes) of each
    string column of a born-sharded version: preferred source is the
    per-range dictionaries the mesh build recorded in
    `_shard_layout.json` (pure JSON, no data read — any query mesh size
    merges the same union); a version without the record (single-device
    builds, ranges past the `distribution.dictionary.max.entries` cap)
    derives them from one host-side read of the string columns."""
    import os

    from hyperspace_tpu.io.columnar import _string_hash64

    out: dict = {}
    if not files:
        for name in str_fields:
            empty = np.asarray([], dtype=str)
            out[name] = {"dictionary": empty,
                         "hashes": _string_hash64(empty)}
        return out

    remaining = list(str_fields)
    roots = {os.path.dirname(f) for f in files}
    if len(roots) == 1:
        from hyperspace_tpu.io.builder import read_shard_layout
        layout = read_shard_layout(next(iter(roots)))
        recorded = (layout or {}).get("dictionaries") or {}
        for name in list(remaining):
            ranges = recorded.get(name)
            if ranges is None or any(r is None for r in ranges):
                continue  # uncapped record absent: derive from files
            merged = np.unique(np.concatenate(
                [np.asarray(r, dtype=str) for r in ranges]
                + [np.asarray([], dtype=str)]))
            out[name] = {"dictionary": merged,
                         "hashes": _string_hash64(merged)}
            remaining.remove(name)

    if remaining:
        from hyperspace_tpu.io import columnar, parquet
        table = parquet.read_table(files, columns=remaining)
        for name in remaining:
            _codes, dictionary, hashes, _validity = \
                columnar._encode_strings_arrow(table.column(name))
            out[name] = {"dictionary": dictionary, "hashes": hashes}
    return out


def _resolve_global_dicts(per_shard_files: List[List[str]],
                          str_fields: Sequence[str], schema, base_ref,
                          conf, budget, cache) -> dict:
    """Version-keyed cached resolution of the global dictionaries (one
    entry per committed version + column set; warm queries never re-read
    or re-merge — `spmd.strings.remap_cache_hits`)."""
    from hyperspace_tpu import telemetry

    all_files = [f for files in per_shard_files for f in files]
    if base_ref is None:
        return _build_global_dicts(all_files, str_fields, schema)
    filled: List[bool] = []

    def fill():
        filled.append(True)
        payload = _build_global_dicts(all_files, str_fields, schema)
        nbytes = sum(int(e["dictionary"].nbytes) + int(e["hashes"].nbytes)
                     for e in payload.values())
        return payload, max(nbytes, 1)

    key = base_ref.key + (("spmd-dicts", tuple(str_fields)),)
    payload = cache.get_or_fill(key, fill, ref=base_ref, conf=conf,
                                budget=budget)
    if not filled:
        telemetry.get_registry().counter(
            "spmd.strings.remap_cache_hits").inc()
    return payload


def _remap_to_global(host: ColumnBatch, global_dicts: dict) -> ColumnBatch:
    """Swap each string column's LOCAL codes for codes in the global
    dictionary (host-side, before placement) — the cached device payload
    then holds globally comparable int32 lanes and no per-shard
    dictionary state. Fails loudly if a valid local value is missing
    from the global dictionary (the two derive from the same committed
    files, so a miss means the record and the data disagree)."""
    for name, col in host.columns.items():
        if not col.is_string:
            continue
        g = global_dicts[name]["dictionary"]
        local = np.asarray(col.dictionary)
        if len(g):
            remap = np.searchsorted(g, local).astype(np.int32)
            found = g[np.clip(remap, 0, len(g) - 1)] == local
        else:
            remap = np.zeros(len(local), dtype=np.int32)
            found = np.zeros(len(local), dtype=bool)
        codes = np.asarray(col.data)
        used = codes if col.validity is None else codes[col.validity]
        if len(used) and not found[used].all():
            raise HyperspaceException(
                f"Born-sharded read: string column {name!r} holds values "
                "absent from the version's global dictionary — the "
                "recorded per-range dictionaries and the data disagree.")
        safe = np.where(found, remap, 0).astype(np.int32)
        host.columns[name] = DeviceColumn(
            data=safe[codes], dtype="string", validity=col.validity,
            dictionary=col.dictionary, dict_hashes=col.dict_hashes)
    return host


def _files_digest(files) -> str:
    """Compact stable identity of an ordered file tuple for sub-shard
    cache key tags."""
    import hashlib

    h = hashlib.sha1()
    for f in files:
        h.update(str(f).encode())
        h.update(b"\x00")
    return h.hexdigest()[:16]


def read_sharded(per_shard_files: List[List[str]], lengths,
                 columns: Sequence[str], schema, mesh,
                 base_ref=None, conf=None, budget=None,
                 shard_specs=None,
                 split_plan: Optional[SubshardPlan] = None
                 ) -> ShardedBatch:
    """Born-sharded read: each flat shard s's bucket-range files decode
    and place onto DEVICE s through the per-device segment cache
    (per-bucket-range fill granularity — the PR-8 "remaining on this
    axis" item). A warm read touches neither parquet nor the link: the
    cached per-device padded shards assemble into the global sharded
    arrays with zero data movement. Cache keys carry the mesh's DEVICE
    TAG: two replica slices of one topology hold the same ranges on
    different devices and must never alias each other's entries.

    `shard_specs` overrides the canonical whole-bucket segmentation
    with explicit per-shard (files, skip_rows, n_rows) windows — the
    virtual-sub-shard lanes (`plan_skew_read` / `plan_aligned_read`);
    `split_plan` is stamped onto the result so the join knows the
    layout is row-balanced, not bucket-aligned."""
    from hyperspace_tpu import telemetry

    lengths = np.asarray(lengths, dtype=np.int64)
    n_shards = total_shards(mesh)
    fills = telemetry.get_registry().counter("cache.segments.fills")
    fills_before = fills.value
    with telemetry.span("hs.mesh.read", "mesh", rows=int(lengths.sum()),
                        shards=n_shards) as sp:
        out = _read_sharded(per_shard_files, lengths, columns, schema,
                            mesh, n_shards, base_ref, conf, budget,
                            shard_specs, split_plan)
        # no fill ran: every device's range came out of its segment cache
        sp.set(cached=int(base_ref is not None
                          and fills.value == fills_before))
    return out


def _read_sharded(per_shard_files, lengths, columns, schema, mesh,
                  n_shards: int, base_ref, conf, budget, shard_specs,
                  split_plan) -> ShardedBatch:
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io import segcache

    if shard_specs is None:
        segs = shard_row_segments(lengths, n_shards)
        ranges = bucket_ranges(len(lengths), n_shards)
        shard_specs = [(tuple(per_shard_files[s]), 0, segs[s][1] - segs[s][0])
                       for s in range(n_shards)]
        key_tags = [("spmd", ranges[s][0], ranges[s][1], n_shards)
                    for s in range(n_shards)]
        out_lengths = lengths
        windowed = False
    else:
        if len(shard_specs) != n_shards:
            raise HyperspaceException(
                f"shard_specs covers {len(shard_specs)} shards; the mesh "
                f"has {n_shards}.")
        # The windowed (skip, rows) coordinates alone do not say WHICH
        # bucket-range files shard s's window slices — the skew/aligned
        # plans depend on the OTHER join side's histogram, so two joins
        # of the same root+version can hand shard s identical window
        # geometry over DIFFERENT bucket spans. The file-tuple digest
        # pins the key to the covered bytes.
        key_tags = [("spmd-sub", spec[1], spec[2], n_shards, s,
                     _files_digest(spec[0]))
                    for s, spec in enumerate(shard_specs)]
        out_lengths = None
        windowed = True
    C = max(1, max(spec[2] for spec in shard_specs))
    devices = mesh_device_list(mesh)
    dev_tag = mesh_device_tag(mesh)
    cols = tuple(columns)
    schema_json = schema.to_json()
    cache = segcache.get_cache()

    out_schema = schema.select(cols)
    str_fields = tuple(f.name for f in out_schema.fields
                       if f.dtype == "string")
    global_dicts = None
    if str_fields:
        # One global sorted dictionary per string column (version-keyed
        # cached): per-shard fills remap their local codes into it on
        # the host, so the cached device lanes are globally comparable.
        all_files = list(dict.fromkeys(
            f for spec in shard_specs for f in spec[0]))
        global_dicts = _resolve_global_dicts([all_files], str_fields,
                                             schema, base_ref, conf,
                                             budget, cache)

    def fill_one(s: int):
        files, skip, rows = shard_specs[s]

        def fill():
            return _fill_device_shard(list(files), cols, schema,
                                      rows, C, devices[s],
                                      global_dicts=global_dicts,
                                      skip=skip, windowed=windowed)

        if base_ref is None:
            return fill()[0]
        key = base_ref.key + (key_tags[s] + (C, dev_tag),
                              cols, schema_json)
        return cache.get_or_fill(key, fill, ref=base_ref, conf=conf,
                                 budget=budget)

    # Concurrent per-shard fills: parquet decode of shard s+1 overlaps
    # shard s's H2D (each fill itself pipelines through put_group). The
    # fan-out rides a DEDICATED lane, not `parquet.io_executor()` — the
    # fills call read_table, which submits to that shared pool and
    # blocks; fanning out on the same pool would deadlock it against
    # itself.
    shards = list(_read_pool().map(
        telemetry.propagating(fill_one), range(n_shards)))

    columns_out = {}
    for f in out_schema.fields:
        data = assemble_sharded_rows(
            mesh, [sh["columns"][f.name]["data"] for sh in shards])
        validity = None
        if any(sh["columns"][f.name].get("validity") is not None
               for sh in shards):
            validity = assemble_sharded_rows(
                mesh, [_shard_validity(sh, f.name, C, devices[s])
                       for s, sh in enumerate(shards)])
        dictionary = dict_hashes = None
        if f.dtype == "string":
            # Codes are already global (the fills remapped); the
            # dictionary is HOST metadata — no bytes on the link.
            from hyperspace_tpu.io.columnar import _split_hashes
            entry = global_dicts[f.name]
            dictionary = entry["dictionary"]
            dict_hashes = _split_hashes(entry["hashes"], device=False)
        columns_out[f.name] = DeviceColumn(data=data, dtype=f.dtype,
                                           validity=validity,
                                           dictionary=dictionary,
                                           dict_hashes=dict_hashes)
    row_valid = assemble_sharded_rows(
        mesh, [_on_device(devices[s],
                          partial(_valid_mask, shard_specs[s][2], C))
               for s in range(n_shards)])
    flat = ColumnBatch(out_schema, columns_out)
    return ShardedBatch(flat, row_valid, mesh, C, len(lengths),
                        lengths=out_lengths, split_plan=split_plan)


_pool = None
_pool_lock = None


def _read_pool():
    """Lazy shared fan-out lane for per-shard fills (one per process,
    atexit-drained). DISTINCT from `parquet.io_executor()` on purpose:
    the fills block on that pool internally."""
    global _pool, _pool_lock
    import threading
    if _pool_lock is None:
        _pool_lock = threading.Lock()
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                from concurrent.futures import ThreadPoolExecutor
                _pool = ThreadPoolExecutor(
                    max_workers=8, thread_name_prefix="hs-spmd-read")
                import atexit
                atexit.register(shutdown_read_pool)
    return _pool


def shutdown_read_pool(wait: bool = True) -> None:
    """Drain + stop the fill fan-out lane (idempotent; lazily
    re-created on the next born-sharded read)."""
    global _pool
    pool, _pool = _pool, None
    if pool is not None:
        pool.shutdown(wait=wait)


def _valid_mask(rows: int, C: int):
    import jax.numpy as jnp
    return jnp.arange(C) < rows


def _on_device(device, fn):
    """Run an eager constant-producing computation ON `device` — device-
    local array creation, no link traffic (XLA materializes the fill on
    the target device)."""
    import jax
    with jax.default_device(device):
        return fn()


def _shard_validity(shard: dict, name: str, C: int, device):
    v = shard["columns"][name].get("validity")
    if v is not None:
        return v
    return _on_device(device, partial(_valid_mask, C, C))


def _fill_device_shard(files: List[str], cols, schema, rows: int, C: int,
                       device, global_dicts=None, skip: int = 0,
                       windowed: bool = False) -> Tuple[dict, int]:
    """Cold fill of one device's bucket range: parquet decode, pad to
    the common per-shard capacity on the host, place every column onto
    THIS device through the transfer engine's fill lane. String columns
    decode to their LOCAL per-range dictionary and remap to the global
    codes on the host (`_remap_to_global`) — only int32 code lanes ever
    cross the link. A virtual-sub-shard window (`skip` > 0 or `rows`
    short of the decoded count) slices the decoded table before
    staging, so a hot bucket split across shards ships each shard only
    its slice. Returns (payload, resident bytes)."""
    from hyperspace_tpu.io import parquet, transfer

    out_schema = schema.select(cols)
    if not files or rows == 0:
        # Empty range: all-padding shard, created device-locally.
        import jax.numpy as jnp

        from hyperspace_tpu.io.columnar import carried_np_dtype
        cols_out = {}
        for f in out_schema.fields:
            dt = carried_np_dtype(f.dtype)
            cols_out[f.name] = {
                "data": _on_device(device, partial(jnp.zeros, C, dt)),
                "validity": None}
        payload = {"columns": cols_out, "rows": 0}
        return payload, _payload_nbytes(payload)

    table = parquet.read_table(files, columns=list(cols))
    if table.num_rows < skip + rows or (not windowed
                                        and table.num_rows != rows):
        raise HyperspaceException(
            f"Born-sharded read expected {rows} rows (skip {skip}), "
            f"decoded {table.num_rows} — footer metadata and data "
            f"disagree.")
    if skip or table.num_rows != rows:
        table = table.slice(skip, rows)
    from hyperspace_tpu.io import columnar
    host = columnar.from_arrow(table, out_schema, device=False)
    if global_dicts:
        host = _remap_to_global(host, global_dicts)
    jobs = []
    for f in out_schema.fields:
        col = host.columns[f.name]
        src = col.carry
        data = np.zeros((C,) + src.shape[1:], dtype=src.dtype)
        data[:rows] = src
        entry = {"data": data}
        if col.validity is not None:
            v = np.zeros(C, dtype=bool)
            v[:rows] = col.validity
            entry["validity"] = v
        jobs.append((f.name, entry))
    engine = transfer.get_engine()
    placed = engine.put_group([partial(lambda e: e, entry)
                               for _name, entry in jobs],
                              device=device, tag="fill")
    cols_out = {name: {"data": entry["data"],
                       "validity": entry.get("validity")}
                for (name, _), entry in zip(jobs, placed)}
    payload = {"columns": cols_out, "rows": rows}
    return payload, _payload_nbytes(payload)


def _payload_nbytes(payload: dict) -> int:
    total = 0
    for entry in payload["columns"].values():
        total += int(getattr(entry["data"], "nbytes", 0))
        if entry.get("validity") is not None:
            total += int(getattr(entry["validity"], "nbytes", 0))
    return total


# ---------------------------------------------------------------------------
# The single-program SPMD join
# ---------------------------------------------------------------------------


def _key_arrays(batch: ColumnBatch, names: Sequence[str]):
    """(data arrays, combined key validity | None) for the key columns.
    String key columns contribute their int32 CODE lanes; cross-side
    comparability comes from the rank-remap tables the join program
    applies in-program (`string_remap_tables`)."""
    import jax.numpy as jnp

    datas = []
    ok = None
    for name in names:
        col = batch.column(name)
        datas.append(jnp.asarray(col.data))
        if col.validity is not None:
            v = jnp.asarray(col.validity)
            ok = v if ok is None else (ok & v)
    return datas, ok


def _dict_fingerprint(dictionary) -> tuple:
    """Content identity of a sorted dictionary (entry count + md5 of the
    packed values) — the cache key of cross-side remap tables. Content
    keying is strictly stronger than version keying: two committed
    versions with identical dictionaries share one resident table."""
    import hashlib

    d = np.ascontiguousarray(np.asarray(dictionary))
    return (int(d.shape[0]), hashlib.md5(d.tobytes()).hexdigest())


def string_remap_tables(lcol: DeviceColumn, rcol: DeviceColumn,
                        conf=None):
    """THE dictionary-remap constructor for the SPMD lane (lint-enforced:
    `check_metrics_coverage.py::check_string_remap_seam` bans calls
    outside this module's consumers). Builds the compact int32
    local-code -> pair-merged-rank tables that make two sides' string
    codes mutually comparable inside the single jitted SMJ program —
    derived from the host dictionaries, NEVER shipping string bytes:
    the tables ride one H2D put cold, are cached content-keyed in the
    segment cache, and replicate into the program over ICI. Warm
    repeats serve them straight from the cache
    (`spmd.strings.remap_cache_hits`) with zero link traffic."""
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io import segcache, transfer
    from hyperspace_tpu.io.columnar import _merged_dictionary

    key = ("spmd-remap", _dict_fingerprint(lcol.dictionary),
           _dict_fingerprint(rcol.dictionary))
    filled: List[bool] = []

    def fill():
        filled.append(True)
        _merged, (ra, rb), _hashes = _merged_dictionary(
            [lcol.dictionary, rcol.dictionary], device=False)
        engine = transfer.get_engine()
        payload = {"l": engine.put(ra), "r": engine.put(rb)}
        return payload, max(int(ra.nbytes) + int(rb.nbytes), 1)

    payload = segcache.get_cache().get_or_fill(key, fill, conf=conf)
    if not filled:
        telemetry.get_registry().counter(
            "spmd.strings.remap_cache_hits").inc()
    return payload["l"], payload["r"]


def string_like_mask(col: DeviceColumn, pattern_regex: str, conf=None):
    """THE device-side LIKE lane for dictionary-encoded strings: a
    boolean membership mask over the column's sorted dictionary —
    mask[code] == pattern matches dictionary[code] — computed ONCE on
    the host (anchored regex over the distinct values, O(dictionary)),
    shipped over the link once, and cached content-keyed in the segment
    cache exactly like the PR-13 rank-remap tables. The jitted filter
    program then evaluates LIKE as one `take(mask, codes)` — warm
    repeats serve the mask straight from HBM
    (`spmd.strings.like_mask_cache_hits`) with zero host regex work and
    zero link traffic, instead of round-tripping every evaluation
    through the generic host regex + fresh code-list H2D."""
    import re as _re

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io import segcache, transfer

    key = ("spmd-like", _dict_fingerprint(col.dictionary), pattern_regex)
    filled: List[bool] = []

    def fill():
        filled.append(True)
        rx = _re.compile(pattern_regex, _re.DOTALL)
        d = np.asarray(col.dictionary)
        mask = np.asarray([rx.fullmatch(str(v)) is not None for v in d],
                          dtype=bool)
        return {"mask": mask}, max(int(mask.nbytes), 1)

    cache = segcache.get_cache()
    payload = cache.get_or_fill(key, fill, conf=conf)
    if not filled:
        telemetry.get_registry().counter(
            "spmd.strings.like_mask_cache_hits").inc()
    import jax

    try:
        tracing = not jax.core.trace_state_clean()
    except Exception:
        tracing = True
    if tracing:
        # Inside a jit trace the engine's chunked put would itself be
        # TRACED and the resulting tracer would escape into the cache
        # (a leak); the host mask constant-folds into the program
        # instead, and the next eager caller promotes it below.
        return payload["mask"]
    # The device copy is its OWN cache entry, sized by the device bytes
    # — it rides the cache's fill/accounting/eviction machinery rather
    # than being patched onto the host entry's payload (which would
    # leave its HBM bytes uncharged and race concurrent readers).
    host_mask = payload["mask"]

    def fill_dev():
        dev = transfer.get_engine().put(host_mask)
        return {"dev": dev}, max(int(dev.nbytes), 1)

    return cache.get_or_fill(("spmd-like-dev",) + key[1:], fill_dev,
                             conf=conf)["dev"]


def _string_key_plan(left: "ShardedBatch", right: "ShardedBatch",
                     left_keys: Sequence[str],
                     right_keys: Sequence[str], need_hashes: bool,
                     conf=None):
    """Per-key string unification plan for the SPMD join: which key
    positions are strings (`remap_idx`, static program structure), their
    rank-remap tables, and — when an in-program repartition will route
    the right side — the right dictionaries' value-hash tables (bucket
    identity must hash the VALUE, exactly like the build)."""
    import jax.numpy as jnp

    idx: List[int] = []
    l_remaps: List = []
    r_remaps: List = []
    r_hashes: List = []
    for i, (lk, rk) in enumerate(zip(left_keys, right_keys)):
        lcol = left.batch.column(lk)
        rcol = right.batch.column(rk)
        if lcol.is_string != rcol.is_string:
            raise HyperspaceException(
                f"Join key type mismatch: {lk} vs {rk}")
        if not lcol.is_string:
            continue
        ra, rb = string_remap_tables(lcol, rcol, conf=conf)
        idx.append(i)
        l_remaps.append(ra)
        r_remaps.append(rb)
        if need_hashes:
            hi, lo = rcol.dict_hashes
            r_hashes.append((jnp.asarray(hi), jnp.asarray(lo)))
    return (tuple(idx), tuple(l_remaps), tuple(r_remaps),
            tuple(r_hashes))


def _promote_pairs(l_datas, r_datas):
    import jax.numpy as jnp
    lp, rp = [], []
    for ld, rd in zip(l_datas, r_datas):
        if ld.dtype != rd.dtype:
            common = jnp.promote_types(ld.dtype, rd.dtype)
            ld, rd = ld.astype(common), rd.astype(common)
        lp.append(ld)
        rp.append(rd)
    return lp, rp


def _side_lane_chain(datas):
    lanes = []
    for d in datas:
        lanes.extend(keymod.key_lanes(d))
    return lanes


def _route_local(arrs, dest, n_peers: int, capacity: int,
                 axis: str = SHARD_AXIS):
    """Route local rows to their destination peers through ONE
    all_to_all over the named mesh `axis` (shard_map-local shapes):
    stable sort by dest, scatter into the [n_peers, capacity] send
    buffer, swap. The collective is CONFINED to the axis's device
    groups — within-slice hops ride ICI, cross-slice hops ride DCN.
    Returns (routed arrays [n_peers*capacity, ...], overflow count).
    Mirrors `parallel/build._route_stage`."""
    import jax
    import jax.numpy as jnp

    n_local = dest.shape[0]
    iota = jnp.arange(n_local, dtype=jnp.int32)
    dest_sorted, perm = jax.lax.sort([dest, iota], num_keys=1,
                                     is_stable=True)
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
        buf = jnp.zeros((n_peers * capacity + 1,) + src.shape[1:],
                        dtype=src.dtype)
        buf = buf.at[slot].set(src, mode="drop")
        send = buf[:n_peers * capacity].reshape(
            (n_peers, capacity) + src.shape[1:])
        recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        return recv.reshape((n_peers * capacity,) + src.shape[1:])

    return [route(a) for a in arrs], overflow


def _route_slabs(mesh, route_capacity: int):
    """Static slab geometry of one in-program repartition on `mesh`:
    (per-shard routed rows, cap_ici, cap_dcn). Flat mesh: one
    all_to_all over all S peers. 2-axis mesh: two axis-confined hops —
    ICI to the owner's position within the source slice, then DCN to
    the owner slice (the build's `_shard_step` discipline) — each with
    its own per-peer capacity; cap_dcn sizes from the stage-1 output
    with the same headroom factor, and the caller's overflow-retry
    doubling grows both together."""
    S = total_shards(mesh)
    d = dcn_size(mesh)
    if d == 1:
        return S * route_capacity, route_capacity, 0
    # Stage 1 fans over n_ici peers (not S), so its per-peer slab is d
    # times the flat per-peer slab for the same expected row volume.
    # Stage 2 receives at most n_ici * cap_ici rows per shard and fans
    # over d slice peers; cap_ici already carries the headroom factor,
    # so stage 2 inherits it rather than compounding it (a second
    # factor would double the slab memory AND make the DCN byte share
    # a statement about the headroom constant instead of the routing —
    # each row crosses DCN at most once, so the share must sit ~1/2).
    # Cross-slice skew beyond the inherited headroom lands in the
    # overflow-retry doubling like every other capacity here.
    ici = ici_size(mesh)
    cap_ici = route_capacity * d
    cap_dcn = max(16, -(-ici * cap_ici // d))
    return d * cap_dcn, cap_ici, cap_dcn


def _record_repartition_bytes(mesh, route_capacity: int,
                              per_row_bytes: int) -> None:
    """Attribute one repartition dispatch's exchange volume to the
    link that carries it: `spmd.repartition.ici.bytes` for the
    within-slice hop, `spmd.repartition.dcn.bytes` for the cross-slice
    hop. The figure is the full send-buffer volume across the mesh
    (capacity slabs, padding included) — a static upper bound the
    regression differ can compare round over round, not a measured
    wire count."""
    from hyperspace_tpu import telemetry

    reg = telemetry.get_registry()
    S = total_shards(mesh)
    d = dcn_size(mesh)
    _rows, cap_ici, cap_dcn = _route_slabs(mesh, route_capacity)
    if d == 1:
        reg.counter("spmd.repartition.ici.bytes").inc(
            S * S * cap_ici * per_row_bytes)
        return
    ici = ici_size(mesh)
    reg.counter("spmd.repartition.ici.bytes").inc(
        S * ici * cap_ici * per_row_bytes)
    reg.counter("spmd.repartition.dcn.bytes").inc(
        S * d * cap_dcn * per_row_bytes)


def _repartition_lanes(lanes, hash_lanes, null, valid, gid,
                       num_buckets_to: int, mesh, route_capacity: int):
    """In-program re-bucket of one side's KEY LANES (+ null/valid masks
    and original-row ids): each row moves to the shard owning its
    bucket under the TARGET bucket count. `hash_lanes` carry the BUCKET
    identity (the build's value-hash lanes — for string keys the
    gathered dictionary value hashes, NOT the rank lanes used for
    matching) and are consumed for routing only, never routed. Runs as
    a shard_map stage inside the caller's jitted program — payload
    never routes, nothing touches the host.

    Topology-aware: on a flat mesh the route is ONE all_to_all over
    ICI; on a 2-axis (dcn, shard) mesh it is TWO axis-confined hops —
    stage 1 over ICI to the owner's position within the source slice,
    stage 2 over DCN to the owner slice, carrying the owner id along
    (the build exchange's `_shard_step` discipline: each hop changes
    exactly one mesh coordinate, and the heavy fan-out stays on the
    fast axis). Returns ([S*C'] lanes..., null, valid, gid,
    route_overflow); C' comes from `_route_slabs`."""
    import jax.numpy as jnp

    n_shards = total_shards(mesh)
    n_dcn = dcn_size(mesh)
    n_ici = ici_size(mesh)
    _rows, cap_ici, cap_dcn = _route_slabs(mesh, route_capacity)
    rows_spec = row_spec(mesh)
    k = len(lanes)
    kh = len(hash_lanes)

    def body(*flat):
        lanes_l = list(flat[:k])
        hlanes_l = list(flat[k:k + kh])
        null_l, valid_l, gid_l = flat[-3], flat[-2], flat[-1]
        from hyperspace_tpu.ops.hash_partition import flat_hash32
        zeroed = [jnp.where(null_l | ~valid_l, jnp.uint32(0),
                            lane.astype(jnp.uint32))
                  for lane in hlanes_l]
        h = flat_hash32(zeroed)
        bucket = (h % jnp.uint32(num_buckets_to)).astype(jnp.int64)
        owner = bucket_owner(bucket, num_buckets_to,
                             n_shards).astype(jnp.int32)
        if n_dcn == 1:
            dest = jnp.where(valid_l, owner, jnp.int32(n_shards))
            routed, overflow = _route_local(
                lanes_l + [null_l, valid_l, gid_l], dest, n_shards,
                cap_ici)
            return tuple(routed) + (overflow.reshape(1),)
        # Stage 1 (ICI): to the owner's position within THIS slice,
        # owner id riding along for stage 2.
        dest1 = jnp.where(valid_l, owner % n_ici, jnp.int32(n_ici))
        routed1, ovf1 = _route_local(
            lanes_l + [null_l, valid_l, gid_l, owner], dest1, n_ici,
            cap_ici, axis=SHARD_AXIS)
        valid1 = routed1[k + 1]
        owner1 = routed1[-1]
        # Stage 2 (DCN): to the owner slice; empty stage-1 slots carry
        # valid=False (zero-init buffers) and drop here.
        dest2 = jnp.where(valid1, owner1 // n_ici, jnp.int32(n_dcn))
        routed, ovf2 = _route_local(routed1[:-1], dest2, n_dcn,
                                    cap_dcn, axis=DCN_AXIS)
        return tuple(routed) + ((ovf1 + ovf2).reshape(1),)

    flat_in = tuple(lanes) + tuple(hash_lanes) + (null, valid, gid)
    out = compat_shard_map(
        body, mesh=mesh,
        in_specs=tuple(rows_spec for _ in flat_in),
        out_specs=tuple([rows_spec] * (k + 4)),
        check_vma=False)(*flat_in)
    routed = out[:-1]
    overflow = jnp.sum(out[-1])
    return (list(routed[:k]), routed[k], routed[k + 1], routed[k + 2],
            overflow)


def _match(l_lanes2d, r_lanes2d, l_null, r_null, l_pad, r_pad, r_gid,
           left_outer: bool, need_right: bool):
    """The counting match over the combined [S, T] layout (T = Cl + Cr).
    Per shard: ONE stable sort by (pad, null, *lanes, side, slot), run
    grouping from adjacent lane differences, right-run brackets by the
    one-chip match's scans along each shard's row
    (`ops/join.run_brackets`: no gather and no flip over the sorted
    rows), and each left element's window [starts, starts + counts) of
    the shard's output. Nothing here has a shape that depends on the
    answer: the pair expansion (`_expand`) is a second program, sized
    from `shard_total` once the host has read it.

    `r_gid` maps a right slot to its ORIGINAL global row id (identity
    for co-bucketed sides; the routed ids after an in-program
    repartition). Returns (starts, rights, rstart, pos_s [S, T],
    shard_total [S], right_unmatched_gid [S, T] | None, un_counts [S] |
    None, is_left, matchable)."""
    import jax
    import jax.numpy as jnp

    S, Cl = l_pad.shape
    Cr = r_pad.shape[1]
    T = Cl + Cr
    lanes2d = [jnp.concatenate([ll, rl], axis=1)
               for ll, rl in zip(l_lanes2d, r_lanes2d)]
    pad = jnp.concatenate([l_pad, r_pad], axis=1).astype(jnp.int32)
    null = jnp.concatenate([l_null, r_null], axis=1).astype(jnp.int32)
    side = jnp.broadcast_to(
        jnp.concatenate([jnp.zeros(Cl, jnp.int32),
                         jnp.ones(Cr, jnp.int32)]), (S, T))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
    results = jax.lax.sort([pad, null, *lanes2d, side, pos],
                           num_keys=3 + len(lanes2d), is_stable=True,
                           dimension=1)
    pad_s, null_s = results[0], results[1]
    lanes_s = results[2:-2]
    side_s = results[-2]
    pos_s = results[-1]

    differs = jnp.zeros((S, T - 1), dtype=bool)
    for k in lanes_s:
        differs = differs | (k[:, 1:] != k[:, :-1])
    differs = differs | (null_s[:, 1:] | null_s[:, :-1]
                         | pad_s[:, 1:] | pad_s[:, :-1]).astype(bool)
    rights, rstart, run_last = run_brackets(differs, side_s)

    is_left = (side_s == 0) & (pad_s == 0)
    matchable = is_left & (null_s == 0)
    counts = jnp.where(matchable, rights, 0)
    if left_outer:
        counts = jnp.maximum(counts, is_left.astype(counts.dtype))
    counts64 = counts.astype(jnp.int64)
    starts = jnp.cumsum(counts64, axis=1) - counts64  # per-shard excl.
    shard_total = starts[:, -1] + counts64[:, -1]

    un_gid_sorted = un_counts = None
    if need_right:
        run_start = jnp.concatenate(
            [jnp.ones((S, 1), dtype=bool), differs], axis=1)
        run_first = jax.lax.cummax(
            jnp.where(run_start, jnp.arange(T, dtype=jnp.int32), 0),
            axis=1)
        lefts = run_last - run_first + 1 - rights
        r_unmatched = ((side_s == 1) & (pad_s == 0)
                       & ((null_s == 1) | (lefts == 0)))
        gid_sorted = jnp.take_along_axis(
            r_gid, jnp.clip(pos_s - Cl, 0, Cr - 1), axis=1)
        un_gid = jnp.where(r_unmatched, gid_sorted, jnp.int64(-1))
        # Per-shard compaction IN-PROGRAM (unmatched gids first): the
        # host then assembles the output from contiguous prefixes with
        # one gather — no data-dependent-shaped eager op ever touches
        # the sharded arrays (each such op would recompile per size).
        un_sorted = jax.lax.sort(
            [(un_gid < 0).astype(jnp.int32), un_gid],
            num_keys=1, is_stable=True, dimension=1)
        un_gid_sorted = un_sorted[1]
        un_counts = jnp.sum(un_gid >= 0, axis=1)
    return (starts, rights, rstart, pos_s, shard_total, un_gid_sorted,
            un_counts, is_left, matchable)


def _expand(starts, rights, rstart, pos_s, r_gid, Cl: int, cap: int):
    """The pair expansion over [S, cap] output slots, from the match
    state: output slot j of shard s belongs to the left element whose
    [starts, starts + counts) window covers j. `cap` is at or above
    every shard's total (`_expand_rung` of the largest), so each
    shard's pairs are the contiguous prefix of its row and nothing can
    overflow. Returns (li, ri) [S, cap] int64: indices into the flat
    padded left space and ORIGINAL right row ids (-1: no match, a left
    outer row)."""
    import jax
    import jax.numpy as jnp

    S, T = starts.shape
    Cr = r_gid.shape[1]
    take = jnp.take_along_axis
    slots = jnp.arange(cap, dtype=jnp.int64)
    row = jax.vmap(lambda st: jnp.searchsorted(st, slots,
                                               side="right"))(starts) - 1
    row = jnp.clip(row, 0, T - 1).astype(jnp.int32)
    offset = (slots[None, :] - take(starts, row, axis=1)).astype(jnp.int32)
    l_slot = take(pos_s, row, axis=1)
    li = l_slot.astype(jnp.int64) \
        + (jnp.arange(S, dtype=jnp.int64) * Cl)[:, None]
    matched = offset < take(rights, row, axis=1)
    r_sorted = jnp.clip(take(rstart, row, axis=1) + offset, 0, T - 1)
    r_slot = take(pos_s, r_sorted, axis=1) - Cl
    ri = jnp.where(matched,
                   take(r_gid, jnp.clip(r_slot, 0, Cr - 1), axis=1),
                   jnp.int64(-1))
    return li, ri


# Per-device dispatch serialization on EMULATED meshes: the CPU
# backend drives every virtual device from one shared runtime, and two
# concurrent multi-device programs whose device sets OVERLAP can
# interleave their per-device tasks into a collective-rendezvous
# inversion (A's device-0 step waits on A's device-1 step queued behind
# B's device-1 step waiting on B's device-0 — a deadlock real hardware
# cannot hit because each device's queue serializes executions). One
# lock per DEVICE, acquired in sorted device-id order, is exactly the
# device-queue semantic: programs on disjoint replica slices still run
# concurrently — which is the whole scale-out story — while any two
# dispatches sharing a device serialize (including a full-mesh program
# — a build, repartition, or the replica-exempt batched lane — against
# a replica-pinned slice program: their sets overlap without being
# equal, so a per-SET lock would not order them). Sorted-order
# acquisition makes the multi-lock hold cycle-free. Real (non-CPU)
# backends skip the lock: their device queues already provide it, and
# host-side pipelining across queries must not be lost.
_DEVICE_LOCKS: Dict[int, object] = {}
_DEVICE_LOCKS_GUARD = None


def dispatch_guard(mesh):
    """THE per-device dispatch lock set (reentrant; see comment above).
    Callers driving multi-device work OUTSIDE this module's entry
    points (`assemble_join_output` gathers, result materialization of a
    concurrent serving loop) hold it around the whole query's device
    section; on non-CPU backends it is a no-op."""
    import contextlib
    import threading

    import jax

    if jax.default_backend() != "cpu":
        return contextlib.nullcontext()
    global _DEVICE_LOCKS_GUARD
    if _DEVICE_LOCKS_GUARD is None:
        _DEVICE_LOCKS_GUARD = threading.Lock()
    tag = mesh_device_tag(mesh)
    with _DEVICE_LOCKS_GUARD:
        locks = []
        for did in sorted(set(tag)):
            lock = _DEVICE_LOCKS.get(did)
            if lock is None:
                lock = threading.RLock()
                _DEVICE_LOCKS[did] = lock
            locks.append(lock)

    @contextlib.contextmanager
    def hold():
        with contextlib.ExitStack() as stack:
            for lock in locks:
                stack.enter_context(lock)
            yield

    return hold()


_dispatch_guard = dispatch_guard


# Program cache: jax.Mesh hashes by value (devices + axis names), so the
# per-query `distribution_mesh()` reconstruction still HITS here — a warm
# repeat join re-dispatches the already-compiled program instead of
# retracing (the retrace counters in `instrumented_jit` pin this).
_PROGRAMS: Dict[tuple, object] = {}


def _cached_program(key: tuple, builder):
    prog = _PROGRAMS.get(key)
    if prog is None:
        prog = builder()
        if len(_PROGRAMS) > 256:  # runaway-shape backstop
            _PROGRAMS.clear()
        _PROGRAMS[key] = prog
    return prog


def _match_program(mesh, n_keys: int, Cl: int, Cr: int,
                   left_outer: bool, need_right: bool,
                   repartition_to: Optional[int], route_capacity: int,
                   membership: Optional[str] = None,
                   remap_idx: Tuple[int, ...] = ()):
    """Compile the join's MATCH as one jitted SPMD program: (optional)
    in-program ICI repartition of the right side, lane decomposition,
    counting match. All shape parameters are static and none depends on
    the answer; the program returns the match state device-resident
    (starts, rights, rstart, pos_s [S, T], the right slots' original
    row ids [S, Cr']), the compacted unmatched-right ids, and the small
    vectors the host reads in ONE sync (per-shard totals, unmatched
    counts, the route-overflow scalar). The pair expansion is
    `_expand_program`, sized from those totals.

    `membership`: None (pairs) or "semi"/"anti" — membership reads the
    match-phase masks and compacts hit LEFT indices per shard
    in-program; it returns (hits, hit_counts, route_overflow) and never
    meets an expansion.

    `remap_idx` marks the STRING key positions: those keys arrive as
    int32 code lanes plus per-side rank-remap tables
    (`string_remap_tables`), applied as in-program takes so equal
    values compare equal across the two dictionaries — the tables are
    the only cross-side state, replicated over ICI by GSPMD; string
    bytes never enter the program. When the right side repartitions,
    its string keys route by their gathered dictionary VALUE hashes
    (the build's bucket identity), not the rank lanes."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.telemetry import instrumented_jit

    S = total_shards(mesh)

    def build():
        # Named for the trace (the device's program is
        # `jit_spmd_join_match`); every op of it under the device scope
        # `hs.mesh.join`.
        def spmd_join_match(l_datas, l_ok, l_valid, r_datas, r_ok,
                            r_valid, l_remaps, r_remaps, r_hash_tables):
            l_d = list(l_datas)
            r_d = list(r_datas)
            r_hash_sub = {}
            for j, ki in enumerate(remap_idx):
                if repartition_to is not None:
                    hi, lo = r_hash_tables[j]
                    r_hash_sub[ki] = [jnp.take(hi, r_d[ki]),
                                      jnp.take(lo, r_d[ki])]
                l_d[ki] = jnp.take(l_remaps[j], l_d[ki])
                r_d[ki] = jnp.take(r_remaps[j], r_d[ki])
            l_d, r_d = _promote_pairs(l_d, r_d)
            l_lanes = [x.reshape(S, Cl) for x in _side_lane_chain(l_d)]
            l_pad = ~l_valid.reshape(S, Cl)
            l_null = (jnp.zeros((S, Cl), bool) if l_ok is None
                      else (~l_ok.reshape(S, Cl)) & ~l_pad)

            r_lanes = []
            r_hash_lanes = []
            for ki, d in enumerate(r_d):
                match_lanes = keymod.key_lanes(d)
                r_lanes.extend(match_lanes)
                r_hash_lanes.extend(r_hash_sub.get(ki, match_lanes))
            r_null_f = (jnp.zeros(r_valid.shape[0], bool) if r_ok is None
                        else ~r_ok)
            r_gid_f = jnp.arange(r_valid.shape[0], dtype=jnp.int64)
            route_ovf = jnp.int64(0)
            if repartition_to is not None:
                r_lanes, r_null_f, r_valid_f, r_gid_f, route_ovf = \
                    _repartition_lanes(r_lanes, r_hash_lanes, r_null_f,
                                       r_valid, r_gid_f, repartition_to,
                                       mesh, route_capacity)
                Cr_eff = _route_slabs(mesh, route_capacity)[0]
            else:
                r_valid_f = r_valid
                Cr_eff = Cr
            r_lanes2d = [x.reshape(S, Cr_eff) for x in r_lanes]
            r_pad = ~r_valid_f.reshape(S, Cr_eff)
            r_null2d = r_null_f.reshape(S, Cr_eff) & ~r_pad
            r_gid2d = r_gid_f.reshape(S, Cr_eff)

            (starts, rights, rstart, pos_s, shard_total, un_gid,
             un_counts, is_left, matchable) = _match(
                l_lanes, r_lanes2d, l_null, r_null2d, l_pad, r_pad,
                r_gid2d, left_outer, need_right)
            if membership is not None:
                # Semi/anti over the match masks: per-shard in-program
                # compaction (hits first), host gathers the prefixes.
                hit = (is_left & (rights == 0) if membership == "anti"
                       else matchable & (rights > 0))
                li2d = (jnp.clip(pos_s, 0, Cl - 1).astype(jnp.int64)
                        + (jnp.arange(S, dtype=jnp.int64) * Cl)[:, None])
                hit_sorted = jax.lax.sort(
                    [(~hit).astype(jnp.int32), li2d], num_keys=1,
                    is_stable=True, dimension=1)
                hit_counts = jnp.sum(hit, axis=1)
                return hit_sorted[1], hit_counts, route_ovf
            if un_counts is None:
                un_gid = jnp.zeros((S, 1), dtype=jnp.int64)
                un_counts = jnp.zeros(S, dtype=jnp.int64)
            # The state stays where the match made it, a row a shard
            # (left alone, GSPMD replicates the co-bucketed side's
            # identity `r_gid2d` on every chip).
            state = tuple(
                jax.lax.with_sharding_constraint(x, shard_rows(mesh))
                for x in (starts, rights, rstart, pos_s, r_gid2d))
            return state, un_gid, (shard_total, un_counts, route_ovf)

        return instrumented_jit("mesh.spmd_join_match", spmd_join_match,
                                scope="hs.mesh.join")

    key = ("join_match", mesh, n_keys, Cl, Cr, left_outer, need_right,
           repartition_to, route_capacity, membership, remap_idx)
    return _cached_program(key, build)


# The expansion's ladder: the per-shard output slots are the smallest
# power of two (from 16) at or above the largest per-shard total the
# match found, so answers of nearby sizes share one compiled program
# and between half and all of the slots hold a pair.
_expand_rung = next_pow2


def _expand_program(Cl: int, cap: int):
    """Compile the join's pair EXPANSION as one jitted SPMD program over
    `cap` output slots a shard (a rung of the ladder, part of the key):
    match state in, (li, ri) [S, cap] out, nothing read back. The mesh
    and the row sharding are the state's own."""
    from hyperspace_tpu.telemetry import instrumented_jit

    def build():
        # `jit_spmd_join_expand` in a trace, under the match's scope.
        def spmd_join_expand(starts, rights, rstart, pos_s, r_gid):
            return _expand(starts, rights, rstart, pos_s, r_gid, Cl, cap)

        return instrumented_jit("mesh.spmd_join_expand", spmd_join_expand,
                                scope="hs.mesh.join")

    return _cached_program(("join_expand", Cl, cap), build)


def _prefix_index(counts, width: int) -> np.ndarray:
    """Flat gather index over per-shard contiguous prefixes: shard s
    contributes rows [s*width, s*width + counts[s])."""
    counts = np.asarray(counts, dtype=np.int64)
    return np.concatenate(
        [s * width + np.arange(int(c)) for s, c in enumerate(counts)]
    ) if counts.sum() else np.zeros(0, dtype=np.int64)


_prefix_gather_jit = None
_prefix_gather_i32_jit = None


def _gather_prefixes(arrays, counts, width: int, as_int32: bool = False):
    """ONE fused device gather of the per-shard prefixes (the output
    sides stay device-resident; only the [S] count vector came to the
    host). The flatten, the take, and — with `as_int32` — the output
    cast all trace into a SINGLE jitted dispatch: on the warm serving
    path every eager primitive here was a measurable per-query python
    dispatch (reshape x2 + take + astype x2 ~ a third of a tiny warm
    join's wall), and fusing them lifts the concurrent-QPS ceiling of
    small replica-routed queries."""
    global _prefix_gather_jit, _prefix_gather_i32_jit
    import jax.numpy as jnp

    idx = _prefix_index(counts, width)
    if not len(idx):
        dt = jnp.int32 if as_int32 else None
        return tuple(jnp.zeros(0, dtype=dt or a.dtype) for a in arrays)
    if _prefix_gather_jit is None:
        from hyperspace_tpu.telemetry import instrumented_jit

        @instrumented_jit("mesh.spmd_gather", scope="hs.gather")
        def _take_flat(arrs, ix):
            return tuple(jnp.take(a.reshape(-1), ix) for a in arrs)

        @instrumented_jit("mesh.spmd_gather_i32", scope="hs.gather")
        def _take_flat_i32(arrs, ix):
            return tuple(jnp.take(a.reshape(-1), ix).astype(jnp.int32)
                         for a in arrs)

        _prefix_gather_jit = _take_flat
        _prefix_gather_i32_jit = _take_flat_i32
    fn = _prefix_gather_i32_jit if as_int32 else _prefix_gather_jit
    return fn(tuple(arrays), idx)


def _route_cap(right: ShardedBatch) -> int:
    """First-attempt per-peer slab capacity for the in-program
    repartition (the build's `_stage_capacity` sizing)."""
    S = right.n_shards
    return max(16, int(right.rows_per_shard / S * CAPACITY_FACTOR))


def _join_inputs(sh: ShardedBatch, keys: Sequence[str]):
    datas, ok = _key_arrays(sh.batch, keys)
    return tuple(datas), ok, sh.row_valid


def _shard_rows_attribution(left: ShardedBatch, right: ShardedBatch):
    """Per-shard TRUE input rows (the load-balance attribution the mesh
    telemetry reports, legacy-event parity): from the bucket histograms
    when known, else the padded per-shard capacities."""
    S = left.n_shards
    out = []
    for sh in (left, right):
        if sh.lengths is not None:
            segs = shard_row_segments(sh.lengths, S)
            out.append([e - s for s, e in segs])
        else:
            out.append([sh.rows_per_shard] * S)
    return [l + r for l, r in zip(*out)]


def _check_one_mesh(left: ShardedBatch, right: ShardedBatch):
    if left.mesh is not right.mesh and \
            mesh_device_list(left.mesh) != mesh_device_list(right.mesh):
        raise HyperspaceException("sharded join requires one mesh")


def _repartition_target(left: ShardedBatch, right: ShardedBatch):
    """(target bucket count, first-attempt route capacity) when the
    right side must re-bucket in-program; (None, 16) for co-bucketed
    sides. Works on flat AND 2-axis meshes — `_repartition_lanes`
    routes hierarchically (ICI within the slice, one DCN hop across)
    on the latter."""
    if right.num_buckets == left.num_buckets:
        return None, 16
    return left.num_buckets, _route_cap(right)


def sharded_join_indices(left: ShardedBatch, right: ShardedBatch,
                         left_keys: Sequence[str],
                         right_keys: Sequence[str],
                         how: str = "inner", conf=None):
    """Join-pair indices over two born-sharded sides as TWO jitted SPMD
    programs with one host readback between them: the match
    (`_match_program`: in-program ICI repartition on bucket-count
    mismatch, counting match), ONE read of the per-shard totals, and
    the expansion (`_expand_program`) over the ladder's rung just above
    the largest total — sized by the answer, so it cannot overflow.
    Only a route overflow of the repartition re-runs the match.
    Returns (li, ri) device int32 arrays indexing the FLAT padded row
    spaces of the two sides. `how`: inner / left_outer / full_outer
    (callers swap sides for right_outer)."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu import telemetry

    if how not in ("inner", "left_outer", "full_outer"):
        raise HyperspaceException(
            f"sharded join supports inner/left_outer/full_outer; "
            f"got {how}.")
    if left.split_plan is not None and how == "full_outer":
        # Replicated right rows break per-shard unmatched-right
        # uniqueness; callers route full_outer off the sub-shard lane.
        raise HyperspaceException(
            "virtual sub-shard joins support inner/left_outer only.")
    _check_one_mesh(left, right)
    mesh = left.mesh
    S = total_shards(mesh)
    left_outer = how in ("left_outer", "full_outer")
    need_right = how == "full_outer"
    repartition_to, route_capacity = _repartition_target(left, right)
    l_in = _join_inputs(left, left_keys)
    r_in = _join_inputs(right, right_keys)
    remap_idx, l_remaps, r_remaps, r_hashes = _string_key_plan(
        left, right, left_keys, right_keys,
        need_hashes=repartition_to is not None, conf=conf)

    reg = telemetry.get_registry()
    tracer = telemetry.tracer()
    span_ts = tracer.now_us() if tracer is not None else 0.0
    attempt = 0
    with _dispatch_guard(mesh):
        while True:
            attempt += 1
            match = _match_program(mesh, len(left_keys),
                                   left.rows_per_shard,
                                   right.rows_per_shard, left_outer,
                                   need_right, repartition_to,
                                   route_capacity, remap_idx=remap_idx)
            if repartition_to is not None:
                # Slab-volume attribution of this attempt's in-program
                # exchange, split by the link that carries each hop.
                _record_repartition_bytes(
                    mesh, route_capacity, 8 * len(right_keys) + 10)
            with telemetry.span("hs.mesh.join.spmd", "mesh", how=how,
                                shards=S) as join_span:
                state, un_gid, small = match(*l_in, *r_in, l_remaps,
                                             r_remaps, r_hashes)
                t0 = _time.perf_counter()
                # THE one host readback of the join: the per-shard
                # totals that size the expansion, the unmatched-right
                # counts and the route-overflow scalar together. The
                # expansion is dispatched after it and read by nobody:
                # the prefix gather below already has its counts.
                with telemetry.span("hs.mesh.join.sync", "mesh",
                                    attempt=attempt):
                    counts, un_counts, r_ovf = jax.device_get(small)
                sync_s = _time.perf_counter() - t0
                reg.counter("mesh.join.sync_s").inc(sync_s)
                telemetry.add_seconds("mesh.sync_s", sync_s)
                if int(r_ovf) == 0:
                    pairs = int(np.max(counts))
                    cap = _expand_rung(pairs) if pairs else 0
                    join_span.set(cap=cap, pairs=pairs)
                    if pairs:
                        reg.histogram("mesh.spmd.expand_fill").observe(
                            pairs / cap)
                        li, ri = _expand_program(left.rows_per_shard,
                                                 cap)(*state)
                    break
            reg.counter("mesh.spmd.overflow_retries").inc()
            route_capacity *= 2

        total = int(np.asarray(counts).sum())
        extra = int(np.asarray(un_counts).sum()) if need_right else 0
        reg.counter("mesh.join.execs").inc()
        reg.counter("mesh.spmd.join_execs").inc()
        shard_rows_attr = _shard_rows_attribution(left, right)
        reg.histogram("mesh.join.shard_rows").observe_many(
            shard_rows_attr)
        telemetry.event("mesh", "join", how=how, shards=S, pairs=total,
                        lane="spmd", shard_rows=shard_rows_attr)
        if tracer is not None:
            tracer.device_spans("join", span_ts,
                                [int(c) for c in np.asarray(counts)],
                                how=how)
        if total == 0:
            li_f = jnp.zeros(0, dtype=jnp.int64)
            ri_f = jnp.zeros(0, dtype=jnp.int64)
        elif not extra:
            # The valid pairs are contiguous per-shard prefixes by
            # construction: ONE fused gather (incl. the int32 output
            # cast) materializes both sides in a single dispatch.
            return _gather_prefixes((li, ri), counts, cap,
                                    as_int32=True)
        else:
            li_f, ri_f = _gather_prefixes((li, ri), counts, cap)
        if extra:
            (ugid,) = _gather_prefixes((un_gid,), un_counts,
                                       un_gid.shape[1])
            li_f = jnp.concatenate([li_f, jnp.full(extra, -1,
                                                   dtype=jnp.int64)])
            ri_f = jnp.concatenate([ri_f, ugid])
        return li_f.astype(jnp.int32), ri_f.astype(jnp.int32)


def sharded_semi_anti_indices(left: ShardedBatch, right: ShardedBatch,
                              left_keys: Sequence[str],
                              right_keys: Sequence[str],
                              anti: bool = False, conf=None):
    """LEFT SEMI / LEFT ANTI membership over born-sharded sides through
    the match program alone (anti emits null-key left rows — NOT EXISTS
    semantics). Membership reads the match-phase masks and compacts its
    hits in that program; no expansion is compiled or run, and only a
    repartition-route overflow can force a retry. Returns indices into
    the left flat padded space."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu import telemetry

    _check_one_mesh(left, right)
    mesh = left.mesh
    S = total_shards(mesh)
    repartition_to, route_capacity = _repartition_target(left, right)
    remap_idx, l_remaps, r_remaps, r_hashes = _string_key_plan(
        left, right, left_keys, right_keys,
        need_hashes=repartition_to is not None, conf=conf)

    reg = telemetry.get_registry()
    with _dispatch_guard(mesh):
        while True:
            program = _match_program(mesh, len(left_keys),
                                     left.rows_per_shard,
                                     right.rows_per_shard,
                                     left_outer=True, need_right=False,
                                     repartition_to=repartition_to,
                                     route_capacity=route_capacity,
                                     membership="anti" if anti else "semi",
                                     remap_idx=remap_idx)
            if repartition_to is not None:
                _record_repartition_bytes(
                    mesh, route_capacity, 8 * len(right_keys) + 10)
            li_sorted, hit_counts_d, route_ovf = program(
                *_join_inputs(left, left_keys),
                *_join_inputs(right, right_keys),
                l_remaps, r_remaps, r_hashes)
            hit_counts, r_ovf = jax.device_get((hit_counts_d, route_ovf))
            if repartition_to is None or int(r_ovf) == 0:
                break
            reg.counter("mesh.spmd.overflow_retries").inc()
            route_capacity *= 2

        total = int(np.asarray(hit_counts).sum())
        shard_rows_attr = _shard_rows_attribution(left, right)
        reg.histogram("mesh.join.shard_rows").observe_many(
            shard_rows_attr)
        telemetry.event("mesh", "join", how=("anti" if anti else "semi"),
                        shards=S, lane="spmd",
                        shard_rows=shard_rows_attr)
        reg.counter("mesh.join.execs").inc()
        reg.counter("mesh.spmd.join_execs").inc()
        if total == 0:
            return jnp.zeros(0, dtype=jnp.int32)
        (li,) = _gather_prefixes((li_sorted,), hit_counts,
                                 li_sorted.shape[1], as_int32=True)
        return li


# ---------------------------------------------------------------------------
# Stage-to-stage: repartition, filter, aggregate over the sharded layout
# ---------------------------------------------------------------------------


def repartition_sharded(batch: ColumnBatch, key_columns: Sequence[str],
                        num_buckets: int, mesh,
                        capacity_factor: float = CAPACITY_FACTOR
                        ) -> ShardedBatch:
    """Re-bucket a DEVICE-resident batch (e.g. a join output feeding the
    next join) into a born-sharded layout: hash, contiguous-range
    owner, then the topology-aware exchange — ONE all_to_all over ICI
    on a flat mesh, or the two axis-confined hops (ICI within the
    slice, one DCN hop across) on a 2-axis mesh — all inside one jitted
    program, with the routed per-shard layout RETURNED AS-IS (padded +
    valid mask, no global compaction), so no per-bucket histogram and
    no row data ever touch the host between stages. Only the overflow
    scalar syncs. Exchange volume lands in
    `spmd.repartition.{ici,dcn}.bytes` per dispatch."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io import transfer
    from hyperspace_tpu.io.columnar import batch_to_tree, tree_to_batch
    from hyperspace_tpu.telemetry import instrumented_jit

    n_shards = total_shards(mesh)
    n_dcn = dcn_size(mesh)
    n_ici = ici_size(mesh)
    n = batch.num_rows
    local = -(-n // n_shards)
    padded = local * n_shards
    key_names = tuple(batch.schema.field(c).name for c in key_columns)
    tree, aux = batch_to_tree(batch, computes_on=key_names)

    def pad(a):
        return jnp.pad(jnp.asarray(a),
                       [(0, padded - n)] + [(0, 0)] * (a.ndim - 1))

    in_tree: dict = {}
    for name, entry in tree.items():
        out = dict(entry)
        out["data"] = pad(entry["data"])
        if "validity" in entry:
            out["validity"] = pad(entry["validity"])
        if "hash_hi" in entry:
            out["hash_hi"] = jnp.tile(jnp.asarray(entry["hash_hi"]),
                                      n_shards)
            out["hash_lo"] = jnp.tile(jnp.asarray(entry["hash_lo"]),
                                      n_shards)
        in_tree[name] = out
    in_tree["__valid__"] = {"data": jnp.concatenate(
        [jnp.ones(n, bool), jnp.zeros(padded - n, bool)])}
    sharding = shard_rows(mesh)
    engine = transfer.get_engine()
    in_tree = jax.tree_util.tree_map(
        lambda a: engine.put(a, device=sharding), in_tree)

    reg = telemetry.get_registry()
    factor = capacity_factor
    while True:
        capacity = max(16, int(local / n_shards * factor))
        _rows_out, cap_ici, cap_dcn = _route_slabs(mesh, capacity)
        rows_spec = row_spec(mesh)

        def make_step(capacity=capacity, cap_ici=cap_ici,
                      cap_dcn=cap_dcn):
            def step(t):
                def body(tt):
                    from hyperspace_tpu.ops.build import _tree_hash_lanes
                    from hyperspace_tpu.ops.hash_partition import \
                        flat_hash32

                    valid_l = tt["__valid__"]["data"]
                    lanes = []
                    for nm in key_names:
                        lanes.extend(_tree_hash_lanes(tt[nm]))
                    h = flat_hash32(lanes)
                    bucket = (h % jnp.uint32(num_buckets)) \
                        .astype(jnp.int64)
                    owner = bucket_owner(bucket, num_buckets,
                                         n_shards).astype(jnp.int32)
                    # Route data/validity leaves; dictionary hash tables
                    # stay shard-local (replicated), like the build.
                    to_route = []
                    spec = []
                    for nm, entry in tt.items():
                        if nm == "__valid__":
                            continue
                        spec.append((nm, "data"))
                        to_route.append(entry["data"])
                        if "validity" in entry:
                            spec.append((nm, "validity"))
                            to_route.append(entry["validity"])
                    if n_dcn == 1:
                        dest = jnp.where(valid_l, owner,
                                         jnp.int32(n_shards))
                        routed, overflow = _route_local(
                            to_route + [valid_l], dest, n_shards,
                            capacity)
                    else:
                        # Two axis-confined hops (build discipline):
                        # ICI to the owner's slice position, DCN to the
                        # owner slice, owner id riding along.
                        dest1 = jnp.where(valid_l, owner % n_ici,
                                          jnp.int32(n_ici))
                        routed1, ovf1 = _route_local(
                            to_route + [valid_l, owner], dest1, n_ici,
                            cap_ici, axis=SHARD_AXIS)
                        valid1 = routed1[-2]
                        owner1 = routed1[-1]
                        dest2 = jnp.where(valid1, owner1 // n_ici,
                                          jnp.int32(n_dcn))
                        routed, ovf2 = _route_local(
                            routed1[:-1], dest2, n_dcn, cap_dcn,
                            axis=DCN_AXIS)
                        overflow = ovf1 + ovf2
                    out_t = {nm: dict(entry) for nm, entry in tt.items()
                             if nm != "__valid__"}
                    for (nm, part), arr in zip(spec, routed[:-1]):
                        out_t[nm][part] = arr
                    out_t["__valid__"] = {"data": routed[-1]}
                    out_t["__overflow__"] = {
                        "data": overflow.reshape(1)}
                    return out_t

                return compat_shard_map(
                    body, mesh=mesh,
                    in_specs=(jax.tree_util.tree_map(
                        lambda _: rows_spec, t),),
                    out_specs=rows_spec, check_vma=False)(t)

            return step

        program = _cached_program(
            ("repartition", mesh, key_names, num_buckets, capacity),
            lambda: instrumented_jit("mesh.spmd_repartition",
                                     make_step(),
                                     scope="hs.mesh.repartition"))
        per_row = sum(
            int(np.dtype(getattr(e["data"], "dtype", np.int64)).itemsize)
            + (1 if "validity" in e else 0)
            for nm, e in in_tree.items() if nm != "__valid__") + 1
        _record_repartition_bytes(mesh, capacity, per_row)
        with _dispatch_guard(mesh):
            routed_tree = program(in_tree)
            overflow = int(jnp.sum(routed_tree["__overflow__"]["data"]))
        if overflow == 0:
            break
        reg.counter("mesh.spmd.overflow_retries").inc()
        factor *= 2

    C = _route_slabs(mesh, capacity)[0]
    row_valid = routed_tree["__valid__"]["data"]
    out_tree = {}
    for name, entry in routed_tree.items():
        if name.startswith("__"):
            continue
        cleaned = dict(entry)
        if "hash_hi" in cleaned:
            cleaned["hash_hi"] = tree[name]["hash_hi"]
            cleaned["hash_lo"] = tree[name]["hash_lo"]
        out_tree[name] = cleaned
    flat = tree_to_batch(out_tree, batch.schema, aux)
    telemetry.event("mesh", "repartition", shards=n_shards,
                    buckets=num_buckets, rows=n, lane="spmd")
    reg.counter("mesh.spmd.repartition_execs").inc()
    return ShardedBatch(flat, row_valid, mesh, C, num_buckets,
                        lengths=None)


def sharded_predicate_mask(sh: ShardedBatch, expression,
                           reuse: bool = True):
    """`row_valid` narrowed by the predicate, as ONE jitted SPMD program
    (`jit_spmd_filter`, its ops under the device scope
    `hs.mesh.filter`): the compiled predicate traces together with the
    validity mask; each device evaluates its shard and no row moves.
    The program is kept per (mesh, predicate, schema, dictionaries) —
    everything its trace reads besides the arrays — so a warm repeat
    dispatches it without retracing; `reuse=False` traces afresh (the
    trace-time dictionary lookups run, and are counted, every call)."""
    import json

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.engine.compiler import compile_predicate
    from hyperspace_tpu.io.columnar import batch_to_tree, tree_to_batch
    from hyperspace_tpu.telemetry import instrumented_jit

    count_string_predicate_lookups(expression, sh.batch)
    tree, aux = batch_to_tree(sh.batch, computes_on=())
    schema = sh.batch.schema

    def build():
        def spmd_filter(t, valid):
            b = tree_to_batch(t, schema, aux)
            return compile_predicate(expression, b) & valid

        return instrumented_jit("mesh.spmd_filter", spmd_filter,
                                scope="hs.mesh.filter")

    try:
        if not reuse:
            return build()(tree, sh.row_valid)
        key = ("filter", sh.mesh,
               json.dumps(expression.to_dict(), sort_keys=True,
                          default=str),
               schema.to_json(),
               tuple((name, _dict_fingerprint(d))
                     for name, d in aux.items() if d is not None))
        return _cached_program(key, build)(tree, sh.row_valid)
    except HyperspaceException:
        raise
    except Exception:
        # A predicate shape the tracer cannot close over (host-only
        # op in a UDF, say) degrades to the eager SPMD evaluation —
        # same math, more dispatches.
        telemetry.get_registry().counter(
            "mesh.spmd.filter_eager_fallbacks").inc()
        return compile_predicate(expression, sh.batch) & sh.row_valid


def sharded_filter(sh: ShardedBatch, expression) -> ColumnBatch:
    """Predicate scan over the born-sharded layout (the SPMD mask
    program above). Only the final compaction gather crosses shards.
    Result equals the single-chip `apply_filter` bit for bit."""
    import time as _time

    import jax.numpy as jnp

    from hyperspace_tpu import telemetry

    reg = telemetry.get_registry()
    with telemetry.span("hs.mesh.filter", "mesh", rows=sh.num_rows,
                        shards=sh.n_shards), _dispatch_guard(sh.mesh):
        # traced per call, as ever: a warm LIKE still asks the segment
        # cache for its mask (`spmd.strings.like_mask_cache_hits`)
        mask = sharded_predicate_mask(sh, expression, reuse=False)
        t0 = _time.perf_counter()
        count = int(jnp.sum(mask))  # the one sizing readback
        sync_s = _time.perf_counter() - t0
        reg.counter("mesh.filter.execs").inc()
        reg.counter("mesh.filter.sync_s").inc(sync_s)
        telemetry.add_seconds("mesh.sync_s", sync_s)
        telemetry.event("mesh", "filter", shards=sh.n_shards,
                        rows=sh.num_rows, selected=count, lane="spmd")
        (indices,) = jnp.nonzero(mask, size=count, fill_value=0)
        return sh.batch.take(indices)


def sharded_group_aggregate(sh: ShardedBatch,
                            group_columns: Sequence[str], aggregates,
                            out_schema) -> ColumnBatch:
    """Group-by aggregation straight over the born-sharded layout: the
    SPMD partial step consumes the resident [S*C] arrays + validity —
    no re-padding, no re-placement, no link traffic before the tiny
    [n_shards, G] partial tables cross for the host combine."""
    from hyperspace_tpu.parallel.aggregate import distributed_group_aggregate

    with _dispatch_guard(sh.mesh):
        return distributed_group_aggregate(
            sh.batch, group_columns, aggregates, out_schema, sh.mesh,
            pre_sharded=(sh.batch, sh.row_valid))


# ---------------------------------------------------------------------------
# Inter-query batched predicate lane (`engine/batcher.py` is the ONLY
# sanctioned caller — `scripts/check_metrics_coverage.py` enforces it)
# ---------------------------------------------------------------------------
#
# K concurrent point/filter queries over one shared scan differ only in
# their predicate CONSTANTS once they share an execution signature
# (`engine/batcher.py` groups them). This program evaluates all K
# predicates in ONE `instrumented_jit("serve.batch")` dispatch: the
# constants ride [K, T] lanes (K padded to a power-of-two bucket by the
# batcher, so cohort size is a compile bucket, not a retrace per K) and
# the result is a [K, N] boolean mask matrix the batcher slices
# per-query. Term semantics mirror `engine/compiler.py`'s definite-truth
# masks exactly for the supported shapes — numeric comparisons against
# literals (compared in the COLUMN's dtype, matching numpy's
# weak-scalar promotion on the solo path), integer IN lists, and
# IS [NOT] NULL — so a batched member's rows are bit-identical to its
# solo run.

# One shape term is a tuple:
#   ("cmp", op, col_index, lane)       lane: "i" (int64) | "f" (float64)
#   ("in", col_index, padded_len)      int lane, `padded_len` values
#   ("isnull"|"notnull", col_index)
_BATCH_CMP_OPS = ("eq", "ne", "lt", "le", "gt", "ge")


def _batched_predicate_program(shape: tuple, dtypes: tuple,
                               valid_flags: tuple):
    """Build (memoized) the jitted K-predicate program for one static
    term shape over columns of the given dtypes/validity presence."""
    import jax.numpy as jnp

    from hyperspace_tpu.telemetry import instrumented_jit

    def build():
        def body(datas, valids, iconst, fconst):
            ops = {"eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
                   "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
                   "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b}
            vmap = {}
            vi = 0
            for ci, flag in enumerate(valid_flags):
                if flag:
                    vmap[ci] = valids[vi]
                    vi += 1
            total = None
            ii = fi = 0
            for term in shape:
                kind = term[0]
                if kind == "cmp":
                    _k, op, ci, lane = term
                    data = jnp.asarray(datas[ci])
                    if lane == "f":
                        const = fconst[:, fi]
                        fi += 1
                        # Compare in the column's own float width (the
                        # solo path's numpy weak-scalar promotion); int
                        # columns against float literals promote to
                        # float64 on both paths.
                        if data.dtype.kind == "f":
                            const = const.astype(data.dtype)
                        else:
                            data = data.astype(jnp.float64)
                    else:
                        const = iconst[:, ii]
                        ii += 1
                        # Integer compares are exact at any width; lift
                        # the column to int64 so the [K] lane broadcasts
                        # without narrowing the literal.
                        if data.dtype.kind == "f":
                            const = const.astype(data.dtype)
                        else:
                            data = data.astype(jnp.int64)
                    m = ops[op](data[None, :], const[:, None])
                elif kind == "in":
                    _k, ci, padded = term
                    vals = iconst[:, ii:ii + padded]
                    ii += padded
                    data = jnp.asarray(datas[ci]).astype(jnp.int64)
                    m = jnp.any(data[None, :, None] == vals[:, None, :],
                                axis=-1)
                elif kind == "isnull":
                    _k, ci = term
                    v = vmap.get(ci)
                    n = jnp.asarray(datas[ci]).shape[0]
                    m = (jnp.zeros((1, n), bool) if v is None
                         else (~v)[None, :])
                else:  # notnull
                    _k, ci = term
                    v = vmap.get(ci)
                    n = jnp.asarray(datas[ci]).shape[0]
                    m = (jnp.ones((1, n), bool) if v is None
                         else v[None, :])
                if kind in ("cmp", "in"):
                    v = vmap.get(term[2] if kind == "cmp" else term[1])
                    if v is not None:
                        m = m & v[None, :]
                total = m if total is None else total & m
            # A constants-free shape (only null-ness terms) evaluates
            # as one [1, N] row — broadcast so every member slices its
            # own lane regardless.
            return jnp.broadcast_to(
                total, (iconst.shape[0],) + total.shape[1:])

        return instrumented_jit("serve.batch", body, scope="hs.serve.batch")

    return _cached_program(("serve.batch", shape, dtypes, valid_flags),
                           build)


def batched_predicate_masks(shape: tuple, datas: tuple, valids: tuple,
                            iconst, fconst):
    """THE batched-execution entry point: evaluate the K stacked
    predicates described by `shape` over the shared columns. `datas` is
    one array per referenced column (shape order indexes into it),
    `valids` the validity arrays of the columns that HAVE one (presence
    is static program structure), `iconst`/`fconst` the [K_bucket, T]
    padded constant lanes. Returns the [K_bucket, N] boolean mask
    matrix (a jax array; callers slice rows per member)."""
    valid_flags = tuple(v is not None for v in valids)
    dtypes = tuple(str(np.asarray(d).dtype) if isinstance(d, np.ndarray)
                   else str(d.dtype) for d in datas)
    prog = _batched_predicate_program(shape, dtypes, valid_flags)
    present = tuple(v for v in valids if v is not None)
    return prog(tuple(datas), present, iconst, fconst)
