"""Physical plan: executable operator tree.

The reference's observable win is Spark's physical planner *not* inserting
ShuffleExchange/Sort under a SortMergeJoin when both sides are bucketed
(`index/rules/JoinIndexRule.scala:41-43`; verified via operator-occurrence
diff, `plananalysis/PhysicalOperatorAnalyzer.scala:44-57`). This framework
owns that planning step: Join compiles to SortMergeJoinExec, with
ExchangeExec (hash repartition) + SortExec inserted only when a side is not
already bucketed+sorted on the join keys — so explain() can show the same
Exchange/Sort elision, and execution actually skips the work.
"""

from __future__ import annotations

import functools
import os

from typing import List, Optional, Sequence, Set, Tuple

from hyperspace_tpu import telemetry
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io import columnar, parquet, segcache
from hyperspace_tpu.plan import expr as E
from hyperspace_tpu.plan.nodes import (Aggregate, BucketSpec, Except, Filter,
                                       Join, Limit, LogicalPlan, Project,
                                       Scan, SetOp, Sort, Union, Window)
from hyperspace_tpu.plan.schema import Schema


def _batch_rows(out) -> Optional[int]:
    """Output row count of an execute/execute_bucketed result, without
    forcing any device sync (ColumnBatch.num_rows is a static shape)."""
    if isinstance(out, columnar.ColumnBatch):
        return out.num_rows
    if isinstance(out, tuple) and out \
            and isinstance(out[0], columnar.ColumnBatch):
        return out[0].num_rows
    return None


def _instrument(fn, bucketed: bool):
    """Wrap an execute/execute_bucketed implementation with the telemetry
    operator hook: a per-query operator record (active recorder) and an
    `hs.op.<Name>` span on the executing thread (the span seam's two
    sinks). With neither, the cost is one ContextVar read + two flag
    reads.
    Applied automatically to every PhysicalNode subclass by
    `PhysicalNode.__init_subclass__`, so a new operator can never
    silently execute unmetered (`scripts/check_metrics_coverage.py`
    enforces the marker repo-wide)."""

    @functools.wraps(fn)
    def wrapper(self, arg=None):
        # Cooperative-cancellation checkpoints bracket every operator
        # (one contextvar read + None check each when no deadline is
        # active — same always-off contract as the recorder hooks).
        # BOTH ends matter in a pull-based executor: every operator
        # STARTS during the initial tree descent (microseconds), so the
        # entry check alone would see the whole plan before any real
        # work ran; the finish check below — after the operator's
        # actual compute, on the way up — is what stops a cancelled
        # query between operators.
        phase = "scan" if self.name == "Scan" else "operator"
        telemetry.check_deadline(phase)
        rec = telemetry.current()
        if rec is None and not telemetry.spans_active():
            out = fn(self, arg)
            telemetry.check_deadline(phase)
            return out
        with telemetry.span("hs.op." + self.name, "operator") as sp:
            op = None
            if rec is not None:
                op = rec.start_operator(self.name, self, bucketed=bucketed)
                if bucketed:
                    op.detail["num_buckets"] = arg
                elif arg is not None:
                    op.detail["bucket"] = arg
            try:
                out = fn(self, arg)
            except BaseException as exc:
                if op is not None:
                    rec.finish_operator(op, error=repr(exc))
                raise
            rows = _batch_rows(out)
            detail = op.detail if op is not None else {}
            sp.set(rows=rows, lane=detail.get("lane"),
                   appended=detail.get("appended"),
                   index=detail.get("index"), source=detail.get("source"),
                   files=detail.get("files_scanned"),
                   path=detail.get("path"), groups=detail.get("groups"),
                   masked=detail.get("masked"))
            if op is not None:
                rec.finish_operator(op, rows_out=rows)
        # Operator-span boundary: fold a device-memory sample into the
        # per-query HBM watermark (throttled; after the span close so
        # the accounting walk never inflates the operator's wall).
        telemetry.memory.maybe_sample()
        # The mid-query cancellation point (see entry comment): the
        # operator's record is already closed cleanly — the QUERY
        # aborts before the parent consumes the result.
        telemetry.check_deadline(phase)
        return out

    wrapper.__telemetry_instrumented__ = True
    return wrapper


class PhysicalNode:
    name: str = "Physical"

    def __init_subclass__(cls, **kwargs):
        # EVERY subclass's execute/execute_bucketed emits an operator
        # metrics record; opting out is not supported by design (the
        # metrics-coverage lint would flag it).
        super().__init_subclass__(**kwargs)
        for attr, bucketed in (("execute", False),
                               ("execute_bucketed", True)):
            fn = cls.__dict__.get(attr)
            if fn is not None and callable(fn) \
                    and not getattr(fn, "__telemetry_instrumented__",
                                    False):
                setattr(cls, attr, _instrument(fn, bucketed))

    @property
    def children(self) -> List["PhysicalNode"]:
        return []

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        raise NotImplementedError

    def execute_sharded(self, num_buckets: int, mesh, align_plan=None):
        """Born-sharded execution (`parallel/spmd.py`): produce this
        node's output as a device-resident `ShardedBatch` whose shard s
        holds bucket range s, or None when the shape does not qualify
        (unbucketed source, host-lane row counts). None is a ROUTING
        answer, not an error — callers fall back to the single-chip
        paths. Hot-bucket skew no longer declines: the scan splits the
        hot range into virtual sub-shards (`spmd.subshard_plan`) and
        stamps the split onto the batch; `align_plan` asks this side to
        read ALIGNED to the other side's split (intersected buckets
        replicated per covering shard). Default: not shardable."""
        return None

    def execute_bucketed(self, num_buckets: int):
        """Produce (batch concat'd in bucket order, per-bucket lengths) for
        the batched bucketed join. Only meaningful on chains over a
        bucketed scan."""
        raise HyperspaceException(
            f"{type(self).__name__} does not support bucketed execution.")

    def simple_string(self) -> str:
        return self.name

    def tree_string(self, depth: int = 0) -> str:
        lines = [("  " * depth) + ("+- " if depth else "") + self.simple_string()]
        for c in self.children:
            lines.append(c.tree_string(depth + 1))
        return "\n".join(lines)

    def collect(self) -> List["PhysicalNode"]:
        out = [self]
        for c in self.children:
            out.extend(c.collect())
        return out


def _empty_batch(schema: Schema) -> columnar.ColumnBatch:
    import pyarrow as pa
    return columnar.from_arrow(
        pa.table({f.name: pa.array([], type=t.type)
                  for f, t in zip(schema.fields, schema.to_arrow())}), schema)


class ScanExec(PhysicalNode):
    name = "Scan"

    def __init__(self, scan: Scan, columns: Sequence[str],
                 allowed_buckets: Optional[Set[int]] = None, conf=None,
                 shared_members: int = 0):
        self.scan = scan
        self.columns = list(columns)
        self.out_schema = scan.schema.select(columns)
        self.conf = conf
        # >0: this scan is the SHARED read of an inter-query batch
        # cohort (`engine/batcher.py`) — one read serving that many
        # concurrent queries. Threaded to the segment cache's shared-
        # read counters and onto the operator record so the differ can
        # attribute amortized reads.
        self.shared_members = shared_members
        # Bucket pruning: when a filter above constrains every bucket
        # column to literal values, only these buckets can contain matches
        # (set by the planner, `_prune_buckets`). The index read then
        # touches 1/num_buckets of the files per point value — the engine
        # analog of partition pruning, and the device-path win the bucketed
        # layout buys beyond the reference (whose filter swap stays
        # unbucketed purely for Spark scan parallelism,
        # `index/rules/FilterIndexRule.scala:112-120`).
        self.allowed_buckets = allowed_buckets

    def _budget(self, device: bool):
        """Session-conf cache budget for this scan's lane (None = the
        process-wide env default). The device lane is the HBM segment
        cache (`spark.hyperspace.cache.segments.bytes`)."""
        if self.conf is None:
            return None
        return (self.conf.segment_cache_bytes if device
                else self.conf.read_cache_bytes)

    def _read_device(self, files: List[str],
                     ref) -> columnar.ColumnBatch:
        """Device-lane read THROUGH the HBM segment cache: a warm hit
        is link-free (no parquet decode, no H2D). Rule-selected index
        scans key by `ref` (index root, committed version, bucket
        selector); unversioned scans (`ref` None) fall back to stamp
        validation inside the cache."""
        return segcache.read_segment(files, self.columns,
                                     self.out_schema, ref=ref,
                                     conf=self.conf,
                                     budget=self._budget(device=True),
                                     shared_members=self.shared_members)

    def _annotate_read(self, facts, host: bool) -> None:
        """Index-usage detail on this scan's operator record: lane, files
        scanned vs total, buckets scanned vs total — all from the facts
        `_resolve` already holds; this hook performs no IO of its own
        (telemetry must not add a listing to the scan hot path)."""
        if telemetry.current() is None:
            return
        detail = {"lane": "host" if host else "device",
                  "files_scanned": len(facts.files),
                  # Raw on-disk bytes behind this read. Feeds the
                  # regression differ and the index advisor's
                  # per-relation scan-bytes signal.
                  "bytes_scanned": facts.bytes_scanned,
                  "roots": list(self.scan.root_paths)}
        if self.scan.index_name is not None:
            detail["index"] = self.scan.index_name
        elif self.scan.root_paths:
            detail["source"] = os.path.basename(
                self.scan.root_paths[0].rstrip("/"))
        if self.shared_members:
            detail["shared_members"] = self.shared_members
        if self.scan.appended:
            # hybrid scan's branch over the files an index lacks
            detail["appended"] = len(facts.files)
        spec = self.scan.bucket_spec
        if spec is not None:
            detail["buckets_total"] = spec.num_buckets
            detail["buckets_scanned"] = (len(self.allowed_buckets)
                                         if self.allowed_buckets is not None
                                         else spec.num_buckets)
            if self.allowed_buckets is not None \
                    and len(self.allowed_buckets) <= 128:
                # Per-bucket access identity for the replica router's
                # hot-range miner (`parallel/replica.py`) — only when
                # pruning narrowed the read (full-range scans carry no
                # hotness signal) and small enough to ride the ring.
                detail["bucket_ids"] = sorted(self.allowed_buckets)
        if facts.files_total is not None:
            detail["files_total"] = facts.files_total
        telemetry.annotate(**detail)

    def simple_string(self) -> str:
        bucket = (f", buckets={self.scan.bucket_spec.num_buckets}"
                  if self.scan.bucket_spec else "")
        pruned = ""
        if self.allowed_buckets is not None and self.scan.bucket_spec:
            pruned = (f", prunedBuckets={len(self.allowed_buckets)}"
                      f"/{self.scan.bucket_spec.num_buckets}")
        return (f"Scan parquet [{', '.join(self.columns)}] "
                f"{self.scan.root_paths}{bucket}{pruned}")

    def _guard_index_read(self, fn):
        """Run one read attempt with the graceful-degradation contract:
        for a RULE-SELECTED index scan (scan.index_name set), data that
        turns out missing or unreadable — root dir gone, files corrupt,
        storage failing past the retry policy — raises the typed
        IndexDataUnavailableError that `DataFrame.collect` converts into
        a fallback to the source plan. Source-data scans keep their raw
        errors: there is nothing to degrade to. HyperspaceExceptions
        (planner contract violations) and BaseExceptions (injected
        crashes) pass through untouched."""
        from hyperspace_tpu.exceptions import IndexDataUnavailableError

        name = self.scan.index_name
        if name is None:
            return fn()
        from hyperspace_tpu.utils import file_utils
        missing = [r for r in self.scan.root_paths
                   if not file_utils.is_dir(r)
                   and not file_utils.is_file(r)]
        if missing:
            raise IndexDataUnavailableError(
                f"Index {name!r} data root(s) missing: "
                f"{', '.join(missing)}", index_name=name)
        try:
            return fn()
        except HyperspaceException:
            raise
        except Exception as exc:
            raise IndexDataUnavailableError(
                f"Index {name!r} data unreadable: {exc!r}",
                index_name=name) from exc

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        if self.scan.index_name is not None \
                and self.scan.pinned_version is not None:
            # Snapshot-pinned index read: hold the version directories
            # pinned for the read's duration so a concurrent vacuum
            # defers its delete instead of yanking files mid-read
            # (index/pins.py). If a delete wins anyway, the guard below
            # still converts the failure into the typed fallback.
            from hyperspace_tpu.index import pins
            with pins.pinned(self.scan.root_paths):
                return self._guard_index_read(lambda: self._execute(bucket))
        return self._guard_index_read(lambda: self._execute(bucket))

    def _per_bucket_files(self) -> dict:
        """{bucket id: files} for this scan. A plan-time-PINNED scan
        (snapshot isolation: `Rule.index_scan` resolved the committed
        version's listing once) and an explicit-file-list scan derive
        the map from that frozen listing — execution performs NO
        directory re-listing, so a writer racing the query between plan
        and scan cannot change what is read. Unpinned scans keep the
        live per-root listing."""
        if self.scan.pinned_version is not None \
                or self.scan._explicit_files:
            return parquet.bucket_map(self.scan.files())
        out: dict = {}
        for root in self.scan.root_paths:
            for b, fs in parquet.bucket_files(root).items():
                out.setdefault(b, []).extend(fs)
        return out

    def _resolve(self, bucket: Optional[int] = None,
                 num_buckets: Optional[int] = None):
        """(facts, ref) of this read — the ONE place a scan learns about
        its files: their names in read order, `files_total`, the rows
        per bucket and in all (the lane choice), the bytes on disk
        (`segcache.ScanFacts`). `num_buckets` asks for the bucket-ordered
        layout of `execute_bucketed` / `execute_sharded`, `bucket` for
        one bucket's files. Where the scan names a committed index
        version (`segcache.segment_ref_for_scan` gives a ref) the facts
        are that version's and come from the memo beside its segments:
        a warm scan stats, opens and lists nothing and gives the
        `hs-io` pool no task. Any other scan (source data, several
        roots, hybrid scan's appended files) resolves from the files
        every time: that pass is how a rewritten file is noticed."""
        ref = segcache.segment_ref_for_scan(
            self.scan, bucket=bucket,
            allowed_buckets=self.allowed_buckets,
            bucketed=num_buckets is not None)
        from_files = functools.partial(self._resolve_from_files, bucket,
                                       num_buckets)
        with telemetry.span("hs.scan.resolve", "cache") as sp:
            if ref is None:
                # bytes only where an operator record will report them
                facts = from_files(want_bytes=telemetry.current() is not None)
                cached = False
            else:
                facts, cached = segcache.get_cache().scan_facts(
                    ref, num_buckets,
                    functools.partial(from_files, want_bytes=True))
            sp.set(files=len(facts.files), cached=int(cached))
        telemetry.get_registry().counter(
            "scan.resolve.hits" if cached else "scan.resolve.misses").inc()
        return facts, ref

    def _resolve_from_files(self, bucket, num_buckets,
                            want_bytes: bool) -> "segcache.ScanFacts":
        """`_resolve`'s answer from the listing, the Parquet footers
        (`parquet.file_row_counts`: a stamp and a cache probe per file,
        on the `hs-io` pool) and the size cache."""
        import numpy as np

        buckets = files_total = lengths = None
        if num_buckets is not None:
            per_bucket: dict = {}
            files_total = 0
            for b, fs in self._per_bucket_files().items():
                files_total += len(fs)
                if (self.allowed_buckets is not None
                        and b not in self.allowed_buckets):
                    # Pruned by the filter above: no row in this bucket
                    # can survive it, so an empty bucket is equivalent.
                    continue
                per_bucket.setdefault(b, []).extend(fs)
            # ONE bucket-ordered file list; per-bucket lengths come from
            # parquet footers (no data read).
            ordered = [(b, f) for b in range(num_buckets)
                       for f in per_bucket.get(b, [])]
            buckets = tuple(b for b, _ in ordered)
            files = [f for _, f in ordered]
        elif bucket is not None:
            files = self._per_bucket_files().get(bucket, [])
        elif self.allowed_buckets is not None and self.scan.bucket_spec:
            files = []
            per_bucket = self._per_bucket_files()
            files_total = sum(len(v) for v in per_bucket.values())
            for b in sorted(self.allowed_buckets):
                files.extend(per_bucket.get(b, []))
        else:
            files = self.scan.files()
            files_total = len(files)
        # Footer row counts only gate the lane choice, which per-bucket
        # reads don't make — keep the metadata pass off that hot path.
        counts = (None if bucket is not None
                  else tuple(parquet.file_row_counts(files)))
        if num_buckets is not None:
            lengths = np.zeros(num_buckets, dtype=np.int64)
            for b, c in zip(buckets, counts):
                lengths[b] += c
            lengths.setflags(write=False)
        bytes_scanned = None
        if want_bytes:
            from hyperspace_tpu.plan import footprint as _footprint
            bytes_scanned = _footprint.file_sizes_total(files)
        return segcache.ScanFacts(
            files=tuple(files), buckets=buckets, files_total=files_total,
            counts=counts, lengths=lengths, bytes_scanned=bytes_scanned)

    def _min_device_rows(self) -> int:
        from hyperspace_tpu.constants import MIN_DEVICE_ROWS_DEFAULT
        return (self.conf.min_device_rows if self.conf is not None
                else MIN_DEVICE_ROWS_DEFAULT)

    def _execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        if bucket is not None and self.scan.bucket_spec is None:
            raise HyperspaceException("Bucket read on unbucketed scan.")
        facts, ref = self._resolve(bucket=bucket)
        files = list(facts.files)
        if not files:
            return _empty_batch(self.out_schema)
        # Adaptive lane: small reads (e.g. a pruned point-filter bucket)
        # stay in host memory — a device round-trip (cost unmeasured on
        # an attached chip) is assumed to dwarf the work. Downstream jnp operators promote host
        # batches to the device transparently when they need it. Host
        # batches come through the stamped decoded-batch cache.
        host = bucket is None and facts.rows < self._min_device_rows()
        self._annotate_read(facts, host)
        if self.scan.appended:
            # What hybrid scan reads beside the index, per scan: a file
            # that two branches of one query read counts twice. Bytes
            # only where `_resolve` had them (an operator record asked).
            reg = telemetry.get_registry()
            reg.counter("hybrid.appended_files").inc(len(files))
            reg.counter("hybrid.appended_bytes").inc(
                facts.bytes_scanned or 0)
        if host:
            batch = parquet.read_host_batch(files, self.columns,
                                            self.out_schema,
                                            budget=self._budget(device=False))
        else:
            batch = self._read_device(files, ref)
        if bucket is not None and len(files) > 1:
            # Multiple sorted runs in one bucket (incremental deltas): the
            # concat is not globally sorted — restore order on device.
            from hyperspace_tpu.ops.sort import sort_batch
            sort_cols = [c for c in self.scan.bucket_spec.sort_columns
                         if self.out_schema.contains(c)]
            if sort_cols:
                batch = sort_batch(batch, sort_cols)
        return batch

    def execute_bucketed(self, num_buckets: int):
        return self._guard_index_read(
            lambda: self._execute_bucketed(num_buckets))

    def execute_sharded(self, num_buckets: int, mesh, align_plan=None):
        return self._guard_index_read(
            lambda: self._execute_sharded(num_buckets, mesh,
                                          align_plan=align_plan))

    def _execute_sharded(self, num_buckets: int, mesh, align_plan=None):
        """Born-sharded bucket-range read: shard s's bucket range decodes
        and places onto DEVICE s through the per-device segment cache
        (per-bucket fill granularity), so each device's HBM holds only
        its range and a warm read is link-free per device. Returns a
        ShardedBatch, or None when the read belongs on another lane.

        Hot-bucket skew (`pad_blowup`) splits the hot range into
        VIRTUAL SUB-SHARDS instead of declining: equal row segments
        whose cuts may fall inside a hot bucket (`spmd.plan_skew_read`),
        stamped as `split_plan` so the join reads its other side
        aligned. `align_plan` IS that other side's read: each shard
        holds every row of the buckets intersecting the plan's segment
        (split buckets replicated per covering shard)."""
        from hyperspace_tpu.parallel import spmd
        from hyperspace_tpu.parallel.mesh import (bucket_ranges,
                                                  total_shards)

        if self.scan.bucket_spec is None:
            return None
        if not spmd.supports_sharded(self.out_schema):
            return None  # a dtype outside the host-lane map (defensive)
        facts, ref = self._resolve(num_buckets=num_buckets)
        total = facts.rows
        if total == 0:
            return None
        mode = self.conf.distribution if self.conf is not None else "auto"
        if mode == "auto":
            from hyperspace_tpu.constants import \
                DISTRIBUTION_MIN_ROWS_DEFAULT
            min_dist = (self.conf.distribution_min_rows
                        if self.conf is not None
                        else DISTRIBUTION_MIN_ROWS_DEFAULT)
            if total < max(self._min_device_rows(), min_dist):
                return None  # host / single-chip lane territory
        n_shards = total_shards(mesh)
        lengths = facts.lengths.copy()
        per_bucket: dict = {}
        for b, f in zip(facts.buckets, facts.files):
            per_bucket.setdefault(b, []).append(f)
        budget = self._budget(device=True)
        self._annotate_read(facts, host=False)
        if align_plan is not None:
            # The other side of a sub-shard join: intersected buckets
            # replicated per covering shard. Decline when replication
            # would itself blow the padded layout (both sides hot).
            if (align_plan.num_buckets != num_buckets
                    or align_plan.n_shards != n_shards):
                return None
            specs = spmd.plan_aligned_read(per_bucket, lengths,
                                           align_plan)
            C = max(1, max(spec[2] for spec in specs))
            if C * n_shards > max(spmd.PAD_BLOWUP_FACTOR * total,
                                  1 << 16):
                return None
            return spmd.read_sharded([], lengths, self.columns,
                                     self.scan.schema, mesh,
                                     base_ref=ref, conf=self.conf,
                                     budget=budget, shard_specs=specs)
        split_plan = None
        shard_specs = None
        per_shard_files = None
        if spmd.pad_blowup(lengths, n_shards):
            # Hot-bucket skew: whole-bucket ownership would pad the
            # [S*C] layout past the blow-up bar — split the hot range
            # into row-balanced virtual sub-shards and stay on the
            # SPMD lane (the join reads its other side aligned).
            split_plan, shard_specs = spmd.plan_skew_read(
                per_bucket, lengths, n_shards, counts=facts.counts)
            telemetry.get_registry().counter(
                "mesh.spmd.subshard_reads").inc()
            telemetry.annotate(subsharded=True)
        else:
            per_shard_files = [[f for b in range(lo, hi)
                                for f in per_bucket.get(b, [])]
                               for lo, hi in bucket_ranges(num_buckets,
                                                           n_shards)]
        return spmd.read_sharded(per_shard_files or [], lengths,
                                 self.columns, self.scan.schema, mesh,
                                 base_ref=ref, conf=self.conf,
                                 budget=budget,
                                 shard_specs=shard_specs,
                                 split_plan=split_plan)

    def _execute_bucketed(self, num_buckets: int):
        """Read all bucket files in bucket order; lengths come from parquet
        metadata — no device work. (The batched join sorts per-bucket ids
        itself, so multi-run buckets need no pre-sort here.)"""
        if self.scan.bucket_spec is None:
            raise HyperspaceException("Bucketed read on unbucketed scan.")
        facts, ref = self._resolve(num_buckets=num_buckets)
        lengths = facts.lengths.copy()
        files = list(facts.files)
        if not files:
            return _empty_batch(self.out_schema), lengths
        host = facts.rows < self._min_device_rows()
        self._annotate_read(facts, host)
        if host:
            return parquet.read_host_batch(
                files, self.columns, self.out_schema,
                budget=self._budget(device=False)), lengths
        return self._read_device(files, ref), lengths


class FilterExec(PhysicalNode):
    name = "Filter"

    def __init__(self, condition: E.Expression, child: PhysicalNode,
                 conf=None):
        self.condition = condition
        self.child = child
        self.conf = conf

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return f"Filter ({self.condition!r})"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.engine.compiler import apply_filter
        from hyperspace_tpu.parallel.context import should_distribute
        batch = self.child.execute(bucket)
        if batch.num_rows == 0:
            return batch
        mesh = should_distribute(self.conf, batch.num_rows,
                                 host_batch=batch.is_host)
        if mesh is not None:
            from hyperspace_tpu.parallel.scan import distributed_filter
            return distributed_filter(batch, self.condition, mesh)
        return apply_filter(batch, self.condition)

    def execute_bucketed(self, num_buckets: int):
        """Filter preserves bucket grouping: the compaction gather is
        stable-ascending, so surviving rows stay in bucket order; new
        per-bucket lengths are the mask's true rows per bucket."""
        import numpy as np
        from hyperspace_tpu.engine.compiler import compile_predicate

        batch, lengths = self.child.execute_bucketed(num_buckets)
        if batch.num_rows == 0:
            return batch, lengths
        mask = compile_predicate(self.condition, batch)
        if isinstance(mask, np.ndarray):  # host lane
            row_bucket = np.searchsorted(np.cumsum(lengths),
                                         np.arange(batch.num_rows),
                                         side="right")
            new_lengths = np.bincount(row_bucket[mask],
                                      minlength=num_buckets).astype(np.int64)
            indices = np.nonzero(mask)[0].astype(np.int32)
            return batch.take(indices), new_lengths
        # Per-bucket survivor counts as ONE device program (the mask's
        # prefix sum read at the buckets' ends), then a single
        # [num_buckets] transfer sizes both the new lengths and the gather.
        from hyperspace_tpu.ops.compact import (bucket_survivors,
                                                compact_indices)
        with telemetry.span("hs.stage.sync", "operator"):
            new_lengths = np.asarray(bucket_survivors(
                mask, lengths)).astype(np.int64)
        count = int(new_lengths.sum())
        with telemetry.span("hs.stage.compact", "operator", rows=count):
            return batch.take(compact_indices(mask, count)), new_lengths

    def execute_sharded(self, num_buckets: int, mesh, align_plan=None):
        """Filter preserves the sharded layout: rows never move, the
        predicate mask just narrows `row_valid` — one SPMD program
        (`spmd.sharded_predicate_mask`) in which each device evaluates
        its shard, nothing crosses the link, and the downstream join /
        aggregate skips masked rows exactly as it skips padding. The
        per-bucket histogram is stale after filtering, so it is dropped;
        a child's virtual-sub-shard split survives (row-local narrowing
        cannot move rows across shards)."""
        sh = self.child.execute_sharded(num_buckets, mesh,
                                        align_plan=align_plan)
        if sh is None:
            return None
        from hyperspace_tpu.parallel.spmd import (ShardedBatch,
                                                  sharded_predicate_mask)
        with telemetry.span("hs.mesh.filter", "mesh",
                            rows=(sh.num_rows if sh.lengths is not None
                                  else None), shards=sh.n_shards):
            row_valid = sharded_predicate_mask(sh, self.condition)
        return ShardedBatch(sh.batch, row_valid, sh.mesh,
                            sh.rows_per_shard, sh.num_buckets,
                            lengths=None, split_plan=sh.split_plan)


class ProjectExec(PhysicalNode):
    """Projection over (out_name, source) entries, where source is a plain
    child column name (pass-through) or a value Expression compiled by the
    same XLA-fused compiler filters use. Computed entries preserve row
    order, so the bucketed contract (batch + lengths) carries through."""

    name = "Project"

    def __init__(self, entries, child: PhysicalNode):
        # Accept bare name strings (pass-through) or (out_name, source)
        # pairs; `source` is a child column name or an Expression.
        self.entries = [(e, e) if isinstance(e, str) else (e[0], e[1])
                        for e in entries]
        self.child = child

    @property
    def columns(self) -> List[str]:
        """Output names (the view older callers and the plan display use)."""
        return [name for name, _ in self.entries]

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        parts = [name if isinstance(src, str) and src == name
                 else f"{src!r} AS {name}" for name, src in self.entries]
        return f"Project [{', '.join(parts)}]"

    def _project(self, batch: columnar.ColumnBatch) -> columnar.ColumnBatch:
        if all(isinstance(src, str) for _, src in self.entries):
            return batch.select([src for _, src in self.entries])
        from hyperspace_tpu.engine.compiler import ExpressionCompiler
        from hyperspace_tpu.plan.expr import infer_dtype
        from hyperspace_tpu.plan.schema import Field
        compiler = ExpressionCompiler(batch)
        fields: List[Field] = []
        columns = {}
        for name, src in self.entries:
            if isinstance(src, str):
                f = batch.schema.field(src)
                columns[name] = batch.column(src)
                fields.append(Field(name, f.dtype, f.nullable))
            else:
                dtype = infer_dtype(src, batch.schema)
                columns[name] = compiler.value_column(src, dtype)
                fields.append(Field(name, dtype, True))
        return columnar.ColumnBatch(Schema(fields), columns)

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        return self._project(self.child.execute(bucket))

    def execute_bucketed(self, num_buckets: int):
        batch, lengths = self.child.execute_bucketed(num_buckets)
        return self._project(batch), lengths

    def execute_sharded(self, num_buckets: int, mesh, align_plan=None):
        """Pure column selection/renaming preserves the sharded layout
        (same rows, same residency); computed entries evaluate
        element-wise over the sharded columns, which XLA keeps
        shard-local."""
        sh = self.child.execute_sharded(num_buckets, mesh,
                                        align_plan=align_plan)
        if sh is None:
            return None
        from hyperspace_tpu.parallel.spmd import ShardedBatch
        projected = self._project(sh.batch)
        return ShardedBatch(projected, sh.row_valid, sh.mesh,
                            sh.rows_per_shard, sh.num_buckets,
                            lengths=sh.lengths,
                            split_plan=sh.split_plan)


class ExchangeExec(PhysicalNode):
    """Hash repartition — a REAL operator, not a marker. `execute` returns
    rows grouped by hash partition of the keys (the single-chip meaning of
    Spark's ShuffleExchange: same hash identity as the index build, so the
    output layout matches what a bucketed index read produces); with a
    mesh active it lowers to the all_to_all shuffle in `parallel/build.py`
    over ICI. Its presence/absence in the plan is the explain() observable
    — and the work it represents is actually performed or actually elided.
    """

    name = "Exchange"

    def __init__(self, keys: Sequence[str], num_partitions: int,
                 child: PhysicalNode, conf=None):
        self.keys = list(keys)
        self.num_partitions = num_partitions
        self.child = child
        self.conf = conf

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return f"Exchange hashpartitioning({', '.join(self.keys)}, {self.num_partitions})"

    def execute_partitioned(self, bucket: Optional[int] = None):
        """(batch grouped by partition id, per-partition lengths)."""
        return self.partition(self.child.execute(bucket))

    def partition(self, batch: columnar.ColumnBatch):
        """Partition an already-executed batch (the join path unwraps the
        Exchange and feeds the child batch back in)."""
        import numpy as np

        if batch.num_rows == 0:
            return batch, np.zeros(self.num_partitions, dtype=np.int64)
        if batch.is_host:
            from hyperspace_tpu.ops.host_hash import (host_column_hash_lanes,
                                                      host_flat_hash32)
            lanes = []
            for k in self.keys:
                lanes.extend(host_column_hash_lanes(batch.column(k)))
            ids = (host_flat_hash32(lanes)
                   % np.uint32(self.num_partitions)).astype(np.int32)
            perm = np.argsort(ids, kind="stable").astype(np.int32)
            lengths = np.bincount(ids, minlength=self.num_partitions
                                  ).astype(np.int64)
            return batch.take(perm), lengths
        import jax
        import jax.numpy as jnp

        from hyperspace_tpu.ops.pallas.partition_kernel import (
            batch_partition, kernel_supported)
        if kernel_supported(self.num_partitions):
            # Fused Pallas kernel: ids + histogram in ONE HBM pass.
            ids, lengths_dev = batch_partition(batch, self.keys,
                                               self.num_partitions)
            lengths = np.asarray(lengths_dev).astype(np.int64)
        else:
            from hyperspace_tpu.ops.hash_partition import bucket_ids
            ids = bucket_ids(batch, self.keys, self.num_partitions)
            lengths = np.asarray(jax.ops.segment_sum(
                jnp.ones(batch.num_rows, dtype=jnp.int32), ids,
                num_segments=self.num_partitions)).astype(np.int64)
        iota = jnp.arange(batch.num_rows, dtype=jnp.int32)
        _, perm = jax.lax.sort([ids, iota], num_keys=1, is_stable=True)
        return batch.take(perm), lengths

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        return self.execute_partitioned(bucket)[0]

    def execute_bucketed(self, num_buckets: int):
        """An Exchange output satisfies the bucketed contract (batch in
        partition order + lengths) — it is how the planner re-buckets ONE
        side of a mismatched-bucket-count index join (the ranker's cost
        model: ride the larger layout, reshuffle the smaller)."""
        if num_buckets != self.num_partitions:
            raise HyperspaceException(
                f"Exchange partitions ({self.num_partitions}) != requested "
                f"buckets ({num_buckets}).")
        return self.execute_partitioned()


class WindowExec(PhysicalNode):
    name = "Window"

    def __init__(self, partition_by, order_by, specs, out_schema: Schema,
                 child: PhysicalNode):
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.specs = list(specs)
        self.out_schema = out_schema
        self.child = child

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        parts = [f"{s.func}({s.column}) AS {s.alias}" for s in self.specs]
        return (f"Window [{', '.join(parts)}] PARTITION BY "
                f"[{', '.join(self.partition_by)}]")

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.window import window_compute
        batch = self.child.execute(bucket)
        return window_compute(batch, self.partition_by, self.order_by,
                              self.specs, self.out_schema)


class SortExec(PhysicalNode):
    name = "Sort"

    def __init__(self, keys: Sequence[str], child: PhysicalNode):
        self.keys = list(keys)
        self.child = child

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return f"Sort [{', '.join(self.keys)}]"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.sort import sort_batch
        batch = self.child.execute(bucket)
        if batch.num_rows == 0:
            return batch
        return sort_batch(batch, self.keys)


class TopKExec(PhysicalNode):
    """Sort+Limit collapsed (`ops/sort.topk_batch`): ORDER BY + LIMIT n
    computes the exact first n rows via a packed-prefix threshold pass
    plus a small candidate sort, instead of fully sorting (and, on a
    TPU, compiling the minutes-long wide chunked-LSD sort for)
    millions of rows that the limit immediately discards."""

    name = "TopK"

    def __init__(self, n: int, keys: Sequence[str], child: PhysicalNode):
        self.n = n
        self.keys = list(keys)
        self.child = child

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return f"TopK {self.n} [{', '.join(self.keys)}]"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.sort import topk_batch
        batch = self.child.execute(bucket)
        if batch.num_rows == 0:
            return batch
        return topk_batch(batch, self.keys, self.n)


class AggregateExec(PhysicalNode):
    name = "Aggregate"

    def __init__(self, group_columns: Sequence[str], aggregates,
                 out_schema: Schema, child: PhysicalNode, conf=None):
        self.group_columns = list(group_columns)
        self.aggregates = list(aggregates)
        self.out_schema = out_schema
        self.child = child
        self.conf = conf

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        aggs = ", ".join(f"{a.func}({a.column})" for a in self.aggregates)
        return f"Aggregate [{', '.join(self.group_columns)}] [{aggs}]"

    def _materialize_inputs(self, batch: columnar.ColumnBatch):
        """Evaluate expression aggregation inputs (sum(x*y)) into temp
        columns so the segment reducers see plain columns; returns
        (augmented batch, rewritten specs)."""
        from hyperspace_tpu.plan.nodes import AggSpec
        if not any(getattr(s, "is_expression", False)
                   for s in self.aggregates):
            return batch, self.aggregates
        from hyperspace_tpu.engine.compiler import ExpressionCompiler
        from hyperspace_tpu.plan.expr import infer_dtype
        from hyperspace_tpu.plan.schema import Field
        compiler = ExpressionCompiler(batch)
        fields = list(batch.schema.fields)
        columns = dict(batch.columns)
        specs = []
        for i, spec in enumerate(self.aggregates):
            if not spec.is_expression:
                specs.append(spec)
                continue
            dtype = infer_dtype(spec.column, batch.schema)
            name = f"__agg_in_{i}"
            columns[name] = compiler.value_column(spec.column, dtype)
            fields.append(Field(name, dtype, True))
            specs.append(AggSpec(spec.func, name, spec.alias))
        return columnar.ColumnBatch(Schema(fields), columns), specs

    def _mesh(self, batch: columnar.ColumnBatch):
        """The mesh the aggregate distributes over, or None."""
        from hyperspace_tpu.parallel.context import should_distribute
        if (self.group_columns and batch.num_rows > 0 and self.aggregates
                # count_distinct is not decomposable into mergeable
                # per-shard partials (a value present on two shards must
                # not count twice); it — and pure DISTINCT (no aggregate
                # lanes) — stay on the single-device lane.
                and not any(s.func == "count_distinct"
                            for s in self.aggregates)):
            return should_distribute(self.conf, batch.num_rows,
                                     host_batch=batch.is_host)
        return None

    def _direct(self, batch: columnar.ColumnBatch):
        """The key domains where the group-by takes direct address
        (`ops/aggregate.direct_domains`), or None."""
        from hyperspace_tpu.ops.aggregate import direct_domains
        domains = direct_domains(batch, self.group_columns)
        if domains is None or self._mesh(batch) is not None:
            return None
        return domains

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.engine.fusion import FusedStageExec
        from hyperspace_tpu.ops.aggregate import (direct_aggregate,
                                                  group_aggregate)
        mask = None
        if bucket is None and isinstance(self.child, FusedStageExec):
            # a direct-address group-by reads the stage's rows through
            # its selection mask: no compaction
            batch, mask = self.child.execute_masked(
                lambda b: self._direct(b) is not None)
        else:
            batch = self.child.execute(bucket)
        domains = self._direct(batch)
        if domains is not None:
            out = direct_aggregate(batch, self.group_columns,
                                   self.aggregates, self.out_schema,
                                   domains, row_mask=mask)
            telemetry.annotate(groups=out.num_rows,
                               masked=batch.num_rows if mask is not None
                               else 0)
            return out
        batch, specs = self._materialize_inputs(batch)
        mesh = self._mesh(batch)
        if mesh is not None:
            from hyperspace_tpu.parallel.aggregate import (
                distributed_group_aggregate)
            return distributed_group_aggregate(batch, self.group_columns,
                                               specs,
                                               self.out_schema, mesh)
        out = group_aggregate(batch, self.group_columns, specs,
                              self.out_schema)
        telemetry.annotate(groups=out.num_rows, masked=0)
        return out


class LimitExec(PhysicalNode):
    name = "Limit"

    def __init__(self, n: int, child: PhysicalNode):
        self.n = n
        self.child = child

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return f"Limit {self.n}"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        import numpy as np
        batch = self.child.execute(bucket)
        if batch.num_rows <= self.n:
            return batch
        if batch.is_host:
            return batch.take(np.arange(self.n, dtype=np.int32))
        import jax.numpy as jnp
        return batch.take(jnp.arange(self.n, dtype=jnp.int32))


class CrossJoinExec(PhysicalNode):
    """Cartesian product (CROSS JOIN). Exists for the scalar-subquery
    assembly idiom — TPC-DS q28/q61/q88 cross their independent one-row
    aggregates into a single result row — so it is guarded against
    accidental blow-ups rather than optimized for scale. Output naming
    matches the equi-join: right-side duplicates get a `_r` suffix."""

    name = "CrossJoin"
    MAX_ROWS = 50_000_000

    def __init__(self, left: PhysicalNode, right: PhysicalNode):
        self.left = left
        self.right = right

    @property
    def children(self):
        return [self.left, self.right]

    def simple_string(self) -> str:
        return "CrossJoin"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        import numpy as np

        from hyperspace_tpu.plan.schema import Field

        lbatch = self.left.execute(bucket)
        rbatch = self.right.execute(bucket)
        n = lbatch.num_rows * rbatch.num_rows
        if n > self.MAX_ROWS:
            raise HyperspaceException(
                f"Cross join would produce {n} rows "
                f"({lbatch.num_rows} x {rbatch.num_rows}); refusing.")
        lt = lbatch.take(np.repeat(
            np.arange(lbatch.num_rows, dtype=np.int32), rbatch.num_rows))
        rt = rbatch.take(np.tile(
            np.arange(rbatch.num_rows, dtype=np.int32), lbatch.num_rows))
        fields = list(lt.schema.fields)
        columns = dict(lt.columns)
        left_names = {f.name.lower() for f in fields}
        for f in rt.schema.fields:
            name = (f.name if f.name.lower() not in left_names
                    else f.name + "_r")
            fields.append(Field(name, f.dtype, f.nullable))
            columns[name] = rt.columns[f.name]
        return columnar.ColumnBatch(Schema(fields), columns)


class UnionExec(PhysicalNode):
    name = "Union"

    def __init__(self, children: Sequence[PhysicalNode]):
        self._children = list(children)

    @property
    def children(self):
        return list(self._children)

    def simple_string(self) -> str:
        return f"Union ({len(self._children)})"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        batches = [c.execute(bucket) for c in self._children]
        non_empty = [b for b in batches if b.num_rows > 0]
        if not non_empty:
            return batches[0]
        if len(non_empty) == 1:
            return non_empty[0]
        return columnar.concat_batches(non_empty)

    def execute_bucketed(self, num_buckets: int):
        """Hybrid scan as a bucketed source: each child produces the
        (batch, lengths) contract — the index side from its on-disk
        layout, the appended side through the ExchangeExec the planner
        wrapped it in — and the parts are interleaved bucket-major so the
        combined batch satisfies the layout the batched join expects."""
        import numpy as np

        parts = [c.execute_bucketed(num_buckets) for c in self._children]
        if len(parts) == 1:
            return parts[0]
        batches = [b for b, _ in parts]
        total_lengths = np.zeros(num_buckets, dtype=np.int64)
        for _, l in parts:
            total_lengths += np.asarray(l, dtype=np.int64)
        non_empty = [b for b in batches if b.num_rows > 0]
        if not non_empty:
            return batches[0], total_lengths
        combined = (non_empty[0] if len(non_empty) == 1
                    else columnar.concat_batches(batches))
        if len(non_empty) == 1:
            return combined, total_lengths
        # Interleave: rows of bucket b from every part become contiguous.
        base = np.concatenate(
            [[0], np.cumsum([b.num_rows for b in batches])])
        part_offsets = [np.concatenate([[0], np.cumsum(
            np.asarray(l, dtype=np.int64))]) for _, l in parts]
        total = int(total_lengths.sum())
        perm = np.empty(total, dtype=np.int64)
        pos = 0
        for bkt in range(num_buckets):
            for pi in range(len(parts)):
                cnt = int(part_offsets[pi][bkt + 1]
                          - part_offsets[pi][bkt])
                if cnt:
                    start = base[pi] + part_offsets[pi][bkt]
                    perm[pos:pos + cnt] = np.arange(start, start + cnt)
                    pos += cnt
        idx = perm.astype(np.int32)
        if not combined.is_host:
            import jax.numpy as jnp
            idx = jnp.asarray(idx)
        return combined.take(idx), total_lengths


class SetOpExec(PhysicalNode):
    """INTERSECT / EXCEPT (DISTINCT set semantics, NULL == NULL — see
    `ops/setops.py`). Output rows come from the left side in
    first-occurrence order; columns align across sides by name."""

    def __init__(self, left: PhysicalNode, right: PhysicalNode,
                 names: Sequence[str], anti: bool):
        self.left = left
        self.right = right
        self.names = list(names)
        self.anti = anti
        self.name = "Except" if anti else "Intersect"

    @property
    def children(self):
        return [self.left, self.right]

    def simple_string(self) -> str:
        return f"{self.name} [{', '.join(self.names)}]"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.setops import set_op_indices
        lbatch = self.left.execute(bucket)
        rbatch = self.right.execute(bucket)
        idx = set_op_indices(lbatch, rbatch, self.names, self.anti)
        return lbatch.select(self.names).take(idx)


class ReusedExec(PhysicalNode):
    """Common-subplan reuse (Spark's ReuseExchange/ReuseSubquery analog):
    the planner routes every occurrence of an identical logical subtree
    (same serialization, same required columns) through ONE shared node
    that memoizes its executed batch. q64-style self-joins of an
    aggregated subquery then compute it once. Physical plans are built
    fresh per query, so the memo's lifetime is a single execution."""

    name = "ReusedSubplan"

    def __init__(self, child: PhysicalNode):
        import threading
        self.child = child
        self._memo = None
        self._memo_bucketed = {}
        # A self-join submits both sides (the SAME instance) to the join's
        # thread pool; without the lock both threads would fill the memo.
        self._lock = threading.Lock()

    @property
    def children(self):
        return [self.child]

    def simple_string(self) -> str:
        return "ReusedSubplan"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        if bucket is not None:
            return self.child.execute(bucket)
        with self._lock:
            if self._memo is None:
                self._memo = self.child.execute()
            else:
                telemetry.annotate(reused=True)
            return self._memo

    def execute_bucketed(self, num_buckets: int):
        with self._lock:
            if num_buckets not in self._memo_bucketed:
                self._memo_bucketed[num_buckets] = \
                    self.child.execute_bucketed(num_buckets)
            else:
                telemetry.annotate(reused=True)
            return self._memo_bucketed[num_buckets]


class SortMergeJoinExec(PhysicalNode):
    name = "SortMergeJoin"

    def __init__(self, left: PhysicalNode, right: PhysicalNode,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 bucketed: bool, num_buckets: int = 0,
                 out_schema: Optional[Schema] = None, how: str = "inner",
                 conf=None, out_columns: Optional[Set[str]] = None):
        self.left = left
        self.right = right
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.bucketed = bucketed
        self.num_buckets = num_buckets
        self.out_schema = out_schema
        self.how = how
        self.conf = conf
        # Late projection: lowered OUTPUT column names the consumer needs;
        # assembly gathers only these (keys and dropped payload are never
        # materialized through the match expansion).
        self.out_columns = out_columns

    @property
    def children(self):
        return [self.left, self.right]

    def simple_string(self) -> str:
        keys = ", ".join(f"{l}={r}" for l, r in zip(self.left_keys, self.right_keys))
        mode = f"bucketed({self.num_buckets})" if self.bucketed else "global"
        return f"SortMergeJoin {self.how} [{keys}] {mode}"

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.join import sort_merge_join
        if self.bucketed and bucket is None:
            # Born-sharded SPMD fast path — THE distributed execution
            # architecture: both sides resident per device by bucket
            # range, ONE jitted program for the match + expansion, no
            # host re-placement and no mid-join sizing sync. None = some
            # precondition failed (counted as `spmd.fallbacks` when a
            # mesh was available); the single-chip bucketed path below
            # remains fully capable.
            out = self._try_spmd()
            if out is not None:
                return out
        if self.how in ("left_semi", "left_anti"):
            # Membership joins: no expansion, no output from the right —
            # one encode + counting-match membership flags, then a
            # single left-side gather.
            from hyperspace_tpu.ops.join import semi_anti_indices
            anti = self.how == "left_anti"
            if self.bucketed:
                lbatch, rbatch, _l_lengths, _r_lengths = \
                    self._bucketed_inputs()
            else:
                lbatch = self.left.execute(bucket)
                rbatch = self.right.execute(bucket)
            idx = semi_anti_indices(lbatch, rbatch, self.left_keys,
                                    self.right_keys, anti=anti)
            return lbatch.take(idx)
        if self.bucketed:
            # Co-partitioned bucket joins, batched into ONE compiled program
            # (`ops/bucketed_join.py`): zero shuffle, zero global sort, no
            # per-bucket compile explosion.
            from hyperspace_tpu.ops.bucketed_join import (
                bucketed_sort_merge_join)
            lbatch, rbatch, l_lengths, r_lengths = self._bucketed_inputs()
            return bucketed_sort_merge_join(lbatch, rbatch, l_lengths,
                                            r_lengths, self.left_keys,
                                            self.right_keys, how=self.how,
                                            columns=self.out_columns)
        # General path: the planner wrapped each side in
        # Sort(Exchange(...)) — the Spark-shaped plan. BOTH wrappers are
        # unwrapped and genuinely elided at execution: the counting join
        # (`ops/join.py`) matches in ORIGINAL row space over unsorted
        # ids with ONE flat sort, so a real hash repartition + per-side
        # sort (what Spark must do, and what an earlier revision ran for
        # co-partitionable sides) is strictly extra work — it cost ~2s of
        # a 24s scale-30 q64 while feeding the same counting core.
        def unwrap(node):
            if isinstance(node, SortExec):
                node = node.child
            if isinstance(node, ExchangeExec):
                node = node.child
            return node

        lbatch = unwrap(self.left).execute(bucket)
        rbatch = unwrap(self.right).execute(bucket)
        telemetry.annotate(lane=("host" if lbatch.is_host
                                 and rbatch.is_host else "device"),
                           left_rows=lbatch.num_rows,
                           right_rows=rbatch.num_rows)
        return sort_merge_join(lbatch, rbatch, self.left_keys,
                               self.right_keys, how=self.how,
                               columns=self.out_columns)

    def _try_spmd(self) -> Optional[columnar.ColumnBatch]:
        """The born-sharded SPMD join (`parallel/spmd.py`), or None when
        any precondition fails: no mesh / bucket count not divisible /
        either side not shardable (host-lane sizing, skew). Strings are
        first-class (per-range dictionaries + in-program rank remaps).
        Covers every equi-join type of the sharded counting match;
        right_outer swaps sides. A decline WITH a mesh available is a
        real lane miss — counted as `spmd.fallbacks`."""
        from hyperspace_tpu.parallel import spmd
        from hyperspace_tpu.parallel.context import (distribution_mesh,
                                                     mesh_size)

        if self.num_buckets <= 0:
            return None
        if self.conf is not None and not self.conf.distribution_spmd:
            return None  # the operational escape hatch: single-chip only
        mesh = distribution_mesh(self.conf)
        if mesh is None:
            return None
        if self.how not in ("inner", "left_outer", "right_outer",
                            "full_outer", "left_semi", "left_anti"):
            spmd.spmd_fallback("join-type")
            return None
        if self.num_buckets % mesh_size(mesh) != 0:
            spmd.spmd_fallback("bucket-count-indivisible")
            return None
        # One device-queue scope for the whole sharded join (reads,
        # match program, output assembly): on emulated meshes two
        # concurrent multi-device programs over one device set can
        # interleave into a collective-rendezvous deadlock; the
        # reentrant per-device-set guard serializes them exactly as a
        # real device queue would, while queries pinned to DISJOINT
        # replica slices still run concurrently (no-op off CPU).
        with spmd.dispatch_guard(mesh):
            return self._run_spmd(mesh)

    def _run_spmd(self, mesh) -> Optional[columnar.ColumnBatch]:
        from hyperspace_tpu.parallel import spmd

        lsh = self.left.execute_sharded(self.num_buckets, mesh)
        if lsh is None:
            spmd.spmd_fallback("left-not-shardable")
            return None
        align = lsh.split_plan
        if align is not None:
            # Hot-bucket skew on the left: the right side reads ALIGNED
            # to the split (intersected buckets replicated per covering
            # shard). Replication breaks unmatched-right uniqueness, so
            # full_outer routes off the lane; membership/inner/left
            # shapes are bit-identical (each left row lives on exactly
            # one shard and meets every matching right row locally).
            if self.how == "full_outer":
                spmd.spmd_fallback("subshard-join-type")
                return None
            if self.how == "right_outer":
                spmd.spmd_fallback("subshard-right-outer")
                return None
            rsh = self.right.execute_sharded(self.num_buckets, mesh,
                                             align_plan=align)
        else:
            rsh = self.right.execute_sharded(self.num_buckets, mesh)
        if rsh is None:
            spmd.spmd_fallback("right-not-shardable")
            return None
        if align is None and rsh.split_plan is not None:
            # Right-side-only skew: the counting layout would need the
            # LEFT replicated, which breaks unmatched-left uniqueness
            # (outer) and duplicates membership take indices (semi /
            # anti). INNER has no unmatched-row semantics on either
            # side, so swap roles instead of declining: re-read the
            # left ALIGNED to the right's split (each right row lives
            # on exactly one shard; intersecting left buckets replicate
            # per covering shard) and run the counting match with the
            # right as the preserved side — bit-identical inner output,
            # one extra left read instead of a full lane miss.
            if self.how != "inner":
                spmd.spmd_fallback("subshard-right")
                return None
            lsh = self.left.execute_sharded(self.num_buckets, mesh,
                                            align_plan=rsh.split_plan)
            if lsh is None:
                spmd.spmd_fallback("subshard-right")
                return None
            telemetry.get_registry().counter(
                "mesh.spmd.side_swapped").inc()
            telemetry.annotate(lane="spmd")
            from hyperspace_tpu.ops.bucketed_join import (
                assemble_join_output)
            ri, li = spmd.sharded_join_indices(
                rsh, lsh, self.right_keys, self.left_keys, how="inner",
                conf=self.conf)
            return assemble_join_output(lsh.batch, rsh.batch, li, ri,
                                        how="inner",
                                        columns=self.out_columns)
        telemetry.annotate(lane="spmd")
        if self.how in ("left_semi", "left_anti"):
            idx = spmd.sharded_semi_anti_indices(
                lsh, rsh, self.left_keys, self.right_keys,
                anti=self.how == "left_anti", conf=self.conf)
            return lsh.batch.take(idx)
        from hyperspace_tpu.ops.bucketed_join import assemble_join_output
        if self.how == "right_outer":
            ri, li = spmd.sharded_join_indices(
                rsh, lsh, self.right_keys, self.left_keys,
                how="left_outer", conf=self.conf)
        else:
            li, ri = spmd.sharded_join_indices(
                lsh, rsh, self.left_keys, self.right_keys, how=self.how,
                conf=self.conf)
        return assemble_join_output(lsh.batch, rsh.batch, li, ri,
                                    how=self.how,
                                    columns=self.out_columns)

    def _bucketed_inputs(self):
        """Read both sides in bucket order (overlapped IO) for the
        single-chip batched bucketed join — the one general path under
        the SPMD lane (the legacy per-query-placement mesh join is
        gone; `parallel/mesh.py` is the sole sharding seam)."""
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=2) as pool:
            # telemetry.propagating: pool threads don't inherit the
            # query's recorder context — re-establish it so each side's
            # scans record under this join.
            lf = pool.submit(telemetry.propagating(
                self.left.execute_bucketed), self.num_buckets)
            rf = pool.submit(telemetry.propagating(
                self.right.execute_bucketed), self.num_buckets)
            lbatch, l_lengths = lf.result()
            rbatch, r_lengths = rf.result()
        telemetry.annotate(lane=("host" if lbatch.is_host
                                 and rbatch.is_host else "device"))
        return lbatch, rbatch, l_lengths, r_lengths


class BroadcastHashJoinExec(PhysicalNode):
    """Small-side join with NO Exchange/Sort on either side — the engine's
    analog of Spark's BroadcastHashJoin, which the reference leans on for
    every dimension join (`E2EHyperspaceRulesTests.scala:42` must disable
    it to exercise the SMJ path). The planner routes a join here when one
    side's estimated size is under `spark.hyperspace.broadcast.threshold`;
    execution replicates that side as a direct-address lookup table and
    matches probe rows with one gather (`ops/broadcast_join.py`). When the
    keys are ineligible at run time (strings/floats/duplicates/wide
    ranges), the counting join runs on the bare batches instead — still
    zero Exchange, just without the no-sort shortcut."""

    name = "BroadcastHashJoin"

    def __init__(self, left: PhysicalNode, right: PhysicalNode,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 build_side: str, how: str = "inner", conf=None,
                 out_columns: Optional[Set[str]] = None):
        self.left = left
        self.right = right
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.build_side = build_side  # "left" | "right"
        self.how = how
        self.conf = conf
        self.out_columns = out_columns

    @property
    def children(self):
        return [self.left, self.right]

    def simple_string(self) -> str:
        keys = ", ".join(f"{l}={r}"
                         for l, r in zip(self.left_keys, self.right_keys))
        return (f"BroadcastHashJoin {self.how} [{keys}] "
                f"build={self.build_side}")

    def execute(self, bucket: Optional[int] = None) -> columnar.ColumnBatch:
        from hyperspace_tpu.ops.broadcast_join import (broadcast_join_indices,
                                                       broadcast_membership)
        from hyperspace_tpu.ops.bucketed_join import assemble_join_output
        from hyperspace_tpu.ops.join import (semi_anti_indices,
                                             sort_merge_join)

        lbatch = self.left.execute(bucket)
        rbatch = self.right.execute(bucket)
        probe, build = ((lbatch, rbatch) if self.build_side == "right"
                        else (rbatch, lbatch))

        def served(path: str) -> None:
            # which path served the join, on the operator's record:
            # the direct-address probe, or the counting join it
            # declined to (strings, floats, duplicate build keys, ...)
            telemetry.annotate(
                lane="host" if probe.is_host else "device", path=path,
                probe_rows=probe.num_rows, build_rows=build.num_rows)

        if self.how in ("left_semi", "left_anti"):
            anti = self.how == "left_anti"
            idx = broadcast_membership(lbatch, rbatch, self.left_keys,
                                       self.right_keys, anti=anti)
            served("direct-address" if idx is not None else "counting")
            if idx is None:
                idx = semi_anti_indices(lbatch, rbatch, self.left_keys,
                                        self.right_keys, anti=anti)
            return lbatch.take(idx)
        if self.build_side == "right":
            pair = broadcast_join_indices(lbatch, rbatch, self.left_keys,
                                          self.right_keys, self.how)
            if pair is not None:
                li, ri = pair
                served("direct-address")
                return assemble_join_output(lbatch, rbatch, li, ri,
                                            how=self.how,
                                            columns=self.out_columns)
        else:
            pair = broadcast_join_indices(
                rbatch, lbatch, self.right_keys, self.left_keys,
                "left_outer" if self.how == "right_outer" else "inner")
            if pair is not None:
                ri, li = pair
                served("direct-address")
                return assemble_join_output(lbatch, rbatch, li, ri,
                                            how=self.how,
                                            columns=self.out_columns)
        served("counting")
        return sort_merge_join(lbatch, rbatch, self.left_keys,
                               self.right_keys, how=self.how,
                               columns=self.out_columns)


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


_PRUNE_MAX_COMBOS = 64


def _literal_values_for(column: str, conjuncts) -> Optional[List]:
    """Literal values `column` may take under the conjunction, from the
    narrowest `col = lit` / `col IN (lits)` constraint; None if
    unconstrained (or only constrained through nulls, where pruning is
    skipped — `x = NULL` is never true, so correctness never depends on
    pruning)."""
    best: Optional[List] = None
    for c in conjuncts:
        values = None
        if isinstance(c, E.EqualTo):
            a, b = c.left, c.right
            if isinstance(a, E.Column) and isinstance(b, E.Literal):
                values = [b.value] if a.name.lower() == column else None
            elif isinstance(b, E.Column) and isinstance(a, E.Literal):
                values = [a.value] if b.name.lower() == column else None
        elif (isinstance(c, E.In) and isinstance(c.child, E.Column)
              and c.child.name.lower() == column):
            values = [v.value for v in c.values]
        if values is None or any(v is None for v in values):
            continue
        if best is None or len(values) < len(best):
            best = values
    return best


def _prune_buckets(condition: E.Expression,
                   scan: Scan) -> Optional[Set[int]]:
    """Bucket ids that can contain rows satisfying `condition`, or None
    when pruning does not apply. Sound because every bucket column must be
    pinned to literals by top-level conjuncts: any matching row hashes to
    one of the returned buckets. The literal tuples are hashed with THE
    build hash kernel (`ops/hash_partition.bucket_ids`) so the computed
    ids match the on-disk layout exactly."""
    import itertools

    spec = scan.bucket_spec
    if spec is None:
        return None
    conjuncts = E.split_conjunctive(condition)
    per_column: List[List] = []
    for c in spec.bucket_columns:
        values = _literal_values_for(c.lower(), conjuncts)
        if values is None:
            return None
        per_column.append(values)
    combos = list(itertools.product(*per_column))
    if not combos or len(combos) > _PRUNE_MAX_COMBOS:
        return None
    import numpy as np_

    from hyperspace_tpu.ops.host_hash import host_bucket_ids

    key_schema = scan.schema.select(list(spec.bucket_columns))
    np_of = {"int64": np_.int64, "int32": np_.int32, "int16": np_.int16,
             "int8": np_.int8, "bool": np_.bool_, "float64": np_.float64,
             "float32": np_.float32, "date32": np_.int32,
             "timestamp": np_.int64, "string": None}
    try:
        columns = []
        for i, f in enumerate(key_schema.fields):
            vals = [combo[i] for combo in combos]
            dt = np_of[f.dtype]
            columns.append(np_.asarray(vals, dtype=str) if dt is None
                           else np_.asarray(vals).astype(dt))
        # Host mirror of the build hash — no device round-trip; identity
        # pinned against `ops/hash_partition.bucket_ids` by test.
        ids = host_bucket_ids(columns, [f.dtype for f in key_schema.fields],
                              spec.num_buckets)
    except (ValueError, TypeError, OverflowError, HyperspaceException):
        return None  # literal not representable in the key type -> no prune
    return set(int(b) for b in ids)


def _apply_bucket_pruning(condition: E.Expression, child: PhysicalNode):
    """Descend Project/Filter chains — and Union fan-outs (hybrid scan:
    index UNION appended files) — to each ScanExec and attach the allowed
    bucket set derived from the filter condition (no-op on unbucketed
    scans). Descending through an intermediate Filter (e.g. the hybrid
    lineage exclusion) is sound: pruning only drops buckets no row of
    which can satisfy the OUTER condition, and inner filters only remove
    more rows."""
    node = child
    while isinstance(node, (ProjectExec, FilterExec)):
        node = node.child
    if isinstance(node, UnionExec):
        for c in node.children:
            _apply_bucket_pruning(condition, c)
    elif isinstance(node, ScanExec) and node.allowed_buckets is None:
        node.allowed_buckets = _prune_buckets(condition, node.scan)
    return child


def _hoist_union(plan: LogicalPlan) -> LogicalPlan:
    """Pull a Union above Filter/Project wrappers (both distribute over
    union row-wise) so join-over-union distribution can see it."""
    if isinstance(plan, (Project, Filter)):
        child = _hoist_union(plan.child)
        if isinstance(child, Union):
            return Union([plan.with_children([c])
                          for c in child.children])
    return plan


def _chain_has_bucketed_scan(node: PhysicalNode) -> bool:
    while isinstance(node, (ProjectExec, FilterExec, ReusedExec)):
        node = node.child
    return isinstance(node, ScanExec) and node.scan.bucket_spec is not None


def _bucketize_union_children(node: PhysicalNode, keys: List[str],
                              num_buckets: int, conf) -> None:
    """Descend a join side's Project/Filter chain; if it feeds a UnionExec
    (hybrid scan), wrap each child that does NOT ride a bucketed layout in
    an ExchangeExec over the join keys — the appended slice then arrives
    co-partitioned with the index buckets. Idempotent (a shared/reused
    union may be visited by both sides of a self-join)."""
    while isinstance(node, (ProjectExec, FilterExec, ReusedExec)):
        node = node.child
    if not isinstance(node, UnionExec):
        return
    wrapped = []
    for c in node._children:
        if _chain_has_bucketed_scan(c) or (
                isinstance(c, ExchangeExec)
                and c.num_partitions == num_buckets):
            wrapped.append(c)
        else:
            wrapped.append(ExchangeExec(keys, num_buckets, c, conf=conf))
    node._children = wrapped


def _split_join_required(required: Set[str], left_schema: Schema,
                         right_schema: Schema, left_keys=(), right_keys=()):
    """Split a join's required OUTPUT names into per-side input column
    sets. A required `<name>_r` maps back to the right-side source AND
    keeps the left-side copy alive — the executor renames the right
    column only when the left batch still carries the collision, so
    pruning the left copy would silently un-suffix the output. ONE home
    for this rule (equi and cross branches both had a hand copy; the
    cross copy had already drifted and dropped the left side)."""
    left_req = ({n for n in required if left_schema.contains(n)}
                | set(left_keys))
    right_req = ({n for n in required if right_schema.contains(n)}
                 | set(right_keys))
    for n in required:
        base = n[:-2] if n.lower().endswith("_r") else None
        if (base and right_schema.contains(base)
                and left_schema.contains(base)):
            right_req.add(base)
            left_req.add(base)
    return left_req, right_req


def _join_keys(condition: E.Expression, left_schema: Schema,
               right_schema: Schema) -> Tuple[List[str], List[str]]:
    """Extract equi-join key pairs from an AND-of-equalities condition
    (reference applicability: `JoinIndexRule.scala:179-185,278-317`)."""
    left_keys: List[str] = []
    right_keys: List[str] = []
    for conjunct in E.split_conjunctive(condition):
        if not isinstance(conjunct, E.EqualTo):
            raise HyperspaceException(
                f"Only equi-join conditions are supported; got {conjunct!r}")
        a, b = conjunct.left, conjunct.right
        if not isinstance(a, E.Column) or not isinstance(b, E.Column):
            raise HyperspaceException(
                "Join condition must compare columns directly.")
        if left_schema.contains(a.name) and right_schema.contains(b.name):
            left_keys.append(a.name)
            right_keys.append(b.name)
        elif left_schema.contains(b.name) and right_schema.contains(a.name):
            left_keys.append(b.name)
            right_keys.append(a.name)
        else:
            raise HyperspaceException(
                f"Join columns not found on both sides: {conjunct!r}")
    return left_keys, right_keys


def _underlying_bucket_spec(plan: LogicalPlan) -> Optional[BucketSpec]:
    """The bucket spec of the scan feeding a linear Filter/Project chain —
    filters and projections preserve bucketing and intra-bucket order. A
    Union whose FIRST child rides a bucketed layout (hybrid scan: index
    data UNION appended files) reports that spec; the planner re-buckets
    the remaining children through ExchangeExec at execution time."""
    node = plan
    while True:
        if isinstance(node, Scan):
            return node.bucket_spec
        if isinstance(node, (Filter, Project)):
            node = node.child
            continue
        if isinstance(node, Union):
            return _underlying_bucket_spec(node.children[0])
        return None


# Approximate in-memory bytes per value; strings budget code + a share of
# the dictionary. Only relative accuracy vs the broadcast threshold
# matters (Spark's estimate — raw file size — is no finer).
_DTYPE_WIDTH = {"bool": 1, "int8": 1, "int16": 2, "int32": 4, "date32": 4,
                "float32": 4, "int64": 8, "float64": 8, "timestamp": 8,
                "string": 16}


def _estimated_plan_bytes(plan: LogicalPlan,
                          required: Set[str]) -> Optional[int]:
    """Upper-bound decoded bytes of `plan`'s output restricted to
    `required`, from parquet footer row counts (cached; no data read).
    None when the subtree's cardinality is not statically bounded by its
    scans — aggregates/joins/windows can shrink OR grow, so they never
    qualify a side for broadcast. Mirrors what Spark's
    `autoBroadcastJoinThreshold` keys on (leaf statistics propagated
    through Filter/Project)."""
    if isinstance(plan, Scan):
        files = plan.files()
        if not files:
            return 0
        try:
            rows = sum(parquet.file_row_counts(files))
        except Exception:
            return None
        lowered = {r.lower() for r in required}
        width = sum(_DTYPE_WIDTH.get(f.dtype, 8) for f in plan.schema.fields
                    if f.name.lower() in lowered)
        return rows * max(width, 1)
    if isinstance(plan, (Filter, Sort, Limit)):
        # Row count bounded by the child's (Filter/Limit only shrink).
        return _estimated_plan_bytes(plan.child, required)
    if isinstance(plan, Project):
        # Map required OUTPUT names back through the projection to child
        # columns (Spark's statistics propagation does the same): a
        # renamed/computed column must contribute its SOURCE columns'
        # width, not silently zero — a side whose broadcast-relevant
        # columns are all computed would otherwise be underestimated and
        # admitted past the threshold. Unmappable entries fall back to
        # the full child width.
        lowered = {r.lower() for r in required}
        child_req: Set[str] = set()
        for c in plan.columns:
            if isinstance(c, str):
                if c.lower() in lowered:
                    child_req.add(c)
                continue
            if c.name.lower() not in lowered:
                continue
            try:
                refs = c.child.references()
            except Exception:
                return _estimated_plan_bytes(
                    plan.child, set(plan.child.schema.names))
            child_req |= refs
        return _estimated_plan_bytes(plan.child, child_req)
    if isinstance(plan, Union):
        total = 0
        for c in plan.children:
            est = _estimated_plan_bytes(c, required)
            if est is None:
                return None
            total += est
        return total
    return None


def _required_for(plan: LogicalPlan, required: Set[str]) -> List[str]:
    """required column names resolved against plan schema, in schema order."""
    schema = plan.schema
    lowered = {r.lower() for r in required}
    return [f.name for f in schema.fields if f.name.lower() in lowered]


def plan_physical(plan: LogicalPlan,
                  required: Optional[Set[str]] = None,
                  conf=None) -> PhysicalNode:
    """Logical -> physical with projection pushdown into scans. `conf`
    carries the session's distribution settings to the operators that can
    execute on the mesh (Filter scans, bucketed SMJ). Identical logical
    subtrees (by fingerprint + required columns) compile to ONE shared
    `ReusedExec` so repeated subqueries execute once."""
    counts: dict = {}
    keys: dict = {}

    def _count(node):
        key = _subtree_key(node, keys)
        counts[key] = counts.get(key, 0) + 1
        for c in node.children:
            _count(c)

    _count(plan)
    return _plan_physical(plan, required, conf,
                          {"counts": counts, "keys": keys, "built": {}})


def _subtree_key(node: LogicalPlan, memo: dict) -> str:
    """Bottom-up md5 fingerprint of a subtree: each node hashes its LOCAL
    fields plus its children's fingerprints, so the whole walk is O(nodes)
    instead of re-serializing every subtree per ancestor. Memoized by node
    identity (nodes stay alive for the duration of planning)."""
    import hashlib
    import json as _json

    k = memo.get(id(node))
    if k is not None:
        return k
    local = node.to_dict()
    for field in ("child", "children", "left", "right"):
        local.pop(field, None)
    payload = (type(node).__name__
               + _json.dumps(local, sort_keys=True)
               + "[" + ",".join(_subtree_key(c, memo)
                                for c in node.children) + "]")
    k = hashlib.md5(payload.encode()).hexdigest()
    memo[id(node)] = k
    return k


def _is_prunable_chain(plan: LogicalPlan) -> bool:
    """Project*/Scan chain over a bucketed scan with no Filter inside —
    the shape `_apply_bucket_pruning` prunes FROM ABOVE. Sharing it would
    either disable pruning or wrongly prune one consumer's rows with
    another's condition, so such chains are never reused (their IO is
    deduplicated by the decoded-read cache anyway)."""
    node = plan
    while isinstance(node, Project):
        node = node.child
    return isinstance(node, Scan) and node.bucket_spec is not None


def _plan_physical(plan: LogicalPlan,
                   required: Optional[Set[str]],
                   conf, ctx) -> PhysicalNode:
    if required is None:
        required = set(plan.schema.names)

    parent_count = ctx.get("parent_count", 1)
    reuse_key = None
    count = parent_count
    if plan.children and not _is_prunable_chain(plan):
        # (leaves are covered by the decoded-read cache)
        subtree = _subtree_key(plan, ctx["keys"])
        count = ctx["counts"].get(subtree, 0)
        # Only MAXIMAL shared subtrees get a ReusedExec: inside a shared
        # subtree every descendant repeats as often as its ancestor, but
        # the ancestor's memo already deduplicates the whole region —
        # inner wrappers would only chop the operator chain into 1-op
        # fragments (defeating whole-stage fusion) and pay per-node
        # locking. A descendant shared MORE widely than its ancestor
        # (used elsewhere too) still gets its own wrapper. The enclosing
        # share count scopes through ctx (saved/restored around the
        # subtree build).
        if count > parent_count:
            reuse_key = (subtree,
                         frozenset(r.lower() for r in required))
            shared = ctx["built"].get(reuse_key)
            if shared is not None:
                return shared

    ctx["parent_count"] = max(parent_count, count)
    try:
        built = _plan_physical_node(plan, required, conf, ctx)
    finally:
        ctx["parent_count"] = parent_count
    if reuse_key is not None:
        built = ReusedExec(built)
        ctx["built"][reuse_key] = built
    return built


def _plan_physical_node(plan: LogicalPlan,
                        required: Set[str],
                        conf, ctx) -> PhysicalNode:

    if isinstance(plan, Scan):
        return ScanExec(plan, _required_for(plan, required), conf=conf)

    if isinstance(plan, Filter):
        child_required = set(required) | plan.condition.references()
        child = _apply_bucket_pruning(
            plan.condition,
            _plan_physical(plan.child, child_required, conf, ctx))
        return FilterExec(plan.condition, child, conf=conf)

    if isinstance(plan, Project):
        child = _plan_physical(plan.child, plan.references(), conf, ctx)
        # Resolve names against the child schema but KEEP the declared
        # order; computed entries carry their expression.
        entries = []
        for c in plan.columns:
            if isinstance(c, str):
                f = plan.child.schema.field(c)
                entries.append((f.name, f.name))
            else:
                entries.append((c.name, c.child))
        return ProjectExec(entries, child)

    if isinstance(plan, Aggregate):
        child_required = set(plan.group_columns)
        for a in plan.aggregates:
            child_required |= a.references()
        if not child_required:
            # Bare count(*): a ColumnBatch carries its row count only
            # through its columns, so read at least one.
            child_required = {plan.child.schema.names[0]}
        return AggregateExec(plan.group_columns, plan.aggregates,
                             plan.schema,
                             _plan_physical(plan.child, child_required,
                                            conf, ctx),
                             conf=conf)

    if isinstance(plan, Window):
        from hyperspace_tpu.plan.nodes import sort_direction
        aliases = {s.alias.lower() for s in plan.specs}
        child_required = ({n for n in required if n.lower() not in aliases
                           and plan.child.schema.contains(n)}
                          | set(plan.partition_by)
                          | {sort_direction(c)[0] for c in plan.order_by})
        for s in plan.specs:
            child_required |= s.references()
        if not child_required:
            child_required = {plan.child.schema.names[0]}
        # Output schema restricted to what survives pruning: child columns
        # actually read + every window column.
        child_phys = _plan_physical(plan.child, child_required, conf, ctx)
        from hyperspace_tpu.plan.schema import Schema as _Schema
        kept = {n.lower() for n in child_required}
        fields = [f for f in plan.child.schema.fields
                  if f.name.lower() in kept]
        out_schema = _Schema(fields + [plan.schema.field(s.alias)
                                       for s in plan.specs])
        return WindowExec(plan.partition_by, plan.order_by, plan.specs,
                          out_schema, child_phys)

    if isinstance(plan, Sort):
        from hyperspace_tpu.plan.nodes import sort_direction
        child_required = (set(required)
                          | {sort_direction(c)[0] for c in plan.columns})
        return SortExec(plan.columns,
                        _plan_physical(plan.child, child_required, conf,
                                       ctx))

    if isinstance(plan, Limit):
        if isinstance(plan.child, Sort):
            from hyperspace_tpu.plan.nodes import sort_direction
            child_required = (set(required) | {sort_direction(c)[0]
                                               for c in plan.child.columns})
            return TopKExec(plan.n, plan.child.columns,
                            _plan_physical(plan.child.child, child_required,
                                           conf, ctx))
        return LimitExec(plan.n,
                         _plan_physical(plan.child, required, conf, ctx))

    if isinstance(plan, Union):
        # Children may expose different column orders for the same names
        # (index schema vs source schema): normalize through a Project.
        wanted = _required_for(plan, required)
        return UnionExec([
            ProjectExec([(c.schema.field(n).name, c.schema.field(n).name)
                         for n in wanted],
                        _plan_physical(c, set(wanted), conf, ctx))
            for c in plan.children])

    if isinstance(plan, SetOp):
        # Set-op identity is over FULL rows of the node schema: children
        # must produce every column regardless of what the parent needs.
        names = [f.name for f in plan.left.schema.fields]
        left_phys = _plan_physical(plan.left, set(names), conf, ctx)
        right_phys = _plan_physical(
            plan.right, set(plan.right.schema.names), conf, ctx)
        return SetOpExec(left_phys, right_phys, names,
                         anti=isinstance(plan, Except))

    if isinstance(plan, Join):
        if plan.join_type == "cross":
            left_req, right_req = _split_join_required(
                set(required), plan.left.schema, plan.right.schema)
            # A side no output column resolves to must still read ONE
            # column: a zero-column batch reports num_rows == 0 and would
            # collapse the whole product (same floor the Aggregate
            # planner applies for bare count(*)).
            if not left_req:
                left_req = {plan.left.schema.names[0]}
            if not right_req:
                right_req = {plan.right.schema.names[0]}
            return CrossJoinExec(
                _plan_physical(plan.left, left_req, conf, ctx),
                _plan_physical(plan.right, right_req, conf, ctx))
        # Join-over-union distribution: (A UNION B) JOIN R executes as
        # (A JOIN R) UNION (B JOIN R) when the join type distributes over
        # that side. The hybrid-scan Union then keeps its index part on
        # the native bucketed fast path while only the (small) appended
        # part pays a general join; the shared right subtree executes
        # once via ReusedExec. Filter/Project wrappers themselves
        # distribute over Union, so the union is hoisted through them
        # first.
        left_h = _hoist_union(plan.left)
        right_h = _hoist_union(plan.right)
        if (isinstance(left_h, Union)
                and plan.join_type in ("inner", "left_outer", "left_semi",
                                       "left_anti")):
            branches = len(left_h.children)
            k = _subtree_key(plan.right, ctx["keys"])
            ctx["counts"][k] = ctx["counts"].get(k, 0) + branches - 1
            return _plan_physical_node(
                Union([Join(c, plan.right, plan.condition, plan.join_type)
                       for c in left_h.children]), required, conf, ctx)
        if (isinstance(right_h, Union)
                and plan.join_type in ("inner", "right_outer")):
            branches = len(right_h.children)
            k = _subtree_key(plan.left, ctx["keys"])
            ctx["counts"][k] = ctx["counts"].get(k, 0) + branches - 1
            return _plan_physical_node(
                Union([Join(plan.left, c, plan.condition, plan.join_type)
                       for c in right_h.children]), required, conf, ctx)
        left_keys, right_keys = _join_keys(plan.condition, plan.left.schema,
                                           plan.right.schema)
        membership = plan.join_type in ("left_semi", "left_anti")
        if membership:
            # Membership join: the right side contributes only its keys.
            out_columns = None
            left_required = ({n for n in required
                              if plan.left.schema.contains(n)}
                             | set(left_keys))
            right_required = set(right_keys)
        else:
            out_columns = {n.lower() for n in required}
            left_required, right_required = _split_join_required(
                set(required), plan.left.schema, plan.right.schema,
                left_keys, right_keys)
        left_phys = _plan_physical(plan.left, left_required, conf, ctx)
        right_phys = _plan_physical(plan.right, right_required, conf, ctx)

        lspec = _underlying_bucket_spec(plan.left)
        rspec = _underlying_bucket_spec(plan.right)

        def _align_to_spec(spec: Optional[BucketSpec]):
            """Reorder the (left, right) key PAIRS so the left list matches
            `spec.bucket_columns`. The CONDITION's conjunct order is
            irrelevant to bucketing — each side hashes in its own
            indexed-column order — so a join written `b = b AND a = a`
            over an (a, b) layout must still take the bucketed path
            (q50's ticket-identity join was silently demoted to
            Exchange+Sort by the old exact-order check). None when the
            key set is not exactly the bucket column set."""
            if spec is None or len(spec.bucket_columns) != len(left_keys):
                return None
            lk_lower = [k.lower() for k in left_keys]
            order = []
            for bc in spec.bucket_columns:
                if bc.lower() not in lk_lower:
                    return None
                order.append(lk_lower.index(bc.lower()))
            if len(set(order)) != len(order):
                return None
            return ([left_keys[i] for i in order],
                    [right_keys[i] for i in order])

        def _key_dtypes_match() -> bool:
            # Co-partitioning assumes both layouts hashed with the SAME
            # lane decomposition; int32 vs int64 (or float32 vs float64)
            # keys bucket equal values differently, so any bucketed path
            # would silently drop matches — fall through to the general
            # path, which promotes dtypes before encoding.
            return all(plan.left.schema.field(lk).dtype
                       == plan.right.schema.field(rk).dtype
                       for lk, rk in zip(left_keys, right_keys))

        threshold = conf.broadcast_threshold if conf is not None else 0
        if membership and threshold > 0:
            # For MEMBERSHIP joins a small right side beats even an
            # aligned bucketed layout: the direct-address probe is one
            # gather over the left, no joint counting match — so
            # broadcast outranks the bucketed path here (unlike payload
            # joins, where the index pair's zero-work layout wins).
            est = _estimated_plan_bytes(plan.right, right_required)
            if est is not None and est <= threshold:
                return BroadcastHashJoinExec(left_phys, right_phys,
                                             left_keys, right_keys,
                                             build_side="right",
                                             how=plan.join_type, conf=conf,
                                             out_columns=out_columns)

        aligned = _align_to_spec(lspec)
        # The right layout must hash the MAPPED columns in the same
        # positions (the rule's order-compat requirement; checked here
        # too for hand-built bucketed joins).
        if (aligned is None or rspec is None
                or [c.lower() for c in rspec.bucket_columns]
                != [k.lower() for k in aligned[1]]):
            aligned = None

        if aligned is not None and _key_dtypes_match():
            left_keys, right_keys = aligned
            # Bucketed SMJ — the indexed fast path. With mismatched bucket
            # counts (the ranker's fallback, reference
            # `JoinIndexRanker.scala:40-55`) ONLY the coarser side is
            # re-bucketed through Exchange to the finer count; the
            # Exchange uses THE hash identity, so its output co-partitions
            # with the other side's on-disk buckets.
            target = max(lspec.num_buckets, rspec.num_buckets)
            if lspec.num_buckets != target:
                left_phys = ExchangeExec(left_keys, target, left_phys,
                                         conf=conf)
            elif rspec.num_buckets != target:
                right_phys = ExchangeExec(right_keys, target, right_phys,
                                          conf=conf)
            # Hybrid-scan sides: re-bucket the appended (unbucketed) Union
            # children through THE hash Exchange so they co-partition with
            # the index layout.
            _bucketize_union_children(left_phys, left_keys, target, conf)
            _bucketize_union_children(right_phys, right_keys, target, conf)
            return SortMergeJoinExec(left_phys, right_phys, left_keys,
                                     right_keys, bucketed=True,
                                     num_buckets=target,
                                     how=plan.join_type, conf=conf,
                                     out_columns=out_columns)
        # Broadcast path: one side estimated small (dimension tables) —
        # no Exchange/Sort on EITHER side; the build side replicates as a
        # direct-address table. The reference relies on Spark's
        # BroadcastHashJoin for exactly these joins; disable with
        # `spark.hyperspace.broadcast.threshold = -1` (the analog of the
        # reference E2E suite pinning autoBroadcastJoinThreshold to -1,
        # `E2EHyperspaceRulesTests.scala:42`). The probe side must keep
        # ALL its rows, so outer joins only broadcast their inner side.
        if threshold > 0:
            build = None
            if plan.join_type in ("inner", "left_outer"):
                est = _estimated_plan_bytes(plan.right, right_required)
                if est is not None and est <= threshold:
                    build = "right"
            if build is None and plan.join_type in ("inner", "right_outer"):
                est = _estimated_plan_bytes(plan.left, left_required)
                if est is not None and est <= threshold:
                    build = "left"
            if build is not None:
                return BroadcastHashJoinExec(left_phys, right_phys,
                                             left_keys, right_keys,
                                             build_side=build,
                                             how=plan.join_type, conf=conf,
                                             out_columns=out_columns)
        if membership:
            # Bare membership probe: Exchange/Sort wrappers would be pure
            # overhead — the counting match sorts only ids.
            return SortMergeJoinExec(left_phys, right_phys, left_keys,
                                     right_keys, bucketed=False,
                                     how=plan.join_type, conf=conf)
        # General path: hash exchange + sort on each side.
        num_partitions = max(lspec.num_buckets if lspec else 0,
                             rspec.num_buckets if rspec else 0, 200)
        left_sorted = SortExec(left_keys, ExchangeExec(left_keys,
                                                       num_partitions,
                                                       left_phys, conf=conf))
        right_sorted = SortExec(right_keys, ExchangeExec(right_keys,
                                                         num_partitions,
                                                         right_phys,
                                                         conf=conf))
        return SortMergeJoinExec(left_sorted, right_sorted, left_keys,
                                 right_keys, bucketed=False,
                                 how=plan.join_type, conf=conf,
                                 out_columns=out_columns)

    raise HyperspaceException(f"Cannot plan node: {plan!r}")
