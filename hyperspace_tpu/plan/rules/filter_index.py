"""FilterIndexRule: redirect filter queries to covering indexes.

Parity: reference `index/rules/FilterIndexRule.scala:41-229`.
- Matches `Project(Filter(Scan))` and bare `Filter(Scan)`.
- Candidate = ACTIVE index whose signature matches the plan AND that covers
  it: the filter must reference the index's FIRST indexed column, and
  project+filter columns must be a subset of indexed+included columns
  (reference `:203-215`).
- Ranking is cost-based — smallest on-disk index (fallback: fewest
  columns), more buckets on ties — exceeding the reference's first-wins
  placeholder (`:222-228`).
- Replacement keeps Project+Filter but swaps the relation for a scan over
  the index data root with NO bucket spec — a plain scan keeps full read
  parallelism (reference `:109-131`).
- Any exception makes the rule a no-op with a warning (reference `:76-80`).
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence

from hyperspace_tpu import telemetry
from hyperspace_tpu.index.log_entry import IndexLogEntry
from hyperspace_tpu.plan.nodes import Filter, LogicalPlan, Project, Scan
from hyperspace_tpu.plan.rules.base import Rule, _version_of_root

logger = logging.getLogger(__name__)


def _entry_size_bytes(entry: IndexLogEntry) -> int:
    """On-disk size of the index data, from the stats the build stamped
    into the log entry (`extra.stats.dataSizeBytes`, written by
    `actions/create.stamp_stats`) — ZERO filesystem calls on this path.
    Entries from builds predating the stamp fall back to one directory
    walk (compatibility only; every data-writing action now stamps)."""
    stats = entry.extra.get("stats") if isinstance(entry.extra, dict) else None
    if isinstance(stats, dict):
        try:
            return int(stats.get("dataSizeBytes", 0))
        except (TypeError, ValueError):
            return 0
    from hyperspace_tpu.utils.file_utils import get_directory_size
    try:
        return int(get_directory_size(entry.content.root))
    except OSError:
        return 0


def _eq_columns(condition) -> List[str]:
    """Columns compared for EQUALITY against a literal anywhere in the
    conjunction (lowercased, sorted) — the predicates bucket pruning
    accelerates. Conservative: non-conjunctive shapes report empty."""
    from hyperspace_tpu.plan import expr as E
    out = set()
    try:
        for conjunct in E.split_conjunctive(condition):
            if isinstance(conjunct, E.EqualTo):
                for side, other in ((conjunct.left, conjunct.right),
                                    (conjunct.right, conjunct.left)):
                    if isinstance(side, E.Column) \
                            and isinstance(other, E.Literal):
                        out.add(side.name.lower())
            elif isinstance(conjunct, E.In) \
                    and isinstance(conjunct.child, E.Column):
                out.add(conjunct.child.name.lower())
    except Exception:
        return []
    return sorted(out)


class FilterIndexRule(Rule):
    def apply(self, plan: LogicalPlan) -> LogicalPlan:
        self._sig_cache = {}
        try:
            # TOP-DOWN, mirroring the reference's `transform` (pre-order,
            # `FilterIndexRule.scala:42-56`): a Project(Filter(Scan)) must
            # match BEFORE its inner bare Filter(Scan) — coverage judged
            # on the projected columns admits narrower (cheaper) indexes
            # than the bare match's full-schema requirement.
            return plan.transform_down(self._rewrite)
        except Exception as exc:
            logger.warning("FilterIndexRule failed; skipping: %s", exc)
            return plan

    def _rewrite(self, node: LogicalPlan) -> LogicalPlan:
        # Project(Filter(Scan)) or Filter(Scan)
        if isinstance(node, Project) and isinstance(node.child, Filter) \
                and isinstance(node.child.child, Scan):
            project, filt, scan = node, node.child, node.child.child
        elif isinstance(node, Filter) and isinstance(node.child, Scan):
            project, filt, scan = None, node, node.child
        else:
            return node
        if scan.bucket_spec is not None:
            return node  # already an index scan

        filter_columns = sorted(filt.condition.references())
        # Coverage is judged on the SOURCE columns a projection reads —
        # computed entries (Alias expressions) contribute their references.
        project_columns = (sorted(project.references())
                           if project is not None else scan.schema.names)

        index = self._find_covering_index(filt, scan, project_columns,
                                          filter_columns)
        if index is not None:
            source: LogicalPlan = self.index_scan(index, bucketed=True)
            logger.info("FilterIndexRule: applying index %s", index.name)
            telemetry.event(
                "rule", "FilterIndexRule", action="applied",
                indexes=[{"name": index.name, "root": index.content.root,
                          "num_buckets": index.num_buckets,
                          "side": "filter"}])
        else:
            source = self._hybrid_scan_source(filt, scan, project_columns,
                                              filter_columns)
            if source is None:
                # No covering index applies — consult DATA-SKIPPING
                # sketches: drop source files whose zones/blooms refute
                # the predicate (or serve from a Z-order clustered
                # copy). Bit-identical by construction: only files that
                # cannot contain a matching row are dropped.
                source = self._skipping_source(filt, scan)
            if source is None:
                # The whyNot record carries everything an advisor needs
                # to synthesize a candidate for THIS miss: the relation
                # (scan roots), the predicate columns, which of them are
                # point (equality) comparisons — bucket pruning only
                # helps those — and the full column set a covering index
                # would have to carry.
                telemetry.event(
                    "rule", "FilterIndexRule", action="skipped",
                    reason="no ACTIVE covering index matches the plan "
                           "signature (filter must reference the first "
                           "indexed column; all columns must be covered) "
                           "and no data-skipping sketch prunes the scan",
                    filter_columns=list(filter_columns),
                    eq_columns=_eq_columns(filt.condition),
                    project_columns=sorted(
                        {c.lower() for c in project_columns}),
                    roots=list(scan.root_paths))
                return node

        rewritten: LogicalPlan = Filter(filt.condition, source)
        if project is not None:
            rewritten = Project(project.columns, rewritten)
        elif source.schema.names != scan.schema.names:
            # Bare Filter(Scan): restore the base relation's column order —
            # enabling indexes must not change result shape. A source that
            # has it already (the skipping rewrite's pruned scan) takes no
            # Project: one over every column would make the scan read all
            # of them, whatever the operators above it read.
            rewritten = Project(scan.schema.names, rewritten)
        return rewritten

    def _skipping_enabled(self) -> bool:
        conf = getattr(self.session, "conf", None)
        return conf is None or conf.skipping_enabled

    def _emit_skipping(self, entry, scan_roots, files_total: int,
                       pruned, bytes_pruned: int, served: str) -> None:
        """Pruning detail into the index-usage telemetry records (the
        event's `root` is the SOURCE root for in-place pruning so the
        usage join finds the scan that read the survivors) + the
        process/per-query `skipping.{files_pruned,bytes_pruned}`
        counters."""
        reg = telemetry.get_registry()
        reg.counter("skipping.files_pruned").inc(len(pruned))
        reg.counter("skipping.bytes_pruned").inc(bytes_pruned)
        telemetry.add_count("skipping.files_pruned", len(pruned))
        telemetry.add_count("skipping.bytes_pruned", bytes_pruned)
        # The MEASURED prune fraction, per served query: the advisor's
        # what-if scorer assumes the blind constant
        # `advisor.skipping.prune.fraction` — this histogram (and the
        # per-index gauge) is what `Hyperspace.advisor()` reports
        # drift against.
        frac = (len(pruned) / files_total) if files_total else 0.0
        reg.histogram("skipping.measured_prune_fraction").observe(frac)
        reg.gauge(
            f"skipping.{entry.name}.measured_prune_fraction").set(
            round(frac, 6))
        telemetry.event(
            "rule", "FilterIndexRule", action="applied",
            indexes=[{"name": entry.name, "root": scan_roots[0],
                      "index_root": entry.content.root,
                      "num_buckets": 0, "side": "skipping",
                      "served": served,
                      # NOT "files_total": index_usage() overlays the
                      # scan's own files_total (the post-prune listing)
                      # over event keys of the same name.
                      "files_considered": files_total,
                      "files_pruned": len(pruned),
                      "bytes_pruned": bytes_pruned}])

    def _consult(self, entry, condition, files, served: str):
        """(survivors, pruned, bytes_pruned) of `files` under `entry`'s
        sketches, or None where its blob is unusable or sketches none of
        `files`: the ONE sketch consult of both rewrites, under the span
        `hs.plan.skipping` (index, files, kept, bytes_pruned, and served:
        how the query is served where files were pruned, "" where none
        were). `files` None means the files the blob sketches (a Z-order
        entry's clustered copy). Sketch-blob problems degrade to no
        pruning, never an error."""
        from hyperspace_tpu.index.sketch import load_sketches
        from hyperspace_tpu.plan.rules.skipping import prune_files
        with telemetry.span("hs.plan.skipping", "plan",
                            index=entry.name) as sp:
            out = None
            try:
                sketches = load_sketches(entry.content.root)
            except Exception as exc:
                logger.warning("Skipping index %s blob unusable (%s); "
                               "not pruning", entry.name, exc)
                sketches = None
            if sketches is not None:
                if files is None:
                    files = sorted(sketches.files)
                if any(f in sketches.files for f in files):
                    out = prune_files(condition, files, sketches)
            n = len(files) if files is not None else 0
            sp.set(files=n, kept=len(out[0]) if out else n,
                   bytes_pruned=out[2] if out else 0,
                   served=served if out and out[1] else "")
        return out

    def _prune_file_list(self, condition, files):
        """Prune `files` (source-data paths) with the best ACTIVE
        non-Z-order skipping sketch available. Returns
        (survivors, pruned, bytes_pruned, entry) — unchanged input and
        entry=None when nothing applies."""
        if not files or not self._skipping_enabled():
            return list(files), [], 0, None
        for entry in self._skipping_indexes():
            if entry.derived_dataset.zorder_by:
                continue  # z-order entries serve whole scans, not lists
            found = self._consult(entry, condition, files,
                                  served="hybrid-remainder")
            if found is not None and found[1]:
                return (*found, entry)
        return list(files), [], 0, None

    def _skipping_source(self, filt: Filter, scan: Scan):
        """Data-skipping rewrite when no covering index applies:

        - a Z-ORDER entry whose signature matches the scan serves the
          query from its clustered copy, restricted to the copy files
          the predicate cannot refute (tight zones by construction);
        - otherwise the scan is restricted IN PLACE to the source files
          the sketches cannot refute (explicit file list — plan-time
          pinned by definition).

        Returns a replacement source plan, or None when nothing prunes
        (an unpruned rewrite would be pure churn)."""
        if not self._skipping_enabled():
            return None
        from hyperspace_tpu.plan.schema import Schema

        files = scan.files()
        if not files:
            return None
        for entry in self._skipping_indexes():
            dd = entry.derived_dataset
            if dd.zorder_by:
                # Serving from the copy requires the copy to represent
                # exactly the CURRENT source: signature match, plus a
                # schema covering the scan's.
                if not self.signature_matches(entry, scan):
                    continue
                try:
                    copy_schema = Schema.from_json(entry.schema_json)
                except Exception:
                    continue
                scan_names = {f.name.lower() for f in scan.schema.fields}
                if not scan_names <= {f.name.lower()
                                      for f in copy_schema.fields}:
                    continue
                found = self._consult(entry, filt.condition, None,
                                      served="zorder-copy")
                if found is None or not found[1]:
                    continue  # no win over the source scan
                survivors, pruned, bytes_pruned = found
                n_copy = len(survivors) + len(pruned)
                replacement = Scan(
                    [entry.content.root], scan.schema,
                    files=survivors, index_name=entry.name,
                    pinned_version=_version_of_root(entry.content.root))
                logger.info(
                    "FilterIndexRule: z-order skipping index %s prunes "
                    "%d/%d copy files", entry.name, len(pruned), n_copy)
                self._emit_skipping(entry, [entry.content.root], n_copy,
                                    pruned, bytes_pruned,
                                    served="zorder-copy")
                return replacement
            found = self._consult(entry, filt.condition, files,
                                  served="source")
            if found is None or not found[1]:
                continue
            survivors, pruned, bytes_pruned = found
            logger.info("FilterIndexRule: skipping index %s prunes "
                        "%d/%d source files", entry.name, len(pruned),
                        len(files))
            self._emit_skipping(entry, scan.root_paths, len(files),
                                pruned, bytes_pruned, served="source")
            return Scan(scan.root_paths, scan.schema, files=survivors)
        return None

    def _hybrid_scan_source(self, filt: Filter, scan: Scan,
                            project_columns: Sequence[str],
                            filter_columns: Sequence[str]):
        """Hybrid Scan (extension; reference roadmap): when the index covers
        the columns but the source has CHANGED since build time, serve the
        query from index data anyway — appended files ride along as a
        UNION branch, and (for lineage-enabled indexes) deleted files'
        rows are excluded by a `_hs_file_id NOT IN (...)` filter pushed
        onto the index scan. No refresh required. Gated on
        `spark.hyperspace.index.hybridscan.enabled`."""
        from hyperspace_tpu import constants
        from hyperspace_tpu.plan.nodes import Union

        if self.session.conf.get(constants.HYBRID_SCAN_ENABLED,
                                 "false").lower() != "true":
            return None
        needed = ({c for c in filter_columns}
                  | {c for c in project_columns})
        for entry in self._covering_indexes():
            if not self._covers(entry, project_columns, filter_columns):
                continue
            usable = self.hybrid_delta(entry, scan)
            if usable is None:
                continue
            appended, deleted_ids = usable
            index_source = self.index_scan(entry, bucketed=True)
            if deleted_ids:
                index_source = Filter(self.lineage_exclusion(deleted_ids),
                                      index_source)
            needed_cols = [f.name for f in index_source.schema.fields
                           if f.name.lower() in {c.lower() for c in needed}]
            logger.info("FilterIndexRule: hybrid scan with index %s "
                        "(+%d appended files, -%d deleted files)",
                        entry.name, len(appended), len(deleted_ids))
            telemetry.event(
                "rule", "FilterIndexRule", action="applied",
                indexes=[{"name": entry.name, "root": entry.content.root,
                          "num_buckets": entry.num_buckets,
                          "side": "filter", "hybrid": True,
                          "appended_files": len(appended),
                          "deleted_files": len(deleted_ids)}])
            if not appended:
                return Project(needed_cols, index_source)
            # The covering index's SOURCE-FILE REMAINDER: data-skipping
            # sketches can still thin the appended-files branch of the
            # hybrid union (files indexed by a refreshed skipping index
            # whose zones/blooms refute the predicate).
            appended, rem_pruned, rem_bytes, sk_entry = \
                self._prune_file_list(filt.condition, appended)
            if sk_entry is not None:
                self._emit_skipping(sk_entry, scan.root_paths,
                                    len(appended) + len(rem_pruned),
                                    rem_pruned, rem_bytes,
                                    served="hybrid-remainder")
            if not appended:
                return Project(needed_cols, index_source)
            appended_scan = Scan(scan.root_paths, scan.schema,
                                 files=appended, appended=True)
            return Union([Project(needed_cols, index_source),
                          Project(needed_cols, appended_scan)])
        return None

    def _find_covering_index(self, filt: Filter, scan: Scan,
                             project_columns: Sequence[str],
                             filter_columns: Sequence[str]) -> Optional[IndexLogEntry]:
        """Reference `FilterIndexRule.scala:146-228`."""
        candidates: List[IndexLogEntry] = []
        for entry in self._covering_indexes():
            if not self._covers(entry, project_columns, filter_columns):
                continue
            if not self.signature_matches(entry, filt):
                continue
            candidates.append(entry)
        if not candidates:
            return None
        if len(candidates) == 1:
            return candidates[0]
        return self._rank(candidates)

    @staticmethod
    def _rank(candidates: List[IndexLogEntry]) -> IndexLogEntry:
        """Cost-based selection — exceeds the reference's first-wins
        placeholder (`FilterIndexRule.scala:222-228`): among covering
        candidates, pick the one that reads the FEWEST BYTES (on-disk
        size of the index data root, the exact cost of the swapped-in
        scan); when any candidate's storage is unstatable, fall back to
        total column count (fewer columns ~ narrower rows ~ fewer
        bytes). Ties break toward MORE buckets (finer point-filter
        bucket pruning: each point value reads 1/num_buckets of the
        files), then name for determinism."""
        sizes = []
        for entry in candidates:
            size = _entry_size_bytes(entry)
            # 0 bytes means missing/unreadable as much as legitimately
            # empty. An index whose data root vanished must never WIN the
            # ranking by looking free: candidates with real bytes beat
            # 0-byte ones outright; with no sized candidate at all, fall
            # back to the column-count proxy. NOTE: stamped stats are
            # trusted as-is (metadata-only ranking, zero FS calls) — a
            # data root deleted out-of-band AFTER a stamped build is not
            # re-detected here and fails loudly at scan time instead;
            # the walk fallback preserves the 0-byte guard only for
            # legacy stampless entries.
            sizes.append(size if size > 0 else None)
        sized = [(s, e) for s, e in zip(sizes, candidates) if s is not None]
        if sized:
            return min(sized,
                       key=lambda p: (p[0], -p[1].num_buckets, p[1].name))[1]
        counts = [len(e.indexed_columns) + len(e.included_columns)
                  for e in candidates]
        return min(zip(counts, candidates),
                   key=lambda p: (p[0], -p[1].num_buckets, p[1].name))[1]

    @staticmethod
    def _covers(entry: IndexLogEntry, project_columns: Sequence[str],
                filter_columns: Sequence[str]) -> bool:
        """Filter columns must include the index's first indexed column and
        all referenced columns must be covered (reference `:203-215`)."""
        first_indexed = entry.indexed_columns[0].lower()
        filter_lower = {c.lower() for c in filter_columns}
        if first_indexed not in filter_lower:
            return False
        covered = {c.lower() for c in
                   (entry.indexed_columns + entry.included_columns)}
        referenced = filter_lower | {c.lower() for c in project_columns}
        return referenced <= covered
