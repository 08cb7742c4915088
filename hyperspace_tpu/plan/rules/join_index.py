"""JoinIndexRule: redirect equi-joins to bucketed covering indexes.

Parity: reference `index/rules/JoinIndexRule.scala:54-595`.
Applicability (reference `:163-166`):
- equi-join condition in AND-only CNF of column equalities (`:179-185`);
- both subplans *linear* (<=1 child per node) — guards against signature
  collisions since the file-based signature ignores plan structure
  (`:194-205, 210-211`);
- join attributes resolve directly to base relations with a strict
  one-to-one left<->right column mapping (`:278-317`).
Index selection (reference `:328-594`):
- per-side candidates by signature match;
- an index is usable iff its indexed columns are SET-equal to that side's
  join columns and it covers every column the side needs;
- left/right indexes are compatible iff their indexed-column ORDER agrees
  under the left<->right mapping;
- best pair chosen by JoinIndexRanker.
Replacement swaps each side's scan for the index scan WITH its bucket spec
so the physical planner elides Exchange+Sort (reference `:124-153`).
Errors degrade to a no-op with a warning (reference `:66-69`).
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

from hyperspace_tpu import telemetry
from hyperspace_tpu.index.log_entry import IndexLogEntry
from hyperspace_tpu.plan import expr as E
from hyperspace_tpu.plan.nodes import Join, LogicalPlan, Scan
from hyperspace_tpu.plan.rules.base import Rule
from hyperspace_tpu.plan.rules.ranker import JoinIndexRanker

logger = logging.getLogger(__name__)


def _skip(reason: str, **detail) -> None:
    """Structured whyNot record (the reference's `PlanAnalyzer.whyNot`
    analog): the rule looked at a join and declined, with the reason."""
    telemetry.event("rule", "JoinIndexRule", action="skipped",
                    reason=reason, **detail)


class JoinIndexRule(Rule):
    def apply(self, plan: LogicalPlan) -> LogicalPlan:
        self._sig_cache = {}
        try:
            return plan.transform_up(self._rewrite)
        except Exception as exc:
            logger.warning("JoinIndexRule failed; skipping: %s", exc)
            return plan

    def _rewrite(self, node: LogicalPlan) -> LogicalPlan:
        # The reference rule matches ANY `Join(l, r, Some(cond))` with a
        # supported equi condition (`JoinIndexRule.scala:55-71`) — outer
        # equi-joins are index-served too.
        if not isinstance(node, Join):
            return node
        join = node
        if join.condition is None:
            return node  # cross join: nothing to bucket on
        mapping = self._column_mapping(join)
        if mapping is None:
            _skip("condition is not an AND-only CNF of one-to-one "
                  "column equalities")
            return node
        if not (join.left.is_linear() and join.right.is_linear()):
            _skip("non-linear join subplan")
            return node
        left_scan = self._base_scan(join.left)
        right_scan = self._base_scan(join.right)
        if left_scan is None or right_scan is None:
            _skip("join side does not resolve to a single base relation")
            return node
        if left_scan.bucket_spec is not None or right_scan.bucket_spec is not None:
            _skip("relation already bucketed (rule already applied)")
            return node  # already rewritten

        pair = self._best_index_pair(join, mapping)
        if pair is None:
            # whyNot with enough detail for the advisor to synthesize a
            # candidate PAIR: per-side relation roots, join keys in
            # mapping order, and the full column set each side's index
            # would have to cover.
            left_cols = sorted(mapping)
            _skip("no usable/compatible index pair",
                  join_columns=left_cols,
                  left_join_columns=left_cols,
                  right_join_columns=[mapping[c] for c in left_cols],
                  left_roots=list(left_scan.root_paths),
                  right_roots=list(right_scan.root_paths),
                  left_referenced=self._referenced_columns(join.left),
                  right_referenced=self._referenced_columns(join.right))
            return node
        ((left_index, left_appended, left_deleted),
         (right_index, right_appended, right_deleted)) = pair
        logger.info("JoinIndexRule: applying indexes %s%s%s, %s%s%s",
                    left_index.name,
                    f" (+{len(left_appended)} appended)" if left_appended
                    else "",
                    f" (-{len(left_deleted)} deleted)" if left_deleted
                    else "",
                    right_index.name,
                    f" (+{len(right_appended)} appended)" if right_appended
                    else "",
                    f" (-{len(right_deleted)} deleted)" if right_deleted
                    else "")
        telemetry.event(
            "rule", "JoinIndexRule", action="applied",
            indexes=[{"name": e.name, "root": e.content.root,
                      "num_buckets": e.num_buckets, "side": side,
                      "appended_files": len(app or ()),
                      "deleted_files": len(dele or ())}
                     for e, app, dele, side in
                     ((left_index, left_appended, left_deleted, "left"),
                      (right_index, right_appended, right_deleted,
                       "right"))])

        def swap(side_plan: LogicalPlan, entry: IndexLogEntry,
                 appended, deleted_ids) -> LogicalPlan:
            from hyperspace_tpu.plan.nodes import Filter, Project, Union
            replacement: LogicalPlan = self.index_scan(entry, bucketed=True)
            if deleted_ids:
                # Deleted source files (lineage-enabled index): exclude
                # their rows right above the bucketed scan — filters
                # preserve bucketing, so the SMJ path is kept.
                replacement = Filter(self.lineage_exclusion(deleted_ids),
                                     replacement)
            if appended or deleted_ids or entry.has_lineage:
                # Hybrid scan (join path): index data (UNION the appended
                # source files, re-bucketed at execution time through the
                # planner's ExchangeExec so the bucketed SMJ still applies
                # — reference roadmap, Hybrid Scan item). The Project also
                # drops the internal lineage column from the join input —
                # needed even on an exact match of a lineage-enabled index,
                # or `_hs_file_id` would leak into the join output schema.
                scan = self._base_scan(side_plan)
                needed = self._referenced_columns(side_plan)
                # Filter preserves its child's schema, so `replacement`
                # still exposes the index scan's fields here.
                names = [f.name for f in replacement.schema.fields
                         if f.name.lower() in set(needed)]
                branches = [Project(names, replacement)]
                if appended:
                    branches.append(Project(names, Scan(
                        scan.root_paths, scan.schema, files=appended,
                        appended=True)))
                replacement = (Union(branches) if len(branches) > 1
                               else branches[0])

            def f(n: LogicalPlan) -> LogicalPlan:
                return replacement if isinstance(n, Scan) else n

            return side_plan.transform_up(f)

        return Join(swap(join.left, left_index, left_appended, left_deleted),
                    swap(join.right, right_index, right_appended,
                         right_deleted),
                    join.condition, join.join_type)

    # -- applicability ----------------------------------------------------

    @staticmethod
    def _base_scan(plan: LogicalPlan) -> Optional[Scan]:
        leaves = plan.collect_leaves()
        if len(leaves) == 1 and isinstance(leaves[0], Scan):
            return leaves[0]
        return None

    def _column_mapping(self, join: Join) -> Optional[Dict[str, str]]:
        """Strict one-to-one left->right join column mapping from an
        AND-only CNF of column equalities (reference `:179-185, 278-317`)."""
        left_schema, right_schema = join.left.schema, join.right.schema
        mapping: Dict[str, str] = {}
        reverse: Dict[str, str] = {}
        for conjunct in E.split_conjunctive(join.condition):
            if not isinstance(conjunct, E.EqualTo):
                return None
            a, b = conjunct.left, conjunct.right
            if not isinstance(a, E.Column) or not isinstance(b, E.Column):
                return None
            if left_schema.contains(a.name) and right_schema.contains(b.name):
                l, r = a.name.lower(), b.name.lower()
            elif left_schema.contains(b.name) and right_schema.contains(a.name):
                l, r = b.name.lower(), a.name.lower()
            else:
                return None
            if mapping.get(l, r) != r or reverse.get(r, l) != l:
                return None  # one-to-many mapping
            mapping[l] = r
            reverse[r] = l
        return mapping or None

    # -- index selection --------------------------------------------------

    @staticmethod
    def _referenced_columns(plan: LogicalPlan) -> List[str]:
        """BASE-relation columns the side needs (reference `:446-457`):
        the output resolved top-down through projections — computed
        entries contribute their references, not their alias names — plus
        every filter/sort/aggregate reference along the chain."""
        from hyperspace_tpu.plan.nodes import (Aggregate, Filter as FilterNode,
                                               Limit, Project as ProjectNode,
                                               Scan as ScanNode, Sort,
                                               sort_direction)

        def walk(node: LogicalPlan, required: set) -> set:
            if isinstance(node, ScanNode):
                return {r.lower() for r in required}
            if isinstance(node, FilterNode):
                return walk(node.child,
                            set(required) | node.condition.references())
            if isinstance(node, ProjectNode):
                return walk(node.child, node.references())
            if isinstance(node, Aggregate):
                req = set(node.group_columns)
                for a in node.aggregates:
                    req |= a.references()
                return walk(node.child, req)
            if isinstance(node, Sort):
                return walk(node.child, set(required)
                            | {sort_direction(c)[0] for c in node.columns})
            if isinstance(node, Limit):
                return walk(node.child, required)
            out = {r.lower() for r in required}
            for c in node.children:
                out |= walk(c, set(c.schema.names))
            return out

        return sorted(walk(plan, set(plan.schema.names)))

    def _usable_indexes(self, plan: LogicalPlan, join_cols: Sequence[str]):
        """(entry, appended_files|None, deleted_ids) candidates for one
        join side: signature-matching ACTIVE indexes whose indexed columns
        are set-equal to the join columns and that cover the side's
        referenced columns (reference `:328-353, 399-409, 515-524`). With
        hybrid scan enabled, an index over a CHANGED source is usable too:
        appended files ride along as a union branch, and (lineage-enabled
        indexes) deleted files' rows are excluded by a lineage filter."""
        from hyperspace_tpu import constants

        hybrid = (self.session.conf.get(constants.HYBRID_SCAN_ENABLED,
                                        "false").lower() == "true")
        referenced = set(self._referenced_columns(plan))
        join_set = {c.lower() for c in join_cols}
        scan = self._base_scan(plan)
        out = []
        for entry in self._covering_indexes():
            indexed = [c.lower() for c in entry.indexed_columns]
            if set(indexed) != join_set:
                continue
            covered = {c.lower() for c in
                       (entry.indexed_columns + entry.included_columns)}
            if not referenced <= covered:
                continue
            if self.signature_matches(entry, plan):
                out.append((entry, None, []))
                continue
            if not hybrid or scan is None:
                continue
            usable = self.hybrid_delta(entry, scan)
            if usable is not None:
                out.append((entry, usable[0] or None, usable[1]))
        return out

    def _best_index_pair(self, join: Join, mapping: Dict[str, str]):
        left_join_cols = list(mapping.keys())
        right_join_cols = [mapping[c] for c in left_join_cols]
        left_candidates = self._usable_indexes(join.left, left_join_cols)
        right_candidates = self._usable_indexes(join.right, right_join_cols)
        if not left_candidates or not right_candidates:
            return None
        compatible = []
        for lc in left_candidates:
            for rc in right_candidates:
                if self._compatible(lc[0], rc[0], mapping):
                    compatible.append((lc, rc))
        if not compatible:
            return None
        ranked = JoinIndexRanker.rank([(l[0], r[0]) for l, r in compatible])
        best = ranked[0]
        for pair in compatible:
            if pair[0][0] is best[0] and pair[1][0] is best[1]:
                return pair
        return compatible[0]

    @staticmethod
    def _compatible(left_index: IndexLogEntry, right_index: IndexLogEntry,
                    mapping: Dict[str, str]) -> bool:
        """Indexed-column ORDER must agree under the left<->right mapping —
        bucket b of each side must hold the same key hashes (reference
        `:547-594`)."""
        left_order = [c.lower() for c in left_index.indexed_columns]
        right_order = [c.lower() for c in right_index.indexed_columns]
        mapped = [mapping.get(c) for c in left_order]
        return mapped == right_order
