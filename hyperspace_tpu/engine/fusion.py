"""Whole-stage fusion: operator chains compiled into FEW XLA executables.

The reference's rewrite exists to remove per-stage data movement
(`index/rules/JoinIndexRule.scala:41-43`); on a TPU behind a dispatch
link the same principle applies to OPERATORS: eager per-operator
execution pays a dispatch round-trip per jnp op (a 26-join TPC-DS q64
chain runs thousands of them) plus an output-sizing host sync per
operator; what either costs on an attached chip is unmeasured. This module fuses maximal chains of
shape-preserving operators — Filter, Project, BroadcastHashJoin — into ONE
jitted executable per chain with MASKED row semantics:

- a Filter contributes its predicate to a running boolean selection mask
  instead of compacting (no sizing sync, no mid-stage gather);
- a Project computes its columns full-length (dead rows compute garbage
  harmlessly — every operator in a region is row-local);
- a BroadcastHashJoin with a unique-keyed build side is ONE gather per
  output column plus a `matched` mask (the direct-address table from
  `ops/broadcast_join.py`, prepared host-side and cached); inner joins
  AND `matched` into the selection, outer joins null the build columns.

One host sync per stage (the selection count, fetched with the stage
output) replaces one-per-operator. Stage leaves (scans, sort-merge
joins, aggregates, unions — anything with data-dependent output shape)
execute eagerly as before and feed the stage as inputs.

Executable reuse: `jax.jit` keys on a canonical stage program
(`_StageProgram`) whose identity covers everything that shapes the trace
— operator structure, expressions (serde dicts), schemas, validity
presence, string-dictionary identity tokens, broadcast table packing —
so re-running the same query hits the in-memory executable cache even
though the physical plan objects are rebuilt per run.

Host-lane stages run the ORIGINAL eager operator graph instead: on
numpy a compaction is free, so eager filters cutting the row count early
beat masked full-length evaluation. The traced masked semantics get CPU
coverage through the device lane on the CPU backend (tests force it via
execution.min.device.rows=0).
"""

from __future__ import annotations

import itertools
import json
import weakref
from collections.abc import MutableMapping as _MutableMapping
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from hyperspace_tpu import telemetry
from hyperspace_tpu.engine.physical import PhysicalNode
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import (ColumnBatch, DeviceColumn,
                                        batch_to_tree, carried,
                                        tree_to_batch)
from hyperspace_tpu.ops.compact import compact_indices
from hyperspace_tpu.plan.schema import Field, Schema


class _FusionIneligible(Exception):
    """Raised at trace/prep time when a region cannot run masked (e.g.
    non-integer broadcast keys); the caller falls back to the original
    eager operator graph — same results, without the fused executable."""


# ---------------------------------------------------------------------------
# Identity tokens: stable per-object ids for arrays whose CONTENT shapes a
# trace (string dictionaries bake searchsorted constants; broadcast tables
# bake their packing). Object identity is enough: warm runs re-serve the
# same cached arrays, and a freed array can never reclaim its token.
# ---------------------------------------------------------------------------

_token_counter = itertools.count()
_tokens: Dict[int, tuple] = {}


def _token_of(obj) -> int:
    if obj is None:
        return -1
    key = id(obj)
    ent = _tokens.get(key)
    if ent is not None and ent[0]() is obj:
        return ent[1]
    tok = next(_token_counter)

    def _drop(_ref, k=key, t=tok):
        # Entry self-removes when its array dies — but only if the slot
        # still belongs to this token (the id may have been reused by a
        # newer array by the time the callback fires).
        cur = _tokens.get(k)
        if cur is not None and cur[1] == t:
            _tokens.pop(k, None)

    try:
        ref = weakref.ref(obj, _drop)
    except TypeError:  # non-weakrefable: pin it (rare)
        ref = (lambda o: (lambda: o))(obj)
    _tokens[key] = (ref, tok)
    return tok


# ---------------------------------------------------------------------------
# Device promotion cache: host (numpy) source columns — dimension tables
# ride the host lane — become device-resident jit arguments ONCE and are
# re-served by token while the host array lives. Without this every
# execution re-transfers dimension payloads over the link.
#
# Both fusion caches hold REAL device memory, so they evict on a BYTE
# budget (conf `spark.hyperspace.fusion.cache.{promote,broadcast}.bytes`
# — the effective values are refreshed from the session conf at each
# fused execution) and report `cache.fusion_{promote,bcast}.*` series
# to the metrics registry.
# ---------------------------------------------------------------------------

_promote_cache: Dict[int, tuple] = {}  # token -> (ref(host src), device)

from hyperspace_tpu import constants as _constants  # noqa: E402

_promote_budget = [_constants.FUSION_PROMOTE_CACHE_BYTES_DEFAULT]
_bcast_budget = [_constants.FUSION_BCAST_CACHE_BYTES_DEFAULT]


def _configure_cache_budgets(conf) -> None:
    """Refresh the effective byte budgets from the session conf (the
    caches are process-wide; sessions sharing a process should agree,
    same caveat as the parquet cache budgets). The transfer engine's
    io.transfer.* knobs refresh on the same cadence — one fused
    execution picks up a session's link tuning."""
    if conf is None:
        return
    _promote_budget[0] = conf.fusion_promote_cache_bytes
    _bcast_budget[0] = conf.fusion_bcast_cache_bytes
    from hyperspace_tpu.io import transfer
    transfer.configure(conf)


def _promote_nbytes(ent) -> int:
    return int(getattr(ent[1], "nbytes", 0))


def _promote_dead(ent) -> bool:
    return ent[0]() is None


def _bcast_nbytes(ent) -> int:
    return int(getattr(ent[0], "nbytes", 0)) if ent is not None else 0


def _evict(cache: dict, name: str, budget_bytes: int, nbytes_of,
           dead=None) -> None:
    """Byte-budget eviction, run on every insert: sweep dead-source
    entries FIRST and unconditionally (a GC'd host source must not pin
    its device buffer until byte pressure — that was a silent HBM
    leak), then drop oldest-inserted entries until held bytes fit the
    budget. Residency lands as `cache.<name>.{bytes_held,entries}`."""
    evicted = 0
    if dead is not None:
        for k in [k for k, v in cache.items() if dead(v)]:
            cache.pop(k, None)
            evicted += 1
    total = sum(nbytes_of(v) for v in cache.values())
    while total > budget_bytes and cache:
        total -= nbytes_of(cache.pop(next(iter(cache))))
        evicted += 1
    telemetry.memory.cache_eviction(name, evicted)
    telemetry.memory.cache_stats(name, total, len(cache))


def _to_device(arr, dtype: Optional[str] = None):
    """Host array -> cached device copy, keyed by the HOST array's
    identity. `dtype` (a column's logical dtype) selects the form that
    crosses the link: float64 payload goes as its carried bits."""
    if arr is None or not isinstance(arr, np.ndarray):
        return arr
    tok = _token_of(arr)
    ent = _promote_cache.get(tok)
    if ent is not None and ent[0]() is arr:
        telemetry.memory.cache_hit("fusion_promote")
        return ent[1]
    telemetry.memory.cache_miss("fusion_promote")
    from hyperspace_tpu.io import transfer
    # Cache MISSES are exactly the executions that pay the link; the
    # engine's transfer record (registry histogram + optional span)
    # makes the promotion cost attributable instead of folded into
    # dispatch_s — and big dimension columns ship chunked/windowed like
    # every other crossing.
    out = transfer.get_engine().put(carried(arr, dtype))
    try:
        ref = weakref.ref(arr)
    except TypeError:
        ref = (lambda o: (lambda: o))(arr)
    _promote_cache[tok] = (ref, out)
    _evict(_promote_cache, "fusion_promote", _promote_budget[0],
           _promote_nbytes, dead=_promote_dead)
    return out


def _promote_batch(batch: ColumnBatch) -> ColumnBatch:
    if not batch.is_host:
        return batch
    columns = {}
    for name, col in batch.columns.items():
        hashes = col.dict_hashes
        if hashes is not None:
            hashes = (_to_device(hashes[0]), _to_device(hashes[1]))
        columns[name] = DeviceColumn(_to_device(col.raw, col.dtype),
                                     col.dtype, _to_device(col.validity),
                                     col.dictionary, hashes)
    return ColumnBatch(batch.schema, columns)


# ---------------------------------------------------------------------------
# Broadcast table prep (host side, cached by build-column identity).
# ---------------------------------------------------------------------------

_bcast_cache: Dict[tuple, object] = {}


def _prepare_broadcast(node, build_batch: ColumnBatch):
    """(table ndarray, mins, ranges) for this join's build side, or None
    when the direct-address path is ineligible (the caller then falls
    back to the eager operator graph, whose own runtime fallback covers
    duplicates/strings/wide ranges). Cached by build key-column identity
    so warm runs skip the host scatter AND the device transfer."""
    membership = node.how in ("left_semi", "left_anti")
    keys = (node.right_keys if node.build_side == "right"
            else node.left_keys)
    if build_batch.num_rows == 0:
        return None  # eager path has exact empty-side shortcuts
    try:
        ident = []
        for k in keys:
            col = build_batch.column(k)
            ident.append((_token_of(col.raw), _token_of(col.validity)))
    except HyperspaceException:
        return None
    ck = (membership, tuple(k.lower() for k in keys), tuple(ident))
    if ck in _bcast_cache:
        telemetry.memory.cache_hit("fusion_bcast")
        return _bcast_cache[ck]
    telemetry.memory.cache_miss("fusion_bcast")
    from hyperspace_tpu.ops.broadcast_join import (build_broadcast_table,
                                                   build_membership_table)
    builder = build_membership_table if membership else build_broadcast_table
    out = builder(build_batch, keys)
    if out is not None:
        table, mins, ranges = out
        out = (table, tuple(int(m) for m in mins),
               tuple(int(r) for r in ranges))
    _bcast_cache[ck] = out
    _evict(_bcast_cache, "fusion_bcast", _bcast_budget[0], _bcast_nbytes)
    return out


_INT_KEY_DTYPES = ("int8", "int16", "int32", "int64", "date32",
                   "timestamp", "bool")


# ---------------------------------------------------------------------------
# Region nodes
# ---------------------------------------------------------------------------


class _SourceExec(PhysicalNode):
    """Region leaf: a materialized input. During a fused execution the
    batch slot is pre-loaded; outside one it delegates to the wrapped
    node (the eager-fallback and bucketed-protocol paths)."""

    name = "StageInput"

    def __init__(self, node, index: int):
        self.node = node
        self.index = index
        self._batch: Optional[ColumnBatch] = None

    @property
    def children(self):
        return [self.node]

    def simple_string(self):
        return "StageInput"

    def execute(self, bucket=None):
        if bucket is None and self._batch is not None:
            return self._batch
        return self.node.execute(bucket)

    def execute_bucketed(self, num_buckets: int):
        return self.node.execute_bucketed(num_buckets)


def _region_nodes(root) -> List:
    """All fused operator nodes of a region (stops at _SourceExec)."""
    from hyperspace_tpu.engine.physical import (BroadcastHashJoinExec,
                                                FilterExec, ProjectExec)
    out = []

    def walk(n):
        if isinstance(n, _SourceExec):
            return
        out.append(n)
        if isinstance(n, (FilterExec, ProjectExec)):
            walk(n.child)
        elif isinstance(n, BroadcastHashJoinExec):
            walk(n.left if n.build_side == "right" else n.right)
    walk(root)
    return out


class _StageProgram:
    """Hashable static argument for the jitted stage interpreter. Two
    equal programs MUST trace identically: the key covers the region
    structure and every host-side constant the trace bakes in."""

    def __init__(self, key: str, region, source_meta, tables_meta):
        self.key = key
        self.region = region
        self.source_meta = source_meta  # [(schema, aux, num_rows)] by index
        self.tables_meta = tables_meta  # {slot: (mins, ranges)}

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return (isinstance(other, _StageProgram)
                and other.key == self.key)

    def __repr__(self):
        # Stable across instances of the SAME program (the compile
        # tracker's retrace-cause diff keys on argument reprs; the
        # default object repr would make every run look like a delta).
        return f"_StageProgram({hash(self.key) & 0xFFFFFFFF:08x})"


# out-batch metadata captured at trace time, re-served on executable
# cache hits (the jit call only returns arrays).
_OUT_META: Dict[str, tuple] = {}


class _RegistryStats(_MutableMapping):
    """PROCESS-WIDE diagnostics aggregate — stage executions, trace
    misses, seconds dispatching / blocked on the output-sizing sync —
    now BACKED BY the metrics registry (counters `fusion.<key>`): one
    storage, two views. The dict-shaped surface keeps the existing
    consumer contract (`scripts/profile_tpcds.py` resets by key and
    reads after runs); the registry exposes the same numbers to
    `session.metrics_registry()` and the Prometheus dump. Per-QUERY
    attribution of the same quantities lands on the active
    `telemetry.QueryMetrics` (counters `fusion.*`) so concurrent
    queries don't smear each other."""

    _KEYS = ("stage_execs", "trace_misses", "sync_s", "dispatch_s")
    _INT_KEYS = ("stage_execs", "trace_misses")

    def _counter(self, key: str):
        if key not in self._KEYS:
            raise KeyError(key)
        return telemetry.get_registry().counter(f"fusion.{key}")

    def __getitem__(self, key):
        value = self._counter(key).value
        return int(value) if key in self._INT_KEYS else value

    def __setitem__(self, key, value):
        self._counter(key).set(float(value))

    def __delitem__(self, key):
        raise TypeError("fusion.STATS keys are fixed")

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


STATS = _RegistryStats()


def _stat(key: str, value) -> None:
    """THE single mutation path for fusion stage statistics: the
    process registry (which `STATS` views) AND the per-query recorder,
    in one place — so the two scopes cannot drift."""
    telemetry.get_registry().counter(f"fusion.{key}").inc(value)
    if isinstance(value, float):
        telemetry.add_seconds(f"fusion.{key}", value)
    else:
        telemetry.add_count(f"fusion.{key}", value)
# program keys whose trace proved ineligible — skip straight to eager.
_INELIGIBLE_KEYS: set = set()


def _gather_build(src_data, src_validity, hit, matched, xp):
    """THE build-side gather semantics (data, validity) — shared by lazy
    materialization and the post-compaction finalize, so the sites can
    never diverge."""
    g = xp.clip(hit, 0, None)
    data = xp.take(src_data, g, axis=0)
    validity = (matched if src_validity is None
                else xp.take(src_validity, g, axis=0) & matched)
    return data, validity


class _LazyGatherColumn:
    """A broadcast join's build-side column inside a traced stage,
    DEFERRED: most dim payload is only CARRIED to the stage output, where
    the selection then discards the vast majority of rows — gathering it
    full-length through every join would be the stage's dominant data
    movement. The gather materializes lazily if a mid-stage expression
    actually reads the column (trace-time property access; the result is
    cached and re-used); columns still lazy at stage end ship only their
    join's (hit, matched) pair through the executable, and the runtime
    gathers them AFTER compaction — at selection size, not row count.

    Duck-types DeviceColumn (`io/columnar.py`); valid only within one
    traced stage execution."""

    __slots__ = ("_src", "hit", "matched", "dtype", "dictionary",
                 "pair_slot", "source_index", "src_name", "_mat")

    data = DeviceColumn.data
    carry = DeviceColumn.carry
    with_raw = DeviceColumn.with_raw

    def __init__(self, src, hit, matched, pair_slot: int,
                 source_index: int, src_name: str):
        self._src = src
        self.hit = hit
        self.matched = matched
        self.dtype = src.dtype
        self.dictionary = src.dictionary
        self.pair_slot = pair_slot
        self.source_index = source_index
        self.src_name = src_name
        self._mat = None

    @property
    def materialized(self) -> bool:
        return self._mat is not None

    def _materialize(self):
        if self._mat is None:
            import jax.numpy as jnp
            self._mat = _gather_build(self._src.raw, self._src.validity,
                                      self.hit, self.matched, jnp)
        return self._mat

    @property
    def raw(self):
        return self._materialize()[0]

    @property
    def carries_bits(self) -> bool:
        return self._src.carries_bits  # a gather keeps the form

    @property
    def validity(self):
        return self._materialize()[1]

    @property
    def dict_hashes(self):
        return self._src.dict_hashes

    @property
    def is_string(self) -> bool:
        return self.dictionary is not None

    @property
    def is_host(self) -> bool:
        return False  # exists only inside the jitted device trace

    def __len__(self) -> int:
        return int(self.hit.shape[0])


# ---------------------------------------------------------------------------
# The masked interpreter (runs INSIDE the jitted device trace; the host
# lane routes to the eager operator graph instead).
# ---------------------------------------------------------------------------


def _interpret(node, env: Dict[int, ColumnBatch], tables: Dict[int, object]):
    from hyperspace_tpu.engine.compiler import compile_predicate
    from hyperspace_tpu.engine.physical import (BroadcastHashJoinExec,
                                                FilterExec, ProjectExec)

    if isinstance(node, _SourceExec):
        return env[node.index], None
    if isinstance(node, FilterExec):
        batch, sel = _interpret(node.child, env, tables)
        # A deferred build column the predicate reads is gathered here,
        # in the stage's own trace: one gathered inside the predicate's
        # nested call would leave its value behind in that call.
        for name in sorted(node.condition.references()):
            col = batch.column(name)
            if isinstance(col, _LazyGatherColumn):
                col._materialize()
        mask = telemetry.device_scoped("hs.predicate")(compile_predicate)(
            node.condition, batch)
        return batch, (mask if sel is None else sel & mask)
    if isinstance(node, ProjectExec):
        batch, sel = _interpret(node.child, env, tables)
        return node._project(batch), sel
    if isinstance(node, BroadcastHashJoinExec):
        return _interpret_bhj(node, env, tables)
    raise HyperspaceException(f"Unfusible node in region: {node!r}")


def _interpret_bhj(node, env, tables):
    from hyperspace_tpu.ops.broadcast_join import _probe_lookup

    probe_is_left = node.build_side == "right"
    probe_node = node.left if probe_is_left else node.right
    build_node = node.right if probe_is_left else node.left
    probe_keys = node.left_keys if probe_is_left else node.right_keys
    probe_batch, sel = _interpret(probe_node, env, tables)
    build_batch = env[build_node.index]
    table, mins, ranges = tables[node._table_slot]
    for k in probe_keys:
        col = probe_batch.column(k)
        if col.is_string or col.dtype not in _INT_KEY_DTYPES:
            raise _FusionIneligible(f"non-integer probe key {k}")
    looked = _probe_lookup(probe_batch, probe_keys, table, list(mins),
                           list(ranges))
    if looked is None:
        raise _FusionIneligible("probe lookup declined")
    hit, matched = looked
    if isinstance(hit, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp

    if node.how in ("left_semi", "left_anti"):
        want = ~matched if node.how == "left_anti" else matched
        return probe_batch, (want if sel is None else sel & want)

    if node.how == "inner":
        sel = matched if sel is None else sel & matched
    # THE shared output-naming contract (`join_output_plan`) keeps the
    # fused lane and the eager assembly from ever diverging.
    from hyperspace_tpu.ops.bucketed_join import join_output_plan
    left_batch = probe_batch if probe_is_left else build_batch
    right_batch = build_batch if probe_is_left else probe_batch
    plan = join_output_plan(left_batch.schema, right_batch.schema,
                            node.out_columns)

    build_side_tag = "r" if probe_is_left else "l"
    fields, out_columns = [], {}
    for out, side, src, dtype in plan:
        if side == build_side_tag:
            col = build_batch.column(src)
            # Deferred: gathers only if a mid-stage expression reads it;
            # otherwise the stage end gathers at selection size
            # (post-sync) instead of full row count per join.
            out_columns[out] = _LazyGatherColumn(
                col, hit, matched, node._table_slot,
                build_node.index, src)
            fields.append(Field(out, dtype, True))
        else:
            # Probe rows are never unmatched-nulled (outer joins only
            # broadcast their inner side), so probe fields keep their
            # nullability.
            col = probe_batch.column(src)
            out_columns[out] = col
            fields.append(Field(out, dtype,
                                probe_batch.schema.field(src).nullable))
    return ColumnBatch(Schema(fields), out_columns), sel


# ---------------------------------------------------------------------------
# Jitted stage runner (built lazily so importing this module does not pull
# in jax — the package imports jax only at first device use).
# ---------------------------------------------------------------------------

_run_stage_jit = None


def _run_stage(prog: _StageProgram, trees, table_args):
    global _run_stage_jit
    if _run_stage_jit is None:
        # instrumented_jit: each actual trace records a compile span,
        # compile.* counters, and the retrace cause on the query.
        @partial(telemetry.instrumented_jit, "fusion.run_stage",
                 scope="hs.stage", static_argnames=("prog",))
        def _run(prog: _StageProgram, trees, table_args):
            import jax.numpy as jnp

            env = {}
            for i, (schema, aux, _rows) in enumerate(prog.source_meta):
                env[i] = tree_to_batch(trees[i], schema, aux)
            tables = {slot: (table_args[slot], mins, ranges)
                      for slot, (mins, ranges) in prog.tables_meta.items()}
            out_batch, sel = _interpret(prog.region, env, tables)
            # Columns still lazy at stage end ship only their join's
            # (hit, matched) pair; the runtime gathers them at selection
            # size after the compaction sync.
            keep_fields, keep_cols = [], {}
            lazy_specs, lazy_pairs = [], {}
            for f in out_batch.schema.fields:
                col = out_batch.columns[f.name]
                if (isinstance(col, _LazyGatherColumn)
                        and not col.materialized):
                    lazy_pairs[col.pair_slot] = (col.hit, col.matched)
                    lazy_specs.append((f.name, col.pair_slot,
                                       col.source_index, col.src_name,
                                       f.dtype))
                else:
                    keep_fields.append(f)
                    keep_cols[f.name] = col
            reduced = ColumnBatch(Schema(keep_fields), keep_cols)
            out_tree, out_aux = batch_to_tree(reduced, computes_on=())
            _OUT_META[prog.key] = (out_batch.schema, reduced.schema,
                                   out_aux, tuple(lazy_specs))
            if sel is None:
                return out_tree, lazy_pairs, None, None
            return (out_tree, lazy_pairs, sel,
                    jnp.sum(sel.astype(jnp.int64)))

        _run_stage_jit = _run
    return _run_stage_jit(prog, trees, table_args)


_finalize_lazy_jit = None


def _finalize_lazy(idx, lazy_pairs, srcs, spec):
    """ONE jitted gather for every deferred build column of a stage:
    composes hit∘idx per slot and applies `_gather_build`. `spec` is the
    static structure ((slot, has_src_validity), ...); `srcs` pairs each
    spec entry with (src_data, src_validity|None). `idx` None = no
    compaction (full-length gathers)."""
    global _finalize_lazy_jit
    if _finalize_lazy_jit is None:
        @partial(telemetry.instrumented_jit, "fusion.finalize_lazy",
                 scope="hs.stage", static_argnames=("spec", "has_idx"))
        def run(idx, lazy_pairs, srcs, spec, has_idx):
            import jax.numpy as jnp

            composed = {}
            for slot, _ in spec:
                if slot not in composed:
                    hit, matched = lazy_pairs[slot]
                    if has_idx:
                        hit = jnp.take(hit, idx)
                        matched = jnp.take(matched, idx)
                    composed[slot] = (hit, matched)
            out = []
            for (slot, _has_validity), (sd, sv) in zip(spec, srcs):
                hit, matched = composed[slot]
                out.append(_gather_build(sd, sv, hit, matched, jnp))
            return tuple(out)

        _finalize_lazy_jit = run
    import jax.numpy as jnp
    return _finalize_lazy_jit(
        idx if idx is not None else jnp.zeros(0, dtype=jnp.int32),
        lazy_pairs, srcs, spec, idx is not None)


# ---------------------------------------------------------------------------
# FusedStageExec
# ---------------------------------------------------------------------------


class FusedStageExec(PhysicalNode):
    """Physical node executing a fused region. Sources run eagerly first;
    the region then runs as ONE jitted executable with a single
    output-sizing sync (device lane) or as the eager operator graph
    (host lane — early compaction wins on numpy)."""

    name = "FusedStage"
    # set by `execute_masked` for the length of one execution
    _takes_mask = None

    def __init__(self, root, sources: Sequence[_SourceExec], conf=None):
        self.root = root
        self.sources = list(sources)
        self.conf = conf
        from hyperspace_tpu.engine.physical import BroadcastHashJoinExec
        self._bhj_nodes = [n for n in _region_nodes(root)
                           if isinstance(n, BroadcastHashJoinExec)]
        for slot, n in enumerate(self._bhj_nodes):
            n._table_slot = slot

    @property
    def children(self):
        return [self.root]

    def simple_string(self):
        return f"FusedStage ({len(_region_nodes(self.root))} ops)"

    def execute_bucketed(self, num_buckets: int):
        """Bucketed-protocol passthrough (regions never contain joins on
        this path — only Filter/Project chains support it)."""
        return self.root.execute_bucketed(num_buckets)

    def execute_masked(self, takes_mask) -> Tuple[ColumnBatch, object]:
        """`execute()` for a consumer that reads rows through a mask:
        where the stage runs masked on the device and `takes_mask(batch)`
        says yes to its full-length output, (that output, its selection
        mask or None), with no sync, compaction or gather; else (the
        compacted output, None)."""
        self._takes_mask = takes_mask
        try:
            out = self.execute()
        finally:
            self._takes_mask = None
        return out if isinstance(out, tuple) else (out, None)

    def execute(self, bucket: Optional[int] = None) -> ColumnBatch:
        if bucket is not None:
            return self.root.execute(bucket)
        # Stage-boundary seams: the fault point the chaos harness
        # drives (`fusion.stage`) and the cooperative-cancellation
        # checkpoint — both BEFORE source execution, so an injected
        # fault or an expired deadline costs nothing downstream.
        from hyperspace_tpu.utils import faults
        faults.fire("fusion.stage")
        telemetry.check_deadline("stage")
        _configure_cache_budgets(self.conf)
        for s in self.sources:
            s._batch = s.node.execute()
        try:
            out = self._execute_masked()
            if out is not None:
                return out
            # Eager fallback: the original operator graph, sources served
            # from the already-executed batches.
            return self.root.execute()
        finally:
            for s in self.sources:
                s._batch = None

    # -- masked execution -------------------------------------------------

    def _execute_masked(self) -> Optional[ColumnBatch]:
        batches = [s._batch for s in self.sources]
        if any(b.num_rows == 0 for b in batches):
            telemetry.event("fusion", "lane", lane="eager",
                            trigger="empty-source")
            return None  # eager path has exact empty-side shortcuts
        from hyperspace_tpu.parallel.context import should_distribute
        host = all(b.is_host for b in batches)
        if should_distribute(self.conf, max(b.num_rows for b in batches),
                             host_batch=host) is not None:
            telemetry.event("fusion", "lane", lane="eager",
                            trigger="mesh-distribution")
            return None  # mesh execution owns these operators instead
        if host:
            # Host lane: run the ORIGINAL eager operator graph (before
            # any broadcast-table prep — the eager join builds its own).
            # Masked execution exists to batch device dispatches and
            # syncs; on numpy a compaction is free, so eager filters
            # cutting the row count EARLY beat full-length masked
            # evaluation of every downstream operator (q27-class
            # selective star queries were ~4x slower masked). The traced
            # masked semantics still get CPU coverage through the device
            # lane on the CPU backend (tests force it via
            # execution.min.device.rows=0).
            telemetry.event("fusion", "lane", lane="eager-host",
                            trigger="host-resident sources")
            return self.root.execute()

        preps = {}
        for n in self._bhj_nodes:
            build_node = n.right if n.build_side == "right" else n.left
            prep = _prepare_broadcast(n, build_node._batch)
            if prep is None:
                telemetry.event("fusion", "lane", lane="eager",
                                trigger="broadcast-prep-declined")
                return None
            preps[n._table_slot] = prep
        return self._execute_device(batches, preps)

    def _execute_device(self, batches, preps) -> Optional[ColumnBatch]:
        key = self._program_key(batches, preps)
        if key in _INELIGIBLE_KEYS:
            telemetry.event("fusion", "lane", lane="eager",
                            trigger="trace-ineligible (cached)")
            return None
        if len(_OUT_META) > 1024:
            # Metadata and executables retire TOGETHER: evicting only
            # _OUT_META would silently force evicted stages eager forever
            # (a jit cache hit never re-runs the traced body that
            # repopulates the metadata). Full reset -> next runs re-trace
            # and re-populate both.
            telemetry.memory.cache_eviction("fusion_trace",
                                            len(_OUT_META))
            _OUT_META.clear()
            try:
                if _run_stage_jit is not None:
                    _run_stage_jit.clear_cache()
            except Exception:
                pass
        source_meta = []
        trees = {}
        promoted = []
        for i, b in enumerate(batches):
            b = _promote_batch(b)
            promoted.append(b)
            # Carried form in: the traced stage decodes a float64
            # column only if an expression reads its values.
            tree, aux = batch_to_tree(b, computes_on=())
            trees[i] = tree
            source_meta.append((b.schema, aux, b.num_rows))
        table_args = {slot: _to_device(p[0]) for slot, p in preps.items()}
        tables_meta = {slot: (p[1], p[2]) for slot, p in preps.items()}
        prog = _StageProgram(key, self.root, source_meta, tables_meta)
        import time as _time
        _stat("stage_execs", 1)
        cache_hit = key in _OUT_META
        if not cache_hit and key not in _INELIGIBLE_KEYS:
            _stat("trace_misses", 1)
        if cache_hit:
            telemetry.memory.cache_hit("fusion_trace")
        else:
            telemetry.memory.cache_miss("fusion_trace")
        telemetry.memory.cache_stats("fusion_trace", None, len(_OUT_META))
        telemetry.event("fusion", "trace-cache",
                        hit=cache_hit, ops=len(_region_nodes(self.root)))
        # Last checkpoint before committing to the jitted dispatch (a
        # cold stage pays an XLA trace here — don't start one a
        # cancelled query will never consume).
        telemetry.check_deadline("stage")
        t0 = _time.perf_counter()
        try:
            with telemetry.span("hs.stage.dispatch", "fusion",
                                ops=len(_region_nodes(self.root)),
                                cache_hit=cache_hit):
                out_tree, lazy_pairs, sel, cnt = _run_stage(prog, trees,
                                                            table_args)
        except _FusionIneligible as exc:
            _INELIGIBLE_KEYS.add(key)
            telemetry.event("fusion", "lane", lane="eager",
                            trigger=f"trace-ineligible ({exc})")
            return None
        _stat("dispatch_s", _time.perf_counter() - t0)
        # Span boundary of the stage dispatch: the working set (sources,
        # broadcast tables, stage outputs) is device-resident here.
        telemetry.memory.maybe_sample()
        meta = _OUT_META.get(key)
        if meta is None:
            # Executable outlived its evicted metadata (>256 distinct
            # stage programs since): run this one eagerly.
            telemetry.event("fusion", "lane", lane="eager",
                            trigger="metadata-evicted")
            return None
        telemetry.event("fusion", "lane", lane="masked-device",
                        trigger="device-resident sources")
        schema, reduced_schema, aux, lazy_specs = meta
        base = tree_to_batch(out_tree, reduced_schema, aux)
        for n in self._bhj_nodes:
            # the joins this stage ran as direct-address probes inside
            # its one program (the eager operator says the same of
            # itself on its record: `BroadcastHashJoinExec.execute`)
            build = n.right if n.build_side == "right" else n.left
            telemetry.event(
                "join", "broadcast", path="fused", lane="device",
                probe_rows=(int(sel.shape[0]) if sel is not None
                            else base.num_rows),
                build_rows=build._batch.num_rows)
        if (self._takes_mask is not None and not lazy_specs
                and self._takes_mask(base)):
            return base, sel
        idx = None
        if sel is not None:
            t0 = _time.perf_counter()
            with telemetry.span("hs.stage.sync", "fusion"):
                count = int(cnt)  # THE stage sync
            _stat("sync_s", _time.perf_counter() - t0)
            with telemetry.span("hs.stage.compact", "fusion", rows=count):
                idx = compact_indices(sel, count)
                base = base.take(idx)
        if not lazy_specs:
            return base
        # Deferred build-side gathers, AT SELECTION SIZE: compose each
        # lazy column's hit chain with the compaction index and gather
        # from the promoted source batch (same arrays the trace saw) —
        # all columns through ONE jitted executable, not per-column
        # eager dispatches (`ColumnBatch.take`'s own rationale).
        spec = []
        srcs = []
        src_cols = []
        for out_name, slot, source_index, src_name, dtype in lazy_specs:
            src = promoted[source_index].column(src_name)
            spec.append((slot, src.validity is not None))
            srcs.append((src.raw, src.validity))
            src_cols.append((out_name, dtype, src))
        with telemetry.span("hs.stage.gather", "fusion",
                            columns=len(spec)):
            gathered = _finalize_lazy(idx, lazy_pairs, tuple(srcs),
                                      tuple(spec))
        columns = dict(base.columns)
        for (out_name, dtype, src), (data, validity) in zip(src_cols,
                                                            gathered):
            columns[out_name] = DeviceColumn(data, dtype, validity,
                                             src.dictionary,
                                             src.dict_hashes)
        return ColumnBatch(schema, columns)

    def _program_key(self, batches, preps) -> str:
        parts = [_node_key(self.root)]
        for b in batches:
            cols = []
            for f in b.schema.fields:
                col = b.columns[f.name]
                cols.append((f.name, f.dtype, col.validity is not None,
                             _token_of(col.dictionary)))
            parts.append(repr(cols))
        for slot in sorted(preps):
            _t, mins, ranges = preps[slot]
            parts.append(f"T{slot}:{mins}:{ranges}")
        return "\x1e".join(parts)


def _node_key(node) -> str:
    from hyperspace_tpu.engine.physical import (BroadcastHashJoinExec,
                                                FilterExec, ProjectExec)
    if isinstance(node, _SourceExec):
        return f"S{node.index}"
    if isinstance(node, FilterExec):
        return (f"F({json.dumps(node.condition.to_dict(), sort_keys=True)})"
                f"[{_node_key(node.child)}]")
    if isinstance(node, ProjectExec):
        entries = [(name, src if isinstance(src, str)
                    else json.dumps(src.to_dict(), sort_keys=True))
                   for name, src in node.entries]
        return f"P({entries!r})[{_node_key(node.child)}]"
    if isinstance(node, BroadcastHashJoinExec):
        probe = node.left if node.build_side == "right" else node.right
        build = node.right if node.build_side == "right" else node.left
        cols = (sorted(node.out_columns)
                if node.out_columns is not None else None)
        return (f"B({node.how},{node.build_side},{node.left_keys},"
                f"{node.right_keys},{cols},{node._table_slot},"
                f"S{build.index})[{_node_key(probe)}]")
    raise HyperspaceException(f"Unfusible node in region: {node!r}")


# ---------------------------------------------------------------------------
# The fusion pass
# ---------------------------------------------------------------------------


def fuse_physical(root, conf=None):
    """Rewrite a physical tree, replacing maximal Filter/Project/
    BroadcastHashJoin regions with FusedStageExec. Sort-merge joins keep
    their subtrees intact on the bucketed path (the (batch, lengths)
    protocol and Exchange/Sort unwrapping are planner contracts); their
    general-path inner children still fuse."""
    from hyperspace_tpu.engine.physical import (BroadcastHashJoinExec,
                                                ExchangeExec, FilterExec,
                                                ProjectExec, ReusedExec,
                                                SortExec, SortMergeJoinExec)
    fusible = (FilterExec, ProjectExec, BroadcastHashJoinExec)
    seen: Dict[int, object] = {}

    def rec(node):
        hit = seen.get(id(node))
        if hit is not None:
            return hit
        if isinstance(node, fusible):
            sources: List[_SourceExec] = []
            new_root = build_region(node, sources)
            out = FusedStageExec(new_root, sources, conf=conf)
        elif isinstance(node, SortMergeJoinExec):
            if not node.bucketed:
                # General path: the join unwraps Sort(Exchange(child))
                # wrappers itself — fuse the inner children, keep the
                # wrapper chain.
                for attr in ("left", "right"):
                    side = getattr(node, attr)
                    inner_holder, inner_attr = None, None
                    probe = side
                    if isinstance(probe, SortExec):
                        inner_holder, inner_attr = probe, "child"
                        probe = probe.child
                    if isinstance(probe, ExchangeExec):
                        inner_holder, inner_attr = probe, "child"
                        probe = probe.child
                    if inner_holder is None:
                        setattr(node, attr, rec(side))
                    else:
                        setattr(inner_holder, inner_attr, rec(probe))
            out = node
        else:
            if isinstance(node, ReusedExec):
                node.child = rec(node.child)
            elif hasattr(node, "_children"):  # UnionExec
                node._children = [rec(c) for c in node._children]
            else:
                for attr in ("child", "left", "right"):
                    c = getattr(node, attr, None)
                    if c is not None and hasattr(c, "execute"):
                        setattr(node, attr, rec(c))
            out = node
        seen[id(node)] = out
        return out

    def build_region(node, sources: List[_SourceExec]):
        if isinstance(node, FilterExec):
            return FilterExec(node.condition, build_region(node.child,
                                                           sources),
                              conf=node.conf)
        if isinstance(node, ProjectExec):
            return ProjectExec(list(node.entries),
                               build_region(node.child, sources))
        if isinstance(node, BroadcastHashJoinExec):
            probe_attr = "left" if node.build_side == "right" else "right"
            build_attr = "right" if node.build_side == "right" else "left"
            probe = build_region(getattr(node, probe_attr), sources)
            build = _SourceExec(rec(getattr(node, build_attr)),
                                len(sources))
            sources.append(build)
            sides = {probe_attr: probe, build_attr: build}
            return BroadcastHashJoinExec(
                sides["left"], sides["right"], node.left_keys,
                node.right_keys, node.build_side, how=node.how,
                conf=node.conf, out_columns=node.out_columns)
        src = _SourceExec(rec(node), len(sources))
        sources.append(src)
        return src

    return rec(root)
