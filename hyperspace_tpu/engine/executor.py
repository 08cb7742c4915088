"""Plan execution entry points."""

from __future__ import annotations

from typing import Optional, Sequence

import itertools
import uuid

import hyperspace_tpu.engine  # noqa: F401  (x64 config)
from hyperspace_tpu.engine.physical import PhysicalNode, plan_physical
from hyperspace_tpu.io.columnar import ColumnBatch, fetched
from hyperspace_tpu.plan.nodes import LogicalPlan

# Profiler capture naming: fast back-to-back queries can share a
# wall-clock stamp, so names carry a process-unique counter. The
# capture itself serializes inside `telemetry.profiler.device_trace`
# (jax permits one active profiler session per process).
_trace_seq = itertools.count()
_trace_run_id = uuid.uuid4().hex[:8]


def compile_plan(plan: LogicalPlan,
                 projection: Optional[Sequence[str]] = None,
                 conf=None, fuse: Optional[bool] = None) -> PhysicalNode:
    """Logical -> executable physical plan. `fuse=None` follows the conf
    (whole-stage fusion on by default); explain/analysis paths pass
    fuse=False — the operator tree IS the display contract (Exchange/Sort
    elision diff), and fusion groups operators without changing them."""
    required = set(projection) if projection is not None else None
    physical = plan_physical(plan, required, conf)
    if projection is not None:
        from hyperspace_tpu.engine.physical import ProjectExec
        physical = ProjectExec(list(projection), physical)
    if fuse is None:
        fuse = conf is None or conf.fusion_enabled
    if fuse:
        from hyperspace_tpu.engine.fusion import fuse_physical
        physical = fuse_physical(physical, conf=conf)
    return physical


def _scalar_subqueries(plan: LogicalPlan):
    """Every ScalarSubquery expression reachable from `plan` (conditions,
    projections, aggregate inputs) — subquery plans are NOT descended
    into here; resolution recurses through execute_plan instead."""
    from hyperspace_tpu.plan import expr as E
    from hyperspace_tpu.plan.nodes import (Aggregate, Filter, Join, Project,
                                           Window)

    found = []

    def walk_expr(e):
        if isinstance(e, E.ScalarSubquery):
            found.append(e)
            return
        # children already includes In values and CaseWhen branches.
        for c in e.children:
            walk_expr(c)

    def visit(node):
        if isinstance(node, Filter):
            walk_expr(node.condition)
        elif isinstance(node, Project):
            for c in node.columns:
                if not isinstance(c, str):
                    walk_expr(c)
        elif isinstance(node, Join) and node.condition is not None:
            walk_expr(node.condition)
        elif isinstance(node, (Aggregate, Window)):
            for spec in (node.aggregates if isinstance(node, Aggregate)
                         else node.specs):
                if spec.is_expression:
                    walk_expr(spec.column)
        for c in node.children:
            visit(c)

    visit(plan)
    return found


def _resolve_scalar_subqueries(plan: LogicalPlan, conf) -> None:
    """Execute every unresolved scalar subquery in `plan` and cache its
    value on the node (the subquery-execution phase; Spark does the same
    before the main plan runs). One column required; one row -> value,
    zero rows -> SQL NULL, more -> error. Nested subqueries resolve
    through the recursive execute_plan call."""
    import numpy as np

    for sub in _scalar_subqueries(plan):
        if sub._resolved:
            continue
        batch = execute_plan(sub.execution_plan(), conf=conf)
        if batch.num_rows > 1:
            from hyperspace_tpu.exceptions import HyperspaceException
            raise HyperspaceException(
                f"Scalar subquery returned {batch.num_rows} rows.")
        if batch.num_rows == 0:
            sub.resolve(None)
            continue
        (field,) = batch.schema.fields
        col = batch.columns[field.name]
        if col.validity is not None and not bool(
                np.asarray(col.validity)[0]):
            sub.resolve(None)
            continue
        raw = fetched(np.asarray(col.raw), col.dtype)[0]
        if col.is_string:
            sub.resolve(str(col.dictionary[int(raw)]))
        elif field.dtype == "bool":
            sub.resolve(bool(raw))
        elif field.dtype in ("float32", "float64"):
            sub.resolve(float(raw))
        else:
            sub.resolve(int(raw))


def execute_plan(plan: LogicalPlan,
                 projection: Optional[Sequence[str]] = None,
                 conf=None) -> ColumnBatch:
    import time as _time

    from hyperspace_tpu import telemetry

    _resolve_scalar_subqueries(plan, conf)
    t0 = _time.perf_counter()
    with telemetry.span("hs.plan.compile", "plan"):
        physical = compile_plan(plan, projection, conf)
    # Physical planning + fusion grouping time, per query (device-side
    # XLA compiles happen lazily inside operators, not here).
    telemetry.add_seconds("plan_s", _time.perf_counter() - t0)
    trace_dir = conf.trace_dir if conf is not None else None
    if not trace_dir:
        return physical.execute()
    # Native tracing (SURVEY §5): one XLA profiler capture per executed
    # query — device compute, transfers, and host gaps land in the same
    # timeline; inspect with TensorBoard/XProf or Perfetto. The capture
    # routes through the ONE device-profiler seam
    # (`telemetry/profiler.py`), which serializes concurrent sessions.
    from hyperspace_tpu.telemetry import profiler

    seq = next(_trace_seq)
    capture = f"{trace_dir.rstrip('/')}/query-{_trace_run_id}-{seq:05d}"
    telemetry.event("profiler", "capture", path=capture)
    with profiler.device_trace(capture):
        out = physical.execute()
        # Materialize ALL device work inside the capture window —
        # validity masks and dictionary hashes included, or their
        # compute/transfers land after the capture closes.
        for col in out.columns.values():
            for arr in (col.raw, col.validity,
                        *(col.dict_hashes or ())):
                if hasattr(arr, "block_until_ready"):
                    arr.block_until_ready()
    return out
