"""Query-level telemetry: per-operator metrics + structured decision events.

The reference ships real query observability — `PlanAnalyzer.explain` /
`whyNot` tell the user which index rules fired and why
(`PlanAnalyzer.scala:45-360`) — and leans on Spark's per-operator SQL
metrics for its tuning story. This package is the engine's runtime half
of that: ONE `QueryMetrics` recorder is threaded through a query
execution end-to-end and returned to the user, capturing

- per-physical-operator wall time and output row counts (the executor's
  operator walk, instrumented in `engine/physical.py`);
- structured decision events: optimizer rule fired/skipped with reason
  (`plan/rules/*`), fusion lane chosen (masked-device vs eager-host)
  with its trigger, trace-cache hit/miss, device dispatch vs sync
  seconds (`engine/fusion.py` — the per-query scoping of the
  module-level `fusion.STATS` aggregate);
- index usage: which covering index served which scan, bucket counts,
  files scanned vs pruned (`plan/rules/*` + `ScanExec`).

Scoping: the active recorder is a `contextvars.ContextVar`, so
concurrent sessions (or threads) never see each other's metrics; the
engine's internal thread pools re-establish the context explicitly via
`propagating(...)`. When no recorder is active every hook is a
single ContextVar read + None check — the always-off cost on hot paths.

Surface: `DataFrame.collect(with_metrics=True)` returns the recorder
next to the result; `session.last_query_metrics()` returns the most
recent one; `to_json()` / `format_tree()` render reports, and
`PlanAnalyzer.explain_string(..., metrics=...)` places the runtime
numbers next to the plan diff.

Process-wide observability rides in sibling modules re-exported here:
`registry` (named counters/gauges/log-bucketed histograms aggregating
across queries and sessions + the structured action-report ring;
Prometheus text dump), `trace` (span tracer with Chrome trace-event /
Perfetto export — `enable_tracing()` then `export_trace(path)`; spans
cover queries, operators, fusion stages, maintenance-action phases,
mesh dispatches, and H2D/D2H link transfers on their real threads),
`memory` (the device-memory accountant — per-device live/peak HBM
gauges, per-query `peak_hbm_bytes` watermarks, Perfetto counter
tracks — plus the byte-aware `cache.<name>.*` instrumentation every
cache in the system reports through), and `compilation`
(`instrumented_jit`: compile spans, trace/cache-hit counters, and
retrace-cause decision events for every jit entry point).

Regression attribution: `flight` (the always-on ring of the last-K
completed QueryMetrics plus the slow-query dump,
`spark.hyperspace.telemetry.slowlog.*`) and `diff` (align two
QueryMetrics trees — a slow-query dump against a live re-run — and
decompose the wall delta into compute / link / compile / cache /
fallback / residual buckets).
"""

from __future__ import annotations

import contextvars
import itertools
import json
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from hyperspace_tpu.telemetry.registry import (MetricsRegistry,
                                               get_registry)
from hyperspace_tpu.telemetry.trace import (DEVICE_SCOPES, SPAN_NAMES,
                                            Tracer, disable_tracing,
                                            enable_tracing, export_trace,
                                            link_transfer,
                                            record_link_transfer, span,
                                            spans_active, tracer,
                                            tracing_enabled)
from hyperspace_tpu.telemetry import memory  # noqa: F401
from hyperspace_tpu.telemetry import compilation  # noqa: F401
from hyperspace_tpu.telemetry import diff  # noqa: F401
from hyperspace_tpu.telemetry import flight  # noqa: F401
from hyperspace_tpu.telemetry import timeseries  # noqa: F401
from hyperspace_tpu.telemetry import ops_server  # noqa: F401
from hyperspace_tpu.telemetry import critical_path  # noqa: F401
from hyperspace_tpu.telemetry import profiler  # noqa: F401
from hyperspace_tpu.telemetry.compilation import (device_scoped,
                                                  instrumented_jit)
from hyperspace_tpu.telemetry.flight import (FlightRecorder,
                                             get_recorder)
from hyperspace_tpu.telemetry.memory import (DeviceMemoryAccountant,
                                             get_accountant)

__all__ = [
    "QueryMetrics", "OperatorRecord", "current", "recording",
    "propagating", "event", "annotate", "add_seconds", "add_count",
    "current_deadline", "deadline_scope", "check_deadline",
    "DEFAULT_TENANT", "current_tenant", "tenant_scope", "charge_tenant",
    "known_tenants", "tenant_digest", "TENANT_CHARGE_COUNTERS",
    "MetricsRegistry", "get_registry", "Tracer", "enable_tracing",
    "disable_tracing", "tracing_enabled", "tracer", "span",
    "spans_active", "SPAN_NAMES", "DEVICE_SCOPES",
    "link_transfer", "record_link_transfer", "export_trace",
    "memory", "compilation", "instrumented_jit", "device_scoped", "diff",
    "flight", "FlightRecorder", "get_recorder",
    "DeviceMemoryAccountant", "get_accountant",
    "timeseries", "ops_server", "critical_path", "profiler",
]


_current: contextvars.ContextVar[Optional["QueryMetrics"]] = \
    contextvars.ContextVar("hyperspace_query_metrics", default=None)

# The active query's Deadline (`engine/scheduler.Deadline`) rides the
# SAME contextvar scoping as the recorder: set by the scheduler around
# execution, carried across the engine's pool threads by
# `propagating(...)`, read by the cooperative-cancellation checkpoints
# (`check_deadline`) at operator / fusion-stage / transfer-chunk /
# sorted-run-write boundaries. The var lives HERE (not in the
# scheduler) because every checkpoint module already imports telemetry
# — the hooks stay one ContextVar read + None check when serving
# features are off, the same always-off contract as the recorder.
_deadline: contextvars.ContextVar = \
    contextvars.ContextVar("hyperspace_query_deadline", default=None)

# The active TENANT identity rides the same contextvar scoping as the
# recorder and deadline: set by the scheduler/session seam
# (`session.tenant(...)` / `collect(tenant=...)` — raw writes anywhere
# else are banned by `scripts/check_metrics_coverage.py`), carried
# across pool threads by `propagating(...)`, read by every chargeback
# site (`compilation.instrumented_jit`, `trace.record_link_transfer`,
# the segment-cache fill paths) to mirror global counters onto
# `tenant.<id>.*`. Unset means the DEFAULT tenant — charges never go
# unattributed, so summing `tenant.<id>.*` over all tenants (including
# "default") equals the global counters EXACTLY.
_tenant: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("hyperspace_query_tenant", default=None)

DEFAULT_TENANT = "default"

# Tenants observed by any chargeback/scope since process start, so the
# report/healthz surfaces can enumerate `tenant.<id>.*` families
# without parsing metric names (tenant ids may themselves contain
# dots). Guarded by its own lock; never pruned (ids are few).
_known_tenants: set = {DEFAULT_TENANT}
_known_tenants_lock = threading.Lock()


def current() -> Optional["QueryMetrics"]:
    """The recorder of the query executing on this thread, or None."""
    return _current.get()


def current_deadline():
    """The Deadline of the query executing on this thread, or None."""
    return _deadline.get()


@contextmanager
def deadline_scope(deadline):
    """Make `deadline` the active cancellation token for the calling
    context (None is allowed and makes the scope a no-op carrier)."""
    token = _deadline.set(deadline)
    try:
        yield deadline
    finally:
        _deadline.reset(token)


def check_deadline(phase: str) -> None:
    """Cooperative-cancellation checkpoint: raises the active
    deadline's typed error (QueryCancelledError /
    QueryDeadlineExceededError, tagged with `phase`) when the query
    was cancelled or its deadline passed; no-op without an active
    deadline. `phase` names what the raise would interrupt —
    scan/operator/stage/transfer/write — so timeout clusters are
    attributable to a bucket (`telemetry/diff.py`), not `residual`."""
    d = _deadline.get()
    if d is not None:
        d.check(phase)


def current_tenant() -> str:
    """The tenant the calling context charges to — the contextvar if a
    tenant scope is active, else the DEFAULT tenant. Never None:
    chargeback sites must always have someone to bill."""
    return _tenant.get() or DEFAULT_TENANT


def known_tenants() -> List[str]:
    """Sorted ids of every tenant observed since process start."""
    with _known_tenants_lock:
        return sorted(_known_tenants)


def _note_tenant(tenant: str) -> None:
    if tenant not in _known_tenants:  # racy pre-check; set add is safe
        with _known_tenants_lock:
            _known_tenants.add(tenant)


@contextmanager
def tenant_scope(tenant: Optional[str]):
    """Make `tenant` the active billing identity for the calling
    context (None keeps the surrounding scope — a no-op carrier). This
    is the ONE sanctioned write seam besides `propagating`; the
    metrics-coverage lint bans raw `_tenant.set(...)` elsewhere."""
    if tenant is None:
        yield None
        return
    tenant = str(tenant)
    _note_tenant(tenant)
    token = _tenant.set(tenant)
    try:
        yield tenant
    finally:
        _tenant.reset(token)


# Every counter family the chargeback sites mirror per-tenant. The
# digest (and `Hyperspace.tenant_report()`) reads exactly these, and
# the exactness contract is: for each name here, the sum of
# `tenant.<id>.<name>` over ALL known tenants equals the global
# counter of the same name.
TENANT_CHARGE_COUNTERS = (
    "device.flops", "device.bytes_accessed", "device.dispatch.seconds",
    "link.h2d.bytes", "link.d2h.bytes", "cache.segments.fills",
)


def tenant_digest() -> Dict[str, Dict[str, float]]:
    """{tenant: {charge counter: value}} for every known tenant, read
    from the registry's `tenant.<id>.*` mirrors. Tenants with zero
    usage are included (the default tenant always appears), so a
    consumer can verify the exactness contract by summing columns.
    UNROUNDED values (`counters_dict` rounds to a microsecond, and sums
    of rounded dispatch-seconds do not equal the rounded sum)."""
    counters = get_registry().series_snapshot()["counters"]
    out: Dict[str, Dict[str, float]] = {}
    for t in known_tenants():
        out[t] = {name: counters.get(f"tenant.{t}.{name}", 0)
                  for name in TENANT_CHARGE_COUNTERS}
    return out


def charge_tenant(name: str, amount: float = 1.0,
                  tenant: Optional[str] = None) -> str:
    """Mirror a global-counter increment onto the active tenant's
    `tenant.<id>.<name>` series. Call this at the SAME site as the
    global `reg.counter(name).inc(amount)` so per-tenant sums stay
    exactly equal to the global counters (the chargeback exactness
    contract `Hyperspace.tenant_report()` asserts). Returns the tenant
    charged."""
    t = tenant if tenant is not None else current_tenant()
    _note_tenant(t)
    get_registry().counter(f"tenant.{t}.{name}").inc(amount)
    return t


@contextmanager
def recording(metrics: "QueryMetrics"):
    """Make `metrics` the active recorder for the calling context."""
    token = _current.set(metrics)
    try:
        yield metrics
    finally:
        _current.reset(token)


def propagating(fn):
    """Wrap `fn` for execution on another thread (the engine's internal
    pools), carrying over the active recorder AND the caller's position
    in the operator tree — contextvars do not cross thread boundaries on
    their own, and the worker's operator records must parent under the
    operator that forked the work (e.g. the bucketed join reading its
    two sides concurrently). The active Deadline rides along too: a
    cancelled query's pool-side subtree hits the same cooperative
    checkpoints its main thread does, and the active TENANT rides along
    so pool-side device dispatches charge the right bill."""
    rec = _current.get()
    deadline = _deadline.get()
    tenant = _tenant.get()
    if rec is None and deadline is None and tenant is None:
        return fn
    parent = rec._current_op_id() if rec is not None else None

    def run(*args, **kwargs):
        token = _current.set(rec)
        dtoken = _deadline.set(deadline)
        ttoken = _tenant.set(tenant)
        if rec is not None:
            rec._adopt_parent(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            if rec is not None:
                rec._clear_adoption()
            _tenant.reset(ttoken)
            _deadline.reset(dtoken)
            _current.reset(token)

    return run


def event(category: str, name: str, **detail) -> None:
    """Record a structured decision event on the active recorder (no-op
    without one). Keep values JSON-serializable."""
    rec = _current.get()
    if rec is not None:
        rec.event(category, name, **detail)


def annotate(**detail) -> None:
    """Attach detail to the operator record currently executing on this
    thread (no-op without a recorder or outside an operator)."""
    rec = _current.get()
    if rec is not None:
        rec.annotate_current(**detail)


def add_seconds(counter: str, seconds: float) -> None:
    """Accumulate a per-query timing counter (no-op without a recorder)."""
    rec = _current.get()
    if rec is not None:
        rec.add_seconds(counter, seconds)


def add_count(counter: str, n: int = 1) -> None:
    rec = _current.get()
    if rec is not None:
        rec.add_count(counter, n)


def _fmt_bytes(n: int) -> str:
    """Human-readable bytes for report rendering (binary units)."""
    value = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if value < 1024 or unit == "TiB":
            return (f"{int(value)}{unit}" if unit == "B"
                    else f"{value:.1f}{unit}")
        value /= 1024
    return f"{n}B"


class OperatorRecord:
    """One physical operator execution: identity, tree position, wall
    time, and output rows. `rows_out` for device batches is the static
    shape (no sync is forced to report it); `wall_s` on the device lane
    measures dispatch-side time unless the operator itself syncs.

    The display label (`simple_string()` of the node) is resolved
    LAZILY — at query finish or first report — so the per-operator
    recording cost on the execute hot path stays at two perf_counter
    reads plus an append."""

    __slots__ = ("op_id", "parent_id", "name", "bucketed",
                 "wall_s", "rows_out", "detail", "error", "_t0",
                 "_node", "_label")

    def __init__(self, op_id: int, parent_id: Optional[int], name: str,
                 node, bucketed: bool):
        self.op_id = op_id
        self.parent_id = parent_id
        self.name = name
        self.bucketed = bucketed
        self.wall_s = 0.0
        self.rows_out: Optional[int] = None
        self.detail: Dict = {}
        self.error: Optional[str] = None
        self._node = node
        self._label: Optional[str] = None
        self._t0 = time.perf_counter()

    @property
    def label(self) -> str:
        if self._label is None:
            node, self._node = self._node, None
            if node is None:
                self._label = self.name
            else:
                try:
                    self._label = node.simple_string()
                except Exception:
                    self._label = self.name
        return self._label

    def to_dict(self) -> dict:
        d = {"op_id": self.op_id, "parent_id": self.parent_id,
             "name": self.name, "label": self.label,
             "wall_s": round(self.wall_s, 6), "rows_out": self.rows_out}
        if self.bucketed:
            d["bucketed"] = True
        if self.detail:
            d["detail"] = dict(self.detail)
        if self.error is not None:
            d["error"] = self.error
        return d


class QueryMetrics:
    """Everything recorded about ONE query execution. Thread-safe for
    append (operators may execute on pool threads); the per-thread
    operator stack lives in a threading.local so concurrent subtree
    executions keep their own parent chains."""

    def __init__(self, description: str = ""):
        self.description = description
        self.started_at = time.time()
        self.wall_s: Optional[float] = None
        self.operators: List[OperatorRecord] = []
        self.events: List[dict] = []
        self.counters: Dict[str, float] = {}
        # Peak HBM watermarks observed while this query was recording:
        # per device, and the peak TOTAL across devices (the headline).
        # Fed by the device-memory accountant at span boundaries and
        # link transfers (`telemetry/memory.py`); 0/{} when the query
        # never touched a device (pure host lane).
        self.peak_hbm_bytes: int = 0
        self.peak_hbm_per_device: Dict[str, int] = {}
        # Serving dimensions, stamped by the scheduler and the batch
        # lane: the routed replica slice (None = unrouted) and the
        # batched-execution cohort this query rode ({"id", "size"},
        # None = solo), plus the tenant billed for the query (None =
        # default tenant / no tenant scope). The flight ring inherits
        # all three, so post-hoc tail diagnosis can group by replica,
        # cohort, and tenant.
        self.replica = None
        self.cohort: Optional[dict] = None
        self.tenant: Optional[str] = None
        # Latency anatomy, stamped at query finish by
        # `telemetry/critical_path.py`: the wall decomposed into the
        # closed segment set ({wall_s, segments, dominant, ...}),
        # segments summing exactly to wall_s. None until stamped.
        self.critical_path: Optional[dict] = None
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._tls = threading.local()
        self._t0 = time.perf_counter()

    # -- recorder side (engine hooks) ----------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def _current_op_id(self) -> Optional[int]:
        stack = self._stack()
        return stack[-1].op_id if stack else None

    def _adopt_parent(self, parent_id: Optional[int]) -> None:
        """Root this worker thread's operator chain under `parent_id`
        (see `propagating`)."""
        self._tls.adopted = parent_id

    def _clear_adoption(self) -> None:
        self._tls.adopted = None

    def start_operator(self, name: str, node=None,
                       bucketed: bool = False) -> OperatorRecord:
        stack = self._stack()
        parent = (stack[-1].op_id if stack
                  else getattr(self._tls, "adopted", None))
        # next() on itertools.count and list.append are both atomic
        # under the GIL — the hot path takes no lock.
        op = OperatorRecord(next(self._ids), parent, name, node, bucketed)
        self.operators.append(op)
        stack.append(op)
        return op

    def finish_operator(self, op: OperatorRecord,
                        rows_out: Optional[int] = None,
                        error: Optional[str] = None) -> None:
        op.wall_s = time.perf_counter() - op._t0
        op.rows_out = rows_out
        op.error = error
        stack = self._stack()
        if stack and stack[-1] is op:
            stack.pop()
        else:  # unbalanced (exception skipped a frame): resync
            while stack and stack[-1] is not op:
                stack.pop()
            if stack:
                stack.pop()

    def annotate_current(self, **detail) -> None:
        stack = self._stack()
        if stack:
            stack[-1].detail.update(detail)

    def event(self, category: str, name: str, **detail) -> None:
        e = {"category": category, "name": name}
        e.update(detail)
        with self._lock:
            self.events.append(e)

    def add_seconds(self, counter: str, seconds: float) -> None:
        with self._lock:
            self.counters[counter] = self.counters.get(counter, 0.0) \
                + float(seconds)

    def add_count(self, counter: str, n: int = 1) -> None:
        with self._lock:
            self.counters[counter] = self.counters.get(counter, 0) + n

    def observe_hbm(self, live_bytes_per_device: Dict[str, int]) -> None:
        """Fold one device-memory sample into this query's peak
        watermarks (called by the accountant while recording)."""
        with self._lock:
            for dev, b in live_bytes_per_device.items():
                if b > self.peak_hbm_per_device.get(dev, 0):
                    self.peak_hbm_per_device[dev] = int(b)
            total = sum(live_bytes_per_device.values())
            if total > self.peak_hbm_bytes:
                self.peak_hbm_bytes = int(total)

    def finish(self) -> "QueryMetrics":
        self.wall_s = time.perf_counter() - self._t0
        for op in self.operators:
            op.label  # resolve now; releases the node references
        return self

    # -- user side (reports) -------------------------------------------

    @property
    def compile(self) -> dict:
        """This query's compile story: how many XLA traces it caused,
        how many jit dispatches were served from the executable cache,
        and the seconds spent tracing/compiling. A warmed query re-run
        must show traces == 0 — nonzero here on a repeat run is a
        retrace, and the `[compile] retrace` events name the
        shape/dtype delta that caused it."""
        return {
            "traces": int(self.counters.get("compile.traces", 0)),
            "cache_hits": int(self.counters.get("compile.cache_hits", 0)),
            "seconds": round(
                float(self.counters.get("compile.seconds", 0.0)), 6),
        }

    @property
    def roofline(self) -> dict:
        """This query's device cost story, from the XLA cost analyses
        `instrumented_jit` captured at trace time and the per-dispatch
        measured walls: modeled flops and bytes accessed, the measured
        warm-dispatch seconds, the device share of the query's wall
        (the device-bound-vs-overhead split — a low share says the
        bottleneck is host orchestration, not the chip), and the
        arithmetic intensity that places the work on a roofline plot.
        Walls on async backends are dispatch-side unless an operator
        syncs, so achieved flops/s is a floor estimate."""
        flops = float(self.counters.get("device.flops", 0.0))
        nbytes = float(self.counters.get("device.bytes_accessed", 0.0))
        disp = float(self.counters.get("device.dispatch_s", 0.0))
        wall = self.wall_s
        return {
            "flops": round(flops, 1),
            "bytes_accessed": round(nbytes, 1),
            "dispatch_s": round(disp, 6),
            "device_share": (round(min(disp / wall, 1.0), 4)
                             if wall else None),
            "intensity_flops_per_byte": (round(flops / nbytes, 4)
                                         if nbytes else None),
            "achieved_flops_per_s": (round(flops / disp, 1)
                                     if disp > 0 else None),
        }

    def events_of(self, category: str, name: Optional[str] = None
                  ) -> List[dict]:
        return [e for e in self.events
                if e["category"] == category
                and (name is None or e["name"] == name)]

    def rows_in(self, op: OperatorRecord) -> Optional[int]:
        """Sum of the operator's direct children's output rows (None when
        no child reported rows — e.g. a leaf scan)."""
        rows = [c.rows_out for c in self.operators
                if c.parent_id == op.op_id and c.rows_out is not None]
        return sum(rows) if rows else None

    def index_usage(self) -> List[dict]:
        """Index-usage records: one per rule application (index name,
        side, bucket count) joined against the scan records that actually
        read the index data (files scanned vs pruned). Bucketed scans no
        rule claimed (hand-built layouts) are reported without a name."""
        scans = [op for op in self.operators if op.name == "Scan"]
        claimed: set = set()
        out = []
        for e in self.events_of("rule"):
            if e.get("action") != "applied":
                continue
            for use in e.get("indexes", []):
                rec = dict(use)
                rec["rule"] = e["name"]
                root = use.get("root")
                for op in scans:
                    if root and root in op.detail.get("roots", ()):
                        claimed.add(op.op_id)
                        for k in ("files_scanned", "files_total",
                                  "buckets_scanned", "buckets_total",
                                  "lane"):
                            if k in op.detail:
                                rec[k] = op.detail[k]
                        rec["rows_out"] = op.rows_out
                out.append(rec)
        for op in scans:
            if op.op_id in claimed or "buckets_total" not in op.detail:
                continue
            rec = {"name": None, "rule": None,
                   "root": (op.detail.get("roots") or [None])[0],
                   "rows_out": op.rows_out}
            for k in ("files_scanned", "files_total", "buckets_scanned",
                      "buckets_total", "lane"):
                if k in op.detail:
                    rec[k] = op.detail[k]
            out.append(rec)
        return out

    def to_dict(self) -> dict:
        out = {
            "description": self.description,
            "started_at": self.started_at,
            "wall_s": (round(self.wall_s, 6)
                       if self.wall_s is not None else None),
            "operators": [op.to_dict() for op in self.operators],
            "events": list(self.events),
            "counters": {k: (round(v, 6) if isinstance(v, float) else v)
                         for k, v in self.counters.items()},
            "index_usage": self.index_usage(),
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "peak_hbm_per_device": dict(self.peak_hbm_per_device),
            "compile": self.compile,
            "roofline": self.roofline,
        }
        if self.replica is not None:
            out["replica"] = self.replica
        if self.cohort is not None:
            out["cohort"] = dict(self.cohort)
        if self.tenant is not None:
            out["tenant"] = self.tenant
        if self.critical_path is not None:
            out["critical_path"] = dict(self.critical_path)
        return out

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=False,
                          default=str)

    def summary(self) -> dict:
        """Compact per-query digest (operator-level trajectories, not
        just totals). Operator seconds are summed per operator type over
        SELF time (child time subtracted), so the digest adds up instead
        of double-counting nested walls."""
        child_s: Dict[Optional[int], float] = {}
        for op in self.operators:
            child_s[op.parent_id] = child_s.get(op.parent_id, 0.0) \
                + op.wall_s
        per_op: Dict[str, dict] = {}
        for op in self.operators:
            ent = per_op.setdefault(op.name, {"count": 0, "self_s": 0.0,
                                              "rows_out": 0})
            ent["count"] += 1
            ent["self_s"] += max(op.wall_s
                                 - child_s.get(op.op_id, 0.0), 0.0)
            ent["rows_out"] += op.rows_out or 0
        for ent in per_op.values():
            ent["self_s"] = round(ent["self_s"], 4)
        lanes: Dict[str, int] = {}
        for e in self.events_of("fusion", "lane"):
            lanes[e.get("lane", "?")] = lanes.get(e.get("lane", "?"), 0) + 1
        rules: Dict[str, int] = {}
        for e in self.events_of("rule"):
            key = f"{e['name']}:{e.get('action', '?')}"
            rules[key] = rules.get(key, 0) + 1
        out = {
            "wall_s": (round(self.wall_s, 4)
                       if self.wall_s is not None else None),
            "operators": per_op,
            "fusion_lanes": lanes,
            "rules": rules,
            "counters": {k: (round(v, 4) if isinstance(v, float) else v)
                         for k, v in self.counters.items()},
            "index_usage": self.index_usage(),
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "compile": self.compile,
            "roofline": self.roofline,
        }
        if self.tenant is not None:
            out["tenant"] = self.tenant
        if self.critical_path is not None:
            out["critical_path"] = dict(self.critical_path)
        return out

    def format_tree(self) -> str:
        """Operator tree with runtime numbers — the companion view to
        `PlanAnalyzer.explain_string`'s plan diff."""
        children: Dict[Optional[int], List[OperatorRecord]] = {}
        for op in self.operators:
            children.setdefault(op.parent_id, []).append(op)
        lines: List[str] = []
        header = "Query metrics"
        if self.description:
            header += f" — {self.description}"
        if self.wall_s is not None:
            header += f" ({self.wall_s:.3f}s)"
        lines.append(header)

        def emit(op: OperatorRecord, depth: int) -> None:
            pad = "  " * depth + ("+- " if depth else "")
            rows = f" rows={op.rows_out}" if op.rows_out is not None else ""
            extra = ""
            if op.detail:
                keys = ("lane", "files_scanned", "files_total",
                        "buckets_scanned", "buckets_total", "reused")
                bits = [f"{k}={op.detail[k]}" for k in keys
                        if k in op.detail]
                if bits:
                    extra = " [" + ", ".join(bits) + "]"
            err = f" ERROR={op.error}" if op.error else ""
            lines.append(f"{pad}{op.label}  ({op.wall_s:.4f}s{rows})"
                         f"{extra}{err}")
            for c in children.get(op.op_id, []):
                emit(c, depth + 1)

        for root in children.get(None, []):
            emit(root, 1)
        if self.events:
            lines.append("Events:")
            for e in self.events:
                detail = {k: v for k, v in e.items()
                          if k not in ("category", "name")}
                lines.append(f"  [{e['category']}] {e['name']} "
                             + json.dumps(detail, default=str))
        if self.counters:
            lines.append("Counters:")
            for k in sorted(self.counters):
                v = self.counters[k]
                lines.append(f"  {k} = "
                             + (f"{v:.4f}" if isinstance(v, float)
                                else str(v)))
        if self.peak_hbm_bytes:
            per_dev = ", ".join(
                f"{dev}={_fmt_bytes(b)}"
                for dev, b in sorted(self.peak_hbm_per_device.items()))
            lines.append(f"Peak HBM: {_fmt_bytes(self.peak_hbm_bytes)}"
                         + (f" ({per_dev})" if per_dev else ""))
        comp = self.compile
        if comp["traces"] or comp["cache_hits"]:
            lines.append(f"Compile: {comp['traces']} traces, "
                         f"{comp['cache_hits']} cache hits, "
                         f"{comp['seconds']:.4f}s")
        roof = self.roofline
        if roof["flops"] or roof["dispatch_s"]:
            bits = [f"{roof['flops']:.0f} flops",
                    f"{roof['bytes_accessed']:.0f} B accessed",
                    f"{roof['dispatch_s']:.4f}s dispatch"]
            if roof["device_share"] is not None:
                bits.append(f"device share {roof['device_share']:.1%}")
            if roof["intensity_flops_per_byte"] is not None:
                bits.append(
                    f"{roof['intensity_flops_per_byte']:.2f} flops/B")
            lines.append("Device: " + ", ".join(bits))
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"QueryMetrics({len(self.operators)} operators, "
                f"{len(self.events)} events, wall_s={self.wall_s})")
