"""Span tracer with Chrome trace-event / Perfetto JSON export.

Where the registry (`telemetry/registry.py`) aggregates, the tracer
keeps a TIMELINE: complete-event spans for queries, physical operators,
fusion stages, index-maintenance action phases, and H2D/D2H link
transfers, each stamped with the REAL thread it ran on — so the export
shows the bucketed join's two sides reading concurrently on their pool
threads, and the link transfer that serialized them. Mesh work adds a
synthetic per-device process (`pid=2`) whose tracks carry per-shard row
attribution, making multi-chip skew visible as unequal track labels.

Off by default: every hook starts with one module-global read + None
check (`tracer()`), the same always-off discipline as the query
recorder. `enable_tracing()` installs a bounded ring (old events drop,
never the process); `export_trace(path)` writes the standard
`{"traceEvents": [...]}` JSON object that chrome://tracing and
https://ui.perfetto.dev load directly.

Timestamps are microseconds on the tracer's own perf_counter clock —
the Chrome format needs only internal consistency, and perf_counter is
the engine's timing base everywhere else.

ONE seam, TWO sinks: `span(...)` also opens a host annotation named as
the span (`hs.*`, `SPAN_NAMES`) in the trace of any running
jax profiler session, through `telemetry/profiler.annotation` — so a
device capture shows the program's own layers on the profiler's clock
beside the device's ops, and a reducer can give each idle gap of the
chip to the layer the host was in. The ring is for the Chrome export;
the annotations are for captures. No conf key, no environment
variable: a sink records when its owner runs (`enable_tracing()`, a
profiler session), and with neither a span costs two flag reads.
`DEVICE_SCOPES` are the matching names ON the device: each jitted
program's scope (`instrumented_jit(name, scope=...)`).
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from hyperspace_tpu.telemetry import profiler as _profiler
from hyperspace_tpu.telemetry import registry as _registry

__all__ = ["Tracer", "enable_tracing", "disable_tracing",
           "tracing_enabled", "tracer", "span", "spans_active",
           "completed", "link_transfer", "record_link_transfer",
           "export_trace", "SPAN_NAMES", "DEVICE_SCOPES", "PID_ENGINE",
           "PID_MESH"]

# Trace "processes": real engine threads vs the synthetic per-device
# tracks (tid = device ordinal) mesh dispatches attribute work to.
PID_ENGINE = 1
PID_MESH = 2

# Every span name the engine opens, by layer (docs/telemetry.md and
# PERF.md quote this table; `bench/lib/program_spans.py` groups by these
# prefixes). `<...>` stands for a class or function name. Stable,
# lower-case, dotted; a refactor keeps them.
SPAN_NAMES = {
    # serving plane — engine/scheduler.collect
    "hs.serve.admit": "queue wait + admission bookkeeping (queue_wait_s)",
    "hs.query": "planning + execution of one query, under its recorder",
    "hs.serve.credit": "footprint re-projection + residency credit",
    "hs.serve.finish": "after execution: metrics.finish, critical-path "
                       "stamp, registry + SLO updates, index-usage "
                       "mining, flight ring",
    "hs.serve.batch": "one batched invocation for a cohort (members)",
    "hs.serve.batch.member": "a member's wait on its cohort",
    # planner — in the query's own path
    "hs.plan.optimize": "session.optimize: the rewrite rules",
    "hs.plan.compile": "compile_plan: physical planning + fusion "
                       "grouping",
    "hs.plan.hybrid": "hybrid scan, inside hs.plan.optimize: one index "
                      "held against the relation's current files "
                      "(index, then files: the listing's, appended and "
                      "deleted: what the index lacks and has lost; "
                      "appended -1 where it is declined)",
    "hs.plan.skipping": "data skipping, inside hs.plan.optimize: one "
                        "skipping index's sketches consulted for a "
                        "scan's files (index, files, kept, "
                        "bytes_pruned, served: source, zorder-copy or "
                        "hybrid-remainder where files were pruned, "
                        "empty where none were)",
    # operators — engine/physical wrapper, on the executing thread
    "hs.op.<Name>": "one physical operator (lane, rows; a Scan also "
                    "index: the index it reads, or source: the source "
                    "directory's name, files: the files it read, and "
                    "a Scan of hybrid scan's appended files appended: "
                    "the same)",
    # fused stage — engine/fusion.py; sync + compact also in the unfused
    # filters (engine/physical.FilterExec, engine/compiler.apply_filter)
    "hs.stage.dispatch": "the stage program's dispatch (ops, cache_hit)",
    "hs.stage.sync": "host blocked on a filter's survivor count(s)",
    "hs.stage.compact": "the survivors' indices + take of them (rows)",
    "hs.stage.gather": "the deferred lazy gathers (columns)",
    # link / residency
    "hs.scan.resolve": "a scan learning its files, per-bucket rows, row "
                       "total and bytes: from the memo kept with a "
                       "committed version's segments, or from the "
                       "listing and footers (files, cached)",
    "hs.segcache.fill": "a segment-cache fill (index, files)",
    "hs.link.h2d": "a host-to-device placement (bytes, chunks)",
    "hs.link.d2h": "a device-to-host fetch (bytes)",
    "hs.to_arrow": "ColumnBatch -> Arrow table (rows, columns)",
    "hs.to_arrow.prefetch": "issuing the async D2H copies",
    # index build — io/builder.py, actions/
    "hs.build.read": "source Parquet decode (files, rows)",
    "hs.build.sort": "bucket hash + (bucket, keys) sort permutation "
                     "(lane, rows)",
    "hs.build.write": "gather + encode + file writes, on the calling "
                      "thread (files)",
    "hs.build.write.file": "one file's encode + write, on the writer "
                           "thread (rows)",
    "hs.action.<Class>": "one maintenance action's run()",
    "hs.action.<Class>.<phase>": "validate / begin / op / end",
    # mesh (multi-chip)
    "hs.mesh.place": "placing a batch over the mesh (rows, shards)",
    "hs.mesh.read": "a born-sharded read: each device's bucket range "
                    "out of its segment cache, or filled (rows, shards, "
                    "cached)",
    "hs.mesh.filter": "the distributed / SPMD filter",
    "hs.mesh.aggregate": "the distributed aggregate",
    "hs.mesh.build.dispatch": "the mesh build step's dispatch",
    "hs.mesh.join.spmd": "one attempt of the SPMD join: the match's "
                         "dispatch, the one readback and, where no "
                         "route overflowed, the expansion's dispatch "
                         "(how, shards; then pairs: the fullest shard's "
                         "total, and cap: the expansion's rung, 0 with "
                         "no pair)",
    "hs.mesh.join.sync": "the host blocked on the join's one readback, "
                         "the match's per-shard totals (attempt)",
    # ring only (recognised in hindsight, `completed`)
    "hs.compile.<name>": "a dispatch of jit entry point <name> that "
                         "traced + compiled",
}

# Names on the DEVICE: the scope every jitted program carries
# (`instrumented_jit(name, scope=...)`, through `device_scoped`), so an
# op's scope path in a capture (the `tf_op` of an `XLA Ops` event's
# metadata: `jit(hs_compact)/hs.compact/jit(hs_compact)/gather:`) says
# which piece it belongs to whatever implements it. An op with none was
# dispatched eagerly. Metadata only: no program computes anything else.
DEVICE_SCOPES = {
    "hs.predicate": "a filter predicate's mask, where a fused stage's "
                    "program traces it (an eager filter's mask has none)",
    "hs.segsum": "per-bucket survivor counts (the mask's prefix sum at "
                 "the buckets' ends)",
    "hs.compact": "mask -> survivor indices (rank select or sort select)",
    "hs.gather": "a batch's row gather: every column and validity "
                 "through one index vector (`jit__take_all`; on a mesh "
                 "`jit__take_flat`, `jit__take_flat_i32`)",
    "hs.stage": "a fused stage's own program (`jit__run`) and its "
                "deferred build-side gathers (`jit_run`); "
                "`hs.predicate` and an inlined `hs.join.broadcast` nest "
                "inside it",
    "hs.topk": "ORDER BY ... LIMIT's threshold over the packed sort "
               "prefix (`jit_run` of `sort.topk_threshold`)",
    "hs.sort": "ORDER BY's sort permutation (`jit__staged_perm_jit`)",
    "hs.join.match": "the counting join's match program, and the "
                     "bucketed join's key encode (`jit__encode_core`)",
    "hs.join.expand": "the counting join's expansion to row pairs",
    "hs.join.broadcast": "the broadcast join's direct-address probe "
                         "(`jit__broadcast_probe`, or inlined into a "
                         "fused stage's program)",
    "hs.setop": "INTERSECT / EXCEPT's membership sort (`jit__setop_core`)",
    "hs.aggregate": "the group-by's programs: the grouping sort "
                    "(`jit__group_phase_a`, `jit__group_phase_a_hashed`), "
                    "the exact integer moments of avg / stddev "
                    "(`jit__exact_moments`) and every other reduction "
                    "after the sort (`jit__group_finish`)",
    "hs.build": "an index build's hash and sort (`jit__build_core`, "
                "`jit__perm_core`) and a compaction's batched bucket "
                "sort (`jit__bucket_sort_core`)",
    "hs.sketch": "a data-skipping sketch's kernel (bloom, zone maps)",
    "hs.serve.batch": "the batched serve lane's stacked predicates "
                      "(`jit_body`)",
    # mesh: the SPMD programs, on every chip's plane
    "hs.mesh.filter": "the SPMD predicate mask (`jit_spmd_filter`)",
    "hs.mesh.join": "the SPMD join's two programs: the match "
                    "(`jit_spmd_join_match`) and the expansion sized by "
                    "its totals (`jit_spmd_join_expand`)",
    "hs.mesh.aggregate": "the per-shard partial aggregation "
                         "(`jit_aggregate_step`)",
    "hs.mesh.build": "the mesh build step (`jit_step`)",
    "hs.mesh.repartition": "the SPMD repartition of a side by bucket "
                           "(`jit_step`)",
}

_tracer: Optional["Tracer"] = None


class Tracer:
    def __init__(self, capacity: int = 200_000):
        self.capacity = capacity
        self.events: deque = deque(maxlen=capacity)
        self.emitted = 0
        self.t0_s = time.perf_counter()
        self.started_at = time.time()
        self._lock = threading.Lock()
        self._thread_names: Dict[int, str] = {}
        self._device_tracks: set = set()

    def now_us(self) -> float:
        return (time.perf_counter() - self.t0_s) * 1e6

    def complete(self, name: str, cat: str, ts_us: float, dur_us: float,
                 tid: Optional[int] = None, pid: int = PID_ENGINE,
                 args: Optional[dict] = None) -> None:
        """One Chrome "X" (complete) event. Same-thread spans nest by
        ts/dur containment — no explicit parent links needed."""
        if tid is None:
            tid = threading.get_ident()
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": round(ts_us, 1), "dur": round(max(dur_us, 0.0), 1),
              "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)
            self.emitted += 1

    def counter(self, name: str, values: Dict[str, float],
                pid: int = PID_ENGINE, tid: int = 0) -> None:
        """One Chrome "C" (counter) event: Perfetto renders each
        distinct `name` as its own counter track, plotting the numeric
        `values` series over time — the per-device HBM tracks the
        memory accountant emits (`telemetry/memory.py`)."""
        ev = {"name": name, "cat": "memory", "ph": "C",
              "ts": round(self.now_us(), 1), "pid": pid, "tid": tid,
              "args": {k: float(v) for k, v in values.items()}}
        with self._lock:
            self.events.append(ev)
            self.emitted += 1

    def instant(self, name: str, cat: str,
                args: Optional[dict] = None) -> None:
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": round(self.now_us(), 1), "pid": PID_ENGINE,
              "tid": tid}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)
            self.emitted += 1

    def device_spans(self, name: str, ts_us: float, rows_per_device,
                     cat: str = "mesh", **common) -> None:
        """One span per mesh device on the synthetic device process.
        SPMD dispatch gives every device the same wall window (the
        jitted step); the per-device ROW attribution in the span args is
        what exposes skew."""
        dur = self.now_us() - ts_us
        for d, rows in enumerate(rows_per_device):
            self._device_tracks.add(d)
            args = {"device": d, "rows": int(rows)}
            args.update(common)
            self.complete(f"{name} [dev{d}]", cat, ts_us, dur,
                          tid=d, pid=PID_MESH, args=args)

    def _metadata_events(self) -> List[dict]:
        out = [
            {"name": "process_name", "ph": "M", "ts": 0,
             "pid": PID_ENGINE, "tid": 0,
             "args": {"name": "hyperspace-engine"}},
        ]
        for tid, tname in sorted(self._thread_names.items()):
            out.append({"name": "thread_name", "ph": "M", "ts": 0,
                        "pid": PID_ENGINE, "tid": tid,
                        "args": {"name": tname}})
        if self._device_tracks:
            out.append({"name": "process_name", "ph": "M", "ts": 0,
                        "pid": PID_MESH, "tid": 0,
                        "args": {"name": "hyperspace-mesh"}})
            for d in sorted(self._device_tracks):
                out.append({"name": "thread_name", "ph": "M", "ts": 0,
                            "pid": PID_MESH, "tid": d,
                            "args": {"name": f"device {d}"}})
        return out

    def export(self, path: str) -> dict:
        with self._lock:
            events = list(self.events)
            emitted = self.emitted
        doc = {
            "traceEvents": self._metadata_events() + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "hyperspace_tpu.telemetry",
                "started_at": self.started_at,
                "events": len(events),
                "dropped": max(emitted - len(events), 0),
            },
        }
        from hyperspace_tpu.utils import file_utils
        file_utils.create_file(path, json.dumps(doc, default=str))
        return {"path": path, "events": len(events),
                "dropped": max(emitted - len(events), 0)}


def enable_tracing(capacity: int = 200_000) -> Tracer:
    """Install (or keep) the process tracer. Idempotent: an already
    running tracer is reused so concurrent enablers don't drop each
    other's spans."""
    global _tracer
    if _tracer is None:
        _tracer = Tracer(capacity)
    return _tracer


def disable_tracing() -> None:
    global _tracer
    _tracer = None


def tracing_enabled() -> bool:
    return _tracer is not None


def tracer() -> Optional[Tracer]:
    """The active tracer, or None — THE always-off check every hook
    makes first."""
    return _tracer


class span:
    """THE span seam: name the enclosed block on this thread, in both
    sinks — the ring (when `enable_tracing()` installed one) and the
    trace of whatever jax profiler session is running (`hs.*` host
    events on the profiler's clock, beside the device's ops). With
    neither, entering costs one global read and one C++ flag read and
    appends nothing anywhere.

        with telemetry.span("hs.stage.compact", "fusion", rows=n) as sp:
            ...
            sp.set(bytes=nbytes)   # what is known only at the end

    Every span carries the active query's identifier as `qid`: the one
    passed in (where no recorder is active: admission, the epilogue),
    else the QueryMetrics recorder's (which `telemetry.propagating`
    carries onto pool threads), else the enclosing span's. The span
    that caused a span is the one that contains it on its thread;
    across threads the `qid` is the link. An exception leaving the
    block is recorded as `error`. Names come from `SPAN_NAMES`."""

    __slots__ = ("name", "cat", "args", "_ring", "_ann", "_ts", "_outer")

    def __init__(self, name: str, cat: str = "engine", **args):
        self.name = name
        self.cat = cat
        self.args = args
        self._ring = self._ann = None
        self._outer = _OFF

    def __enter__(self):
        ring = _tracer
        live = _profiler.annotations_enabled()
        if ring is None and not live:
            return self
        args = self.args
        self._outer = getattr(_enclosing, "qid", None)
        if args.get("qid") is None:
            args["qid"] = _active_query_id() or self._outer
        _enclosing.qid = args["qid"]
        if live:
            self._ann = _profiler.annotation(self.name, **_plain(args))
        if ring is not None:
            self._ring = ring
            self._ts = ring.now_us()
        return self

    def set(self, **args) -> None:
        """Add arguments to an open span (no-op when nothing records)."""
        if self._ann is not None:
            self._ann.set_metadata(**_plain(args))
        if self._ring is not None:
            self.args.update(args)

    def __exit__(self, exc_type, exc, tb):
        if self._outer is _OFF:
            return False
        if exc is not None:
            self.set(error=repr(exc))
        ann, ring = self._ann, self._ring
        self._ann = self._ring = None
        _enclosing.qid, self._outer = self._outer, _OFF
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
        if ring is not None:
            args = {k: v for k, v in self.args.items() if v is not None}
            ring.complete(self.name, self.cat, self._ts,
                          ring.now_us() - self._ts, args=args or None)
        return False


def spans_active() -> bool:
    """Whether a span opened now would be recorded by either sink —
    for hooks that skip more than the span itself when nobody
    listens (the operator wrapper)."""
    return _tracer is not None or _profiler.annotations_enabled()


def completed(name: str, cat: str, seconds: float, **args) -> None:
    """A RING-ONLY event for work recognised in hindsight, ending now
    (a dispatch that turned out to compile: `telemetry/compilation.py`).
    A profiler annotation cannot be back-dated, and the profiler's
    trace has XLA's own compile events."""
    t = _tracer
    if t is not None:
        end = t.now_us()
        t.complete(name, cat, end - seconds * 1e6, seconds * 1e6,
                   args=args or None)


_ANNOTATION_ARG_CHARS = 120
_annotation_unsafe = str.maketrans({"#": ";", ",": ";", "=": ":",
                                    "\n": " "})


def _plain(args: dict) -> dict:
    """Arguments as a profiler annotation can carry them (the event's
    name is extended by `#k=v,k=v#`): numbers as they are, anything
    else as a short string without the separators; None left out."""
    out = {}
    for k, v in args.items():
        if v is None:
            continue
        if not isinstance(v, (int, float)):
            v = str(v)[:_ANNOTATION_ARG_CHARS].translate(_annotation_unsafe)
        out[k] = v
    return out


_current_recorder = None
_enclosing = threading.local()  # .qid of the innermost recorded span
_OFF = object()  # a span's `_outer` while nothing records it


def _active_query_id() -> Optional[str]:
    global _current_recorder
    if _current_recorder is None:
        from hyperspace_tpu import telemetry
        _current_recorder = telemetry.current
    rec = _current_recorder()
    return getattr(rec, "query_id", None) if rec is not None else None


def record_link_transfer(direction: str, nbytes: int, seconds: float,
                         chunks: int = 1) -> None:
    """Account one device-link transfer (`direction` = "h2d" | "d2h"):
    registry counters + log-bucketed byte/seconds histograms ALWAYS, a
    per-query counter when a recorder is active. The timeline side is
    `link_transfer` (or an `hs.link.<dir>` span of the caller's own
    where one accounting record covers several crossings, as in
    `io/columnar.to_arrow`). `chunks` is how many pipelined chunk puts
    the logical transfer shipped as (`io/transfer.py`) —
    `link.<dir>.chunks` vs `link.<dir>.transfers` is the chunking
    ratio. jax dispatch is asynchronous — the measured wall is
    dispatch-side unless the measuring code synced; the byte counts are
    exact either way."""
    reg = _registry.get_registry()
    reg.counter(f"link.{direction}.bytes").inc(nbytes)
    reg.counter(f"link.{direction}.seconds").inc(seconds)
    reg.counter(f"link.{direction}.transfers").inc()
    reg.counter(f"link.{direction}.chunks").inc(max(int(chunks), 1))
    reg.histogram(f"link.{direction}.bytes_per_transfer").observe(nbytes)
    from hyperspace_tpu import telemetry
    # Tenant chargeback at the ONE link seam: mirroring the global inc
    # here keeps per-tenant link-byte sums exactly equal to the global
    # `link.<dir>.bytes` counters.
    telemetry.charge_tenant(f"link.{direction}.bytes", nbytes)
    telemetry.add_seconds(f"link.{direction}_s", seconds)
    telemetry.add_count(f"link.{direction}_bytes", int(nbytes))
    # Every instrumented transfer moves device residency: fold a memory
    # sample (throttled; no-op unless a recorder or tracer is active).
    from hyperspace_tpu.telemetry import memory as _memory
    _memory.maybe_sample()


_LINK_SPANS = {"h2d": "hs.link.h2d", "d2h": "hs.link.d2h"}


class link_transfer:
    """One link crossing as a block: an `hs.link.<direction>` span
    around it and, on the way out, `record_link_transfer` with the
    block's wall. `chunks` may be set on the yielded object before the
    block ends (a chunked put learns its count as it goes)."""

    __slots__ = ("direction", "nbytes", "chunks", "_span", "_t0")

    def __init__(self, direction: str, nbytes: int, chunks: int = 1):
        self.direction = direction
        self.nbytes = nbytes
        self.chunks = chunks

    def __enter__(self):
        self._span = span(_LINK_SPANS[self.direction], "link",
                          direction=self.direction)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        seconds = time.perf_counter() - self._t0
        self._span.set(bytes=int(self.nbytes), chunks=int(self.chunks))
        self._span.__exit__(exc_type, exc, tb)
        record_link_transfer(self.direction, self.nbytes, seconds,
                             chunks=self.chunks)
        return False


def export_trace(path: str) -> dict:
    """Write the collected spans as Chrome trace-event JSON at `path`
    (loadable in chrome://tracing and ui.perfetto.dev). Returns
    {path, events, dropped}. Raises if tracing was never enabled —
    silently exporting an empty timeline would mask a missing
    `enable_tracing()` call."""
    t = _tracer
    if t is None:
        from hyperspace_tpu.exceptions import HyperspaceException
        raise HyperspaceException(
            "Tracing is not enabled; call telemetry.enable_tracing() "
            "before the work you want captured.")
    return t.export(path)
