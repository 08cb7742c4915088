"""Regression attribution: diff two `QueryMetrics` trees and decompose
the wall-clock delta into attributed buckets.

The operator's use is a flight-recorder slow-query dump against a live
re-run of the same query (`telemetry/flight.py`: the dump carries the
full tree): `diff_trees(doc["metrics"], live.to_dict())` aligns
operator nodes by tree path and splits the wall delta into:

- `compute`   — per-operator self-time movement net of link/compile/
                device-dispatch (node-level deltas ride in the bucket
                detail) — i.e. host-side OVERHEAD;
- `device_bound` — measured warm jit-dispatch seconds
                (`device.dispatch_s`, the device half of the
                device-bound-vs-overhead split), with the modeled XLA
                cost movement (`device.{flops,bytes_accessed}`) as
                evidence;
- `link`      — H2D/D2H seconds from the per-query `link.{h2d,d2h}_s`
                counters (the transfer engine's chunk counters ride
                along as evidence);
- `compile`   — `compile.seconds` movement + the retrace-cause events
                of the new run;
- `plan`      — optimizer/planning seconds (`plan_s`);
- `cache`     — cache-behavior evidence: per-query
                `cache.<name>.{hits,misses,evictions}` deltas. Counted
                in events, not seconds — the seconds a miss costs
                already land in compute/link, so attributing them here
                too would double-count;
- `fallback`  — resilience degradation events (`resilience.fallbacks`,
                `degraded`); evidence, not seconds, same reason;
- `cancellation` — serving-plane interruptions: deadline/cancel events
                with the phase they interrupted
                (`serve.interrupted.<phase>` counters), so timeout
                clusters name their phase instead of landing in
                residual;
- `residual`  — whatever the telemetry cannot attribute.

Buckets are ranked by attributed magnitude; `QueryDiff.dominant` names
the biggest. `tests/test_tree_diff.py` and
`tests/test_flight_recorder.py::test_slow_dump_round_trip_and_diff`
hold the attribution.
"""

from __future__ import annotations

from typing import Dict, List, Optional

__all__ = ["Bucket", "QueryDiff", "diff_trees"]

# Evidence-only buckets attribute counts, never seconds (their cost is
# already inside compute/link); they rank below any timed bucket.
_EVIDENCE_BUCKETS = ("cache", "fallback", "cancellation")


class Bucket:
    """One attributed slice of a wall-clock delta."""

    __slots__ = ("name", "seconds", "detail")

    def __init__(self, name: str, seconds: float,
                 detail: Optional[dict] = None):
        self.name = name
        self.seconds = float(seconds)
        self.detail = detail or {}

    def to_dict(self) -> dict:
        d = {"name": self.name, "seconds": round(self.seconds, 4)}
        if self.detail:
            d["detail"] = self.detail
        return d


def _rollup(block) -> Optional[dict]:
    """Normalize a telemetry block into one comparable shape.

    Accepts a full `QueryMetrics.to_dict()` tree (operators as a LIST
    of records with parent links — node alignment possible), a
    `summary()` digest (operators as a per-name rollup dict), or a
    `QueryMetrics` instance. Returns
    {wall, per_op: {name: self_s}, nodes: {path: self_s} | None,
     counters, events} or None when there is nothing to roll up."""
    if block is None:
        return None
    if hasattr(block, "to_dict"):  # live QueryMetrics
        block = block.to_dict()
    if not isinstance(block, dict):
        return None
    ops = block.get("operators")
    counters = dict(block.get("counters") or {})
    events = list(block.get("events") or [])
    wall = block.get("wall_s")
    if isinstance(ops, list):
        # Tree form: self time = wall minus direct children's walls.
        child_s: Dict[Optional[int], float] = {}
        for op in ops:
            child_s[op.get("parent_id")] = \
                child_s.get(op.get("parent_id"), 0.0) \
                + float(op.get("wall_s") or 0.0)
        per_op: Dict[str, float] = {}
        nodes: Dict[str, float] = {}
        # Path = name#occurrence under the parent — stable across runs
        # of the same plan, insensitive to op_id numbering.
        paths: Dict[Optional[int], str] = {None: ""}
        sibling_seen: Dict[tuple, int] = {}
        for op in ops:
            parent = op.get("parent_id")
            name = op.get("name", "?")
            k = (parent, name)
            idx = sibling_seen.get(k, 0)
            sibling_seen[k] = idx + 1
            path = f"{paths.get(parent, '?')}/{name}#{idx}"
            paths[op.get("op_id")] = path
            self_s = max(float(op.get("wall_s") or 0.0)
                         - child_s.get(op.get("op_id"), 0.0), 0.0)
            per_op[name] = per_op.get(name, 0.0) + self_s
            nodes[path] = nodes.get(path, 0.0) + self_s
        return {"wall": wall, "per_op": per_op, "nodes": nodes,
                "counters": counters, "events": events}
    if isinstance(ops, dict):  # summary form
        per_op = {name: float(ent.get("self_s") or 0.0)
                  for name, ent in ops.items()}
        return {"wall": wall, "per_op": per_op, "nodes": None,
                "counters": counters, "events": events}
    if counters or wall is not None:
        return {"wall": wall, "per_op": {}, "nodes": None,
                "counters": counters, "events": events}
    return None


def _counter(roll: Optional[dict], *names: str) -> float:
    if not roll:
        return 0.0
    return sum(float(roll["counters"].get(n, 0.0)) for n in names)


def _cache_deltas(old: Optional[dict], new: Optional[dict]) -> dict:
    out: Dict[str, float] = {}
    keys = set()
    for roll in (old, new):
        if roll:
            keys.update(k for k in roll["counters"]
                        if k.startswith("cache."))
    for k in sorted(keys):
        d = _counter(new, k) - _counter(old, k)
        if d:
            out[k] = round(d, 4)
    return out


class QueryDiff:
    """Attribution of ONE aligned query's wall-clock delta."""

    def __init__(self, name: str, old_wall: Optional[float],
                 new_wall: Optional[float]):
        self.name = name
        self.old_wall = old_wall
        self.new_wall = new_wall
        self.buckets: List[Bucket] = []
        self.notes: List[str] = []

    @property
    def delta(self) -> Optional[float]:
        if self.old_wall is None or self.new_wall is None:
            return None
        return self.new_wall - self.old_wall

    @property
    def ratio(self) -> Optional[float]:
        if not self.old_wall or self.new_wall is None:
            return None
        return self.new_wall / self.old_wall

    def ranked(self) -> List[Bucket]:
        timed = [b for b in self.buckets
                 if b.name not in _EVIDENCE_BUCKETS]
        evid = [b for b in self.buckets if b.name in _EVIDENCE_BUCKETS]
        timed.sort(key=lambda b: -abs(b.seconds))
        return timed + evid

    @property
    def dominant(self) -> Optional[str]:
        """Largest attributed bucket, or None when nothing moved."""
        for b in self.ranked():
            if abs(b.seconds) > 1e-9:
                return b.name
        return None

    def to_dict(self) -> dict:
        return {
            "query": self.name,
            "old_wall_s": self.old_wall,
            "new_wall_s": self.new_wall,
            "delta_s": (round(self.delta, 4)
                        if self.delta is not None else None),
            "ratio": (round(self.ratio, 3)
                      if self.ratio is not None else None),
            "dominant": self.dominant,
            "buckets": [b.to_dict() for b in self.ranked()],
            "notes": list(self.notes),
        }


def _attribute_from_rollups(qd: QueryDiff, old: Optional[dict],
                            new: Optional[dict]) -> None:
    """Telemetry-based decomposition. Sums exactly:
    delta = plan + compute + link + compile + device_bound + residual
    (compute is the operator self-time movement net of the link/
    compile/device-dispatch seconds that happened inside operators —
    no double counting; what remains in `compute` is host-side
    overhead, the other half of the device-bound-vs-overhead split)."""
    link_d = (_counter(new, "link.h2d_s", "link.d2h_s")
              - _counter(old, "link.h2d_s", "link.d2h_s"))
    compile_d = (_counter(new, "compile.seconds")
                 - _counter(old, "compile.seconds"))
    device_d = (_counter(new, "device.dispatch_s")
                - _counter(old, "device.dispatch_s"))
    plan_d = _counter(new, "plan_s") - _counter(old, "plan_s")
    self_d = (sum((new or {}).get("per_op", {}).values())
              - sum((old or {}).get("per_op", {}).values()))
    compute_d = self_d - link_d - compile_d - device_d
    delta = qd.delta if qd.delta is not None else self_d + plan_d
    residual = delta - plan_d - self_d

    compute_detail: dict = {}
    old_nodes = (old or {}).get("nodes")
    new_nodes = (new or {}).get("nodes")
    if old_nodes is not None and new_nodes is not None:
        moves = {p: round(new_nodes.get(p, 0.0) - old_nodes.get(p, 0.0), 4)
                 for p in set(old_nodes) | set(new_nodes)}
        top = sorted(moves.items(), key=lambda kv: -abs(kv[1]))[:5]
        compute_detail["top_node_deltas"] = {p: d for p, d in top if d}
    else:
        per = {n: round((new or {}).get("per_op", {}).get(n, 0.0)
                        - (old or {}).get("per_op", {}).get(n, 0.0), 4)
               for n in set((old or {}).get("per_op", {}))
               | set((new or {}).get("per_op", {}))}
        top = sorted(per.items(), key=lambda kv: -abs(kv[1]))[:5]
        compute_detail["top_operator_deltas"] = {n: d for n, d in top if d}

    link_detail = {}
    for k in ("link.h2d_bytes", "link.d2h_bytes"):
        d = _counter(new, k) - _counter(old, k)
        if d:
            link_detail[k] = int(d)
    compile_detail: dict = {
        "traces": int(_counter(new, "compile.traces")
                      - _counter(old, "compile.traces"))}
    retraces = [e for e in (new or {}).get("events", [])
                if e.get("category") == "compile"
                and e.get("name") == "retrace"
                and e.get("cause") != "first trace"]
    if retraces:
        compile_detail["retrace_causes"] = [
            {"target": e.get("target"), "cause": e.get("cause")}
            for e in retraces[:5]]

    # Device-bound vs overhead: the measured warm-dispatch seconds the
    # instrumented jits charged (`device.dispatch_s`) move in their own
    # bucket, with the MODELED cost movement (XLA cost_analysis flops /
    # bytes) as evidence — "the chip did 2x the flops" and "the chip
    # did the same flops slower" are different regressions.
    device_detail: dict = {}
    for k in ("device.flops", "device.bytes_accessed"):
        d = _counter(new, k) - _counter(old, k)
        if d:
            device_detail[k] = round(d, 1)

    qd.buckets.append(Bucket("compute", compute_d, compute_detail))
    qd.buckets.append(Bucket("link", link_d, link_detail))
    qd.buckets.append(Bucket("compile", compile_d, compile_detail))
    qd.buckets.append(Bucket("device_bound", device_d, device_detail))
    qd.buckets.append(Bucket("plan", plan_d))
    qd.buckets.append(Bucket("residual", residual))

    caches = _cache_deltas(old, new)
    qd.buckets.append(Bucket("cache", 0.0, caches or {}))
    fallbacks = int(_counter(new, "resilience.fallbacks")
                    - _counter(old, "resilience.fallbacks"))
    degraded = [e for e in (new or {}).get("events", [])
                if e.get("category") == "resilience"]
    qd.buckets.append(Bucket(
        "fallback", 0.0,
        {"fallbacks": fallbacks,
         "events": degraded[:3]} if (fallbacks or degraded) else {}))

    # Serving-plane interruptions: a deadline/cancellation event is
    # recorded WITH the phase it interrupted (scan/operator/stage/
    # transfer/write — `serve.interrupted.<phase>` counters + `serve`
    # events), so a cluster of timeouts attributes to its phase bucket
    # here instead of polluting `residual` — "q64 times out in
    # transfer" is actionable, "q64 got slower somehow" is not.
    serve_detail: dict = {}
    phases = {}
    for roll, sign in ((old, -1), (new, +1)):
        for k, v in ((roll or {}).get("counters") or {}).items():
            if k.startswith("serve.interrupted."):
                phase = k.split(".", 2)[2]
                phases[phase] = phases.get(phase, 0) + sign * int(v)
    phases = {p: d for p, d in phases.items() if d}
    if phases:
        serve_detail["interrupted_by_phase"] = phases
    serve_events = [e for e in (new or {}).get("events", [])
                    if e.get("category") == "serve"
                    and e.get("name") in ("cancelled",
                                          "deadline_exceeded",
                                          "rejected")]
    if serve_events:
        serve_detail["events"] = serve_events[:3]
    qd.buckets.append(Bucket("cancellation", 0.0, serve_detail))


def _tree_critpath(tree) -> Optional[dict]:
    if isinstance(tree, dict):
        return tree.get("critical_path")
    return getattr(tree, "critical_path", None)


def diff_trees(old_tree, new_tree, name: str = "query") -> QueryDiff:
    """Diff two `QueryMetrics` trees (instances or `to_dict()` dicts)
    directly — e.g. a flight-recorder dump against a live re-run.
    When both trees carry a stamped critical-path decomposition
    (`telemetry/critical_path.py`), the biggest segment movements ride
    along as a note: the differ's bucket attribution and the anatomy's
    closed-set view of the same delta, side by side."""
    old_roll = _rollup(old_tree)
    new_roll = _rollup(new_tree)
    qd = QueryDiff(name,
                   (old_roll or {}).get("wall"),
                   (new_roll or {}).get("wall"))
    _attribute_from_rollups(qd, old_roll, new_roll)
    old_cp, new_cp = _tree_critpath(old_tree), _tree_critpath(new_tree)
    if old_cp and new_cp:
        deltas = {
            seg: (new_cp.get("segments", {}).get(seg, 0.0)
                  - old_cp.get("segments", {}).get(seg, 0.0))
            for seg in (set(old_cp.get("segments", {}))
                        | set(new_cp.get("segments", {})))}
        movers = sorted(deltas.items(), key=lambda kv: -abs(kv[1]))[:3]
        if movers and any(abs(d) > 1e-9 for _, d in movers):
            qd.notes.append(
                "critical path moved: " + ", ".join(
                    f"{seg} {d:+.4f}s" for seg, d in movers
                    if abs(d) > 1e-9))
    return qd
