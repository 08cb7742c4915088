"""The program's own spans and device scopes, read from the traced run's
profiler trace.

The program writes a host event named `hs.<layer>.<what>` for every
layer boundary of a `collect` or a `create_index` into whatever
profiler session runs (its span seam, `hyperspace_tpu/telemetry/trace.py`:
`SPAN_NAMES`), each with the query's identifier as `qid`, and names the
pieces of its device programs with `jax.named_scope` (`DEVICE_SCOPES`:
`hs.compact`, `hs.segsum`, `hs.predicate`, `hs.join.match`,
`hs.join.expand`). This module re-reads the run's `.xplane.pb` for
them, once per run:

    load(run) -> {"spans": [(name, thread, start_s, dur_s, stats), ...]
                  "ops":   [(start_s, dur_s, scopes, name), ...]} | None

- host spans come through jax's `ProfileData`, every `hs.*` event of
  every host thread with its stats, on the clock of the bench's own
  spans (`trace_reduce`);
- device ops need the file itself: the scope path of an op
  (`jit(hs_compact)/hs.compact/scatter-add:`) is the `tf_op` stat of
  the op's EVENT METADATA, which `ProfileData` does not hand out (an
  `XLA Ops` event there has only its offset and duration, and its name
  is the HLO line). `read_device_ops` parses just that much of the
  protobuf: planes, the device planes' `XLA Ops` lines, their events'
  metadata ids, and those metadata's `tf_op`.

The trace is found under the run's work directory,
`<root>/.bench_work/<cell>/seed*/trace`; where there is not exactly one,
or the program wrote no such span (a parent commit without the seam),
every reader returns None, never 0, and the result line leaves its
metric out.
"""

from __future__ import annotations

import glob
import os
import statistics

from lib import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PREFIX = "hs."
QUERY = "bench.collect"
BUILD = "bench.build"

# The idle groups of a query, by span-name prefix; a gap under any other
# `hs.*` span is the execution's (`stage`), a gap under `hs.query` alone
# or under no program span at all is `unnamed`.
IDLE_GROUPS = ("serve", "plan", "stage", "out", "unnamed")
_GROUP_PREFIXES = (
    ("serve", ("hs.serve.",)),
    ("plan", ("hs.plan.",)),
    ("out", ("hs.link.", "hs.to_arrow")),
    ("stage", ("hs.op.", "hs.stage.", "hs.segcache.")),
)
_UNNAMED = ("hs.query",)

_parsed = {}  # xplane path -> {"spans": ..., "ops": ...}


def find_trace(cell: str, root=None):
    """The one xplane file of the cell's traced run, or None."""
    dirs = glob.glob(os.path.join(root or ROOT, ".bench_work", cell,
                                  "seed*", "trace"))
    if len(dirs) != 1:
        return None
    try:
        return trace_reduce.find_xplane(dirs[0])
    except FileNotFoundError:
        return None


def load(run, root=None):
    """The run's program spans and scoped device ops (parsed once per
    trace file), or None where the run was not traced or no one trace
    is found."""
    if not run.get("trace"):
        return None
    path = find_trace(run["cell"]["name"], root)
    if path is None:
        return None
    if path not in _parsed:
        _parsed[path] = parse(path)
    return _parsed[path]


def parse(path: str) -> dict:
    return {"spans": read_host_spans(path), "ops": read_device_ops(path)}


# -- host spans ----------------------------------------------------------


def read_host_spans(path: str):
    """Every host event named `hs.*`: (name, thread, start_s, dur_s,
    stats). `thread` numbers the host lines; a `qid` stat is the
    query's identifier."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    spans, thread = [], 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name, thread, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9, dict(e.stats)))
    return sorted(spans, key=lambda s: s[2])


# -- device ops, from the protobuf itself --------------------------------
#
# tsl/profiler/protobuf/xplane.proto, the fields read here:
#   XSpace          1 planes
#   XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata
#   XLine           2 name, 3 timestamp_ns, 4 events
#   XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
#   XEventMetadata  1 id, 2 name, 5 stats
#   XStatMetadata   1 id, 2 name
#   XStat           1 metadata_id, 5 str_value, 7 ref_value
# A map entry is a message of key (1) and value (2).


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def scopes_of(tf_op: str):
    """`jit(hs_compact)/hs.compact/scatter-add:` -> ("hs.compact",): the
    `hs.*` components of an op's scope path."""
    return tuple(part for part in tf_op.rstrip(":").split("/")
                 if part.startswith(PREFIX))


def read_device_ops(path: str):
    """Every op of every TPU plane's `XLA Ops` line: (start_s, dur_s,
    scopes, name), `scopes` from the op's `tf_op`."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_meta = "", [], [], []
        for n, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n == 4:
                event_meta.append(v)
            elif n == 5:
                stat_meta.append(v)
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for entry in stat_meta:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            if 1 in meta:
                stat_names[meta[1]] = _text(meta.get(2, b""))
        tf_op_ids = {i for i, n in stat_names.items() if n == "tf_op"}
        meta_of = {}  # metadata id -> (name, scopes)
        for entry in event_meta:
            op_name, op_id, tf_op = "", None, ""
            for n, v in _fields(dict(_fields(entry)).get(2, b"")):
                if n == 1:
                    op_id = v
                elif n == 2:
                    op_name = _text(v)
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op_ids:
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            meta_of[op_id] = (op_name, scopes_of(tf_op))
        for line in lines:
            fields = list(_fields(line))
            if _text(next((v for n, v in fields if n == 2),
                          b"")) != trace_reduce.OPS_LINE:
                continue
            t0_ns = next((v for n, v in fields if n == 3), 0)
            for n, event in fields:
                if n != 4:
                    continue
                e = dict(_fields(event))
                op_name, scopes = meta_of.get(e.get(1), ("", ()))
                ops.append(((t0_ns + e.get(2, 0) * 1e-3) * 1e-9,
                            e.get(3, 0) * 1e-12, scopes, op_name))
    return sorted(ops)


# -- what the metric files read ------------------------------------------


def _whole(run, name: str):
    """The bench's spans of `name` that lie whole in the traced window:
    [(start_s, end_s), ...]."""
    trace = run["trace"]
    lo, hi = trace["window"]
    return [(s, s + d) for n, _, s, d in trace["spans"]
            if n == name and s >= lo and s + d <= hi]


def span_ms(run, names, inside: str = QUERY):
    """Median, over the traced window's whole `inside` spans (queries,
    or builds), of the summed duration of the program spans named in
    `names` that lie inside each. None where the program wrote none."""
    found = load(run)
    if found is None:
        return None
    mine = [(s, s + d) for n, _, s, d, _ in found["spans"] if n in names]
    outer = _whole(run, inside)
    if not mine or not outer:
        return None
    return 1e3 * statistics.median(
        sum(e - s for s, e in mine if lo <= s and e <= hi)
        for lo, hi in outer)


def scope_device_ms(run, scope: str):
    """Median, over the traced window's whole queries, of the device
    seconds of the ops under the device scope `scope` that started in
    each. None where no op carries the scope."""
    found = load(run)
    if found is None:
        return None
    mine = [(s, d) for s, d, scopes, _ in found["ops"] if scope in scopes]
    queries = _whole(run, QUERY)
    if not mine or not queries:
        return None
    return 1e3 * statistics.median(
        sum(d for s, d in mine if lo <= s < hi) for lo, hi in queries)


def group_of(name) -> str:
    if name is None or name in _UNNAMED:
        return "unnamed"
    for group, prefixes in _GROUP_PREFIXES:
        if name.startswith(prefixes):
            return group
    return "stage"


def idle_by_group(busy, spans, lo: float, hi: float) -> dict:
    """The device's idle seconds inside [lo, hi) by group: every moment
    between the merged busy intervals `busy` goes to the innermost
    (shortest) program span that covers it, so a gap that runs across
    several spans is split at their boundaries."""
    near = [(ss, ss + d, d, n) for n, _, ss, d, _ in spans
            if ss < hi and ss + d > lo]
    inside = trace_reduce.clip(busy, lo, hi)
    edges = [lo] + [t for iv in inside for t in iv] + [hi]
    out = dict.fromkeys(IDLE_GROUPS, 0.0)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        cuts = sorted({s, e} | {t for ss, ee, _, _ in near
                                for t in (ss, ee) if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            covering = [(d, n) for ss, ee, d, n in near if ss <= mid < ee]
            out[group_of(min(covering)[1] if covering else None)] += b - a
    return out


def idle_ms(run, group: str):
    """The device's idle milliseconds, in the traced window's median
    query by host gap, that fell to `group`. The five groups of one
    query sum to its host gap (its latency minus the device's busy time
    inside it) by construction, so taking them from the median query
    (the mean of the two middle ones where the count is even) makes
    them sum to `host_gap_ms`."""
    found = load(run)
    trace = run["trace"]
    if found is None or not found["spans"] or not trace["chips"]:
        return None
    busy = trace_reduce.busy_all_chips(trace)
    per_query = sorted(
        (idle_by_group(busy, found["spans"], lo, hi)
         for lo, hi in _whole(run, QUERY)),
        key=lambda groups: sum(groups.values()))
    if not per_query:
        return None
    n = len(per_query)
    middle = per_query[(n - 1) // 2:n // 2 + 1]
    return 1e3 * sum(q[group] for q in middle) / len(middle)
