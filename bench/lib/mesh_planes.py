"""What the four-chip readers share: the traced run's device planes one
by one. `trace_reduce.reduce` keeps each plane's busy intervals
(`busy`) but sums programs and ops over the planes; a reader that asks
which chip was busiest, or what one chip spent in collectives, needs
the ops of each plane with their names, re-read here from the run's
`.xplane.pb` with jax's own `ProfileData` (an `XLA Ops` event's name is
its HLO line, which is all a collective is told by).

Every function returns None where the run was not traced, no one trace
is found or no device plane ran anything: a reader then leaves its
metric out.
"""

from __future__ import annotations

import re

from lib import layers, program_spans, trace_reduce

# HLO op names of the collectives XLA puts between chips (async pairs
# end in -start / -done), at the head of an op's HLO line.
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast)\b")

_ops = {}  # xplane path -> {plane: [(name, start_s, dur_s), ...]}


def read_plane_ops(path: str) -> dict:
    """{plane name: [(op name, start_s, dur_s), ...]} of every device
    plane's `XLA Ops` line."""
    import jax.profiler

    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                out[plane.name] = [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events]
    return out


def plane_ops(run, root=None):
    trace = run.get("trace")
    if not trace or not trace["chips"]:
        return None
    path = program_spans.find_trace(run["cell"]["name"], root)
    if path is None:
        return None
    if path not in _ops:
        _ops[path] = read_plane_ops(path)
    return _ops[path] or None


def busy_per_plane(run):
    """{plane: seconds an op ran on it inside the traced window's whole
    queries}, for the planes that ran anything; None without them."""
    trace = run.get("trace")
    if not trace or not trace["chips"]:
        return None
    queries = layers.traced_queries(trace)
    busy = {plane: sum(trace_reduce.busy_within(intervals, lo, hi)
                       for lo, hi in queries)
            for plane, intervals in trace["busy"].items() if intervals}
    return busy if queries and any(busy.values()) else None


def busiest_plane(run):
    busy = busy_per_plane(run)
    return max(busy, key=busy.get) if busy else None


def collective_seconds_per_query(run, root=None):
    """On the busiest chip, the seconds of collective ops that started
    inside a traced query, per traced query. 0.0 where that chip ran
    ops and none of them was a collective."""
    ops, plane = plane_ops(run, root), busiest_plane(run)
    if ops is None or plane is None or plane not in ops:
        return None
    queries = layers.traced_queries(run["trace"])
    seconds = sum(d for name, s, d in ops[plane]
                  if COLLECTIVE.match(name)
                  and any(lo <= s < hi for lo, hi in queries))
    return seconds / len(queries)
