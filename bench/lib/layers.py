"""What several per-layer readers share: the device's idle share of the
traced window or of one kind of span, and the device seconds per traced
query of the programs that ran inside the bench's own `collect` spans.

Which programs make up one operator is data, not code: a traffic mix
may give `"programs": {"<operator>": "<regular expression>"}` over the
names the trace prints for the device's programs (XLA modules). Where a
mix gives no pattern for an operator, every program that ran inside the
query and that no other operator's pattern claims counts, so a program
that is renamed or replaced stays in sight.
"""

from __future__ import annotations

import re

from lib import trace_reduce

COLLECT = "bench.collect"


def _trace(run):
    trace = run["trace"]
    return trace if trace and trace["chips"] else None


def idle_pct(run, span_names=None):
    """Idle share of the traced window; with `span_names`, of the time
    inside the bench's spans of those names that lie whole in it."""
    trace = _trace(run)
    if trace is None:
        return None
    lo, hi = trace["window"]
    if span_names is None:
        return 100.0 * (1.0 - trace["busy_s"] / (hi - lo)) if hi > lo else None
    busy = trace_reduce.busy_all_chips(trace)
    inside = [(s, s + d) for name, _, s, d in trace["spans"]
              if name in span_names and s >= lo and s + d <= hi]
    seconds = sum(e - s for s, e in inside)
    if seconds <= 0:
        return None
    return 100.0 * (1.0 - sum(trace_reduce.busy_within(busy, s, e)
                              for s, e in inside) / seconds)


def traced_queries(trace):
    """The `collect` spans that lie whole in the traced window."""
    lo, hi = trace["window"]
    return [(s, s + d) for name, _, s, d in trace["spans"]
            if name == COLLECT and s >= lo and s + d <= hi]


def device_seconds_per_query(run, operator=None):
    """Device seconds per traced query of the programs that started
    inside it: all of them (`operator` None), those whose name matches
    the mix's pattern for `operator`, or, where the mix has none for
    it, those that no other operator's pattern matches. None where no
    such program ran: a reader then leaves its metric out, and never
    reports 0."""
    trace = _trace(run)
    if trace is None:
        return None
    queries = traced_queries(trace)
    patterns = run["traffic"].get("programs", {})
    mine = re.compile(patterns[operator]) if operator in patterns else None
    others = [re.compile(p) for o, p in patterns.items()
              if operator and o != operator and mine is None]

    def counts(name: str) -> bool:
        if mine is not None:
            return bool(mine.search(name))
        return not any(rx.search(name) for rx in others)

    seconds = sum(
        d for name, s, d in trace["programs"]
        if counts(name) and any(lo <= s < hi for lo, hi in queries))
    return seconds / len(queries) if queries and seconds > 0 else None
