"""Per traced query, its latency minus the time an op ran on the device
inside it: what the host adds around the device's work (median). Both
from the profiler's clock: the `bench.collect` span and the device's op
intervals."""

import statistics

from lib import trace_reduce


def compute(run):
    trace = run["trace"]
    if not trace or not trace["chips"]:
        return None
    lo, hi = trace["window"]
    busy = trace_reduce.busy_all_chips(trace)
    gaps = [1e3 * (d - trace_reduce.busy_within(busy, s, s + d))
            for name, _, s, d in trace["spans"]
            if name == "bench.collect" and s >= lo and s + d <= hi]
    return statistics.median(gaps) if gaps else None
