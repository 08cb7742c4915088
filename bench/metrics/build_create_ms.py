"""Median of the bench's own span around `Hyperspace.create_index` in the
window's build cycles (host clock)."""

import statistics


def compute(run):
    d = run["spans"].durations("build", lo=run["window"]["start"])
    return 1e3 * statistics.median(d) if d else None
