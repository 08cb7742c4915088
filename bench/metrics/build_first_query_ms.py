"""Median of the first `collect` through a freshly built index: plan,
the index's load into the segment cache, the stage, Arrow out (the
bench's own span, host clock)."""

import statistics


def compute(run):
    if not any("built" in r for r in run["records"]):
        return None
    d = run["spans"].durations("collect", lo=run["window"]["start"])
    return 1e3 * statistics.median(d) if d else None
