"""Median of the bench's own span around `session.optimize` +
`compile_plan` for the cell's query (traced runs plan once more per
query for this, outside the query's latency). Host clock."""

import statistics


def compute(run):
    d = run["spans"].durations("plan", lo=run["window"]["start"])
    return 1e3 * statistics.median(d) if d else None
