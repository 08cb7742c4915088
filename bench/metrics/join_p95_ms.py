"""95th percentile of `DataFrame.collect(...)` to the Arrow table in
hand over the window's queries, in a cell whose window holds about a
dozen of them: in effect the slowest. A per-layer reading, because a
dozen values make no tail to hold a bound. Host clock."""

import numpy as np


def compute(run):
    ms = [1e3 * (r["end"] - r["start"]) for r in run["records"]]
    return float(np.percentile(ms, 95)) if ms else None
