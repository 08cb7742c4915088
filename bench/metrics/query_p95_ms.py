"""95th percentile of `DataFrame.collect(...)` to the Arrow table in
hand, over all queries of the window. Host clock."""

import numpy as np


def compute(run):
    ms = [1e3 * (r["end"] - r["start"]) for r in run["records"]]
    return float(np.percentile(ms, 95)) if ms else None
