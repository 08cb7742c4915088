"""Per traced query, on the busiest chip, the time of collective ops
(all-reduce, all-gather, all-to-all, reduce-scatter,
collective-permute, told by HLO op name; device trace). The SPMD join
is shuffle-free by design, so this is what the gathers across shards
and the overflow reductions cost."""

from lib import mesh_planes


def compute(run):
    seconds = mesh_planes.collective_seconds_per_query(run)
    return None if seconds is None else 1e3 * seconds
