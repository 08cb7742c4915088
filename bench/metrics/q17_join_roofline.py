"""q17's counting joins' share of the HBM roofline: the least time the
chip could take for the bytes that any equi-join of their shapes must
move, over the device time of their programs (`join_device_ms`: the
programs the mix names under `"programs": {"join": ...}`, the hashed
match and the expansion, for the three-key index join and the two-key
join to catalog_sales alike).

Which joins those are is the program's own word, per query: the join
operators whose record says `match` (`hashed`, `exact` or
`hashed-fallback`: the device counting join of `ops/join.py` ran). The
bytes are counted here from shapes alone (`roofline.join_min_bytes`):
each side's keys read once (8 bytes a key column: three for the index
join, two for the join to catalog_sales) and a pair of index vectors
written for every pair placed (the operator's output rows). Bytes of
one traced query (the first record's) over the median query's device
time. None where there is no device plane or no such join; never 0."""

import os

from lib import plugins, roofline

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY_BYTES = 8


def least_bytes(joins: list) -> int:
    """Bytes of the joins whose record says `match` and holds every
    count (a program that records neither gives 0)."""
    shapes = ("keys", "left_rows", "right_rows", "rows")
    return sum(roofline.join_min_bytes(int(j["left_rows"]),
                                       int(j["right_rows"]), int(j["rows"]),
                                       key_bytes=KEY_BYTES * int(j["keys"]))
               for j in joins
               if j.get("match") and all(j.get(k) is not None
                                         for k in shapes))


def compute(run):
    ms = plugins.load(_BENCH, "metrics", "join_device_ms").compute(run)
    n_bytes = least_bytes(run["records"][0].get("q17", {}).get("joins", ()))
    if not ms or not n_bytes:
        return None
    return roofline.share_pct(n_bytes, ms * 1e-3, run["device_kind"])
