"""The join's share of the HBM roofline: the least time the chip could
take for the bytes an equi-join must move (`roofline.join_min_bytes`,
from row counts alone: each side's key read once, two index vectors
written) over the device time of the join's programs. The row counts
are the join's own inputs, from the query's metrics; a join from
`lineitem` to its `orders` yields one row per left row."""

from lib import layers, roofline


def compute(run):
    if "join" not in run["traffic"].get("programs", {}):
        return None
    s = layers.device_seconds_per_query(run, "join")
    join_rows = run["records"][0]["lanes"].get("join_rows")
    if s is None or not join_rows or None in join_rows[0]:
        return None
    left, right = join_rows[0]
    return roofline.share_pct(roofline.join_min_bytes(left, right, left), s,
                              run["device_kind"])
