"""The SPMD join's share of the chips' HBM roofline: the least time for
the bytes ANY equi-join of this query must move
(`roofline.join_min_bytes`: the filter's survivors and the orders' keys
read once, the pairs' two index vectors written; the same count
`join_roofline` uses on one chip) over the join programs' device
seconds SUMMED over the chips. Bytes over one chip's peak over summed
chip-seconds is the share of all the chips' peaks together: n chips
could do it in 1/n of the time, and each ran 1/n of the sum.

The survivors are the answer's own two counts summed (Q12 counts every
line that passed the filter and found its order, and every line has
one); the pairs are as many. The first traced record as a rule holds no
table of its own: its answer repeated the bytes of the first answer to
the same parameters (`ops/select.settle`), whose record `same_as`
points at, and that one's table is read."""

from lib import layers, roofline


def survivors(run):
    rec = run["records"][0]
    answer = rec.get("same_as", rec).get("answer")
    if answer is None:
        return None
    return sum(sum(answer.column(c).to_pylist())
               for c in ("high_line_count", "low_line_count"))


def compute(run):
    if "join" not in run["traffic"].get("programs", {}):
        return None
    chip_seconds = layers.device_seconds_per_query(run, "join")
    lines = survivors(run)
    if chip_seconds is None or not lines:
        return None
    n_bytes = roofline.join_min_bytes(lines, run["rows"]["orders"], lines)
    return roofline.share_pct(n_bytes, chip_seconds, run["device_kind"])
