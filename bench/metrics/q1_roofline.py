"""TPC-H Q1's share of the HBM roofline: the bytes any implementation has
to move, which are the covering index's columns over ALL of its rows
(the table's: every line is in the index) at their stored widths, read
once, plus the report's rows read and written (`roofline.stage_min_bytes`),
over the device time per traced query of the ops under the mix's scopes
(the fused stage, its predicate, the aggregate, and any compaction or
gather a program makes), read op by op from the device trace, so the
share does not depend on which program the work was fused into. The
mix says which columns (`"roofline": {"q1": {"table", "reads",
"returns", "out_rows"}}`) and which scopes (`"scopes"`). None where
nothing ran under those scopes (no device plane, or a program without
them)."""

import statistics

from lib import program_spans, roofline


def scoped_seconds(run, scopes):
    """Median over the traced window's whole queries of the device
    seconds of the ops under any of `scopes` that started in each, or
    None."""
    found = program_spans.load(run)
    if found is None:
        return None
    mine = [(s, d) for s, d, names, _ in found["ops"]
            if any(scope in names for scope in scopes)]
    queries = program_spans._whole(run, program_spans.QUERY)
    if not mine or not queries:
        return None
    return statistics.median(
        sum(d for s, d in mine if lo <= s < hi) for lo, hi in queries)


def compute(run):
    q1 = run["traffic"].get("roofline", {}).get("q1")
    if not q1 or not q1.get("scopes"):
        return None
    s = scoped_seconds(run, q1["scopes"])
    if not s:
        return None

    def width(columns):
        return sum(roofline.column_bytes(c, run["dataset"]) for c in columns)

    n_bytes = roofline.stage_min_bytes(
        run["rows"][q1["table"]], width(q1["reads"]), q1["out_rows"],
        width(q1["returns"]))
    return roofline.share_pct(n_bytes, s, run["device_kind"])
