"""The skipping-served filter-aggregate stage's share of the HBM
roofline: the rows its scan actually read (the median of the records'
`rows_scanned`: the surviving files', not the table's) x bytes of the
columns its predicate and sum read, plus its one-row output read and
written (`roofline.stage_min_bytes`), over the device time per traced
query of the ops under the scopes of the stage's programs (its fused
stage, compaction, gathers and sum), read op by op from the device
trace. The mix says which columns (`"roofline": {"stage": {"reads",
"returns"}}`) and which scopes (`"scopes"`). None where nothing ran
under those scopes (no device plane, or a program without them)."""

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
    stage = run["traffic"].get("roofline", {}).get("stage")
    rows = [r["rows_scanned"] for r in run["records"]
            if r.get("rows_scanned")]
    if not stage or not rows or not stage.get("scopes"):
        return None
    s = scoped_seconds(run, stage["scopes"])
    if not s:
        return None

    def width(columns):
        return sum(roofline.column_bytes(c, run["dataset"]) for c in columns)

    n_bytes = roofline.stage_min_bytes(
        int(statistics.median(rows)), width(stage["reads"]), 1,
        width(stage["returns"]))
    return roofline.share_pct(n_bytes, s, run["device_kind"])
