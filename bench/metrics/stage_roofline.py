"""The filter + project stage's share of the HBM roofline: rows x bytes
of the columns the predicate reads, plus the rows it keeps x bytes of
the columns it hands on, read and written (`roofline.stage_min_bytes`),
over the device time of the stage's programs. The mix says which
columns (`"roofline": {"stage": {"table", "reads", "returns"}}`); the
rows kept are the join's left input where the stage feeds a join, else
the query's answer."""

from lib import layers, roofline


def compute(run):
    stage = run["traffic"].get("roofline", {}).get("stage")
    s = layers.device_seconds_per_query(run, "stage")
    if s is None or not stage:
        return None
    first = run["records"][0]
    join_rows = first["lanes"].get("join_rows")
    kept = join_rows[0][0] if join_rows else first["rows"]

    def width(columns):
        return sum(roofline.column_bytes(c, run["dataset"]) for c in columns)

    n_bytes = roofline.stage_min_bytes(
        run["rows"][stage["table"]], width(stage["reads"]), kept,
        width(stage["returns"]))
    return roofline.share_pct(n_bytes, s, run["device_kind"])
