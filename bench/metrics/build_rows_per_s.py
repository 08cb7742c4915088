"""Source rows indexed by the builds completed in the window over the
time those builds took: from the start of each cycle (the drop and
vacuum of the previous index) to the return of `create_index`. The
query that follows each build, which stands for "readable" and drives
the device in the traced window, is not build time: it is the per-layer
`build_first_query_ms`. One caller, back to back, so there is no other
time in the window. Host clock."""


def compute(run):
    built = [r for r in run["records"] if "built" in r]
    seconds = sum(r["built"] - r["start"] for r in built)
    rows = sum(r["rows_indexed"] for r in built)
    return rows / seconds if seconds > 0 and rows else None
