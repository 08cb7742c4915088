"""Median per traced query of the `hs.op.Scan` spans that carry
`appended` (the files hybrid scan reads beside the index), summed over
the query's branches: the listing's stamps, the decoded-batch cache or a
Parquet decode, and a placement where the lane is the device's. None
where no scan says `appended` (a program without the argument, or a
query that fell off hybrid scan)."""

import statistics

from lib import program_spans


def compute(run):
    found = program_spans.load(run)
    if found is None:
        return None
    mine = [(s, s + d) for n, _, s, d, stats in found["spans"]
            if n == "hs.op.Scan" and "appended" in stats]
    queries = program_spans._whole(run, program_spans.QUERY)
    if not mine or not queries:
        return None
    return 1e3 * statistics.median(
        sum(e - s for s, e in mine if lo <= s and e <= hi)
        for lo, hi in queries)
