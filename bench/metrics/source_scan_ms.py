"""Median per traced query of the `hs.op.Scan` spans whose `source` is
the table the mix names under `"source_scan"` (q17: `catalog_sales`,
read from its Parquet files every query, with no index version and so
no segment reference): the listing and footers, the decode and the
placement on the device, or the cache that spares them. None where no
scan span says `source` (a program without the argument)."""

import statistics

from lib import program_spans


def compute(run):
    table = run["traffic"].get("source_scan")
    found = program_spans.load(run)
    if table is None or found is None:
        return None
    mine = [(s, s + d) for n, _, s, d, stats in found["spans"]
            if n == "hs.op.Scan" and stats.get("source") == table]
    queries = program_spans._whole(run, program_spans.QUERY)
    if not mine or not queries:
        return None
    return 1e3 * statistics.median(
        sum(e - s for s, e in mine if lo <= s and e <= hi)
        for lo, hi in queries)
