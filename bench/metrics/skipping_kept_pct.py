"""The share of the lake's bytes that a query's scan read after the
data-skipping rewrite pruned its files, median over the window's
queries: the scan's bytes on disk (its operator record's
`bytes_scanned`) over those plus the bytes the rule pruned (its
skipping event's `bytes_pruned`, the query's `skipping.bytes_pruned`),
as the op keeps them on each record. None where no record holds both
(an op that keeps no skipping record)."""

import statistics


def compute(run):
    shares = [100.0 * r["bytes_scanned"]
              / (r["bytes_scanned"] + r["bytes_pruned"])
              for r in run["records"]
              if r.get("bytes_scanned") and "bytes_pruned" in r]
    return statistics.median(shares) if shares else None
