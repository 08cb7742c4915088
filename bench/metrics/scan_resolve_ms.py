"""Median per traced query of the program's own span `hs.scan.resolve`,
summed over the query's scans: what the scans spend learning their
files, per-bucket rows, row total and bytes before they ask the segment
cache for anything (from the memo kept with a committed version's
segments, or from the listing and the Parquet footers)."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.scan.resolve",), inside=program_spans.QUERY)
