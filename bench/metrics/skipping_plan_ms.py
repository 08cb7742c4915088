"""Median per traced query of the program's own span `hs.plan.skipping`,
summed over the query: what the filter rule spends, inside
`hs.plan.optimize`, loading a data-skipping index's sketch blob (cached
per version) and holding the scan's files against it, one `stat` a file
for its live stamp. None where the program writes no such span."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.plan.skipping",), inside=program_spans.QUERY)
