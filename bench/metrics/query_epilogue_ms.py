"""Median per traced query of the program's own span `hs.serve.finish`:
everything `scheduler.collect` does after execution and before the
answer leaves as Arrow (metrics.finish, critical-path stamp, registry and
SLO updates, index-usage mining, flight ring)."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.serve.finish",), inside=program_spans.QUERY)
