"""Median per traced query of the program's own spans `hs.serve.admit`
(footprint projection, routing, the recorder, the queue and admission)
plus `hs.serve.credit` (footprint re-projection and residency credit):
what the serving plane spends before a query executes (program spans on
the profiler's clock)."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.serve.admit", "hs.serve.credit"), inside=program_spans.QUERY)
