"""Median per traced query of the program's own spans `hs.plan.optimize`
(the rewrite rules) plus `hs.plan.compile` (physical planning and fusion
grouping) INSIDE the query: the planning that the query's latency
holds. (`plan_ms` times a second planning call outside it.)"""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.plan.optimize", "hs.plan.compile"), inside=program_spans.QUERY)
