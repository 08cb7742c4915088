"""Median per traced query of the program's own span `hs.plan.hybrid`,
summed over the query's join sides: what hybrid scan's rules spend, inside
`hs.plan.optimize`, holding each candidate index against the relation's
current files (the listing, a stamp of every captured file or the
signature over them). None where the program writes no such span."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.plan.hybrid",), inside=program_spans.QUERY)
