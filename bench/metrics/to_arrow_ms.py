"""Median per traced query of the program's own span `hs.to_arrow`: the
answer's device-to-host fetches and its Arrow encoding."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.to_arrow",), inside=program_spans.QUERY)
