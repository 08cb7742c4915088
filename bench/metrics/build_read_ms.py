"""Median per traced build of the program's own span `hs.build.read`: the
source's Parquet decode inside `create_index`."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.build.read",), inside=program_spans.BUILD)
