"""Median per traced build of the program's own span `hs.build.sort`: the
bucket hash and the (bucket, keys) sort permutation (the native radix
lane on the host today, the device permutation on that lane)."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.build.sort",), inside=program_spans.BUILD)
