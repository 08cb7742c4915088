"""Median per traced build of the program's own span `hs.build.write`: the
write phase's wall on the calling thread (Arrow gather, and the encode +
file writes it waits for on the writer thread)."""

from lib import program_spans


def compute(run):
    return program_spans.span_ms(
        run, ("hs.build.write",), inside=program_spans.BUILD)
