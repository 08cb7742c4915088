"""The device's idle time inside the traced window's median query that fell
under no program span but `hs.query`, or none at all inside the
bench's `collect`: a layer boundary that lacks its span. Every idle
moment between device ops goes to the innermost program span over it;
the five `idle_*_ms` sum to `host_gap_ms` (same trace, same gaps)."""

from lib import program_spans


def compute(run):
    return program_spans.idle_ms(run, "unnamed")
