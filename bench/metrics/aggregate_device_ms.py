"""Device time per traced query of the group-by, whatever program holds
it: the ops of the device trace that carry the scope `hs.aggregate`
(the grouping sort and the exact integer moments of avg / stddev),
summed per query, median. None where no op carries the scope (a program
without it)."""

from lib import program_spans


def compute(run):
    return program_spans.scope_device_ms(run, "hs.aggregate")
