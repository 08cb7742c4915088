"""Device time per traced query of the row gathers, whatever program
holds them: the ops of the device trace that carry the scope `hs.gather`
(a batch's columns through one index vector: a join's outputs, the
aggregate's sorted batch, a compaction's survivors), summed per query,
median. None where no op carries the scope (a program without it)."""

from lib import program_spans


def compute(run):
    return program_spans.scope_device_ms(run, "hs.gather")
