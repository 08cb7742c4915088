"""Device time per traced query of the fused stage's own programs: the
ops of the device trace that carry the scope `hs.stage` (the stage
program, with its predicate and any broadcast probe inlined into it,
and its deferred build-side gathers), summed per query, median. The
compaction and the gathers that follow the stage carry scopes of their
own. None where no op carries the scope (no fused stage ran, or a
program without it)."""

from lib import program_spans


def compute(run):
    return program_spans.scope_device_ms(run, "hs.stage")
