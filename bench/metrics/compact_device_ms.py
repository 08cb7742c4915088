"""Device time per traced query of the compaction, whatever implements
it: the ops of the device trace that carry the scope `hs.compact` (the
`tf_op` of the op's metadata), summed per query, median. Found by name,
not by a shape or an HLO line."""

from lib import program_spans


def compute(run):
    return program_spans.scope_device_ms(run, "hs.compact")
