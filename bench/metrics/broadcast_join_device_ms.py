"""Device time per traced query of the broadcast join's direct-address
probe, whatever program holds it: the ops of the device trace that carry
the scope `hs.join.broadcast` (the `tf_op` of the op's metadata), in the
program `jit__broadcast_probe` (the mix's `"programs": {"broadcast":
...}`) or inlined into a fused stage's program, summed per query,
median. None where no op carries the scope."""

from lib import program_spans


def compute(run):
    return program_spans.scope_device_ms(run, "hs.join.broadcast")
