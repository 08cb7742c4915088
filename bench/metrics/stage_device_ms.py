"""Device time per traced query of the fused stage and the compaction
and gathers that follow it (device trace): every program that ran
inside the query, unless the mix names the stage's programs under
`"programs": {"stage": ...}`. In a cell whose query is one filter and
project stage that is all the device does for a query, whatever the
programs are called."""

from lib import layers


def compute(run):
    s = layers.device_seconds_per_query(run, "stage")
    return None if s is None else 1e3 * s
