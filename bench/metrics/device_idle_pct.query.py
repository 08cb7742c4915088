"""Share of the traced window in which no op ran on the device (device
trace). One reader per end-to-end rate it moves."""

from lib import layers


def compute(run):
    return layers.idle_pct(run)
