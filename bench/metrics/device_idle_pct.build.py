"""Share of the build's own time (the bench's `drop` and `build` spans
that lie whole in the traced window) in which no op ran on the device
(device trace). 100 while the build runs on the host; the query that
follows each build is not counted here."""

from lib import layers


def compute(run):
    return layers.idle_pct(run, ("bench.build", "bench.drop"))
