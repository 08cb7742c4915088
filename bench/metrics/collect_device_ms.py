"""Device time per traced query of every program that ran inside it,
whatever it is called (device trace): the join's, the payload gathers,
the stage's and any that a later PR puts in their place."""

from lib import layers


def compute(run):
    s = layers.device_seconds_per_query(run)
    return None if s is None else 1e3 * s
