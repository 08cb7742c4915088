"""Seconds jax spent in backend compiles (persistent-cache loads
included) inside the window, from jax's own monitoring events. The
warm-up is right when this is 0."""


def compute(run):
    return run["compiled"]["seconds"]["backend"]
