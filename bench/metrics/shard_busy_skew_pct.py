"""Inside the traced queries, how far the busiest chip's busy time lies
above the mean of the chips': (max - mean) / mean, in percent (device
trace). 0 is an even mesh; the slowest chip sets a query's time."""

from lib import mesh_planes


def compute(run):
    busy = mesh_planes.busy_per_plane(run)
    if not busy:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (max(busy.values()) - mean) / mean
