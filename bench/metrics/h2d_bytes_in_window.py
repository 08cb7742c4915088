"""Bytes sent to the device inside the window: `link.h2d.bytes` (program
counter). With the index resident in the segment cache this is about 0;
more means the cache missed."""


def compute(run):
    return run["counters"].get("link.h2d.bytes", 0)
