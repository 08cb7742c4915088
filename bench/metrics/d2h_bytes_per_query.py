"""Bytes that left the device per query: the registry's `link.d2h.bytes`
over the window, by the queries completed in it (program counter)."""


def compute(run):
    n = len(run["records"])
    return run["counters"].get("link.d2h.bytes", 0) / n if n else None
