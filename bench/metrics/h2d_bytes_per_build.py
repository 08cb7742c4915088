"""Bytes sent to the device per build cycle: `link.h2d.bytes` over the
window by the cycles completed (program counter). The native-host build
sends none; the first query through the new index loads what it scans."""


def compute(run):
    n = len(run["records"])
    sent = run["counters"].get("link.h2d.bytes", 0)
    return sent / n if n and sent else None
