"""Where a run's time went, for the one `[bench] where:` note a run
prints: nothing here is a metric. It tells a slow window from a slow
machine: the spread between the quarters of one window, against the
spread between runs on one machine, against machines.
"""

from __future__ import annotations

import gc
import os
import statistics
import time

import numpy as np

STALL_FACTOR = 3.0  # an operation over this many medians is a stall
STAT_CALLS = 100


def rss_bytes() -> int:
    """The process's resident set (Linux: the second field of
    /proc/self/statm, in pages)."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def fs_type(path: str) -> str:
    """The type of the file system `path` sits on: the mount with the
    longest mount point that leads to it."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _, mount, fstype = line.split()[:3]
            if (path == mount or path.startswith(mount.rstrip("/") + "/")) \
                    and len(mount) > len(best):
                best, kind = mount, fstype
    return kind


def stat_us(directory: str) -> float | None:
    """Median microseconds of one `os.stat` of one file under
    `directory` (the first by name), over STAT_CALLS calls."""
    files = sorted(os.path.join(d, f)
                   for d, _, fs in os.walk(directory) for f in fs)
    if not files:
        return None
    costs = []
    for _ in range(STAT_CALLS):
        t0 = time.perf_counter()
        os.stat(files[0])
        costs.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(costs)


class Watch:
    """What the process spends between `start` and `stop`: the resident
    set at both ends, CPU seconds (user, system; all threads), and
    Python's own collections: how many, how many of the oldest
    generation, and the seconds they took. (Faults and context switches
    are not read: the chip's host, a sandboxed VM, reports none.)"""

    def start(self) -> "Watch":
        self.collections = self.oldest = 0
        self.gc_seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on_gc)
        self.rss = [rss_bytes()]
        self.cpu = os.times()
        return self

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.gc_seconds += time.perf_counter() - self._t0
            self.collections += 1
            self.oldest += info["generation"] == 2
            self._t0 = None

    def stop(self) -> dict:
        cpu = os.times()
        self.rss.append(rss_bytes())
        gc.callbacks.remove(self._on_gc)
        return {"rss_bytes": self.rss,
                "cpu_s": [cpu.user - self.cpu.user,
                          cpu.system - self.cpu.system],
                "gc": {"collections": self.collections,
                       "oldest": self.oldest, "seconds": self.gc_seconds}}


def window_notes(records: list, window: dict, spans) -> dict:
    """The window's operations by quarter of the window (by when each
    ended), their p50 / p95 / max, the stalls (operations over
    STALL_FACTOR medians and the seconds they took beyond the median),
    and the median of the op's `settle` spans, where it records any."""
    elapsed = window["end"] - window["start"]
    if not records or elapsed <= 0:
        return {"ops": len(records)}
    settled = spans.durations("settle", lo=window["start"])
    quarters = [0, 0, 0, 0]
    for r in records:
        quarters[min(3, int(4 * (r["end"] - window["start"]) / elapsed))] += 1
    took = sorted(r["end"] - r["start"] for r in records)
    median = statistics.median(took)
    stalls = [t for t in took if t > STALL_FACTOR * median]
    return {
        "ops": len(records), "window_s": elapsed,
        "quarter_ops": quarters,
        "quarter_per_s": [4 * q / elapsed for q in quarters],
        "op_ms": {"p50": 1e3 * median,
                  "p95": 1e3 * float(np.percentile(took, 95)),
                  "max": 1e3 * took[-1]},
        "stalls": {"n": len(stalls),
                   "over_s": sum(t - median for t in stalls)},
        "settle_ms_p50": 1e3 * statistics.median(settled) if settled
        else None,
    }
