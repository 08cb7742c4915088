"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read. Reads the file with jax's own `ProfileData` and nothing
else.

    reduce(path) -> {
      "window": (start_s, end_s)   the `bench.window` span, profiler clock
      "chips": n                   device planes that ran anything
      "busy_s": seconds an op ran on the device inside the window (union
                of op intervals per chip, averaged over the chips)
      "busy": {plane: [(start_s, end_s), ...]}  merged op intervals
      "ops": {name: seconds}       device ops, summed over the window
      "programs": [(name, start_s, dur_s), ...]  XLA modules on the device
      "spans": [(name, op, start_s, dur_s), ...] the bench's own spans
    }
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint
    ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_within(busy, lo: float, hi: float) -> float:
    """Seconds of [lo, hi) covered by the merged intervals `busy`."""
    return total(clip(busy, lo, hi))


def program_name(event_name: str) -> str:
    """`jit__perm_core(123456789)` -> `jit__perm_core`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(path: str) -> dict:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    spans, per_plane_ops, programs = [], {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    per_plane_ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    programs += [
                        (program_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        op = dict(e.stats).get("op", -1)
                        spans.append((e.name, int(op), e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    windows = [(s, s + d) for n, _, s, d in spans if n == WINDOW_SPAN]
    everything = [(s, s + d) for evs in per_plane_ops.values()
                  for _, s, d in evs] + [(s, s + d) for _, _, s, d in spans]
    if windows:
        window = windows[0]
    elif everything:
        window = (min(s for s, _ in everything),
                  max(e for _, e in everything))
    else:
        window = (0.0, 0.0)
    busy, ops = {}, {}
    for plane, evs in per_plane_ops.items():
        busy[plane] = merge(clip([(s, s + d) for _, s, d in evs], *window))
        for name, s, d in evs:
            if s + d > window[0] and s < window[1]:
                ops[name] = ops.get(name, 0.0) + d
    chips = sum(1 for b in busy.values() if b)
    return {
        "window": window,
        "chips": chips,
        "busy_s": (sum(total(b) for b in busy.values()) / chips
                   if chips else 0.0),
        "busy": busy,
        "ops": ops,
        "programs": sorted(programs, key=lambda p: p[1]),
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def busy_all_chips(reduced: dict):
    """Intervals in which any chip was busy."""
    return merge([iv for b in reduced["busy"].values() for iv in b])


def idle_gaps(reduced: dict, top: int = 10):
    """The window's idle time by what the host was doing in it: each gap
    between device ops is cut where a bench span starts or ends inside
    it, and each piece goes to the innermost span that covers it
    (`outside` where none does). [[name, seconds], ...], most first."""
    lo, hi = reduced["window"]
    busy = busy_all_chips(reduced)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = [(n, s, s + d) for n, _, s, d in reduced["spans"]
             if n != WINDOW_SPAN]
    by_name = {}
    for s, e in gaps:
        over = [(n, ss, ee) for n, ss, ee in named if ss < e and ee > s]
        cuts = sorted({s, e} | {t for _, ss, ee in over for t in (ss, ee)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            covering = [(ee - ss, n) for n, ss, ee in over
                        if ss <= a and b <= ee]
            name = min(covering)[1][len(SPAN_PREFIX):] if covering \
                else "outside"
            by_name[name] = by_name.get(name, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_ops(reduced: dict, top: int = 10, name_chars: int = 160):
    """The device ops that took most time; an op's name is its HLO line,
    cut to `name_chars` (a fusion's operand list runs to kilobytes)."""
    return [[n[:name_chars], s] for n, s in sorted(
        reduced["ops"].items(), key=lambda kv: -kv[1])[:top]]


def program_seconds(reduced: dict, pattern: str, lo=None, hi=None) -> float:
    """Summed device seconds of the programs whose name matches."""
    rx = re.compile(pattern)
    w_lo, w_hi = reduced["window"]
    lo = w_lo if lo is None else lo
    hi = w_hi if hi is None else hi
    return sum(d for n, s, d in reduced["programs"]
               if rx.search(n) and s >= lo and s + d <= hi)
