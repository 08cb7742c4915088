"""Shared bench utilities: link-health probe + timing helpers.

A one-chip machine shares its host's CPU cores, so host-clock numbers
wobble run to run (how much on the current chip is unmeasured). Every
artifact therefore
carries a `link_probe` — raw device_put bandwidth + scalar-fetch sync
latency, median of N — so a regression in a committed number can be
attributed to code vs link after the fact, and per-phase timings report
median alongside best.
"""

import statistics
import sys
import time

import numpy as np

PROBE_RUNS = 5
# H2D probed at several buffer sizes: a single mid-size probe conflates
# per-transfer latency with stream bandwidth (the r05 artifact's
# "17 MB/s" was a small-buffer latency artifact — ~2s of per-put
# overhead dwarfing a 32 MB payload, not a 17 MB/s wire). Per-size
# MB/s + the sync-latency floor reported separately let a reader
# decompose the two. Fewer runs at the big sizes keep the probe's
# wall bounded on a slow link.
PROBE_SIZES_BYTES = (1 * 1024 * 1024, 16 * 1024 * 1024, 128 * 1024 * 1024)
PROBE_RUNS_BY_SIZE = (5, 3, 2)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _size_label(nbytes: int) -> str:
    return f"{nbytes // (1 << 20)}MiB"


def link_probe(runs: int = PROBE_RUNS) -> dict:
    """Raw-link health: host->device bandwidth probed at EACH size in
    `PROBE_SIZES_BYTES` (median of a few synced raw `device_put`s per
    size — deliberately bypassing the transfer engine: this measures
    the wire, not the pipeline) plus the sync round-trip latency floor
    (fetch of an already-computed device scalar, median of `runs`).
    Runs against whatever backend jax resolves (the real chip under the
    driver; CPU locally) — the artifact records which. The headline
    `h2d_mb_s` is the LARGEST-buffer bandwidth, where per-put latency
    amortizes away."""
    import jax

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)

    bump = jax.jit(lambda x: x + 1.0)
    small = jax.device_put(np.float32(1.0), dev)
    float(bump(small))  # warm compile
    jax.device_put(rng.random(1024).astype(np.float32),
                   dev).block_until_ready()  # warm the put path

    sync_s = []
    for _ in range(runs):
        # One jitted dispatch + device->host scalar fetch: the cost every
        # output-sizing sync in query execution pays.
        t0 = time.perf_counter()
        small = bump(small)
        float(small)
        sync_s.append(time.perf_counter() - t0)

    by_size = {}
    h2d_s_by_size = {}
    for nbytes, n_runs in zip(PROBE_SIZES_BYTES, PROBE_RUNS_BY_SIZE):
        # DISTINCT payloads per trial: a repeated put of the same host
        # array can hit client-side caching and under-report.
        payloads = [rng.random(nbytes // 4).astype(np.float32)
                    for _ in range(n_runs)]
        times = []
        for payload in payloads:
            t0 = time.perf_counter()
            jax.device_put(payload, dev).block_until_ready()
            times.append(time.perf_counter() - t0)
        label = _size_label(nbytes)
        by_size[label] = round(nbytes / (1 << 20)
                               / statistics.median(times), 1)
        h2d_s_by_size[label] = [round(x, 4) for x in times]

    largest = _size_label(PROBE_SIZES_BYTES[-1])
    probe = {
        "platform": dev.platform,
        "h2d_mb_s": by_size[largest],
        "h2d_mb_s_by_size": by_size,
        "sync_latency_s": round(statistics.median(sync_s), 4),
        "h2d_s_by_size": h2d_s_by_size,
        "sync_s_all": [round(x, 4) for x in sync_s],
    }
    per_size = ", ".join(f"{k} {v} MB/s" for k, v in by_size.items())
    log(f"link probe: h2d [{per_size}], "
        f"{probe['sync_latency_s'] * 1e3:.1f} ms sync floor "
        f"({dev.platform})")
    return probe


def transfer_summary() -> dict:
    """Ladder-lifetime digest of the pipelined transfer engine's link
    counters (process registry) — embedded by both bench drivers so the
    overlap the engine claims is a committed number, not an assumption.
    The schema authority is `telemetry.artifact.transfer_digest`; this
    is the bench-side alias (kept for stderr logging before the final
    artifact assembly)."""
    from hyperspace_tpu.telemetry import artifact

    return artifact.transfer_digest()


def timed_runs(fn, runs: int, label: str = ""):
    """Run `fn` `runs` times; returns (best_s, median_s, last_output).
    Medians ride next to best in every artifact so a lucky single run
    can't carry a headline."""
    times = []
    out = None
    for i in range(runs):
        t0 = time.perf_counter()
        out = fn()
        elapsed = time.perf_counter() - t0
        log(f"  {label} run {i}: {elapsed:.3f}s")
        times.append(elapsed)
    return min(times), statistics.median(times), out
