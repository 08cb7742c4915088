"""Device-memory accountant + byte-aware cache instrumentation.

PRs 1-2 measure TIME (operator walls, spans, link seconds); this module
lights the RESOURCE dimension — the triad that bites first in any
production accelerator stack:

- **HBM**: per-device live/peak bytes, sampled at span boundaries
  (operator finish), at every instrumented H2D/D2H link transfer, and
  at query end. On real accelerators the numbers come from
  `device.memory_stats()` (allocator truth, including fragmentation);
  on CPU/virtual meshes — where `memory_stats()` returns None — an
  accounting fallback sums `jax.live_arrays()` per device (sharded
  arrays split their bytes across their device set). Samples land as
  registry gauges (`memory.<dev>.bytes_in_use` / `.peak_bytes`),
  per-query peak watermarks on the active `QueryMetrics`
  (`peak_hbm_bytes` + per-device), and — when tracing — Chrome
  counter-track events (`"ph":"C"`), one track per device in Perfetto.

- **Caches**: every cache in the system reports
  `cache.<name>.{hits,misses,evictions}` counters and
  `cache.<name>.{bytes_held,entries}` gauges through the helpers here
  (fusion promotion + broadcast-table caches, the fused-stage trace
  cache, the jit executable caches, parquet read/host/device batch
  caches, the index metadata cache) — so cache thrash is a scrape-able
  series instead of a guess.

Sampling discipline: `maybe_sample()` is a no-op unless a per-query
recorder is active or tracing is enabled (the same always-off contract
as every other hook), and throttles to `SAMPLE_MIN_INTERVAL_S` between
walks so the live-arrays fallback cannot dominate a tight operator
loop; `sample(force=True)` bypasses the throttle at query boundaries.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

from hyperspace_tpu.telemetry import registry as _registry

__all__ = ["DeviceMemoryAccountant", "get_accountant", "maybe_sample",
           "sample", "snapshot", "cache_hit",
           "cache_miss", "cache_eviction", "cache_stats"]

# Minimum seconds between throttled samples. The live-arrays fallback
# walks every live jax array; at span-boundary call rates an unthrottled
# walk would tax exactly the hot paths telemetry must not.
SAMPLE_MIN_INTERVAL_S = 0.01


def _device_label(device) -> str:
    try:
        return f"{device.platform}:{device.id}"
    except Exception:
        return str(device)


def _stats_sample() -> Optional[Dict[str, Tuple[int, int]]]:
    """{device: (bytes_in_use, peak_bytes)} from the allocator, or None
    when ANY visible device lacks `memory_stats()` (CPU/virtual meshes,
    older runtimes) — mixed sources would make per-device comparison
    meaningless, so the fallback then covers all of them."""
    import jax

    out: Dict[str, Tuple[int, int]] = {}
    for d in jax.devices():
        try:
            st = d.memory_stats()
        except Exception:
            st = None
        if not st or "bytes_in_use" not in st:
            return None
        in_use = int(st["bytes_in_use"])
        out[_device_label(d)] = (in_use,
                                 int(st.get("peak_bytes_in_use", in_use)))
    return out or None


def _live_arrays_sample() -> Dict[str, Tuple[int, int]]:
    """Accounting fallback: sum live-array bytes per device. A sharded
    array's `nbytes` is the GLOBAL logical size; its per-device share is
    the even split over its device set (exact for the engine's row
    sharding). Peak is tracked by the accountant, not the walk."""
    import jax

    live: Dict[str, int] = {}
    for arr in jax.live_arrays():
        try:
            devices = arr.devices()
            nbytes = int(arr.nbytes)
        except Exception:
            continue
        if not devices:
            continue
        share = nbytes // len(devices)
        for d in devices:
            label = _device_label(d)
            live[label] = live.get(label, 0) + share
    return {label: (b, b) for label, b in live.items()}


class DeviceMemoryAccountant:
    """Tracks per-device live and peak HBM bytes for the process, and
    attributes per-query peak watermarks to the active recorder."""

    def __init__(self):
        self._lock = threading.Lock()
        self._last_sample_t = 0.0
        self.live: Dict[str, int] = {}
        self.peak: Dict[str, int] = {}
        self.backend: Optional[str] = None  # "memory_stats"|"live_arrays"
        self.samples = 0

    # -- sampling ------------------------------------------------------

    def sample(self, force: bool = True) -> Optional[Dict[str, int]]:
        """Take one sample: update gauges, process peaks, the active
        recorder's watermarks, and (when tracing) the per-device counter
        tracks. Returns {device: bytes_in_use} or None when throttled."""
        now = time.monotonic()
        with self._lock:
            if not force and now - self._last_sample_t \
                    < SAMPLE_MIN_INTERVAL_S:
                return None
            self._last_sample_t = now
        per_dev = _stats_sample()
        if per_dev is not None:
            backend = "memory_stats"
        else:
            per_dev = _live_arrays_sample()
            backend = "live_arrays"
        reg = _registry.get_registry()
        live: Dict[str, int] = {}
        with self._lock:
            self.backend = backend
            self.samples += 1
            for dev, (in_use, dev_peak) in per_dev.items():
                self.live[dev] = in_use
                self.peak[dev] = max(self.peak.get(dev, 0), dev_peak,
                                     in_use)
                live[dev] = in_use
            peaks = dict(self.peak)
        for dev, in_use in live.items():
            reg.gauge(f"memory.{dev}.bytes_in_use").set(in_use)
            reg.gauge(f"memory.{dev}.peak_bytes").set(peaks[dev])
        from hyperspace_tpu import telemetry
        rec = telemetry.current()
        if rec is not None:
            rec.observe_hbm(live)
        tracer = telemetry.tracer()
        if tracer is not None:
            for dev, in_use in live.items():
                tracer.counter(f"HBM {dev}", {"bytes_in_use": in_use})
        return live

    def maybe_sample(self) -> None:
        """Throttled sample, and only when someone is listening (active
        recorder or tracer) — THE span-boundary hook."""
        from hyperspace_tpu import telemetry
        if telemetry.current() is None and telemetry.tracer() is None:
            return
        self.sample(force=False)

    # -- snapshots -----------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "backend": self.backend,
                "samples": self.samples,
                "devices": {dev: {"bytes_in_use": self.live.get(dev, 0),
                                  "peak_bytes": peak}
                            for dev, peak in sorted(self.peak.items())},
                "peak_hbm_bytes": sum(self.peak.values()),
            }


_ACCOUNTANT = DeviceMemoryAccountant()


def get_accountant() -> DeviceMemoryAccountant:
    """THE process-wide device-memory accountant."""
    return _ACCOUNTANT


def maybe_sample() -> None:
    _ACCOUNTANT.maybe_sample()


def sample(force: bool = True):
    return _ACCOUNTANT.sample(force=force)


def snapshot() -> dict:
    return _ACCOUNTANT.snapshot()


# ---------------------------------------------------------------------------
# Byte-aware cache instrumentation: one naming scheme for every cache.
# ---------------------------------------------------------------------------


def cache_hit(name: str, n: int = 1) -> None:
    _registry.get_registry().counter(f"cache.{name}.hits").inc(n)
    _query_cache_count(f"cache.{name}.hits", n)


def cache_miss(name: str, n: int = 1) -> None:
    _registry.get_registry().counter(f"cache.{name}.misses").inc(n)
    _query_cache_count(f"cache.{name}.misses", n)


def cache_eviction(name: str, n: int = 1) -> None:
    if n:
        _registry.get_registry().counter(f"cache.{name}.evictions").inc(n)
        _query_cache_count(f"cache.{name}.evictions", n)


def _query_cache_count(counter: str, n: int) -> None:
    """Mirror a cache event onto the active per-query recorder (no-op
    without one) — the regression differ's `cache` bucket reads these
    per-query `cache.<name>.*` deltas, so WHICH query thrashed a cache
    is attributable round-over-round, not just that the process did."""
    from hyperspace_tpu import telemetry
    telemetry.add_count(counter, n)


def cache_stats(name: str, bytes_held: Optional[int],
                entries: Optional[int]) -> None:
    """Post-mutation residency gauges; pass None to leave one unset
    (e.g. a metadata cache with no meaningful byte size)."""
    reg = _registry.get_registry()
    if bytes_held is not None:
        reg.gauge(f"cache.{name}.bytes_held").set(bytes_held)
    if entries is not None:
        reg.gauge(f"cache.{name}.entries").set(entries)
