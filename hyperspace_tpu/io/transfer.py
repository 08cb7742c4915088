"""Pipelined host<->device transfer engine — THE link seam.

The index build stages every key column over the link and fetches the
permutation back, and the paper's data-plane lesson — keep it a
streaming recipe, not a blocking copy — maps on TPU to classic
input-pipeline software pipelining: chunk the batch and keep the
decoder, the link, the device, and the writer busy at once.

Every host->device crossing in the package routes through this module
(`scripts/check_metrics_coverage.py` bans raw `jax.device_put` anywhere
else), which buys three things at one seam:

- **chunked, double-buffered staging**: large host arrays ship as
  byte-budgeted row chunks; chunk i+1 is converted (dtype cast / null
  fill) on a staging thread into a REUSED preallocated host buffer
  while chunk i's `device_put` is in flight, under a bounded in-flight
  byte window so a wide table can't balloon pinned host + device
  transfer memory;
- **async multi-column placement**: `put_group` decodes columns on the
  staging pool and issues every column's puts before anything blocks,
  so Arrow decode overlaps the wire for the whole batch
  (`io/columnar.from_arrow`'s device path);
- **one observable, fault-injectable link**: every put fires the
  `transfer.put` fault seam, retries transiently via `utils/retry`, and
  lands in the `link.{h2d,d2h}.{bytes,seconds,chunks}` counters plus
  the `transfer.overlap_saved_seconds` estimate (serial sum of stage
  walls minus pipelined wall) — the overlap is measured, not assumed.

Knobs (session conf, `TransferEngine.configure` /
`transfer.configure`): `spark.hyperspace.io.transfer.chunk.bytes`
(chunk granularity), `...inflight.bytes` (in-flight byte window),
`...threads` (staging pool width). The engine is process-wide
(`get_engine()`); sessions sharing a process should agree on the knobs,
same caveat as the parquet cache budgets.

Staging-buffer reuse is gated on a one-time probe that `device_put`
COPIES the host buffer (it does on TPU and on current CPU jax): on a
backend where the put aliases host memory, rewriting the buffer would
corrupt the device array, so the engine falls back to fresh
materialisation there.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from hyperspace_tpu import constants

__all__ = ["TransferEngine", "HostCast", "Host", "get_engine",
           "set_engine", "reset_engine", "configure", "device_put",
           "TransferAcquireTimeoutError", "shutdown"]


class TransferAcquireTimeoutError(TimeoutError):
    """Waiting for in-flight-window headroom exceeded
    `spark.hyperspace.io.transfer.acquire.timeout.ms`. A put that died
    without releasing its bytes (hung runtime, dead link) would
    otherwise block every later caller FOREVER on a window that can
    never drain. TimeoutError parentage is deliberate: `utils/retry.py`
    classifies it transient, so retry-wrapped callers back off and
    re-try instead of treating a recoverable stall as fatal. Counted
    as `io.transfer.acquire_timeouts`."""

import logging

logger = logging.getLogger(__name__)

# Staging below this size skips the buffer pool: the copy-into-buffer
# bookkeeping costs more than the fresh allocation it avoids.
_STAGING_MIN_BYTES = 1 << 16

# Upper bound on D2H permutation chunking (`d2h_chunk_count`): each
# chunk adds a slice output to the compiled program. 8 was where a
# link that no longer exists stopped scaling; unmeasured on an attached
# chip.
_MAX_D2H_CHUNKS = 8


class HostCast:
    """A deferred host-side conversion: `src` reinterpreted/cast to
    `dtype` lazily, chunk by chunk, into a reused staging buffer at put
    time — instead of a fresh full-size `astype` materialisation per
    column."""

    __slots__ = ("src", "dtype")

    def __init__(self, src: np.ndarray, dtype):
        self.src = np.asarray(src)
        self.dtype = np.dtype(dtype)

    @property
    def nbytes(self) -> int:
        shape = self.src.shape
        n = 1
        for d in shape:
            n *= d
        return n * self.dtype.itemsize

    def materialize(self) -> np.ndarray:
        return np.ascontiguousarray(self.src).astype(self.dtype)


class Host:
    """Marker for `put_group` payload values that must STAY host-resident
    (string dictionaries); the engine passes `value` through unplaced."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value


class _WindowEntry:
    __slots__ = ("dev", "nbytes", "buf")

    def __init__(self, dev, nbytes: int, buf):
        self.dev = dev
        self.nbytes = nbytes
        self.buf = buf


def _block_ready(dev) -> None:
    fn = getattr(dev, "block_until_ready", None)
    if fn is not None:
        fn()


class TransferEngine:
    """Process-wide pipelined host<->device transfer engine. See module
    docstring; `put_fn` is the test seam for a fake link (signature
    `(host_array, device_or_sharding_or_None) -> device_array`)."""

    def __init__(self, chunk_bytes: Optional[int] = None,
                 inflight_bytes: Optional[int] = None,
                 threads: Optional[int] = None,
                 put_fn: Optional[Callable] = None,
                 acquire_timeout_s: Optional[float] = None):
        self.chunk_bytes = int(
            chunk_bytes or constants.IO_TRANSFER_CHUNK_BYTES_DEFAULT)
        self.inflight_bytes = int(
            inflight_bytes or constants.IO_TRANSFER_INFLIGHT_BYTES_DEFAULT)
        self.threads = int(
            threads or constants.IO_TRANSFER_THREADS_DEFAULT)
        self.acquire_timeout_s = (
            acquire_timeout_s if acquire_timeout_s is not None
            else constants.IO_TRANSFER_ACQUIRE_TIMEOUT_MS_DEFAULT
            / 1000.0)
        self._put_fn = put_fn
        self._lock = threading.RLock()
        self._pool = None
        # In-flight window: puts issued but not known complete. Shared
        # across calls so concurrent callers honor ONE byte budget.
        self._window: deque = deque()
        self._window_bytes = 0
        # Staging buffer pool: [buf uint8 ndarray, gate devarr|None].
        # A gated buffer's last consumer transfer may still be in
        # flight; acquisition blocks on the gate before reuse.
        self._staging_free: List[list] = []
        self._staging_safe: Optional[bool] = None
        self.stats: Dict[str, int] = {
            "puts": 0, "chunks": 0, "groups": 0, "reshards": 0,
            "staging_allocated": 0, "staging_reused": 0,
            "window_waits": 0,
        }

    # -- configuration ----------------------------------------------------

    def configure(self, conf) -> None:
        """Refresh the knobs from a session conf (process-wide engine;
        co-resident sessions should agree)."""
        if conf is None:
            return
        self.chunk_bytes = max(1, conf.io_transfer_chunk_bytes)
        self.inflight_bytes = max(self.chunk_bytes,
                                  conf.io_transfer_inflight_bytes)
        self.threads = max(1, conf.io_transfer_threads)
        try:
            self.acquire_timeout_s = \
                conf.io_transfer_acquire_timeout_ms / 1000.0
        except Exception:
            pass  # conf-shaped test fakes without the property

    def _staging_pool(self):
        if self._pool is None:
            with self._lock:
                if self._pool is None:
                    from concurrent.futures import ThreadPoolExecutor
                    self._pool = ThreadPoolExecutor(
                        max_workers=max(1, self.threads),
                        thread_name_prefix="hs-transfer")
        return self._pool

    # -- the raw put seam -------------------------------------------------

    def _raw_put(self, arr, device):
        """ONE guarded `jax.device_put`: fault-injectable at the
        `transfer.put` seam and transiently retried (a retried attempt
        re-puts the same host view, so chunk order cannot be corrupted
        — results are placed by index, not completion order)."""
        from hyperspace_tpu.utils import faults, retry

        def attempt():
            faults.fire("transfer.put")
            if self._put_fn is not None:
                return self._put_fn(arr, device)
            import jax
            if device is None:
                return jax.device_put(arr)
            return jax.device_put(arr, device)

        return retry.call(attempt, operation="transfer.put")

    # -- in-flight byte window -------------------------------------------

    def _sweep(self) -> None:
        """Drop window entries whose transfers already completed
        (non-blocking `is_ready` probe), releasing their bytes and
        staging buffers — keeps the engine from pinning device arrays
        past their transfer (a silent leak the leak-sentinel tests
        would otherwise trip on)."""
        released = []
        with self._lock:
            keep: deque = deque()
            while self._window:
                ent = self._window.popleft()
                probe = getattr(ent.dev, "is_ready", None)
                done = False
                if probe is not None:
                    try:
                        done = bool(probe())
                    except Exception:
                        done = False
                if done:
                    self._window_bytes -= ent.nbytes
                    if ent.buf is not None:
                        released.append(ent.buf)
                else:
                    keep.append(ent)
            self._window = keep
        for buf in released:
            self._release_staging(buf, gate=None)

    def _wait_entry_ready(self, ent: _WindowEntry,
                          t_end: Optional[float]) -> None:
        """Block until `ent`'s transfer lands, bounded by `t_end`
        (monotonic). With an `is_ready` probe (every jax array; fakes
        by contract) the wait polls so it CAN time out; without one it
        falls back to the unbounded blocking sync. Timeout raises
        `TransferAcquireTimeoutError` with the entry untouched — the
        caller must re-queue it before propagating."""
        probe = getattr(ent.dev, "is_ready", None)
        if probe is None or t_end is None:
            _block_ready(ent.dev)
            return
        while True:
            try:
                if probe():
                    return
            except Exception:
                return  # a dead array is as released as it gets
            if time.monotonic() >= t_end:
                raise TransferAcquireTimeoutError(
                    f"in-flight window acquisition timed out after "
                    f"{self.acquire_timeout_s:.1f}s "
                    f"({self._window_bytes} B held, "
                    f"{self.inflight_bytes} B window)")
            time.sleep(0.002)

    def _admit(self, nbytes: int) -> None:
        """Reserve `nbytes` of in-flight budget, blocking on the OLDEST
        outstanding transfers until the window fits (their completion
        also releases their staging buffers). The wait is BOUNDED by
        the acquire timeout: a transfer that never completes raises a
        typed transient error (`TransferAcquireTimeoutError`, counted
        as `io.transfer.acquire_timeouts`) instead of hanging every
        later caller on bytes that can never drain."""
        self._sweep()
        t_end = (time.monotonic() + self.acquire_timeout_s
                 if self.acquire_timeout_s > 0 else None)
        while True:
            with self._lock:
                if (self._window_bytes + nbytes <= self.inflight_bytes
                        or not self._window):
                    self._window_bytes += nbytes
                    return
                ent = self._window.popleft()
                self.stats["window_waits"] += 1
            try:
                self._wait_entry_ready(ent, t_end)
            except TransferAcquireTimeoutError:
                with self._lock:
                    # The entry's transfer is still outstanding: its
                    # bytes stay accounted, back at the window head.
                    self._window.appendleft(ent)
                from hyperspace_tpu import telemetry
                telemetry.get_registry().counter(
                    "io.transfer.acquire_timeouts").inc()
                raise
            with self._lock:
                self._window_bytes -= ent.nbytes
            if ent.buf is not None:
                self._release_staging(ent.buf, gate=None)

    def _track(self, dev, nbytes: int, buf) -> None:
        with self._lock:
            self._window.append(_WindowEntry(dev, nbytes, buf))

    def _windowed_put(self, view, device, buf=None):
        nbytes = int(getattr(view, "nbytes", 0))
        self._admit(nbytes)
        try:
            dev = self._raw_put(view, device)
        except BaseException:
            # A put that dies must RELEASE its reservation (and its
            # staging buffer) — leaked bytes would shrink the window
            # for every later caller until nothing fits and the
            # acquire timeout becomes the only way out.
            with self._lock:
                self._window_bytes -= nbytes
            if buf is not None:
                self._release_staging(buf, gate=None)
            raise
        self._track(dev, nbytes, buf)
        with self._lock:
            self.stats["chunks"] += 1
        return dev

    # -- staging buffers --------------------------------------------------

    def _staging_ok(self) -> bool:
        """Staging reuse is only safe when `device_put` COPIES the host
        buffer (rewriting an aliased buffer would corrupt the device
        array). The CPU PJRT client zero-copies suitably ALIGNED host
        buffers — and whether a given numpy allocation is aligned is
        luck, so no runtime probe can clear it — while accelerators
        behind a real link always copy; gate on the platform."""
        if self._staging_safe is None:
            if self._put_fn is not None:
                self._staging_safe = True  # fakes copy by contract
            else:
                try:
                    import jax
                    platform = jax.devices()[0].platform
                except Exception:
                    platform = "cpu"
                self._staging_safe = platform != "cpu"
        return self._staging_safe

    def _acquire_staging(self, nbytes: int) -> Optional[np.ndarray]:
        """A host staging buffer of capacity >= nbytes (reused when one
        is free), or None when staging is disabled/pointless."""
        if nbytes < _STAGING_MIN_BYTES or not self._staging_ok():
            return None
        gate = None
        buf = None
        with self._lock:
            for i, ent in enumerate(self._staging_free):
                if ent[0].nbytes >= nbytes:
                    buf, gate = ent
                    del self._staging_free[i]
                    break
        if buf is not None:
            if gate is not None:
                _block_ready(gate)  # prior consumer transfer must land
            with self._lock:
                self.stats["staging_reused"] += 1
            return buf
        buf = np.empty(max(nbytes, self.chunk_bytes), dtype=np.uint8)
        with self._lock:
            self.stats["staging_allocated"] += 1
        return buf

    def _release_staging(self, buf: np.ndarray, gate) -> None:
        with self._lock:
            if len(self._staging_free) < 2 * max(1, self.threads) + 2:
                self._staging_free.append([buf, gate])

    def _convert(self, cast: HostCast, start: int, stop: int):
        """Chunk [start, stop) of a HostCast into a staging buffer (or a
        fresh array when staging is off). Runs on the staging pool.
        Returns (view, buf, seconds)."""
        t0 = time.perf_counter()
        src = cast.src[start:stop]
        shape = src.shape
        nbytes = int(np.prod(shape)) * cast.dtype.itemsize if shape else \
            cast.dtype.itemsize
        buf = self._acquire_staging(nbytes)
        if buf is None:
            view = np.ascontiguousarray(src).astype(cast.dtype)
        else:
            view = buf[:nbytes].view(cast.dtype).reshape(shape)
            np.copyto(view, src, casting="unsafe")
        return view, buf, time.perf_counter() - t0

    # -- chunk planning ---------------------------------------------------

    def _chunk_bounds(self, shape, itemsize: int):
        """[(start, stop)) row ranges of <= chunk_bytes each, or None for
        a single-chunk transfer."""
        if not shape:
            return None
        rows = shape[0]
        row_bytes = itemsize
        for d in shape[1:]:
            row_bytes *= d
        if row_bytes <= 0:
            return None
        per = max(1, self.chunk_bytes // row_bytes)
        if rows <= per:
            return None
        return [(i, min(rows, i + per)) for i in range(0, rows, per)]

    def d2h_chunk_count(self, nbytes: int) -> int:
        """How many concurrent D2H streams a fetch of `nbytes` should
        split into (consumed by `ops/build.permutation_from_tree` — the
        compiled program slices the permutation accordingly)."""
        if nbytes < self.chunk_bytes:
            return 1
        return int(min(_MAX_D2H_CHUNKS,
                       -(-nbytes // self.chunk_bytes)))

    # -- entry placement --------------------------------------------------

    def _assemble(self, parts):
        if len(parts) == 1:
            return parts[0]
        import jax.numpy as jnp
        return jnp.concatenate(parts)

    def _put_parts(self, entry, device, timings) -> list:
        """Place one logical array (ndarray or HostCast) as windowed
        device chunk(s); conversions run on the staging pool one chunk
        ahead of the put. Returns the ordered chunk list (length 1 for
        sub-chunk arrays)."""
        cast = isinstance(entry, HostCast)
        arr = entry.src if cast else entry
        dtype = entry.dtype if cast else arr.dtype
        bounds = self._chunk_bounds(arr.shape, dtype.itemsize)
        if bounds is None:
            if cast:
                view, buf, conv_s = self._convert(entry, 0,
                                                  arr.shape[0]
                                                  if arr.shape else 0)
                timings["convert_s"] += conv_s
            else:
                view, buf = arr, None
            t0 = time.perf_counter()
            dev = self._windowed_put(view, device, buf=buf)
            timings["put_s"] += time.perf_counter() - t0
            timings["chunks"] += 1
            return [dev]

        from hyperspace_tpu import telemetry

        parts = [None] * len(bounds)
        pending: deque = deque()
        lookahead = max(1, self.threads) + 1
        pool = self._staging_pool()

        def emit():
            # Chunk-boundary cancellation checkpoint: a cancelled query
            # stops shipping chunks here; already-issued puts complete
            # and release through the window sweep.
            telemetry.check_deadline("transfer")
            idx, fut, ready = pending.popleft()
            buf = None
            if fut is not None:
                view, buf, conv_s = fut.result()
                timings["convert_s"] += conv_s
            else:
                view = ready
            t0 = time.perf_counter()
            parts[idx] = self._windowed_put(view, device, buf=buf)
            timings["put_s"] += time.perf_counter() - t0
            timings["chunks"] += 1

        try:
            for idx, (s, e) in enumerate(bounds):
                while len(pending) >= lookahead:
                    emit()
                if cast:
                    pending.append((idx, pool.submit(self._convert,
                                                     entry, s, e), None))
                else:
                    pending.append((idx, None, arr[s:e]))
            while pending:
                emit()
        except BaseException:
            # Guaranteed release of in-flight STAGING on the error path
            # (cancellation included): conversions already submitted to
            # the pool hold pooled buffers their put will now never
            # consume — drain and return them, or the pool bleeds
            # buffers one cancelled query at a time.
            while pending:
                _idx, fut, _ready = pending.popleft()
                if fut is None:
                    continue
                try:
                    _view, buf, _s = fut.result()
                except Exception:
                    continue
                if buf is not None:
                    self._release_staging(buf, gate=None)
            raise
        return parts

    def _put_entry(self, entry, device, timings) -> object:
        """As `_put_parts`, reassembled into ONE device array."""
        return self._assemble(self._put_parts(entry, device, timings))

    # -- public API -------------------------------------------------------

    def put(self, arr, device=None, chunked: Optional[bool] = None):
        """Place one array on the device (or under a Sharding passed as
        `device`). Host numpy inputs cross the link chunked + windowed
        and land in the h2d telemetry; already-device inputs are a
        re-placement (resharding), counted but not a link crossing.
        Sharded placements are never chunk-split — each device receives
        only its slice already."""
        if not isinstance(arr, (np.ndarray, HostCast)):
            with self._lock:
                self.stats["reshards"] += 1
            return self._raw_put(arr, device)
        if chunked is None:
            chunked = device is None
        nbytes = int(arr.nbytes)
        timings = {"convert_s": 0.0, "put_s": 0.0, "chunks": 0}
        from hyperspace_tpu import telemetry
        with telemetry.link_transfer("h2d", nbytes) as link:
            if chunked:
                dev = self._put_entry(arr, device, timings)
            else:
                if isinstance(arr, HostCast):
                    arr = arr.materialize()
                dev = self._windowed_put(arr, device)
                timings["chunks"] = 1
            link.chunks = timings["chunks"]
        with self._lock:
            self.stats["puts"] += 1
        self._sweep()
        return dev

    def put_chunks(self, arr, device=None):
        """Place a host array (ndarray or HostCast) as a TUPLE of device
        row-chunks without reassembly — for consumers whose compiled
        program concatenates internally (`ops/build._entry_assemble`'s
        `lo32_chunks`)."""
        if not isinstance(arr, HostCast):
            arr = np.asarray(arr)
        nbytes = int(arr.nbytes)
        from hyperspace_tpu import telemetry
        timings = {"convert_s": 0.0, "put_s": 0.0, "chunks": 0}
        with telemetry.link_transfer("h2d", nbytes) as link:
            parts = tuple(self._put_parts(arr, device, timings))
            link.chunks = len(parts)
        with self._lock:
            self.stats["puts"] += 1
        self._sweep()
        return parts

    def put_group(self, jobs: Sequence[Callable[[], dict]], device=None,
                  tag: Optional[str] = None) -> List[dict]:
        """Pipelined multi-column placement. Each job runs on the
        staging pool and returns {name: value} where ndarray / HostCast
        values get placed (chunked + windowed), `Host(v)` unwraps to v,
        and anything else passes through. Decode of column i+1 overlaps
        column i's puts; one h2d telemetry record covers the group, and
        the measured overlap (serial stage sum minus pipelined wall)
        accumulates in `transfer.overlap_saved_seconds`.

        `tag` names the LANE for attribution: segment-cache fills pass
        `tag="fill"`, which lands the group in `transfer.fill.{bytes,
        seconds,chunks}` counters alongside the shared `link.h2d.*`
        series (fills share the link, the in-flight window, and the
        staging pool with live queries' transfers — the budget is one;
        only the accounting is split) and stamps the cancellation
        checkpoints with the `transfer.fill` phase so an interrupted
        fill is distinguishable from an interrupted query transfer in
        `serve.interrupted.*`."""
        if not jobs:
            return []
        from hyperspace_tpu import telemetry
        pool = self._staging_pool()
        phase = f"transfer.{tag}" if tag else "transfer"
        t0 = time.perf_counter()

        def timed(job):
            j0 = time.perf_counter()
            out = job()
            return out, time.perf_counter() - j0

        futs = [pool.submit(timed, job) for job in jobs]
        timings = {"convert_s": 0.0, "put_s": 0.0, "chunks": 0}
        decode_s = 0.0
        total_bytes = 0
        results: List[dict] = []
        # One span (and, below, one accounting record) for the group.
        with telemetry.span("hs.link.h2d", "link", direction="h2d",
                            tag=tag) as link:
            for fut in futs:
                # Per-column checkpoint: remaining decodes still run on the
                # pool (futures are not revoked) but their results are
                # plain host arrays — nothing device-side leaks.
                telemetry.check_deadline(phase)
                produced, job_s = fut.result()
                decode_s += job_s
                placed = {}
                for key, value in produced.items():
                    if isinstance(value, Host):
                        placed[key] = value.value
                    elif isinstance(value, (np.ndarray, HostCast)):
                        total_bytes += int(value.nbytes)
                        placed[key] = self._put_entry(value, device, timings)
                    else:
                        placed[key] = value
                results.append(placed)
            link.set(bytes=total_bytes, chunks=timings["chunks"])
        wall = time.perf_counter() - t0
        serial_s = decode_s + timings["convert_s"] + timings["put_s"]
        saved = max(serial_s - wall, 0.0)
        with self._lock:
            self.stats["groups"] += 1
        if total_bytes:
            reg = telemetry.get_registry()
            reg.counter("transfer.overlap_saved_seconds").inc(saved)
            if tag:
                reg.counter(f"transfer.{tag}.bytes").inc(total_bytes)
                reg.counter(f"transfer.{tag}.seconds").inc(wall)
                reg.counter(f"transfer.{tag}.chunks").inc(
                    max(timings["chunks"], 1))
            telemetry.record_link_transfer("h2d", total_bytes, wall,
                                           chunks=max(timings["chunks"],
                                                      1))
        self._sweep()
        return results

    # -- lifecycle --------------------------------------------------------

    def sweep(self) -> None:
        """Public probe-and-release pass over the in-flight window:
        completed transfers give back their bytes and staging buffers
        NOW (the scheduler calls this after a cancellation so a dead
        query's window share does not wait for the next caller's
        put)."""
        self._sweep()

    def drain(self) -> None:
        """Block (bounded by the acquire timeout per entry) until every
        outstanding transfer lands and its resources are released."""
        while True:
            with self._lock:
                if not self._window:
                    return
                ent = self._window.popleft()
            t_end = (time.monotonic() + self.acquire_timeout_s
                     if self.acquire_timeout_s > 0 else None)
            try:
                self._wait_entry_ready(ent, t_end)
            except TransferAcquireTimeoutError:
                logger.warning("drain: abandoning a transfer that "
                               "never completed (%d B)", ent.nbytes)
            with self._lock:
                self._window_bytes -= ent.nbytes
            if ent.buf is not None:
                self._release_staging(ent.buf, gate=None)

    def shutdown(self) -> None:
        """Drain the window and stop the staging pool (idempotent;
        registered atexit so interpreter teardown neither leaks the
        staging threads nor abandons in-flight puts)."""
        try:
            self.drain()
        except Exception:
            pass
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # -- device -> host ---------------------------------------------------

    def fetch(self, arr) -> np.ndarray:
        """One device->host fetch with d2h telemetry; host-resident
        inputs pass through uncounted."""
        if isinstance(arr, np.ndarray):
            return arr
        from hyperspace_tpu import telemetry
        with telemetry.link_transfer("d2h", int(getattr(arr, "nbytes",
                                                        0))):
            return np.asarray(arr)

    def prefetch(self, *arrs) -> None:
        """Issue best-effort async D2H copies so later `fetch`es hit
        landed bytes. A failing prefetch silently degrades to the
        serial fetch — so it is COUNTED (`link.d2h.prefetch_errors`)
        and debug-logged instead of swallowed invisibly."""
        from hyperspace_tpu import telemetry
        for arr in arrs:
            fn = getattr(arr, "copy_to_host_async", None)
            if fn is None:
                continue
            try:
                fn()
            except Exception as exc:
                telemetry.get_registry().counter(
                    "link.d2h.prefetch_errors").inc()
                logger.debug("d2h prefetch failed (serial fallback): %r",
                             exc)


# -- process-wide engine ---------------------------------------------------

_engine: Optional[TransferEngine] = None
_engine_lock = threading.Lock()


def get_engine() -> TransferEngine:
    global _engine
    if _engine is None:
        with _engine_lock:
            if _engine is None:
                _engine = TransferEngine()
    return _engine


def set_engine(engine: TransferEngine) -> TransferEngine:
    """Install a specific engine (tests: tiny chunk sizes, fake links)."""
    global _engine
    _engine = engine
    return engine


def reset_engine() -> None:
    global _engine
    _engine = None


def configure(conf) -> None:
    """Refresh the process engine's knobs from a session conf."""
    get_engine().configure(conf)


def device_put(arr, device=None, chunked: Optional[bool] = None):
    """Module-level convenience: `get_engine().put(...)`."""
    return get_engine().put(arr, device=device, chunked=chunked)


def shutdown() -> None:
    """Shut the process engine down (atexit hook; idempotent — a new
    engine lazily re-creates on the next put, so tests that reset the
    module keep working)."""
    engine = _engine
    if engine is not None:
        engine.shutdown()


import atexit  # noqa: E402

atexit.register(shutdown)
