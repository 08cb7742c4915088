"""Config system.

The reference piggybacks on Spark `SQLConf` string keys declared in
`index/IndexConstants.scala:21-50` and read lazily at use sites
(`actions/CreateActionBase.scala:44-48`). Here `HyperspaceConf` is a small
string-keyed config owned by the session, with the same keys and defaults.
Both the `spark.hyperspace.*` spelling and a `hyperspace.*` short form are
accepted.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from hyperspace_tpu import constants


def _canonical(key: str) -> str:
    if key.startswith("hyperspace."):
        return "spark." + key
    return key


class HyperspaceConf:
    """String-keyed configuration with lazy reads at use sites."""

    def __init__(self, conf: Optional[Dict[str, str]] = None):
        self._conf: Dict[str, str] = {}
        for k, v in (conf or {}).items():
            self.set(k, v)

    def set(self, key: str, value) -> "HyperspaceConf":
        self._conf[_canonical(key)] = str(value)
        return self

    def unset(self, key: str) -> "HyperspaceConf":
        self._conf.pop(_canonical(key), None)
        return self

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._conf.get(_canonical(key), default)

    def get_int(self, key: str, default: int) -> int:
        value = self.get(key)
        return int(value) if value is not None else default

    def contains(self, key: str) -> bool:
        return _canonical(key) in self._conf

    # Derived settings, mirroring reference defaulting rules.

    @property
    def warehouse_dir(self) -> str:
        return self.get(constants.WAREHOUSE_PATH,
                        os.path.join(os.getcwd(), constants.WAREHOUSE_PATH_DEFAULT))

    @property
    def system_path(self) -> str:
        """Index system root; default `<warehouse>/indexes`.

        Parity: reference `index/PathResolver.scala:65-69`.
        """
        configured = self.get(constants.INDEX_SYSTEM_PATH)
        if configured:
            return configured
        return os.path.join(self.warehouse_dir, constants.INDEXES_DIR)

    @property
    def num_buckets(self) -> int:
        return self.get_int(constants.INDEX_NUM_BUCKETS,
                            constants.INDEX_NUM_BUCKETS_DEFAULT)

    @property
    def distribution(self) -> str:
        """"auto" | "true" | "false" — see `parallel/context.py`."""
        return (self.get(constants.DISTRIBUTION_ENABLED,
                         constants.DISTRIBUTION_ENABLED_DEFAULT) or
                "auto").lower()

    @property
    def trace_dir(self):
        """Directory for XLA profiler traces of executed queries (None =
        tracing off)."""
        return self.get(constants.TRACE_DIR)

    @property
    def fusion_enabled(self) -> bool:
        """Whole-stage fusion (engine/fusion.py): operator chains compile
        into one jitted executable per chain instead of eager
        per-operator dispatch."""
        return (self.get(constants.FUSION_ENABLED,
                         constants.FUSION_ENABLED_DEFAULT)
                or "true").lower() == "true"

    @property
    def min_device_rows(self) -> int:
        """Batches below this row count run on the host lane."""
        return self.get_int(constants.MIN_DEVICE_ROWS,
                            constants.MIN_DEVICE_ROWS_DEFAULT)

    @property
    def distribution_min_rows(self) -> int:
        return self.get_int(constants.DISTRIBUTION_MIN_ROWS,
                            constants.DISTRIBUTION_MIN_ROWS_DEFAULT)

    @property
    def distribution_spmd(self) -> bool:
        """Born-sharded SPMD execution lane (`parallel/spmd.py`) on/off;
        off = the legacy per-query-placement mesh path."""
        return (self.get(constants.DISTRIBUTION_SPMD,
                         constants.DISTRIBUTION_SPMD_DEFAULT)
                or "true").lower() == "true"

    @property
    def distribution_slices(self) -> int:
        """Number of slices (DCN rows) in the mesh topology.
        `distribution.slices` is canonical; the original
        `distribution.dcn.size` spelling is the legacy fallback."""
        value = self.get(constants.DISTRIBUTION_SLICES)
        if value is not None:
            try:
                return int(value)
            except ValueError:
                return constants.DISTRIBUTION_DCN_SIZE_DEFAULT
        return self.get_int(constants.DISTRIBUTION_DCN_SIZE,
                            constants.DISTRIBUTION_DCN_SIZE_DEFAULT)

    @property
    def distribution_replication(self) -> bool:
        """Read replication across slices (`parallel/replica.py`): each
        slice serves as a full replica and the scheduler routes queries
        to the least-loaded one."""
        return (self.get(constants.DISTRIBUTION_REPLICATION,
                         constants.DISTRIBUTION_REPLICATION_DEFAULT)
                or "true").lower() == "true"

    @property
    def distribution_replication_min_slices(self) -> int:
        return self.get_int(
            constants.DISTRIBUTION_REPLICATION_MIN_SLICES,
            constants.DISTRIBUTION_REPLICATION_MIN_SLICES_DEFAULT)

    @property
    def distribution_replication_hot_fraction(self) -> float:
        value = self.get(constants.DISTRIBUTION_REPLICATION_HOT_FRACTION)
        return (float(value) if value is not None else
                constants.DISTRIBUTION_REPLICATION_HOT_FRACTION_DEFAULT)

    @property
    def distribution_dict_max_entries(self) -> int:
        """Per-range string-dictionary entry cap for the recorded
        born-sharded layout (`_shard_layout.json`); <= 0 disables
        recording (readers derive dictionaries from the files)."""
        return self.get_int(constants.DISTRIBUTION_DICT_MAX_ENTRIES,
                            constants.DISTRIBUTION_DICT_MAX_ENTRIES_DEFAULT)

    @property
    def broadcast_threshold(self) -> int:
        """Join sides estimated under this many bytes broadcast as a
        direct-address table instead of riding Exchange+Sort; <= 0
        disables (Spark `autoBroadcastJoinThreshold` analog)."""
        return self.get_int(constants.BROADCAST_THRESHOLD,
                            constants.BROADCAST_THRESHOLD_DEFAULT)

    @property
    def read_cache_bytes(self):
        """Host decoded-batch cache budget; None = env/process default.
        The cache itself is PROCESS-wide — a session that sets this
        governs the shared cache while its queries run, so sessions
        sharing a process should agree on it."""
        value = self.get(constants.READ_CACHE_BYTES_KEY)
        return int(value) if value is not None else None

    @property
    def device_cache_bytes(self):
        """Legacy spelling of the HBM segment-cache budget (the old
        device-batch LRU); kept as the fallback key for
        `segment_cache_bytes`."""
        value = self.get(constants.DEVICE_CACHE_BYTES_KEY)
        return int(value) if value is not None else None

    @property
    def segment_cache_bytes(self):
        """HBM segment-cache budget (`io/segcache.py`); None = the
        legacy `cache.device.bytes` key, then the env/process default.
        Competes with join/sort working sets for device memory — lower
        it (or 0) when large queries OOM; 0 releases already-resident
        segments. Process-wide cache, same caveat as
        read_cache_bytes."""
        value = self.get(constants.SEGMENT_CACHE_BYTES_KEY)
        if value is not None:
            return int(value)
        return self.device_cache_bytes

    @property
    def segment_cache_host_bytes(self) -> int:
        """Host-RAM tier budget of the tiered segment cache
        (`io/segcache.py`): device-tier evictions demote into host
        memory up to this many bytes instead of dropping, and a later
        read re-promotes through the TransferEngine fill lane (H2D
        paid, parquet decode skipped). 0 (default) disables the tier."""
        return self.get_int(constants.SEGMENT_CACHE_HOST_BYTES_KEY,
                            constants.SEGMENT_CACHE_HOST_BYTES_DEFAULT)

    @property
    def segment_cache_pin_indexes(self) -> str:
        """Comma-separated index names whose cached segments are never
        evicted by byte pressure (invalidation still drops them)."""
        return self.get(constants.SEGMENT_CACHE_PIN_INDEXES, "") or ""

    @property
    def fusion_promote_cache_bytes(self) -> int:
        """Byte budget for the fusion device-promotion cache (host
        source columns held device-resident between executions); evicts
        dead-source entries first, then oldest-inserted."""
        return self.get_int(constants.FUSION_PROMOTE_CACHE_BYTES,
                            constants.FUSION_PROMOTE_CACHE_BYTES_DEFAULT)

    @property
    def fusion_bcast_cache_bytes(self) -> int:
        """Byte budget for the broadcast direct-address table cache."""
        return self.get_int(constants.FUSION_BCAST_CACHE_BYTES,
                            constants.FUSION_BCAST_CACHE_BYTES_DEFAULT)

    @property
    def io_retry_attempts(self) -> int:
        """Total tries (first call included) for transient storage-IO
        failures; see `utils/retry.py`."""
        return self.get_int(constants.IO_RETRY_ATTEMPTS,
                            constants.IO_RETRY_ATTEMPTS_DEFAULT)

    @property
    def io_retry_base_ms(self) -> float:
        """First backoff delay; doubles per retry (jittered)."""
        return float(self.get(constants.IO_RETRY_BASE_MS,
                              str(constants.IO_RETRY_BASE_MS_DEFAULT)))

    @property
    def io_retry_max_ms(self) -> float:
        """Backoff ceiling per retry."""
        return float(self.get(constants.IO_RETRY_MAX_MS,
                              str(constants.IO_RETRY_MAX_MS_DEFAULT)))

    @property
    def io_transfer_chunk_bytes(self) -> int:
        """Chunk granularity of pipelined H2D stagings
        (`io/transfer.py`); large arrays ship as row chunks of at most
        this many bytes."""
        return self.get_int(constants.IO_TRANSFER_CHUNK_BYTES,
                            constants.IO_TRANSFER_CHUNK_BYTES_DEFAULT)

    @property
    def io_transfer_inflight_bytes(self) -> int:
        """Bound on bytes in flight over the device link across all
        outstanding puts (the transfer engine blocks the oldest put
        before admitting more)."""
        return self.get_int(constants.IO_TRANSFER_INFLIGHT_BYTES,
                            constants.IO_TRANSFER_INFLIGHT_BYTES_DEFAULT)

    @property
    def io_transfer_threads(self) -> int:
        """Staging-thread pool width: how many column decodes / chunk
        conversions can run ahead of the link."""
        return self.get_int(constants.IO_TRANSFER_THREADS,
                            constants.IO_TRANSFER_THREADS_DEFAULT)

    @property
    def io_transfer_acquire_timeout_ms(self) -> float:
        """Bound on waiting for in-flight-window headroom before a put
        raises a typed transient `TransferAcquireTimeoutError` instead
        of hanging on bytes a dead transfer never released; <= 0
        disables the bound."""
        return float(self.get(
            constants.IO_TRANSFER_ACQUIRE_TIMEOUT_MS,
            str(constants.IO_TRANSFER_ACQUIRE_TIMEOUT_MS_DEFAULT)))

    @property
    def serve_hbm_budget_bytes(self) -> int:
        """Serving-plane admission budget: the sum of concurrently
        admitted queries' projected HBM footprints stays under this; 0
        (the default) disables budgeting. Process-wide scheduler —
        co-resident sessions should agree (same caveat as the transfer
        knobs)."""
        return self.get_int(constants.SERVE_HBM_BUDGET_BYTES,
                            constants.SERVE_HBM_BUDGET_BYTES_DEFAULT)

    @property
    def serve_queue_depth(self) -> int:
        """How many over-budget queries may WAIT for admission; a query
        arriving at a full queue gets a typed QueryRejectedError
        immediately (backpressure to the caller)."""
        return self.get_int(constants.SERVE_QUEUE_DEPTH,
                            constants.SERVE_QUEUE_DEPTH_DEFAULT)

    @property
    def serve_deadline_seconds(self) -> float:
        """Default per-query deadline (queued time included); 0 = none.
        `collect(timeout=...)` overrides per call."""
        return float(self.get(constants.SERVE_DEADLINE_SECONDS,
                              str(constants.SERVE_DEADLINE_SECONDS_DEFAULT)))

    @property
    def serve_batch_enabled(self) -> bool:
        """Inter-query batched execution (`engine/batcher.py`):
        concurrent same-signature point/filter queries coalesce into
        one jitted predicate program over the shared scan. "false"
        restores strictly per-query execution."""
        return (self.get(constants.SERVE_BATCH_ENABLED,
                         constants.SERVE_BATCH_ENABLED_DEFAULT)
                or "true").lower() == "true"

    @property
    def serve_batch_window_ms(self) -> float:
        """Gather window: how long the first query of a signature waits
        for cohort joiners before executing. Skipped when nothing else
        is in flight (serial latency untouched)."""
        return float(self.get(
            constants.SERVE_BATCH_WINDOW_MS,
            str(constants.SERVE_BATCH_WINDOW_MS_DEFAULT)))

    @property
    def serve_batch_max(self) -> int:
        """Cohort-size cap per batched invocation; also the top padded
        constant-lane bucket (cohorts pad to the next power of two up
        to this, so K is a compile bucket, not a retrace)."""
        return self.get_int(constants.SERVE_BATCH_MAX,
                            constants.SERVE_BATCH_MAX_DEFAULT)

    @property
    def serve_batch_aot_warmup(self) -> bool:
        """Pre-compile the canonical cohort-size buckets of a batch
        signature the first time it is seen (and for the explicit
        `engine.batcher.warmup(df)` replica API)."""
        return (self.get(constants.SERVE_BATCH_AOT_WARMUP,
                         constants.SERVE_BATCH_AOT_WARMUP_DEFAULT)
                or "true").lower() == "true"

    @property
    def serve_breaker_failures(self) -> int:
        """Degraded-fallback count within the window that OPENS a
        per-index circuit breaker (known-bad index skips straight to
        the source plan)."""
        return self.get_int(constants.SERVE_BREAKER_FAILURES,
                            constants.SERVE_BREAKER_FAILURES_DEFAULT)

    @property
    def serve_breaker_window_seconds(self) -> float:
        return float(self.get(
            constants.SERVE_BREAKER_WINDOW_SECONDS,
            str(constants.SERVE_BREAKER_WINDOW_SECONDS_DEFAULT)))

    @property
    def serve_breaker_cooldown_seconds(self) -> float:
        """Open-state dwell before one half-open probe is allowed."""
        return float(self.get(
            constants.SERVE_BREAKER_COOLDOWN_SECONDS,
            str(constants.SERVE_BREAKER_COOLDOWN_SECONDS_DEFAULT)))

    @property
    def serve_slo_p99_seconds(self) -> float:
        """Sliding-window SLO target: 99% of queries must finish under
        this many seconds. 0 (default) disables SLO tracking."""
        return float(self.get(constants.SERVE_SLO_P99_SECONDS,
                              str(constants.SERVE_SLO_P99_SECONDS_DEFAULT)))

    @property
    def serve_slo_window_seconds(self) -> float:
        """Span of the sliding window the burn rate is computed over
        (also the default trailing window of the timeseries sampler's
        `window.*` quantile gauges)."""
        return float(self.get(
            constants.SERVE_SLO_WINDOW_SECONDS,
            str(constants.SERVE_SLO_WINDOW_SECONDS_DEFAULT)))

    @property
    def serve_slo_shed_enabled(self) -> bool:
        """Opt-in load shedding: while the SLO burn rate exceeds 1.0
        the admission wait queue is tightened to half its configured
        depth (`serve.slo.shed` counts queries the tightening
        rejected). Off by default — tracking alone never sheds."""
        return (self.get(constants.SERVE_SLO_SHED_ENABLED,
                         constants.SERVE_SLO_SHED_ENABLED_DEFAULT)
                or "false").lower() == "true"

    # -- multi-tenant serving (tenant id embedded in the conf key) -----

    def serve_tenant_weight(self, tenant: str) -> float:
        """Deficit-round-robin dequeue weight for `tenant` (default
        1.0). Relative: a weight-2 tenant drains its wait queue twice
        as fast as a weight-1 tenant under contention."""
        v = self.get(f"{constants.SERVE_TENANT_PREFIX}{tenant}.weight")
        try:
            w = float(v) if v is not None else \
                constants.SERVE_TENANT_WEIGHT_DEFAULT
        except ValueError:
            w = constants.SERVE_TENANT_WEIGHT_DEFAULT
        return w if w > 0 else constants.SERVE_TENANT_WEIGHT_DEFAULT

    def serve_tenant_hbm_fraction(self, tenant: str) -> float:
        """Fraction of `serve.hbm.budget.bytes` the tenant may hold
        admitted concurrently (0, the default, = unlimited)."""
        v = self.get(
            f"{constants.SERVE_TENANT_PREFIX}{tenant}.hbm.fraction")
        try:
            f = float(v) if v is not None else \
                constants.SERVE_TENANT_HBM_FRACTION_DEFAULT
        except ValueError:
            f = constants.SERVE_TENANT_HBM_FRACTION_DEFAULT
        return min(max(f, 0.0), 1.0)

    def serve_tenant_queue_depth(self, tenant: str) -> int:
        """Per-tenant cap on WAITING queries (0, the default, = only
        the global `serve.queue.depth` applies)."""
        return self.get_int(
            f"{constants.SERVE_TENANT_PREFIX}{tenant}.queue.depth",
            constants.SERVE_TENANT_QUEUE_DEPTH_DEFAULT)

    def advisor_tenant_budget_bytes(self, tenant: str) -> int:
        """Per-tenant cap on summed estimated index bytes the advisor
        may auto-build for candidates mined from that tenant's queries
        (0, the default, = only the global advisor budget applies)."""
        return self.get_int(
            f"{constants.ADVISOR_TENANT_PREFIX}{tenant}.budget.bytes",
            constants.ADVISOR_TENANT_BUDGET_BYTES_DEFAULT)

    @property
    def telemetry_ops_port(self) -> Optional[int]:
        """Operations-plane HTTP port (`telemetry/ops_server.py`):
        unset (default) = no server; 0 = bind an ephemeral port; any
        other value = bind that port. Setting it also starts the
        background timeseries sampler."""
        value = self.get(constants.TELEMETRY_OPS_PORT)
        if value is None or value == "":
            return None
        return int(value)

    @property
    def telemetry_ops_host(self) -> str:
        """Bind address of the ops server — localhost by default (the
        endpoints are unauthenticated; exposing them wider is an
        explicit decision)."""
        return self.get(constants.TELEMETRY_OPS_HOST,
                        constants.TELEMETRY_OPS_HOST_DEFAULT) \
            or constants.TELEMETRY_OPS_HOST_DEFAULT

    @property
    def timeseries_interval_seconds(self) -> float:
        """Fixed sampling interval of the background timeseries
        sampler (`telemetry/timeseries.py`)."""
        return float(self.get(
            constants.TELEMETRY_TIMESERIES_INTERVAL_SECONDS,
            str(constants.TELEMETRY_TIMESERIES_INTERVAL_SECONDS_DEFAULT)))

    @property
    def timeseries_capacity(self) -> int:
        """Bound on the sampler's ring (samples retained; older samples
        rotate out)."""
        return self.get_int(
            constants.TELEMETRY_TIMESERIES_CAPACITY,
            constants.TELEMETRY_TIMESERIES_CAPACITY_DEFAULT)

    @property
    def slowlog_seconds(self) -> float:
        """Slow-query dump threshold for the flight recorder
        (`telemetry/flight.py`): any query whose wall exceeds this many
        seconds persists its full metric tree, a registry snapshot,
        and a trace slice to `slowlog_dir`. 0 (the default) disables
        dumping; the in-memory ring of recent queries is always on."""
        return float(self.get(constants.TELEMETRY_SLOWLOG_SECONDS,
                              str(constants.TELEMETRY_SLOWLOG_SECONDS_DEFAULT)))

    @property
    def slowlog_dir(self) -> str:
        """Slow-query dump directory; default `<warehouse>/slowlog`."""
        configured = self.get(constants.TELEMETRY_SLOWLOG_DIR)
        if configured:
            return configured
        return os.path.join(self.warehouse_dir, "slowlog")

    @property
    def slowlog_keep(self) -> int:
        """How many slow-query dump files to retain (oldest pruned)."""
        return self.get_int(constants.TELEMETRY_SLOWLOG_KEEP,
                            constants.TELEMETRY_SLOWLOG_KEEP_DEFAULT)

    @property
    def critpath_enabled(self) -> bool:
        """Per-query critical-path stamping
        (`telemetry/critical_path.py`): "false" skips the decomposition
        at query finish (the per-segment source counters still
        record)."""
        return (self.get(constants.TELEMETRY_CRITPATH_ENABLED,
                         constants.TELEMETRY_CRITPATH_ENABLED_DEFAULT)
                or "true").lower() == "true"

    @property
    def profiler_enabled(self) -> bool:
        """Host sampling profiler (`telemetry/profiler.py`): "true"
        starts the stack-sampling daemon at session init."""
        return (self.get(constants.TELEMETRY_PROFILER_ENABLED,
                         constants.TELEMETRY_PROFILER_ENABLED_DEFAULT)
                or "false").lower() == "true"

    @property
    def profiler_hz(self) -> float:
        """Stack-sampling rate of the host profiler (samples/second;
        the default sits off the 10/100 Hz grid to avoid aliasing
        periodic work)."""
        return float(self.get(
            constants.TELEMETRY_PROFILER_HZ,
            str(constants.TELEMETRY_PROFILER_HZ_DEFAULT)))

    @property
    def profiler_capture_seconds(self) -> float:
        """Length of a TRIGGERED device-trace capture (SLO burn or a
        slowlog dump fires one). 0 (the default) disarms triggered
        capture."""
        return float(self.get(
            constants.TELEMETRY_PROFILER_CAPTURE_SECONDS,
            str(constants.TELEMETRY_PROFILER_CAPTURE_SECONDS_DEFAULT)))

    @property
    def profiler_capture_keep(self) -> int:
        """How many triggered `profile-*` capture directories to
        retain next to the slow-query dumps (oldest pruned)."""
        return self.get_int(
            constants.TELEMETRY_PROFILER_CAPTURE_KEEP,
            constants.TELEMETRY_PROFILER_CAPTURE_KEEP_DEFAULT)

    @property
    def profiler_capture_min_interval_s(self) -> float:
        """Rate limit between triggered captures — a sustained SLO
        burn produces a trickle of profiles, not a flood."""
        return float(self.get(
            constants.TELEMETRY_PROFILER_CAPTURE_MIN_INTERVAL_SECONDS,
            str(constants
                .TELEMETRY_PROFILER_CAPTURE_MIN_INTERVAL_SECONDS_DEFAULT)))

    @property
    def telemetry_history_enabled(self) -> bool:
        """Durable on-lake telemetry history (`telemetry/history.py`):
        "true" makes the sampler's tick hook flush periodic history
        segments under `telemetry_history_dir`. Off by default — the
        history store writes to the warehouse, which is an explicit
        operator decision."""
        return (self.get(constants.TELEMETRY_HISTORY_ENABLED,
                         constants.TELEMETRY_HISTORY_ENABLED_DEFAULT)
                or "false").lower() == "true"

    @property
    def telemetry_history_dir(self) -> str:
        """History segment directory; defaults to
        `constants.TELEMETRY_HISTORY_DIRNAME` under the warehouse
        (telemetry history is metadata, and metadata lives on the
        lake)."""
        configured = self.get(constants.TELEMETRY_HISTORY_DIR)
        if configured:
            return configured
        return os.path.join(self.warehouse_dir,
                            constants.TELEMETRY_HISTORY_DIRNAME)

    @property
    def telemetry_history_interval_seconds(self) -> float:
        """Minimum seconds between periodic history flushes (incident
        flushes are immediate and ignore this)."""
        return float(self.get(
            constants.TELEMETRY_HISTORY_INTERVAL_SECONDS,
            str(constants.TELEMETRY_HISTORY_INTERVAL_SECONDS_DEFAULT)))

    @property
    def telemetry_history_keep_seconds(self) -> float:
        """Age past which history segments are pruned (0 = keep by
        byte budget only)."""
        return float(self.get(
            constants.TELEMETRY_HISTORY_KEEP_SECONDS,
            str(constants.TELEMETRY_HISTORY_KEEP_SECONDS_DEFAULT)))

    @property
    def telemetry_history_keep_bytes(self) -> int:
        """Total byte budget of the history directory; oldest segments
        pruned beyond it (0 = no byte bound)."""
        return self.get_int(constants.TELEMETRY_HISTORY_KEEP_BYTES,
                            constants.TELEMETRY_HISTORY_KEEP_BYTES_DEFAULT)

    @property
    def alerts_enabled(self) -> bool:
        """Rule-driven alerting (`telemetry/alerts.py`): "false" skips
        rule evaluation on sampler ticks entirely."""
        return (self.get(constants.TELEMETRY_ALERTS_ENABLED,
                         constants.TELEMETRY_ALERTS_ENABLED_DEFAULT)
                or "true").lower() == "true"

    def alert_rule_override(self, rule: str, knob: str) -> Optional[str]:
        """Per-rule alert override (`telemetry.alerts.rule.<rule>.
        <knob>`), or None when unset. Knobs: `enabled`, `threshold`,
        `clear`, `sustain.seconds`, `window.seconds`."""
        return self.get(
            f"{constants.TELEMETRY_ALERTS_RULE_PREFIX}{rule}.{knob}")

    @property
    def skipping_enabled(self) -> bool:
        """Query-side gate on data-skipping pruning (`plan/rules/
        skipping.py`): "false" stops FilterIndexRule consulting sketch
        blobs (unpruned scans — correct, just unaccelerated). Build
        verbs ignore it."""
        return (self.get(constants.SKIPPING_ENABLED,
                         constants.SKIPPING_ENABLED_DEFAULT)
                or "true").lower() == "true"

    @property
    def skipping_bloom_fpp(self) -> float:
        """Target false-positive rate of the per-file blocked bloom
        filters; sizes the filter from the file's row count."""
        return float(self.get(constants.SKIPPING_BLOOM_FPP,
                              str(constants.SKIPPING_BLOOM_FPP_DEFAULT)))

    @property
    def skipping_bloom_max_bytes(self) -> int:
        """Per-file, per-column cap on bloom filter bytes — a huge file
        gets a degraded (higher-FPP) filter, never an unbounded blob."""
        return self.get_int(constants.SKIPPING_BLOOM_MAX_BYTES,
                            constants.SKIPPING_BLOOM_MAX_BYTES_DEFAULT)

    @property
    def skipping_zorder_files(self) -> int:
        """Output file count of the optional Z-order clustering rewrite
        at data-skipping build time (more files = tighter zones)."""
        return self.get_int(constants.SKIPPING_ZORDER_FILES,
                            constants.SKIPPING_ZORDER_FILES_DEFAULT)

    @property
    def compile_cache_dir(self):
        """Directory for JAX's persistent compilation cache (warm-start
        compilation: a fresh replica's first canonical-shape query
        loads persisted executables instead of tracing). None = off.
        Wired at session init via
        `telemetry.compilation.configure_persistent_cache`."""
        return self.get(constants.COMPILE_CACHE_DIR)

    @property
    def advisor_enabled(self) -> bool:
        """Self-driving index advisor (`hyperspace_tpu/advisor/`) on/off
        — "false" makes `IndexAdvisor.run_once` a mine-only no-op (no
        recommendations acted on, no builds)."""
        return (self.get(constants.ADVISOR_ENABLED,
                         constants.ADVISOR_ENABLED_DEFAULT)
                or "true").lower() == "true"

    @property
    def advisor_build_budget_bytes(self) -> int:
        """Per-run cap on summed ESTIMATED index bytes the advisor may
        build (its per-warehouse build budget)."""
        return self.get_int(constants.ADVISOR_BUILD_BUDGET_BYTES,
                            constants.ADVISOR_BUILD_BUDGET_BYTES_DEFAULT)

    @property
    def advisor_max_builds(self) -> int:
        """How many builds one advisor run may start."""
        return self.get_int(constants.ADVISOR_MAX_BUILDS,
                            constants.ADVISOR_MAX_BUILDS_DEFAULT)

    @property
    def advisor_serve_headroom(self) -> float:
        """Fraction of `serve.hbm.budget.bytes` that may be admitted
        before the advisor defers its builds (never starve admission)."""
        return float(self.get(
            constants.ADVISOR_SERVE_HEADROOM,
            str(constants.ADVISOR_SERVE_HEADROOM_DEFAULT)))

    @property
    def advisor_min_benefit_bytes(self) -> int:
        """Minimum amortized bytes-avoided estimate before a candidate
        is recommended."""
        return self.get_int(constants.ADVISOR_MIN_BENEFIT_BYTES,
                            constants.ADVISOR_MIN_BENEFIT_BYTES_DEFAULT)

    @property
    def advisor_skipping_prune_fraction(self) -> float:
        """Assumed prune effectiveness of a hypothetical data-skipping
        index in the what-if math (sketches don't exist yet, so this is
        a conservative constant, not a measurement)."""
        return float(self.get(
            constants.ADVISOR_SKIPPING_PRUNE_FRACTION,
            str(constants.ADVISOR_SKIPPING_PRUNE_FRACTION_DEFAULT)))

    @property
    def advisor_min_repeats(self) -> int:
        """Observed repeat count below which a workload signature is
        not considered recurring."""
        return self.get_int(constants.ADVISOR_MIN_REPEATS,
                            constants.ADVISOR_MIN_REPEATS_DEFAULT)

    @property
    def ingest_interval_seconds(self) -> float:
        """Cadence between ingest-coordinator micro-batch ticks; the
        caller's loop sleeps this long between `run_once` calls (the
        coordinator never owns a thread)."""
        return float(self.get(constants.INGEST_INTERVAL_SECONDS,
                              str(constants.INGEST_INTERVAL_SECONDS_DEFAULT)))

    @property
    def ingest_serve_headroom(self) -> float:
        """Fraction of `serve.hbm.budget.bytes` that may be admitted
        before the ingest coordinator defers index refresh (appends
        still land; refresh never starves admission)."""
        return float(self.get(constants.INGEST_SERVE_HEADROOM,
                              str(constants.INGEST_SERVE_HEADROOM_DEFAULT)))

    @property
    def ingest_conflict_attempts(self) -> int:
        """Total refresh tries per tick when the coordinator loses the
        op-log race to a manual refresher, before it concedes."""
        return self.get_int(constants.INGEST_CONFLICT_ATTEMPTS,
                            constants.INGEST_CONFLICT_ATTEMPTS_DEFAULT)

    @property
    def maintenance_lease_seconds(self) -> int:
        """Age past which a transient op-log entry is treated as a crashed
        writer and auto-recovered (Cancel FSM) by the next maintenance
        action; `Hyperspace.recover_index` forces it immediately."""
        return self.get_int(constants.MAINTENANCE_LEASE_SECONDS,
                            constants.MAINTENANCE_LEASE_SECONDS_DEFAULT)

    @property
    def cache_expiry_seconds(self) -> int:
        return self.get_int(
            constants.INDEX_CACHE_EXPIRY_DURATION_SECONDS,
            constants.INDEX_CACHE_EXPIRY_DURATION_SECONDS_DEFAULT)

    def copy(self) -> "HyperspaceConf":
        return HyperspaceConf(dict(self._conf))
