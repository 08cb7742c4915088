"""Framework-wide constants: config keys, op-log layout, lifecycle states.

Parity: reference `index/IndexConstants.scala:21-50` and
`actions/Constants.scala:19-33`. Config keys keep the reference's
`spark.hyperspace.*` spelling (so existing user configs translate 1:1) and the
`hyperspace.*` short form is accepted as an alias (see `config.py`).
"""

INDEXES_DIR = "indexes"

# Config keys (reference `index/IndexConstants.scala:24-35`).
INDEX_SYSTEM_PATH = "spark.hyperspace.system.path"
INDEX_CREATION_PATH = "spark.hyperspace.index.creation.path"
INDEX_SEARCH_PATHS = "spark.hyperspace.index.search.paths"
INDEX_NUM_BUCKETS = "spark.hyperspace.index.num.buckets"
# The reference defaults numBuckets to spark.sql.shuffle.partitions (= 200).
# On TPU the analogous width is chosen to divide evenly over typical mesh
# sizes; 200 is kept for drop-in config parity.
INDEX_NUM_BUCKETS_DEFAULT = 200

INDEX_CACHE_EXPIRY_DURATION_SECONDS = (
    "spark.hyperspace.index.cache.expiryDurationInSeconds")
INDEX_CACHE_EXPIRY_DURATION_SECONDS_DEFAULT = 300

# Decoded-batch cache budgets (no reference analog — Spark's block manager
# owns executor memory there). Session-conf keys; when unset, the
# HYPERSPACE_READ_CACHE_BYTES / HYPERSPACE_DEVICE_CACHE_BYTES env vars
# (read at `io/parquet.py` import) provide the process-wide defaults.
# The device budget shares HBM with join/sort working sets — size it
# against the largest query, not the chip.
READ_CACHE_BYTES_KEY = "spark.hyperspace.cache.read.bytes"
DEVICE_CACHE_BYTES_KEY = "spark.hyperspace.cache.device.bytes"

# HBM segment cache (`io/segcache.py`): byte budget for device-resident
# index segments (falls back to the legacy `cache.device.bytes` key,
# then the HYPERSPACE_SEGMENT_CACHE_BYTES / HYPERSPACE_DEVICE_CACHE_BYTES
# env defaults), and a comma-separated list of index names whose
# segments are PINNED — never evicted by byte pressure (invalidation on
# refresh/optimize/vacuum still drops them). When a serving budget
# (`serve.hbm.budget.bytes`) is set, the cache's effective budget is
# additionally capped by what that budget leaves after non-cache device
# residency (one truth with the admission controller).
SEGMENT_CACHE_BYTES_KEY = "spark.hyperspace.cache.segments.bytes"
SEGMENT_CACHE_PIN_INDEXES = "spark.hyperspace.cache.segments.pin.indexes"

# Tiered segment cache: host-RAM tier below HBM (`io/segcache.py`).
# When > 0, a segment evicted from the device tier by byte pressure is
# DEMOTED into a host-resident copy (decoded columns fetched D2H once)
# instead of dropped outright, up to this many host bytes (host LRU
# past the budget evicts for real). A later read of a demoted key
# re-promotes through the TransferEngine fill lane — H2D cost paid,
# parquet decode skipped. 0 (the default) disables the tier: eviction
# drops, exactly the pre-tier behavior. Invalidation (refresh/vacuum/
# drop) sweeps both tiers.
SEGMENT_CACHE_HOST_BYTES_KEY = "spark.hyperspace.cache.segments.host.bytes"
SEGMENT_CACHE_HOST_BYTES_DEFAULT = 0

# Fusion cache byte budgets: the device-promotion cache (host source
# columns promoted to device-resident jit arguments, keyed by host-array
# identity) and the broadcast-table cache (direct-address join tables,
# keyed by build-column identity) evict dead-source entries first, then
# oldest-inserted, until held bytes fit the budget. Both hold REAL HBM
# on device backends — size them against the chip, and read their
# residency as `cache.fusion_promote.*` / `cache.fusion_bcast.*` in the
# metrics registry.
FUSION_PROMOTE_CACHE_BYTES = "spark.hyperspace.fusion.cache.promote.bytes"
FUSION_PROMOTE_CACHE_BYTES_DEFAULT = 1 * 1024 ** 3
FUSION_BCAST_CACHE_BYTES = "spark.hyperspace.fusion.cache.broadcast.bytes"
FUSION_BCAST_CACHE_BYTES_DEFAULT = 256 * 1024 * 1024

# Broadcast-join size threshold in estimated decoded bytes; <= 0 disables
# (the analog of Spark's `spark.sql.autoBroadcastJoinThreshold`, which
# the reference leans on for dimension joins and its E2E suite pins to
# -1 to force the SMJ path, `E2EHyperspaceRulesTests.scala:42`). Default
# matches Spark's 10 MB.
BROADCAST_THRESHOLD = "spark.hyperspace.broadcast.threshold"
BROADCAST_THRESHOLD_DEFAULT = 10 * 1024 * 1024

# Object-store OCC: backends with no create precondition (neither GCS
# generation match nor S3 conditional put nor atomic exclusive create)
# make write_log RAISE, because check-then-create corrupts the op log
# under concurrency — unless this conf explicitly accepts single-writer
# semantics.
SINGLE_WRITER = "spark.hyperspace.single.writer"

# Storage-IO retry policy (`utils/retry.py`, the ONE backoff point in the
# package — the metrics-coverage lint fails any ad-hoc sleep-in-except
# loop elsewhere). Exponential backoff with deterministic per-operation
# jitter; transient errors (connection resets, timeouts, HTTP 429/5xx,
# torn reads of in-flight publishes) retry up to `attempts` total tries,
# permanent errors (not-found, permission, 4xx) fail immediately.
IO_RETRY_ATTEMPTS = "spark.hyperspace.io.retry.attempts"
IO_RETRY_ATTEMPTS_DEFAULT = 5
IO_RETRY_BASE_MS = "spark.hyperspace.io.retry.base.ms"
IO_RETRY_BASE_MS_DEFAULT = 20
IO_RETRY_MAX_MS = "spark.hyperspace.io.retry.max.ms"
IO_RETRY_MAX_MS_DEFAULT = 2000

# Pipelined transfer engine (`io/transfer.py`, THE host<->device link
# seam): chunk granularity of large H2D stagings, the bounded in-flight
# byte window across all outstanding puts, and the staging-thread pool
# width (decode/convert of chunk i+1 overlaps chunk i's transfer).
# Tune chunk.bytes against the link: small enough that several chunks
# pipeline, large enough that the per-put dispatch latency amortizes.
IO_TRANSFER_CHUNK_BYTES = "spark.hyperspace.io.transfer.chunk.bytes"
IO_TRANSFER_CHUNK_BYTES_DEFAULT = 4 * 1024 * 1024
IO_TRANSFER_INFLIGHT_BYTES = "spark.hyperspace.io.transfer.inflight.bytes"
IO_TRANSFER_INFLIGHT_BYTES_DEFAULT = 64 * 1024 * 1024
IO_TRANSFER_THREADS = "spark.hyperspace.io.transfer.threads"
IO_TRANSFER_THREADS_DEFAULT = 2
# Bound on how long a put may wait for in-flight-window headroom. A put
# that died without releasing its bytes (dead link, hung runtime) would
# otherwise block every later caller forever; past the timeout the
# waiter raises a TYPED transient error (`TransferAcquireTimeoutError`,
# a TimeoutError — `utils/retry.py` classifies it retryable) and counts
# `io.transfer.acquire_timeouts`. <= 0 disables the bound.
IO_TRANSFER_ACQUIRE_TIMEOUT_MS = \
    "spark.hyperspace.io.transfer.acquire.timeout.ms"
IO_TRANSFER_ACQUIRE_TIMEOUT_MS_DEFAULT = 30_000

# Serving plane (`engine/scheduler.py`): every DataFrame.collect routes
# through the process-wide QueryScheduler. Admission control budgets
# concurrent queries' projected HBM footprints against
# `serve.hbm.budget.bytes` (0, the default, disables budgeting — every
# query admits immediately); queries that do not fit wait in a bounded
# FIFO queue of depth `serve.queue.depth`, and when the queue is full
# the caller gets a typed QueryRejectedError at once — backpressure,
# not silent pile-up. `serve.deadline.seconds` gives every query a
# default deadline (0 = none; `collect(timeout=...)` overrides per
# call), enforced cooperatively at operator / fusion-stage / transfer-
# chunk / sorted-run-write boundaries.
SERVE_HBM_BUDGET_BYTES = "spark.hyperspace.serve.hbm.budget.bytes"
SERVE_HBM_BUDGET_BYTES_DEFAULT = 0
SERVE_QUEUE_DEPTH = "spark.hyperspace.serve.queue.depth"
SERVE_QUEUE_DEPTH_DEFAULT = 32
SERVE_DEADLINE_SECONDS = "spark.hyperspace.serve.deadline.seconds"
SERVE_DEADLINE_SECONDS_DEFAULT = 0.0

# Inter-query batched execution (`engine/batcher.py`): concurrent
# point/filter queries sharing one execution signature (same scan
# identity + pinned index version + predicate SHAPE, literals free)
# coalesce into ONE jitted predicate program over the shared resident
# segments — PR-8's coalescing dedupes the cache FILL, this dedupes the
# EXECUTION. The first query of a signature gathers joiners for
# `batch.window.ms` (skipped entirely when nothing else is in flight,
# so serial latency is untouched), up to `batch.max` cohort members per
# invocation; predicate constants ride padded power-of-two lanes so the
# cohort size is a compile-time bucket, not a retrace per K.
# `batch.aot.warmup` pre-compiles the canonical cohort-size buckets the
# first time a signature is seen (and via the explicit
# `engine.batcher.warmup(df)` replica API), riding the persistent
# compile cache (`compile.cache.dir`) so a fresh replica's first
# batched query loads executables instead of tracing.
SERVE_BATCH_ENABLED = "spark.hyperspace.serve.batch.enabled"
SERVE_BATCH_ENABLED_DEFAULT = "true"
SERVE_BATCH_WINDOW_MS = "spark.hyperspace.serve.batch.window.ms"
SERVE_BATCH_WINDOW_MS_DEFAULT = 2.0
SERVE_BATCH_MAX = "spark.hyperspace.serve.batch.max"
SERVE_BATCH_MAX_DEFAULT = 16
SERVE_BATCH_AOT_WARMUP = "spark.hyperspace.serve.batch.aot.warmup"
SERVE_BATCH_AOT_WARMUP_DEFAULT = "true"

# Degradation circuit breaker (per index): after `breaker.failures`
# IndexDataUnavailableError fallbacks within `breaker.window.seconds`,
# the breaker OPENS and queries selecting that index skip straight to
# the source plan without re-paying the failed index scan. After
# `breaker.cooldown.seconds` one probe query is allowed through
# (half-open); success closes the breaker, failure re-opens it.
SERVE_BREAKER_FAILURES = "spark.hyperspace.serve.breaker.failures"
SERVE_BREAKER_FAILURES_DEFAULT = 3
SERVE_BREAKER_WINDOW_SECONDS = "spark.hyperspace.serve.breaker.window.seconds"
SERVE_BREAKER_WINDOW_SECONDS_DEFAULT = 60.0
SERVE_BREAKER_COOLDOWN_SECONDS = \
    "spark.hyperspace.serve.breaker.cooldown.seconds"
SERVE_BREAKER_COOLDOWN_SECONDS_DEFAULT = 30.0

# Sliding-window SLO tracking (`engine/scheduler.py`): when
# `slo.p99.seconds` > 0, every completed query's wall is folded into a
# sliding window of `slo.window.seconds`, queries over the target count
# as `serve.slo.violations`, and the `serve.slo.burn_rate` gauge is the
# observed violation fraction over the 1% a p99 objective allows
# (burn 1.0 = burning the error budget exactly as fast as allowed; > 1
# = the SLO is failing). `slo.shed.enabled` (OFF by default) arms the
# shedding hook: while the burn rate exceeds 1.0, the admission wait
# queue is tightened to half its configured depth, and each query
# rejected by the tightened (rather than the configured) depth counts
# `serve.slo.shed` — controlled load shedding at the admission door
# instead of queue collapse under sustained overload.
SERVE_SLO_P99_SECONDS = "spark.hyperspace.serve.slo.p99.seconds"
SERVE_SLO_P99_SECONDS_DEFAULT = 0.0
SERVE_SLO_WINDOW_SECONDS = "spark.hyperspace.serve.slo.window.seconds"
SERVE_SLO_WINDOW_SECONDS_DEFAULT = 60.0
SERVE_SLO_SHED_ENABLED = "spark.hyperspace.serve.slo.shed.enabled"
SERVE_SLO_SHED_ENABLED_DEFAULT = "false"

# Multi-tenant serving (`engine/scheduler.py`): tenant-keyed knobs
# embed the tenant id in the conf key —
# `serve.tenant.<id>.weight` (float, default 1.0) is the tenant's
# deficit-round-robin share of the admission dequeue; a tenant with
# weight 2 drains its wait queue twice as fast as a weight-1 tenant
# under contention. `serve.tenant.<id>.hbm.fraction` (float in (0, 1],
# default 0 = unlimited) caps the tenant's concurrently-admitted
# footprint at that fraction of `serve.hbm.budget.bytes`;
# `serve.tenant.<id>.queue.depth` (int, default 0 = share the global
# depth) caps how many of the tenant's queries may WAIT at once. The
# default tenant is unlimited unless explicitly configured — existing
# single-tenant deployments see no behavior change.
# `advisor.tenant.<id>.budget.bytes` (default 0 = share the global
# advisor budget) caps auto-built index bytes attributed to that
# tenant's mined candidates.
SERVE_TENANT_PREFIX = "spark.hyperspace.serve.tenant."
SERVE_TENANT_WEIGHT_DEFAULT = 1.0
SERVE_TENANT_HBM_FRACTION_DEFAULT = 0.0
SERVE_TENANT_QUEUE_DEPTH_DEFAULT = 0
ADVISOR_TENANT_PREFIX = "spark.hyperspace.advisor.tenant."
ADVISOR_TENANT_BUDGET_BYTES_DEFAULT = 0

# Operations plane (`telemetry/timeseries.py`, `telemetry/ops_server.py`):
# the background sampler snapshots selected registry series every
# `timeseries.interval.seconds` into a bounded ring of
# `timeseries.capacity` samples, deriving counter rates and sliding-
# window quantiles (`window.<series>.*` gauges). Setting `ops.port`
# starts the in-process HTTP server (and the sampler with it) serving
# `/metrics` (Prometheus text), `/healthz` (scheduler/breaker/cache/
# replica state as JSON), and `/timeseries` (the ring as JSON). The
# server binds `ops.host` — 127.0.0.1 by default: the endpoints are
# unauthenticated operational surfaces, so exposing them beyond
# localhost is an explicit decision. Port 0 binds an ephemeral port
# (read it back from `get_server().port`); unset = no server.
TELEMETRY_OPS_PORT = "spark.hyperspace.telemetry.ops.port"
TELEMETRY_OPS_HOST = "spark.hyperspace.telemetry.ops.host"
TELEMETRY_OPS_HOST_DEFAULT = "127.0.0.1"
TELEMETRY_TIMESERIES_INTERVAL_SECONDS = \
    "spark.hyperspace.telemetry.timeseries.interval.seconds"
TELEMETRY_TIMESERIES_INTERVAL_SECONDS_DEFAULT = 1.0
TELEMETRY_TIMESERIES_CAPACITY = \
    "spark.hyperspace.telemetry.timeseries.capacity"
TELEMETRY_TIMESERIES_CAPACITY_DEFAULT = 600

# Crash recovery lease: a maintenance action that finds the op log's
# latest entry in a TRANSIENT state (CREATING/REFRESHING/...) treats the
# in-flight writer as crashed once the entry is older than this many
# seconds, and runs the Cancel FSM transition back to the last stable
# state before proceeding (`Hyperspace.recover_index` forces the same
# recovery immediately). Size it above the longest expected build.
MAINTENANCE_LEASE_SECONDS = "spark.hyperspace.maintenance.lease.seconds"
MAINTENANCE_LEASE_SECONDS_DEFAULT = 600

HYBRID_SCAN_ENABLED = "spark.hyperspace.index.hybridscan.enabled"

# Data-skipping indexes (`index/sketch.py`, `actions/skipping.py`,
# `plan/rules/skipping.py`): a second index kind flowing through the same
# log/action FSM — per-source-file min/max zone maps + blocked bloom
# filters persisted as a compact parquet sketch blob under the index
# root, consulted at plan time by FilterIndexRule to drop files whose
# zones/blooms refute the predicate. `skipping.enabled` gates the
# QUERY-side consult only (build verbs always work); the bloom knobs
# size the per-file split-block filter (bits from the standard
# -n*ln(p)/ln(2)^2 estimate, rounded up to whole 256-bit blocks and
# capped at `max.bytes` per file per column); `zorder.files` is how
# many clustered output files the optional build-time Z-order rewrite
# produces (more files = tighter zones = finer pruning, at small-file
# cost).
SKIPPING_ENABLED = "spark.hyperspace.index.skipping.enabled"
SKIPPING_ENABLED_DEFAULT = "true"
SKIPPING_BLOOM_FPP = "spark.hyperspace.index.skipping.bloom.fpp"
SKIPPING_BLOOM_FPP_DEFAULT = 0.01
SKIPPING_BLOOM_MAX_BYTES = "spark.hyperspace.index.skipping.bloom.max.bytes"
SKIPPING_BLOOM_MAX_BYTES_DEFAULT = 64 * 1024
SKIPPING_ZORDER_FILES = "spark.hyperspace.index.skipping.zorder.files"
SKIPPING_ZORDER_FILES_DEFAULT = 16

# Per-row lineage (extension; the reference's v0.2 direction): when enabled
# at build time, every index row carries the id of the source file it came
# from (`LINEAGE_COLUMN`, internal — never surfaced in query results) and
# the log entry stores per-file (size, stamp, id) records. Hybrid scan can
# then serve queries over a source with DELETED files by excluding those
# rows, and incremental refresh handles deletions as a per-bucket lineage
# filter instead of a full rebuild.
LINEAGE_ENABLED = "spark.hyperspace.index.lineage.enabled"
LINEAGE_COLUMN = "_hs_file_id"

# Mesh distribution of the data plane (no reference analog — Spark owns the
# cluster there; here the "cluster" is the jax device mesh). Values:
# "auto" (default: distribute when >1 device is visible), "true", "false".
DISTRIBUTION_ENABLED = "spark.hyperspace.distribution.enabled"
DISTRIBUTION_ENABLED_DEFAULT = "auto"
# Minimum row count before the sharded filter scan pays for itself.
DISTRIBUTION_MIN_ROWS = "spark.hyperspace.distribution.min.rows"
DISTRIBUTION_MIN_ROWS_DEFAULT = 4096
# Multi-host topology: number of slices (DCN rows) in the mesh. 1 (the
# default) = a flat single-axis ICI mesh; >1 builds a 2-axis
# (dcn, shard) mesh whose exchanges route hierarchically — the heavy
# re-bucket all_to_all confined to the inner ICI axis, one cross-slice
# hop over DCN (SURVEY §2.12 "DCN only across slices"). This covers the
# build exchange AND the in-program query-time repartitions
# (`parallel/spmd._repartition_lanes` / `repartition_sharded`), whose
# per-axis traffic is attributed as `spmd.repartition.{ici,dcn}.bytes`.
# `distribution.slices` is the canonical knob; the original
# `distribution.dcn.size` spelling is honored as a legacy fallback.
DISTRIBUTION_SLICES = "spark.hyperspace.distribution.slices"
DISTRIBUTION_DCN_SIZE = "spark.hyperspace.distribution.dcn.size"
DISTRIBUTION_DCN_SIZE_DEFAULT = 1

# Read replication across slices (`parallel/replica.py`): on a
# multi-slice mesh, each slice is a full REPLICA — its devices hold the
# whole bucket-range map at slice-local granularity — and the query
# scheduler routes each admitted query's fills + execution to the
# least-loaded replica slice (`serve.replica.*` series). Replicas are
# coherent by construction: the segment cache keys device residency by
# (index root, committed version, bucket range, device set), so a
# version commit invalidates every slice's entries through the same FSM
# hooks. "true" (default) replicates whenever the mesh has >= 2 slices.
DISTRIBUTION_REPLICATION = \
    "spark.hyperspace.distribution.replication.enabled"
DISTRIBUTION_REPLICATION_DEFAULT = "true"
# Minimum slice count before replica routing engages (below it the
# whole mesh executes each query, the PR-10/13 behavior).
DISTRIBUTION_REPLICATION_MIN_SLICES = \
    "spark.hyperspace.distribution.replication.min.slices"
DISTRIBUTION_REPLICATION_MIN_SLICES_DEFAULT = 2
# Hot-bucket mining threshold: a bucket whose flight-ring access count
# reaches this fraction of the hottest bucket's count is HOT — queries
# over hot buckets fan to the least-loaded replica (so hot ranges end
# up resident on >= 2 slices), while provably-cold-range queries pin to
# their range's home slice so cold data is not duplicated across HBMs.
DISTRIBUTION_REPLICATION_HOT_FRACTION = \
    "spark.hyperspace.distribution.replication.hot.fraction"
DISTRIBUTION_REPLICATION_HOT_FRACTION_DEFAULT = 0.5
# Born-sharded SPMD execution (`parallel/spmd.py`): bucketed SMJ /
# scan / aggregate over device-resident bucket-range shards as single
# jitted programs. "true" (default) uses it whenever the shape
# qualifies; "false" forces the legacy per-query placement mesh path
# (the escape hatch if a workload hits an SPMD-lane defect).
DISTRIBUTION_SPMD = "spark.hyperspace.distribution.spmd.enabled"
DISTRIBUTION_SPMD_DEFAULT = "true"
# Born-sharded string layout: a mesh build records each device range's
# sorted local string dictionary in `_shard_layout.json` so query-time
# global-dictionary resolution is pure JSON (no data read). A range
# whose dictionary exceeds this entry cap is recorded as null and the
# reader derives it from the parquet files instead (one host read per
# committed version, then cached). <= 0 disables recording entirely.
DISTRIBUTION_DICT_MAX_ENTRIES = \
    "spark.hyperspace.distribution.dictionary.max.entries"
DISTRIBUTION_DICT_MAX_ENTRIES_DEFAULT = 65536

# Warm-start compilation: when set to a directory, JAX's persistent
# compilation cache is enabled there (jax_compilation_cache_dir) via
# `telemetry/compilation.configure_persistent_cache`, wired at session
# init so every `instrumented_jit` entry point participates. A fresh
# replica pointed at a shared cache dir serves its first
# canonical-shape query from persisted executables instead of paying
# the trace+compile (PR-3's warm-trace==0 property, made to survive
# process restarts). Empty (default) = the process default
# (`_jax_config.py`). Where the knob applies, the size/compile-time
# eligibility floors are dropped to zero so the engine's small bucketed
# kernels qualify. Yields to the environment: with
# JAX_COMPILATION_CACHE_DIR set, the knob is ignored.
COMPILE_CACHE_DIR = "spark.hyperspace.compile.cache.dir"

# Self-driving index advisor (`hyperspace_tpu/advisor/`): mines the
# query flight ring for recurring un-indexed filter/join signatures,
# what-if scores hypothetical covering + data-skipping indexes by
# replaying recorded plans through the real rewrite rules, and
# auto-builds the winners through the normal Create actions (lease,
# OCC, action reports — the executor module is the ONLY sanctioned
# build caller inside advisor/, lint-enforced).
ADVISOR_ENABLED = "spark.hyperspace.advisor.enabled"
ADVISOR_ENABLED_DEFAULT = "true"
# Per-run ceiling on the summed ESTIMATED on-disk bytes of indexes the
# advisor may build (its per-warehouse build budget); candidates past
# the budget are recorded as rejected, not silently dropped.
ADVISOR_BUILD_BUDGET_BYTES = "spark.hyperspace.advisor.build.budget.bytes"
ADVISOR_BUILD_BUDGET_BYTES_DEFAULT = 1 * 1024 ** 3
# How many index builds one advisor run may start (a run that
# recommends ten indexes still builds incrementally over runs).
ADVISOR_MAX_BUILDS = "spark.hyperspace.advisor.max.builds"
ADVISOR_MAX_BUILDS_DEFAULT = 2
# Serving-pressure gate: the advisor defers every build while queries
# wait in the scheduler queue, or while admitted bytes exceed this
# fraction of `serve.hbm.budget.bytes` (advisor builds must never
# starve admission; deferred runs retry on the next cycle).
ADVISOR_SERVE_HEADROOM = "spark.hyperspace.advisor.serve.headroom"
ADVISOR_SERVE_HEADROOM_DEFAULT = 0.5
# Minimum estimated bytes avoided (amortized over the observed repeat
# count) before a candidate is recommended at all.
ADVISOR_MIN_BENEFIT_BYTES = "spark.hyperspace.advisor.min.benefit.bytes"
ADVISOR_MIN_BENEFIT_BYTES_DEFAULT = 0
# Assumed fraction of scan bytes a hypothetical DATA-SKIPPING index
# prunes (zone/bloom effectiveness is unknowable without building the
# sketches; the what-if math uses this conservative constant and the
# docs tell you to tune it against `skipping.bytes_pruned` telemetry).
ADVISOR_SKIPPING_PRUNE_FRACTION = \
    "spark.hyperspace.advisor.skipping.prune.fraction"
ADVISOR_SKIPPING_PRUNE_FRACTION_DEFAULT = 0.5
# Minimum observed repeat count of a workload signature before the
# advisor considers it recurring (one-off queries never justify a
# build).
ADVISOR_MIN_REPEATS = "spark.hyperspace.advisor.min.repeats"
ADVISOR_MIN_REPEATS_DEFAULT = 2

# Continuous-ingest coordinator (`engine/ingest.py`): cadence between
# micro-batch ticks when the caller drives `run_once` on a timer. The
# coordinator itself never spawns threads (the engine thread seam keeps
# background threads in `scheduler.py`); this is the interval the
# owning loop should sleep between ticks.
INGEST_INTERVAL_SECONDS = "spark.hyperspace.ingest.interval.seconds"
INGEST_INTERVAL_SECONDS_DEFAULT = 5.0
# Serving-pressure gate, same shape as the advisor's: refresh work is
# deferred while queries wait for admission, or while admitted bytes
# exceed this fraction of `serve.hbm.budget.bytes`. Appends still land
# (the source is append-only either way); only index refresh yields.
INGEST_SERVE_HEADROOM = "spark.hyperspace.ingest.serve.headroom"
INGEST_SERVE_HEADROOM_DEFAULT = 0.5
# Total tries the coordinator makes when a refresh loses the op-log
# race to a manual refresher (typed conflict → bounded jittered backoff
# via `utils/retry.py`, then a clean concession — never an error).
INGEST_CONFLICT_ATTEMPTS = "spark.hyperspace.ingest.conflict.attempts"
INGEST_CONFLICT_ATTEMPTS_DEFAULT = 3

# XLA profiler integration: when set to a directory, every executed
# query is captured as a profiler trace under it (one subdirectory per
# query), viewable in TensorBoard/XProf/Perfetto. Empty (default) = off.
TRACE_DIR = "spark.hyperspace.trace.dir"

# Query flight recorder (`telemetry/flight.py`): the bounded ring of
# the last-K completed QueryMetrics is ALWAYS on (it costs one deque
# append per query); the slow-query dump persists the full metric
# tree + registry snapshot + trace slice of any query whose wall
# exceeds `slowlog.seconds` (0, the default, disables dumping). Dumps
# land under `slowlog.dir` (default `<warehouse>/slowlog`); only the
# newest `slowlog.keep` dump files are retained.
TELEMETRY_SLOWLOG_SECONDS = "spark.hyperspace.telemetry.slowlog.seconds"
TELEMETRY_SLOWLOG_SECONDS_DEFAULT = 0.0
TELEMETRY_SLOWLOG_DIR = "spark.hyperspace.telemetry.slowlog.dir"
TELEMETRY_SLOWLOG_KEEP = "spark.hyperspace.telemetry.slowlog.keep"
TELEMETRY_SLOWLOG_KEEP_DEFAULT = 20

# Critical-path decomposition (`telemetry/critical_path.py`): every
# scheduled query's wall is decomposed into the closed segment set
# (queue_wait/batch_window/.../host_python residual), stamped onto its
# QueryMetrics, and published as `critpath.<segment>.seconds` counters.
# "false" skips the per-query stamp (the source counters still record).
TELEMETRY_CRITPATH_ENABLED = "spark.hyperspace.telemetry.critpath.enabled"
TELEMETRY_CRITPATH_ENABLED_DEFAULT = "true"

# Sampling profiler (`telemetry/profiler.py`): when enabled, a daemon
# thread samples every live thread's stack at `profiler.hz` and
# aggregates host time by collapsed stack (served at `/profile`).
# Off by default; `tests/test_profiler.py` bounds the sampler's own
# loop cost.
TELEMETRY_PROFILER_ENABLED = "spark.hyperspace.telemetry.profiler.enabled"
TELEMETRY_PROFILER_ENABLED_DEFAULT = "false"
TELEMETRY_PROFILER_HZ = "spark.hyperspace.telemetry.profiler.hz"
TELEMETRY_PROFILER_HZ_DEFAULT = 19.0

# Triggered device-trace capture: when `capture.seconds` > 0, SLO burn
# crossing 1.0 or a slowlog dump fires a background device-trace
# capture of that many seconds of device activity, written as a
# `profile-*` directory next to the slow-query dumps (atomic rename;
# only the newest `capture.keep` retained; at most one capture per
# `capture.min.interval.seconds`). 0 (the default) disarms capture.
TELEMETRY_PROFILER_CAPTURE_SECONDS = \
    "spark.hyperspace.telemetry.profiler.capture.seconds"
TELEMETRY_PROFILER_CAPTURE_SECONDS_DEFAULT = 0.0
TELEMETRY_PROFILER_CAPTURE_KEEP = \
    "spark.hyperspace.telemetry.profiler.capture.keep"
TELEMETRY_PROFILER_CAPTURE_KEEP_DEFAULT = 4
TELEMETRY_PROFILER_CAPTURE_MIN_INTERVAL_SECONDS = \
    "spark.hyperspace.telemetry.profiler.capture.min.interval.seconds"
TELEMETRY_PROFILER_CAPTURE_MIN_INTERVAL_SECONDS_DEFAULT = 30.0

# Durable on-lake telemetry history (`telemetry/history.py`): when
# enabled, the sampler's tick hook periodically flushes the registry
# snapshot, the new ring samples, SLO/burn state, and a flight-ring
# digest as append-only schema-versioned segment files under
# `history.dir` (default `<warehouse>/.hyperspace_telemetry` — history
# is metadata, and metadata lives on the lake). Segments older than
# `keep.seconds` or beyond `keep.bytes` total are pruned oldest-first;
# a crash-torn final segment is skipped on read.
TELEMETRY_HISTORY_ENABLED = "spark.hyperspace.telemetry.history.enabled"
TELEMETRY_HISTORY_ENABLED_DEFAULT = "false"
TELEMETRY_HISTORY_DIR = "spark.hyperspace.telemetry.history.dir"
# The one place the on-lake history directory NAME is spelled —
# `scripts/check_metrics_coverage.py` bans the literal everywhere but
# here and `telemetry/history.py`, so every segment write routes
# through the history seam.
TELEMETRY_HISTORY_DIRNAME = ".hyperspace_telemetry"
TELEMETRY_HISTORY_INTERVAL_SECONDS = \
    "spark.hyperspace.telemetry.history.interval.seconds"
TELEMETRY_HISTORY_INTERVAL_SECONDS_DEFAULT = 60.0
TELEMETRY_HISTORY_KEEP_SECONDS = \
    "spark.hyperspace.telemetry.history.keep.seconds"
TELEMETRY_HISTORY_KEEP_SECONDS_DEFAULT = 7 * 24 * 3600.0
TELEMETRY_HISTORY_KEEP_BYTES = \
    "spark.hyperspace.telemetry.history.keep.bytes"
TELEMETRY_HISTORY_KEEP_BYTES_DEFAULT = 64 * 1024 * 1024

# Rule-driven alerting (`telemetry/alerts.py`): declarative rules over
# the sampler's windowed series, evaluated on every tick. A firing
# rule opens a structured incident with an attached evidence bundle
# (served at `/alerts`, persisted into the history store). Per-rule
# overrides live under `alerts.rule.<name>.{enabled,threshold,clear,
# sustain.seconds,window.seconds}`; `alerts.enabled=false` disables
# evaluation entirely.
TELEMETRY_ALERTS_ENABLED = "spark.hyperspace.telemetry.alerts.enabled"
TELEMETRY_ALERTS_ENABLED_DEFAULT = "true"
TELEMETRY_ALERTS_RULE_PREFIX = "spark.hyperspace.telemetry.alerts.rule."

# Adaptive host/device execution lane: batches below this row count are
# evaluated with host numpy, larger batches run on the accelerator. The
# default was tuned on a high-latency device link that no longer exists,
# where the crossover for query operators sat in the millions of rows
# (index reads are pruned/pre-sorted, so the host work per row is tiny).
# Where it sits on an attached chip is unmeasured; 0 forces everything
# onto the device.
MIN_DEVICE_ROWS = "spark.hyperspace.execution.min.device.rows"
MIN_DEVICE_ROWS_DEFAULT = 4_194_304

# Whole-stage fusion: compile Filter/Project/BroadcastHashJoin chains
# into one jitted executable per chain (engine/fusion.py). "false"
# restores eager per-operator execution.
FUSION_ENABLED = "spark.hyperspace.execution.fusion.enabled"
FUSION_ENABLED_DEFAULT = "true"

WAREHOUSE_PATH = "spark.hyperspace.warehouse.dir"
WAREHOUSE_PATH_DEFAULT = "warehouse"

# Operation log layout (reference `index/IndexConstants.scala:38-39`).
HYPERSPACE_LOG = "_hyperspace_log"
INDEX_VERSION_DIRECTORY_PREFIX = "v__"
LATEST_STABLE_LOG = "latestStable"

# Commit marker written LAST into every `v__=N` data dir (the Delta-style
# finalize): readers (`IndexDataManager.get_latest_version_id`, optimize/
# incremental refresh picking the "current" version) only see versions
# carrying it, so a crashed build's partially-written dir is invisible —
# it is skipped for the next version number and hard-deleted by vacuum.
# The leading underscore keeps it out of every parquet file listing.
INDEX_DATA_COMMIT_MARKER = "_committed"

# Explain display mode (reference `index/IndexConstants.scala:42-49`).
DISPLAY_MODE = "spark.hyperspace.explain.displayMode"
HIGHLIGHT_BEGIN_TAG = "spark.hyperspace.explain.displayMode.highlight.beginTag"
HIGHLIGHT_END_TAG = "spark.hyperspace.explain.displayMode.highlight.endTag"


class DisplayModeNames:
    CONSOLE = "console"
    PLAIN_TEXT = "plaintext"
    HTML = "html"


class States:
    """Index lifecycle states (reference `actions/Constants.scala:20-30`)."""

    ACTIVE = "ACTIVE"
    CREATING = "CREATING"
    DELETING = "DELETING"
    DELETED = "DELETED"
    REFRESHING = "REFRESHING"
    VACUUMING = "VACUUMING"
    RESTORING = "RESTORING"
    DOESNOTEXIST = "DOESNOTEXIST"
    CANCELLING = "CANCELLING"
    OPTIMIZING = "OPTIMIZING"  # extension: incremental merge-compaction


STABLE_STATES = (States.ACTIVE, States.DELETED, States.DOESNOTEXIST)
