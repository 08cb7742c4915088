"""Memory, cache, and compile observability (PR 3): the device-memory
accountant (per-device peaks on the virtual 8-device mesh, per-query
watermarks), byte-budget cache eviction, jit compile/retrace tracking,
Perfetto counter tracks, the leak sentinel, and the peak-HBM bench
gate."""

import gc
import json
import os
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine import fusion
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.plan.expr import col, lit

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracing():
    tracer = telemetry.enable_tracing()
    try:
        yield tracer
    finally:
        telemetry.disable_tracing()


@pytest.fixture
def sales_env(tmp_path):
    """One fact table + a session factory (device lane forced)."""
    rng = np.random.default_rng(7)
    n = 4000
    fact_dir = tmp_path / "fact"
    fact_dir.mkdir()
    pq.write_table(pa.table({
        "key": rng.integers(0, 100, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": rng.random(n) * 100,
    }), str(fact_dir / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh"),
                "spark.hyperspace.execution.min.device.rows": "0",
                "spark.hyperspace.distribution.enabled": "false"}
        conf.update(extra)
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(fact_dir)


# ---------------------------------------------------------------------------
# Device-memory accountant
# ---------------------------------------------------------------------------


def test_accountant_per_device_attribution():
    """live-arrays fallback on the virtual mesh: bytes placed on ONE
    device show up on THAT device's gauge and in the recording query's
    per-device watermark."""
    import jax

    devices = jax.devices()
    assert len(devices) >= 8  # conftest's virtual mesh
    payload = np.ones(1 << 16, dtype=np.float64)  # 512 KiB
    held = jax.device_put(payload, devices[3])
    held.block_until_ready()
    label = f"{devices[3].platform}:{devices[3].id}"
    rec = telemetry.QueryMetrics("mem attribution")
    with telemetry.recording(rec):
        live = telemetry.memory.sample()
    assert live is not None and live.get(label, 0) >= payload.nbytes
    assert rec.peak_hbm_per_device[label] >= payload.nbytes
    assert rec.peak_hbm_bytes >= payload.nbytes
    reg = telemetry.get_registry()
    assert reg.gauge(f"memory.{label}.bytes_in_use").value \
        >= payload.nbytes
    assert reg.gauge(f"memory.{label}.peak_bytes").value >= payload.nbytes
    snap = telemetry.memory.snapshot()
    assert snap["backend"] == "live_arrays"  # no memory_stats on CPU
    assert snap["devices"][label]["peak_bytes"] >= payload.nbytes
    assert snap["peak_hbm_bytes"] >= payload.nbytes
    del held


def test_maybe_sample_noop_without_consumers():
    acct = telemetry.get_accountant()
    before = acct.samples
    assert telemetry.current() is None and telemetry.tracer() is None
    telemetry.memory.maybe_sample()
    assert acct.samples == before


def test_query_metrics_peak_and_compile_fields(sales_env):
    session, fact_dir = sales_env
    sess = session()
    q = lambda: sess.read_parquet(fact_dir).filter(  # noqa: E731
        col("qty") > lit(10)).select("key", "price")
    q().collect()  # warm: traces, promotes, caches
    _, warm = q().collect(with_metrics=True)
    assert warm.peak_hbm_bytes > 0
    assert warm.peak_hbm_per_device
    # Re-running the SAME query causes ZERO new traces (the acceptance
    # bar: a warm query must be retrace-free), while the jit cache
    # serves the dispatches.
    assert warm.compile["traces"] == 0, (
        f"warm rerun re-traced: {warm.events_of('compile')}")
    assert warm.compile["cache_hits"] >= 1
    d = warm.to_dict()
    assert d["peak_hbm_bytes"] == warm.peak_hbm_bytes
    assert d["compile"]["traces"] == 0
    assert "peak_hbm_bytes" in warm.summary()
    tree = warm.format_tree()
    assert "Peak HBM:" in tree and "Compile:" in tree


# ---------------------------------------------------------------------------
# Byte-budget cache eviction
# ---------------------------------------------------------------------------


@pytest.fixture
def promote_cache():
    """Isolated fusion promotion cache with restored budget."""
    saved_budget = fusion._promote_budget[0]
    saved = dict(fusion._promote_cache)
    fusion._promote_cache.clear()
    try:
        yield fusion._promote_cache
    finally:
        fusion._promote_budget[0] = saved_budget
        fusion._promote_cache.clear()
        fusion._promote_cache.update(saved)


def test_promote_cache_byte_budget_eviction_order(promote_cache):
    arrays = [np.arange(100, dtype=np.float64) + i for i in range(4)]
    nbytes = arrays[0].nbytes  # 800
    fusion._promote_budget[0] = int(nbytes * 2.5)  # room for two
    reg = telemetry.get_registry()
    ev_before = reg.counter("cache.fusion_promote.evictions").value
    for a in arrays:
        fusion._to_device(a)
    tokens = [fusion._token_of(a) for a in arrays]
    held = [t for t in tokens if t in promote_cache]
    # Oldest-inserted evicted first: the survivors are exactly the
    # newest entries that fit the byte budget.
    assert held == tokens[2:]
    assert reg.counter("cache.fusion_promote.evictions").value \
        == ev_before + 2
    assert reg.gauge("cache.fusion_promote.bytes_held").value \
        <= fusion._promote_budget[0]
    assert reg.gauge("cache.fusion_promote.entries").value == 2


def test_promote_cache_sweeps_dead_refs_on_insert(promote_cache):
    """A GC'd host source must not linger holding its device buffer
    until byte pressure (the silent HBM leak): the dead entry is swept
    on the NEXT insert, budget headroom or not. (On CPU backends
    `device_put` may zero-copy-alias the host buffer, keeping the
    source alive through the cached device array — so a dead entry is
    planted directly rather than via real GC.)"""
    import weakref

    fusion._promote_budget[0] = 1 << 30
    a = np.arange(64, dtype=np.float64)
    dev = fusion._to_device(a)
    assert len(promote_cache) == 1

    class _Src:
        pass

    src = _Src()
    promote_cache[-99] = (weakref.ref(src), dev)
    del src
    gc.collect()
    assert promote_cache[-99][0]() is None  # entry is dead
    b = np.arange(32, dtype=np.float64)
    fusion._to_device(b)
    assert -99 not in promote_cache  # dead entry swept on insert
    assert fusion._token_of(a) in promote_cache
    assert fusion._token_of(b) in promote_cache


def test_promote_cache_hit_miss_series(promote_cache):
    fusion._promote_budget[0] = 1 << 30
    reg = telemetry.get_registry()
    hits0 = reg.counter("cache.fusion_promote.hits").value
    miss0 = reg.counter("cache.fusion_promote.misses").value
    a = np.arange(128, dtype=np.float64)
    d1 = fusion._to_device(a)
    d2 = fusion._to_device(a)
    assert d1 is d2  # served from cache, no second transfer
    assert reg.counter("cache.fusion_promote.misses").value == miss0 + 1
    assert reg.counter("cache.fusion_promote.hits").value == hits0 + 1


def test_parquet_device_cache_series(sales_env):
    """The device read lane is the HBM segment cache (`io/segcache.py`)
    — repeat device scans hit it and report the `cache.segments.*`
    series."""
    session, fact_dir = sales_env
    sess = session()
    reg = telemetry.get_registry()
    miss0 = reg.counter("cache.segments.misses").value
    hits0 = reg.counter("cache.segments.hits").value
    q = lambda: sess.read_parquet(fact_dir).select("key")  # noqa: E731
    q().collect()
    q().collect()
    assert reg.counter("cache.segments.misses").value > miss0
    assert reg.counter("cache.segments.hits").value > hits0
    assert reg.gauge("cache.segments.bytes_held").value > 0
    assert reg.gauge("cache.segments.entries").value >= 1


# ---------------------------------------------------------------------------
# Index metadata cache: monotonic clock + series
# ---------------------------------------------------------------------------


def test_index_metadata_cache_monotonic(monkeypatch, conf):
    from hyperspace_tpu.index import cache as index_cache

    cache = index_cache.CreationTimeBasedCache(conf)  # expiry 300 s
    reg = telemetry.get_registry()
    hits0 = reg.counter("cache.index_metadata.hits").value
    ev0 = reg.counter("cache.index_metadata.evictions").value
    cache.set("entry")
    # A wall-clock jump (NTP step, manual change) must NOT expire the
    # entry: expiry is a duration, measured on the monotonic clock.
    real_time = time.time
    monkeypatch.setattr(index_cache.time, "time",
                        lambda: real_time() + 10_000)
    assert cache.get() == "entry"
    assert reg.counter("cache.index_metadata.hits").value == hits0 + 1
    # Monotonic advance past the expiry DOES.
    real_mono = time.monotonic
    monkeypatch.setattr(index_cache.time, "monotonic",
                        lambda: real_mono() + 301)
    assert cache.get() is None
    assert reg.counter("cache.index_metadata.evictions").value == ev0 + 1
    assert reg.gauge("cache.index_metadata.entries").value == 0


# ---------------------------------------------------------------------------
# Compile observability
# ---------------------------------------------------------------------------


def test_instrumented_jit_retrace_agreement():
    """Our trace counter must agree with jax's OWN executable-cache
    size — the counter is only trustworthy if it counts exactly the
    traces XLA performed."""
    import jax.numpy as jnp

    from hyperspace_tpu.telemetry.compilation import instrumented_jit

    name = "test.retrace_agreement"
    fn = instrumented_jit(name, scope="hs.stage")(lambda x: x * 2)
    reg = telemetry.get_registry()
    base = reg.counter(f"compile.{name}.traces").value
    rec = telemetry.QueryMetrics("retrace probe")
    with telemetry.recording(rec):
        fn(jnp.ones(8))                       # trace 1 (first)
        fn(jnp.ones(8))                       # executable-cache hit
        fn(jnp.ones(16))                      # trace 2 (shape delta)
        fn(jnp.ones(16, dtype=jnp.int64))     # trace 3 (dtype delta)
    assert reg.counter(f"compile.{name}.traces").value == base + 3
    jax_count = fn.cache_size()
    if jax_count is not None:  # agreement with jax's trace count
        assert jax_count == 3
    assert rec.compile["traces"] == 3
    assert rec.compile["cache_hits"] == 1
    assert rec.compile["seconds"] > 0
    events = rec.events_of("compile")
    assert len(events) == 3
    assert events[0]["name"] == "trace"
    assert events[0]["cause"] == "first trace"
    # Retrace causes name the shape/dtype signature delta.
    assert events[1]["name"] == "retrace"
    assert "[8]" in events[1]["cause"] and "[16]" in events[1]["cause"]
    assert "int64" in events[2]["cause"]
    assert getattr(fn, "__compile_span_instrumented__", False)


def test_compile_span_lands_in_trace(tracing):
    import jax.numpy as jnp

    from hyperspace_tpu.telemetry.compilation import instrumented_jit

    fn = instrumented_jit("test.compile_span",
                          scope="hs.stage")(lambda x: x + 1)
    fn(jnp.ones(4))
    spans = [e for e in tracing.events
             if e["ph"] == "X" and e.get("cat") == "compile"]
    assert spans and spans[-1]["args"]["target"] == "test.compile_span"


def test_coverage_lint_flags_raw_jit(tmp_path):
    """The source lint behind check_metrics_coverage: a direct jax.jit
    call is a jit entry point without the compile-span stamp."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    try:
        from check_metrics_coverage import check_jit_entry_points
    finally:
        sys.path.pop(0)
    pkg = tmp_path / "pkg"
    (pkg / "telemetry").mkdir(parents=True)
    (pkg / "ok.py").write_text(
        "from hyperspace_tpu.telemetry import instrumented_jit\n"
        "# mentions jax.jit in prose only\n")
    (pkg / "bad.py").write_text(
        "import jax\n\n\ndef f(x):\n    return jax.jit(lambda y: y)(x)\n")
    failures = check_jit_entry_points(str(pkg))
    assert len(failures) == 1 and "bad.py" in failures[0]
    # ...and the shipped package itself is clean (no raw jax.jit).
    import hyperspace_tpu
    shipped = check_jit_entry_points(
        os.path.dirname(hyperspace_tpu.__file__))
    assert shipped == [], shipped


# ---------------------------------------------------------------------------
# Perfetto counter tracks
# ---------------------------------------------------------------------------


def test_trace_export_has_memory_counter_tracks(sales_env, tmp_path,
                                                tracing):
    session, fact_dir = sales_env
    sess = session()
    sess.read_parquet(fact_dir).filter(
        col("qty") > lit(5)).select("price").collect()
    path = str(tmp_path / "trace.json")
    telemetry.export_trace(path)
    with open(path) as f:
        doc = json.load(f)
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert counters, "no counter-track events in the export"
    hbm = [e for e in counters if e["name"].startswith("HBM ")]
    assert hbm
    for ev in hbm:
        assert ev["args"]["bytes_in_use"] >= 0
        assert isinstance(ev["ts"], (int, float))


# ---------------------------------------------------------------------------
# Leak sentinel
# ---------------------------------------------------------------------------


def test_no_device_array_leak_across_repeat_queries(sales_env,
                                                    leak_sentinel):
    session, fact_dir = sales_env
    sess = session()
    q = lambda: sess.read_parquet(fact_dir).filter(  # noqa: E731
        col("qty") > lit(10)).select("key", "price")
    for _ in range(2):
        q().collect()  # warm: executables, promote + device caches
    with leak_sentinel():
        for _ in range(3):
            q().collect()
