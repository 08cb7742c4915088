"""Pipelined transfer engine (`io/transfer.py`): chunked round-trip
equivalence with the plain path, in-flight byte-window enforcement,
staging-buffer reuse, fault-injected put retry, decode/link overlap on a
slow-link fake, and sorted-run output identity between the chunked and
serial build paths."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu.io import columnar, transfer
from hyperspace_tpu.io.transfer import Host, HostCast, TransferEngine


@pytest.fixture
def engine():
    """Install a purpose-built engine as THE process engine; restore the
    default on teardown (the engine is process-wide state)."""
    def make(**kwargs) -> TransferEngine:
        return transfer.set_engine(TransferEngine(**kwargs))

    yield make
    transfer.reset_engine()


def sample_table(n: int = 5000) -> pa.Table:
    rng = np.random.default_rng(7)
    ints = rng.integers(0, 1 << 40, n).astype(np.int64)
    return pa.table({
        "i64": ints,
        "i32": pa.array(
            np.where(np.arange(n) % 7 == 0, None,
                     rng.integers(-1000, 1000, n)).tolist(),
            type=pa.int32()),
        "f64": pa.array(
            np.where(np.arange(n) % 5 == 0, None, rng.random(n)).tolist(),
            type=pa.float64()),
        "s": pa.array([None if i % 11 == 0 else f"v{i % 97}"
                       for i in range(n)], type=pa.string()),
        "b": rng.integers(0, 2, n).astype(bool),
    })


def batch_host_view(batch):
    """{name: (data, validity)} as numpy, for value comparison."""
    out = {}
    for name, col in batch.columns.items():
        out[name] = (np.asarray(col.data),
                     None if col.validity is None
                     else np.asarray(col.validity))
    return out


class FakeDev:
    """A fake device array for fake-link engines: remembers its payload,
    completes after `latency_s` (block_until_ready waits it out)."""

    def __init__(self, arr, latency_s: float = 0.0):
        self.np = np.asarray(arr).copy()  # copy, like a real transfer
        self.nbytes = self.np.nbytes
        self.done_at = time.perf_counter() + latency_s
        self.blocked = False

    def block_until_ready(self):
        delay = self.done_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        self.blocked = True
        return self

    def __array__(self, dtype=None):
        return self.np if dtype is None else self.np.astype(dtype)


# ---------------------------------------------------------------------------
# Chunked round-trip equivalence
# ---------------------------------------------------------------------------


def test_chunked_from_arrow_matches_plain(engine):
    table = sample_table()
    plain = columnar.from_arrow(table)  # default engine: few/no chunks
    engine(chunk_bytes=1024, inflight_bytes=8192, threads=2)
    chunked = columnar.from_arrow(table)
    assert transfer.get_engine().stats["chunks"] > len(table.column_names)

    a, b = batch_host_view(plain), batch_host_view(chunked)
    for name in a:
        np.testing.assert_array_equal(a[name][0], b[name][0])
        da, db = plain.columns[name], chunked.columns[name]
        assert np.asarray(da.data).dtype == np.asarray(db.data).dtype
        if a[name][1] is None:
            assert b[name][1] is None
        else:
            np.testing.assert_array_equal(a[name][1], b[name][1])
        if da.is_string:
            np.testing.assert_array_equal(da.dictionary, db.dictionary)
    # Arrow round trip preserves values + null masks exactly.
    assert columnar.to_arrow(chunked).equals(columnar.to_arrow(plain))
    assert columnar.to_arrow(chunked).equals(table)


def test_chunked_roundtrip_empty_and_tiny(engine):
    engine(chunk_bytes=64, inflight_bytes=256, threads=1)
    empty = sample_table(0)
    assert columnar.to_arrow(columnar.from_arrow(empty)).equals(empty)
    tiny = sample_table(3)
    assert columnar.to_arrow(columnar.from_arrow(tiny)).equals(tiny)


def test_put_chunks_concatenate_to_source(engine):
    engine(chunk_bytes=4096, inflight_bytes=1 << 20, threads=2)
    arr = np.arange(10_000, dtype=np.int64)
    parts = transfer.get_engine().put_chunks(HostCast(arr, np.uint32))
    assert len(parts) > 1
    got = np.concatenate([np.asarray(p) for p in parts])
    np.testing.assert_array_equal(got, arr.astype(np.uint32))


# ---------------------------------------------------------------------------
# In-flight byte window
# ---------------------------------------------------------------------------


def test_inflight_byte_window_enforced(engine):
    outstanding = []
    lock = threading.Lock()
    max_seen = [0]

    def slow_put(arr, device):
        dev = FakeDev(arr, latency_s=0.002)
        with lock:
            outstanding.append(dev)
            live = sum(d.nbytes for d in outstanding if not d.blocked)
            max_seen[0] = max(max_seen[0], live)
        return dev

    window = 4096
    eng = engine(chunk_bytes=1024, inflight_bytes=window, threads=2,
                 put_fn=slow_put)
    arr = np.arange(8192, dtype=np.int8)  # 8 chunks of 1 KiB
    parts = eng.put_chunks(arr)
    assert len(parts) == 8
    assert max_seen[0] <= window
    assert eng.stats["window_waits"] > 0
    got = np.concatenate([p.np for p in parts])
    np.testing.assert_array_equal(got, arr)


# ---------------------------------------------------------------------------
# Staging-buffer reuse
# ---------------------------------------------------------------------------


def test_staging_buffers_reused_not_rematerialized(engine, monkeypatch):
    # Drop the staging floor so test-size chunks hit the buffer pool.
    # The fake link COPIES (like a real accelerator link); on the bare
    # CPU backend staging is disabled — see the test below.
    monkeypatch.setattr(transfer, "_STAGING_MIN_BYTES", 1)
    eng = engine(chunk_bytes=4096, inflight_bytes=8192, threads=2,
                 put_fn=lambda arr, device: FakeDev(arr))
    arr = np.arange(64_000, dtype=np.int64)  # ~63 int32 chunks
    parts = eng.put_chunks(HostCast(arr, np.int32))
    got = np.concatenate([p.np for p in parts])
    np.testing.assert_array_equal(got, arr.astype(np.int32))
    stats = eng.stats
    assert stats["staging_reused"] > 20, stats
    # Double-buffering needs only a handful of buffers, not one per chunk.
    assert stats["staging_allocated"] <= 2 * eng.threads + 2, stats
    assert stats["staging_allocated"] + stats["staging_reused"] \
        == len(parts)


def test_staging_disabled_on_cpu_aliasing_backend(engine):
    # The CPU PJRT client may ZERO-COPY aligned host buffers into the
    # "device" array; rewriting a reused staging buffer would then
    # corrupt already-placed chunks, so the engine must refuse staging
    # on the cpu platform — and values must stay correct without it.
    eng = engine(chunk_bytes=4096, inflight_bytes=1 << 20, threads=2)
    assert eng._staging_ok() is False  # conftest forces the cpu backend
    arr = np.arange(100_000, dtype=np.int64)
    parts = eng.put_chunks(HostCast(arr, np.int32))
    got = np.concatenate([np.asarray(p) for p in parts])
    np.testing.assert_array_equal(got, arr.astype(np.int32))
    assert eng.stats["staging_reused"] == 0
    assert eng.stats["staging_allocated"] == 0


# ---------------------------------------------------------------------------
# Fault-injected transient put
# ---------------------------------------------------------------------------


def test_transient_put_retries_preserving_chunk_order(engine,
                                                      fault_injector):
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.utils.faults import FaultRule

    eng = engine(chunk_bytes=1024, inflight_bytes=8192, threads=2)
    inj = fault_injector(FaultRule("transfer.put", kind="transient",
                                   nth=3, times=2))
    retries_before = telemetry.get_registry().counter("io.retries").value
    arr = np.arange(4096, dtype=np.int16)  # 4 chunks
    parts = eng.put_chunks(arr)
    got = np.concatenate([np.asarray(p) for p in parts])
    np.testing.assert_array_equal(got, arr)  # order survived the retries
    assert inj.fired("transfer.put") == 2
    assert telemetry.get_registry().counter("io.retries").value \
        == retries_before + 2


def test_permanent_put_raises(engine, fault_injector):
    from hyperspace_tpu.utils.faults import (FaultRule,
                                             InjectedPermanentError)

    eng = engine(chunk_bytes=1 << 20, inflight_bytes=1 << 22)
    fault_injector(FaultRule("transfer.put", kind="permanent"))
    with pytest.raises(InjectedPermanentError):
        eng.put(np.arange(10))


# ---------------------------------------------------------------------------
# Overlap: decode + link pipelining beats the serial sum
# ---------------------------------------------------------------------------


def test_slow_link_overlap_beats_serial(engine):
    from hyperspace_tpu import telemetry

    put_s = 0.01
    decode_s = 0.02
    n_jobs = 6

    def slow_put(arr, device):
        time.sleep(put_s)  # a dispatch-blocking link
        return FakeDev(arr)

    eng = engine(chunk_bytes=1 << 20, inflight_bytes=1 << 22, threads=2,
                 put_fn=slow_put)

    def job():
        time.sleep(decode_s)  # Arrow decode stage
        return {"data": np.arange(256, dtype=np.int64)}

    saved_before = telemetry.get_registry().counter(
        "transfer.overlap_saved_seconds").value
    t0 = time.perf_counter()
    results = eng.put_group([job] * n_jobs)
    wall = time.perf_counter() - t0
    serial = n_jobs * (decode_s + put_s)
    assert wall < 0.8 * serial, (wall, serial)
    assert len(results) == n_jobs
    for r in results:
        np.testing.assert_array_equal(r["data"].np,
                                      np.arange(256, dtype=np.int64))
    assert telemetry.get_registry().counter(
        "transfer.overlap_saved_seconds").value > saved_before


def test_put_group_host_marker_passthrough(engine):
    eng = engine()
    dictionary = np.array(["a", "b"])
    [res] = eng.put_group([lambda: {"data": np.arange(4),
                                    "dictionary": Host(dictionary),
                                    "n": 4, "none": None}])
    assert res["dictionary"] is dictionary
    assert res["n"] == 4 and res["none"] is None
    assert not isinstance(res["data"], np.ndarray)  # placed on device


# ---------------------------------------------------------------------------
# Telemetry & counters
# ---------------------------------------------------------------------------


def test_link_chunk_counters_and_d2h(engine):
    from hyperspace_tpu import telemetry

    reg = telemetry.get_registry()
    h2d_chunks0 = reg.counter("link.h2d.chunks").value
    d2h_chunks0 = reg.counter("link.d2h.chunks").value
    eng = engine(chunk_bytes=1024, inflight_bytes=8192, threads=2)
    dev = eng.put(np.arange(1024, dtype=np.int64))  # 8 chunks
    assert reg.counter("link.h2d.chunks").value >= h2d_chunks0 + 8
    np.testing.assert_array_equal(eng.fetch(dev),
                                  np.arange(1024, dtype=np.int64))
    assert reg.counter("link.d2h.chunks").value > d2h_chunks0


def test_prefetch_errors_are_counted(engine):
    from hyperspace_tpu import telemetry

    class BadPrefetch:
        def copy_to_host_async(self):
            raise RuntimeError("dead DMA path")

    reg = telemetry.get_registry()
    before = reg.counter("link.d2h.prefetch_errors").value
    eng = engine()
    eng.prefetch(BadPrefetch(), np.arange(3), BadPrefetch())
    assert reg.counter("link.d2h.prefetch_errors").value == before + 2


def test_conf_knobs_configure_engine(engine):
    from hyperspace_tpu.config import HyperspaceConf

    eng = engine()
    conf = HyperspaceConf({
        "spark.hyperspace.io.transfer.chunk.bytes": "2048",
        "spark.hyperspace.io.transfer.inflight.bytes": "16384",
        "spark.hyperspace.io.transfer.threads": "3",
    })
    transfer.configure(conf)
    assert eng.chunk_bytes == 2048
    assert eng.inflight_bytes == 16384
    assert eng.threads == 3


# ---------------------------------------------------------------------------
# Build-path identity: chunked pipeline == serial path, byte for byte
# ---------------------------------------------------------------------------


def build_table(n: int = 20_000) -> pa.Table:
    rng = np.random.default_rng(11)
    return pa.table({
        "key": rng.integers(0, n // 4, n).astype(np.int64),
        "score": rng.random(n).astype(np.float64),
    })


def read_sorted_runs(path):
    from hyperspace_tpu.io import parquet as pq_io
    per_bucket = pq_io.bucket_files(str(path))
    return {b: pq_io.read_table(files)
            for b, files in sorted(per_bucket.items())}


def test_sorted_runs_identical_across_chunking(engine, tmp_path,
                                               monkeypatch):
    from hyperspace_tpu.io import builder

    table = build_table()
    # Force the DEVICE permutation lane regardless of build size so the
    # chunked D2H + pipelined writer path runs under test.
    monkeypatch.setattr(builder, "BUILD_MIN_DEVICE_ROWS", 0)
    monkeypatch.setattr(builder, "_host_lane_preferred", lambda rows: False)

    engine(chunk_bytes=1 << 26, inflight_bytes=1 << 28)  # effectively serial
    serial = builder.write_bucketed_table(table, ["key"], 8,
                                          str(tmp_path / "serial"))
    engine(chunk_bytes=16 * 1024, inflight_bytes=64 * 1024, threads=2)
    chunked = builder.write_bucketed_table(table, ["key"], 8,
                                           str(tmp_path / "chunked"))
    assert serial and chunked
    a = read_sorted_runs(tmp_path / "serial")
    b = read_sorted_runs(tmp_path / "chunked")
    assert set(a) == set(b)
    for bucket in a:
        # Same rows in the same order per bucket; the chunked path may
        # split a bucket into more run files, but their name-ordered
        # concatenation must be identical.
        assert a[bucket].equals(b[bucket]), f"bucket {bucket} diverged"


def test_pipelined_file_build_matches_host_lane(engine, tmp_path,
                                                monkeypatch):
    import pyarrow.parquet as pq

    from hyperspace_tpu.io import builder

    table = build_table(8000)
    src = tmp_path / "src"
    src.mkdir()
    pq.write_table(table.slice(0, 3000), str(src / "part-0.parquet"))
    pq.write_table(table.slice(3000), str(src / "part-1.parquet"))
    files = [str(src / "part-0.parquet"), str(src / "part-1.parquet")]

    engine(chunk_bytes=8 * 1024, inflight_bytes=32 * 1024, threads=2)
    host = builder.write_bucketed_from_files(
        files, ["key", "score"], ["key"], 8, str(tmp_path / "host"))
    monkeypatch.setattr(builder, "_host_lane_preferred", lambda rows: False)
    dev = builder.write_bucketed_from_files(
        files, ["key", "score"], ["key"], 8, str(tmp_path / "dev"))
    assert host and dev
    a = read_sorted_runs(tmp_path / "host")
    b = read_sorted_runs(tmp_path / "dev")
    assert set(a) == set(b)
    for bucket in a:
        assert a[bucket].equals(b[bucket])
