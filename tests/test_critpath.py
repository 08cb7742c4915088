"""Critical-path extraction (telemetry/critical_path.py): the
closed-set decomposition and its sum-exactness contract (the residual
makes the sum exact BY CONSTRUCTION), counter publication, span
classification, flight-ring + sampler integration, and sum-exactness
under concurrent stamping."""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.telemetry import (critical_path, flight, timeseries,
                                      trace)
from hyperspace_tpu.telemetry.critical_path import (SEGMENT_SOURCES,
                                                    SEGMENTS,
                                                    SUM_EXACT_EPSILON_S)


def _counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


def _finished_metrics(seconds_by_source=None, tag="q", busy_s=0.0):
    """A finished QueryMetrics with chosen per-query second counters;
    `busy_s` gives the query real wall so attributed segments fit
    under it (a zero-wall query overlaps by construction)."""
    qm = telemetry.QueryMetrics(description=tag)
    for source, s in (seconds_by_source or {}).items():
        qm.add_seconds(source, s)
    if busy_s:
        time.sleep(busy_s)
    qm.finish()
    return qm


# ---------------------------------------------------------------------------
# The decomposition + the sum contract
# ---------------------------------------------------------------------------


def test_decompose_closed_set_and_sum_exact():
    qm = _finished_metrics({
        "serve.queue_wait_s": 0.010,
        "compile.seconds": 0.005,
        "device.dispatch_s": 0.002,
        "link.h2d_s": 0.001,
    }, busy_s=0.025)
    cp = critical_path.decompose(qm)
    assert set(cp["segments"]) == set(SEGMENTS)
    assert abs(cp["sum_s"] - cp["wall_s"]) <= SUM_EXACT_EPSILON_S
    # the residual is exactly wall minus the attributed segments
    attributed = sum(v for k, v in cp["segments"].items()
                     if k != "host_python")
    assert cp["segments"]["host_python"] == \
        pytest.approx(cp["wall_s"] - attributed, abs=2e-6)
    assert cp["overlap_s"] == 0.0


def test_decompose_unfinished_is_none():
    qm = telemetry.QueryMetrics(description="unfinished")
    assert critical_path.decompose(qm) is None
    assert critical_path.stamp(qm) is None


def test_dominant_segment_named():
    qm = _finished_metrics({"compile.seconds": 30.0})
    cp = critical_path.decompose(qm)
    assert cp["dominant"] == "compile"
    # a bare query's wall is all host orchestration
    cp2 = critical_path.decompose(_finished_metrics())
    assert cp2["dominant"] == "host_python"


def test_overlap_reported_not_clamped_silently():
    """Pool threads can attribute more seconds than the wall; the
    negative residual and the positive overlap both say so, and the
    sum STAYS exact (the signed residual is the contract)."""
    qm = _finished_metrics({"link.h2d_s": 5.0, "link.d2h_s": 5.0})
    cp = critical_path.decompose(qm)
    assert cp["segments"]["host_python"] < 0
    assert cp["overlap_s"] == pytest.approx(10.0 - cp["wall_s"],
                                            abs=1e-5)
    assert cp["segments"]["host_python"] == pytest.approx(
        cp["wall_s"] - 10.0, abs=1e-5)
    assert abs(cp["sum_s"] - cp["wall_s"]) <= SUM_EXACT_EPSILON_S


def test_negative_source_counter_clamped():
    qm = _finished_metrics({"serve.queue_wait_s": -1.0})
    cp = critical_path.decompose(qm)
    assert cp["segments"]["queue_wait"] == 0.0


# ---------------------------------------------------------------------------
# stamp(): attachment + counter publication
# ---------------------------------------------------------------------------


def test_stamp_attaches_and_rides_to_dict():
    qm = _finished_metrics({"compile.seconds": 0.004})
    cp = critical_path.stamp(qm, publish=False)
    assert qm.critical_path is cp
    assert qm.to_dict()["critical_path"]["dominant"] == cp["dominant"]
    assert qm.summary()["critical_path"]["wall_s"] == cp["wall_s"]


def test_stamp_publishes_monotonic_counters():
    before_wall = _counter("critpath.wall.seconds")
    before_q = _counter("critpath.queries")
    before_compile = _counter("critpath.compile.seconds")
    before_overlap = _counter("critpath.overlap.seconds")

    qm = _finished_metrics({"compile.seconds": 0.25})
    critical_path.stamp(qm)
    assert _counter("critpath.queries") == before_q + 1
    assert _counter("critpath.wall.seconds") == \
        pytest.approx(before_wall + qm.critical_path["wall_s"], abs=1e-6)
    assert _counter("critpath.compile.seconds") == \
        pytest.approx(before_compile + 0.25, abs=1e-3)

    # an overlapping query publishes overlap and never DECREMENTS a
    # segment counter for its negative residual
    over = _finished_metrics({"link.h2d_s": 2.0})
    critical_path.stamp(over)
    assert over.critical_path["segments"]["host_python"] < 0
    assert _counter("critpath.overlap.seconds") > before_overlap
    assert _counter("critpath.host_python.seconds") >= 0


def test_sum_exact_under_concurrent_stamping():
    """N threads stamping interleaved: every stamped decomposition is
    individually sum-exact and the process counters account for every
    wall exactly once."""
    before_q = _counter("critpath.queries")
    before_wall = _counter("critpath.wall.seconds")
    rng = np.random.default_rng(7)
    sources = list(SEGMENT_SOURCES.values())
    stamped = []
    lock = threading.Lock()

    def worker(seed):
        r = np.random.default_rng(seed)
        for _ in range(25):
            chosen = {s: float(r.random() * 1e-3)
                      for s in r.choice(sources, size=3, replace=False)}
            qm = _finished_metrics(chosen)
            critical_path.stamp(qm)
            with lock:
                stamped.append(qm)

    threads = [threading.Thread(target=worker, args=(int(s),))
               for s in rng.integers(0, 1 << 31, size=6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert len(stamped) == 150
    for qm in stamped:
        cp = qm.critical_path
        assert abs(cp["sum_s"] - cp["wall_s"]) <= SUM_EXACT_EPSILON_S
    assert _counter("critpath.queries") == before_q + 150
    walls = sum(q.critical_path["wall_s"] for q in stamped)
    assert _counter("critpath.wall.seconds") == \
        pytest.approx(before_wall + walls, rel=1e-6)


# ---------------------------------------------------------------------------
# The timeline view is the span seam's now (`telemetry/trace.py`): what a
# span records, whichever sink listens
# ---------------------------------------------------------------------------


def test_span_records_errors_late_arguments_and_inherits_the_id():
    tracer = telemetry.enable_tracing()
    try:
        with pytest.raises(ValueError):
            with telemetry.span("hs.serve.admit", "serve", qid="q-x") as sp:
                sp.set(queue_wait_s=0.5)
                with telemetry.span("hs.to_arrow.prefetch", "api"):
                    pass  # no recorder here: the enclosing span's id
                raise ValueError("boom")
        seen = []

        def worker():
            with telemetry.span("hs.build.write.file", "build"):
                seen.append(threading.get_ident())

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        events = {e["name"]: e for e in tracer.events}
    finally:
        telemetry.disable_tracing()
    admit = events["hs.serve.admit"]
    assert admit["args"]["qid"] == "q-x"
    assert admit["args"]["queue_wait_s"] == 0.5
    assert "boom" in admit["args"]["error"]
    assert events["hs.to_arrow.prefetch"]["args"]["qid"] == "q-x"
    # another thread inherits nothing; nothing leaks past the block
    assert "args" not in events["hs.build.write.file"]
    assert events["hs.build.write.file"]["tid"] == seen[0]
    assert getattr(trace._enclosing, "qid", None) is None


def test_annotation_arguments_are_plain():
    plain = trace._plain({"rows": 5, "share": 0.25, "none": None,
                          "lane": "device", "ok": True,
                          "description": "a, b=c #d\ne", "obj": [1, 2]})
    assert plain == {"rows": 5, "share": 0.25, "lane": "device", "ok": True,
                     "description": "a; b:c ;d e", "obj": "[1; 2]"}
    assert len(trace._plain({"e": "x" * 999})["e"]) == 120


# ---------------------------------------------------------------------------
# Engine integration: the scheduler stamps, the ring and sampler carry
# ---------------------------------------------------------------------------


@pytest.fixture
def small_env(tmp_path):
    rng = np.random.default_rng(3)
    n = 4000
    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(pa.table({
        "a": rng.integers(0, 100, n).astype(np.int64),
        "v": rng.random(n).astype(np.float64),
    }), str(data / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
    }))
    return sess, str(data)


def test_collect_stamps_flight_ring_entries(small_env):
    sess, data = small_env
    seq0 = flight.get_recorder().last_seq
    df = sess.read_parquet(data).filter(col("a") > lit(50))
    df.collect()
    df.collect()
    fresh, _last = flight.get_recorder().snapshot(seq0)
    stamped = [m for m in fresh
               if getattr(m, "critical_path", None) is not None]
    assert len(stamped) >= 2
    for qm in stamped:
        cp = qm.critical_path
        assert set(cp["segments"]) == set(SEGMENTS)
        assert abs(cp["sum_s"] - cp["wall_s"]) <= SUM_EXACT_EPSILON_S
        # wall includes the queue wait: no segment exceeds the wall
        # unless overlap says so
        if cp["overlap_s"] == 0.0:
            assert max(cp["segments"].values()) <= cp["wall_s"] + 1e-6


def test_critpath_disabled_by_conf(tmp_path, small_env):
    _sess, data = small_env
    off = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh2"),
        "spark.hyperspace.telemetry.critpath.enabled": "false",
    }))
    seq0 = flight.get_recorder().last_seq
    off.read_parquet(data).filter(col("a") > lit(50)).collect()
    fresh, _last = flight.get_recorder().snapshot(seq0)
    assert fresh and all(getattr(m, "critical_path", None) is None
                         for m in fresh)


def test_window_shares_from_sampler(small_env):
    sess, data = small_env
    sampler = timeseries.get_sampler()
    sampler.tick()
    t0 = time.time()
    df = sess.read_parquet(data).filter(col("a") > lit(50))
    for _ in range(3):
        df.collect()
    sampler.tick()
    shares = critical_path.window_shares(since_t=t0)
    assert shares["queries_per_s"] > 0
    assert shares["dominant"] in SEGMENTS
    # shares cover the wall to within rounding + reported overlap
    total = sum(shares["shares"].values())
    assert total == pytest.approx(1.0 + shares["overlap"], abs=0.02)
    # and the windowed gauges were published for scrapers
    gauges = telemetry.get_registry().series_snapshot()["gauges"]
    assert f"window.critpath.{shares['dominant']}.share" in gauges


def test_window_shares_empty_window_renders_shape():
    sampler = timeseries.get_sampler()
    sampler.tick()
    out = critical_path.window_shares(since_t=time.time() + 60)
    assert out["queries_per_s"] == 0.0
    assert set(out["shares"]) == set(SEGMENTS)
    assert out["dominant"] is None
