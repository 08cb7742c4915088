"""Sampling profiler + triggered device capture
(telemetry/profiler.py): sampler lifecycle and export shapes, the
self-exclusion rule, bounded sampling cost, triggered-capture
atomicity / keep-N pruning / rate limiting (device_trace stubbed —
the capture plumbing is what's under test, not jax), the /profile +
/critpath endpoint round-trips, and the serving chaos run with the
profiler ON."""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request
from contextlib import contextmanager

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.telemetry import flight, ops_server, profiler

from chaos import canonical, run_chaos


def _counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


@pytest.fixture
def stopped_profiler():
    """Guarantee the process singleton is stopped (and capture rate
    state cleared) after the test, whatever happened inside."""
    yield
    profiler.stop_profiler()
    with profiler._capture_lock:
        profiler._last_capture_t = None


@pytest.fixture
def busy_thread():
    """A thread with a recognizable stack for the sampler to find."""
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(i * i for i in range(500))
            time.sleep(0.001)

    t = threading.Thread(target=spin, name="busy-probe", daemon=True)
    t.start()
    yield t
    stop.set()
    t.join(5)


# ---------------------------------------------------------------------------
# Sampler lifecycle + exports
# ---------------------------------------------------------------------------


def test_sampler_lifecycle_and_exports(stopped_profiler, busy_thread):
    p = profiler.SamplingProfiler(hz=100)
    assert not p.running
    p.start()
    assert p.running
    p.start()  # idempotent
    time.sleep(0.35)
    p.drain()
    assert not p.running
    assert p.ticks > 5
    assert p.samples > 0

    snap = p.snapshot()
    assert snap and all(isinstance(k, tuple) and n > 0
                        for k, n in snap.items())
    # the sampler never profiles itself
    assert not any(label.startswith(profiler.__name__ + ":")
                   for stack in snap for label in stack)

    total = sum(snap.values())
    mods = p.by_module()
    assert sum(m["samples"] for m in mods) == total
    assert all(0 <= m["share"] <= 1 for m in mods)
    funcs = p.by_function(top=5)
    assert len(funcs) <= 5

    # collapsed-stack text: `a;b;c N` per line (flamegraph.pl input)
    collapsed = p.collapsed()
    for line in collapsed.strip().splitlines():
        assert re.fullmatch(r"\S.*? \d+", line), line
    # nested flamegraph: root counts every sample, children bounded
    flame = p.flamegraph()
    assert flame["name"] == "all" and flame["value"] == total
    assert sum(c["value"] for c in flame.get("children", [])) <= total

    p.reset()
    assert p.samples == 0 and p.snapshot() == {}


def test_sampling_cost_is_bounded(stopped_profiler, busy_thread):
    """The continuous-profiling promise in microcosm: the sampler's
    own measured loop cost over a real window is a small fraction of
    that window."""
    cost0 = _counter("profiler.sample.seconds")
    samples0 = _counter("profiler.samples")
    p = profiler.start_profiler(hz=50)
    time.sleep(0.5)
    profiler.stop_profiler()
    assert not p.running
    assert _counter("profiler.samples") > samples0
    assert _counter("profiler.sample.seconds") - cost0 < 0.1


def test_process_singleton(stopped_profiler):
    p1 = profiler.start_profiler(hz=31)
    p2 = profiler.start_profiler(hz=7)  # second start keeps the first
    assert p1 is p2 and p2.hz == 31
    assert profiler.get_profiler() is p1
    profiler.stop_profiler()
    assert not p1.running


def test_atexit_stop_is_safe_and_idempotent(stopped_profiler):
    p = profiler.start_profiler(hz=50)
    profiler._atexit_stop()   # what interpreter shutdown runs
    assert not p.running
    profiler._atexit_stop()   # and again, after everything stopped
    assert not p.running


def test_configure_respects_enabled_knob(stopped_profiler, tmp_path):
    off = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh")})
    p = profiler.configure(off)  # default: enabled=false
    assert p is None or not p.running

    on = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.telemetry.profiler.enabled": "true",
        "spark.hyperspace.telemetry.profiler.hz": "43",
    })
    p = profiler.configure(on)
    assert p is not None and p.running and p.hz == 43


# ---------------------------------------------------------------------------
# Triggered device capture (device_trace stubbed)
# ---------------------------------------------------------------------------


def _capture_conf(tmp_path, **extra):
    conf = {
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.telemetry.slowlog.dir": str(tmp_path / "sl"),
        "spark.hyperspace.telemetry.profiler.capture.seconds": "0.01",
        "spark.hyperspace.telemetry.profiler.capture.min.interval."
        "seconds": "0",
    }
    conf.update({k: str(v) for k, v in extra.items()})
    return HyperspaceConf(conf)


@pytest.fixture
def stub_trace(monkeypatch):
    """Replace the jax seam with a stub that writes a marker file —
    the capture plumbing (tmp dir, atomic rename, pruning, counters)
    is what's under test."""
    traced = []

    @contextmanager
    def fake_trace(path):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "trace.marker"), "w") as f:
            f.write("x")
        traced.append(path)
        yield

    monkeypatch.setattr(profiler, "device_trace", fake_trace)
    return traced


def _wait_done(paths, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        recent = {c["path"]: c["state"]
                  for c in profiler.recent_captures(32)}
        if all(recent.get(p) in ("done", "error") for p in paths):
            return recent
        time.sleep(0.02)
    raise AssertionError(f"captures never settled: {recent}")


def test_capture_disabled_returns_none(tmp_path, stopped_profiler):
    conf = _capture_conf(tmp_path)
    conf.set("spark.hyperspace.telemetry.profiler.capture.seconds",
             "0")
    assert profiler.request_capture(conf) is None
    assert profiler.maybe_capture_on_burn(conf, 5.0) is None


def test_triggered_capture_atomic_and_pruned(tmp_path, stub_trace,
                                             stopped_profiler):
    conf = _capture_conf(
        tmp_path, **{"spark.hyperspace.telemetry.profiler.capture."
                     "keep": "2"})
    captures0 = _counter("profiler.captures")
    paths = []
    for i in range(4):
        target = profiler.request_capture(conf, reason=f"manual-{i}")
        assert target is not None
        paths.append(target)
        _wait_done([target])
    states = _wait_done(paths)
    assert all(states[p] == "done" for p in paths)
    assert _counter("profiler.captures") == captures0 + 4

    entries = os.listdir(conf.slowlog_dir)
    kept = [e for e in entries if e.startswith("profile-")]
    # keep-N pruned to the newest 2, no half-written .tmp survives
    assert len(kept) == 2
    assert not any(e.endswith(".tmp") for e in entries)
    assert sorted(os.path.join(conf.slowlog_dir, e) for e in kept) == \
        sorted(paths[-2:])
    for e in kept:
        assert os.path.exists(os.path.join(conf.slowlog_dir, e,
                                           "trace.marker"))


def test_capture_rate_limited(tmp_path, stub_trace, stopped_profiler):
    conf = _capture_conf(
        tmp_path, **{"spark.hyperspace.telemetry.profiler.capture."
                     "min.interval.seconds": "3600"})
    with profiler._capture_lock:
        profiler._last_capture_t = None
    first = profiler.request_capture(conf, reason="first")
    assert first is not None
    assert profiler.request_capture(conf, reason="too-soon") is None
    _wait_done([first])


def test_burn_hook_fires_only_above_one(tmp_path, stub_trace,
                                        stopped_profiler):
    conf = _capture_conf(tmp_path)
    assert profiler.maybe_capture_on_burn(conf, None) is None
    assert profiler.maybe_capture_on_burn(conf, 0.7) is None
    assert profiler.maybe_capture_on_burn(conf, 1.0) is None
    target = profiler.maybe_capture_on_burn(conf, 2.5)
    assert target is not None
    entry = profiler.recent_captures()[-1]
    assert entry["reason"] == "slo-burn:2.50"
    _wait_done([target])


def test_capture_error_counted_and_tmp_cleaned(tmp_path, monkeypatch,
                                               stopped_profiler):
    @contextmanager
    def broken_trace(path):
        os.makedirs(path, exist_ok=True)
        raise RuntimeError("no profiler backend")
        yield  # pragma: no cover

    monkeypatch.setattr(profiler, "device_trace", broken_trace)
    errors0 = _counter("profiler.capture_errors")
    conf = _capture_conf(tmp_path)
    target = profiler.request_capture(conf, reason="doomed")
    assert target is not None
    states = _wait_done([target])
    assert states[target] == "error"
    assert _counter("profiler.capture_errors") == errors0 + 1
    assert not os.path.exists(target)
    assert not os.path.exists(target + ".tmp")


def test_slowlog_dump_embeds_capture_path(tmp_path, stub_trace,
                                          stopped_profiler):
    """A slow query's dump carries its own anatomy AND the device
    profile it triggered."""
    rng = np.random.default_rng(9)
    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(pa.table({
        "a": rng.integers(0, 100, 2000).astype(np.int64),
    }), str(data / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.telemetry.slowlog.seconds": "0.000001",
        "spark.hyperspace.telemetry.slowlog.dir": str(tmp_path / "sl"),
        "spark.hyperspace.telemetry.profiler.capture.seconds": "0.01",
        "spark.hyperspace.telemetry.profiler.capture.min.interval."
        "seconds": "0",
    }))
    sess.read_parquet(str(data)).filter(col("a") > lit(10)).collect()
    # Dumps ride the flight recorder's background writer lane; flush
    # it before reading (the dir itself is created by the lane job).
    flight.get_recorder().drain()
    dumps = [f for f in os.listdir(tmp_path / "sl")
             if f.endswith(".json")]
    assert dumps
    with open(tmp_path / "sl" / sorted(dumps)[-1]) as f:
        doc = json.load(f)
    assert "critical_path" in doc
    assert abs(doc["critical_path"]["sum_s"]
               - doc["critical_path"]["wall_s"]) <= 1e-4
    assert doc["device_profile"].startswith(str(tmp_path / "sl"))
    _wait_done([doc["device_profile"]])


# ---------------------------------------------------------------------------
# Endpoint round-trips
# ---------------------------------------------------------------------------


@pytest.fixture
def server():
    srv = ops_server.start_server(port=0)
    yield srv
    ops_server.stop_server()


def _get(srv, path):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10)
    except urllib.error.HTTPError as exc:
        resp = exc
    with resp:
        return resp.status, resp.headers.get("Content-Type"), \
            resp.read().decode("utf-8")


def test_profile_endpoint_round_trip(server, stopped_profiler,
                                     busy_thread):
    profiler.start_profiler(hz=97)
    time.sleep(0.25)
    status, ctype, body = _get(server, "/profile")
    assert status == 200 and ctype == "application/json"
    doc = json.loads(body)
    assert doc["enabled"] is True and doc["hz"] == 97
    assert doc["samples"] > 0 and doc["flamegraph"]["value"] >= 0
    assert isinstance(doc["captures"], list)

    status, ctype, text = _get(server, "/profile?format=collapsed")
    assert status == 200 and ctype.startswith("text/plain")
    assert text == "" or re.fullmatch(
        r"\S.*? \d+", text.strip().splitlines()[0])

    profiler.stop_profiler()
    status, _ctype, body = _get(server, "/profile")
    assert json.loads(body)["enabled"] is False


def test_critpath_endpoint_round_trip(server, tmp_path):
    rng = np.random.default_rng(4)
    data = tmp_path / "data"
    data.mkdir()
    pq.write_table(pa.table({
        "a": rng.integers(0, 100, 2000).astype(np.int64),
    }), str(data / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
    }))
    sess.read_parquet(str(data)).filter(col("a") > lit(50)).collect()

    status, ctype, body = _get(server, "/critpath")
    assert status == 200 and ctype == "application/json"
    doc = json.loads(body)
    from hyperspace_tpu.telemetry.critical_path import SEGMENTS
    assert set(doc["window"]["shares"]) == set(SEGMENTS)
    assert doc["recent"], "the served query's stamp must appear"
    cp = doc["recent"][-1]["critical_path"]
    assert abs(cp["sum_s"] - cp["wall_s"]) <= 1e-4
    assert doc["totals"]["critpath.queries"] >= 1

    status, _ctype, body = _get(server, "/nope")
    assert status == 404 and "/critpath" in body and "/profile" in body


# ---------------------------------------------------------------------------
# Chaos with the profiler ON: visibility must not cost liveness
# ---------------------------------------------------------------------------


def test_chaos_run_with_profiler_on(tmp_path, stopped_profiler):
    rng = np.random.default_rng(11)
    n = 20_000
    facts = tmp_path / "facts"
    facts.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 500, n).astype(np.int64),
        "g": rng.integers(0, 16, n).astype(np.int64),
        "v": rng.random(n).astype(np.float64),
    }), str(facts / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
    }))
    fact = sess.read_parquet(str(facts))
    workload = [
        ("filter", fact.filter(col("k") > lit(250))),
        ("agg", fact.group_by("g").agg(("sum", "v", "sv"))),
        ("proj", fact.filter(col("g") == lit(3)).select("k", "v")),
    ]
    expected = {name: canonical(df.collect()) for name, df in workload}

    profiler.start_profiler(hz=67)
    try:
        report = run_chaos(workload, expected, clients=6,
                           total_queries=90)
    finally:
        profiler.stop_profiler()

    assert report.stuck_threads == [], report.summary()
    assert report.mismatches == [], report.summary()
    assert report.outcomes["ok"] == 90, report.summary()
    # the sampler watched the whole run and every ok query got stamped
    p = profiler.get_profiler()
    assert p is not None and p.samples > 0
    stamped = [m for m in report.success_metrics
               if getattr(m, "critical_path", None) is not None]
    assert len(stamped) == len(report.success_metrics)
    for qm in stamped:
        cp = qm.critical_path
        assert abs(cp["sum_s"] - cp["wall_s"]) <= 1e-4
