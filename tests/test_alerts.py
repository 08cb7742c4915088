"""Incident plane: rule sustain/hysteresis against scripted series,
SLO-burn chaos firing exactly ONE evidence-bundled incident, durable
history segments surviving a crash-torn writer, and cross-process
history merge into one CLI trend report."""

import json
import os
import time

import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine import scheduler as sched_mod
from hyperspace_tpu.engine.scheduler import QueryScheduler
from hyperspace_tpu.telemetry import alerts, history, timeseries
from hyperspace_tpu.telemetry.alerts import AlertManager, AlertRule
from hyperspace_tpu.telemetry.history import TelemetryHistory
from hyperspace_tpu.telemetry.timeseries import TimeSeriesSampler


@pytest.fixture
def fresh_scheduler():
    sch = sched_mod.set_scheduler(QueryScheduler())
    yield sch
    sched_mod.set_scheduler(QueryScheduler())


@pytest.fixture
def no_history():
    """Tests that must not write segments anywhere."""
    prev = history.get_history()
    history.reset_history()
    yield
    history.set_history(prev)


def _counters(*names):
    c = telemetry.get_registry().counters_dict()
    return tuple(c.get(n, 0) for n in names)


# ---------------------------------------------------------------------------
# Sustain + hysteresis against a scripted series
# ---------------------------------------------------------------------------


def test_sustain_and_hysteresis_scripted_gauge(no_history):
    """The full lifecycle, driven tick-by-tick with scripted times: a
    breach must HOLD for sustain_s (one hiccup resets the clock), a
    firing rule resolves only across `clear` (the hysteresis band
    between clear and threshold neither resolves nor suppresses), and
    the counters agree exactly."""
    reg = telemetry.get_registry()
    g = reg.gauge("testx.alerts.gauge")
    rule = AlertRule("test_gauge", "gauge", "testx.alerts.gauge",
                     threshold=10.0, clear=5.0, sustain_s=3.0,
                     description="scripted")
    m = AlertManager(rules=[rule])
    ev0, f0, r0, s0 = _counters("alerts.evaluations", "alerts.fired",
                                "alerts.resolved", "alerts.suppressed")

    g.set(20.0)
    assert m.evaluate(now=100.0) == []      # breach starts, not sustained
    g.set(4.0)
    assert m.evaluate(now=101.0) == []      # hiccup: sustain clock reset
    g.set(20.0)
    assert m.evaluate(now=102.0) == []      # breach restarts
    assert m.evaluate(now=104.9) == []      # 2.9s held < 3s sustain
    fired = m.evaluate(now=105.1)           # 3.1s held: fires
    assert len(fired) == 1
    assert fired[0]["rule"] == "test_gauge"
    assert fired[0]["state"] == "firing"
    assert m.active_count() == 1

    g.set(7.0)                              # hysteresis band (5 < 7 < 10)
    assert m.evaluate(now=106.0) == []      # neither resolved nor breach
    g.set(20.0)
    assert m.evaluate(now=107.0) == []      # repeat breach: suppressed
    g.set(4.0)
    resolved = m.evaluate(now=108.0)        # crosses clear: resolves
    assert len(resolved) == 1
    assert resolved[0]["state"] == "resolved"
    assert resolved[0]["resolved_at"] == 108.0
    assert resolved[0]["id"] == fired[0]["id"]
    assert m.active_count() == 0

    ev, f, r, s = _counters("alerts.evaluations", "alerts.fired",
                            "alerts.resolved", "alerts.suppressed")
    assert (ev - ev0, f - f0, r - r0, s - s0) == (8, 1, 1, 1)
    # The exact-agreement contract, post-lifecycle.
    assert (f - f0) - (r - r0) == m.active_count() == 0
    assert reg.to_dict()["gauges"]["alerts.active"] == 0


def test_window_delta_rule_fires_and_decays_with_scripted_ticks(
        no_history):
    """A breaker-open-shaped rule (window_delta, sustain 0) against a
    scripted sampler: the delta fires on the tick that sees the
    increment and resolves once the window slides past it."""
    reg = telemetry.get_registry()
    c = reg.counter("testx.alerts.opened")
    sampler = TimeSeriesSampler(interval_s=1.0, capacity=64,
                                window_s=4.0,
                                counter_prefixes=("testx.",))
    rule = AlertRule("test_breaker", "window_delta",
                     "testx.alerts.opened", threshold=0.0, clear=0.5,
                     sustain_s=0.0, description="scripted breaker")
    m = AlertManager(rules=[rule])

    sampler.tick(t=200.0)
    assert m.evaluate(sampler=sampler, now=200.0) == []
    c.inc()
    sampler.tick(t=201.0)
    fired = m.evaluate(sampler=sampler, now=201.0)
    assert len(fired) == 1 and fired[0]["state"] == "firing"
    assert fired[0]["value"] == 1.0
    # The window still covers the increment: suppressed, not re-fired.
    sampler.tick(t=202.0)
    assert m.evaluate(sampler=sampler, now=202.0) == []
    # Slide past the 4s window: delta decays to 0 < clear, resolves.
    for t in (203.0, 204.0, 205.0, 206.0, 207.0):
        sampler.tick(t=t)
    resolved = m.evaluate(sampler=sampler, now=207.0)
    assert len(resolved) == 1 and resolved[0]["state"] == "resolved"
    sampler.drain()


def test_conf_overrides_disable_and_retune(no_history):
    reg = telemetry.get_registry()
    g = reg.gauge("testx.alerts.gauge2")
    rule = AlertRule("test_tune", "gauge", "testx.alerts.gauge2",
                     threshold=10.0, clear=5.0, sustain_s=0.0,
                     description="tunable")
    g.set(20.0)

    # Per-rule kill switch.
    m = AlertManager(rules=[rule])
    off = HyperspaceConf({
        "spark.hyperspace.telemetry.alerts.rule.test_tune.enabled":
            "false"})
    assert m.evaluate(conf=off, now=1.0) == []
    assert m.active_count() == 0

    # Threshold override: 20 no longer breaches a threshold of 50.
    m2 = AlertManager(rules=[rule])
    tuned = HyperspaceConf({
        "spark.hyperspace.telemetry.alerts.rule.test_tune.threshold":
            "50", })
    assert m2.evaluate(conf=tuned, now=1.0) == []
    g.set(60.0)
    assert len(m2.evaluate(conf=tuned, now=2.0)) == 1

    # Global kill switch short-circuits evaluation entirely.
    m3 = AlertManager(rules=[rule])
    ev0 = _counters("alerts.evaluations")[0]
    killed = HyperspaceConf({
        "spark.hyperspace.telemetry.alerts.enabled": "false"})
    assert m3.evaluate(conf=killed, now=1.0) == []
    assert _counters("alerts.evaluations")[0] == ev0


# ---------------------------------------------------------------------------
# SLO-burn chaos: exactly ONE incident, with the full evidence bundle
# ---------------------------------------------------------------------------


def test_slo_burn_chaos_fires_one_evidence_bundled_incident(
        tmp_path, fresh_scheduler):
    """Inject a sustained SLO burn and drive the DEFAULT rule set:
    exactly one incident opens (repeat breaching ticks suppress), its
    evidence bundle carries registry snapshot + window quantiles +
    flight entries with critical paths + a device-capture path + SLO
    state, both transitions persist into the history store, and the
    burn decay resolves it with exact counter agreement."""
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.serve.slo.p99.seconds": "0.01",
        "spark.hyperspace.serve.slo.window.seconds": "1.0",
        "spark.hyperspace.telemetry.profiler.capture.seconds": "0.05",
        "spark.hyperspace.telemetry.profiler.capture.min.interval."
        "seconds": "0",
    })
    hist_dir = tmp_path / "hist"
    prev_hist = history.get_history()
    history.set_history(TelemetryHistory(str(hist_dir), interval_s=1.0))
    prev_mgr = alerts.get_manager()
    m = alerts.set_manager(AlertManager())
    m.configure(conf)
    # Flight entries with stamped critical paths for the bundle.
    for i in range(2):
        qm = telemetry.QueryMetrics(description=f"burnq{i}")
        qm.finish()
        qm.critical_path = {"wall_s": 0.05,
                            "segments": {"host_python": 0.05}}
        telemetry.flight.get_recorder().record(qm)
    f0, r0, s0 = _counters("alerts.fired", "alerts.resolved",
                           "alerts.suppressed")
    try:
        # Chaos: every completed query violates the 10ms target.
        for _ in range(10):
            sch.slo.record(0.05, conf)
        t0 = time.time()
        assert m.evaluate(conf=conf, now=t0) == []        # sustain starts
        fired = m.evaluate(conf=conf, now=t0 + 3.5)       # past 3s sustain
        assert len(fired) == 1
        incident = fired[0]
        assert incident["rule"] == "slo_burn"
        assert incident["value"] > 1.0
        # Still burning: more ticks suppress, never duplicate.
        for dt in (4.0, 4.5, 5.0):
            assert m.evaluate(conf=conf, now=t0 + dt) == []
        f, r, s = _counters("alerts.fired", "alerts.resolved",
                            "alerts.suppressed")
        assert (f - f0, r - r0) == (1, 0)
        assert s - s0 >= 3
        assert m.active_count() == 1 == (f - f0) - (r - r0)

        # The evidence bundle is complete.
        ev = incident["evidence"]
        for key in ("registry", "window_quantiles", "flight", "slowlog",
                    "device_profile", "slo", "captured_at"):
            assert key in ev, key
        assert "counters" in ev["registry"]
        assert not isinstance(ev["flight"], dict)
        flights = {e["description"]: e for e in ev["flight"]}
        assert flights["burnq1"]["critical_path"]["segments"]
        assert ev["slowlog"]["kind"] == "hyperspace-slowlog"
        assert isinstance(ev["device_profile"], str)  # capture path
        assert ev["slo"]["window_violations"] >= 10

        # The firing transition persisted durably, reason "incident".
        segs, skipped = history.read_segments(str(hist_dir))
        assert skipped == 0
        fire_segs = [d for d in segs if d["reason"] == "incident"]
        assert len(fire_segs) == 1
        assert fire_segs[0]["incidents"][0]["id"] == incident["id"]

        # Recovery: the 1s burn window slides empty, refresh() decays
        # the gauge, the incident resolves.
        time.sleep(1.1)
        resolved = m.evaluate(conf=conf, now=t0 + 10.0)
        assert len(resolved) == 1
        assert resolved[0]["state"] == "resolved"
        assert resolved[0]["id"] == incident["id"]
        f, r, _s = _counters("alerts.fired", "alerts.resolved",
                             "alerts.suppressed")
        assert (f - f0) - (r - r0) == 0 == m.active_count()
        segs, _ = history.read_segments(str(hist_dir))
        states = [d["incidents"][0]["state"] for d in segs
                  if d["reason"] == "incident"]
        assert states == ["firing", "resolved"]

        # The manager's digest tells the same story.
        digest = m.digest()
        assert digest["active"] == 0
        assert digest["incidents"][-1]["rule"] == "slo_burn"
        assert digest["incidents"][-1]["state"] == "resolved"
    finally:
        alerts.set_manager(prev_mgr)
        history.set_history(prev_hist)


# ---------------------------------------------------------------------------
# Durable history: torn segments, pruning, cross-process merge
# ---------------------------------------------------------------------------


def test_history_survives_crash_torn_final_segment(tmp_path, conf):
    """Two clean segments + a torn final segment of a 'crashed' writer
    + a foreign json + a .tmp leftover: the reader keeps the clean
    pair, counts the torn/foreign skips, and the merge stays whole."""
    d = tmp_path / "hist"
    h = TelemetryHistory(str(d), interval_s=1.0)
    assert h.flush(conf=conf, reason="manual", now=1000.0)
    assert h.flush(conf=conf, reason="manual", now=1100.0)
    # A crash mid-write that somehow published half a document.
    (d / "history-1200000-42-000003.json").write_text(
        '{"kind": "hyperspace-telemetry-history", "schema_ver')
    # A foreign-but-parseable file someone dropped in the directory.
    (d / "history-1300000-42-000004.json").write_text(
        '{"kind": "not-ours"}')
    # The atomic-publish tmp of a writer that died pre-rename.
    (d / "history-1400000-42-000005.json.tmp").write_text("{")

    skipped0 = _counters("history.read_skipped")[0]
    segs, skipped = history.read_segments(str(d))
    assert len(segs) == 2
    assert skipped == 2          # torn + foreign; .tmp excluded by name
    assert _counters("history.read_skipped")[0] - skipped0 == 2
    assert [s["written_at"] for s in segs] == [1000.0, 1100.0]
    merged = history.merge(str(d))
    assert merged["segments"] == 2 and merged["skipped"] == 2
    report = history.trend_report(merged, window_s=300.0)
    assert report["samples"] == len(merged["samples"])


def test_history_byte_budget_prunes_oldest(tmp_path, conf):
    d = tmp_path / "hist"
    h = TelemetryHistory(str(d), interval_s=1.0, keep_seconds=0,
                         keep_bytes=1)  # everything but the newest
    p0 = _counters("history.segments_pruned")[0]
    h.flush(conf=conf, reason="manual", now=1000.0)
    h.flush(conf=conf, reason="manual", now=1001.0)
    h.flush(conf=conf, reason="manual", now=1002.0)
    names = sorted(f for f in os.listdir(str(d))
                   if f.endswith(".json"))
    assert len(names) == 1            # newest survives, always
    assert names[0].startswith("history-1002000-")
    assert _counters("history.segments_pruned")[0] - p0 == 2


@pytest.fixture
def scripted_global_sampler(no_history):
    """A fresh GLOBAL sampler (the history writer snapshots it), driven
    by explicit tick(t=...) calls only."""
    s = timeseries.set_sampler(
        TimeSeriesSampler(interval_s=1.0, capacity=64))
    yield s
    timeseries.reset_sampler()


def test_history_cross_process_merge_and_cli_report(
        tmp_path, conf, monkeypatch, capsys, scripted_global_sampler):
    """Two writer lifetimes (distinct pids) into one directory: the
    merge sees both writers, dedups the incident by id with the latest
    state winning, and the CLI renders ONE trend report over the
    combined history."""
    d = tmp_path / "hist"
    reg = telemetry.get_registry()
    incident = {"id": "inc-1-0001", "rule": "slo_burn",
                "state": "firing", "opened_at": 1000.0,
                "resolved_at": None, "value": 2.0, "threshold": 1.0}
    reg.counter("queries.total").inc(5)
    scripted_global_sampler.tick(t=1000.0)
    TelemetryHistory(str(d)).flush(conf=conf, reason="incident",
                                   now=1000.0, incidents=[incident])
    # "Another process" resumes the story and resolves the incident.
    monkeypatch.setattr(
        "hyperspace_tpu.telemetry.history.os.getpid", lambda: 9990042)
    reg.counter("queries.total").inc(7)
    scripted_global_sampler.tick(t=2000.0)
    done = dict(incident, state="resolved", resolved_at=2000.0)
    TelemetryHistory(str(d)).flush(conf=conf, reason="incident",
                                   now=2000.0, incidents=[done])

    merged = history.merge(str(d))
    assert merged["segments"] == 2
    assert len(merged["writers"]) == 2
    assert len(merged["incidents"]) == 1          # deduped by id
    assert merged["incidents"][0]["state"] == "resolved"
    assert len(merged["registry_by_pid"]) == 2
    report = history.trend_report(merged, window_s=3600.0,
                                  series=["queries.total"])
    assert "queries.total" in report["counters"]
    assert report["incidents"] == 1

    # One CLI report over both lifetimes.
    rc = history._main(["report", "--dir", str(d), "--series",
                        "queries.total"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["segments"] == 2
    assert len(doc["writers"]) == 2
    assert doc["incident_list"] == [
        {"id": "inc-1-0001", "rule": "slo_burn", "state": "resolved",
         "opened_at": 1000.0, "resolved_at": 2000.0, "value": 2.0,
         "threshold": 1.0}]
    assert "queries.total" in doc["counters"]


# ---------------------------------------------------------------------------
# The false-positive gate in miniature: a clean lap fires nothing
# ---------------------------------------------------------------------------


def test_clean_closed_loop_lap_fires_zero_incidents(
        tmp_path, fresh_scheduler, no_history):
    """The false-positive gate in miniature: a
    healthy concurrent closed-loop lap with the GLOBAL alert manager
    live (the sampler's tick hook evaluating every default rule) must
    fire ZERO incidents — the plane evaluates, nothing alarms."""
    import threading

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.engine.session import HyperspaceSession
    from hyperspace_tpu.plan.expr import col, lit

    rng = np.random.default_rng(3)
    src = tmp_path / "src"
    src.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 100, 4000).astype(np.int64),
        "v": rng.random(4000),
    }), str(src / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        # SLO tracking live, with a target a healthy CPU lap meets.
        "spark.hyperspace.serve.slo.p99.seconds": "30",
    }))
    manager = alerts.set_manager(AlertManager())
    manager.configure(sess.conf)
    sampler = timeseries.set_sampler(
        TimeSeriesSampler(interval_s=0.05, capacity=256))
    try:
        df = sess.read_parquet(str(src))
        q = df.filter(col("k") == lit(7)).select("k", "v")
        q.collect()                    # warm outside the timed lap
        ev0, f0 = _counters("alerts.evaluations", "alerts.fired")

        def client():
            for _ in range(5):
                q.collect()
                sampler.tick()         # the hook evaluates every rule

        threads = [threading.Thread(target=client) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        sampler.tick()

        ev, f = _counters("alerts.evaluations", "alerts.fired")
        assert ev - ev0 > 0            # the plane was LIVE, not asleep
        assert f - f0 == 0             # and a clean lap fired nothing
        assert manager.active_count() == 0
        assert manager.digest()["active"] == 0
    finally:
        alerts.reset_manager()
        timeseries.reset_sampler()
        sess.close()
