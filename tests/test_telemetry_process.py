"""Process-wide observability (PR 2): metrics registry, trace-span
export (Chrome trace-event schema round-trip), structured action
reports, and mesh-path telemetry on the virtual 8-device mesh."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import Hyperspace, IndexConfig, telemetry
from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.plan.expr import col, lit


@pytest.fixture
def tracing():
    """Enable the process tracer for one test, always tearing it back
    down (the tracer is process-global)."""
    tracer = telemetry.enable_tracing()
    try:
        yield tracer
    finally:
        telemetry.disable_tracing()


@pytest.fixture
def sales_env(tmp_path):
    """Two joinable tables + a session factory with a tmp warehouse."""
    rng = np.random.default_rng(23)
    n, n_dim = 5000, 200
    fact_dir = tmp_path / "fact"
    dim_dir = tmp_path / "dim"
    fact_dir.mkdir()
    dim_dir.mkdir()
    pq.write_table(pa.table({
        "key": rng.integers(0, n_dim, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": rng.random(n) * 100,
    }), str(fact_dir / "part-0.parquet"))
    pq.write_table(pa.table({
        "key": np.arange(n_dim, dtype=np.int64),
        "grp": rng.integers(0, 10, n_dim).astype(np.int64),
    }), str(dim_dir / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh"),
                "spark.hyperspace.index.num.buckets": "8"}
        conf.update(extra)
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(fact_dir), str(dim_dir)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = telemetry.MetricsRegistry()
    reg.counter("a.b").inc()
    reg.counter("a.b").inc(2.5)
    reg.gauge("g").set(7)
    for v in (1, 3, 1000, 0.25, 0):
        reg.histogram("h.bytes").observe(v)
    assert reg.counter("a.b").value == 3.5
    assert reg.gauge("g").value == 7
    h = reg.histogram("h.bytes").to_dict()
    assert h["count"] == 5 and h["min"] == 0 and h["max"] == 1000
    # log2 buckets: 1 -> le 1, 3 -> le 4, 1000 -> le 1024, 0.25 -> le
    # 0.25, 0 -> the "0" bucket.
    assert h["buckets"]["1024.0"] == 1 and h["buckets"]["0"] == 1
    snap = reg.to_dict()
    assert snap["counters"]["a.b"] == 3.5
    assert "h.bytes" in snap["histograms"]
    # name collisions across types are an error, not silent aliasing
    with pytest.raises(TypeError):
        reg.gauge("a.b")


def test_registry_prometheus_text():
    reg = telemetry.MetricsRegistry()
    reg.counter("fusion.stage_execs").inc(4)
    reg.gauge("mesh.devices").set(8)
    reg.histogram("link.h2d.bytes_per_transfer").observe(100)
    reg.histogram("link.h2d.bytes_per_transfer").observe(5000)
    text = reg.to_text()
    assert "# TYPE hs_fusion_stage_execs counter" in text
    assert "hs_fusion_stage_execs 4" in text
    assert "hs_mesh_devices 8" in text
    # histogram exposition: cumulative buckets, +Inf, sum, count
    assert 'hs_link_h2d_bytes_per_transfer_bucket{le="128"} 1' in text
    assert 'hs_link_h2d_bytes_per_transfer_bucket{le="+Inf"} 2' in text
    assert "hs_link_h2d_bytes_per_transfer_count 2" in text


def test_process_registry_shared_across_sessions(sales_env):
    session, fact_dir, _dim = sales_env
    s1, s2 = session(), session()
    assert s1.metrics_registry() is s2.metrics_registry()
    assert s1.metrics_registry() is telemetry.get_registry()
    before = s1.metrics_registry().counter("queries.total").value
    s1.read_parquet(fact_dir).select("key").collect()
    s2.read_parquet(fact_dir).select("qty").collect()
    reg = s1.metrics_registry()
    assert reg.counter("queries.total").value == before + 2
    assert reg.counter("queries.seconds").value > 0


def test_fusion_stats_is_registry_view(sales_env):
    from hyperspace_tpu.engine import fusion

    session, fact_dir, _dim = sales_env
    sess = session(**{
        "spark.hyperspace.execution.min.device.rows": "0",
        "spark.hyperspace.distribution.enabled": "false"})
    for k in fusion.STATS:
        fusion.STATS[k] = 0 if isinstance(fusion.STATS[k], int) else 0.0
    q = sess.read_parquet(fact_dir).filter(
        col("qty") > lit(10)).select("key")
    q.collect()
    # One storage, two views: the dict-shaped consumer contract and the
    # registry counter agree exactly.
    reg = telemetry.get_registry()
    assert fusion.STATS["stage_execs"] >= 1
    assert reg.counter("fusion.stage_execs").value \
        == fusion.STATS["stage_execs"]
    assert reg.counter("fusion.dispatch_s").value \
        == fusion.STATS["dispatch_s"]
    # Fused device lane promoted host batches over the link — the
    # transfer histograms saw it.
    assert reg.counter("link.h2d.bytes").value > 0
    assert reg.histogram("link.h2d.bytes_per_transfer").count > 0


# ---------------------------------------------------------------------------
# Action reports
# ---------------------------------------------------------------------------


def test_action_reports_full_maintenance_cycle(sales_env, tmp_path):
    session, fact_dir, _dim = sales_env
    sess = session()
    hs = Hyperspace(sess)
    reg = sess.metrics_registry()

    def runs(name):
        return reg.counter(f"actions.{name}.runs").value

    base = {n: runs(n) for n in ("CreateAction", "RefreshAction",
                                 "OptimizeAction")}
    fact = sess.read_parquet(fact_dir)
    hs.create_index(fact, IndexConfig("sales_key", ["key"],
                                      ["qty", "price"]))
    hs.refresh_index("sales_key", mode="full")
    hs.optimize_index("sales_key")

    # The acceptance surface: nonzero action-report counters after a
    # create+refresh+optimize cycle, via session.metrics_registry().
    assert runs("CreateAction") == base["CreateAction"] + 1
    assert runs("RefreshAction") == base["RefreshAction"] + 1
    assert runs("OptimizeAction") == base["OptimizeAction"] + 1
    assert reg.counter("actions.rows_indexed").value > 0
    assert reg.counter("actions.bytes_written").value > 0

    # The report ring holds the structured reports, newest last.
    report = reg.last_action_report()
    assert report["action"] == "OptimizeAction"
    assert report["ok"] is True and report["index"] == "sales_key"
    assert set(report["phases"]) == {"validate", "begin", "op", "end"}
    assert all(v >= 0 for v in report["phases"].values())
    assert report["detail"]["rows"] > 0 and report["detail"]["bytes"] > 0
    assert report["detail"]["files_written"] > 0

    # Persisted alongside the final log entry, keyed by its id.
    log_dir = os.path.join(sess.conf.system_path, "sales_key",
                           "_hyperspace_log")
    sidecars = sorted(f for f in os.listdir(log_dir)
                      if f.endswith(".report.json"))
    assert len(sidecars) == 3  # create, refresh, optimize
    with open(os.path.join(log_dir, sidecars[0])) as f:
        persisted = json.load(f)
    assert persisted["action"] == "CreateAction"
    assert persisted["log_id"] == int(sidecars[0].split(".")[0])
    # ...and readable back through the log manager API.
    from hyperspace_tpu.index.log_manager import IndexLogManagerImpl
    lm = IndexLogManagerImpl(os.path.join(sess.conf.system_path,
                                          "sales_key"))
    assert lm.get_action_report(persisted["log_id"])["action"] \
        == "CreateAction"
    # The sidecars never perturb log-id resolution.
    assert lm.get_latest_id() == persisted["log_id"] + 4


def test_failed_action_reports_failure_counter(sales_env):
    session, fact_dir, _dim = sales_env
    sess = session()
    hs = Hyperspace(sess)
    reg = sess.metrics_registry()
    fact = sess.read_parquet(fact_dir)
    hs.create_index(fact, IndexConfig("dupe", ["key"], ["qty"]))
    before = reg.counter("actions.CreateAction.failures").value
    with pytest.raises(HyperspaceException):
        hs.create_index(fact, IndexConfig("dupe", ["key"], ["qty"]))
    assert reg.counter("actions.CreateAction.failures").value \
        == before + 1
    report = reg.last_action_report()
    assert report["ok"] is False and "error" in report
    assert "log_id" not in report  # nothing was committed


# ---------------------------------------------------------------------------
# Trace export
# ---------------------------------------------------------------------------


def test_export_trace_requires_enable(tmp_path):
    assert not telemetry.tracing_enabled()
    with pytest.raises(HyperspaceException):
        telemetry.export_trace(str(tmp_path / "t.json"))


def test_trace_export_roundtrip_chrome_schema(sales_env, tmp_path,
                                              tracing):
    session, fact_dir, dim_dir = sales_env
    sess = session(**{
        "spark.hyperspace.execution.min.device.rows": "0",
        "spark.hyperspace.distribution.enabled": "false"})
    hs = Hyperspace(sess)
    fact = sess.read_parquet(fact_dir)
    dim = sess.read_parquet(dim_dir)
    hs.create_index(fact, IndexConfig("tr_fact", ["key"],
                                      ["qty", "price"]))
    hs.create_index(dim, IndexConfig("tr_dim", ["key"], ["grp"]))
    sess.enable_hyperspace()
    # Bucketed SMJ: both sides read concurrently on pool threads.
    (fact.join(dim, on="key").select("qty", "grp")).collect()
    # Fused filter on the forced device lane: link-transfer spans.
    fact.filter(col("qty") > lit(5)).select("price").collect()

    path = str(tmp_path / "trace.json")
    info = telemetry.export_trace(path)
    assert info["path"] == path and info["events"] > 0

    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0

    spans = [e for e in events if e["ph"] == "X"]
    cats = {e.get("cat") for e in spans}
    assert {"query", "operator", "fusion", "link", "action"} <= cats
    # Spans from at least two REAL threads (join sides on the pool).
    op_tids = {e["tid"] for e in spans if e.get("cat") == "operator"}
    assert len(op_tids) >= 2
    # Nesting: an operator span contained within a query span on the
    # same thread (Chrome nests by ts/dur containment).
    queries = [e for e in spans if e.get("cat") == "query"]
    nested = [
        (q, o) for q in queries
        for o in spans if o.get("cat") == "operator"
        and o["tid"] == q["tid"] and o["ts"] >= q["ts"]
        and o["ts"] + o["dur"] <= q["ts"] + q["dur"] + 1.0]
    assert nested, "no operator span nested inside a query span"
    # ...and a link transfer nested inside the query window too.
    links = [e for e in spans if e.get("cat") == "link"]
    assert links and all(e["args"]["bytes"] >= 0 for e in links)
    # Thread-name metadata present for the engine process.
    metas = [e for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"]
    assert metas


def test_facade_export_trace(sales_env, tmp_path, tracing):
    session, fact_dir, _dim = sales_env
    sess = session()
    hs = Hyperspace(sess)
    sess.read_parquet(fact_dir).select("key").collect()
    out = hs.export_trace(str(tmp_path / "t.json"))
    assert os.path.exists(out["path"])
    assert hs.metrics_registry() is telemetry.get_registry()


# ---------------------------------------------------------------------------
# Mesh-path telemetry (virtual 8-device mesh; conftest ensures devices)
# ---------------------------------------------------------------------------


def test_mesh_build_telemetry_and_device_spans(tmp_path, sales_env,
                                               tracing):
    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.parallel.build import distributed_build
    from hyperspace_tpu.parallel.mesh import make_mesh
    from hyperspace_tpu.telemetry.trace import PID_MESH

    mesh = make_mesh(8)
    reg = telemetry.get_registry()
    assert reg.gauge("mesh.devices").value == 8
    execs_before = reg.counter("mesh.build.execs").value

    rng = np.random.default_rng(5)
    batch = columnar.from_arrow(pa.table({
        "k": rng.integers(0, 100, 2000).astype(np.int64),
        "v": rng.random(2000)}))
    # Recorder propagation: the mesh path attributes its events and
    # sync seconds to the active per-query recorder.
    rec = telemetry.QueryMetrics("mesh build")
    with telemetry.recording(rec):
        built, lengths = distributed_build(batch, ["k"], 16, mesh)
    assert built.num_rows == 2000

    assert reg.counter("mesh.build.execs").value == execs_before + 1
    assert reg.counter("mesh.build.dispatch_s").value > 0
    assert reg.histogram("mesh.build.shard_rows").count >= 8
    mesh_events = rec.events_of("mesh", "build")
    assert mesh_events and mesh_events[0]["shards"] == 8
    assert sum(mesh_events[0]["shard_rows"]) == 2000
    assert rec.counters["mesh.sync_s"] >= 0
    # Per-device span attribution on the synthetic mesh process: one
    # track per device, rows in args.
    dev_spans = [e for e in tracing.events
                 if e.get("pid") == PID_MESH and e["ph"] == "X"]
    assert {e["tid"] for e in dev_spans} == set(range(8))
    assert sum(e["args"]["rows"] for e in dev_spans
               if e["name"].startswith("build")) == 2000


def test_mesh_join_query_attributes_to_recorder(sales_env, tracing):
    """A distributed bucketed join inside collect(): mesh events, shard
    attribution, and link bytes all land on THAT query's recorder
    (propagation across the join's pool threads included)."""
    session, fact_dir, dim_dir = sales_env
    sess = session(**{"spark.hyperspace.distribution.enabled": "true"})
    hs = Hyperspace(sess)
    fact = sess.read_parquet(fact_dir)
    dim = sess.read_parquet(dim_dir)
    hs.create_index(fact, IndexConfig("mj_fact", ["key"],
                                      ["qty", "price"]))
    hs.create_index(dim, IndexConfig("mj_dim", ["key"], ["grp"]))
    sess.enable_hyperspace()
    _, m = (fact.join(dim, on="key").select("qty", "grp")).collect(
        with_metrics=True)
    joins = m.events_of("mesh", "join")
    assert joins, f"no mesh join events; got {m.events}"
    assert joins[0]["shards"] == 8
    assert len(joins[0]["shard_rows"]) == 8
    assert m.counters.get("link.h2d_bytes", 0) > 0
    reg = telemetry.get_registry()
    assert reg.counter("mesh.join.execs").value >= 1
    assert reg.histogram("mesh.join.shard_rows").count >= 8
