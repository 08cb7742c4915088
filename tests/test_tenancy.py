"""Multi-tenant serving (ISSUE 16): the tenant contextvar seam and
its propagation, tenant resolution/stamping through `collect`, the
weighted-fair (deficit-round-robin) wait queue, per-tenant HBM/queue
quotas, shed-the-burning-tenant-first, the flight ring's `tenant=`
filter (cursor-stable across rotation, composable with `replica=`),
`/healthz` tenant-section error isolation, Prometheus exposition
conformance under metric-hostile tenant ids, and the chargeback
exactness contract behind `Hyperspace.tenant_report()`.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            telemetry)
from hyperspace_tpu.engine import scheduler as sched_mod
from hyperspace_tpu.engine.scheduler import (Deadline, QueryScheduler,
                                             _QueryEntry)
from hyperspace_tpu.exceptions import QueryRejectedError
from hyperspace_tpu.telemetry import flight

MIB = 1024 * 1024


def _counter(name):
    # Unrounded: `counters_dict` rounds to 1e-6, and a delta of two
    # rounded dispatch-seconds readings is off by up to a microsecond
    # once any earlier test in the process has dispatched to a device.
    return telemetry.get_registry().series_snapshot()["counters"].get(
        name, 0)


@pytest.fixture
def fresh_scheduler():
    """A scheduler with clean budgets/queues for this test; a fresh one
    is installed again on teardown so no state leaks either way."""
    sch = sched_mod.set_scheduler(QueryScheduler())
    yield sch
    sched_mod.set_scheduler(QueryScheduler())


@pytest.fixture
def sales_env(tmp_path):
    rng = np.random.default_rng(7)
    n = 3000
    data_dir = tmp_path / "sales"
    data_dir.mkdir()
    pq.write_table(pa.table({
        "key": rng.integers(0, 50, n).astype(np.int64),
        "qty": rng.integers(1, 10, n).astype(np.int64),
    }), str(data_dir / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh")}
        conf.update({k: str(v) for k, v in extra.items()})
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(data_dir)


def _entry(qid, nbytes, tenant="default", timeout_s=None):
    ent = _QueryEntry(qid, Deadline(qid, timeout_s), nbytes, None)
    ent.tenant = tenant
    return ent


def _hold(sch, nbytes, qid="blocker", tenant="holder"):
    """Occupy `nbytes` of the serving budget (a stand-in for a
    long-running admitted query). Returns the entry for `_release`."""
    ent = _entry(qid, nbytes, tenant)
    with sch._cv:
        sch._active[qid] = ent
        sch._grant(ent, telemetry.get_registry())
    return ent


def _finished_metrics(tag, tenant=None, replica=None):
    qm = telemetry.QueryMetrics(description=tag)
    op = qm.start_operator("Scan")
    qm.finish_operator(op, rows_out=5)
    qm.tenant = tenant
    qm.replica = replica
    qm.finish()
    return qm


# ---------------------------------------------------------------------------
# The contextvar seam
# ---------------------------------------------------------------------------


def test_tenant_scope_and_charge_mirror():
    """`tenant_scope` is the billing seam: inside it `charge_tenant`
    mirrors onto the scoped tenant's series, outside onto "default"
    (never dropped), and `propagating` carries the scope to pool
    threads exactly as it carries the recorder and deadline."""
    assert telemetry.current_tenant() == telemetry.DEFAULT_TENANT
    reg = telemetry.get_registry()
    before = _counter("tenant.t-scope.device.flops")
    with telemetry.tenant_scope("t-scope"):
        assert telemetry.current_tenant() == "t-scope"
        # The contract shape: global inc + mirror at the same site.
        reg.counter("device.flops").inc(5)
        assert telemetry.charge_tenant("device.flops", 5) == "t-scope"
        # None is a no-op carrier: the surrounding scope survives.
        with telemetry.tenant_scope(None):
            assert telemetry.current_tenant() == "t-scope"
    assert _counter("tenant.t-scope.device.flops") == before + 5
    assert telemetry.current_tenant() == telemetry.DEFAULT_TENANT
    d0 = _counter("tenant.default.cache.segments.fills")
    reg.counter("cache.segments.fills").inc()
    telemetry.charge_tenant("cache.segments.fills")
    assert _counter("tenant.default.cache.segments.fills") == d0 + 1
    assert "t-scope" in telemetry.known_tenants()

    seen = []
    with telemetry.tenant_scope("t-pool"):
        wrapped = telemetry.propagating(
            lambda: seen.append(telemetry.current_tenant()))
    t = threading.Thread(target=wrapped)
    t.start()
    t.join(5)
    assert seen == ["t-pool"]


def test_tenant_digest_covers_every_charge_family():
    with telemetry.tenant_scope("t-digest"):
        for name in telemetry.TENANT_CHARGE_COUNTERS:
            telemetry.get_registry().counter(name).inc(2)
            telemetry.charge_tenant(name, 2)
    digest = telemetry.tenant_digest()
    assert set(digest["t-digest"]) == set(telemetry.TENANT_CHARGE_COUNTERS)
    assert all(v >= 2 for v in digest["t-digest"].values())
    # Zero-usage tenants still appear (exactness sums need every row).
    assert telemetry.DEFAULT_TENANT in digest


# ---------------------------------------------------------------------------
# Tenant resolution + stamping through collect
# ---------------------------------------------------------------------------


def test_collect_tenant_resolution_and_stamping(sales_env,
                                                fresh_scheduler):
    """Resolution order: explicit `collect(tenant=)` > the session's
    sticky `session.tenant(...)` > "default" — and the EFFECTIVE tenant
    is stamped on the recorder and billed the admission counters."""
    session, data_dir = sales_env
    sess = session()
    df = sess.read_parquet(data_dir).select("key")

    _t, qm = df.collect(with_metrics=True)
    assert qm.tenant == "default"

    sess.tenant("sticky")
    a0 = _counter("serve.tenant.sticky.admitted")
    _t, qm = df.collect(with_metrics=True)
    assert qm.tenant == "sticky"
    assert _counter("serve.tenant.sticky.admitted") == a0 + 1

    e0 = _counter("serve.tenant.explicit.admitted")
    _t, qm = df.collect(with_metrics=True, tenant="explicit")
    assert qm.tenant == "explicit"
    assert _counter("serve.tenant.explicit.admitted") == e0 + 1

    sess.tenant(None)
    _t, qm = df.collect(with_metrics=True)
    assert qm.tenant == "default"

    # The tenant-dimensioned wall histogram observed each query.
    hists = telemetry.get_registry().to_dict()["histograms"]
    assert hists["tenant.sticky.query_wall_s"]["count"] >= 1
    assert hists["tenant.explicit.query_wall_s"]["count"] >= 1


def test_instrumented_jit_charges_active_tenant():
    """Every device dispatch bills the ACTIVE tenant scope: the warm
    dispatch's measured seconds (and modeled flops/bytes when the HLO
    cost is known) land on `tenant.<id>.device.*` at the same site as
    the global inc — so the deltas are exactly equal by construction."""
    import jax.numpy as jnp

    fn = telemetry.instrumented_jit("test.tenancy_kernel",
                                    lambda x: x * 2 + 1, scope="hs.stage")
    x = jnp.arange(64)
    fn(x)  # cold: compile (compile time stays in the compile bucket)

    t0 = {n: _counter(f"tenant.t-bill.{n}")
          for n in telemetry.TENANT_CHARGE_COUNTERS}
    g0 = {n: _counter(n) for n in telemetry.TENANT_CHARGE_COUNTERS}
    with telemetry.tenant_scope("t-bill"):
        fn(x)  # warm: dispatch-seconds charged to the scope
    t1 = {n: _counter(f"tenant.t-bill.{n}")
          for n in telemetry.TENANT_CHARGE_COUNTERS}
    g1 = {n: _counter(n) for n in telemetry.TENANT_CHARGE_COUNTERS}

    assert t1["device.dispatch.seconds"] > t0["device.dispatch.seconds"]
    for n in telemetry.TENANT_CHARGE_COUNTERS:
        assert t1[n] - t0[n] == pytest.approx(g1[n] - g0[n]), n


def test_tenant_report_exactness(sales_env, fresh_scheduler):
    """`Hyperspace.tenant_report()`: per-tenant sums equal the global
    charge counters (bit-exact for the integer families, a few ulps
    for dispatch-seconds), every observed tenant appears, and the
    serving snapshot rides along."""
    session, data_dir = sales_env
    sess = session()
    hs = Hyperspace(sess)
    df = sess.read_parquet(data_dir).select("key")
    df.collect(tenant="rep-a")
    df.collect(tenant="rep-b")
    df.collect()

    rep = hs.tenant_report()
    assert rep["exact"] is True
    for name in telemetry.TENANT_CHARGE_COUNTERS:
        assert rep["totals"][name] == pytest.approx(
            rep["global"][name], rel=1e-9)
    for t in ("rep-a", "rep-b", "default"):
        assert t in rep["tenants"]
        assert set(rep["tenants"][t]["usage"]) == \
            set(telemetry.TENANT_CHARGE_COUNTERS)


# ---------------------------------------------------------------------------
# Weighted-fair admission (unit level: deterministic DRR semantics)
# ---------------------------------------------------------------------------


def _drain_order(sch, conf, n):
    """Selection order of the next `n` dequeues, simulating each
    selected waiter admitting and leaving the queue."""
    order = []
    with sch._cv:
        for _ in range(n):
            ent = sch._drr_select(conf)
            if ent is None:
                break
            order.append(ent.tenant)
            sch._remove_waiter(ent)
    return order


def test_drr_weighted_fairness_and_no_starvation(fresh_scheduler):
    """A weight-2 tenant drains twice per round; a weight-1/2 tenant
    every other round; and a one-tenant burst cannot starve another
    tenant's head the way the old global FIFO could."""
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.tenant.heavy.weight": "2",
        "spark.hyperspace.serve.tenant.light.weight": "0.5"})
    with sch._cv:
        for i in range(8):
            sch._enqueue_waiter(_entry(f"h{i}", 1, "heavy"))
        for i in range(4):
            sch._enqueue_waiter(_entry(f"n{i}", 1, "normal"))
        for i in range(2):
            sch._enqueue_waiter(_entry(f"l{i}", 1, "light"))
    order = _drain_order(sch, conf, 14)
    assert len(order) == 14
    # Per full round: heavy 2, normal 1, light 1/2 — so in the first
    # 7 dequeues heavy got 4, normal 2, light 1 (2x the weight ratio).
    first = order[:7]
    assert first.count("heavy") == 4
    assert first.count("normal") == 2
    assert first.count("light") == 1
    # The burst did not starve anyone: every tenant appears early.
    assert set(order[:4]) >= {"heavy", "normal"}

    # FIFO within a tenant: heavy's own entries drain in arrival order.
    with sch._cv:
        assert not sch._waiters


def test_drr_selection_is_pinned_across_wakeups(fresh_scheduler):
    """The selected head stays selected until it admits or leaves —
    repeated `_drr_select` calls (spurious cv wakeups) must not rotate
    past the pick, or waiters livelock."""
    sch = fresh_scheduler
    conf = HyperspaceConf({})
    with sch._cv:
        sch._enqueue_waiter(_entry("a1", 1, "a"))
        sch._enqueue_waiter(_entry("b1", 1, "b"))
        first = sch._drr_select(conf)
        assert sch._drr_select(conf) is first
        assert sch._drr_select(conf) is first
        sch._remove_waiter(first)
        second = sch._drr_select(conf)
        assert second is not first
        sch._remove_waiter(second)
        assert sch._drr_select(conf) is None


def test_tenant_hbm_fraction_quota_with_progress(fresh_scheduler,
                                                 monkeypatch):
    """`serve.tenant.<id>.hbm.fraction` caps a tenant's CONCURRENT
    admitted bytes at its fraction of the budget — with the progress
    guarantee: a tenant with nothing in flight always admits one."""
    sch = fresh_scheduler
    # `_fits` also charges LIVE device bytes against the budget; any
    # suite that ran real queries before this one leaves cached device
    # buffers that dwarf the toy 1000-byte budget here. Pin that term
    # to zero — this test is about the per-tenant fraction math only.
    monkeypatch.setattr(sch, "_live_device_bytes", lambda: 0)
    conf = HyperspaceConf({
        "spark.hyperspace.serve.hbm.budget.bytes": "1000",
        "spark.hyperspace.serve.tenant.capped.hbm.fraction": "0.2"})
    other = _hold(sch, 10, qid="other", tenant="other")
    try:
        # Progress: capped has nothing in flight — even an entry far
        # over its 200-byte share fits.
        with sch._cv:
            assert sch._fits(_entry("big", 500, "capped"), 1000, conf)
        big = _hold(sch, 500, qid="big", tenant="capped")
        with sch._cv:
            # With 500 B in flight the quota now binds: +100 > 200.
            assert not sch._fits(_entry("more", 100, "capped"),
                                 1000, conf)
            # Another tenant is untouched by capped's quota.
            assert sch._fits(_entry("free", 100, "other"), 1000, conf)
        sch._release(big)
        with sch._cv:
            assert sch._fits(_entry("more", 100, "capped"), 1000, conf)
    finally:
        sch._release(other)


def test_tenant_queue_depth_rejects_only_that_tenant(fresh_scheduler):
    """`serve.tenant.<id>.queue.depth` backpressures the tenant's OWN
    burst before it can occupy the shared queue; other tenants keep
    queueing under the global depth."""
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.hbm.budget.bytes": "100",
        "spark.hyperspace.serve.queue.depth": "10",
        "spark.hyperspace.serve.tenant.noisy.queue.depth": "1"})
    holder = _hold(sch, 100)
    results = []

    def waiter(qid, tenant):
        ent = _entry(qid, 60, tenant)
        try:
            sch._admit(ent, conf)
            results.append((qid, "admitted"))
            sch._release(ent)
        except QueryRejectedError:
            results.append((qid, "rejected"))

    threads = [threading.Thread(target=waiter, args=("n1", "noisy")),
               threading.Thread(target=waiter, args=("q1", "quiet"))]
    for t in threads:
        t.start()
    for _ in range(400):
        with sch._cv:
            if len(sch._waiters) == 2:
                break
        time.sleep(0.005)
    with sch._cv:
        assert len(sch._waiters) == 2

    r0 = _counter("serve.tenant.noisy.rejected")
    with pytest.raises(QueryRejectedError) as ei:
        sch._admit(_entry("n2", 60, "noisy"), conf)
    assert ei.value.phase == "queue"
    assert _counter("serve.tenant.noisy.rejected") == r0 + 1

    sch._release(holder)
    for t in threads:
        t.join(5)
    assert sorted(results) == [("n1", "admitted"), ("q1", "admitted")]


def test_shed_evicts_burning_tenants_queue_first(fresh_scheduler):
    """With SLO shedding active, the tightened queue sheds the BURNING
    tenant's newest waiter to make room for the arriver — the burning
    tenant's burst pays for its own burn, not everyone else."""
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.hbm.budget.bytes": "100",
        "spark.hyperspace.serve.queue.depth": "2",
        "spark.hyperspace.serve.slo.p99.seconds": "0.001",
        "spark.hyperspace.serve.slo.window.seconds": "60",
        "spark.hyperspace.serve.slo.shed.enabled": "true"})
    # Burn both the global window and the burning tenant's own window
    # far past the shed threshold.
    for _ in range(20):
        sch.slo.record(1.0, conf)
        sch._tenant_slo_for("burny").record(1.0, conf)
    assert sch.slo.burn_rate(conf) > sched_mod.SLO_SHED_BURN_THRESHOLD

    holder = _hold(sch, 100)
    outcomes = {}

    def waiter(qid, tenant):
        ent = _entry(qid, 60, tenant)
        try:
            sch._admit(ent, conf)
            outcomes[qid] = "admitted"
            sch._release(ent)
        except QueryRejectedError as exc:
            outcomes[qid] = f"rejected:{exc.phase}"

    burny = threading.Thread(target=waiter, args=("b1", "burny"))
    burny.start()
    for _ in range(400):
        with sch._cv:
            if sch._waiters:
                break
        time.sleep(0.005)

    # Effective depth is 2 // 2 = 1 while shedding: the arriving calm
    # tenant finds the queue "full", the shed hook evicts burny's
    # newest waiter, and the calm query queues in its place.
    shed0 = _counter("serve.slo.shed")
    rej0 = _counter("serve.tenant.burny.rejected")
    calm = threading.Thread(target=waiter, args=("c1", "calm"))
    calm.start()
    burny.join(5)
    assert outcomes.get("b1") == "rejected:queue"
    assert _counter("serve.slo.shed") == shed0 + 1
    assert _counter("serve.tenant.burny.rejected") == rej0 + 1

    sch._release(holder)
    calm.join(5)
    assert outcomes.get("c1") == "admitted"


# ---------------------------------------------------------------------------
# Flight ring: tenant filter + cursor stability (mirrors the PR-11
# rotation pin in test_flight_recorder.py::test_snapshot_incremental_cursor)
# ---------------------------------------------------------------------------


def test_snapshot_tenant_filter_cursor_stable_across_rotation():
    """`snapshot(tenant=)` narrows to one tenant's entries while the
    cursor stays GLOBAL: it advances past other tenants' entries and
    past rotated-out entries, so a filtered consumer skips, never
    stalls — and the filter composes with `replica=`."""
    rec = flight.FlightRecorder(capacity=4)
    for i in range(3):
        rec.record(_finished_metrics(
            f"q{i}", tenant=("acme" if i % 2 == 0 else "zen")))
    fresh, cursor = rec.snapshot(0, tenant="acme")
    assert [m.description for m in fresh] == ["q0", "q2"]
    assert cursor == rec.last_seq  # advanced past zen's q1 too
    again, cursor2 = rec.snapshot(cursor, tenant="acme")
    assert again == [] and cursor2 == cursor

    # More entries than capacity arrive between polls: the filtered
    # consumer gets acme's survivors, cursor jumps past the rotated.
    for i in range(3, 10):
        rec.record(_finished_metrics(
            f"q{i}", tenant=("acme" if i % 2 == 0 else "zen"),
            replica=i % 2))
    fresh, cursor3 = rec.snapshot(cursor, tenant="acme")
    assert [m.description for m in fresh] == ["q6", "q8"]
    assert cursor3 == cursor + 7
    # Composition: acme AND replica 0 (acme entries all landed on 0).
    both, _ = rec.snapshot(cursor, tenant="acme", replica=0)
    assert [m.description for m in both] == ["q6", "q8"]
    none, _ = rec.snapshot(cursor, tenant="acme", replica=1)
    assert none == []
    # A different tenant's view over the same cursor: disjoint entries,
    # identical cursor arithmetic.
    zen, zcur = rec.snapshot(cursor, tenant="zen")
    assert [m.description for m in zen] == ["q7", "q9"]
    assert zcur == cursor3


def test_flight_tenant_filter_e2e(sales_env, fresh_scheduler):
    """Scheduled collects land in the ring with their effective tenant
    stamped; the recorder-level filter sees exactly them."""
    session, data_dir = sales_env
    sess = session()
    rec = sess.flight_recorder()
    cursor = rec.last_seq
    df = sess.read_parquet(data_dir).select("key")
    df.collect(tenant="flt-a")
    df.collect()
    df.collect(tenant="flt-a")
    mine, _ = rec.snapshot(cursor, tenant="flt-a")
    assert len(mine) == 2
    assert all(m.tenant == "flt-a" for m in mine)
    other, _ = rec.snapshot(cursor, tenant="default")
    assert len(other) == 1


# ---------------------------------------------------------------------------
# /healthz tenant section: error isolation
# ---------------------------------------------------------------------------


def test_healthz_tenant_section_error_isolated(fresh_scheduler,
                                               monkeypatch):
    """A tenants-section failure degrades to an `{"error": ...}` stub;
    the rest of the health document is intact (a health endpoint that
    500s because one subsystem is mid-teardown lies about the rest)."""
    from hyperspace_tpu.telemetry import ops_server

    doc = ops_server.healthz_doc()
    assert doc["status"] == "ok"
    assert "tenants" in doc and "error" not in doc["tenants"]

    monkeypatch.setattr(
        QueryScheduler, "tenant_snapshot",
        lambda self, conf=None: (_ for _ in ()).throw(
            RuntimeError("mid-teardown")))
    doc = ops_server.healthz_doc()
    assert doc["status"] == "ok"
    assert "error" in doc["tenants"]
    assert "mid-teardown" in doc["tenants"]["error"]
    for section in ("scheduler", "breakers", "flight"):
        assert "error" not in doc[section], section


def test_healthz_groups_flight_by_tenant(sales_env, fresh_scheduler):
    from hyperspace_tpu.telemetry import ops_server

    session, data_dir = sales_env
    sess = session()
    df = sess.read_parquet(data_dir).select("key")
    df.collect(tenant="hz-a")
    df.collect(tenant="hz-a")
    doc = ops_server.healthz_doc()
    assert doc["flight"]["by_tenant"].get("hz-a", 0) >= 2
    assert "hz-a" in doc["tenants"]
    assert "usage" in doc["tenants"]["hz-a"]


# ---------------------------------------------------------------------------
# Prometheus exposition under metric-hostile tenant ids
# ---------------------------------------------------------------------------


def test_prometheus_conformance_hostile_tenant_ids():
    """Tenant ids are user-supplied strings that land inside metric
    names: exposition must sanitize every id to the Prometheus grammar,
    keep HELP/TYPE per family, and disambiguate ids that COLLIDE after
    sanitization (`a.b` vs `a/b`) with a numeric serial instead of
    emitting a duplicate family."""
    import re

    from hyperspace_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    hostile = ['acme corp/eu-1', 'acme"corp"eu 1', 'acme.corp.eu.1',
               'über-mieter', '1st-tenant', 'tab\ttenant']
    for t in hostile:
        reg.counter(f"tenant.{t}.device.flops").inc(3)
        reg.counter(f"serve.tenant.{t}.admitted").inc()
    text = reg.to_text()

    name_re = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    families = []
    for line in text.splitlines():
        assert line == line.strip()
        if line.startswith("# HELP "):
            families.append(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            assert line.split()[2] == families[-1], \
                "TYPE must follow its family's HELP"
            continue
        sample_name = line.split("{")[0].split()[0]
        assert name_re.match(sample_name), sample_name
    assert all(name_re.match(f) for f in families)
    # One family per dotted source metric: the two colliding ids map
    # to distinct (serial-suffixed) families, never a repeated TYPE.
    assert len(families) == len(set(families))
    assert len(families) == 2 * len(hostile)
    # The HELP line carries the original dotted name for reverse
    # mapping, correctly escaped (the tab rides through as-is; the
    # newline rules are pinned by test_tree_diff's conformance).
    assert 'acme"corp"eu 1' in text


# ---------------------------------------------------------------------------
# tenant_snapshot: the serving-side view
# ---------------------------------------------------------------------------


def test_tenant_snapshot_reports_knobs_and_slo(fresh_scheduler):
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.slo.p99.seconds": "10",
        "spark.hyperspace.serve.slo.window.seconds": "60",
        "spark.hyperspace.serve.tenant.snap.weight": "3",
        "spark.hyperspace.serve.tenant.snap.hbm.fraction": "0.5",
        "spark.hyperspace.serve.tenant.snap.queue.depth": "4"})
    ent = _hold(sch, 128, qid="s1", tenant="snap")
    try:
        sch._tenant_slo_for("snap").record(0.5, conf)
        snap = sch.tenant_snapshot(conf)["snap"]
        assert snap["admitted_bytes"] == 128
        assert snap["inflight"] == 1
        assert snap["queued"] == 0
        assert snap["weight"] == 3.0
        assert snap["hbm_fraction"] == 0.5
        assert snap["queue_depth"] == 4
        assert snap["slo"]["window_queries"] == 1
        assert snap["slo"]["burn_rate"] == 0.0  # 0.5 s under 10 s p99
    finally:
        sch._release(ent)
