"""Serving-plane resilience: admission control, deadlines & cooperative
cancellation, backpressure, the degradation circuit breaker, and the
chaos run over the virtual 8-device mesh.

The acceptance bar this suite pins (ISSUE 7): >=8 client threads x
>=200 mixed queries with fault injection active — zero deadlocks, zero
HBM-budget breaches, every successful query bit-identical to its
serial run, and every rejected/timed-out query surfaced as a TYPED
error with a matching `serve.*` counter.
"""

import os
import shutil
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, telemetry)
from hyperspace_tpu.engine import scheduler as sched_mod
from hyperspace_tpu.engine.scheduler import (Deadline, QueryScheduler,
                                             _QueryEntry)
from hyperspace_tpu.exceptions import (HyperspaceException,
                                       QueryCancelledError,
                                       QueryDeadlineExceededError,
                                       QueryRejectedError)
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.utils.faults import FaultRule

from chaos import canonical, run_chaos

MIB = 1024 * 1024


def _counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


@pytest.fixture
def fresh_scheduler():
    """A scheduler with clean budgets/breakers for this test; a fresh
    one is installed again on teardown so no state leaks either way."""
    sch = sched_mod.set_scheduler(QueryScheduler())
    yield sch
    sched_mod.set_scheduler(QueryScheduler())


@pytest.fixture
def serving_env(tmp_path):
    """facts/dims parquet + a session factory taking conf overrides."""
    rng = np.random.default_rng(11)
    n = 50_000
    n_dims = 500
    facts_dir = tmp_path / "facts"
    dims_dir = tmp_path / "dims"
    facts_dir.mkdir()
    dims_dir.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, n_dims, n).astype(np.int64),
        "g": rng.integers(0, 16, n).astype(np.int64),
        "v": rng.random(n).astype(np.float64),
    }), str(facts_dir / "part-0.parquet"))
    pq.write_table(pa.table({
        "k": np.arange(n_dims, dtype=np.int64),
        "w": rng.random(n_dims).astype(np.float64),
    }), str(dims_dir / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh")}
        conf.update({k: str(v) for k, v in extra.items()})
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(facts_dir), str(dims_dir)


def _hold(sch, nbytes, qid="blocker"):
    """Manually occupy `nbytes` of the serving budget (a stand-in for a
    long-running admitted query). Returns the entry for `_release`."""
    ent = _QueryEntry(qid, Deadline(qid), nbytes, None)
    with sch._cv:
        sch._active[qid] = ent
        sch._grant(ent, telemetry.get_registry())
    return ent


# ---------------------------------------------------------------------------
# Deadline primitive
# ---------------------------------------------------------------------------


def test_deadline_expiry_and_cancel_are_typed():
    d = Deadline("q-x", timeout_s=0.01)
    d.check("scan")  # not yet expired
    time.sleep(0.015)
    with pytest.raises(QueryDeadlineExceededError) as ei:
        d.check("transfer")
    assert ei.value.phase == "transfer"
    assert ei.value.query_id == "q-x"

    d2 = Deadline("q-y")  # no time limit: cancel-only
    assert d2.remaining() is None
    d2.check("stage")
    d2.cancel()
    with pytest.raises(QueryCancelledError) as ei:
        d2.check("write")
    assert ei.value.phase == "write"
    # the deadline error IS a cancellation (one except catches both)
    assert issubclass(QueryDeadlineExceededError, QueryCancelledError)


def test_deadline_propagates_to_pool_threads():
    d = Deadline("q-z")
    d.cancel()
    seen = []

    def probe():
        try:
            telemetry.check_deadline("operator")
            seen.append("no-raise")
        except QueryCancelledError as exc:
            seen.append(exc.phase)

    with telemetry.deadline_scope(d):
        wrapped = telemetry.propagating(probe)
    t = threading.Thread(target=wrapped)
    t.start()
    t.join(5)
    assert seen == ["operator"]
    # outside the scope the checkpoint is a no-op
    telemetry.check_deadline("operator")


# ---------------------------------------------------------------------------
# Admission control (unit level: deterministic FIFO / reject semantics)
# ---------------------------------------------------------------------------


def test_admission_fifo_queue_and_reject(fresh_scheduler):
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.hbm.budget.bytes": "100",
        "spark.hyperspace.serve.queue.depth": "1"})
    e1 = _QueryEntry("q1", Deadline("q1"), 60, None)
    assert sch._admit(e1, conf) == 0.0
    assert sch.admitted_bytes() == 60

    admitted = threading.Event()

    def queued_worker():
        e2 = _QueryEntry("q2", Deadline("q2"), 60, None)
        sch._admit(e2, conf)
        admitted.set()
        sch._release(e2)

    t = threading.Thread(target=queued_worker)
    t.start()
    for _ in range(200):  # wait until q2 is genuinely queued
        with sch._cv:
            if sch._waiters:
                break
        time.sleep(0.005)
    assert not admitted.is_set()

    # Queue full (depth 1): immediate typed backpressure.
    e3 = _QueryEntry("q3", Deadline("q3"), 60, None)
    with pytest.raises(QueryRejectedError) as ei:
        sch._admit(e3, conf)
    assert ei.value.phase == "queue"

    # Release the holder: the queued query admits (FIFO head).
    sch._release(e1)
    assert admitted.wait(5.0)
    t.join(5)
    assert sch.admitted_bytes() == 0

    # A query whose deadline expires while QUEUED raises typed too.
    e_hold = _hold(sch, 100)
    try:
        e4 = _QueryEntry("q4", Deadline("q4", timeout_s=0.05), 60, None)
        with pytest.raises(QueryDeadlineExceededError) as ei:
            sch._admit(e4, conf)
        assert ei.value.phase == "queue"
    finally:
        sch._release(e_hold)


def test_oversized_query_still_admits_when_idle(fresh_scheduler):
    """Progress guarantee: the budget bounds concurrency, it must never
    wedge serving — a query bigger than the whole budget runs alone."""
    sch = fresh_scheduler
    conf = HyperspaceConf({
        "spark.hyperspace.serve.hbm.budget.bytes": "100"})
    big = _QueryEntry("big", Deadline("big"), 10_000, None)
    assert sch._admit(big, conf) == 0.0
    sch._release(big)


# ---------------------------------------------------------------------------
# End-to-end: collect under budget pressure
# ---------------------------------------------------------------------------


def test_collect_backpressure_and_queue_deadline(serving_env,
                                                 fresh_scheduler):
    session, facts_dir, _dims = serving_env
    sess = session(**{
        "spark.hyperspace.serve.hbm.budget.bytes": 2 * MIB,
        "spark.hyperspace.serve.queue.depth": 0})
    df = sess.read_parquet(facts_dir).select("k")
    df.collect()  # warm; admits alone

    sch = fresh_scheduler
    holder = _hold(sch, 2 * MIB)
    try:
        rejected_before = _counter("serve.rejected")
        with pytest.raises(QueryRejectedError) as ei:
            df.collect()
        assert ei.value.phase == "queue"
        assert _counter("serve.rejected") == rejected_before + 1

        # With queue room, the query WAITS — and its deadline fires in
        # the queue, typed, with the queue phase attributed.
        sess.conf.set("spark.hyperspace.serve.queue.depth", "4")
        exceeded_before = _counter("serve.deadline_exceeded")
        with pytest.raises(QueryDeadlineExceededError) as ei:
            df.collect(timeout=0.05)
        assert ei.value.phase == "queue"
        assert _counter("serve.deadline_exceeded") == exceeded_before + 1
        assert _counter("serve.interrupted.queue") >= 1
    finally:
        sch._release(holder)
    # Budget freed: serving resumes.
    assert df.collect().num_rows > 0


def test_cancel_queued_query_via_session(serving_env, fresh_scheduler):
    session, facts_dir, _dims = serving_env
    sess = session(**{
        "spark.hyperspace.serve.hbm.budget.bytes": 2 * MIB,
        "spark.hyperspace.serve.queue.depth": 4})
    df = sess.read_parquet(facts_dir).select("k")
    df.collect()  # warm

    sch = fresh_scheduler
    holder = _hold(sch, 2 * MIB)
    outcome = {}

    def worker():
        try:
            df.collect()
            outcome["result"] = "finished"
        except QueryCancelledError as exc:
            outcome["result"] = exc

    t = threading.Thread(target=worker)
    try:
        t.start()
        target = None
        for _ in range(400):
            live = [q for q in sess.active_queries() if q != "blocker"]
            if live:
                target = live[0]
                break
            time.sleep(0.005)
        assert target is not None, "query never registered"
        assert sess.cancel(target) is True
        t.join(10)
        assert not t.is_alive()
        exc = outcome["result"]
        assert isinstance(exc, QueryCancelledError)
        assert exc.phase == "queue"
        assert sess.cancel(target) is False  # gone from the registry
    finally:
        sch._release(holder)


# ---------------------------------------------------------------------------
# Deadline mid-execution + telemetry isolation (the satellite test)
# ---------------------------------------------------------------------------


def _join_query(sess, facts_dir, dims_dir):
    facts = sess.read_parquet(facts_dir)
    dims = sess.read_parquet(dims_dir)
    return facts.join(dims, on="k").filter(col("w") > lit(0.25)) \
        .group_by("g").agg(("sum", "v", "total"), cnt=("count", "*"))


def test_deadline_mid_query_is_typed_and_flight_recorded(
        serving_env, fresh_scheduler):
    session, facts_dir, dims_dir = serving_env
    sess = session()
    df = _join_query(sess, facts_dir, dims_dir)
    df.collect()  # warm caches + jit so the timed run is steady-state

    before = _counter("serve.deadline_exceeded")
    with pytest.raises(QueryDeadlineExceededError) as ei:
        df.collect(timeout=0.002)
    exc = ei.value
    assert exc.phase in ("plan", "scan", "operator", "stage",
                         "transfer", "write", "queue", "batch")
    assert _counter("serve.deadline_exceeded") == before + 1
    assert _counter(f"serve.interrupted.{exc.phase}") >= 1

    # The cancelled query's recorder joined the flight ring WITH the
    # interrupted phase — that is what lets `telemetry.diff` attribute
    # a timeout cluster to a bucket instead of residual.
    ring = telemetry.get_recorder().queries(5)
    dumped = [m for m in ring
              if getattr(m, "query_id", None) == exc.query_id]
    assert dumped, "cancelled query missing from the flight ring"
    ev = dumped[-1].events_of("serve", "deadline_exceeded")
    assert ev and ev[-1]["phase"] == exc.phase
    assert dumped[-1].counters.get(
        f"serve.interrupted.{exc.phase}") == 1


def test_concurrent_deadline_and_survivor_isolation(
        serving_env, fresh_scheduler, leak_sentinel):
    """Satellite: two threads on ONE session — one hits its deadline
    mid-join, the other succeeds; the survivor's telemetry is
    unpolluted and the cancelled query's device buffers are freed."""
    session, facts_dir, dims_dir = serving_env
    sess = session()
    victim_df = _join_query(sess, facts_dir, dims_dir)
    survivor_df = sess.read_parquet(facts_dir) \
        .filter(col("v") > lit(0.5)).select("k", "v")
    victim_df.collect()    # warm both paths first
    expected = canonical(survivor_df.collect())

    results = {}

    def victim():
        try:
            victim_df.collect(timeout=0.002)
            results["victim"] = "finished"  # fast machine: not a fail
        except QueryDeadlineExceededError as exc:
            results["victim"] = exc

    def survivor():
        results["survivor"] = survivor_df.collect(with_metrics=True)

    with leak_sentinel(tolerance=8):
        for _ in range(3):  # steady state must not accrete arrays
            t1 = threading.Thread(target=victim)
            t2 = threading.Thread(target=survivor)
            t1.start()
            t2.start()
            t1.join(30)
            t2.join(30)
            assert not t1.is_alive() and not t2.is_alive()

    exc = results["victim"]
    assert isinstance(exc, QueryDeadlineExceededError), \
        f"victim outcome: {exc!r}"
    table, m = results["survivor"]
    assert canonical(table).equals(expected)
    # Survivor's recorder: its own identity, no interruption markers,
    # exactly one admission event — and not the victim's.
    assert m.query_id != exc.query_id
    assert not any(k.startswith("serve.interrupted")
                   for k in m.counters)
    admitted = m.events_of("serve", "admitted")
    assert len(admitted) == 1
    assert admitted[0]["query_id"] == m.query_id
    assert not m.events_of("serve", "deadline_exceeded")


# ---------------------------------------------------------------------------
# Degradation circuit breaker
# ---------------------------------------------------------------------------


def _indexed_env(tmp_path, **conf_extra):
    rng = np.random.default_rng(5)
    src = tmp_path / "src"
    src.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 40, 4000).astype(np.int64),
        "x": rng.random(4000).astype(np.float64),
    }), str(src / "part-0.parquet"))
    conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh"),
            "hyperspace.index.num.buckets": "4"}
    conf.update({k: str(v) for k, v in conf_extra.items()})
    sess = HyperspaceSession(HyperspaceConf(conf))
    hs = Hyperspace(sess)
    df = sess.read_parquet(str(src))
    hs.create_index(df, IndexConfig("idx", ["k"], ["x"]))
    sess.enable_hyperspace()
    query = lambda: df.filter(col("k") == lit(7)).select("x")
    idx_data = str(tmp_path / "wh" / "indexes" / "idx" / "v__=0")
    return sess, query, idx_data


def test_breaker_opens_short_circuits_probes_and_closes(
        tmp_path, fresh_scheduler):
    sess, query, idx_data = _indexed_env(
        tmp_path,
        **{"spark.hyperspace.serve.breaker.failures": 2,
           "spark.hyperspace.serve.breaker.window.seconds": 60,
           "spark.hyperspace.serve.breaker.cooldown.seconds": 0.05})
    want = canonical(query().collect())
    backup = str(tmp_path / "backup_v0")
    shutil.copytree(idx_data, backup)
    shutil.rmtree(idx_data)

    c0 = {k: _counter(k) for k in (
        "resilience.fallbacks", "resilience.breaker.opened",
        "resilience.breaker.half_open", "resilience.breaker.closed",
        "resilience.breaker.short_circuits")}

    # Failures 1 & 2: the expensive fallback path, breaker counting.
    for i in range(2):
        assert canonical(query().collect()).equals(want)
    assert _counter("resilience.fallbacks") - \
        c0["resilience.fallbacks"] == 2
    assert _counter("resilience.breaker.opened") - \
        c0["resilience.breaker.opened"] == 1

    # Open: the source answer WITHOUT re-paying the failed index scan.
    table, m = query().collect(with_metrics=True)
    assert canonical(table).equals(want)
    assert m.counters.get("resilience.breaker.short_circuits") == 1
    degraded = m.events_of("resilience", "degraded")
    assert degraded and degraded[-1]["reason"] == "breaker open"
    assert _counter("resilience.breaker.short_circuits") - \
        c0["resilience.breaker.short_circuits"] == 1

    # Cooldown -> half-open probe; index still broken -> re-opens.
    time.sleep(0.06)
    assert canonical(query().collect()).equals(want)
    assert _counter("resilience.breaker.half_open") - \
        c0["resilience.breaker.half_open"] == 1
    assert _counter("resilience.breaker.opened") - \
        c0["resilience.breaker.opened"] == 2

    # Repair the index; next probe succeeds -> breaker closes and the
    # index serves again.
    shutil.copytree(backup, idx_data)
    time.sleep(0.06)
    table, m = query().collect(with_metrics=True)
    assert canonical(table).equals(want)
    assert _counter("resilience.breaker.closed") - \
        c0["resilience.breaker.closed"] == 1
    assert m.counters.get("resilience.fallbacks") is None
    assert m.index_usage(), "closed breaker must serve from the index"


# ---------------------------------------------------------------------------
# Transfer engine: acquire timeout + reservation release (satellite)
# ---------------------------------------------------------------------------


class _NeverReady:
    """A 'device array' whose transfer never completes."""

    nbytes = 128

    def is_ready(self):
        return False


def test_transfer_acquire_timeout_is_typed_and_transient():
    from hyperspace_tpu.io import transfer
    from hyperspace_tpu.io.transfer import (TransferAcquireTimeoutError,
                                            _WindowEntry)
    from hyperspace_tpu.utils import retry

    eng = transfer.TransferEngine(chunk_bytes=64, inflight_bytes=128,
                                  put_fn=lambda a, d: np.asarray(a),
                                  acquire_timeout_s=0.05)
    # A transfer that died holding its bytes: the window is pinned full.
    dead = _WindowEntry(_NeverReady(), 128, None)
    with eng._lock:
        eng._window.append(dead)
        eng._window_bytes = 128

    before = _counter("io.transfer.acquire_timeouts")
    t0 = time.perf_counter()
    with pytest.raises(TransferAcquireTimeoutError) as ei:
        eng.put(np.zeros(64, dtype=np.uint8))
    assert time.perf_counter() - t0 < 5.0  # bounded, not forever
    assert _counter("io.transfer.acquire_timeouts") == before + 1
    # Typed TRANSIENT: the retry seam would back off and re-try it.
    assert retry.is_transient(ei.value)
    # The dead entry's accounting was preserved (nothing leaked out).
    assert eng._window_bytes == 128 and len(eng._window) == 1


def test_failed_put_releases_window_reservation():
    from hyperspace_tpu.io import transfer

    def dying_put(arr, device):
        raise RuntimeError("link died mid-put")

    eng = transfer.TransferEngine(chunk_bytes=1024,
                                  inflight_bytes=4096,
                                  put_fn=dying_put,
                                  acquire_timeout_s=0.2)
    with pytest.raises(RuntimeError):
        eng.put(np.zeros(256, dtype=np.uint8))
    # The reservation died with the put — later callers see a clean
    # window instead of permanently lost budget.
    assert eng._window_bytes == 0
    assert len(eng._window) == 0


def test_transfer_chunk_loop_honors_deadline():
    from hyperspace_tpu.io import transfer

    eng = transfer.TransferEngine(chunk_bytes=1024,
                                  inflight_bytes=1 << 20,
                                  put_fn=lambda a, d: np.asarray(a))
    d = Deadline("q-t")
    d.cancel()
    with telemetry.deadline_scope(d):
        with pytest.raises(QueryCancelledError) as ei:
            eng.put(np.zeros(1 << 16, dtype=np.uint8))  # 64 chunks
    assert ei.value.phase == "transfer"
    # All staged conversions were drained; no window bytes leaked.
    assert eng._window_bytes == 0


# ---------------------------------------------------------------------------
# Footprint estimation
# ---------------------------------------------------------------------------


def test_projected_footprint_scales_with_scan_bytes(tmp_path):
    from hyperspace_tpu.plan import footprint

    big_dir = tmp_path / "big"
    big_dir.mkdir()
    n = 400_000
    pq.write_table(pa.table({
        "a": np.arange(n, dtype=np.int64),
        "b": np.random.default_rng(0).random(n),
    }), str(big_dir / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf(
        {"hyperspace.warehouse.dir": str(tmp_path / "wh")}))
    df = sess.read_parquet(str(big_dir))
    size = os.path.getsize(str(big_dir / "part-0.parquet"))
    est = footprint.projected_bytes(df.plan)
    assert est >= size  # conservative: decoded >= on-disk
    assert est >= footprint.MIN_FOOTPRINT_BYTES
    # A join charges BOTH sides.
    est_join = footprint.projected_bytes(df.join(df, on="a").plan)
    assert est_join >= 2 * size


def test_projected_footprint_degrades_never_raises():
    from hyperspace_tpu.plan import footprint
    from hyperspace_tpu.plan.nodes import Scan
    from hyperspace_tpu.plan.schema import Schema, Field

    schema = Schema([Field("a", "int64")])
    ghost = Scan(["/nonexistent/path/xyz"], schema)
    est = footprint.projected_bytes(ghost)
    assert est >= footprint.MIN_FOOTPRINT_BYTES


# ---------------------------------------------------------------------------
# Session lifecycle
# ---------------------------------------------------------------------------


def test_session_close_is_idempotent_and_refuses_new_queries(
        serving_env, fresh_scheduler):
    session, facts_dir, _dims = serving_env
    sess = session()
    df = sess.read_parquet(facts_dir).select("k")
    assert df.collect().num_rows > 0
    sess.close()
    sess.close()  # idempotent
    with pytest.raises(HyperspaceException):
        df.collect()


# ---------------------------------------------------------------------------
# THE chaos run (acceptance): 8 clients x 240 mixed queries, faults on
# ---------------------------------------------------------------------------


def test_chaos_concurrent_serving_with_faults(serving_env,
                                              fresh_scheduler,
                                              fault_injector):
    session, facts_dir, dims_dir = serving_env
    budget = 64 * MIB
    sess = session(**{
        "spark.hyperspace.serve.hbm.budget.bytes": budget,
        "spark.hyperspace.serve.queue.depth": 16,
        "spark.hyperspace.io.retry.base.ms": 1,
        "spark.hyperspace.io.retry.max.ms": 5})
    facts = sess.read_parquet(facts_dir)
    dims = sess.read_parquet(dims_dir)
    workload = [
        ("filter", facts.filter(col("v") > lit(0.9)).select("k", "v")),
        ("agg", facts.group_by("g").agg(("sum", "v", "total"),
                                        cnt=("count", "*"))),
        ("join", facts.join(dims, on="k").filter(col("w") > lit(0.5))
         .group_by("g").agg(("avg", "v", "avg_v"))),
        ("topn", facts.sort("-v").limit(20).select("k", "v")),
        ("distinct", facts.select("g").distinct()),
    ]
    # Serial oracles BEFORE faults arm (clean expected results).
    expected = {name: canonical(df.collect()) for name, df in workload}

    counters0 = {k: _counter(k) for k in (
        "serve.rejected", "serve.deadline_exceeded", "serve.cancelled")}

    # Transients at every layer the serving plane must survive:
    # storage reads (retried under the io policy), fusion stage entry,
    # and the scheduler's own admission boundary.
    fault_injector(
        FaultRule("parquet.read:*", kind="transient", nth=1, times=-1,
                  probability=0.05),
        FaultRule("fusion.stage", kind="transient", nth=1, times=-1,
                  probability=0.02),
        FaultRule("scheduler.admit", kind="transient", nth=1, times=-1,
                  probability=0.01),
        seed=1234)

    clients, total = 8, 240
    report = run_chaos(
        workload, expected, clients=clients, total_queries=total,
        # Every 9th query gets a deadline it cannot meet: the typed
        # timeout path stays exercised under load, deterministically.
        timeout_for=lambda i: 0.0015 if i % 9 == 0 else None,
        join_timeout_s=300.0)

    # 1. No deadlock: every client thread came home.
    assert not report.stuck_threads, report.summary()
    assert report.total == total

    # 2. No silent failure modes: every non-ok outcome is typed (or an
    # injected fault that legitimately escaped the resilience layers).
    assert report.outcomes["error"] == 0, report.errors[:5]

    # 3. Correctness: every query that reported success is
    # bit-identical to its serial run.
    assert not report.mismatches, report.mismatches[:5]
    assert report.outcomes["ok"] >= total // 2, report.summary()

    # 4. The deadline path actually fired under load, typed.
    assert report.outcomes["deadline"] >= 1, report.summary()
    assert all(p in ("queue", "plan", "scan", "operator", "stage",
                     "transfer", "write", "batch")
               for p in report.typed_phases)

    # 5. Budget: the scheduler never admitted past it, and no
    # successful query's HBM watermark breached it.
    sch = sched_mod.get_scheduler()
    assert sch.peak_admitted_bytes <= budget
    assert sch.admitted_bytes() == 0  # fully drained
    peak_hbm = max((m.peak_hbm_bytes for m in report.success_metrics),
                   default=0)
    assert peak_hbm <= budget

    # 6. Every typed outcome has its matching serve.* counter delta —
    # exactly, not approximately.
    assert _counter("serve.rejected") - counters0["serve.rejected"] \
        == report.outcomes["rejected"]
    assert (_counter("serve.deadline_exceeded")
            - counters0["serve.deadline_exceeded"]) \
        == report.outcomes["deadline"]
    assert _counter("serve.cancelled") - counters0["serve.cancelled"] \
        == report.outcomes["cancelled"]

    # 7. No cross-query telemetry bleed: every success carries its own
    # unique identity, exactly one admission event (its own), and no
    # interruption markers from its cancelled neighbors.
    ids = [m.query_id for m in report.success_metrics]
    assert len(ids) == len(set(ids))
    for m in report.success_metrics:
        admitted = m.events_of("serve", "admitted")
        assert len(admitted) == 1
        assert admitted[0]["query_id"] == m.query_id
        assert not any(k.startswith("serve.interrupted")
                       for k in m.counters)
        assert m.wall_s is not None and m.operators
