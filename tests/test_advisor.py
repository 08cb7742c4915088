"""Self-driving index advisor suite (ISSUE 11).

The acceptance bar: a recurring un-indexed filter+join workload makes
the advisor recommend AND auto-build at least one index under the
maintenance lease; the repeat workload is served by it (rule-usage
telemetry), reads strictly fewer bytes, and returns bit-identical
results. Plus: deterministic rankings over a fixed recorded workload,
clean one-winner behavior against a concurrent/stranded manual create,
deferral under serving pressure, and the persisted advisor state.
"""

import json
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, telemetry)
from hyperspace_tpu.advisor import STATE_FILE, IndexAdvisor
from hyperspace_tpu.advisor.miner import WorkloadMiner
from hyperspace_tpu.engine import scheduler as sched_mod
from hyperspace_tpu.io import segcache
from hyperspace_tpu.plan.expr import col

from chaos import canonical


def _counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


def _scan_bytes(metrics) -> int:
    return sum(op.detail.get("bytes_scanned", 0)
               for op in metrics.operators if op.name == "Scan")


@pytest.fixture(autouse=True)
def fresh_ring_and_cache():
    """Advisor tests read the PROCESS flight ring: empty it first so
    other suites' queries (over now-deleted tmp dirs) are not mined.
    The scorer also reads the PROCESS registry's measured skipping
    prune fractions (`whatif.measured_prune_fraction`): what other
    suites' skipping queries measured would re-rank the candidates here
    (the skipping one first, and the lease tests see `exists` for
    `conflict`), so those series start absent too."""
    telemetry.get_recorder().clear()
    segcache.set_cache(segcache.SegmentCache())
    registry = telemetry.get_registry()
    with registry._lock:
        for name in [n for n in registry._metrics
                     if n.startswith("skipping.")
                     and n.endswith("measured_prune_fraction")]:
            del registry._metrics[name]
    yield
    telemetry.get_recorder().clear()
    segcache.set_cache(segcache.SegmentCache())


@pytest.fixture
def workload_env(tmp_path):
    """Facts+dims source dirs and a rules-enabled session, no indexes."""
    rng = np.random.default_rng(11)
    n = 6000
    facts_dir = tmp_path / "facts"
    facts_dir.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, n // 8, n).astype(np.int64),
        "v": rng.random(n),
        "tag": rng.integers(0, 40, n).astype(np.int32),
    }), str(facts_dir / "part-0.parquet"))
    dims_dir = tmp_path / "dims"
    dims_dir.mkdir()
    pq.write_table(pa.table({
        "k": np.arange(n // 8, dtype=np.int64),
        "label": rng.integers(0, 9, n // 8).astype(np.int64),
    }), str(dims_dir / "part-0.parquet"))

    conf = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.index.num.buckets": "4",
        # One cycle may build every winner (filter covering, skipping,
        # and the join PAIR) — the default of 2 spreads them over runs,
        # which is production-sane but makes "second run is a no-op"
        # assertions noisy.
        "spark.hyperspace.advisor.max.builds": "6"})
    sess = HyperspaceSession(conf).enable_hyperspace()
    return sess, str(facts_dir), str(dims_dir)


def _run_filter_workload(sess, facts, repeats=3):
    df = sess.read_parquet(facts)
    q = df.filter(col("tag") == 7).select("k", "v", "tag")
    table = None
    for _ in range(repeats):
        table = q.collect()
    return q, table


# ---------------------------------------------------------------------------
# End-to-end: the acceptance criterion
# ---------------------------------------------------------------------------


def test_e2e_recurring_workload_auto_builds_and_serves(workload_env):
    sess, facts, dims = workload_env
    hs = Hyperspace(sess)
    df = sess.read_parquet(facts)
    d = sess.read_parquet(dims)
    filter_q = df.filter(col("tag") == 7).select("k", "v", "tag")
    join_q = df.join(d, on="k").select("k", "v", "label")

    before_tables = []
    before_bytes = 0
    for _ in range(3):
        before_tables = [filter_q.collect(), join_q.collect()]
        m = sess.last_query_metrics()
    for q in (filter_q, join_q):
        q.collect()
        before_bytes += _scan_bytes(sess.last_query_metrics())

    advisor = hs.advisor()
    builds_before = _counter("advisor.builds")
    summary = advisor.run_once()

    # At least one recommendation became a real ACTIVE index through
    # the lease path (CreateAction emits its report; state says so).
    built = [dec for dec in summary["decisions"]
             if dec.get("action") == "built"]
    assert built, summary["decisions"]
    assert _counter("advisor.builds") >= builds_before + 1
    catalog = hs.indexes()
    assert (catalog["state"] == "ACTIVE").all()
    assert any(name.startswith("adv_")
               for name in catalog["name"])

    # The repeat workload is SERVED by the new index...
    after_bytes = 0
    applied = 0
    after_tables = []
    for q in (filter_q, join_q):
        after_tables.append(q.collect())
        m = sess.last_query_metrics()
        after_bytes += _scan_bytes(m)
        applied += sum(1 for e in m.events
                       if e.get("category") == "rule"
                       and e.get("action") == "applied")
    assert applied >= 1
    # ...reads strictly fewer bytes...
    assert after_bytes < before_bytes
    # ...and answers bit-identically (row order is not part of the
    # contract; canonical() sorts, as everywhere in this repo).
    for want, got in zip(before_tables, after_tables):
        assert canonical(got).equals(canonical(want))

    # Persisted state round-trips and records the decisions.
    state = advisor.state()
    assert state is not None
    assert state["kind"] == "hyperspace-advisor-state"
    assert state["last_run"]["decisions"] == summary["decisions"]
    assert os.path.exists(os.path.join(sess.conf.system_path,
                                       STATE_FILE))

    # A second cycle over the same ring is a no-op: the built shapes
    # are served now (rule applied -> no misses) and already-built
    # candidates are recognized by their deterministic names.
    second = advisor.run_once()
    assert not [dec for dec in second["decisions"]
                if dec.get("action") == "built"]


# ---------------------------------------------------------------------------
# Determinism
# ---------------------------------------------------------------------------


def test_same_recorded_workload_same_ranked_recommendations(workload_env):
    """Two independent advisors polling the same ring must mine the
    same signatures and rank the same candidates with the same scores
    — and scoring twice must be idempotent."""
    sess, facts, dims = workload_env
    _run_filter_workload(sess, facts)
    df = sess.read_parquet(facts)
    d = sess.read_parquet(dims)
    for _ in range(3):
        df.join(d, on="k").select("k", "v", "label").collect()

    def ranked():
        hs = Hyperspace(sess)
        adv = IndexAdvisor(sess)
        adv.observe()
        from hyperspace_tpu.advisor import score_signatures
        cands = score_signatures(sess, adv.miner.recurring(), sess.conf)
        return [(c.name, c.kind, c.score,
                 c.est_bytes_avoided_per_query) for c in cands]

    first = ranked()
    second = ranked()
    assert first, "no candidates mined from a recurring workload"
    assert first == second
    kinds = {k for _n, k, _s, _b in first}
    assert "covering" in kinds


def test_miner_counts_and_ignores_served_queries(workload_env):
    sess, facts, _dims = workload_env
    _run_filter_workload(sess, facts, repeats=4)
    miner = WorkloadMiner(min_repeats=2)
    assert miner.poll() == 4
    sigs = miner.recurring()
    assert len(sigs) == 1
    assert sigs[0].kind == "filter"
    assert sigs[0].count == 4
    assert sigs[0].filter_columns == ("tag",)
    assert "tag" in sigs[0].eq_columns
    assert sigs[0].total_scan_bytes > 0
    # Incremental: nothing new -> nothing re-mined.
    assert miner.poll() == 0
    assert miner.recurring()[0].count == 4


# ---------------------------------------------------------------------------
# Lease contention: advisor vs manual create — one winner, clean
# recovery
# ---------------------------------------------------------------------------


def test_lease_contention_one_winner_clean_recovery(workload_env,
                                                    monkeypatch):
    sess, facts, _dims = workload_env
    hs = Hyperspace(sess)
    _run_filter_workload(sess, facts)
    advisor = hs.advisor()
    advisor.observe()
    from hyperspace_tpu.advisor import score_signatures
    cands = score_signatures(sess, advisor.miner.recurring(), sess.conf)
    cov = next(c for c in cands if c.kind == "covering")

    # A "manual create" that crashed between begin and end holds the
    # transient op-log slot for the advisor's own candidate name.
    from hyperspace_tpu.index.factories import IndexLogManagerFactory
    from hyperspace_tpu.index.path_resolver import PathResolver
    path = PathResolver(sess.conf).get_index_path(cov.name)
    log_manager = IndexLogManagerFactory().create(path, conf=sess.conf)
    import time as _time

    from hyperspace_tpu.index.log_entry import IndexLogEntry
    stranded = IndexLogEntry.from_dict(json.loads(json.dumps({
        "version": "0.1", "id": 0, "state": "CREATING",
        # FRESH timestamp: the writer is presumed LIVE within the
        # maintenance lease — the advisor must concede, not auto-recover.
        "timestamp": int(_time.time() * 1000),
        "name": cov.name,
        "derivedDataset": {"kind": "CoveringIndex", "properties": {
            "columns": {"indexed": ["tag"], "included": []},
            "schemaString": "{}", "numBuckets": 4}},
        "content": {"root": path, "directories": []},
        "source": {"plan": {"properties": {
            "rawPlan": "{}",
            "fingerprint": {"properties": {"signatures": []}}},
            "kind": "Spark"}, "data": []},
        "extra": {},
    })))
    assert log_manager.write_log(0, stranded)

    conflicts_before = _counter("advisor.build_conflicts")
    summary = advisor.run_once()
    decisions = {d["name"]: d for d in summary["decisions"]}
    assert decisions[cov.name]["action"] == "conflict"
    assert _counter("advisor.build_conflicts") == conflicts_before + 1
    # The stranded writer still owns the slot; the catalog is intact.
    assert log_manager.get_latest_log().state == "CREATING"

    # Clean recovery (the lease path's Cancel FSM), then the next run
    # builds for real.
    assert hs.recover_index(cov.name) is True
    summary2 = advisor.run_once()
    built = {name for d in summary2["decisions"]
             if d.get("action") == "built"
             for name in d.get("indexes", ())}
    assert cov.name in built
    states = dict(zip(hs.indexes()["name"], hs.indexes()["state"]))
    assert states[cov.name] == "ACTIVE"


def test_concurrent_manual_create_races_cleanly(workload_env):
    """A racing manual create of the advisor's candidate: exactly one
    writer wins the op-log slot, the loser concedes, and the index ends
    ACTIVE exactly once."""
    sess, facts, _dims = workload_env
    hs = Hyperspace(sess)
    _run_filter_workload(sess, facts)
    advisor = hs.advisor()
    advisor.observe()
    from hyperspace_tpu.advisor import score_signatures
    cov = next(c for c in score_signatures(sess,
                                           advisor.miner.recurring(),
                                           sess.conf)
               if c.kind == "covering")

    barrier = threading.Barrier(2)
    manual_error = []

    def manual():
        barrier.wait()
        try:
            hs.create_index(
                sess.read_parquet(facts),
                IndexConfig(cov.name, list(cov.configs[0].indexed_columns),
                            list(cov.configs[0].included_columns)))
        except Exception as exc:
            manual_error.append(repr(exc))

    summaries = []

    def advised():
        barrier.wait()
        summaries.append(advisor.run_once())

    threads = [threading.Thread(target=manual),
               threading.Thread(target=advised)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    decisions = {d["name"]: d["action"]
                 for d in summaries[0]["decisions"]}
    advisor_built = decisions.get(cov.name) == "built"
    manual_won = not manual_error
    assert advisor_built or manual_won  # somebody built it
    states = dict(zip(hs.indexes()["name"], hs.indexes()["state"]))
    assert states.get(cov.name) == "ACTIVE"
    # The repeat workload is served regardless of who won.
    _q, _t = _run_filter_workload(sess, facts, repeats=1)
    m = sess.last_query_metrics()
    assert any(e.get("action") == "applied" for e in m.events
               if e.get("category") == "rule")


# ---------------------------------------------------------------------------
# Budget starvation: advisor yields to serving
# ---------------------------------------------------------------------------


class _PressuredScheduler(sched_mod.QueryScheduler):
    def __init__(self, pressure):
        super().__init__()
        self._fake_pressure = pressure

    def pressure(self):
        return dict(self._fake_pressure)


def test_advisor_defers_under_serving_pressure(workload_env):
    sess, facts, _dims = workload_env
    hs = Hyperspace(sess)
    _run_filter_workload(sess, facts)
    advisor = hs.advisor()

    old = sched_mod.get_scheduler()
    try:
        # Queued queries: every build defers, nothing is created.
        sched_mod.set_scheduler(_PressuredScheduler(
            {"queue_depth": 3, "admitted_bytes": 0, "inflight": 3}))
        deferred_before = _counter("advisor.deferred")
        summary = advisor.run_once()
        assert summary["recommendations"], "nothing recommended"
        assert all(d["action"] == "deferred"
                   for d in summary["decisions"])
        assert _counter("advisor.deferred") == deferred_before + 1
        assert len(hs.indexes()) == 0

        # Admitted bytes past the headroom fraction of the serving
        # budget: same deferral.
        sess.conf.set("spark.hyperspace.serve.hbm.budget.bytes", 1000)
        sched_mod.set_scheduler(_PressuredScheduler(
            {"queue_depth": 0, "admitted_bytes": 900, "inflight": 1}))
        summary = advisor.run_once()
        assert all(d["action"] == "deferred"
                   for d in summary["decisions"])
        assert len(hs.indexes()) == 0

        # Pressure clears: the SAME advisor builds on the next cycle.
        sched_mod.set_scheduler(_PressuredScheduler(
            {"queue_depth": 0, "admitted_bytes": 0, "inflight": 0}))
        summary = advisor.run_once()
        assert any(d["action"] == "built" for d in summary["decisions"])
    finally:
        sched_mod.set_scheduler(old)
        sess.conf.unset("spark.hyperspace.serve.hbm.budget.bytes")


def test_build_budget_rejects_past_cap(workload_env):
    sess, facts, _dims = workload_env
    hs = Hyperspace(sess)
    _run_filter_workload(sess, facts)
    sess.conf.set("spark.hyperspace.advisor.build.budget.bytes", 1)
    rejected_before = _counter("advisor.rejected_budget")
    summary = hs.advisor().run_once()
    assert summary["recommendations"]
    assert all(d["action"] == "rejected_budget"
               for d in summary["decisions"])
    assert _counter("advisor.rejected_budget") > rejected_before
    assert len(hs.indexes()) == 0


def test_advisor_disabled_knob(workload_env):
    sess, facts, _dims = workload_env
    sess.conf.set("spark.hyperspace.advisor.enabled", "false")
    hs = Hyperspace(sess)
    _run_filter_workload(sess, facts)
    summary = hs.advisor().run_once()
    assert summary["recommendations"]
    assert all(d["action"] == "disabled" for d in summary["decisions"])
    assert len(hs.indexes()) == 0


# ---------------------------------------------------------------------------
# Warm-start compilation knob (satellite)
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_jax_cache_config():
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_compile_cache_dir_wires_persistent_cache(tmp_path, monkeypatch,
                                                  restore_jax_cache_config):
    import jax

    from hyperspace_tpu.telemetry import compilation

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = tmp_path / "jitcache"
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.compile.cache.dir": str(cache_dir)}))
    assert compilation.persistent_cache_dir() == str(cache_dir)
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
    assert _counter("compile.persistent_cache.configured") >= 1
    # Unset knob: configure is a no-op, not a reset.
    HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh2")}))
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
    sess.close()


def test_compile_cache_dir_knob_yields_to_environment(
        tmp_path, monkeypatch, restore_jax_cache_config):
    """Whoever launches the process places the cache: with
    JAX_COMPILATION_CACHE_DIR set, the session knob moves nothing."""
    import jax

    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_dir", env_dir)  # as at import
    configured = _counter("compile.persistent_cache.configured")
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.compile.cache.dir": str(tmp_path / "from_knob")}))
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert _counter("compile.persistent_cache.configured") == configured
    sess.close()


@pytest.mark.parametrize("placed_by", ["default", "environment"])
def test_compile_cache_placement_at_import(placed_by, tmp_path):
    """With the variable unset, `_jax_config` places the cache at one
    fixed git-ignored path inside the checkout — never a temp name; with
    it set, the environment's dir stands. Either way every program is
    stored (jax's compile-time and entry-size floors are dropped in ONE
    place, whoever placed the dir)."""
    import subprocess
    import sys

    import hyperspace_tpu

    repo = os.path.dirname(os.path.dirname(
        os.path.abspath(hyperspace_tpu.__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"  # the probe never needs a chip
    want_dir = os.path.join(repo, ".jax_cache")
    if placed_by == "environment":
        want_dir = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "env")
    probe = ("import jax, hyperspace_tpu._jax_config; c = jax.config; "
             "print(c.jax_compilation_cache_dir, "
             "c.jax_persistent_cache_min_compile_time_secs, "
             "c.jax_persistent_cache_min_entry_size_bytes)")
    out = subprocess.run([sys.executable, "-c", probe], cwd=repo, env=env,
                         check=True, capture_output=True, text=True,
                         timeout=120).stdout.split()
    assert out == [want_dir, "0.0", "-1"]


# ---------------------------------------------------------------------------
# Measured prune fraction closes the what-if loop (ISSUE 18 satellite)
# ---------------------------------------------------------------------------


def test_measured_prune_fraction_drives_skipping_rank(workload_env):
    """The measured per-index prune gauge overrides the conf
    assumption and deterministically flips the skipping candidate's
    rank against the covering candidate for the same signature."""
    sess, facts, _dims = workload_env
    _run_filter_workload(sess, facts)
    from hyperspace_tpu.advisor import score_signatures
    adv = IndexAdvisor(sess)
    adv.observe()
    sigs = adv.miner.recurring()

    def ranked():
        cands = score_signatures(sess, sigs, sess.conf)
        return cands, [c.name for c in cands]

    cands, _names = ranked()
    sk = next(c for c in cands if c.kind == "skipping")
    cov = next(c for c in cands if c.kind == "covering")
    # Nothing measured for THIS index yet (the suite's global
    # histogram may already hold other workloads' measurements).
    assert sk.detail["prune_fraction_source"] in ("assumed",
                                                  "measured:global")

    gauge = telemetry.get_registry().gauge(
        f"skipping.{sk.name}.measured_prune_fraction")

    # Reality says the sketches prune (nearly) everything: skipping
    # outranks the replay-verified covering index.
    gauge.set(1.0)
    cands, names = ranked()
    sk_hi = next(c for c in cands if c.kind == "skipping")
    assert sk_hi.detail["prune_fraction_source"] == "measured:index"
    assert sk_hi.detail["prune_fraction"] == 1.0
    assert names.index(sk_hi.name) < names.index(cov.name)
    assert sk_hi.est_bytes_avoided_per_query > \
        cov.est_bytes_avoided_per_query

    # Reality says they barely prune: the SAME candidate sinks below
    # the covering index instead.
    gauge.set(0.001)
    cands, names = ranked()
    sk_lo = next(c for c in cands if c.kind == "skipping")
    assert sk_lo.detail["prune_fraction_source"] == "measured:index"
    assert names.index(sk_lo.name) > names.index(cov.name)
    assert sk_lo.score < cov.score
