"""A warm scan of a committed index version touches no file (ISSUE 29).

What a scan has to know about its files before it reads them (their
names in read order, the rows per bucket, the row total its lane choice
reads, the bytes on disk) is resolved once per (index root, committed
version, bucket selector) and kept with the version's cached segments
(`io/segcache.ScanFacts`, `ScanExec._resolve`). The bar, for each of
the three entry points `execute`, `execute_bucketed` and
`execute_sharded`:

(a) a warm rule-selected scan stats no data file, reads no footer,
    lists no directory and gives the `hs-io` pool no task;
(b) its facts and its operator record equal a cold read's and pyarrow's
    own reading of the footers;
(c) every lifecycle step that replaces the version's bytes (drop +
    vacuum + create under the same name with OTHER rows, refresh,
    optimize, `invalidate_index`, `clear()`) is followed by the new
    rows and the new lengths;
(d) a version directory removed from outside still raises the typed
    error and the query falls back to the source plan;
(e) a scan that names no committed version still notices a file
    rewritten in place.
"""

import glob
import os
import shutil

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, telemetry)
from hyperspace_tpu.engine import physical
from hyperspace_tpu.exceptions import IndexDataUnavailableError
from hyperspace_tpu.io import parquet, segcache
from hyperspace_tpu.io.segcache import SegmentCache
from hyperspace_tpu.parallel.mesh import make_mesh
from hyperspace_tpu.plan import footprint
from hyperspace_tpu.plan.expr import col, lit
from hyperspace_tpu.plan.nodes import Scan
from hyperspace_tpu.plan.schema import Schema

ENTRY_POINTS = ["execute", "execute_bucketed", "execute_sharded"]
BUCKETS = 8
INDEX = "res_idx"


def _counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


@pytest.fixture(autouse=True)
def fresh_cache():
    segcache.set_cache(SegmentCache())
    yield
    segcache.set_cache(SegmentCache())


def write_source(src, n, seed, name="part-0.parquet"):
    rng = np.random.default_rng(seed)
    os.makedirs(src, exist_ok=True)
    pq.write_table(pa.table({
        "key": rng.integers(0, 10_000, n).astype(np.int64),
        "val": rng.random(n).astype(np.float64),
    }), os.path.join(src, name))


def source_rows(src):
    t = pq.read_table(sorted(glob.glob(os.path.join(src, "*.parquet"))))
    return sorted(zip(t["key"].to_pylist(), t["val"].to_pylist()))


@pytest.fixture
def env(tmp_path):
    """A source, a session over it (device lane forced, SPMD lane open
    to small reads) and one covering index of 8 buckets."""
    src = str(tmp_path / "src")
    write_source(src, 4000, seed=1)
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "hyperspace.index.num.buckets": str(BUCKETS),
        "spark.hyperspace.execution.min.device.rows": "0",
        "spark.hyperspace.distribution.enabled": "true"}))
    hs = Hyperspace(sess)
    hs.create_index(sess.read_parquet(src),
                    IndexConfig(INDEX, ["key"], ["val"]))
    sess.enable_hyperspace()
    return sess, hs, src, str(tmp_path / "wh" / "indexes" / INDEX)


def index_scan(sess, src):
    """The rule's replacement relation for a fresh query, as every
    `collect` makes it anew."""
    plan = (sess.read_parquet(src).filter(col("key") >= lit(0))
            .select("key", "val")._optimized_plan())
    scans = [s for s in plan.collect_leaves() if s.index_name]
    assert len(scans) == 1, "not index-served"
    return scans[0]


def run_entry(entry, sess, scan):
    """One read through `entry`: (sorted rows, per-bucket lengths or
    None)."""
    ex = physical.ScanExec(scan, ["key", "val"], conf=sess.conf)
    if entry == "execute":
        batch, lengths = ex.execute(), None
        valid = slice(None)
    elif entry == "execute_bucketed":
        batch, lengths = ex.execute_bucketed(BUCKETS)
        valid = slice(None)
    else:
        sh = ex.execute_sharded(BUCKETS, make_mesh(4))
        assert sh is not None, "the read left the SPMD lane"
        batch, lengths = sh.batch, sh.lengths
        valid = np.asarray(sh.row_valid)
    keys = np.asarray(batch.column("key").data)[valid]
    vals = np.asarray(batch.column("val").data)[valid]
    return (sorted(zip(keys.tolist(), vals.tolist())),
            None if lengths is None else [int(x) for x in lengths])


def read(entry, sess, src):
    return run_entry(entry, sess, index_scan(sess, src))


def footer_lengths(version_dir):
    """Rows per bucket by pyarrow's own reading of the footers."""
    lengths = [0] * BUCKETS
    for b, files in parquet.bucket_map(
            sorted(glob.glob(os.path.join(version_dir, "*.parquet")))).items():
        for f in files:
            lengths[b] += pq.read_metadata(f).num_rows
    return lengths


class FileTouches:
    """Counts every way the scan path can ask the filesystem about a
    path under `root` (another test's leftover thread may touch its
    own), and every task given to the `hs-io` pool, through
    wrappers."""

    def __init__(self, monkeypatch, root):
        self.stats = []       # paths given to os.stat
        self.stamps = 0       # parquet._file_stamp
        self.sizes = 0        # footprint._file_size
        self.footers = 0      # pq.read_metadata
        self.listings = 0     # os.listdir / glob.glob / os.scandir
        self.pool_tasks = 0   # io_executor().submit / .map
        root = str(root)

        def counting(target, name, bump):
            inner = getattr(target, name)

            def wrapper(*a, **k):
                if a and str(a[0]).startswith(root):
                    bump(str(a[0]))
                return inner(*a, **k)
            monkeypatch.setattr(target, name, wrapper)

        def inc(name):
            return lambda path: setattr(self, name, getattr(self, name) + 1)

        counting(os, "stat", self.stats.append)
        counting(parquet, "_file_stamp", inc("stamps"))
        counting(footprint, "_file_size", inc("sizes"))
        counting(pq, "read_metadata", inc("footers"))
        for target, name in ((os, "listdir"), (os, "scandir"),
                             (glob, "glob")):
            counting(target, name, inc("listings"))
        pool = parquet.io_executor()
        touches = self

        class CountingPool:
            def submit(self, *a, **k):
                touches.pool_tasks += 1
                return pool.submit(*a, **k)

            def map(self, fn, *iterables, **k):
                iterables = [list(it) for it in iterables]
                touches.pool_tasks += min(map(len, iterables))
                return pool.map(fn, *iterables, **k)

        monkeypatch.setattr(parquet, "io_executor", lambda: CountingPool())

    def summary(self):
        return {"data_file_stats": [p for p in self.stats
                                    if p.endswith(".parquet")],
                "stats": len(self.stats), "stamps": self.stamps,
                "sizes": self.sizes, "footers": self.footers,
                "listings": self.listings, "pool_tasks": self.pool_tasks}


# -- (a) a warm scan touches no file ----------------------------------------


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_warm_scan_touches_no_file(env, entry, monkeypatch, tmp_path):
    sess, hs, src, idx_root = env
    cold = read(entry, sess, src)
    scan = index_scan(sess, src)  # planned before the count starts
    hits0, misses0 = (_counter("scan.resolve.hits"),
                      _counter("scan.resolve.misses"))
    touches = FileTouches(monkeypatch, tmp_path)
    warm = run_entry(entry, sess, scan)
    got = touches.summary()
    monkeypatch.undo()
    assert warm == cold
    assert got["data_file_stats"] == []
    # the one stat allowed: `_guard_index_read`'s look at the version dir
    assert got["stats"] <= 1, touches.stats
    assert all(p.rstrip("/").endswith("v__=0") for p in touches.stats)
    assert (got["stamps"], got["sizes"], got["footers"], got["listings"],
            got["pool_tasks"]) == (0, 0, 0, 0, 0), got
    assert _counter("scan.resolve.hits") == hits0 + 1
    assert _counter("scan.resolve.misses") == misses0


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_cold_scan_goes_to_the_files_once(env, entry, monkeypatch, tmp_path):
    """The first read of a version resolves from the files through the
    calls that were there before (`file_row_counts`, `file_sizes_total`)
    and counts as a miss; the wrappers do see that pass."""
    sess, hs, src, idx_root = env
    scan = index_scan(sess, src)
    misses0 = _counter("scan.resolve.misses")
    touches = FileTouches(monkeypatch, tmp_path)
    run_entry(entry, sess, scan)
    got = touches.summary()
    monkeypatch.undo()
    assert _counter("scan.resolve.misses") == misses0 + 1
    # (the footers themselves are in `parquet._count_cache` since the
    # build; the stamp per file that validates them is the pass)
    assert got["stamps"] >= BUCKETS and got["pool_tasks"] >= BUCKETS
    assert segcache.stats_snapshot()["scan_facts"] == 1


def test_a_pruned_and_a_per_bucket_read_keep_their_own_facts(env):
    """The selector tells bucket sets and layouts apart: a read pruned
    to two buckets, one bucket's read and the whole-index read of one
    version are three memo entries, each warm on its second read."""
    sess, hs, src, idx_root = env
    scan = index_scan(sess, src)
    whole = physical.ScanExec(scan, ["key", "val"], conf=sess.conf)
    pruned = physical.ScanExec(scan, ["key", "val"], conf=sess.conf,
                               allowed_buckets={1, 5})
    lengths = footer_lengths(os.path.join(idx_root, "v__=0"))
    for _ in range(2):
        assert whole.execute().num_rows == 4000
        assert pruned.execute().num_rows == lengths[1] + lengths[5]
        assert whole.execute(3).num_rows == lengths[3]
        batch, got = pruned.execute_bucketed(BUCKETS)
        assert [int(x) for x in got] == [
            n if b in (1, 5) else 0 for b, n in enumerate(lengths)]
    assert segcache.stats_snapshot()["scan_facts"] == 4


# -- (b) warm facts == cold facts == the footers ------------------------------


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_warm_facts_equal_cold_and_footers(env, entry, monkeypatch):
    sess, hs, src, idx_root = env
    details = []
    monkeypatch.setattr(telemetry, "annotate",
                        lambda **d: details.append(d))
    results = []
    with telemetry.recording(telemetry.QueryMetrics("facts")):
        for _ in range(2):
            results.append(read(entry, sess, src))
    cold, warm = [d for d in details if "files_scanned" in d]
    assert warm == cold
    assert results[1] == results[0] == (
        source_rows(src), results[0][1])
    version_dir = os.path.join(idx_root, "v__=0")
    files = sorted(glob.glob(os.path.join(version_dir, "*.parquet")))
    assert warm["lane"] == "device"
    assert warm["files_scanned"] == warm["files_total"] == len(files)
    assert warm["bytes_scanned"] == sum(os.path.getsize(f) for f in files)
    assert (warm["buckets_total"], warm["buckets_scanned"]) == (BUCKETS,
                                                                BUCKETS)
    assert warm["roots"] == [version_dir]
    if entry != "execute":
        assert results[1][1] == footer_lengths(version_dir)
    facts, ref = physical.ScanExec(
        index_scan(sess, src), ["key", "val"], conf=sess.conf)._resolve(
            num_buckets=None if entry == "execute" else BUCKETS)
    assert facts.rows == 4000 == sum(
        pq.read_metadata(f).num_rows for f in files)
    assert ref is not None and ref.version == 0


# -- (c) the facts never outlive the bytes they describe -----------------------


def _recreate_with_other_rows(sess, hs, src):
    """Drop + vacuum + create under the same name: version ids start
    over at 0, the rows and their count are others."""
    hs.delete_index(INDEX)
    hs.vacuum_index(INDEX)
    shutil.rmtree(src)
    write_source(src, 2500, seed=2)
    hs.create_index(sess.read_parquet(src),
                    IndexConfig(INDEX, ["key"], ["val"]))
    return 0


def _refresh_full(sess, hs, src):
    write_source(src, 700, seed=3, name="part-1.parquet")
    hs.refresh_index(INDEX, mode="full")
    return 1


def _refresh_incremental(sess, hs, src):
    write_source(src, 700, seed=4, name="part-1.parquet")
    hs.refresh_index(INDEX, mode="incremental")
    return 1


def _refresh_incremental_then_optimize(sess, hs, src):
    write_source(src, 700, seed=5, name="part-1.parquet")
    hs.refresh_index(INDEX, mode="incremental")
    hs.optimize_index(INDEX)
    return 2


LIFECYCLES = {"recreate_other_rows": _recreate_with_other_rows,
              "refresh_full": _refresh_full,
              "refresh_incremental": _refresh_incremental,
              "incremental_then_optimize": _refresh_incremental_then_optimize}


@pytest.mark.parametrize("entry", ENTRY_POINTS)
@pytest.mark.parametrize("step", sorted(LIFECYCLES))
def test_next_read_after_a_lifecycle_step_is_the_new_version(env, entry,
                                                             step):
    sess, hs, src, idx_root = env
    for _ in range(2):  # cold, then warm: the memo holds the old facts
        assert read(entry, sess, src)[0] == source_rows(src)
    version = LIFECYCLES[step](sess, hs, src)
    scan = index_scan(sess, src)
    assert scan.root_paths[0].endswith(f"v__={version}")
    want_lengths = footer_lengths(scan.root_paths[0])
    for _ in range(2):  # the new version's first read, then its warm one
        rows, lengths = run_entry(entry, sess, index_scan(sess, src))
        assert rows == source_rows(src)
        if lengths is not None:
            assert lengths == want_lengths


@pytest.mark.parametrize("entry", ENTRY_POINTS)
@pytest.mark.parametrize("hook", ["invalidate_index", "invalidate_version",
                                  "clear", "reset_cache"])
def test_explicit_invalidation_drops_the_facts(env, entry, hook):
    sess, hs, src, idx_root = env
    cold = read(entry, sess, src)
    assert segcache.stats_snapshot()["scan_facts"] == 1
    if hook == "invalidate_index":
        segcache.get_cache().invalidate_index(idx_root)
    elif hook == "invalidate_version":
        segcache.get_cache().invalidate_version(idx_root, 0)
    elif hook == "clear":
        segcache.clear()
    else:
        segcache.reset_cache()
    assert segcache.stats_snapshot()["scan_facts"] == 0
    misses0 = _counter("scan.resolve.misses")
    assert read(entry, sess, src) == cold
    assert _counter("scan.resolve.misses") == misses0 + 1


def test_rewritten_under_the_same_path_after_clear(env):
    """PERF.md section 6, PR 24: one process, a deployment torn down
    and another put up under the same paths, `clear()` between them.
    The facts go with the segments."""
    sess, hs, src, idx_root = env
    for _ in range(2):
        assert read("execute_bucketed", sess, src)[0] == source_rows(src)
    old = footer_lengths(os.path.join(idx_root, "v__=0"))
    shutil.rmtree(os.path.dirname(os.path.dirname(idx_root)))  # the warehouse
    shutil.rmtree(src)
    segcache.clear()
    parquet.invalidate_paths(idx_root)
    footprint.invalidate_sizes(idx_root)
    write_source(src, 1500, seed=9)
    sess2 = HyperspaceSession(sess.conf)  # a new session, no catalog cache
    hs2 = Hyperspace(sess2)
    hs2.create_index(sess2.read_parquet(src),
                     IndexConfig(INDEX, ["key"], ["val"]))
    sess2.enable_hyperspace()
    rows, lengths = read("execute_bucketed", sess2, src)
    assert rows == source_rows(src) and len(rows) == 1500
    assert lengths == footer_lengths(os.path.join(idx_root, "v__=0")) != old


def test_a_resolve_that_raced_an_invalidation_is_not_kept():
    """Like a doomed fill: facts resolved while their index was being
    invalidated are served to their caller and never kept."""
    cache = SegmentCache()
    ref = segcache.SegmentRef("i", "/wh/i", 0, "all")
    facts = segcache.ScanFacts(files=("/wh/i/v__=0/a.parquet",),
                               buckets=None, files_total=1, counts=(3,),
                               lengths=None, bytes_scanned=10)

    def resolve_during_a_drop():
        cache.invalidate_index("/wh/i")
        return facts

    assert cache.scan_facts(ref, None, resolve_during_a_drop) == (facts,
                                                                  False)
    assert cache.snapshot()["scan_facts"] == 0
    assert cache.scan_facts(ref, None, lambda: facts) == (facts, False)
    assert cache.scan_facts(ref, None, lambda: 1 / 0) == (facts, True)


def test_concurrent_resolves_and_invalidations_keep_no_stale_facts():
    """More threads than cores resolving the facts of a few versions
    while another keeps invalidating them: whatever a reader is handed,
    and whatever is left in the memo at the end, is the facts OF THE
    VERSION ASKED FOR at its current generation, and nothing raises."""
    import sys
    import threading
    import time

    cache = SegmentCache()
    generation = [0] * 4           # bumped by the invalidator, per version
    lock = threading.Lock()
    errors, stop = [], threading.Event()

    def facts_of(version):
        with lock:
            gen = generation[version]
        time.sleep(1e-4)  # the footers take a while: room for a drop
        return segcache.ScanFacts((f"/wh/i/v__={version}/g{gen}",), None, 1,
                                  (gen,), None, version)

    def reader():
        try:
            while not stop.is_set():
                for v in range(4):
                    with lock:
                        floor = generation[v]
                    facts, _ = cache.scan_facts(
                        segcache.SegmentRef("i", "/wh/i", v, "all"), None,
                        lambda v=v: facts_of(v))
                    # never older than the generation current when asked
                    if facts.bytes_scanned != v or facts.rows < floor:
                        errors.append((v, floor, facts))
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    def invalidator():
        while not stop.is_set():
            for v in range(4):
                with lock:  # the bytes change and the hook runs: one step
                    generation[v] += 1
                    cache.invalidate_version("/wh/i", v)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader)
               for _ in range((os.cpu_count() or 4) * 2)]
    threads.append(threading.Thread(target=invalidator))
    try:
        for t in threads:
            t.start()
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    for v in range(4):
        facts, _ = cache.scan_facts(
            segcache.SegmentRef("i", "/wh/i", v, "all"), None,
            lambda v=v: facts_of(v))
        assert facts.rows == generation[v]


def test_kept_facts_are_bounded():
    cache = SegmentCache()
    facts = segcache.ScanFacts((), None, 0, (), None, 0)
    for v in range(segcache._SCAN_FACTS_MAX + 10):
        cache.scan_facts(segcache.SegmentRef("i", "/wh/i", v, "all"), None,
                         lambda: facts)
    assert cache.snapshot()["scan_facts"] == segcache._SCAN_FACTS_MAX
    # the oldest went first
    assert cache.scan_facts(segcache.SegmentRef("i", "/wh/i", 0, "all"),
                            None, lambda: facts)[1] is False


# -- (d) the typed fallback survives a warm memo -------------------------------


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_removed_version_dir_still_raises_typed_error(env, entry):
    sess, hs, src, idx_root = env
    read(entry, sess, src)
    read(entry, sess, src)  # warm: facts and segment both held
    scan = index_scan(sess, src)
    shutil.rmtree(os.path.join(idx_root, "v__=0"))
    with pytest.raises(IndexDataUnavailableError):
        run_entry(entry, sess, scan)


def test_removed_version_dir_falls_back_to_the_source_plan(env):
    sess, hs, src, idx_root = env
    query = lambda: (sess.read_parquet(src)  # noqa: E731
                     .filter(col("key") >= lit(5000)).select("key", "val"))
    want = sorted(query().collect().to_pandas().itertuples(index=False))
    hits0 = _counter("scan.resolve.hits")
    assert sorted(query().collect().to_pandas()
                  .itertuples(index=False)) == want
    assert _counter("scan.resolve.hits") > hits0  # the memo is warm
    shutil.rmtree(os.path.join(idx_root, "v__=0"))
    table, metrics = query().collect(with_metrics=True)
    assert sorted(table.to_pandas().itertuples(index=False)) == want
    assert metrics.counters.get("resilience.fallbacks") == 1


# -- (e) a scan that names no version keeps its stamp validation ---------------


@pytest.mark.parametrize("rule_selected", [False, True],
                         ids=["source_scan", "index_scan_off_version_dir"])
def test_unversioned_scan_notices_a_file_rewritten_in_place(
        tmp_path, rule_selected, monkeypatch):
    """Source data, and a rule-selected scan whose root is not a
    `v__=N` directory: no `SegmentRef`, so nothing is memoised and the
    metadata pass runs every read."""
    root = str(tmp_path / "data")
    os.makedirs(root)
    path = os.path.join(root, "part-0.parquet")
    table = pa.table({"key": np.arange(300, dtype=np.int64),
                      "val": np.arange(300, dtype=np.float64)})
    pq.write_table(table, path)
    schema = Schema.from_arrow(table.schema)
    conf = HyperspaceConf({
        "spark.hyperspace.execution.min.device.rows": "0"})

    def scan_rows():
        scan = Scan([root], schema,
                    index_name="idx" if rule_selected else None)
        assert segcache.segment_ref_for_scan(scan) is None
        batch = physical.ScanExec(scan, ["key", "val"], conf=conf).execute()
        return sorted(np.asarray(batch.column("key").data).tolist())

    assert scan_rows() == list(range(300))
    touches = FileTouches(monkeypatch, tmp_path)
    hits0 = _counter("scan.resolve.hits")
    assert scan_rows() == list(range(300))
    assert touches.stamps >= 1, "the stamp validation is gone"
    monkeypatch.undo()
    assert _counter("scan.resolve.hits") == hits0
    assert segcache.stats_snapshot()["scan_facts"] == 0
    # rewritten in place, another row count
    pq.write_table(pa.table({"key": np.arange(1000, 1120, dtype=np.int64),
                             "val": np.arange(120, dtype=np.float64)}), path)
    os.utime(path, ns=(1, 1))  # whatever the clock's granularity
    assert scan_rows() == list(range(1000, 1120))
