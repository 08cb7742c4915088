"""Chip smoke: the main path on the attached TPU, end to end, once.

    python chip_smoke.py [--seed N]     one chip: build, filter scan, join
    python chip_smoke.py --four-chips   the mesh build and the SPMD join
                                        on a four-chip host, nothing else

Drives the public API — `Hyperspace.create_index`, `DataFrame.collect`
with the rewrite rules on — at sizes that cross the DEFAULT
`execution.min.device.rows` threshold on every scan, checks each result
against a pandas reference (every column exact, float64 to the bit, with
values the chip's own f64 cannot hold planted in the payload), and
asserts from the query metrics that the device lane (not a host
fallback) did the work. One process, no child, no `JAX_PLATFORMS`:
without a TPU it exits non-zero before any phase, and a phase that
raises or disagrees with its reference ends the run.

The last line of stdout is the result JSON; everything else worth
knowing (lanes, cold and repeat wall, compile seconds, link bytes, peak
HBM) is printed on earlier lines. Those are notes, not measurements: no
rate or speed-up is derived from them.
"""

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
WORK_DIR = os.path.join(HERE, ".chip_smoke_work")

# Both join sides sit at or above MIN_DEVICE_ROWS_DEFAULT (4,194,304), so
# default conf routes every scan to the device. Compile time of the sort
# programs does not fall with size, so these are not worth shrinking.
FACT_ROWS = 8_388_608
DIM_ROWS = 4_194_304
FACT_FILES = 8
DIM_FILES = 4
NUM_BUCKETS = 64
# Keys are spread past 2^32 so the int64 hi lane carries real bits.
KEY_STRIDE = 1_000_003
FACT_COLUMNS = ["key", "id", "measure", "k2"]
INDEXES = (("fact", "smoke_fact", ["id", "measure", "k2"]),
           ("dim", "smoke_dim", ["val"]))
# Default conf except the bucket count (and the warehouse dir, which the
# lake sets): in particular `execution.min.device.rows` is NOT lowered.
CONF = {"spark.hyperspace.index.num.buckets": str(NUM_BUCKETS)}
# float64 payload must come back from the chip bit for bit. The chip's
# own f64 is an f32 pair (48 bits, f32's exponent range), so these are
# the values a float64 H2D would change: beyond 3.4e38, subnormals,
# below f32's range, 53 significant bits, -0.0, inf, nan.
F64_EDGE = np.array([
    1e300, -1e300, np.finfo(np.float64).max, 1e-300, 5e-324,
    np.finfo(np.float64).tiny, 1e-40, -0.0, 0.1 + 0.2, 1.0 / 3.0,
    np.inf, -np.inf, np.nan])
EDGE_EVERY = 1024  # one row in 1024 of `measure` and `val` is an edge value


def note(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Lake, session, reference
# ---------------------------------------------------------------------------


class Lake:
    """Fact and dimension tables as several Parquet files each, made in
    bulk from `seed`; every fact key matches exactly one dimension row.
    Holds the session over them and the pandas copies the references
    are computed from."""

    def __init__(self, work: str, seed: int):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from hyperspace_tpu import (Hyperspace, HyperspaceConf,
                                    HyperspaceSession)

        t0 = time.perf_counter()
        rng = np.random.default_rng(seed)

        def f64_payload(n: int) -> np.ndarray:
            out = rng.random(n)
            out[::EDGE_EVERY] = np.resize(F64_EDGE, len(out[::EDGE_EVERY]))
            return out

        self.work = work
        self.fact = pa.table({
            "key": rng.integers(0, DIM_ROWS, FACT_ROWS).astype(np.int64)
            * KEY_STRIDE,
            "id": np.arange(FACT_ROWS, dtype=np.int64),
            "measure": f64_payload(FACT_ROWS),
            "k2": rng.integers(0, 100, FACT_ROWS).astype(np.int64),
        })
        self.dim = pa.table({
            "key": rng.permutation(DIM_ROWS).astype(np.int64) * KEY_STRIDE,
            "val": f64_payload(DIM_ROWS),
        })
        for name, table, n_files in (("fact", self.fact, FACT_FILES),
                                     ("dim", self.dim, DIM_FILES)):
            os.makedirs(os.path.join(work, name))
            per = -(-table.num_rows // n_files)
            for i in range(n_files):
                pq.write_table(table.slice(i * per, per), os.path.join(
                    work, name, f"part-{i:05d}.parquet"))
        self.fact_pd = self.fact.to_pandas()
        self.dim_pd = self.dim.to_pandas()
        self.sess = HyperspaceSession(HyperspaceConf(dict(
            CONF, **{"hyperspace.warehouse.dir": os.path.join(work, "wh")})))
        self.hs = Hyperspace(self.sess)
        self.fdf = self.sess.read_parquet(os.path.join(work, "fact"))
        self.ddf = self.sess.read_parquet(os.path.join(work, "dim"))
        note(f"lake: fact {FACT_ROWS} rows x {FACT_FILES} files, dim "
             f"{DIM_ROWS} rows x {DIM_FILES} files, {NUM_BUCKETS} buckets, "
             f"seed {seed}, wall {time.perf_counter() - t0:.2f}s")

    def create_indexes(self) -> dict:
        """Both covering indexes through the facade; {name: version dir}."""
        from hyperspace_tpu import IndexConfig

        for table, name, included in INDEXES:
            df = self.fdf if table == "fact" else self.ddf
            t0 = time.perf_counter()
            self.hs.create_index(df, IndexConfig(name, ["key"], included))
            note(f"create_index {name}: wall "
                 f"{time.perf_counter() - t0:.2f}s")
        return {r["name"]: r["indexLocation"]
                for _, r in self.hs.indexes().iterrows()}

    def join_df(self):
        """Both tables' float64 payload rides the join, so a mesh-built
        index is read back whole."""
        return (self.fdf.select("key", "id", "measure")
                .join(self.ddf.select("key", "val"), on="key")
                .select("id", "measure", "val"))

    def join_reference(self):
        return (self.fact_pd[["key", "id", "measure"]]
                .merge(self.dim_pd, on="key")[["id", "measure", "val"]])


def assert_same_rows(table, want, what: str) -> None:
    """Arrow result == pandas reference as row sets (both sorted by the
    unique `id`), every column exact; float64 is held to the bit, so
    -0.0, nan and values outside the chip's own f64 range count."""
    got = table.to_pandas().sort_values("id").reset_index(drop=True)
    want = want.sort_values("id").reset_index(drop=True)[list(got.columns)]
    assert len(got) == len(want), f"{what}: {len(got)} rows != {len(want)}"
    for name in got.columns:
        g, w = got[name].to_numpy(), want[name].to_numpy()
        assert g.dtype == w.dtype, (what, name, g.dtype, w.dtype)
        if g.dtype == np.float64:
            g, w = g.view(np.int64), w.view(np.int64)
        assert np.array_equal(g, w), \
            f"{what}: column {name} differs in {np.sum(g != w)} rows"
        if want[name].dtype == np.float64:
            v = np.abs(want[name].to_numpy())
            beyond = np.isfinite(v) & ~((v >= 1.2e-38) & (v <= 3.4e38))
            note(f"{what}: float64 column {name} bit-exact; {beyond.sum()} "
                 f"of its {len(v)} values lie outside f32's exponent range "
                 f"(the chip's own f64 cannot hold them)")


def assert_no_shuffle(df) -> None:
    _, _, physical = df.explain_plans()
    names = [type(n).__name__ for n in physical.collect()]
    shuffles = [n for n in names if n in ("ExchangeExec", "SortExec")]
    assert not shuffles, f"the plan still shuffles: {names}"


def scan_ops(metrics):
    return [op for op in metrics.operators if op.name == "Scan"]


def assert_index_device_scans(metrics, expect: int) -> None:
    scans = scan_ops(metrics)
    assert len(scans) == expect, [op.to_dict() for op in scans]
    for op in scans:
        roots = op.detail.get("roots", [])
        assert roots and all("v__=" in r for r in roots), \
            f"scan not served from an index version dir: {roots}"
        assert op.detail.get("lane") == "device", op.to_dict()


def join_lane(metrics) -> str:
    joins = [op for op in metrics.operators if op.name == "SortMergeJoin"]
    assert len(joins) == 1, [op.name for op in metrics.operators]
    return joins[0].detail.get("lane")


# ---------------------------------------------------------------------------
# Notes: compile seconds, link bytes, HBM
# ---------------------------------------------------------------------------


# Seconds jax spent in backend compiles and persistent-cache loads,
# process-wide (covers the eager ops' compiles too, which the registry's
# `compile.seconds` does not see).
COMPILE_S = {"backend": 0.0, "cache_load": 0.0}
_JAX_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}
_LISTENING = False


def _on_jax_event(event: str, seconds: float, **_kw) -> None:
    if event in _JAX_EVENTS:
        COMPILE_S[_JAX_EVENTS[event]] += seconds


def listen_for_compiles() -> None:
    """Register the listener once per process, however often called."""
    import jax.monitoring
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _LISTENING = True


def timed(label: str, fn):
    """Run `fn` cold and once more in-process; print each run's wall and
    what it spent compiling. Returns the repeat's result."""
    from hyperspace_tpu import telemetry

    def registry():
        c = telemetry.get_registry().counters_dict()
        return c.get("compile.seconds", 0.0), c.get("compile.traces", 0)

    out = None
    for run in ("cold", "repeat"):
        (s0, n0), before = registry(), dict(COMPILE_S)
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        s1, n1 = registry()
        note(f"{label} [{run}]: wall {wall:.2f}s; jax backend compile "
             f"{COMPILE_S['backend'] - before['backend']:.2f}s, cache load "
             f"{COMPILE_S['cache_load'] - before['cache_load']:.2f}s; "
             f"registry compile.seconds {s1 - s0:.2f} compile.traces "
             f"{int(n1 - n0)}")
    return out


def print_process_notes() -> None:
    """The process's totals from the registry (the link first: what the
    phases moved, and what the pipelined transfer saved), then the
    allocator's peak, which only a chip's accountant reads."""
    import jax

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.telemetry import memory

    c = telemetry.get_registry().counters_dict()
    note("link totals: " + json.dumps({
        name: c.get(name, 0) for name in (
            "link.h2d.bytes", "link.h2d.seconds", "link.h2d.chunks",
            "link.h2d.transfers", "link.d2h.bytes", "link.d2h.seconds",
            "link.d2h.chunks", "link.d2h.prefetch_errors",
            "transfer.overlap_saved_seconds")}))
    note(f"registry totals: compile.seconds "
         f"{c.get('compile.seconds', 0.0):.2f} compile.traces "
         f"{int(c.get('compile.traces', 0))}")
    memory.sample()
    backend = memory.get_accountant().backend
    assert backend == "memory_stats", \
        f"HBM accountant read {backend!r}, not the allocator's memory_stats"
    for d in jax.devices():
        st = d.memory_stats()
        note(f"{d.platform}:{d.id} peak_bytes_in_use "
             f"{st['peak_bytes_in_use']} of bytes_limit "
             f"{st.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# One chip: build, filter scan, bucketed join
# ---------------------------------------------------------------------------


def probe_float64() -> None:
    """What the device does to a float64, printed so that every run
    reproduces what the program's representation rests on. Sent as
    float64, the edge values come back changed on a TPU; sent as int64
    bit patterns (`columnar.carried`, how every float64 column crosses)
    they come back exact — asserted; `f64_from_bits` is what an
    expression that computes on such a column sees, and the last column
    is what a 64-bit bitcast gives there (`ops/keys._can_bitcast64`)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import carried, f64_from_bits, fetched

    bits = jax.device_put(carried(F64_EDGE, "float64"))
    as_bits = fetched(np.asarray(bits), "float64")
    as_f64 = np.asarray(jax.device_put(F64_EDGE))
    decoded = np.asarray(f64_from_bits(bits))
    bitcast = np.asarray(jax.lax.bitcast_convert_type(bits, jnp.float64))
    note("float64 edge values through the device: sent | back, sent as "
         "int64 bits | back, sent as float64 | f64_from_bits on the device "
         "| bitcast on the device")
    for row in zip(F64_EDGE, as_bits, as_f64, decoded, bitcast):
        note("  " + " | ".join(repr(float(v)) for v in row))
    assert np.array_equal(as_bits.view(np.int64), F64_EDGE.view(np.int64)), \
        "int64 bit patterns did not survive H2D + D2H"
    bulk = np.random.default_rng(0).random(1 << 20)
    back = np.asarray(jax.device_put(bulk))
    changed = back != bulk
    note(f"uniform [0,1) float64 sent as float64: {np.mean(changed):.1%} of "
         f"{len(bulk)} values come back changed, max relative error "
         f"{np.max(np.abs(back - bulk) / bulk, initial=0.0):.3g}")


def phase_build(lake: Lake) -> None:
    """create_index through the facade (prints the lane it took), then
    the on-chip build of the same index, reached by residency, and the
    README's "identical on-disk layout either way" held to the row."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import native
    from hyperspace_tpu.io import columnar, parquet
    from hyperspace_tpu.io.builder import build_lane, write_bucketed_table

    note(f"native host library loaded: {native.get_lib() is not None}; "
         f"build_lane fact={build_lane(FACT_ROWS)} "
         f"dim={build_lane(DIM_ROWS)}")
    index_dir = lake.create_indexes()["smoke_fact"]

    # `_perm_core` + the Pallas hash kernel: the key staged on the device.
    key_batch = columnar.from_arrow(lake.fact.select(["key"]))
    assert not key_batch.is_host
    table = lake.fact.select(FACT_COLUMNS)
    outs = []

    def on_chip_build():
        outs.append(os.path.join(lake.work, f"onchip{len(outs)}"))
        write_bucketed_table(table, ["key"], NUM_BUCKETS, outs[-1],
                             key_batch=key_batch)

    timed("on-chip build", on_chip_build)
    # A bucket that spans a D2H chunk boundary of the permutation is
    # written as several run files (`part-NNNNN-cKK.parquet`) whose
    # name-ordered concatenation is the bucket; compare as a reader
    # would see them.
    want = parquet.bucket_files(index_dir)
    got = parquet.bucket_files(outs[0])
    assert sorted(want) == sorted(got), (sorted(want), sorted(got))
    rows = runs = 0
    for b in sorted(want):
        a, o = (pa.concat_tables([pq.read_table(f) for f in sorted(files)])
                for files in (want[b], got[b]))
        assert a.schema.equals(o.select(a.column_names).schema)
        for name in a.column_names:  # float64 to the bit (nan, -0.0)
            x, y = (t.column(name).to_numpy() for t in (a, o))
            if x.dtype == np.float64:
                x, y = x.view(np.int64), y.view(np.int64)
            assert np.array_equal(x, y), \
                f"bucket {b}: on-chip build differs in column {name}"
        rows += a.num_rows
        runs += len(got[b])
    assert rows == FACT_ROWS, rows
    note(f"on-chip build: {len(want)} buckets ({runs} run files) identical "
         f"to create_index's {sum(len(v) for v in want.values())} files, "
         f"row by row")


def phase_filter(lake: Lake) -> None:
    """A range predicate (~1% of rows) on the indexed column through the
    fused masked stage on the device, the same with a float64 predicate
    added, and one point filter whose lane is printed, whatever it is."""
    from hyperspace_tpu import col, lit

    lo = int(0.40 * DIM_ROWS) * KEY_STRIDE
    hi = int(0.41 * DIM_ROWS) * KEY_STRIDE
    fact_pd = lake.fact_pd
    table, metrics = timed("range filter", lambda: (
        lake.fdf.filter((col("key") >= lit(lo)) & (col("key") < lit(hi)))
        .select(*FACT_COLUMNS).collect(with_metrics=True)))
    assert_index_device_scans(metrics, 1)
    lanes = [e.get("lane") for e in metrics.events_of("fusion", "lane")]
    assert lanes == ["masked-device"], lanes
    assert_same_rows(table, fact_pd[(fact_pd.key >= lo) & (fact_pd.key < hi)],
                     "range filter")
    note(f"range filter: {table.num_rows} rows "
         f"({table.num_rows / FACT_ROWS:.2%}) equal to pandas; scan lane "
         f"device, fused stage masked-device")

    # The same range with a float64 predicate: the stage decodes `measure`
    # on the device to compare it (the chip's own f64 — this data has no
    # value within its rounding of the bound), and still returns the
    # column as it was carried, to the bit.
    table, metrics = timed("float64 predicate", lambda: (
        lake.fdf.filter((col("key") >= lit(lo)) & (col("key") < lit(hi))
                        & (col("measure") >= lit(0.5)))
        .select(*FACT_COLUMNS).collect(with_metrics=True)))
    assert_index_device_scans(metrics, 1)
    lanes = [e.get("lane") for e in metrics.events_of("fusion", "lane")]
    assert lanes == ["masked-device"], lanes
    assert_same_rows(table, fact_pd[(fact_pd.key >= lo) & (fact_pd.key < hi)
                                    & (fact_pd.measure >= 0.5)],
                     "float64 predicate")
    note(f"float64 predicate: {table.num_rows} rows equal to pandas; scan "
         f"lane device, fused stage masked-device")

    point = int(fact_pd.key.iloc[0])
    table, metrics = timed("point filter", lambda: (
        lake.fdf.filter(col("key") == lit(point))
        .select(*FACT_COLUMNS).collect(with_metrics=True)))
    assert_same_rows(table, fact_pd[fact_pd.key == point], "point filter")
    scans = scan_ops(metrics)
    note(f"point filter: {table.num_rows} rows equal to pandas; scan lanes "
         f"{[op.detail.get('lane') for op in scans]}, buckets scanned "
         f"{[op.detail.get('buckets_scanned') for op in scans]} of "
         f"{NUM_BUCKETS}")


def phase_join(lake: Lake) -> None:
    assert_no_shuffle(lake.join_df())
    table, metrics = timed("bucketed join", lambda: (
        lake.join_df().collect(with_metrics=True)))
    assert_index_device_scans(metrics, 2)
    lane = join_lane(metrics)
    assert lane not in (None, "host"), lane
    assert_same_rows(table, lake.join_reference(), "join")
    note(f"join: {table.num_rows} rows equal to pandas.merge; both scans "
         f"device, join lane {lane}, no Exchange/Sort in the plan")


def run_one_chip(lake: Lake) -> None:
    probe_float64()
    phase_build(lake)
    lake.sess.enable_hyperspace()
    phase_filter(lake)
    phase_join(lake)


# ---------------------------------------------------------------------------
# Four chips: the mesh build and the SPMD join, nothing else
# ---------------------------------------------------------------------------


def run_mesh(lake: Lake, n_devices: int) -> None:
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io.builder import read_shard_layout
    from hyperspace_tpu.parallel.context import distribution_mesh, mesh_size

    mesh = distribution_mesh(lake.sess.conf)
    assert mesh is not None and mesh_size(mesh) == n_devices, mesh
    reg = telemetry.get_registry()
    assert reg.gauge("mesh.devices").value == n_devices

    def counters(*names):
        c = reg.counters_dict()
        return [int(c.get(n, 0)) for n in names]

    build_names = ("mesh.build.execs", "mesh.build.overflow_retries")
    before, c0 = counters(*build_names), COMPILE_S["backend"]
    roots = lake.create_indexes()
    execs, retries = (a - b for a, b in zip(counters(*build_names), before))
    assert execs == len(INDEXES), execs
    for name, root in roots.items():
        layout = read_shard_layout(root)
        assert layout is not None and layout["numShards"] == n_devices, \
            f"{name} was not born sharded: {layout}"
    note(f"mesh build: both indexes born sharded over {n_devices} devices, "
         f"overflow retries {retries}, jax backend compile "
         f"{COMPILE_S['backend'] - c0:.2f}s")

    lake.sess.enable_hyperspace()
    assert_no_shuffle(lake.join_df())
    join_names = ("mesh.spmd.join_execs", "spmd.fallbacks",
                  "mesh.spmd.overflow_retries")
    before = counters(*join_names)
    table, metrics = timed("SPMD join", lambda: (
        lake.join_df().collect(with_metrics=True)))
    execs, fallbacks, retries = (
        a - b for a, b in zip(counters(*join_names), before))
    assert execs > 0, "join missed the SPMD lane"
    assert fallbacks == 0, fallbacks
    assert join_lane(metrics) == "spmd", join_lane(metrics)
    assert_same_rows(table, lake.join_reference(), "SPMD join")
    note(f"SPMD join: {table.num_rows} rows equal to pandas.merge; "
         f"mesh.spmd.join_execs +{execs}, spmd.fallbacks +0, overflow "
         f"retries +{retries}")

    # Code that has only met virtual devices may put everything on the
    # first. Each device joined rows of its own (the join's metrics), and
    # the join's result, taken before it leaves the devices, is held by
    # all n of them.
    (event,) = metrics.events_of("mesh", "join")
    assert len(event["shard_rows"]) == n_devices and \
        min(event["shard_rows"]) > 0, event
    from hyperspace_tpu.engine.executor import compile_plan
    batch = compile_plan(lake.sess.optimize(lake.join_df().plan),
                         conf=lake.sess.conf).execute()
    for name, col in batch.columns.items():
        holders = sorted(shard.device.id
                         for shard in col.raw.addressable_shards
                         if shard.data.size)
        assert len(set(holders)) == n_devices, (name, holders)
        note(f"SPMD join result column {name}: {col.raw.sharding.spec} on "
             f"devices {holders}; input rows per shard "
             f"{event['shard_rows']}")


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=22)
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the mesh build and the SPMD join, "
                             "on a host with four chips")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — jax reports platform "
              f"{dev.platform!r}; refusing to run on it", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) != want:
        print(f"chip_smoke: needs {want} chip(s), jax reports "
              f"{len(devices)}", file=sys.stderr)
        return 2

    import jaxlib

    from hyperspace_tpu.ops.pallas.hash_kernel import pallas_available
    from hyperspace_tpu.telemetry import compilation

    from importlib import metadata
    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a note only
        libtpu_version = "unknown"
    note(f"device: {dev.device_kind} x{len(devices)}; jax {jax.__version__} "
         f"jaxlib {jaxlib.__version__} libtpu {libtpu_version}")
    note(f"compile cache dir: {compilation.persistent_cache_dir()} "
         f"(JAX_COMPILATION_CACHE_DIR "
         f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    assert pallas_available(), "the build would not compile the Pallas kernel"

    listen_for_compiles()
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    t0 = time.perf_counter()
    try:
        lake = Lake(WORK_DIR, args.seed)
        if args.four_chips:
            run_mesh(lake, n_devices=4)
        else:
            run_one_chip(lake)
        print_process_notes()
        lake.sess.close()
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    note(f"total wall {time.perf_counter() - t0:.2f}s; jax backend compile "
         f"{COMPILE_S['backend']:.2f}s, cache load "
         f"{COMPILE_S['cache_load']:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
