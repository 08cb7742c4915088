"""TPC-H Q12 on the mesh (the four-chip cell's query, `bench/ops/q12.py`'s
DataFrame) over a small seeded lake on the virtual devices: lineitem with
dictionary ship modes, date32 dates and planted float64 edge rows, orders
with dictionary priorities, both covering indexes built by the mesh
`create_index`. Equal to a pandas oracle AND bit-equal to the same query
with distribution off; join lane `spmd`, no fallback, rows on every
shard, nothing sent to the devices and nothing retraced on a warm run;
the new spans and device scopes are in their tables and are emitted.

The size is small, so the two row thresholds a four-chip host's SF 3
lake clears by itself (`execution.min.device.rows`,
`distribution.min.rows`) are set to 0 here; everything else is default
conf, `distribution.enabled` left at `auto`."""

import datetime
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, col, lit, telemetry)
from hyperspace_tpu.plan.expr import when

MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
HIGH = ("1-URGENT", "2-HIGH")
BUCKETS = 64
EDGES = np.array([1e300, 5e-324, -0.0, np.inf, -np.inf, np.nan])


def _days(year: int) -> int:
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def _lake(n_orders=6000, seed=12):
    """lineitem (1 + key mod 7 lines an order) and orders. Lines of the
    orders whose bucket the FIRST shard owns never ship by RAIL or FOB."""
    import jax

    from hyperspace_tpu.ops.host_hash import host_bucket_ids
    from hyperspace_tpu.parallel.mesh import bucket_ranges

    rng = np.random.default_rng(seed)
    okey = np.arange(1, n_orders + 1, dtype=np.int64)
    lkey = np.repeat(okey, 1 + okey % 7)
    n = len(lkey)
    receipt = rng.integers(_days(1993), _days(1996), n).astype(np.int32)
    commit = receipt + rng.integers(-40, 40, n).astype(np.int32)
    ship = commit + rng.integers(-40, 40, n).astype(np.int32)
    mode = rng.integers(0, len(MODES), n)
    lo, hi = bucket_ranges(BUCKETS, len(jax.devices()))[0]
    bucket = host_bucket_ids([lkey], ["int64"], BUCKETS)
    first = (bucket >= lo) & (bucket < hi)
    mode[first & np.isin(mode, [MODES.index("RAIL"), MODES.index("FOB")])] \
        = MODES.index("TRUCK")
    price = rng.random(n) * 1e5
    price[::97] = EDGES[np.arange(len(price[::97])) % len(EDGES)]
    lineitem = pa.table({
        "l_orderkey": lkey,
        "l_shipmode": pa.DictionaryArray.from_arrays(
            pa.array(mode, pa.int32()), pa.array(MODES)),
        "l_shipdate": pa.array(ship, pa.date32()),
        "l_commitdate": pa.array(commit, pa.date32()),
        "l_receiptdate": pa.array(receipt, pa.date32()),
        "l_extendedprice": price})
    orders = pa.table({
        "o_orderkey": rng.permutation(okey),
        "o_orderpriority": pa.DictionaryArray.from_arrays(
            pa.array(rng.integers(0, len(PRIORITIES), n_orders), pa.int32()),
            pa.array(PRIORITIES))})
    return lineitem, orders, first


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    import jax

    root = tmp_path_factory.mktemp("q12mesh")
    lineitem, orders, first = _lake()
    for name, table, files in (("lineitem", lineitem, 4),
                               ("orders", orders, 2)):
        (root / name).mkdir()
        rows = -(-table.num_rows // files)
        for i in range(files):
            pq.write_table(table.slice(i * rows, rows),
                           str(root / name / f"part-{i}.parquet"))
    base = {"hyperspace.warehouse.dir": str(root / "wh"),
            "spark.hyperspace.index.num.buckets": str(BUCKETS),
            "spark.hyperspace.execution.min.device.rows": "0",
            "spark.hyperspace.distribution.min.rows": "0"}
    out = {"lineitem": lineitem.to_pandas(), "orders": orders.to_pandas(),
           "first_shard": first, "devices": len(jax.devices())}
    for mode, extra in (("mesh", {}), ("one_chip", {
            "spark.hyperspace.distribution.enabled": "false"})):
        sess = HyperspaceSession(HyperspaceConf(dict(base, **extra)))
        dfs = {t: sess.read_parquet(str(root / t))
               for t in ("lineitem", "orders")}
        if mode == "mesh":
            hs = Hyperspace(sess)
            hs.create_index(dfs["lineitem"], IndexConfig(
                "li_q12", ["l_orderkey"],
                ["l_shipmode", "l_shipdate", "l_commitdate",
                 "l_receiptdate", "l_extendedprice"]))
            hs.create_index(dfs["orders"], IndexConfig(
                "ord_q12", ["o_orderkey"], ["o_orderpriority"]))
            out["hs"] = hs
        sess.enable_hyperspace()
        out[mode] = dfs
    return out


def q12(dfs, shipmodes, year):
    """`bench/ops/q12.py`'s DataFrame."""
    li = dfs["lineitem"].filter(
        col("l_shipmode").isin(*shipmodes)
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= lit(_days(year)))
        & (col("l_receiptdate") < lit(_days(year + 1)))
    ).select("l_orderkey", "l_shipmode")
    j = li.join(dfs["orders"].select("o_orderkey", "o_orderpriority"),
                on=col("l_orderkey") == col("o_orderkey"))
    high = when(col("o_orderpriority").isin(*HIGH), 1).otherwise(0)
    low = when(col("o_orderpriority").isin(*HIGH), 0).otherwise(1)
    return (j.group_by("l_shipmode")
            .agg(("sum", high, "high_line_count"),
                 ("sum", low, "low_line_count"))
            .sort("l_shipmode"))


def survivors(lake, shipmodes, year):
    li = lake["lineitem"]
    lo, hi = datetime.date(year, 1, 1), datetime.date(year + 1, 1, 1)
    return (li["l_shipmode"].isin(shipmodes)
            & (li["l_commitdate"] < li["l_receiptdate"])
            & (li["l_shipdate"] < li["l_commitdate"])
            & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < hi))


def oracle(lake, shipmodes, year):
    li = lake["lineitem"][survivors(lake, shipmodes, year)]
    j = li.merge(lake["orders"], left_on="l_orderkey", right_on="o_orderkey")
    high = j["o_orderpriority"].isin(HIGH)
    g = pd.DataFrame({"l_shipmode": j["l_shipmode"].astype(str),
                      "high_line_count": high.astype(np.int64),
                      "low_line_count": (~high).astype(np.int64)}
                     ).groupby("l_shipmode").sum().sort_index()
    return {"l_shipmode": list(g.index),
            "high_line_count": g["high_line_count"].tolist(),
            "low_line_count": g["low_line_count"].tolist()}


def counters(*names):
    c = telemetry.get_registry().counters_dict()
    return [c.get(n, 0) for n in names]


MESH_COUNTERS = ("mesh.spmd.join_execs", "spmd.fallbacks",
                 "mesh.spmd.overflow_retries", "link.h2d.bytes",
                 "compile.traces")


@pytest.mark.parametrize("index", ["li_q12", "ord_q12"])
def test_both_indexes_are_born_sharded_by_the_mesh_build(lake, index):
    from hyperspace_tpu.io.builder import read_shard_layout

    found = {r["name"]: r["indexLocation"]
             for _, r in lake["hs"].indexes().iterrows()}
    layout = read_shard_layout(found[index])
    assert layout is not None and layout["numShards"] == lake["devices"]


@pytest.mark.parametrize("shipmodes,year,rows", [
    (("MAIL", "SHIP"), 1994, 2),   # the validation run's parameters
    (("RAIL", "FOB"), 1994, 2),    # the first shard keeps no line
    (("MAIL", "SHIP"), 2001, 0),   # no line at all
])
def test_q12_on_the_mesh_is_the_oracles_and_the_one_chips(lake, shipmodes,
                                                          year, rows):
    kept = survivors(lake, shipmodes, year)
    if rows:
        assert kept.sum() > 20
    else:
        assert kept.sum() == 0
    if "RAIL" in shipmodes:
        assert kept[lake["first_shard"]].sum() == 0 and kept.sum() > 0
    want = oracle(lake, shipmodes, year)
    assert len(want["l_shipmode"]) == rows
    before = counters(*MESH_COUNTERS)
    table, metrics = q12(lake["mesh"], shipmodes, year).collect(
        with_metrics=True)
    assert table.to_pydict() == want
    one = q12(lake["one_chip"], shipmodes, year).collect()
    assert table.schema == one.schema
    for name in table.column_names:  # bit-equal, not merely equal
        assert table.column(name).combine_chunks().equals(
            one.column(name).combine_chunks()), name
    joins = [op for op in metrics.operators if op.name == "SortMergeJoin"]
    assert [op.detail.get("lane") for op in joins] == ["spmd"]
    assert not [op.name for op in metrics.operators if op.name == "Exchange"]
    assert [op.name for op in metrics.operators
            if op.name == "Sort"] == ["Sort"]
    (event,) = metrics.events_of("mesh", "join")
    assert len(event["shard_rows"]) == lake["devices"]
    assert min(event["shard_rows"]) > 0
    assert not metrics.events_of("spmd", "fallback")
    execs, fallbacks, _, _, _ = (
        a - b for a, b in zip(counters(*MESH_COUNTERS), before))
    assert execs == 1 and fallbacks == 0
    # the same query again: nothing retried, sent or traced
    before = counters(*MESH_COUNTERS)
    again = q12(lake["mesh"], shipmodes, year).collect()
    assert again.equals(table)
    assert [a - b for a, b in zip(counters(*MESH_COUNTERS), before)] == \
        [1, 0, 0, 0, 0]


def test_float64_edge_rows_cross_the_mesh_to_the_bit(lake):
    """The payload the mesh build moved (all_to_all) and the SPMD join
    gathered: every float64 as its bits, nan, -0.0 and subnormals too."""
    def query(dfs):
        li = dfs["lineitem"].filter(col("l_shipmode").isin("MAIL")
                                    ).select("l_orderkey", "l_extendedprice")
        return li.join(dfs["orders"].select("o_orderkey"),
                       on=col("l_orderkey") == col("o_orderkey")
                       ).select("l_orderkey", "l_extendedprice")

    table, metrics = query(lake["mesh"]).collect(with_metrics=True)
    assert [op.detail.get("lane") for op in metrics.operators
            if op.name == "SortMergeJoin"] == ["spmd"]
    li = lake["lineitem"]
    want = li[li["l_shipmode"] == "MAIL"]

    def bits(keys, prices):
        return sorted(zip(np.asarray(keys).tolist(), np.asarray(
            prices, dtype=np.float64).view(np.int64).tolist()))

    got = bits(table.column("l_orderkey").to_numpy(),
               table.column("l_extendedprice").to_numpy())
    assert got == bits(want["l_orderkey"], want["l_extendedprice"])
    planted = set(EDGES.view(np.int64).tolist())
    assert planted <= {b for _, b in got} | planted - set(
        want["l_extendedprice"].to_numpy().view(np.int64).tolist())


def test_the_mesh_spans_are_in_the_table_and_emitted(lake):
    new = {"hs.mesh.read", "hs.mesh.join.sync"}
    assert new <= set(telemetry.SPAN_NAMES)
    q12(lake["mesh"], ("MAIL", "SHIP"), 1994).collect()  # fills the caches
    telemetry.enable_tracing()
    try:
        q12(lake["mesh"], ("MAIL", "SHIP"), 1994).collect()
        events = [e for e in telemetry.tracer().events
                  if e["name"].startswith("hs.mesh.")]
    finally:
        telemetry.disable_tracing()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert set(by_name) >= new | {"hs.mesh.filter", "hs.mesh.join.spmd",
                                  "hs.mesh.aggregate", "hs.mesh.place"}
    reads = by_name["hs.mesh.read"]
    assert len(reads) == 2  # lineitem's index, then orders'
    assert all(r["args"]["shards"] == lake["devices"]
               and r["args"]["cached"] == 1 for r in reads)
    assert sorted(r["args"]["rows"] for r in reads) == sorted(
        [len(lake["lineitem"]), len(lake["orders"])])
    (sync,) = by_name["hs.mesh.join.sync"]
    (join,) = by_name["hs.mesh.join.spmd"]
    assert sync["args"]["attempt"] == 1
    # sized by what the match found: the rung just above the fullest
    # shard's pairs, not the inputs' rows
    pairs, cap = join["args"]["pairs"], join["args"]["cap"]
    kept = survivors(lake, ("MAIL", "SHIP"), 1994).sum()
    assert kept / lake["devices"] <= pairs <= kept
    assert pairs <= cap < max(2 * pairs, 32) and cap & (cap - 1) == 0
    # a child: inside the join's span, on its thread
    assert sync["tid"] == join["tid"]
    assert join["ts"] <= sync["ts"] and \
        sync["ts"] + sync["dur"] <= join["ts"] + join["dur"] + 0.2


@pytest.fixture
def recorded_programs(monkeypatch):
    """The four SPMD programs of one Q12 (they are kept in one table,
    `spmd._cached_program`), with the arguments they were called with."""
    from hyperspace_tpu.parallel import spmd

    calls = {}
    real = spmd._cached_program

    def recording(key, builder):
        program = real(key, builder)

        def call(*args):
            calls.setdefault(key[0], (program, args))
            return program(*args)
        return call

    monkeypatch.setattr(spmd, "_cached_program", recording)
    return calls


@pytest.mark.parametrize("kind,scope,program", [
    ("filter", "hs.mesh.filter", "spmd_filter"),
    ("join_match", "hs.mesh.join", "spmd_join_match"),
    ("join_expand", "hs.mesh.join", "spmd_join_expand"),
    ("aggregate", "hs.mesh.aggregate", "aggregate_step")])
def test_the_spmd_programs_are_named_and_scoped(lake, recorded_programs,
                                                kind, scope, program):
    """What a device capture shows on every chip's plane: the program's
    name, and its ops under the device scope (through a nested jit:
    `telemetry.device_scoped`)."""
    assert scope in telemetry.DEVICE_SCOPES
    q12(lake["mesh"], ("MAIL", "SHIP"), 1994).collect()
    jitted, args = recorded_programs[kind]
    hlo = jitted.__wrapped_jit__.lower(*args).compile().as_text()
    assert f"jit_{program}" in hlo.splitlines()[0]
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scoped = [n for n in names if f"/{scope}/" in n]
    assert scoped and len(scoped) > 0.5 * len(names), (len(scoped),
                                                       len(names))
