"""TPC-H Q6 (`tpch.queries.q6`, DATE one of 1993-1997) through the
second index kind: a min/max data-skipping index on `l_shipdate` over a
small lake of `lineitem` landed one Parquet file per month of the
order date (80 files, 1992-01 to 1998-08; `l_shipdate` is the order
date plus 1..121 days, so it follows the layout without being a
partition column). The skipping-served answer equals a plain reference
over the whole table (this file's copy of `bench/reference/
tpch_q6_skip.py`'s exact decimal revenue, which imports nothing of the
program) on the device lane and on the host lane, reads only the files
whose zones meet the year and only the columns the query reads; with
the index dropped the same answers come back from every file; and the
rule's consult shows as the span `hs.plan.skipping` with its
arguments, the scan's span says its source and its files, and the
query record carries the skipping counters."""

import datetime
import json
from fractions import Fraction

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (DataSkippingIndexConfig, Hyperspace,
                            HyperspaceConf, HyperspaceSession, telemetry)
from hyperspace_tpu.tpch import queries

YEARS = (1993, 1994, 1995, 1996, 1997)
N_ORDERS = 15_000  # SF 0.01
_EPOCH = datetime.date(1970, 1, 1)
_FIRST, _LAST = datetime.date(1992, 1, 1), datetime.date(1998, 8, 2)
READ = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


def _days(d: datetime.date) -> int:
    return (d - _EPOCH).days


def _lineitem(seed: int) -> dict:
    """Lines of N_ORDERS orders (1 + key mod 7 each) in order-date
    order: prices in whole cents, discounts in hundredths, quantities
    1..50, ship dates the order's plus 1..121 days."""
    rng = np.random.default_rng(seed)
    keys = np.arange(1, N_ORDERS + 1, dtype=np.int64)
    ordered = rng.integers(_days(_FIRST), _days(_LAST) + 1, N_ORDERS)
    lkey = np.repeat(keys, 1 + keys % 7)
    odate = np.repeat(ordered, 1 + keys % 7)
    order = np.argsort(odate, kind="stable")
    n = len(lkey)
    return {"l_orderkey": lkey[order],
            "o_orderdate": odate[order].astype(np.int32),
            "l_quantity": rng.integers(1, 51, n).astype(np.float64),
            "l_extendedprice": rng.integers(0, 10_500_000, n) / 100.0,
            "l_discount": rng.integers(0, 11, n) / 100.0,
            "l_shipdate": (odate[order] + rng.integers(1, 122, n)
                           ).astype(np.int32)}


def _months(days: np.ndarray) -> np.ndarray:
    return days.astype("datetime64[D]").astype("datetime64[M]") \
        .astype(np.int64)


def _write_lake(root, li: dict) -> list:
    """One file per order month; returns each file's (least, greatest)
    ship date."""
    months = _months(li["o_orderdate"])
    starts = np.flatnonzero(np.r_[True, months[1:] != months[:-1]])
    ends = np.r_[starts[1:], len(months)]
    table = pa.table({
        "l_orderkey": li["l_orderkey"],
        "l_quantity": li["l_quantity"],
        "l_extendedprice": li["l_extendedprice"],
        "l_discount": li["l_discount"],
        "l_shipdate": pa.array(li["l_shipdate"]).cast(pa.date32())})
    root.mkdir()
    zones = []
    for s, e, m in zip(starts, ends, months[starts]):
        y, mo = divmod(int(m), 12)
        pq.write_table(table.slice(s, e - s),
                       str(root / f"part-{1970 + y}-{mo + 1:02d}.parquet"))
        zones.append((int(li["l_shipdate"][s:e].min()),
                      int(li["l_shipdate"][s:e].max())))
    return zones


def exact_revenue(li: dict, year: int) -> Fraction:
    """Q6's revenue, exactly: whole cents x whole hundredths, over the
    whole table."""
    lo = _days(datetime.date(year, 1, 1))
    hi = _days(datetime.date(year + 1, 1, 1))
    disc = np.rint(li["l_discount"] * 100).astype(np.int64)
    m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
         & (disc >= 5) & (disc <= 7) & (li["l_quantity"] < 24))
    cents = np.rint(li["l_extendedprice"][m] * 100).astype(np.int64)
    return Fraction(int((cents * disc[m]).sum()), 10 ** 4)


def expected_files(zones: list, year: int) -> int:
    lo = _days(datetime.date(year, 1, 1))
    hi = _days(datetime.date(year + 1, 1, 1))
    return sum(1 for least, most in zones if least < hi and most >= lo)


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("q6_lake")
    li = _lineitem(2 ** 31 + 40)
    zones = _write_lake(root / "lineitem", li)
    assert len(zones) == 80
    return root, li, zones


def _session(root, tag: str, min_rows: str = "0"):
    """A session whose scans take the lane `min_rows` gives them, over a
    warehouse holding `li_ship_skip`. The index is built by a session of
    its own whose threshold keeps each file's sketch on the host lane,
    as the chip's threshold does for SF 10's 750,000-line months (a
    device-lane sketch compiles once per file length)."""
    def session(rows):
        sess = HyperspaceSession(HyperspaceConf({
            "hyperspace.warehouse.dir": str(root / f"wh_{tag}"),
            "spark.hyperspace.execution.min.device.rows": rows,
            "spark.hyperspace.distribution.enabled": "false"}))
        return sess, Hyperspace(sess), sess.read_parquet(
            str(root / "lineitem"))

    maker, hs, df = session("1000000")
    hs.create_index(df, DataSkippingIndexConfig(
        "li_ship_skip", ["l_shipdate"], sketch_types=["zonemap"]))
    maker.close()
    sess, hs, df = session(min_rows)
    sess.enable_hyperspace()
    return sess, hs, df


def _served(metrics) -> list:
    return [ix for e in metrics.events_of("rule", "FilterIndexRule")
            if e.get("action") == "applied"
            for ix in e["indexes"] if ix["side"] == "skipping"]


def _scans(metrics) -> list:
    return [op.detail for op in metrics.operators if op.name == "Scan"]


@pytest.mark.parametrize("min_rows,lane", [("0", "device"),
                                           ("1000000", "host")])
def test_q6_served_from_the_sketches_equals_the_reference(lake, min_rows,
                                                          lane):
    root, li, zones = lake
    sess, _, df = _session(root, "served" + min_rows, min_rows)
    try:
        for year in YEARS:
            table, metrics = queries.q6({"lineitem": df}, year).collect(
                with_metrics=True)
            assert table.column_names == ["revenue"] and table.num_rows == 1
            got = Fraction(table.column(0)[0].as_py())
            assert abs(got - exact_revenue(li, year)) < Fraction(1, 10 ** 6)
            (served,) = _served(metrics)
            assert served["name"] == "li_ship_skip"
            assert served["served"] == "source"
            assert served["files_considered"] == 80
            (scan,) = _scans(metrics)
            kept = expected_files(zones, year)
            assert 0 < scan["files_scanned"] == kept < 80
            assert served["files_pruned"] == 80 - kept
            assert scan["lane"] == lane and scan["source"] == "lineitem"
            # the pruned scan reads the four columns Q6 reads, not the
            # relation's every column
            assert f"Scan parquet [{', '.join(READ)}]" in \
                metrics.format_tree()
    finally:
        sess.close()


@pytest.mark.parametrize("year", YEARS)
def test_without_the_skipping_index_every_file_gives_the_same_answer(
        lake, year):
    """Pruning removed no qualifying row: the same revenue, to the bit,
    from all 80 files once the index is dropped."""
    root, _, _ = lake
    sess, hs, df = _session(root, f"dropped{year}")
    try:
        pruned = queries.q6({"lineitem": df}, year).collect()
        hs.delete_index("li_ship_skip")
        table, metrics = queries.q6({"lineitem": df}, year).collect(
            with_metrics=True)
        assert not _served(metrics)
        assert _scans(metrics)[0]["files_scanned"] == 80
        assert table.column(0).to_pylist() == pruned.column(0).to_pylist()
    finally:
        sess.close()


def test_the_skipping_span_and_counters_show_the_prune(lake, tmp_path):
    root, _, zones = lake
    sess, _, df = _session(root, "span")
    reg = telemetry.get_registry()
    ring = telemetry.enable_tracing()
    try:
        queries.q6({"lineitem": df}, 1995).collect()  # cold caches
        ring.events.clear()
        before = {n: reg.counters_dict().get(n, 0)
                  for n in ("skipping.files_pruned", "skipping.bytes_pruned")}
        _, metrics = queries.q6({"lineitem": df}, 1995).collect(
            with_metrics=True)
        out = tmp_path / "trace.json"
        telemetry.export_trace(str(out))
        with open(out) as f:
            events = json.load(f)["traceEvents"]
        kept = expected_files(zones, 1995)
        (skip,) = [e for e in events if e["name"] == "hs.plan.skipping"]
        assert skip["args"]["index"] == "li_ship_skip"
        assert skip["args"]["files"] == 80 and skip["args"]["kept"] == kept
        assert skip["args"]["served"] == "source"
        assert skip["args"]["bytes_pruned"] == \
            metrics.counters["skipping.bytes_pruned"] > 0
        assert metrics.counters["skipping.files_pruned"] == 80 - kept
        (optimize,) = [e for e in events if e["name"] == "hs.plan.optimize"]
        assert optimize["ts"] <= skip["ts"] and \
            skip["ts"] + skip["dur"] <= optimize["ts"] + optimize["dur"]
        (scan,) = [e for e in events if e["name"] == "hs.op.Scan"]
        assert scan["args"]["source"] == "lineitem"
        assert scan["args"]["files"] == kept
        gained = {n: reg.counters_dict().get(n, 0) - before[n]
                  for n in before}
        assert gained == {
            "skipping.files_pruned": 80 - kept,
            "skipping.bytes_pruned": metrics.counters[
                "skipping.bytes_pruned"]}
    finally:
        telemetry.disable_tracing()
        sess.close()


# -- what the cell forced --------------------------------------------------------


@pytest.mark.parametrize("group_by", [[], ["g"]])
def test_a_global_aggregate_reduces_without_a_scatter(group_by):
    """One group is reduced by plain reductions, which XLA lowers to a
    tree (a segment op into one slot is a scatter that adds every row in
    turn: serial on the chip, and 1e-12 of a sum off in its f32-pair
    float64); every aggregate keeps SQL's null semantics and the host
    lane's value, and a group-by of one group takes the same program."""
    import jax

    from hyperspace_tpu.io.columnar import from_arrow, to_arrow
    from hyperspace_tpu.ops import aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    rng = np.random.default_rng(6)
    n = 5000
    table = pa.table({
        "g": np.zeros(n, dtype=np.int64),
        "x": pa.array([None if i % 13 == 0 else float(v) for i, v in
                       enumerate(rng.integers(0, 10 ** 7, n) / 100.0)],
                      type=pa.float64()),
        "y": rng.integers(-100, 100, n).astype(np.int64)})
    specs = [AggSpec("count", "*", "cnt"), AggSpec("count", "x", "cx"),
             AggSpec("sum", "x", "sx"), AggSpec("avg", "x", "ax"),
             AggSpec("min", "x", "mnx"), AggSpec("max", "y", "mxy"),
             AggSpec("sum", "y", "sy"), AggSpec("stddev", "x", "dx")]
    out_schema = Aggregate(group_by, specs, Scan(
        ["/nonexistent"], Schema.from_arrow(table.schema))).schema
    got, want = (to_arrow(aggregate.group_aggregate(
        from_arrow(table, device=device), group_by, specs, out_schema))
        .to_pylist() for device in (True, False))
    assert len(got) == 1
    assert got[0] == pytest.approx(want[0], rel=1e-12)
    x = table.column("x").drop_null().to_numpy()
    assert got[0]["cx"] == len(x) and got[0]["sy"] == int(
        table.column("y").to_numpy().sum())
    assert got[0]["mnx"] == x.min() and got[0]["mxy"] == int(
        table.column("y").to_numpy().max())
    # the program of one group holds no scatter
    seg = jax.numpy.zeros(n, dtype=np.int32)
    col = jax.numpy.asarray(table.column("y").to_numpy())
    hlo = jax.jit(aggregate._group_finish.__wrapped__,
                  static_argnames=("plan", "num_groups")).lower(
        seg, (), ((col, None),), plan=(("sum", "int64", "int64", False),),
        num_groups=1).compile().as_text()
    assert "scatter" not in hlo


def test_a_skipping_index_made_again_under_its_name_prunes_anew(lake,
                                                                tmp_path):
    """Dropped, vacuumed and made again on another column, the index
    writes its blob at the same version path: the query reads the new
    sketches, not the blob the process cached there."""
    from hyperspace_tpu import col, lit

    root, _, _ = lake
    sess, hs, df = _session(root, "again", "1000000")
    try:
        queries.q6({"lineitem": df}, 1994).collect()  # caches the blob
        hs.delete_index("li_ship_skip")
        hs.vacuum_index("li_ship_skip")
        hs.create_index(df, DataSkippingIndexConfig(
            "li_ship_skip", ["l_orderkey"], sketch_types=["zonemap"]))
        _, metrics = df.filter(col("l_orderkey") < lit(100)).select(
            "l_orderkey").collect(with_metrics=True)
        (served,) = _served(metrics)
        assert served["served"] == "source" and served["files_pruned"] > 0
        _, metrics = queries.q6({"lineitem": df}, 1994).collect(
            with_metrics=True)
        assert not _served(metrics)  # no sketch of l_shipdate is left
    finally:
        sess.close()


# -- one stat per file per query ------------------------------------------------


def test_a_served_query_stats_each_lake_file_once(lake, monkeypatch):
    """Admission's footprint, the prune's stamps, the scan's resolve and
    the segment cache all stamp the lake's files; on the query's own
    thread each file is stat'ed once (`storage.one_stat_per_file`)."""
    import collections
    import os
    import threading

    root, _, zones = lake
    sess, _, df = _session(root, "stats")
    try:
        queries.q6({"lineitem": df}, 1995).collect()  # cold caches
        lake_dir = str(root / "lineitem")
        me = threading.get_ident()
        stats = collections.Counter()
        real = os.stat

        def counting(path, *a, **k):
            if threading.get_ident() == me \
                    and str(path).startswith(lake_dir):
                stats[str(path)] += 1
            return real(path, *a, **k)
        monkeypatch.setattr(os, "stat", counting)
        table = queries.q6({"lineitem": df}, 1995).collect()
        monkeypatch.undo()
        assert table.num_rows == 1
        assert len(stats) == 80 and set(stats.values()) == {1}
    finally:
        sess.close()


def test_a_month_rewritten_between_two_queries_is_read_anew(lake, tmp_path):
    """What a query remembers of its stats ends with it: a kept month
    rewritten in place with other prices gives the next query the new
    revenue."""
    import shutil

    root, li, zones = lake
    mine = tmp_path / "lake"
    shutil.copytree(root / "lineitem", mine / "lineitem")
    sess, _, df = _session(mine, "rewrite")
    try:
        before = queries.q6({"lineitem": df}, 1995).collect()
        assert Fraction(before.column(0)[0].as_py()) == pytest.approx(
            exact_revenue(li, 1995), abs=1e-6)
        months = _months(li["o_orderdate"])
        june = months == (1995 - 1970) * 12 + 5
        changed = dict(li, l_extendedprice=np.where(
            june, li["l_extendedprice"] + 1.0, li["l_extendedprice"]))
        sel = np.flatnonzero(june)
        pq.write_table(pa.table({
            "l_orderkey": changed["l_orderkey"][sel],
            "l_quantity": changed["l_quantity"][sel],
            "l_extendedprice": changed["l_extendedprice"][sel],
            "l_discount": changed["l_discount"][sel],
            "l_shipdate": pa.array(changed["l_shipdate"][sel]).cast(
                pa.date32())}),
            str(mine / "lineitem" / "part-1995-06.parquet"))
        after = queries.q6({"lineitem": df}, 1995).collect()
        want = exact_revenue(changed, 1995)
        assert want != exact_revenue(li, 1995)
        assert abs(Fraction(after.column(0)[0].as_py()) - want) \
            < Fraction(1, 10 ** 6)
    finally:
        sess.close()
