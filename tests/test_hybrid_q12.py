"""TPC-H Q12 through Hybrid Scan (the cell `tpch_sf3_q12_hybrid`'s query,
`bench/ops/q12.py`'s DataFrame) over a small seeded lake that took
appends AFTER both covering indexes were built and was not refreshed:
equal to a plain numpy reference over the WHOLE lake (base plus appends;
this file's copy of `bench/reference/q12.py`'s logic, which imports
nothing of the program), for appends to both tables, to one of them, in
1 and in 8 files; with upstream's switch off the answer is still right
and the rule says why no index served it; the `hybrid.*` counters and
the `hs.plan.hybrid` / `appended` span arguments move as the plan says;
and the plan's shape (which branches, which join operator each) is
pinned for the two lane assignments the repo meets: everything on the
device (the CPU rehearsals) and the chip's (index sides over the device
threshold, appended sides under it, the broadcast threshold between the
appended lines' and the orders index's estimates).

One chip's path: distribution is off (the suite runs on eight virtual
devices); `execution.min.device.rows` is 0 but where a case says."""

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, col, lit, telemetry)
from hyperspace_tpu.plan.expr import when

MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
HIGH = ("1-URGENT", "2-HIGH")
N_ORDERS = 3000
N_NEW = 240  # orders of the appends to `orders`
HYBRID = "spark.hyperspace.index.hybridscan.enabled"


def _days(year: int) -> int:
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def _lines(keys, rng) -> dict:
    """Lines of the orders `keys` (1 + key mod 7 each) as code / day
    columns; about one line in eight passes Q12's predicate."""
    lkey = np.repeat(keys, 1 + keys % 7)
    n = len(lkey)
    receipt = rng.integers(_days(1993), _days(1996), n).astype(np.int32)
    commit = receipt + rng.integers(-40, 40, n).astype(np.int32)
    ship = commit + rng.integers(-40, 40, n).astype(np.int32)
    return {"l_orderkey": lkey,
            "l_shipmode": rng.integers(0, len(MODES), n).astype(np.int32),
            "l_shipdate": ship, "l_commitdate": commit,
            "l_receiptdate": receipt}


def _orders(keys, rng) -> dict:
    return {"o_orderkey": rng.permutation(keys),
            "o_orderpriority": rng.integers(
                0, len(PRIORITIES), len(keys)).astype(np.int32)}


def _arrow(columns: dict):
    out = {}
    for name, data in columns.items():
        if name == "l_shipmode":
            out[name] = pa.DictionaryArray.from_arrays(
                pa.array(data, pa.int32()), pa.array(MODES))
        elif name == "o_orderpriority":
            out[name] = pa.DictionaryArray.from_arrays(
                pa.array(data, pa.int32()), pa.array(PRIORITIES))
        elif name.endswith("date"):
            out[name] = pa.array(data, pa.date32())
        else:
            out[name] = data
    return pa.table(out)


def _write(columns: dict, directory, n_files: int, stem: str) -> None:
    directory.mkdir(exist_ok=True)
    table = _arrow(columns)
    per = -(-table.num_rows // n_files)
    for i in range(n_files):
        pq.write_table(table.slice(i * per, per),
                       str(directory / f"{stem}-{i:05d}.parquet"))


def _whole(*parts) -> dict:
    return {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}


def _passes(lineitem: dict, modes, year):
    """Q12's predicate over code / day columns, as a mask."""
    return (np.isin(lineitem["l_shipmode"], [MODES.index(m) for m in modes])
            & (lineitem["l_commitdate"] < lineitem["l_receiptdate"])
            & (lineitem["l_shipdate"] < lineitem["l_commitdate"])
            & (lineitem["l_receiptdate"] >= _days(year))
            & (lineitem["l_receiptdate"] < _days(year + 1)))


def reference_q12(lineitem: dict, orders: dict, modes, year) -> dict:
    """Q12 in numpy over code / day columns: {mode: (high, low)}."""
    keep = _passes(lineitem, modes, year)
    keys, mode = lineitem["l_orderkey"][keep], lineitem["l_shipmode"][keep]
    row_of = np.full(int(max(orders["o_orderkey"].max(), keys.max())) + 1, -1)
    row_of[orders["o_orderkey"]] = np.arange(len(orders["o_orderkey"]))
    rows = row_of[keys]
    matched = rows >= 0
    high = np.isin(orders["o_orderpriority"][rows[matched]],
                   [PRIORITIES.index(p) for p in HIGH])
    mode = mode[matched]
    return {MODES[g]: (int(np.count_nonzero(high[mode == g])),
                       int(np.count_nonzero(~high[mode == g])))
            for g in np.unique(mode)}


def q12(dfs, modes=("MAIL", "SHIP"), year=1994):
    li = dfs["lineitem"].filter(
        col("l_shipmode").isin(*modes)
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


def _answer(table) -> dict:
    d = table.to_pydict()
    return {m: (h, lo) for m, h, lo in zip(
        d["l_shipmode"], d["high_line_count"], d["low_line_count"])}


class Lake:
    """Base tables as 4 + 2 files, both Q12 indexes built over them,
    then the appends (`to`: which tables; `files`: how many a table) and
    DataFrames read anew. New orders have keys above the base's; new
    lines belong to the new orders where `orders` takes appends too, and
    to orders of the base otherwise (so that they change the answer)."""

    def __init__(self, root, to: str, files: int, conf=None):
        rng = np.random.default_rng(34)
        base_keys = np.arange(1, N_ORDERS + 1, dtype=np.int64)
        self.lineitem = _lines(base_keys, rng)
        self.orders = _orders(base_keys, rng)
        _write(self.lineitem, root / "lineitem", 4, "part")
        _write(self.orders, root / "orders", 2, "part")
        self.conf = {"hyperspace.warehouse.dir": str(root / "wh"),
                     "spark.hyperspace.index.num.buckets": "8",
                     "spark.hyperspace.execution.min.device.rows": "0",
                     "spark.hyperspace.distribution.enabled": "false",
                     HYBRID: "true"}
        self.conf.update(conf or {})
        self.root = root
        sess, dfs = self.session()
        hs = Hyperspace(sess)
        hs.create_index(dfs["lineitem"], IndexConfig(
            "li_q12", ["l_orderkey"],
            ["l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"]))
        hs.create_index(dfs["orders"], IndexConfig(
            "ord_q12", ["o_orderkey"], ["o_orderpriority"]))
        sess.close()
        new_keys = np.arange(N_ORDERS + 1, N_ORDERS + N_NEW + 1,
                             dtype=np.int64)
        self.appended = {"lineitem": 0, "orders": 0}
        if to in ("both", "orders"):
            new = _orders(new_keys, rng)
            _write(new, root / "orders", files, "appended")
            self.orders = _whole(self.orders, new)
            self.appended["orders"] = files
        if to in ("both", "lineitem"):
            new = _lines(new_keys if to == "both" else base_keys[:N_NEW], rng)
            _write(new, root / "lineitem", files, "appended")
            self.lineitem = _whole(self.lineitem, new)
            self.appended["lineitem"] = files

    def session(self, **conf):
        sess = HyperspaceSession(HyperspaceConf(dict(self.conf, **conf)))
        dfs = {t: sess.read_parquet(str(self.root / t))
               for t in ("lineitem", "orders")}
        sess.enable_hyperspace()
        return sess, dfs

    def reference(self, modes=("MAIL", "SHIP"), year=1994) -> dict:
        return reference_q12(self.lineitem, self.orders, modes, year)


def _applied(metrics) -> dict:
    """{index name: its entry of the JoinIndexRule `applied` event}."""
    return {ix["name"]: ix
            for e in metrics.events_of("rule", "JoinIndexRule")
            if e.get("action") == "applied" for ix in e["indexes"]}


def _scans(metrics) -> list:
    return [op.detail for op in metrics.operators if op.name == "Scan"]


@pytest.mark.parametrize("files", [1, 8])
@pytest.mark.parametrize("to", ["both", "lineitem", "orders"])
def test_q12_through_hybrid_scan_equals_the_reference_over_the_whole_lake(
        tmp_path, to, files):
    lake = Lake(tmp_path, to, files)
    sess, dfs = lake.session()
    try:
        table, metrics = q12(dfs).collect(with_metrics=True)
        want = lake.reference()
        assert _answer(table) == want
        # the base alone is another answer wherever lines were appended
        if to != "orders":
            rng = np.random.default_rng(34)
            base_keys = np.arange(1, N_ORDERS + 1, dtype=np.int64)
            assert reference_q12(_lines(base_keys, rng),
                                 _orders(base_keys, rng),
                                 ("MAIL", "SHIP"), 1994) != want
        # both sides through their index version, each with exactly the
        # files appended to its table, and nothing else
        applied = _applied(metrics)
        assert {n: ix["appended_files"] for n, ix in applied.items()} == {
            "li_q12": lake.appended["lineitem"],
            "ord_q12": lake.appended["orders"]}
        scans = _scans(metrics)
        index_roots = {r for s in scans for r in s["roots"] if "v__=" in r}
        assert index_roots == {ix["root"] for ix in applied.values()}
        source = [s for s in scans if not any("v__=" in r
                                              for r in s["roots"])]
        assert source and all(
            s["appended"] == s["files_scanned"] == files for s in source)
        assert {r.rsplit("/", 1)[-1] for s in source for r in s["roots"]} \
            == {t for t, n in lake.appended.items() if n}
        # a second query over other parameters, through the same plan
        assert _answer(q12(dfs, ("RAIL", "FOB"), 1995).collect()) \
            == lake.reference(("RAIL", "FOB"), 1995)
    finally:
        sess.close()


def test_with_the_switch_off_the_answer_is_right_and_no_index_serves_it(
        tmp_path):
    lake = Lake(tmp_path, "both", 2)
    sess, dfs = lake.session(**{HYBRID: "false"})
    try:
        table, metrics = q12(dfs).collect(with_metrics=True)
        assert _answer(table) == lake.reference()
        assert not _applied(metrics)
        skipped = [e for e in metrics.events_of("rule", "JoinIndexRule")
                   if e.get("action") == "skipped"]
        assert [e["reason"] for e in skipped] == [
            "no usable/compatible index pair"]
        assert skipped[0]["left_join_columns"] == ["l_orderkey"]
        assert skipped[0]["right_join_columns"] == ["o_orderkey"]
        scans = _scans(metrics)
        assert scans and not any("v__=" in r for s in scans
                                 for r in s["roots"])
        assert not any("appended" in s for s in scans)
        assert not [op for op in metrics.operators
                    if op.name == "BroadcastHashJoin"]
    finally:
        sess.close()


def test_hybrid_counters_and_span_arguments_move_as_the_plan_says(tmp_path):
    lake = Lake(tmp_path, "both", 8)
    sess, dfs = lake.session()
    reg = telemetry.get_registry()
    names = ("hybrid.queries", "hybrid.appended_files",
             "hybrid.appended_bytes")
    ring = telemetry.enable_tracing()
    try:
        q12(dfs).collect()  # cold caches are not what is counted
        before = {n: reg.counters_dict().get(n, 0) for n in names}
        ring.events.clear()
        table, metrics = q12(dfs).collect(with_metrics=True)
        gained = {n: reg.counters_dict().get(n, 0) - before[n]
                  for n in names}
        source = [s for s in _scans(metrics) if "appended" in s]
        assert gained["hybrid.queries"] == 1
        # per scan of appended files: each table's files twice (its
        # branch against the other side's index and against its appends)
        assert len(source) == 4
        assert gained["hybrid.appended_files"] == sum(
            s["appended"] for s in source) == 32
        assert gained["hybrid.appended_bytes"] == sum(
            s["bytes_scanned"] for s in source) > 0
        events = list(ring.events)
        hybrid = [e for e in events if e["name"] == "hs.plan.hybrid"]
        assert sorted((e["args"]["index"], e["args"]["appended"],
                       e["args"]["deleted"], e["args"]["files"])
                      for e in hybrid) == [("li_q12", 8, 0, 12),
                                           ("ord_q12", 8, 0, 10)]
        optimize = [e for e in events if e["name"] == "hs.plan.optimize"]
        assert len(optimize) == 1 and all(
            optimize[0]["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= optimize[0]["ts"] + optimize[0]["dur"]
            for e in hybrid)
        scan_spans = [e for e in events if e["name"] == "hs.op.Scan"]
        assert len(scan_spans) == 8
        assert sorted(e["args"].get("appended", 0) for e in scan_spans) \
            == [0, 0, 0, 0, 8, 8, 8, 8]
        # a query that no hybrid rewrite serves moves none of them
        before = {n: reg.counters_dict().get(n, 0) for n in names}
        dfs["orders"].select("o_orderkey").collect()
        assert {n: reg.counters_dict().get(n, 0) for n in names} == before
    finally:
        telemetry.disable_tracing()
        sess.close()


def _shape(node, depth=0) -> list:
    """The physical plan's unions, joins and scans, depth first."""
    from hyperspace_tpu.engine import physical as P

    out = []
    if isinstance(node, P.UnionExec):
        out.append("Union")
    elif isinstance(node, P.SortMergeJoinExec):
        out.append("SortMergeJoin bucketed" if node.bucketed
                   else "SortMergeJoin")
    elif isinstance(node, P.BroadcastHashJoinExec):
        out.append(f"BroadcastHashJoin build={node.build_side}")
    elif isinstance(node, P.ScanExec):
        out.append("Scan " + ("index" if node.scan.index_name
                              else "appended" if node.scan.appended
                              else "source"))
    for c in node.children:
        out.extend(_shape(c, depth + 1))
    return out


# the join distributes over both unions: index x index keeps the bucketed
# sort-merge join, every branch with an appended side is a broadcast join
ALL_ON_DEVICE = [
    "Union", "Union",
    "SortMergeJoin bucketed", "Scan index", "Scan index",
    "BroadcastHashJoin build=right", "Scan index", "Scan appended",
    "Union",
    "BroadcastHashJoin build=right", "Scan appended", "Scan index",
    "BroadcastHashJoin build=right", "Scan appended", "Scan appended"]
# the chip's: the orders index is over the broadcast threshold, so the
# appended LINES are the build side of the third branch
AS_ON_THE_CHIP = [s if i != 9 else "BroadcastHashJoin build=left"
                  for i, s in enumerate(ALL_ON_DEVICE)]


@pytest.mark.parametrize("conf,shape,paths", [
    ({}, ALL_ON_DEVICE,
     [("fused", "device"), ("fused", "device"), ("fused", "device")]),
    # orders index 3,000 x 24 B = 72,000 over the threshold; appended
    # lines 1,0xx x 36 B under it; index scans (3,000 and more rows) on
    # the device lane, appended scans (at most 1,0xx rows) on the host's
    ({"spark.hyperspace.broadcast.threshold": "60000",
      "spark.hyperspace.execution.min.device.rows": "2000"},
     AS_ON_THE_CHIP,
     [("fused", "device"), ("counting", "device"),
      ("direct-address", "host")]),
], ids=["all_on_device", "as_on_the_chip"])
def test_the_plans_shape_is_pinned(tmp_path, conf, shape, paths):
    """Which branches, which join operator each, and which path served
    each broadcast join: a change to the join-over-union distribution,
    to the broadcast estimate or to the probe's eligibility shows here
    before it shows on the chip."""
    from hyperspace_tpu.engine.executor import compile_plan

    lake = Lake(tmp_path, "both", 8, conf=conf)
    sess, dfs = lake.session()
    try:
        df = q12(dfs)
        physical = compile_plan(sess.optimize(df.plan), conf=sess.conf,
                                fuse=False)
        assert _shape(physical) == shape
        table, metrics = df.collect(with_metrics=True)
        assert _answer(table) == lake.reference()
        served = [(e["path"], e["lane"])
                  for e in metrics.events_of("join", "broadcast")] + [
            (op.detail["path"], op.detail["lane"])
            for op in metrics.operators if op.name == "BroadcastHashJoin"]
        assert served == paths
        if conf:
            assert [(s["lane"], "appended" in s) for s in _scans(metrics)
                    ].count(("host", True)) == 4
            assert [(s["lane"], "appended" in s) for s in _scans(metrics)
                    ].count(("device", False)) == 4
            # why the third branch's direct-address table declined: the
            # appended lines that pass the filter repeat an order key
            li = lake.lineitem
            keys = li["l_orderkey"][(li["l_orderkey"] > N_ORDERS)
                                    & _passes(li, ("MAIL", "SHIP"), 1994)]
            assert len(np.unique(keys)) < len(keys)
    finally:
        sess.close()
