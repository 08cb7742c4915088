"""The refreshed lake's files (`datasets/tpch_rf1.py`,
`drivers/closed_loop_refreshed.py`, `ops/q12_hybrid.py`,
`control_refreshed.py`) and the four readers of the cell
`tpch_sf3_q12_hybrid`, on the CPU: the dataset gives `tpch`'s base to the
bit and refresh sets whose counts follow the keys alone; an answer over
the base alone is not correct (the control); two faults planted under a
rehearsal turn `correct` false (the appended rows left out of the answer;
the sides served from the source files instead of their indexes); and
each new reader reads records and spans as a run leaves them: the two
span readers on a traced rehearsal, the two device readers on a trace
written here field by field (the CPU has no device plane), None, never
0, where there is nothing to read.

`test_run.py`'s parametrised cases (the rehearsal traced and untraced,
the altered answer, `control.py`'s control) take the new cell from the
manifest like any other."""

import os

import numpy as np
import pytest

from conftest import ROOT, plug
from lib import program_spans, roofline, trace_reduce
from test_mesh_readers import _field
from test_run import TINY

import control_refreshed
import run as bench_run

CELL = "tpch_sf3_q12_hybrid"
MANIFEST = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FOUND = bench_run.resolve(MANIFEST, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]


# -- the dataset --------------------------------------------------------------


def test_the_base_is_tpchs_to_the_bit_and_the_sets_come_apart():
    rf1, tpch = plug("datasets", "tpch_rf1"), plug("datasets", "tpch")
    base, plain = rf1.make(CONFIG, 5, 0.01), tpch.make(CONFIG, 5, 0.01)
    for t in plain:
        for c in plain[t]:
            assert base[t][c].dtype == plain[t][c].dtype
            assert base[t][c].tobytes() == plain[t][c].tobytes()
    sets = rf1.refresh_sets(CONFIG, 5, 0.01)
    n = rf1.order_count(0.01)
    assert len(sets) == CONFIG["refresh"]["sets"] == 8
    for i, one in enumerate(sets):
        keys = np.sort(one["orders"]["o_orderkey"])
        assert keys.tolist() == rf1.set_keys(0.01, i).tolist()
        assert keys[0] == n + 1 + 15 * i and len(keys) == 15
        assert np.array_equal(
            np.sort(one["lineitem"]["l_orderkey"]),
            np.repeat(keys, 1 + keys % 7))
        for t in plain:  # the base's columns, order and types
            assert list(one[t]) == list(plain[t])
            assert [a.dtype for a in one[t].values()] == \
                [a.dtype for a in plain[t].values()]
    whole = rf1.whole(base["orders"], [s["orders"] for s in sets])
    assert len(np.unique(whole["o_orderkey"])) == n + 120 \
        == len(whole["o_orderkey"])


def test_a_sets_counts_and_filter_columns_follow_the_keys_not_the_seed():
    rf1 = plug("datasets", "tpch_rf1")
    a = rf1.refresh_sets(CONFIG, 1, 0.05)
    b = rf1.refresh_sets(CONFIG, 2 ** 31 + 7, 0.05)
    fixed = ("l_shipdate", "l_commitdate", "l_receiptdate", "l_shipmode")
    moved = 0
    for one, other in zip(a, b):
        by_a = np.lexsort((one["lineitem"]["l_linenumber"],
                           one["lineitem"]["l_orderkey"]))
        by_b = np.lexsort((other["lineitem"]["l_linenumber"],
                           other["lineitem"]["l_orderkey"]))
        for c in fixed + ("l_orderkey", "l_linenumber"):
            assert np.array_equal(one["lineitem"][c][by_a],
                                  other["lineitem"][c][by_b])
        moved += int(np.any(one["lineitem"]["l_partkey"][by_a]
                            != other["lineitem"]["l_partkey"][by_b]))
        moved += int(np.any(one["lineitem"]["l_orderkey"]
                            != other["lineitem"]["l_orderkey"]))
    assert moved == 16  # the seed sets the payload and the row order
    # the cell's own size, from the keys alone: 8 x 4,500 orders, and
    # their lines
    keys = np.concatenate([rf1.set_keys(3.0, i) for i in range(8)])
    assert keys[0] == 4_500_001 and len(keys) == 36_000
    assert int((1 + keys % 7).sum()) == 144_002
    assert rf1.lineitem_count(rf1.order_count(3.0)) == 17_999_998


def test_a_set_lands_as_one_file_beside_the_base(tmp_path):
    import pyarrow.parquet as pq

    rf1 = plug("datasets", "tpch_rf1")
    base = rf1.make(CONFIG, 3, 0.01)
    rf1.write_parquet(base["orders"], str(tmp_path / "orders"), 2)
    one = rf1.refresh_sets(CONFIG, 3, 0.01)[0]
    path = rf1.land_set(one["orders"], str(tmp_path / "orders"), 0)
    assert sorted(os.listdir(tmp_path / "orders")) == [
        "part-00000.parquet", "part-00001.parquet", "part-rf1-00000.parquet"]
    assert pq.read_schema(path) == pq.read_schema(
        str(tmp_path / "orders" / "part-00000.parquet"))
    assert pq.read_metadata(path).num_rows == 15


# -- the control --------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_an_answer_over_the_base_alone_is_not_correct(seed):
    """The reference over the tables as the indexes saw them, put in the
    program's place, fails the comparison's limit of 0 (at 1/20 scale,
    where both of Q12's rows gain lines from the sets)."""
    dataset = plug("datasets", CONFIG["dataset"])
    op = plug("ops", TRAFFIC["op"]).Op
    reference = plug("reference", TRAFFIC["reference"])
    base, whole = control_refreshed.lake(dataset, CONFIG, seed, 0.05,
                                         TRAFFIC["tables"])
    params = op.control_params(TRAFFIC["query"], dataset, 0.05, seed)
    reading = control_refreshed.stale_reading(
        reference, base, whole, TRAFFIC["query"], params)
    assert reading == {"mismatched_rows": 2, "rows": 2}
    same = control_refreshed.stale_reading(
        reference, whole, whole, TRAFFIC["query"], params)
    assert same["mismatched_rows"] == 0


# -- the timed path broken underneath ---------------------------------------


def test_the_rehearsal_reads_every_landed_file_through_both_indexes():
    result = bench_run.run_cell(CELL, 2 ** 31 + 11, 1.0, False, **TINY)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["unindexed_queries"] == [0, 0]
    assert compared["appended_files_unread"] == [0, 0]
    assert compared["answers_compared"][0] == result["attempted"] + 2
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}


# The chip's lanes at 1/25 scale: the index scans over the device
# threshold and the appended ones under it, the broadcast threshold
# between the appended lines' estimate (1,923 x 36 B) and the orders
# index's (60,000 x 24 B); the appended lines that pass the filter repeat
# an order key there too, so the third branch declines to the counting
# join as it does at SF 3.
AS_ON_THE_CHIP = dict(TINY, scale=0.04, conf_overrides={
    "spark.hyperspace.distribution.enabled": "false",
    "spark.hyperspace.execution.min.device.rows": "40000",
    "spark.hyperspace.broadcast.threshold": "200000"})


def test_the_mixs_lanes_are_the_chips_and_are_held():
    """Steered to the chip's lanes, every query is on the lanes the mix
    wrote down from the chip (`lanes`, all seven keys held); with the
    broadcast joins switched off it is right and off them."""
    result = bench_run.run_cell(CELL, 15, 1.0, False, **AS_ON_THE_CHIP)
    assert result["correct"] is True, result["compared"]
    assert set(TRAFFIC["lanes"]) == {
        "index_scan", "appended_scan", "joins", "join", "fusion",
        "broadcast", "shuffles"}
    off = dict(AS_ON_THE_CHIP, conf_overrides=dict(
        AS_ON_THE_CHIP["conf_overrides"],
        **{"spark.hyperspace.broadcast.threshold": "-1"}))
    result = bench_run.run_cell(CELL, 15, 1.0, False, **off)
    compared, n = result["compared"], result["attempted"] + 2
    assert compared["mismatched_rows"] == [0, 0]
    assert compared["unindexed_queries"] == [0, 0]
    assert compared["off_lane_queries"] == [n, 0]
    assert result["correct"] is False


def test_appended_rows_left_out_of_the_answer_is_not_correct(monkeypatch):
    """The rule takes each index as if the lake had not moved (the delta
    comes back empty): right plan for the base, a stale answer."""
    from hyperspace_tpu.plan.rules.base import Rule

    monkeypatch.setattr(Rule, "hybrid_delta",
                        lambda self, entry, scan: ([], []))
    # at 1/20 scale both of the answer's rows gain lines from the sets
    result = bench_run.run_cell(CELL, 12, 1.0, False,
                                **dict(TINY, scale=0.05))
    assert result["correct"] is False
    compared, n = result["compared"], result["attempted"] + 2
    assert compared["wrong_answers"] == [n, 0]
    assert compared["mismatched_rows"] == [2 * n, 0]
    assert compared["appended_files_unread"] == [16 * n, 0]
    assert compared["unindexed_queries"] == [n, 0]


def test_sides_served_from_the_source_files_are_not_correct():
    """With upstream's switch off the rule declines and every query
    falls to the source files: the answers are right, the cell is not
    the cell it is named for."""
    tiny = dict(TINY, conf_overrides=dict(TINY["conf_overrides"], **{
        "spark.hyperspace.index.hybridscan.enabled": "false"}))
    result = bench_run.run_cell(CELL, 13, 1.0, False, **tiny)
    compared, n = result["compared"], result["attempted"] + 2
    assert compared["mismatched_rows"] == [0, 0]
    assert compared["wrong_answers"] == [0, 0]
    assert compared["unindexed_queries"] == [n, 0]
    assert result["correct"] is False


def test_the_ops_counts_on_records_as_a_query_leaves_them():
    """`unindexed` and `appended_read` over hand-made records: a side's
    source scan that reads the whole table, a side with no source scan,
    and an index the rule named that no scan read."""
    op_module = plug("ops", "q12_hybrid")
    op = object.__new__(op_module.Op)
    op.spec = {"tables": ["lineitem", "orders"],
               "indexes": ["li_q12", "ord_q12"]}
    op.dep = type("Dep", (), {"config": CONFIG})()
    op.landed = {"lineitem": 8, "orders": 8}
    li, ords = "/w/wh/indexes/li_q12/v__=0", "/w/wh/indexes/ord_q12/v__=0"

    def rec(scans, rule=((("li_q12"), li, 8), ("ord_q12", ords, 8))):
        return {"rule": [{"name": n, "root": r, "appended_files": a,
                          "deleted_files": 0, "side": "x"}
                         for n, r, a in rule],
                "scans": [{"roots": [r], "lane": "device", "files": f}
                          for r, f in scans]}

    good = rec([(li, 64), (ords, 64), ("/w/lineitem", 8), ("/w/orders", 8),
                ("/w/lineitem", 8)])
    assert not op.unindexed(good)
    assert op.appended_read(good) == {"lineitem": 8, "orders": 8}
    whole_table = rec([(li, 64), (ords, 64), ("/w/lineitem", 24),
                       ("/w/orders", 8)])
    assert op.unindexed(whole_table)
    no_source = rec([(li, 64), (ords, 64), ("/w/lineitem", 8)])
    assert not op.unindexed(no_source)  # its files are counted unread
    assert op.appended_read(no_source) == {"lineitem": 8, "orders": 0}
    unread_index = rec([(li, 64), ("/w/lineitem", 8), ("/w/orders", 8)])
    assert op.unindexed(unread_index)
    assert op.unindexed(rec([(li, 64), (ords, 64)], rule=()))


# -- the readers ---------------------------------------------------------------


def test_the_span_readers_read_a_traced_rehearsal():
    """`hybrid_delta_ms` and `appended_scan_ms` come out of the spans
    the program writes into the rehearsal's own trace; the two device
    readers find no device plane on the CPU and stay out of the line."""
    result = bench_run.run_cell(CELL, 14, 1.0, True, **TINY)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert metrics["hybrid_delta_ms"]["value"] > 0
    assert metrics["appended_scan_ms"]["value"] > 0
    assert metrics["optimize_ms"]["value"] > metrics["hybrid_delta_ms"]["value"]
    assert "broadcast_join_device_ms" not in metrics
    assert "broadcast_join_roofline" not in metrics
    listed = {m["name"] for m in FOUND["per_layer"]}
    assert set(metrics) <= listed and {
        "hybrid_delta_ms", "appended_scan_ms", "broadcast_join_device_ms",
        "broadcast_join_roofline"} <= listed


def _stat(stat_id: int, value) -> bytes:
    return _field(1, stat_id) + (_field(4, value) if isinstance(value, int)
                                 else _field(5, value))


def _plane(name: str, lines: dict, stat_names=("tf_op", "appended")) -> bytes:
    """{line: [(event, start_s, dur_s, tf_op or None, {stat: int})]} as
    one XPlane: `tf_op` on the event's METADATA (a device op's scope
    path), the other stats on the event (a host span's arguments)."""
    stat_id = {n: i + 1 for i, n in enumerate(stat_names)}
    ids, body = {}, _field(2, name)
    for line, events in lines.items():
        msg = _field(2, line) + _field(3, 0)
        for ev, start, dur, tf_op, stats in events:
            mid = ids.setdefault((ev, tf_op), len(ids) + 1)
            event = (_field(1, mid) + _field(2, round(start * 1e12))
                     + _field(3, round(dur * 1e12)))
            for k, v in stats.items():
                event += _field(4, _stat(stat_id[k], v))
            msg += _field(4, event)
        body += _field(3, msg)
    for (ev, tf_op), mid in ids.items():
        meta = _field(1, mid) + _field(2, ev)
        if tf_op:
            meta += _field(5, _stat(stat_id["tf_op"], tf_op))
        body += _field(4, _field(1, mid) + _field(2, meta))
    for n, i in stat_id.items():
        body += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    return body


SCOPED = "jit(_run)/hs.join.broadcast/jit(_broadcast_probe)/gather:"
TRACED = "made_here"


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Window 0..10 s; two whole queries (1..4, 5..8) and one cut off.
    A query's device ops: 0.5 s of the stage's predicate, 0.3 + 0.1 s
    under the broadcast probe's scope. Its host spans: two index scans,
    two appended scans of 0.02 s, two `hs.plan.hybrid` of 0.01 s."""
    host, ops = [("bench.window", 0.0, 10.0, None, {})], []
    for q in (1.0, 5.0, 9.0):
        host += [("bench.collect", q, 3.0 if q < 9 else 2.0, None, {}),
                 ("hs.plan.hybrid", q + 0.01, 0.01, None, {}),
                 ("hs.plan.hybrid", q + 0.03, 0.01, None, {}),
                 ("hs.op.Scan", q + 0.1, 0.3, None, {}),
                 ("hs.op.Scan", q + 0.5, 0.02, None, {"appended": 8}),
                 ("hs.op.Scan", q + 0.6, 0.02, None, {"appended": 8})]
        ops += [("%fusion.1 = fusion(...)", q + 1.0, 0.5,
                 "jit(_run)/hs.predicate/lt:", {}),
                ("%gather.2 = gather(...)", q + 1.5, 0.3, SCOPED, {}),
                ("%fusion.3 = fusion(...)", q + 1.8, 0.1, SCOPED, {})]
    d = tmp_path / ".bench_work" / TRACED / "seed1" / "trace" / "plugins" \
        / "profile" / "t"
    os.makedirs(d)
    (d / "x.xplane.pb").write_bytes(
        _field(1, _plane("/host:CPU", {"thread": host}))
        + _field(1, _plane("/device:TPU:0", {"XLA Ops": ops})))
    monkeypatch.setattr(program_spans, "ROOT", str(tmp_path))
    trace = trace_reduce.reduce(trace_reduce.find_xplane(os.path.join(
        str(tmp_path), ".bench_work", TRACED, "seed1", "trace")))
    broadcast = [  # as `ops/q12_hybrid.of_metrics` keeps them
        {"path": "fused", "lane": "device", "probe_rows": 1_000_000,
         "build_rows": 2_000},
        {"path": "counting", "lane": "device", "probe_rows": 250_000,
         "build_rows": 40},
        {"path": "direct-address", "lane": "host", "probe_rows": 40,
         "build_rows": 2_000}]
    return {"trace": trace, "cell": {"name": TRACED}, "traffic": TRAFFIC,
            "records": [{"broadcast": broadcast}] * 2,
            "device_kind": "TPU v5 lite"}


def test_the_span_readers_on_a_trace_made_here(traced):
    assert plug("metrics", "hybrid_delta_ms").compute(traced) == \
        pytest.approx(20.0)
    # the scans that say `appended`, and not the index scans' 300 ms
    assert plug("metrics", "appended_scan_ms").compute(traced) == \
        pytest.approx(40.0)
    spans = program_spans.load(traced)["spans"]
    assert sorted({s[4].get("appended") for s in spans
                   if s[0] == "hs.op.Scan"}, key=str) == [8, None]


def test_the_broadcast_readers_on_a_trace_made_here(traced):
    device_ms = plug("metrics", "broadcast_join_device_ms")
    share = plug("metrics", "broadcast_join_roofline")
    # the two ops under the scope, not the predicate's
    assert device_ms.compute(traced) == pytest.approx(400.0)
    # only the join that ran as a probe on the device is in the bytes
    n_bytes = share.least_bytes(traced["records"][0]["broadcast"])
    assert n_bytes == 8 * (1_000_000 + 2_000)
    want = 100.0 * roofline.least_seconds(n_bytes, "TPU v5 lite") / 0.4
    got = share.compute(traced)
    assert got == pytest.approx(want) and 0 < got < 100


def test_the_new_readers_say_none_where_there_is_nothing_to_read(traced):
    readers = {n: plug("metrics", n) for n in (
        "hybrid_delta_ms", "appended_scan_ms", "broadcast_join_device_ms",
        "broadcast_join_roofline")}
    share = readers["broadcast_join_roofline"]
    # a program that says nothing of its broadcast joins (the parent)
    silent = dict(traced, records=[{}, {}])
    assert share.compute(silent) is None
    # every broadcast join declined or ran on the host lane
    none_on_device = dict(traced, records=[{"broadcast": [
        b for b in traced["records"][0]["broadcast"]
        if b["path"] != "fused"]}])
    assert share.compute(none_on_device) is None
    # an untraced run, and a traced one whose trace is not found
    for run in (dict(traced, trace=None),
                dict(traced, cell={"name": "no_such_cell"})):
        assert {n: r.compute(run) for n, r in readers.items()} == \
            dict.fromkeys(readers)

