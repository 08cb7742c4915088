"""The cell `tpcds_sf10_q17`'s files on the CPU: the dataset `tpcds`
(the specification's SF 10 row counts and every column of its schema;
keys, dates and strings that follow a row's number and not the seed, so
that every count the program compiles for is the same for every seed),
the op `tpcds_q17` against its plain reference at 1/1000 of the
configuration's scale with the lanes SF 10 takes (the store_sales index
on the device, the store_returns index on the host), three planted
faults that turn `correct` false (one pair left out of the aggregate's
input; stddev_samp finished in float32; the cov quotients in float32),
the two readings the cov limit lies between, and the new readers on what
a run leaves.

`test_run.py`'s parametrised cases (the rehearsal traced and untraced,
the altered answer, the control of three seeds) take the cell from the
manifest like any other."""

import os

import numpy as np
import pytest

from conftest import ROOT, plug
from test_run import TINY

import run as bench_run

CELL = "tpcds_sf10_q17"
MANIFEST = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FOUND = bench_run.resolve(MANIFEST, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
# SF 0.01: 28,801 store_sales rows; a threshold of 10,000 puts the
# store_sales index and catalog_sales on the device and the 2,875
# store_returns rows on the host, as SF 10 and the default threshold do
SF10_LANES = dict(TINY, conf_overrides={
    "spark.hyperspace.execution.min.device.rows": "10000",
    "spark.hyperspace.distribution.enabled": "false"})


def _key_rows(table: dict, columns) -> np.ndarray:
    return np.stack([table[c] for c in columns], axis=1)


def _sorted_rows(rows: np.ndarray) -> np.ndarray:
    return rows[np.lexsort(rows.T[::-1])]


# -- the dataset --------------------------------------------------------------


def test_sf10_row_counts_are_the_specifications():
    ds = plug("datasets", "tpcds")
    assert {t: ds.row_count(t, 10.0) for t in ds.ROWS_SF10} == {
        "store_sales": 28_800_991, "store_returns": 2_875_432,
        "catalog_sales": 14_401_261, "item": 102_000, "store": 102,
        "customer": 500_000}
    assert ds.row_count("date_dim", 0.01) == ds.row_count("date_dim", 10.0) \
        == 73_049
    assert len(ds.VOCABULARY["i_item_id"]) == 51_000
    assert all(len(s) == 16 for s in ds.VOCABULARY["i_item_id"][:: 997])
    assert max(map(len, ds.VOCABULARY["i_item_desc"])) <= 200
    assert all(len(s) == 2 for s in ds.VOCABULARY["s_state"])
    for pool in ds.VOCABULARY.values():  # a code's order is its string's
        assert pool == sorted(pool) and len(set(pool)) == len(pool)


def test_the_lake_holds_every_column_of_the_specification(tmp_path):
    """Each table's files hold its clause 2 columns in their order, with
    no NULL: surrogate keys int64, the specification's dates as dates,
    strings as dictionaries; the columns `make` hands the reference are
    written as made."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    ds = plug("datasets", "tpcds")
    widths = {"store_sales": 23, "store_returns": 20, "catalog_sales": 34,
              "date_dim": 28, "store": 29, "item": 22}
    tables = ds.make(CONFIG, 2 ** 31 + 5, 0.01)
    assert set(tables) == set(widths)
    for name, made in tables.items():
        ds.write_parquet(made, str(tmp_path / name), 3)
        got = pq.read_table(str(tmp_path / name))
        assert got.column_names == list(ds.SCHEMA[name])
        assert len(got.column_names) == widths[name]
        assert got.num_rows == len(next(iter(made.values())))
        for c in got.column_names:
            column = got.column(c)
            assert column.null_count == 0, (name, c)
            if c.endswith("_sk"):
                assert column.type == pa.int64(), (name, c)
            if c in ds._DATE32:
                assert column.type == pa.date32(), (name, c)
            if c in ds.VOCABULARY:
                assert pa.types.is_dictionary(column.type), (name, c)
        for c, data in made.items():
            if c not in ds.VOCABULARY and c not in ds._DATE32:
                assert np.array_equal(got.column(c).to_numpy(), data), c


@pytest.mark.parametrize("seeds", [(1, 2 ** 31 + 7)])
def test_keys_follow_the_row_number_and_not_the_seed(seeds):
    ds = plug("datasets", "tpcds")
    a, b = (ds.make(CONFIG, s, 0.01) for s in seeds)
    keys = {"store_sales": ("ss_customer_sk", "ss_item_sk",
                            "ss_ticket_number", "ss_store_sk",
                            "ss_sold_date_sk"),
            "store_returns": ("sr_customer_sk", "sr_item_sk",
                              "sr_ticket_number", "sr_returned_date_sk"),
            "catalog_sales": ("cs_bill_customer_sk", "cs_item_sk",
                              "cs_sold_date_sk")}
    for table, cols in keys.items():
        ra, rb = _key_rows(a[table], cols), _key_rows(b[table], cols)
        assert np.array_equal(_sorted_rows(ra), _sorted_rows(rb)), table
        assert not np.array_equal(ra, rb), table  # the seed orders rows
    for table in ("item", "store", "date_dim"):
        for c in a[table]:
            assert np.array_equal(a[table][c], b[table][c]), (table, c)
    # the seed sets the payload: a line's quantity, by its key
    qty = []
    for t in (a, b):
        ss = t["store_sales"]
        order = np.lexsort((ss["ss_item_sk"], ss["ss_ticket_number"]))
        qty.append(ss["ss_quantity"][order])
    assert not np.array_equal(*qty)
    ss = a["store_sales"]
    # the specification's key of store_sales is unique
    assert len(np.unique(_key_rows(ss, ("ss_item_sk", "ss_ticket_number")),
                         axis=0)) == len(ss["ss_item_sk"])
    # every return is one sale line's (customer, item, ticket), once
    sale = {tuple(r) for r in _key_rows(ss, keys["store_sales"][:3]).tolist()}
    ret = _key_rows(a["store_returns"], keys["store_returns"][:3]).tolist()
    assert len({tuple(r) for r in ret}) == len(ret)
    assert all(tuple(r) in sale for r in ret)


@pytest.mark.parametrize("seeds", [(3, 2 ** 31 + 11)])
def test_the_pairs_each_join_places_follow_the_keys(seeds):
    """The rows each date predicate keeps, the pairs each join places and
    the groups of the answer are the same for two seeds; the averages
    are not."""
    ds = plug("datasets", "tpcds")
    ref = plug("reference", "tpcds_q17")
    op = plug("ops", "tpcds_q17").Op
    params = op.control_params(TRAFFIC["query"], ds, 0.01, None)
    answers, pairs = [], []
    for seed in seeds:
        t = ds.make(CONFIG, seed, 0.01)
        ss, sr, cs = t["store_sales"], t["store_returns"], t["catalog_sales"]
        li, ri = ref._join(
            [ss[c] for c in ("ss_customer_sk", "ss_item_sk",
                             "ss_ticket_number")],
            [sr[c] for c in ("sr_customer_sk", "sr_item_sk",
                             "sr_ticket_number")])
        li2, _ = ref._join([sr[c][ri] for c in ("sr_customer_sk",
                                                "sr_item_sk")],
                           [cs[c] for c in ("cs_bill_customer_sk",
                                            "cs_item_sk")])
        pairs.append((len(li), len(li2)))
        answers.append(ref.Reference(t).answer(TRAFFIC["query"], params))
    assert pairs[0] == pairs[1] and pairs[0][0] == ds.row_count(
        "store_returns", 0.01)
    assert pairs[0][1] >= len(ds.catalog_plan(0.01)[0])
    a, b = answers
    for c in ("i_item_id", "i_item_desc", "s_state",
              "store_sales_quantitycount", "catalog_sales_quantitycount"):
        assert np.array_equal(a[c], b[c]), c
    assert len(a["i_item_id"]) > 20
    assert not np.array_equal(a["store_sales_quantityave"],
                              b["store_sales_quantityave"])
    # groups of two and more rows: stddev_samp is computed, not only NULL
    assert np.isfinite(a["catalog_sales_quantitystdev"]).sum() > 5


# -- the system against the reference ----------------------------------------


def test_q17_matches_the_reference_on_sf10s_lanes():
    result = bench_run.run_cell(CELL, 2 ** 31 + 3, 1.0, False, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["answers_compared"][0] == compared["answers_compared"][1]
    assert all(got == limit for name, (got, limit) in compared.items()
               if name not in ("answers_compared", "cov_rel_err"))
    # the CPU's float64 is IEEE's: the quotients are exact here
    assert compared["cov_rel_err"][0] == 0.0


def test_q17_records_every_scan_and_join_lane(monkeypatch):
    records = []
    driver = plug("drivers", "closed_loop").Driver
    real = driver.check

    def check(self):
        records.extend(self.warm_records + self.records)
        return real(self)

    monkeypatch.setattr(driver, "check", check)
    result = bench_run.run_cell(CELL, 13, 1.0, False, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    lanes = records[-1]["q17"]
    scans = {(s["relation"], s["index"]): s["lane"] for s in lanes["scans"]}
    assert scans[("idx_ss_ret", True)] == "device"
    assert scans[("idx_sr_ret", True)] == "host"
    assert scans[("catalog_sales", False)] == "device"
    (bucketed,) = [j for j in lanes["joins"] if j["buckets"]]
    assert bucketed == dict(bucketed, op="SortMergeJoin", lane="device",
                            buckets=64, match="hashed", keys=3,
                            left_rows=28_801, right_rows=2_875, rows=2_875)
    assert all(j["lane"] for j in lanes["joins"])
    assert lanes["shuffles"] == []
    assert all(r["hashed_fallbacks"] == 0 for r in records)


def test_one_pair_left_out_is_not_correct(monkeypatch):
    """The aggregate's input loses its first row (one pair of the last
    join): at this scale every group is in the answer, so one count and
    its averages change."""
    from hyperspace_tpu.ops import aggregate

    real = aggregate.group_aggregate

    def dropped(batch, *a, **kw):
        import jax.numpy as jnp

        if batch.num_rows > 1:
            batch = batch.take(jnp.arange(1, batch.num_rows))
        return real(batch, *a, **kw)

    monkeypatch.setattr(aggregate, "group_aggregate", dropped)
    result = bench_run.run_cell(CELL, 17, 1.0, False, **SF10_LANES)
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0
    assert result["compared"]["mismatched_rows"][0] > 0


def test_stddev_finished_in_float32_is_not_correct(monkeypatch):
    from hyperspace_tpu.ops import aggregate

    real = aggregate._finish_exact

    def in_float32(func, n, total, squares, amax):
        out = real(func, n, total, squares, amax)
        if func == "stddev" and out is not None:
            num = (n * squares - total * total).astype(np.float32)
            den = np.maximum(n * (n - 1), 1).astype(np.float32)
            out = np.sqrt(num / den).astype(np.float64)
        return out

    monkeypatch.setattr(aggregate, "_finish_exact", in_float32)
    result = bench_run.run_cell(CELL, 19, 1.0, False, **SF10_LANES)
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0


def _float32_cov(answer: dict) -> dict:
    """An answer whose `*_cov` quotients are divided in float32."""
    out = dict(answer)
    for name, (values, valid) in answer.items():
        if name.endswith("cov"):
            stdev, ave = (answer[name[:-3] + k][0] for k in ("stdev", "ave"))
            out[name] = ((stdev.astype(np.float32) / ave.astype(np.float32))
                         .astype(np.float64), valid)
    return out


def test_the_cov_limit_lies_between_its_two_readings():
    """The reference's cov divided in float32 is out by more than the
    limit; one a few ulps of an f32 pair (2^-44) off is within it."""
    ds = plug("datasets", "tpcds")
    ref = plug("reference", "tpcds_q17")
    op = plug("ops", "tpcds_q17")
    params = op.Op.control_params(TRAFFIC["query"], ds, 0.01, None)
    answer = op.reference_columns(ref.Reference(
        ds.make(CONFIG, 2 ** 31 + 9, 0.01)).answer(TRAFFIC["query"], params))
    assert sum(valid.sum() for name, (_, valid) in answer.items()
               if name.endswith("cov")) > 5
    assert op.cov_rel_err(answer, answer, ds.VOCABULARY) == 0.0
    assert op.cov_rel_err(_float32_cov(answer), answer,
                          ds.VOCABULARY) > 2 ** 6 * op.COV_REL_ERR
    near = {name: ((values * (1 + 2.0 ** -44), valid)
                   if name.endswith("cov") else (values, valid))
            for name, (values, valid) in answer.items()}
    assert 0 < op.cov_rel_err(near, answer, ds.VOCABULARY) < \
        op.COV_REL_ERR / 2 ** 6


def test_cov_divided_in_float32_is_not_correct(monkeypatch):
    """Every other column exact, the quotients in float32: only
    `cov_rel_err` turns `correct` false."""
    op = plug("ops", "tpcds_q17")
    real = op.arrow_columns
    monkeypatch.setattr(op, "arrow_columns",
                        lambda table: _float32_cov(real(table)))
    result = bench_run.run_cell(CELL, 31, 1.0, False, **SF10_LANES)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["cov_rel_err"][0] > compared["cov_rel_err"][1]
    assert compared["mismatched_rows"][0] == 0


# -- the readers ----------------------------------------------------------------


def test_the_roofline_counts_the_counting_joins_bytes():
    reader = plug("metrics", "q17_join_roofline")
    joins = [{"match": "hashed", "keys": 3, "left_rows": 100,
              "right_rows": 10, "rows": 10},
             {"match": "hashed", "keys": 2, "left_rows": 10,
              "right_rows": 50, "rows": 12},
             {"match": None, "keys": None, "left_rows": 12,
              "right_rows": 90, "rows": 3},  # a broadcast probe: not its
             {"match": "hashed", "keys": 2, "left_rows": None,
              "right_rows": None, "rows": 5}]  # rows not recorded
    assert reader.least_bytes(joins) == (110 * 24 + 10 * 8) + (60 * 16
                                                               + 12 * 8)
    run = {"records": [{"q17": {"joins": joins}}], "trace": None,
           "traffic": TRAFFIC, "device_kind": "TPU v5 lite"}
    assert reader.compute(run) is None  # no device plane: nothing to read


def test_the_span_readers_read_a_traced_rehearsal():
    result = bench_run.run_cell(CELL, 23, 1.0, True, **TINY)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert metrics["source_scan_ms"]["value"] > 0
    # the CPU has no device plane: the device readers leave theirs out
    for name in ("join_device_ms", "q17_join_roofline",
                 "aggregate_device_ms", "broadcast_join_device_ms",
                 "broadcast_join_roofline"):
        assert name not in metrics


def test_source_scan_ms_is_none_without_the_spans_argument(monkeypatch):
    """A program whose `hs.op.Scan` spans carry no `source` (the parent
    of this cell) leaves the metric out and raises nothing."""
    from lib import program_spans

    reader = plug("metrics", "source_scan_ms")
    spans = [("bench.collect", 0, 0.0, 1.0, {}),
             ("hs.op.Scan", 0, 0.1, 0.2, {"lane": "device"})]
    monkeypatch.setattr(program_spans, "load",
                        lambda run: {"spans": spans, "ops": []})
    monkeypatch.setattr(program_spans, "_whole",
                        lambda run, name: [(0.0, 1.0)])
    assert reader.compute({"traffic": TRAFFIC, "trace": {}}) is None
    spans[1] = ("hs.op.Scan", 0, 0.1, 0.2, {"source": "catalog_sales"})
    assert reader.compute({"traffic": TRAFFIC, "trace": {}}) == \
        pytest.approx(200.0)
