"""The cell `tpch_sf10_q6_skip`'s files on the CPU: the dataset
`tpch_by_month` (one Parquet file per month of the line's order date,
the rows `tpch` makes to the bit but for the two hashed filter columns;
a year's surviving files and kept lines the same for every seed), the reference's exact revenue against an independent
decimal computation, the op `tpch_q6_skip` against its reference at
1/1000 of the configuration's scale with the lanes SF 10 takes (each
month's file sketched on the host, a year's survivors scanned on the
device), planted faults that turn `correct` false (a revenue a cent
and more off; answers summed in float32; the scan on the host lane),
the two readings the cent lies between, and the new readers on what a run leaves.

`test_run.py`'s parametrised cases (the rehearsal traced and untraced,
the control of three seeds) take the cell from the manifest like any
other."""

import datetime
import os
from decimal import Decimal
from fractions import Fraction

import numpy as np
import pytest

from conftest import ROOT, plug
from test_run import TINY

import run as bench_run

CELL = "tpch_sf10_q6_skip"
MANIFEST = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FOUND = bench_run.resolve(MANIFEST, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
QUERY = TRAFFIC["query"]
# SF 0.01: months of about 750 lines and years of about 12,000 surviving
# lines; a threshold of 4,194 (the default's 1/1000) sketches each file
# on the host and scans a year's survivors on the device, as SF 10 and
# the default threshold do
SF10_LANES = dict(TINY, conf_overrides={
    "spark.hyperspace.execution.min.device.rows": "4194",
    "spark.hyperspace.distribution.enabled": "false"})
SEEDS = (5, 2 ** 31 + 13)


def _days(d: datetime.date) -> int:
    return (d - datetime.date(1970, 1, 1)).days


# -- the dataset --------------------------------------------------------------


def test_the_lake_lands_one_file_per_order_month(tmp_path):
    """80 files, 1992-01 to 1998-08, each holding the lines of one month
    of their order's date, named by it; together the rows `tpch` makes
    for the seed, to the bit, but for `l_discount` and `l_quantity`,
    which are the fixed hashes of the line, in the specification's
    domains."""
    import pyarrow.parquet as pq

    ds = plug("datasets", "tpch_by_month")
    tpch = plug("datasets", "tpch")
    made = ds.make(CONFIG, SEEDS[1], 0.01)["lineitem"]
    ds.write_parquet(made, str(tmp_path / "lineitem"), 80)
    names = sorted(os.listdir(tmp_path / "lineitem"))
    assert len(names) == 80
    assert (names[0], names[-1]) == ("part-1992-01.parquet",
                                     "part-1998-08.parquet")
    total = 0
    for name in names:
        got = pq.read_table(str(tmp_path / "lineitem" / name))
        assert got.column_names == list(tpch.LINEITEM_COLUMNS)
        months = ds.month_of(ds.order_date(got.column("l_orderkey")
                                           .to_numpy()))
        assert len(set(months.tolist())) == 1
        assert ds.file_name(int(months[0])) == name
        total += got.num_rows
    plain = tpch.make_tables(0.01, SEEDS[1], edge_every=0)["lineitem"]
    assert total == len(plain["l_orderkey"])
    order = np.lexsort((made["l_linenumber"], made["l_orderkey"]))
    plain_order = np.lexsort((plain["l_linenumber"], plain["l_orderkey"]))
    hashed = ds.filter_columns(plain["l_orderkey"], plain["l_linenumber"])
    for c in tpch.LINEITEM_COLUMNS:
        want = hashed.get(c, plain[c])
        assert np.array_equal(made[c][order], want[plain_order]), c
    assert set(np.unique(made["l_discount"] * 100).round()) == set(range(11))
    assert set(np.unique(made["l_quantity"])) == set(range(1, 51))
    with pytest.raises(ValueError, match="80 months"):
        ds.write_parquet(made, str(tmp_path / "other"), 64)


def test_a_years_survivors_are_the_same_files_for_every_seed():
    """The month a line lands in and its ship date follow its key, not
    the seed: each year meets the same 16 files (the four months before
    it and its twelve) for every seed."""
    ds = plug("datasets", "tpch_by_month")
    op = plug("ops", "tpch_q6_skip")
    kept = []
    for seed in SEEDS:
        li = ds.make(CONFIG, seed, 0.01)["lineitem"]
        ranges = op.file_ship_ranges(li, ds)
        bounds = ds.file_months(li)
        kept.append({y: [ds.file_name(m) for (_, _, m), least, most in zip(
                        bounds, *ranges)
                         if least < _days(datetime.date(y + 1, 1, 1))
                         and most >= _days(datetime.date(y, 1, 1))]
                     for y in QUERY["years"]})
        assert {y: op.expected_files(ranges, y) for y in QUERY["years"]} \
            == {y: 16 for y in QUERY["years"]}
    assert kept[0] == kept[1]
    assert kept[0][1995][0] == "part-1994-09.parquet"
    assert kept[0][1995][-1] == "part-1995-12.parquet"


def test_a_years_kept_lines_are_the_same_for_every_seed():
    """Q6's predicate reads only hashes of the line (its dates, discount
    and quantity), so a year keeps the same lines for every seed, and
    the program compiles the same survivor counts; the prices, which
    the revenue sums, still follow the seed."""
    ds = plug("datasets", "tpch_by_month")
    ref = plug("reference", "tpch_q6_skip")
    kept, prices = [], []
    for seed in SEEDS:
        li = ds.make(CONFIG, seed, 0.01)["lineitem"]
        line_id = li["l_orderkey"] * 8 + li["l_linenumber"]
        kept.append({y: np.sort(line_id[ref.kept(li, QUERY, y)])
                     for y in QUERY["years"]})
        prices.append(li["l_extendedprice"][np.argsort(line_id)])
    for y in QUERY["years"]:
        assert len(kept[0][y]) > 500
        assert np.array_equal(kept[0][y], kept[1][y]), y
    assert not np.array_equal(prices[0], prices[1])


# -- the reference ------------------------------------------------------------


@pytest.mark.parametrize("year", [1993, 1997])
def test_the_reference_is_the_exact_decimal_revenue(year):
    """Against pandas' own filter and Python decimals (each float's
    shortest repr is its two-place decimal)."""
    import pandas as pd

    ds = plug("datasets", "tpch_by_month")
    ref = plug("reference", "tpch_q6_skip")
    li = ds.make(CONFIG, SEEDS[0], 0.01)["lineitem"]
    df = pd.DataFrame({c: li[c] for c in ("l_shipdate", "l_discount",
                                          "l_quantity", "l_extendedprice")})
    ship = pd.to_datetime(df.l_shipdate, unit="D")
    m = df[(ship >= pd.Timestamp(year, 1, 1))
           & (ship < pd.Timestamp(year + 1, 1, 1))
           & (df.l_discount >= 0.05) & (df.l_discount <= 0.07)
           & (df.l_quantity < 24)]
    want = sum(Decimal(repr(p)) * Decimal(repr(d))
               for p, d in zip(m.l_extendedprice, m.l_discount))
    got = ref.Reference({"lineitem": li}).answer(QUERY, {"year": year})
    assert got["rows"].tolist() == [len(m)] and len(m) > 500
    assert Decimal(int(got["revenue_e4"][0])) / 10 ** 4 == want


def _in_float32(lineitem: dict, mask: np.ndarray) -> np.float32:
    """sum(l_extendedprice x l_discount) over `mask` in float32: each
    product rounded to it, then added in row order."""
    products = (lineitem["l_extendedprice"][mask].astype(np.float32)
                * lineitem["l_discount"][mask].astype(np.float32))
    return np.cumsum(products, dtype=np.float32)[-1]


def test_the_cent_lies_between_its_two_readings():
    """The reference's own sum in float32 (the next precision down: the
    products rounded to it and added one by one) is off by more than a
    cent in every year; a sum 2^-44 off (a few ulps of the chip's f32
    pair) of SF 10's revenue is well within it."""
    ds = plug("datasets", "tpch_by_month")
    ref = plug("reference", "tpch_q6_skip")
    op = plug("ops", "tpch_q6_skip")
    li = ds.make(CONFIG, SEEDS[0], 0.01)["lineitem"]
    for year in QUERY["years"]:
        mask = ref.kept(li, QUERY, year)
        exact = Fraction(ref.revenue_e4(li, mask), 10 ** 4)
        assert abs(Fraction(float(_in_float32(li, mask))) - exact) > op.CENT
    # SF 10's revenue is about 4e9 dollars a year
    assert Fraction(4 * 10 ** 9) * Fraction(1, 2 ** 44) < op.CENT / 10


# -- the system against the reference ----------------------------------------


def test_q6_matches_the_reference_on_sf10s_lanes(monkeypatch):
    records = []
    driver = plug("drivers", "closed_loop").Driver
    real = driver.check

    def check(self):
        records.extend(self.warm_records + self.records)
        return real(self)

    monkeypatch.setattr(driver, "check", check)
    result = bench_run.run_cell(CELL, 2 ** 31 + 3, 1.0, False, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert all(got == limit for name, (got, limit) in compared.items()
               if name not in ("answers_compared", "revenue_miss"))
    assert compared["revenue_miss"][0] < 1e-6  # the CPU's float64 is IEEE's
    assert {r["params"]["year"] for r in records} == set(QUERY["years"])
    first = records[0]
    assert first["served"] == ["source"]
    assert first["skipping_index"] == ["li_ship_skip"]
    assert first["files_considered"] == 80 and first["files_kept"] == 16
    assert first["scan_lane"] == ["device"]
    assert 10_000 < first["rows_scanned"] < 15_000
    assert 0 < first["bytes_scanned"] < first["bytes_pruned"]


def _shifted(monkeypatch, dollars: float):
    """Every answer's revenue moved by `dollars` as it reaches the
    client."""
    import pyarrow as pa

    op = plug("ops", "tpch_q6_skip")
    real = op.Op.settle

    def settle(self, rec, table):
        value = table.column(0)[0].as_py() + dollars
        return real(self, rec, table.set_column(
            0, "revenue", pa.array([value], pa.float64())))

    monkeypatch.setattr(op.Op, "settle", settle)


def test_a_revenue_a_cent_off_is_not_correct(monkeypatch):
    _shifted(monkeypatch, 0.0125)
    result = bench_run.run_cell(CELL, 17, 1.0, False, **SF10_LANES)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["wrong_answers"][0] == compared["answers_compared"][0]
    assert compared["revenue_miss"][0] > compared["revenue_miss"][1]
    assert compared["off_lane_scans"][0] == 0
    assert compared["wrong_files_kept"][0] == 0


def test_a_revenue_under_a_cent_off_is_correct(monkeypatch):
    _shifted(monkeypatch, 0.0075)
    result = bench_run.run_cell(CELL, 19, 1.0, False, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    assert 0.007 < result["compared"]["revenue_miss"][0] < 0.008


def test_answers_summed_in_float32_are_not_correct(monkeypatch):
    """Each answer replaced by the reference's own sum of the kept
    products in float32, the precision below the configuration's: the
    harness's comparison finds every answer more than a cent off."""
    import pyarrow as pa

    op = plug("ops", "tpch_q6_skip")
    ref = plug("reference", "tpch_q6_skip")
    real = op.Op.settle

    def settle(self, rec, table):
        li = self.dep.tables["lineitem"]
        mask = ref.kept(li, self.query, rec["params"]["year"])
        return real(self, rec, table.set_column(0, "revenue", pa.array(
            [float(_in_float32(li, mask))], pa.float64())))

    monkeypatch.setattr(op.Op, "settle", settle)
    result = bench_run.run_cell(CELL, 31, 1.0, False, **SF10_LANES)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["wrong_answers"][0] == compared["answers_compared"][0] > 0
    assert compared["revenue_miss"][0] > compared["revenue_miss"][1]
    for name in ("unskipped_queries", "off_lane_scans", "wrong_files_kept"):
        assert compared[name][0] == 0, name


def test_a_scan_off_the_device_lane_is_not_correct():
    """A threshold over a year's survivors puts the scan on the host:
    every answer right, every query off its lane."""
    result = bench_run.run_cell(CELL, 23, 1.0, False, **dict(
        TINY, conf_overrides={
            "spark.hyperspace.execution.min.device.rows": "1000000",
            "spark.hyperspace.distribution.enabled": "false"}))
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["off_lane_scans"][0] == \
        compared["answers_compared"][0] > 0
    assert compared["wrong_answers"][0] == 0


# -- the readers ----------------------------------------------------------------


def test_the_span_readers_read_a_traced_rehearsal():
    result = bench_run.run_cell(CELL, 29, 1.0, True, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert metrics["skipping_plan_ms"]["value"] > 0
    assert 15 < metrics["skipping_kept_pct"]["value"] < 25
    for name in ("source_scan_ms", "query_epilogue_ms", "to_arrow_ms"):
        assert metrics[name]["value"] > 0, name
    # the CPU has no device plane: the device readers leave theirs out
    for name in ("skip_stage_roofline", "stage_program_device_ms",
                 "aggregate_device_ms", "compact_device_ms",
                 "gather_device_ms"):
        assert name not in metrics


def _fake_trace(monkeypatch, spans, ops=()):
    from lib import program_spans

    monkeypatch.setattr(program_spans, "load",
                        lambda run: {"spans": spans, "ops": list(ops)})
    monkeypatch.setattr(program_spans, "_whole",
                        lambda run, name: [(0.0, 1.0), (1.0, 2.0)])


def test_skipping_plan_ms_sums_a_querys_spans(monkeypatch):
    reader = plug("metrics", "skipping_plan_ms")
    _fake_trace(monkeypatch, [("hs.plan.skipping", 0, 0.1, 0.002, {}),
                              ("hs.plan.skipping", 0, 0.2, 0.001, {}),
                              ("hs.plan.skipping", 0, 1.1, 0.004, {})])
    assert reader.compute({"trace": {}}) == pytest.approx(3.5)
    # a program without the span (the parent of this cell): left out
    _fake_trace(monkeypatch, [("hs.plan.optimize", 0, 0.1, 0.002, {})])
    assert reader.compute({"trace": {}}) is None


def test_skipping_kept_pct_reads_the_records():
    reader = plug("metrics", "skipping_kept_pct")
    records = [{"bytes_scanned": 20, "bytes_pruned": 80},
               {"bytes_scanned": 30, "bytes_pruned": 70},
               {"bytes_scanned": 25, "bytes_pruned": 75}]
    assert reader.compute({"records": records}) == pytest.approx(25.0)
    assert reader.compute({"records": [{"rows": 1}]}) is None


def test_skip_stage_roofline_counts_the_rows_scanned(monkeypatch):
    """Bytes of the four columns over the rows the scan read (not the
    table's), plus the one-row output, over the device time of the ops
    under the stage's scopes, each op counted once."""
    reader = plug("metrics", "skip_stage_roofline")
    ds = plug("datasets", "tpch_by_month")
    ops = [(0.1, 0.002, ("hs.stage", "hs.predicate"), "fusion"),
           (0.2, 0.001, ("hs.compact",), "sort"),
           (0.3, 0.001, (), "convert"),  # unscoped: not the stage's
           (1.1, 0.004, ("hs.aggregate",), "scatter")]
    _fake_trace(monkeypatch, [], ops)
    run = {"trace": {}, "traffic": TRAFFIC, "dataset": ds,
           "device_kind": "TPU v5 lite",
           "records": [{"rows_scanned": 10 ** 6}, {"rows_scanned": 2 * 10 ** 6},
                       {"rows_scanned": 3 * 10 ** 6}],
           "rows": {"lineitem": 60 * 10 ** 6}}
    want_bytes = 2 * 10 ** 6 * (4 + 8 + 8 + 8) + 2 * 8
    assert reader.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 0.0035)
    _fake_trace(monkeypatch, [], [(0.3, 0.001, (), "convert")])
    assert reader.compute(run) is None
