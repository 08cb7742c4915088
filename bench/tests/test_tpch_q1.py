"""The cell `tpch_sf10_q1`'s files on the CPU: the dataset
`tpch_by_month_flags` (`tpch_by_month`'s lake to the bit but for the
two flags, which follow clause 4.2.3; the four groups hold the same
lines for every seed), the reference's exact sums against an
independent decimal computation, the op `tpch_q1` against its reference
at 1/1000 of the configuration's scale with the lanes SF 10 takes (the
covering index scanned on the device), planted faults that turn
`correct` false (a lost group, a count off by one, products rounded to
float32, the scan on the host lane), the two readings `q1_rel_err` lies
between, and `q1_roofline` on what a run leaves.

`test_run.py`'s parametrised cases (the rehearsal traced and untraced,
an altered answer, the control of three seeds) take the cell from the
manifest like any other."""

import os
from decimal import Decimal

import numpy as np
import pytest

from conftest import ROOT, plug
from test_run import TINY

import run as bench_run

CELL = "tpch_sf10_q1"
MANIFEST = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FOUND = bench_run.resolve(MANIFEST, CELL)
CONFIG, TRAFFIC = FOUND["config"], FOUND["traffic"]
QUERY = TRAFFIC["query"]
PARAMS = {"delta": QUERY["delta"]}
# SF 0.01: 60,003 lines, all of them in the index; a threshold of 4,194
# (the default's 1/1000) scans the index on the device, as SF 10 and the
# default threshold do
SF10_LANES = dict(TINY, conf_overrides={
    "spark.hyperspace.execution.min.device.rows": "4194",
    "spark.hyperspace.distribution.enabled": "false"})
SEEDS = (5, 2 ** 31 + 13)


def _codes(name: str, *values) -> list:
    vocab = plug("datasets", "tpch_by_month_flags").VOCABULARY[name]
    return [vocab.index(v) for v in values]


# -- the dataset --------------------------------------------------------------


def test_the_flags_follow_clause_4_2_3():
    """'O' exactly where the ship date is after 1995-06-17; 'N' exactly
    where the receipt date is; R and A both present otherwise; every
    other column `tpch_by_month`'s to the bit."""
    ds = plug("datasets", "tpch_by_month_flags")
    by_month = plug("datasets", "tpch_by_month")
    li = ds.make(CONFIG, SEEDS[1], 0.01)["lineitem"]
    plain = by_month.make(CONFIG, SEEDS[1], 0.01)["lineitem"]
    assert list(li) == list(plain)
    for c in li:
        if c not in ("l_returnflag", "l_linestatus"):
            assert np.array_equal(li[c], plain[c]), c
    current = ds.CURRENTDATE
    o, f = _codes("l_linestatus", "O", "F")
    assert np.array_equal(li["l_linestatus"] == o, li["l_shipdate"] > current)
    assert set(np.unique(li["l_linestatus"])) == {o, f}
    n, r, a = _codes("l_returnflag", "N", "R", "A")
    returned = li["l_receiptdate"] <= current
    assert np.array_equal(li["l_returnflag"] == n, ~returned)
    assert set(np.unique(li["l_returnflag"][returned])) == {r, a}
    share_r = np.mean(li["l_returnflag"][returned] == r)
    assert 0.45 < share_r < 0.55


def test_the_groups_hold_the_same_lines_for_every_seed():
    """The filter and both flags follow the line, not the seed: each of
    the four groups keeps the same lines (so the program compiles the
    same shapes), while the prices still follow the seed."""
    ds = plug("datasets", "tpch_by_month_flags")
    ref = plug("reference", "tpch_q1")
    kept, prices = [], []
    for seed in SEEDS:
        li = ds.make(CONFIG, seed, 0.01)["lineitem"]
        line = li["l_orderkey"] * 8 + li["l_linenumber"]
        keep = li["l_shipdate"] <= ref.cutoff(QUERY["delta"])
        group = (li["l_returnflag"].astype(np.int64) * 256
                 + li["l_linestatus"])
        kept.append({int(g): np.sort(line[keep & (group == g)])
                     for g in np.unique(group[keep])})
        prices.append(li["l_extendedprice"][np.argsort(line)])
    assert len(kept[0]) == 4
    assert kept[0].keys() == kept[1].keys()
    for g in kept[0]:
        assert np.array_equal(kept[0][g], kept[1][g]), g
    assert not np.array_equal(prices[0], prices[1])


# -- the reference ------------------------------------------------------------


def test_the_reference_is_the_exact_decimal_report():
    """Against pandas' own filter and grouping and Python decimals (each
    float's shortest repr is its decimal)."""
    import pandas as pd

    ds = plug("datasets", "tpch_by_month_flags")
    ref = plug("reference", "tpch_q1")
    li = ds.make(CONFIG, SEEDS[0], 0.01)["lineitem"]
    df = pd.DataFrame({c: li[c] for c in (
        "l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax")})
    df = df[pd.to_datetime(df.l_shipdate, unit="D")
            <= pd.Timestamp(1998, 9, 2)]
    got = ref.Reference({"lineitem": li}).groups(QUERY, PARAMS)
    assert list(got) == sorted(got) and len(got) == 4
    for (flag, status), part in df.groupby(["l_returnflag",
                                            "l_linestatus"]):
        p = [Decimal(repr(v)) for v in part.l_extendedprice]
        d = [Decimal(repr(v)) for v in part.l_discount]
        t = [Decimal(repr(v)) for v in part.l_tax]
        sums = got[(flag, status)]
        assert sums["count_order"] == len(part)
        assert sums["sum_qty"] == int(part.l_quantity.sum())
        assert Decimal(sums["sum_base_price_e2"]) / 100 == sum(p)
        assert Decimal(sums["sum_disc_price_e4"]) / 10 ** 4 == sum(
            x * (1 - y) for x, y in zip(p, d))
        assert Decimal(sums["sum_charge_e6"]) / 10 ** 6 == sum(
            x * (1 - y) * (1 + z) for x, y, z in zip(p, d, t))
        assert Decimal(sums["sum_disc_e2"]) / 100 == sum(d)
    flat = ref.Reference({"lineitem": li}).answer(QUERY, PARAMS)
    assert len(flat) == 4 * 6 and {len(v) for v in flat.values()} == {1}


def control_report(lineitem: dict, groups: dict) -> dict:
    """The control of `q1_rel_err`: the reference's report with each
    line's values and products rounded to float32 and their sums in
    float64, as {column: [value a group, in key order]}."""
    ref = plug("reference", "tpch_q1")
    keep = lineitem["l_shipdate"] <= ref.cutoff(QUERY["delta"])
    group = (lineitem["l_returnflag"][keep].astype(np.int64) * 256
             + lineitem["l_linestatus"][keep])
    f32 = {c: lineitem[c][keep].astype(np.float32)
           for c in ("l_quantity", "l_extendedprice", "l_discount",
                     "l_tax")}
    one = np.float32(1)
    disc_price = f32["l_extendedprice"] * (one - f32["l_discount"])
    per_line = {"sum_qty": f32["l_quantity"],
                "sum_base_price": f32["l_extendedprice"],
                "sum_disc_price": disc_price,
                "sum_charge": disc_price * (one + f32["l_tax"]),
                "avg_disc": f32["l_discount"]}
    out = {c: [] for c in ("sum_qty", "sum_base_price", "sum_disc_price",
                           "sum_charge", "avg_qty", "avg_price",
                           "avg_disc", "count_order")}
    for flag, status in groups:
        m = group == flag * 256 + status
        n = int(np.count_nonzero(m))
        sums = {c: float(np.sum(v[m], dtype=np.float64))
                for c, v in per_line.items()}
        out["count_order"].append(n)
        for c in ("sum_qty", "sum_base_price", "sum_disc_price",
                  "sum_charge"):
            out[c].append(sums[c])
        out["avg_qty"].append(sums["sum_qty"] / n)
        out["avg_price"].append(sums["sum_base_price"] / n)
        out["avg_disc"].append(sums["avg_disc"] / n)
    return out


def _miss_of(report: dict, groups: dict) -> float:
    """The op's own judgement of a report's worst relative miss."""
    import pyarrow as pa

    op = plug("ops", "tpch_q1")
    vocab = plug("datasets", "tpch_by_month_flags").VOCABULARY
    keys = {"l_returnflag": [vocab["l_returnflag"][f] for f, _ in groups],
            "l_linestatus": [vocab["l_linestatus"][s] for _, s in groups]}
    table = pa.table({c: keys[c] if c in keys else report[c]
                      for c in op.COLUMNS})
    worst, bad = op.judge(table, groups, vocab)
    return worst


def test_q1_rel_err_lies_between_its_two_readings():
    """The control (products in float32, sums in float64) misses by more
    than 2^2 x the limit in every seed at SF 0.01. Its miss does not
    shrink with the lines: float32's nearest values to 1 - l_discount
    and 1 + l_tax err the same way on every line, so `sum_charge` misses
    by 2^-26.2 at SF 0.01 and 2^-26.3 (1.24e-8) at SF 10. The program's
    reading, its worst miss on a TPU v5e over seven SF 10 runs (trees of
    the chip's f32-pair float64), is more than 2^2 under the limit."""
    op = plug("ops", "tpch_q1")
    ds = plug("datasets", "tpch_by_month_flags")
    ref = plug("reference", "tpch_q1")
    for seed in SEEDS:
        li = ds.make(CONFIG, seed, 0.01)["lineitem"]
        groups = ref.Reference({"lineitem": li}).groups(QUERY, PARAMS)
        assert _miss_of(control_report(li, groups), groups) \
            > 4 * op.Q1_REL_ERR
    program_reading = 5.06e-14  # 2^-44.2
    assert 4 * program_reading <= op.Q1_REL_ERR < 1.24e-8 / 4


# -- the system against the reference ----------------------------------------


def _records(monkeypatch) -> list:
    records = []
    driver = plug("drivers", "closed_loop").Driver
    real = driver.check

    def check(self):
        records.extend(self.warm_records + self.records)
        return real(self)

    monkeypatch.setattr(driver, "check", check)
    return records


def test_q1_matches_the_reference_on_sf10s_lanes(monkeypatch):
    records = _records(monkeypatch)
    result = bench_run.run_cell(CELL, 2 ** 31 + 3, 1.0, False, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert all(got == limit for name, (got, limit) in compared.items()
               if name not in ("answers_compared", "q1_rel_err"))
    assert compared["q1_rel_err"][0] < 2.0 ** -48  # the CPU's float64 is IEEE
    first = records[0]
    assert first["served"] == ["li_q1"] and first["scan_index"] == ["li_q1"]
    assert first["scan_lane"] == ["device"]
    assert first["aggregate"] == [{"path": "direct", "groups": 4,
                                   "masked": 60_003}]


def _through(monkeypatch, change):
    """Every answer passes through `change(table)` as it reaches the
    client."""
    op = plug("ops", "tpch_q1")
    real = op.Op.settle

    def settle(self, rec, table):
        return real(self, rec, change(self, table))

    monkeypatch.setattr(op.Op, "settle", settle)


def _not_correct_by(result, *names):
    assert result["correct"] is False
    compared = result["compared"]
    for name, (got, limit) in compared.items():
        if name == "answers_compared":
            continue
        assert (got > limit) == (name in names), (name, compared)


def test_a_lost_group_is_not_correct(monkeypatch):
    _through(monkeypatch, lambda self, t: t.slice(0, t.num_rows - 1))
    result = bench_run.run_cell(CELL, 17, 1.0, False, **SF10_LANES)
    _not_correct_by(result, "wrong_answers", "mismatched_rows")
    compared = result["compared"]
    assert compared["wrong_answers"][0] == compared["answers_compared"][0]


def test_a_count_off_by_one_is_not_correct(monkeypatch):
    import pyarrow as pa

    def change(self, table):
        counts = table.column("count_order").to_numpy().copy()
        counts[1] += 1
        i = table.column_names.index("count_order")
        return table.set_column(i, "count_order", pa.array(counts))

    _through(monkeypatch, change)
    result = bench_run.run_cell(CELL, 19, 1.0, False, **SF10_LANES)
    _not_correct_by(result, "wrong_answers", "mismatched_rows")


def test_products_in_float32_are_not_correct_by_q1_rel_err(monkeypatch):
    """Each answer's sums and averages replaced by the control's
    (float32 products, float64 sums): the counts and keys still right,
    so only the tolerance catches it."""
    import pyarrow as pa

    ref = plug("reference", "tpch_q1")

    def change(self, table):
        li = self.dep.tables["lineitem"]
        groups = ref.Reference(self.dep.tables).groups(self.query, PARAMS)
        report = control_report(li, groups)
        report["sum_qty"] = [float(g["sum_qty"]) for g in groups.values()]
        for name, values in report.items():
            i = table.column_names.index(name)
            table = table.set_column(i, name, pa.array(
                values, table.schema.field(name).type))
        return table

    _through(monkeypatch, change)
    result = bench_run.run_cell(CELL, 31, 1.0, False, **SF10_LANES)
    _not_correct_by(result, "wrong_answers", "mismatched_rows",
                    "q1_rel_err")


def test_a_scan_off_the_device_lane_is_not_correct():
    """A threshold over the index's lines puts its scan on the host:
    every answer right, every query off its lane."""
    result = bench_run.run_cell(CELL, 23, 1.0, False, **dict(
        TINY, conf_overrides={
            "spark.hyperspace.execution.min.device.rows": "1000000",
            "spark.hyperspace.distribution.enabled": "false"}))
    _not_correct_by(result, "off_lane_queries")
    compared = result["compared"]
    assert compared["off_lane_queries"][0] == \
        compared["answers_compared"][0] > 0


# -- the reader ---------------------------------------------------------------


def test_q1_roofline_counts_the_index_once(monkeypatch):
    """Every line's seven columns at their stored widths, plus the
    report, over the device time of the ops under the mix's scopes,
    each op counted once; a traced rehearsal has no device plane and
    leaves the share out."""
    from lib import program_spans

    result = bench_run.run_cell(CELL, 29, 1.0, True, **SF10_LANES)
    assert result["correct"] is True, result["compared"]
    assert "q1_roofline" not in result["metrics"]
    for name in ("optimize_ms", "scan_resolve_ms", "to_arrow_ms",
                 "serve_admit_ms"):
        assert result["metrics"][name]["value"] > 0, name

    reader = plug("metrics", "q1_roofline")
    ops = [(0.1, 0.002, ("hs.stage", "hs.predicate"), "fusion"),
           (0.2, 0.001, ("hs.aggregate",), "reduce"),
           (0.3, 0.001, (), "convert"),  # unscoped: not Q1's
           (1.1, 0.004, ("hs.aggregate",), "reduce")]
    monkeypatch.setattr(program_spans, "load",
                        lambda run: {"spans": [], "ops": list(ops)})
    monkeypatch.setattr(program_spans, "_whole",
                        lambda run, name: [(0.0, 1.0), (1.0, 2.0)])
    run = {"trace": {}, "traffic": TRAFFIC,
           "dataset": plug("datasets", "tpch_by_month_flags"),
           "device_kind": "TPU v5 lite", "rows": {"lineitem": 6 * 10 ** 7}}
    want_bytes = 6 * 10 ** 7 * (4 * 3 + 8 * 4) + 2 * 4 * (4 * 2 + 8 * 8)
    assert reader.compute(run) == pytest.approx(
        100 * want_bytes / 819e9 / 0.0035)
    ops[:] = [(0.3, 0.001, (), "convert")]
    assert reader.compute(run) is None
