"""The generator's invariants: every count is the same for every seed."""

import numpy as np
import pytest

from conftest import plug

datagen = plug("datasets", "tpch")
reference = plug("reference", "select")

SEEDS = (1, 77, 2 ** 31 + 5)
SF = 0.02  # 30,000 orders


@pytest.fixture(scope="module")
def tables():
    return {s: datagen.make_tables(SF, s, edge_every=64) for s in SEEDS}


def test_row_counts_follow_the_rule_at_the_cells_scales():
    assert datagen.order_count(1.0) == 1_500_000
    assert datagen.lineitem_count(1_500_000) == 6_000_000
    assert datagen.lineitem_count(4_500_000) == 17_999_998
    assert datagen.range_width(1_500_000, 0.01) == 15_001
    assert datagen.lines_in_range(15_001) == 60_004


def test_row_counts_are_equal_across_seeds(tables):
    n = datagen.order_count(SF)
    for t in tables.values():
        assert len(t["orders"]["o_orderkey"]) == n
        assert len(t["lineitem"]["l_orderkey"]) == datagen.lineitem_count(n)
        assert np.array_equal(np.sort(t["orders"]["o_orderkey"]),
                              np.arange(1, n + 1))
        keys, counts = np.unique(t["lineitem"]["l_orderkey"],
                                 return_counts=True)
        assert np.array_equal(counts, datagen.lines_of(keys))


def test_line_numbers_run_from_one_within_each_order(tables):
    li = tables[SEEDS[0]]["lineitem"]
    order = np.lexsort((li["l_linenumber"], li["l_orderkey"]))
    keys, counts = np.unique(li["l_orderkey"], return_counts=True)
    starts = np.cumsum(counts) - counts
    assert np.array_equal(
        li["l_linenumber"][order],
        np.arange(len(order)) - np.repeat(starts, counts) + 1)


def test_per_bucket_counts_are_equal_across_seeds(tables):
    # any function of the key alone gives equal bucket counts; this one
    # stands for the program's hash
    def buckets(keys):
        return np.bincount((keys * 2654435761 >> 7) % 64, minlength=64)

    first = buckets(tables[SEEDS[0]]["lineitem"]["l_orderkey"])
    for t in tables.values():
        assert np.array_equal(buckets(t["lineitem"]["l_orderkey"]), first)


@pytest.mark.parametrize("lo", [1, 2, 5, 1234, 29_000])
def test_every_range_holds_the_same_number_of_lines(tables, lo):
    n = datagen.order_count(SF)
    width = datagen.range_width(n, 0.01)
    query = {"table": "lineitem", "range": {"column": "l_orderkey"},
             "select": ["l_orderkey", "l_linenumber"]}
    for t in tables.values():
        got = reference.Reference(t).answer(query, {"lo": lo,
                                                    "hi": lo + width})
        assert len(got["l_orderkey"]) == datagen.lines_in_range(width)


def test_the_rows_a_tpch_predicate_keeps_are_as_many_for_every_seed(tables):
    """Q12's predicate over the seed-free columns: the same count in the
    table and in every bucket, whatever the seed."""
    def kept(t):
        li = t["lineitem"]
        keep = (np.isin(li["l_shipmode"], [2, 5])
                & (li["l_commitdate"] < li["l_receiptdate"])
                & (li["l_shipdate"] < li["l_commitdate"])
                & (li["l_receiptdate"] >= 8766) & (li["l_receiptdate"] < 9131))
        return np.bincount((li["l_orderkey"][keep] * 2654435761 >> 7) % 64,
                           minlength=64)

    first = kept(tables[SEEDS[0]])
    assert first.sum() > 100
    for t in tables.values():
        assert np.array_equal(kept(t), first)


def test_dates_follow_clause_4_2_3(tables):
    li = tables[SEEDS[0]]["lineitem"]
    o = tables[SEEDS[0]]["orders"]
    ordered = dict(zip(o["o_orderkey"].tolist(), o["o_orderdate"].tolist()))
    od = np.array([ordered[k] for k in li["l_orderkey"].tolist()])
    assert 8035 <= o["o_orderdate"].min() and o["o_orderdate"].max() < 8035 + 2406
    ship, commit, receipt = (li["l_shipdate"] - od, li["l_commitdate"] - od,
                             li["l_receiptdate"] - li["l_shipdate"])
    assert (ship.min(), ship.max()) == (1, 121)
    assert (commit.min(), commit.max()) == (30, 90)
    assert (receipt.min(), receipt.max()) == (1, 30)


def test_payload_and_row_order_differ_between_seeds(tables):
    a, b = (tables[s]["lineitem"] for s in SEEDS[:2])
    assert not np.array_equal(a["l_orderkey"], b["l_orderkey"])
    assert not np.array_equal(a["l_extendedprice"].view(np.int64),
                              b["l_extendedprice"].view(np.int64))
    again = datagen.make_tables(SF, SEEDS[0], edge_every=64)["lineitem"]
    for name, data in a.items():
        assert np.array_equal(data.view(np.int64) if data.dtype == np.float64
                              else data, again[name].view(np.int64)
                              if data.dtype == np.float64 else again[name])


def test_edge_values_are_planted_and_columns_are_the_specifications(tables):
    t = tables[SEEDS[0]]
    assert tuple(t["lineitem"]) == datagen.LINEITEM_COLUMNS
    assert tuple(t["orders"]) == datagen.ORDERS_COLUMNS
    price = t["lineitem"]["l_extendedprice"]
    assert np.isnan(price).any() and np.isinf(price).any()
    assert (price == 5e-324).any() and (np.abs(price) == 1e300).any()
    table = datagen.to_arrow(t["lineitem"])
    assert str(table.schema.field("l_shipdate").type) == "date32[day]"
    assert str(table.schema.field("l_shipmode").type).startswith("dictionary")
    assert max(map(len, datagen.VOCABULARY["l_comment"])) <= 44
    assert max(map(len, datagen.VOCABULARY["o_comment"])) <= 79
    assert all(len(c) == 15 for c in datagen.VOCABULARY["o_clerk"])
