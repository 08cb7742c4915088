"""reference/select.py against a row-at-a-time join and filter."""

import numpy as np
import pytest

from conftest import plug

reference = plug("reference", "select")

QUERY = {"table": "l", "join": {"table": "r", "left_on": "lk",
                                "right_on": "rk"},
         "select": ["lv", "rv"]}


@pytest.mark.parametrize("stride", [1, 1000])  # dense keys, sparse keys
def test_join_to_a_unique_key_with_unmatched_rows(stride):
    rng = np.random.default_rng(3)
    rk = rng.permutation(np.arange(10, 200)) * stride
    tables = {"r": {"rk": rk, "rv": rng.random(len(rk))},
              "l": {"lk": rng.integers(0, 230, 5000) * stride,
                    "lv": np.arange(5000)}}
    got = reference.Reference(tables).answer(QUERY, {})
    by_key = dict(zip(rk.tolist(), tables["r"]["rv"].tolist()))
    want = [(v, by_key[k]) for k, v in zip(tables["l"]["lk"].tolist(),
                                           tables["l"]["lv"].tolist())
            if k in by_key]
    assert 0 < len(want) < 5000
    assert sorted(zip(got["lv"].tolist(), got["rv"].tolist())) == sorted(want)


def test_range_then_join():
    rng = np.random.default_rng(4)
    tables = {"r": {"rk": np.arange(1, 101), "rv": rng.random(100)},
              "l": {"lk": rng.integers(1, 101, 1000), "lv": np.arange(1000)}}
    query = dict(QUERY, range={"column": "lk"})
    got = reference.Reference(tables).answer(query, {"lo": 20, "hi": 30})
    keep = (tables["l"]["lk"] >= 20) & (tables["l"]["lk"] < 30)
    assert sorted(got["lv"].tolist()) == tables["l"]["lv"][keep].tolist()
    assert np.array_equal(
        got["rv"], tables["r"]["rv"][tables["l"]["lk"][got["lv"]] - 1])


def test_a_join_to_a_repeated_key_is_refused():
    tables = {"r": {"rk": np.array([1, 1, 2]), "rv": np.zeros(3)},
              "l": {"lk": np.array([1, 2]), "lv": np.zeros(2)}}
    with pytest.raises(ValueError):
        reference.Reference(tables).answer(QUERY, {})


def test_q12_against_rows_taken_one_at_a_time():
    tpch = plug("datasets", "tpch")
    q12 = plug("reference", "q12")
    tables = tpch.make_tables(0.01, 5)
    params = plug("ops", "q12").Op.control_params(
        {"shipmodes": ["MAIL", "SHIP"], "year": 1994}, tpch, None, None)
    got = q12.Reference(tables).answer({}, params)
    li, orders = tables["lineitem"], tables["orders"]
    priority = dict(zip(orders["o_orderkey"].tolist(),
                        orders["o_orderpriority"].tolist()))
    want = {}
    for i in range(len(li["l_orderkey"])):
        mode = int(li["l_shipmode"][i])
        if mode in params["shipmode_codes"] \
                and li["l_commitdate"][i] < li["l_receiptdate"][i] \
                and li["l_shipdate"][i] < li["l_commitdate"][i] \
                and params["receipt_lo"] <= li["l_receiptdate"][i] \
                < params["receipt_hi"]:
            high = priority[int(li["l_orderkey"][i])] in params["high_codes"]
            counts = want.setdefault(mode, [0, 0])
            counts[0 if high else 1] += 1
    assert got["l_shipmode"].tolist() == sorted(want)
    assert [[h, l] for h, l in zip(got["high_line_count"].tolist(),
                                   got["low_line_count"].tolist())] == \
        [want[m] for m in sorted(want)]
    assert sum(map(sum, want.values())) > 100


def test_no_plain_reference_imports_the_program():
    import ast
    import os

    from conftest import BENCH

    for f in sorted(os.listdir(os.path.join(BENCH, "reference"))):
        if not f.endswith(".py"):
            continue
        with open(os.path.join(BENCH, "reference", f)) as fh:
            tree = ast.parse(fh.read())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert all(n.split(".")[0] in ("numpy", "__future__") for n in names), \
            (f, names)
