"""The 22 TPC-H queries on the framework DataFrame API, with pandas
oracles.

Shapes follow the official SQL: expression aggregates (q1), correlated
scalar subqueries as aggregate+join-back (q2/q15/q17/q20), EXISTS /
NOT EXISTS as semi/anti joins (q4/q16/q22), scalar totals via cross join
(q11), LIKE predicates in dictionary space (q2/q9/q13/q14/q16/q20),
CASE pivots (q8/q12/q14), and multi-supplier order logic expressed as
per-order distinct-supplier aggregates (q21 — `exists l2 / not exists
l3` is exactly "the order has >= 2 distinct suppliers and only one
distinct supplier among its late lines").

EXTRACT(year) compiles to a CASE WHEN chain over date32 literals — the
engine stores dates as day ordinals, so the year boundaries are plain
integer comparisons (no date kernel needed).

Queries whose official ORDER BY does not totally order rows append a
deterministic key to BOTH lanes (q3/q10/q18: the 3-way equality check
needs a stable top-N; the TPC-DS suite does the same for q79).

Each oracle doubles as the CPU baseline; `tests/test_tpch.py`
asserts rules-on == rules-off == oracle — the reference's
E2E guarantee (`E2EHyperspaceRulesTests.scala:330-346`) across the full
TPC-H set its serde layer pins (`index/serde/package.scala:46-49`).
"""

from __future__ import annotations

import datetime
from typing import Callable, Dict, Tuple

import numpy as np
import pandas as pd

from hyperspace_tpu.plan.expr import col, lit, when
from hyperspace_tpu.tpch.generator import days

_EPOCH = datetime.date(1970, 1, 1)


def normalize_result(df: pd.DataFrame) -> pd.DataFrame:
    """THE result-normalization contract the 3-way equality checks use
    (`tests/test_tpch.py`): stringify non-str object columns (date objects),
    sort by every column, widen numerics to float64."""
    out = df.copy()
    for c in out.columns:
        if out[c].dtype == object and len(out) and not isinstance(
                out[c].iloc[0], str):
            out[c] = out[c].astype(str)
    out = out.sort_values(list(out.columns)).reset_index(drop=True)
    return out.astype({c: "float64" for c in out.columns
                       if out[c].dtype.kind in "fi"})


def _date(y, m, d):
    return datetime.date(y, m, d)


def _year(s):
    return pd.to_datetime(s).dt.year


def _volume():
    return col("l_extendedprice") * (lit(1.0) - col("l_discount"))


def _year_expr(name: str):
    """EXTRACT(year) over a date32 column as a CASE chain (data years are
    1992..1998)."""
    e = when(col(name) < lit(days(1993, 1, 1)), 1992)
    for y in range(1993, 1999):
        e = e.when(col(name) < lit(days(y + 1, 1, 1)), y)
    return e.otherwise(1999)


# ---------------------------------------------------------------------------
# q1 — pricing summary report
# ---------------------------------------------------------------------------


def q1(dfs):
    li = dfs["lineitem"].filter(
        col("l_shipdate") <= lit(days(1998, 9, 2)))
    disc = _volume()
    charge = (col("l_extendedprice") * (lit(1.0) - col("l_discount"))
              * (lit(1.0) + col("l_tax")))
    return (li.group_by("l_returnflag", "l_linestatus").agg(
        ("sum", "l_quantity", "sum_qty"),
        ("sum", "l_extendedprice", "sum_base_price"),
        ("sum", disc, "sum_disc_price"),
        ("sum", charge, "sum_charge"),
        ("avg", "l_quantity", "avg_qty"),
        ("avg", "l_extendedprice", "avg_price"),
        ("avg", "l_discount", "avg_disc"),
        ("count", "*", "count_order"))
        .sort("l_returnflag", "l_linestatus"))


def q1_pandas(t):
    li = t["lineitem"]
    li = li[li.l_shipdate <= _date(1998, 9, 2)].copy()
    li["disc_price"] = li.l_extendedprice * (1 - li.l_discount)
    li["charge"] = li.disc_price * (1 + li.l_tax)
    g = li.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size")).reset_index()
    return g.sort_values(["l_returnflag", "l_linestatus"]) \
        .reset_index(drop=True)


# ---------------------------------------------------------------------------
# q2 — minimum cost supplier (correlated scalar subquery -> join-back)
# ---------------------------------------------------------------------------


def q2(dfs):
    part = (dfs["part"]
            .filter((col("p_size") == lit(15))
                    & col("p_type").like("%BRASS"))
            .select("p_partkey", "p_mfgr"))
    region = dfs["region"].filter(col("r_name") == lit("EUROPE")) \
        .select("r_regionkey")
    nation = dfs["nation"].select("n_nationkey", "n_name", "n_regionkey")
    nation = nation.join(region, on=col("n_regionkey") == col("r_regionkey")) \
        .select("n_nationkey", "n_name")
    supp = dfs["supplier"].select(
        "s_suppkey", "s_name", "s_address", "s_nationkey", "s_phone",
        "s_acctbal", "s_comment")
    supp = supp.join(nation, on=col("s_nationkey") == col("n_nationkey")) \
        .select("s_suppkey", "s_name", "s_address", "s_phone", "s_acctbal",
                "s_comment", "n_name")
    ps = dfs["partsupp"].select("ps_partkey", "ps_suppkey", "ps_supplycost")
    ps_eu = ps.join(supp, on=col("ps_suppkey") == col("s_suppkey"))
    mincost = (ps_eu.group_by("ps_partkey")
               .agg(("min", "ps_supplycost", "min_cost")))
    j = part.join(ps_eu, on=col("p_partkey") == col("ps_partkey"))
    j = j.join(mincost, on=(col("ps_partkey") == col("ps_partkey"))
               & (col("ps_supplycost") == col("min_cost")))
    return (j.select("s_acctbal", "s_name", "n_name", "p_partkey",
                     "p_mfgr", "s_address", "s_phone", "s_comment")
            .sort("-s_acctbal", "n_name", "s_name", "p_partkey")
            .limit(100))


def q2_pandas(t):
    part = t["part"]
    part = part[(part.p_size == 15)
                & part.p_type.str.endswith("BRASS")][
        ["p_partkey", "p_mfgr"]]
    region = t["region"][t["region"].r_name == "EUROPE"][["r_regionkey"]]
    nation = t["nation"].merge(region, left_on="n_regionkey",
                               right_on="r_regionkey")[
        ["n_nationkey", "n_name"]]
    supp = t["supplier"].merge(nation, left_on="s_nationkey",
                               right_on="n_nationkey")
    ps = t["partsupp"].merge(supp, left_on="ps_suppkey",
                             right_on="s_suppkey")
    mincost = ps.groupby("ps_partkey", as_index=False).agg(
        min_cost=("ps_supplycost", "min"))
    j = part.merge(ps, left_on="p_partkey", right_on="ps_partkey")
    j = j.merge(mincost, on="ps_partkey")
    j = j[j.ps_supplycost == j.min_cost]
    return (j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
               "s_address", "s_phone", "s_comment"]]
            .sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                         ascending=[False, True, True, True])
            .head(100).reset_index(drop=True))


# ---------------------------------------------------------------------------
# q3 — shipping priority (top unshipped orders)
# ---------------------------------------------------------------------------


def q3(dfs):
    cust = dfs["customer"].filter(
        col("c_mktsegment") == lit("BUILDING")).select("c_custkey")
    orders = dfs["orders"].filter(
        col("o_orderdate") < lit(days(1995, 3, 15))).select(
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority")
    li = dfs["lineitem"].filter(
        col("l_shipdate") > lit(days(1995, 3, 15))).select(
        "l_orderkey", "l_extendedprice", "l_discount")
    j = orders.join(cust, on=col("o_custkey") == col("c_custkey"))
    j = li.join(j, on=col("l_orderkey") == col("o_orderkey"))
    return (j.group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(("sum", _volume(), "revenue"))
            .sort("-revenue", "o_orderdate", "l_orderkey").limit(10))


def q3_pandas(t):
    cust = t["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"][["c_custkey"]]
    orders = t["orders"]
    orders = orders[orders.o_orderdate < _date(1995, 3, 15)]
    li = t["lineitem"]
    li = li[li.l_shipdate > _date(1995, 3, 15)].copy()
    li["revenue"] = li.l_extendedprice * (1 - li.l_discount)
    j = orders.merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = li.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).agg(revenue=("revenue", "sum"))
    return (g.sort_values(["revenue", "o_orderdate", "l_orderkey"],
                          ascending=[False, True, True])
            .head(10).reset_index(drop=True)
            [["l_orderkey", "o_orderdate", "o_shippriority", "revenue"]])


# ---------------------------------------------------------------------------
# q4 — order priority checking (EXISTS -> semi join)
# ---------------------------------------------------------------------------


def q4(dfs):
    orders = dfs["orders"].filter(
        (col("o_orderdate") >= lit(days(1993, 7, 1)))
        & (col("o_orderdate") < lit(days(1993, 10, 1)))).select(
        "o_orderkey", "o_orderpriority")
    late = dfs["lineitem"].filter(
        col("l_commitdate") < col("l_receiptdate")).select("l_orderkey")
    j = orders.join(late, on=col("o_orderkey") == col("l_orderkey"),
                    how="left_semi")
    return (j.group_by("o_orderpriority")
            .agg(("count", "*", "order_count")).sort("o_orderpriority"))


def q4_pandas(t):
    orders = t["orders"]
    orders = orders[(orders.o_orderdate >= _date(1993, 7, 1))
                    & (orders.o_orderdate < _date(1993, 10, 1))]
    li = t["lineitem"]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    j = orders[orders.o_orderkey.isin(late)]
    g = j.groupby("o_orderpriority", as_index=False).agg(
        order_count=("o_orderkey", "size"))
    return g.sort_values("o_orderpriority").reset_index(drop=True)


# ---------------------------------------------------------------------------
# q5 — local supplier volume
# ---------------------------------------------------------------------------


def q5(dfs):
    region = dfs["region"].filter(col("r_name") == lit("ASIA")) \
        .select("r_regionkey")
    nation = dfs["nation"].join(
        region, on=col("n_regionkey") == col("r_regionkey")).select(
        "n_nationkey", "n_name")
    orders = dfs["orders"].filter(
        (col("o_orderdate") >= lit(days(1994, 1, 1)))
        & (col("o_orderdate") < lit(days(1995, 1, 1)))).select(
        "o_orderkey", "o_custkey")
    cust = dfs["customer"].select("c_custkey", "c_nationkey")
    li = dfs["lineitem"].select("l_orderkey", "l_suppkey",
                                "l_extendedprice", "l_discount")
    supp = dfs["supplier"].select("s_suppkey", "s_nationkey")
    j = orders.join(cust, on=col("o_custkey") == col("c_custkey"))
    j = li.join(j, on=col("l_orderkey") == col("o_orderkey"))
    j = j.join(supp, on=(col("l_suppkey") == col("s_suppkey"))
               & (col("c_nationkey") == col("s_nationkey")))
    j = j.join(nation, on=col("s_nationkey") == col("n_nationkey"))
    return (j.group_by("n_name").agg(("sum", _volume(), "revenue"))
            .sort("-revenue"))


def q5_pandas(t):
    region = t["region"][t["region"].r_name == "ASIA"][["r_regionkey"]]
    nation = t["nation"].merge(region, left_on="n_regionkey",
                               right_on="r_regionkey")[
        ["n_nationkey", "n_name"]]
    orders = t["orders"]
    orders = orders[(orders.o_orderdate >= _date(1994, 1, 1))
                    & (orders.o_orderdate < _date(1995, 1, 1))]
    j = orders.merge(t["customer"], left_on="o_custkey",
                     right_on="c_custkey")
    j = t["lineitem"].merge(j, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["supplier"], left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
    j = j.merge(nation, left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby("n_name", as_index=False).agg(revenue=("revenue", "sum"))
    return g.sort_values("revenue", ascending=False).reset_index(drop=True)


# ---------------------------------------------------------------------------
# q6 — forecasting revenue change (pure filter aggregate)
# ---------------------------------------------------------------------------


def q6(dfs, year: int = 1994):
    """Q6 with DATE = January 1 of `year` (the specification draws it
    from 1993-1997; the validation run's is 1994) and the validation
    run's DISCOUNT 0.06 and QUANTITY 24."""
    li = dfs["lineitem"].filter(
        (col("l_shipdate") >= lit(days(year, 1, 1)))
        & (col("l_shipdate") < lit(days(year + 1, 1, 1)))
        & col("l_discount").between(lit(0.05), lit(0.07))
        & (col("l_quantity") < lit(24)))
    return li.agg(("sum", col("l_extendedprice") * col("l_discount"),
                   "revenue"))


def q6_pandas(t):
    li = t["lineitem"]
    m = ((li.l_shipdate >= _date(1994, 1, 1))
         & (li.l_shipdate < _date(1995, 1, 1))
         & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
         & (li.l_quantity < 24))
    return pd.DataFrame(
        {"revenue": [(li[m].l_extendedprice * li[m].l_discount).sum()]})


# ---------------------------------------------------------------------------
# q7 — volume shipping between two nations
# ---------------------------------------------------------------------------


def q7(dfs):
    pair = col("n_name").isin("FRANCE", "GERMANY")
    n1 = dfs["nation"].filter(pair).select("n_nationkey", "n_name")
    n2 = dfs["nation"].filter(pair).select("n_nationkey", "n_name")
    li = dfs["lineitem"].filter(
        col("l_shipdate").between(lit(days(1995, 1, 1)),
                                  lit(days(1996, 12, 31)))).select(
        "l_orderkey", "l_suppkey", "l_shipdate", "l_extendedprice",
        "l_discount")
    j = li.join(dfs["supplier"].select("s_suppkey", "s_nationkey"),
                on=col("l_suppkey") == col("s_suppkey"))
    j = j.join(dfs["orders"].select("o_orderkey", "o_custkey"),
               on=col("l_orderkey") == col("o_orderkey"))
    j = j.join(dfs["customer"].select("c_custkey", "c_nationkey"),
               on=col("o_custkey") == col("c_custkey"))
    j = j.join(n1, on=col("s_nationkey") == col("n_nationkey"))
    j = j.join(n2, on=col("c_nationkey") == col("n_nationkey"))
    # Only FR/DE rows survive, so "pair in {(FR,DE),(DE,FR)}" == inequality.
    j = j.filter(col("n_name") != col("n_name_r"))
    j = j.select(col("n_name").alias("supp_nation"),
                 col("n_name_r").alias("cust_nation"),
                 _year_expr("l_shipdate").alias("l_year"),
                 _volume().alias("volume"))
    return (j.group_by("supp_nation", "cust_nation", "l_year")
            .agg(("sum", "volume", "revenue"))
            .sort("supp_nation", "cust_nation", "l_year"))


def q7_pandas(t):
    n = t["nation"][t["nation"].n_name.isin(["FRANCE", "GERMANY"])]
    li = t["lineitem"]
    li = li[(li.l_shipdate >= _date(1995, 1, 1))
            & (li.l_shipdate <= _date(1996, 12, 31))]
    j = li.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey",
                right_on="n_nationkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="c_nationkey",
                right_on="n_nationkey", suffixes=("", "_r"))
    j = j[j.n_name != j.n_name_r].copy()
    j["supp_nation"] = j.n_name
    j["cust_nation"] = j.n_name_r
    j["l_year"] = _year(j.l_shipdate)
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["supp_nation", "cust_nation", "l_year"],
                  as_index=False).agg(revenue=("volume", "sum"))
    return g.sort_values(["supp_nation", "cust_nation", "l_year"]) \
        .reset_index(drop=True)


# ---------------------------------------------------------------------------
# q8 — national market share
# ---------------------------------------------------------------------------


def q8(dfs):
    region = dfs["region"].filter(col("r_name") == lit("AMERICA")) \
        .select("r_regionkey")
    n1 = dfs["nation"].join(
        region, on=col("n_regionkey") == col("r_regionkey")).select(
        "n_nationkey")
    n2 = dfs["nation"].select("n_nationkey", "n_name")
    part = dfs["part"].filter(
        col("p_type") == lit("ECONOMY ANODIZED STEEL")).select("p_partkey")
    orders = dfs["orders"].filter(
        col("o_orderdate").between(lit(days(1995, 1, 1)),
                                   lit(days(1996, 12, 31)))).select(
        "o_orderkey", "o_custkey", "o_orderdate")
    li = dfs["lineitem"].select("l_orderkey", "l_partkey", "l_suppkey",
                                "l_extendedprice", "l_discount")
    j = li.join(part, on=col("l_partkey") == col("p_partkey"))
    j = j.join(orders, on=col("l_orderkey") == col("o_orderkey"))
    j = j.join(dfs["customer"].select("c_custkey", "c_nationkey"),
               on=col("o_custkey") == col("c_custkey"))
    j = j.join(n1, on=col("c_nationkey") == col("n_nationkey"))
    j = j.join(dfs["supplier"].select("s_suppkey", "s_nationkey"),
               on=col("l_suppkey") == col("s_suppkey"))
    j = j.join(n2, on=col("s_nationkey") == col("n_nationkey"))
    j = j.select(_year_expr("o_orderdate").alias("o_year"),
                 _volume().alias("volume"), "n_name")
    brazil = when(col("n_name") == lit("BRAZIL"), col("volume")) \
        .otherwise(0.0)
    g = j.group_by("o_year").agg(("sum", brazil, "brazil_volume"),
                                 ("sum", "volume", "total_volume"))
    return (g.select("o_year",
                     (col("brazil_volume") / col("total_volume"))
                     .alias("mkt_share")).sort("o_year"))


def q8_pandas(t):
    region = t["region"][t["region"].r_name == "AMERICA"][["r_regionkey"]]
    n1 = t["nation"].merge(region, left_on="n_regionkey",
                           right_on="r_regionkey")[["n_nationkey"]]
    part = t["part"][t["part"].p_type == "ECONOMY ANODIZED STEEL"][
        ["p_partkey"]]
    orders = t["orders"]
    orders = orders[(orders.o_orderdate >= _date(1995, 1, 1))
                    & (orders.o_orderdate <= _date(1996, 12, 31))]
    j = t["lineitem"].merge(part, left_on="l_partkey",
                            right_on="p_partkey")
    j = j.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(n1, left_on="c_nationkey", right_on="n_nationkey")
    j = j.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(t["nation"][["n_nationkey", "n_name"]],
                left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(o_year=_year(j.o_orderdate),
                 volume=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby("o_year", as_index=False).apply(
        lambda x: pd.Series({
            "mkt_share": (x[x.n_name == "BRAZIL"].volume.sum()
                          / x.volume.sum())}), include_groups=False)
    return g.sort_values("o_year").reset_index(drop=True)


# ---------------------------------------------------------------------------
# q9 — product type profit measure
# ---------------------------------------------------------------------------


def q9(dfs):
    part = dfs["part"].filter(col("p_name").like("%green%")) \
        .select("p_partkey")
    li = dfs["lineitem"].select("l_orderkey", "l_partkey", "l_suppkey",
                                "l_quantity", "l_extendedprice",
                                "l_discount")
    j = li.join(part, on=col("l_partkey") == col("p_partkey"))
    j = j.join(dfs["supplier"].select("s_suppkey", "s_nationkey"),
               on=col("l_suppkey") == col("s_suppkey"))
    j = j.join(dfs["partsupp"].select("ps_partkey", "ps_suppkey",
                                      "ps_supplycost"),
               on=(col("l_suppkey") == col("ps_suppkey"))
               & (col("l_partkey") == col("ps_partkey")))
    j = j.join(dfs["orders"].select("o_orderkey", "o_orderdate"),
               on=col("l_orderkey") == col("o_orderkey"))
    j = j.join(dfs["nation"].select("n_nationkey", "n_name"),
               on=col("s_nationkey") == col("n_nationkey"))
    amount = (_volume()
              - col("ps_supplycost") * col("l_quantity"))
    j = j.select(col("n_name").alias("nation"),
                 _year_expr("o_orderdate").alias("o_year"),
                 amount.alias("amount"))
    return (j.group_by("nation", "o_year")
            .agg(("sum", "amount", "sum_profit"))
            .sort("nation", "-o_year"))


def q9_pandas(t):
    part = t["part"][t["part"].p_name.str.contains("green")][["p_partkey"]]
    j = t["lineitem"].merge(part, left_on="l_partkey",
                            right_on="p_partkey")
    j = j.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(t["partsupp"], left_on=["l_suppkey", "l_partkey"],
                right_on=["ps_suppkey", "ps_partkey"])
    j = j.merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["nation"], left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(nation=j.n_name, o_year=_year(j.o_orderdate),
                 amount=j.l_extendedprice * (1 - j.l_discount)
                 - j.ps_supplycost * j.l_quantity)
    g = j.groupby(["nation", "o_year"], as_index=False).agg(
        sum_profit=("amount", "sum"))
    return g.sort_values(["nation", "o_year"],
                         ascending=[True, False]).reset_index(drop=True)


# ---------------------------------------------------------------------------
# q10 — returned item reporting
# ---------------------------------------------------------------------------


def q10(dfs):
    orders = dfs["orders"].filter(
        (col("o_orderdate") >= lit(days(1993, 10, 1)))
        & (col("o_orderdate") < lit(days(1994, 1, 1)))).select(
        "o_orderkey", "o_custkey")
    li = dfs["lineitem"].filter(col("l_returnflag") == lit("R")).select(
        "l_orderkey", "l_extendedprice", "l_discount")
    j = li.join(orders, on=col("l_orderkey") == col("o_orderkey"))
    j = j.join(dfs["customer"].select(
        "c_custkey", "c_name", "c_acctbal", "c_phone", "c_nationkey",
        "c_address", "c_comment"),
        on=col("o_custkey") == col("c_custkey"))
    j = j.join(dfs["nation"].select("n_nationkey", "n_name"),
               on=col("c_nationkey") == col("n_nationkey"))
    return (j.group_by("c_custkey", "c_name", "c_acctbal", "c_phone",
                       "n_name", "c_address", "c_comment")
            .agg(("sum", _volume(), "revenue"))
            .sort("-revenue", "c_custkey").limit(20))


def q10_pandas(t):
    orders = t["orders"]
    orders = orders[(orders.o_orderdate >= _date(1993, 10, 1))
                    & (orders.o_orderdate < _date(1994, 1, 1))]
    li = t["lineitem"]
    li = li[li.l_returnflag == "R"]
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(t["nation"], left_on="c_nationkey", right_on="n_nationkey")
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone",
                   "n_name", "c_address", "c_comment"],
                  as_index=False).agg(revenue=("revenue", "sum"))
    return (g.sort_values(["revenue", "c_custkey"],
                          ascending=[False, True])
            .head(20).reset_index(drop=True)
            [["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
              "c_address", "c_comment", "revenue"]])


# ---------------------------------------------------------------------------
# q11 — important stock identification (scalar total via cross join)
# ---------------------------------------------------------------------------


def q11(dfs):
    nation = dfs["nation"].filter(col("n_name") == lit("GERMANY")) \
        .select("n_nationkey")
    supp = dfs["supplier"].select("s_suppkey", "s_nationkey").join(
        nation, on=col("s_nationkey") == col("n_nationkey")).select(
        "s_suppkey")
    ps = dfs["partsupp"].select("ps_partkey", "ps_suppkey",
                                "ps_supplycost", "ps_availqty")
    ps_de = ps.join(supp, on=col("ps_suppkey") == col("s_suppkey"))
    value = col("ps_supplycost") * col("ps_availqty")
    per_part = (ps_de.group_by("ps_partkey").agg(("sum", value, "value")))
    total = ps_de.agg(("sum", value, "total_value"))
    j = per_part.join(total, how="cross")
    j = j.filter(col("value") > col("total_value") * lit(0.0001))
    return j.select("ps_partkey", "value").sort("-value", "ps_partkey")


def q11_pandas(t):
    nation = t["nation"][t["nation"].n_name == "GERMANY"][["n_nationkey"]]
    supp = t["supplier"].merge(nation, left_on="s_nationkey",
                               right_on="n_nationkey")[["s_suppkey"]]
    ps = t["partsupp"].merge(supp, left_on="ps_suppkey",
                             right_on="s_suppkey")
    ps = ps.assign(value=ps.ps_supplycost * ps.ps_availqty)
    g = ps.groupby("ps_partkey", as_index=False).agg(
        value=("value", "sum"))
    g = g[g.value > ps.value.sum() * 0.0001]
    return g.sort_values(["value", "ps_partkey"],
                         ascending=[False, True]).reset_index(drop=True)


# ---------------------------------------------------------------------------
# q12 — shipping modes and order priority (CASE pivots)
# ---------------------------------------------------------------------------


def q12(dfs):
    li = dfs["lineitem"].filter(
        col("l_shipmode").isin("MAIL", "SHIP")
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= lit(days(1994, 1, 1)))
        & (col("l_receiptdate") < lit(days(1995, 1, 1)))).select(
        "l_orderkey", "l_shipmode")
    j = li.join(dfs["orders"].select("o_orderkey", "o_orderpriority"),
                on=col("l_orderkey") == col("o_orderkey"))
    high = when(col("o_orderpriority").isin("1-URGENT", "2-HIGH"), 1) \
        .otherwise(0)
    low = when(col("o_orderpriority").isin("1-URGENT", "2-HIGH"), 0) \
        .otherwise(1)
    return (j.group_by("l_shipmode")
            .agg(("sum", high, "high_line_count"),
                 ("sum", low, "low_line_count")).sort("l_shipmode"))


def q12_pandas(t):
    li = t["lineitem"]
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"])
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= _date(1994, 1, 1))
            & (li.l_receiptdate < _date(1995, 1, 1))]
    j = li.merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    j = j.assign(high_line_count=hi.astype(int),
                 low_line_count=(~hi).astype(int))
    g = j.groupby("l_shipmode", as_index=False).agg(
        high_line_count=("high_line_count", "sum"),
        low_line_count=("low_line_count", "sum"))
    return g.sort_values("l_shipmode").reset_index(drop=True)


# ---------------------------------------------------------------------------
# q13 — customer distribution (left outer + NOT LIKE)
# ---------------------------------------------------------------------------


def q13(dfs):
    orders = dfs["orders"].filter(
        ~col("o_comment").like("%special%requests%")).select(
        "o_orderkey", "o_custkey")
    cust = dfs["customer"].select("c_custkey")
    j = cust.join(orders, on=col("c_custkey") == col("o_custkey"),
                  how="left_outer")
    per_cust = (j.group_by("c_custkey")
                .agg(("count", "o_orderkey", "c_count")))
    return (per_cust.group_by("c_count")
            .agg(("count", "*", "custdist"))
            .sort("-custdist", "-c_count"))


def q13_pandas(t):
    orders = t["orders"]
    orders = orders[~orders.o_comment.str.match(
        ".*special.*requests.*")][["o_orderkey", "o_custkey"]]
    j = t["customer"][["c_custkey"]].merge(
        orders, left_on="c_custkey", right_on="o_custkey", how="left")
    per = j.groupby("c_custkey", as_index=False).agg(
        c_count=("o_orderkey", "count"))
    g = per.groupby("c_count", as_index=False).agg(
        custdist=("c_custkey", "size"))
    return g.sort_values(["custdist", "c_count"],
                         ascending=[False, False]).reset_index(drop=True)


# ---------------------------------------------------------------------------
# q14 — promotion effect
# ---------------------------------------------------------------------------


def q14(dfs):
    li = dfs["lineitem"].filter(
        (col("l_shipdate") >= lit(days(1995, 9, 1)))
        & (col("l_shipdate") < lit(days(1995, 10, 1)))).select(
        "l_partkey", "l_extendedprice", "l_discount")
    j = li.join(dfs["part"].select("p_partkey", "p_type"),
                on=col("l_partkey") == col("p_partkey"))
    promo = when(col("p_type").like("PROMO%"), _volume()).otherwise(0.0)
    g = j.agg(("sum", promo, "promo"), ("sum", _volume(), "total"))
    return g.select((lit(100.0) * col("promo") / col("total"))
                    .alias("promo_revenue"))


def q14_pandas(t):
    li = t["lineitem"]
    li = li[(li.l_shipdate >= _date(1995, 9, 1))
            & (li.l_shipdate < _date(1995, 10, 1))]
    j = li.merge(t["part"], left_on="l_partkey", right_on="p_partkey")
    vol = j.l_extendedprice * (1 - j.l_discount)
    promo = vol[j.p_type.str.startswith("PROMO")].sum()
    return pd.DataFrame({"promo_revenue": [100.0 * promo / vol.sum()]})


# ---------------------------------------------------------------------------
# q15 — top supplier (scalar max via join-back on the aggregate)
# ---------------------------------------------------------------------------


def q15(dfs):
    li = dfs["lineitem"].filter(
        (col("l_shipdate") >= lit(days(1996, 1, 1)))
        & (col("l_shipdate") < lit(days(1996, 4, 1)))).select(
        "l_suppkey", "l_extendedprice", "l_discount")
    revenue = (li.group_by("l_suppkey")
               .agg(("sum", _volume(), "total_revenue")))
    top = revenue.agg(("max", "total_revenue", "max_revenue"))
    j = revenue.join(top,
                     on=col("total_revenue") == col("max_revenue"))
    j = j.join(dfs["supplier"].select("s_suppkey", "s_name", "s_address",
                                      "s_phone"),
               on=col("l_suppkey") == col("s_suppkey"))
    return (j.select("s_suppkey", "s_name", "s_address", "s_phone",
                     "total_revenue").sort("s_suppkey"))


def q15_pandas(t):
    li = t["lineitem"]
    li = li[(li.l_shipdate >= _date(1996, 1, 1))
            & (li.l_shipdate < _date(1996, 4, 1))]
    li = li.assign(vol=li.l_extendedprice * (1 - li.l_discount))
    rev = li.groupby("l_suppkey", as_index=False).agg(
        total_revenue=("vol", "sum"))
    top = rev[rev.total_revenue == rev.total_revenue.max()]
    j = top.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    return (j[["s_suppkey", "s_name", "s_address", "s_phone",
               "total_revenue"]].sort_values("s_suppkey")
            .reset_index(drop=True))


# ---------------------------------------------------------------------------
# q16 — parts/supplier relationship (anti join on complaints)
# ---------------------------------------------------------------------------


def q16(dfs):
    part = dfs["part"].filter(
        (col("p_brand") != lit("Brand#45"))
        & ~col("p_type").like("MEDIUM POLISHED%")
        & col("p_size").isin(49, 14, 23, 45, 19, 3, 36, 9)).select(
        "p_partkey", "p_brand", "p_type", "p_size")
    bad_supp = dfs["supplier"].filter(
        col("s_comment").like("%Customer%Complaints%")).select("s_suppkey")
    ps = dfs["partsupp"].select("ps_partkey", "ps_suppkey")
    ps = ps.join(bad_supp, on=col("ps_suppkey") == col("s_suppkey"),
                 how="left_anti")
    j = ps.join(part, on=col("ps_partkey") == col("p_partkey"))
    return (j.group_by("p_brand", "p_type", "p_size")
            .agg(("count_distinct", "ps_suppkey", "supplier_cnt"))
            .sort("-supplier_cnt", "p_brand", "p_type", "p_size"))


def q16_pandas(t):
    part = t["part"]
    part = part[(part.p_brand != "Brand#45")
                & ~part.p_type.str.startswith("MEDIUM POLISHED")
                & part.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    bad = t["supplier"][t["supplier"].s_comment.str.match(
        ".*Customer.*Complaints.*")].s_suppkey
    ps = t["partsupp"][~t["partsupp"].ps_suppkey.isin(bad)]
    j = ps.merge(part, left_on="ps_partkey", right_on="p_partkey")
    g = j.groupby(["p_brand", "p_type", "p_size"], as_index=False).agg(
        supplier_cnt=("ps_suppkey", "nunique"))
    return g.sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                         ascending=[False, True, True, True]) \
        .reset_index(drop=True)


# ---------------------------------------------------------------------------
# q17 — small-quantity-order revenue (correlated avg -> join-back)
# ---------------------------------------------------------------------------


def q17(dfs):
    part = dfs["part"].filter(
        (col("p_brand") == lit("Brand#23"))
        & (col("p_container") == lit("MED BOX"))).select("p_partkey")
    li = dfs["lineitem"].select("l_partkey", "l_quantity",
                                "l_extendedprice")
    avg_qty = (li.group_by("l_partkey")
               .agg(("avg", "l_quantity", "avg_qty")))
    j = li.join(part, on=col("l_partkey") == col("p_partkey"))
    j = j.join(avg_qty, on=col("l_partkey") == col("l_partkey"))
    j = j.filter(col("l_quantity") < col("avg_qty") * lit(0.2))
    g = j.agg(("sum", "l_extendedprice", "total"))
    return g.select((col("total") / lit(7.0)).alias("avg_yearly"))


def q17_pandas(t):
    part = t["part"]
    part = part[(part.p_brand == "Brand#23")
                & (part.p_container == "MED BOX")][["p_partkey"]]
    li = t["lineitem"]
    avg_qty = li.groupby("l_partkey", as_index=False).agg(
        avg_qty=("l_quantity", "mean"))
    j = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    j = j.merge(avg_qty, on="l_partkey")
    j = j[j.l_quantity < 0.2 * j.avg_qty]
    return pd.DataFrame({"avg_yearly": [j.l_extendedprice.sum() / 7.0]})


# ---------------------------------------------------------------------------
# q18 — large volume customers (HAVING sum > 300 -> semi join)
# ---------------------------------------------------------------------------


def q18(dfs):
    li = dfs["lineitem"].select("l_orderkey", "l_quantity")
    big = (li.group_by("l_orderkey").agg(("sum", "l_quantity", "sum_qty"))
           .having(col("sum_qty") > lit(300)).select("l_orderkey"))
    orders = dfs["orders"].select("o_orderkey", "o_custkey", "o_orderdate",
                                  "o_totalprice")
    orders = orders.join(big, on=col("o_orderkey") == col("l_orderkey"),
                         how="left_semi")
    j = orders.join(dfs["customer"].select("c_custkey", "c_name"),
                    on=col("o_custkey") == col("c_custkey"))
    j = li.join(j, on=col("l_orderkey") == col("o_orderkey"))
    return (j.group_by("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                       "o_totalprice")
            .agg(("sum", "l_quantity", "sum_qty"))
            .sort("-o_totalprice", "o_orderdate", "o_orderkey").limit(100))


def q18_pandas(t):
    li = t["lineitem"]
    sums = li.groupby("l_orderkey", as_index=False).agg(
        sum_qty=("l_quantity", "sum"))
    big = sums[sums.sum_qty > 300].l_orderkey
    orders = t["orders"][t["orders"].o_orderkey.isin(big)]
    j = orders.merge(t["customer"], left_on="o_custkey",
                     right_on="c_custkey")
    j = li.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"))
    return (g.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                          ascending=[False, True, True])
            .head(100).reset_index(drop=True))


# ---------------------------------------------------------------------------
# q19 — discounted revenue (OR-of-brackets above the part join)
# ---------------------------------------------------------------------------


def q19(dfs):
    li = dfs["lineitem"].filter(
        col("l_shipmode").isin("AIR", "REG AIR")
        & (col("l_shipinstruct") == lit("DELIVER IN PERSON"))).select(
        "l_partkey", "l_quantity", "l_extendedprice", "l_discount")
    part = dfs["part"].select("p_partkey", "p_brand", "p_container",
                              "p_size")
    j = li.join(part, on=col("l_partkey") == col("p_partkey"))
    b1 = ((col("p_brand") == lit("Brand#12"))
          & col("p_container").isin("SM CASE", "SM BOX", "SM PACK",
                                    "SM PKG")
          & col("l_quantity").between(lit(1), lit(11))
          & col("p_size").between(lit(1), lit(5)))
    b2 = ((col("p_brand") == lit("Brand#23"))
          & col("p_container").isin("MED BAG", "MED BOX", "MED PKG",
                                    "MED PACK")
          & col("l_quantity").between(lit(10), lit(20))
          & col("p_size").between(lit(1), lit(10)))
    b3 = ((col("p_brand") == lit("Brand#34"))
          & col("p_container").isin("LG CASE", "LG BOX", "LG PACK",
                                    "LG PKG")
          & col("l_quantity").between(lit(20), lit(30))
          & col("p_size").between(lit(1), lit(15)))
    j = j.filter(b1 | b2 | b3)
    return j.agg(("sum", _volume(), "revenue"))


def q19_pandas(t):
    li = t["lineitem"]
    li = li[li.l_shipmode.isin(["AIR", "REG AIR"])
            & (li.l_shipinstruct == "DELIVER IN PERSON")]
    j = li.merge(t["part"], left_on="l_partkey", right_on="p_partkey")
    b1 = ((j.p_brand == "Brand#12")
          & j.p_container.isin(["SM CASE", "SM BOX", "SM PACK", "SM PKG"])
          & j.l_quantity.between(1, 11) & j.p_size.between(1, 5))
    b2 = ((j.p_brand == "Brand#23")
          & j.p_container.isin(["MED BAG", "MED BOX", "MED PKG",
                                "MED PACK"])
          & j.l_quantity.between(10, 20) & j.p_size.between(1, 10))
    b3 = ((j.p_brand == "Brand#34")
          & j.p_container.isin(["LG CASE", "LG BOX", "LG PACK", "LG PKG"])
          & j.l_quantity.between(20, 30) & j.p_size.between(1, 15))
    j = j[b1 | b2 | b3]
    return pd.DataFrame({"revenue": [
        (j.l_extendedprice * (1 - j.l_discount)).sum()]})


# ---------------------------------------------------------------------------
# q20 — potential part promotion (nested IN -> semi joins + join-back)
# ---------------------------------------------------------------------------


def q20(dfs):
    part = dfs["part"].filter(col("p_name").like("forest%")) \
        .select("p_partkey")
    li = dfs["lineitem"].filter(
        (col("l_shipdate") >= lit(days(1994, 1, 1)))
        & (col("l_shipdate") < lit(days(1995, 1, 1)))).select(
        "l_partkey", "l_suppkey", "l_quantity")
    half = (li.group_by("l_partkey", "l_suppkey")
            .agg(("sum", "l_quantity", "qty_sum")))
    ps = dfs["partsupp"].select("ps_partkey", "ps_suppkey", "ps_availqty")
    ps = ps.join(part, on=col("ps_partkey") == col("p_partkey"),
                 how="left_semi")
    j = ps.join(half, on=(col("ps_partkey") == col("l_partkey"))
                & (col("ps_suppkey") == col("l_suppkey")))
    j = j.filter(col("ps_availqty") > col("qty_sum") * lit(0.5))
    supp = dfs["supplier"].select("s_suppkey", "s_name", "s_address",
                                  "s_nationkey")
    supp = supp.join(j.select("ps_suppkey"),
                     on=col("s_suppkey") == col("ps_suppkey"),
                     how="left_semi")
    nation = dfs["nation"].filter(col("n_name") == lit("CANADA")) \
        .select("n_nationkey")
    supp = supp.join(nation, on=col("s_nationkey") == col("n_nationkey"))
    return supp.select("s_name", "s_address").sort("s_name")


def q20_pandas(t):
    part = t["part"][t["part"].p_name.str.startswith("forest")][
        ["p_partkey"]]
    li = t["lineitem"]
    li = li[(li.l_shipdate >= _date(1994, 1, 1))
            & (li.l_shipdate < _date(1995, 1, 1))]
    half = li.groupby(["l_partkey", "l_suppkey"], as_index=False).agg(
        qty_sum=("l_quantity", "sum"))
    ps = t["partsupp"][t["partsupp"].ps_partkey.isin(part.p_partkey)]
    j = ps.merge(half, left_on=["ps_partkey", "ps_suppkey"],
                 right_on=["l_partkey", "l_suppkey"])
    j = j[j.ps_availqty > 0.5 * j.qty_sum]
    nation = t["nation"][t["nation"].n_name == "CANADA"][["n_nationkey"]]
    supp = t["supplier"][t["supplier"].s_suppkey.isin(j.ps_suppkey)]
    supp = supp.merge(nation, left_on="s_nationkey",
                      right_on="n_nationkey")
    return (supp[["s_name", "s_address"]].sort_values("s_name")
            .reset_index(drop=True))


# ---------------------------------------------------------------------------
# q21 — suppliers who kept orders waiting
# ---------------------------------------------------------------------------


def q21(dfs):
    li = dfs["lineitem"].select("l_orderkey", "l_suppkey", "l_commitdate",
                                "l_receiptdate")
    # Per order: distinct suppliers overall and among LATE lines. The
    # official EXISTS l2 == ">= 2 distinct suppliers"; NOT EXISTS l3 ==
    # "exactly 1 distinct supplier among late lines" (l1 is late, so that
    # one supplier is l1's).
    n_supp = (li.group_by("l_orderkey")
              .agg(("count_distinct", "l_suppkey", "n_supp")))
    late = li.filter(col("l_receiptdate") > col("l_commitdate"))
    n_late = (late.group_by("l_orderkey")
              .agg(("count_distinct", "l_suppkey", "n_late_supp")))
    orders = dfs["orders"].filter(col("o_orderstatus") == lit("F")) \
        .select("o_orderkey")
    j = late.select("l_orderkey", "l_suppkey").join(
        orders, on=col("l_orderkey") == col("o_orderkey"), how="left_semi")
    j = j.join(n_supp, on=col("l_orderkey") == col("l_orderkey"))
    j = j.join(n_late, on=col("l_orderkey") == col("l_orderkey"))
    j = j.filter((col("n_supp") >= lit(2)) & (col("n_late_supp") == lit(1)))
    supp = dfs["supplier"].select("s_suppkey", "s_name", "s_nationkey")
    nation = dfs["nation"].filter(col("n_name") == lit("SAUDI ARABIA")) \
        .select("n_nationkey")
    supp = supp.join(nation, on=col("s_nationkey") == col("n_nationkey"))
    j = j.join(supp, on=col("l_suppkey") == col("s_suppkey"))
    return (j.group_by("s_name").agg(("count", "*", "numwait"))
            .sort("-numwait", "s_name").limit(100))


def q21_pandas(t):
    li = t["lineitem"]
    n_supp = li.groupby("l_orderkey").l_suppkey.nunique()
    late = li[li.l_receiptdate > li.l_commitdate]
    n_late = late.groupby("l_orderkey").l_suppkey.nunique()
    orders = set(t["orders"][t["orders"].o_orderstatus == "F"].o_orderkey)
    j = late[late.l_orderkey.isin(orders)].copy()
    j = j[j.l_orderkey.map(n_supp).ge(2)
          & j.l_orderkey.map(n_late).eq(1)]
    nation = t["nation"][t["nation"].n_name == "SAUDI ARABIA"]
    supp = t["supplier"].merge(nation, left_on="s_nationkey",
                               right_on="n_nationkey")
    j = j.merge(supp, left_on="l_suppkey", right_on="s_suppkey")
    g = j.groupby("s_name", as_index=False).agg(
        numwait=("l_orderkey", "size"))
    return (g.sort_values(["numwait", "s_name"], ascending=[False, True])
            .head(100).reset_index(drop=True))


# ---------------------------------------------------------------------------
# q22 — global sales opportunity (anti join + scalar avg + SUBSTR group)
# ---------------------------------------------------------------------------


def q22(dfs):
    codes = ("13", "31", "23", "29", "30", "18", "17")
    cust = dfs["customer"].select(
        col("c_phone").substr(1, 2).alias("cntrycode"), "c_acctbal",
        "c_custkey")
    cust = cust.filter(col("cntrycode").isin(*codes))
    pos_avg = (cust.filter(col("c_acctbal") > lit(0.0))
               .agg(("avg", "c_acctbal", "avg_bal")))
    cust = cust.join(pos_avg, how="cross")
    cust = cust.filter(col("c_acctbal") > col("avg_bal"))
    orders = dfs["orders"].select("o_custkey")
    cust = cust.join(orders, on=col("c_custkey") == col("o_custkey"),
                     how="left_anti")
    return (cust.group_by("cntrycode")
            .agg(("count", "*", "numcust"), ("sum", "c_acctbal", "totacctbal"))
            .sort("cntrycode"))


def q22_pandas(t):
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cust = t["customer"].copy()
    cust["cntrycode"] = cust.c_phone.str[:2]
    cust = cust[cust.cntrycode.isin(codes)]
    avg_bal = cust[cust.c_acctbal > 0.0].c_acctbal.mean()
    cust = cust[cust.c_acctbal > avg_bal]
    cust = cust[~cust.c_custkey.isin(t["orders"].o_custkey)]
    g = cust.groupby("cntrycode", as_index=False).agg(
        numcust=("c_custkey", "size"), totacctbal=("c_acctbal", "sum"))
    return g.sort_values("cntrycode").reset_index(drop=True)


# ---------------------------------------------------------------------------
# Registry + index definitions
# ---------------------------------------------------------------------------


QUERIES: Dict[str, Tuple[Callable, Callable]] = {
    "q1": (q1, q1_pandas), "q2": (q2, q2_pandas), "q3": (q3, q3_pandas),
    "q4": (q4, q4_pandas), "q5": (q5, q5_pandas), "q6": (q6, q6_pandas),
    "q7": (q7, q7_pandas), "q8": (q8, q8_pandas), "q9": (q9, q9_pandas),
    "q10": (q10, q10_pandas), "q11": (q11, q11_pandas),
    "q12": (q12, q12_pandas), "q13": (q13, q13_pandas),
    "q14": (q14, q14_pandas), "q15": (q15, q15_pandas),
    "q16": (q16, q16_pandas), "q17": (q17, q17_pandas),
    "q18": (q18, q18_pandas), "q19": (q19, q19_pandas),
    "q20": (q20, q20_pandas), "q21": (q21, q21_pandas),
    "q22": (q22, q22_pandas),
}


# (index name, table, (indexed, included), used by) — the hot equi-join
# pairs (lineitem<->orders on the order key; lineitem<->part on the part
# key) plus the shipdate filter index q1/q6 can cover.
_INDEX_DEFS = [
    ("tpch_li_ord", "lineitem", (["l_orderkey"],
     ["l_suppkey", "l_extendedprice", "l_discount", "l_quantity",
      "l_shipdate", "l_returnflag"]),
     ("q3", "q5", "q7", "q10", "q18")),
    ("tpch_ord_key", "orders", (["o_orderkey"],
     ["o_custkey", "o_orderdate", "o_shippriority", "o_totalprice",
      "o_orderpriority"]),
     ("q3", "q5", "q7", "q10", "q12", "q18")),
    ("tpch_li_part", "lineitem", (["l_partkey"],
     ["l_suppkey", "l_quantity", "l_extendedprice", "l_discount",
      "l_shipdate", "l_shipmode", "l_shipinstruct"]),
     ("q8", "q9", "q14", "q17", "q19")),
    ("tpch_part_key", "part", (["p_partkey"],
     ["p_brand", "p_type", "p_size", "p_container", "p_name", "p_mfgr"]),
     ("q8", "q9", "q14", "q17", "q19")),
    ("tpch_li_ship", "lineitem", (["l_shipdate"],
     ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
      "l_returnflag", "l_linestatus"]),
     ("q1", "q6")),
]


def create_indexes(hs, dfs, queries=None, skip=()) -> None:
    """Build the covering indexes the given queries (default: all) can
    use — the hot join pairs and the shipdate filter index."""
    from hyperspace_tpu import IndexConfig

    wanted = None if queries is None else set(queries)
    for name, table, (indexed, included), used_by in _INDEX_DEFS:
        if wanted is not None and not (wanted & set(used_by)):
            continue
        if name in skip:
            continue
        hs.create_index(dfs[table], IndexConfig(name, indexed, included))
