"""The plain reference of the operation `tpch_q6_skip`: TPC-H query 6
("Forecasting Revenue Change", specification clause 2.4.6) over the
whole generated `lineitem`, not over the lake's files, so that a file
pruned wrongly shows as a wrong answer. Imports nothing of the program
and takes nothing the program made.

    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '[DATE]'
      AND l_shipdate < DATE '[DATE]' + INTERVAL '1' YEAR
      AND l_discount BETWEEN [DISCOUNT] - 0.01 AND [DISCOUNT] + 0.01
      AND l_quantity < [QUANTITY]

The revenue is exact: every price and discount is a decimal of two
places (the dataset draws them as whole cents and hundredths), so
round(price x 100) x round(discount x 100), summed in Python integers,
is the decimal answer in units of 10^-4. The predicate on the discount
is applied to the same whole hundredths, as SQL compares decimals.
"""

from __future__ import annotations

import numpy as np


def year_days(year: int) -> tuple:
    """[January 1 of `year`, January 1 of the next) as days since the
    epoch."""
    return tuple(int(np.datetime64(f"{y:04d}-01-01", "D").astype(np.int64))
                 for y in (year, year + 1))


def hundredths(values: np.ndarray) -> np.ndarray:
    """Two-place decimals as whole hundredths."""
    return np.rint(np.asarray(values) * 100.0).astype(np.int64)


def kept(lineitem: dict, query: dict, year: int) -> np.ndarray:
    """The rows Q6's predicate keeps, as a mask."""
    lo, hi = year_days(year)
    ship = lineitem["l_shipdate"]
    disc = hundredths(lineitem["l_discount"])
    d = int(round(query["discount"] * 100))
    return ((ship >= lo) & (ship < hi) & (disc >= d - 1) & (disc <= d + 1)
            & (lineitem["l_quantity"] < query["quantity"]))


def revenue_e4(lineitem: dict, mask: np.ndarray) -> int:
    """sum(l_extendedprice x l_discount) over `mask`, exactly, in units
    of 10^-4: a product of whole cents (under 10^7) and hundredths
    (under 10^2) is under 2^30, so an int64 sum of them is exact for
    any table under 2^33 rows."""
    cents = hundredths(lineitem["l_extendedprice"][mask])
    disc = hundredths(lineitem["l_discount"][mask])
    return int(np.sum(cents * disc, dtype=np.int64))


class Reference:
    def __init__(self, tables: dict):
        self.tables = tables

    def answer(self, query: dict, params: dict) -> dict:
        """{"revenue_e4": the exact revenue in units of 10^-4, "rows":
        the lines the predicate keeps}, one row, for the year `params`
        names."""
        lineitem = self.tables[query.get("table", "lineitem")]
        mask = kept(lineitem, query, params["year"])
        return {"revenue_e4": np.array([revenue_e4(lineitem, mask)]),
                "rows": np.array([int(mask.sum())])}
