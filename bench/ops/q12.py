"""The operation `q12`: TPC-H query 12 (specification clause 2.4.12,
"Shipping Modes and Order Priority") through the covering indexes:
`lineitem` filtered by ship mode and by its three dates within one year
of receipt, joined to `orders` on the order key, counted per ship mode
by whether the order's priority is high. The substitution parameters
(`shipmodes`, `year`) are the mix's; the validation run's are MAIL,
SHIP and 1994. Two rows of three columns come back, so nothing large
crosses the link.

The `select` operation's `run` and `check` serve; this file gives the
query and names its own plain reference (`reference/q12.py`).
"""

from __future__ import annotations

import datetime
import os

from lib import plugins

HIGH = ("1-URGENT", "2-HIGH")


def _days(year: int) -> int:
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Op(plugins.load(_BENCH, "ops", "select").Op):
    reference = "q12"

    def params(self, i: int, warming: bool) -> dict:
        return self.control_params(self.query, self.dep.dataset, None, None)

    @staticmethod
    def control_params(query: dict, dataset, scale_factor, seed) -> dict:
        """The same for every query and seed: what the reference needs,
        as codes and days."""
        q, vocab = query, dataset.VOCABULARY
        return {
            "shipmode_codes": [vocab["l_shipmode"].index(m)
                               for m in q["shipmodes"]],
            "high_codes": [vocab["o_orderpriority"].index(p)
                           for p in HIGH],
            "receipt_lo": _days(q["year"]),
            "receipt_hi": _days(q["year"] + 1)}

    def dataframe(self, params: dict):
        from hyperspace_tpu import col, lit
        from hyperspace_tpu.plan.expr import when

        q, dfs = self.query, self.dep.dfs
        li = dfs["lineitem"].filter(
            col("l_shipmode").isin(*q["shipmodes"])
            & (col("l_commitdate") < col("l_receiptdate"))
            & (col("l_shipdate") < col("l_commitdate"))
            & (col("l_receiptdate") >= lit(params["receipt_lo"]))
            & (col("l_receiptdate") < lit(params["receipt_hi"]))
        ).select("l_orderkey", "l_shipmode")
        j = li.join(dfs["orders"].select("o_orderkey", "o_orderpriority"),
                    on=col("l_orderkey") == col("o_orderkey"))
        high = when(col("o_orderpriority").isin(*HIGH), 1).otherwise(0)
        low = when(col("o_orderpriority").isin(*HIGH), 0).otherwise(1)
        return (j.group_by("l_shipmode")
                .agg(("sum", high, "high_line_count"),
                     ("sum", low, "low_line_count"))
                .sort("l_shipmode"))
