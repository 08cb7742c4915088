"""The plain reference of the operation `q12` (TPC-H query 12, clause
2.4.12), in numpy over the generated tables alone: dictionary columns
are their codes and dates are days, as the dataset makes them. Imports
nothing of the program."""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, tables: dict):
        self.tables = tables

    def answer(self, query: dict, params: dict) -> dict:
        li, orders = self.tables["lineitem"], self.tables["orders"]
        keep = (np.isin(li["l_shipmode"], params["shipmode_codes"])
                & (li["l_commitdate"] < li["l_receiptdate"])
                & (li["l_shipdate"] < li["l_commitdate"])
                & (li["l_receiptdate"] >= params["receipt_lo"])
                & (li["l_receiptdate"] < params["receipt_hi"]))
        keys = li["l_orderkey"][keep]
        modes = li["l_shipmode"][keep].astype(np.int64)
        # orders' key is unique: a table from key to its row
        o_key = orders["o_orderkey"]
        row_of = np.full(int(o_key.max()) + 1, -1, dtype=np.int64)
        row_of[o_key] = np.arange(len(o_key))
        rows = row_of[keys]
        matched = rows >= 0  # inner join
        high = np.isin(orders["o_orderpriority"][rows[matched]],
                       params["high_codes"])
        modes = modes[matched]
        groups = np.unique(modes)
        return {
            "l_shipmode": groups,
            "high_line_count": np.array(
                [int(np.count_nonzero(high[modes == g])) for g in groups],
                dtype=np.int64),
            "low_line_count": np.array(
                [int(np.count_nonzero(~high[modes == g])) for g in groups],
                dtype=np.int64)}
