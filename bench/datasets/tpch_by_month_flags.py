"""The dataset `tpch_by_month_flags`: the dataset `tpch_by_month`'s
`lineitem`, to the bit and in the same month files, but for its two
flag columns, which follow the specification's rule (clause 4.2.3)
instead of being drawn uniformly:

    l_linestatus   'O' where l_shipdate > CURRENTDATE, else 'F'
    l_returnflag   'R' or 'A' at random where l_receiptdate <= CURRENTDATE,
                   else 'N'

with CURRENTDATE 1995-06-17. R against A is a fixed hash of the order
key and line number (`tpch._hashed`, as `tpch` makes its dates), so the
four groups TPC-H Q1 reports hold the same lines for every seed: N-O
about half the table, A-F and R-F about a quarter each, N-F the lines
shipped before CURRENTDATE and received after it. The seed still sets
prices, taxes and the rest of the payload.

Nothing here imports the program under test.
"""

from __future__ import annotations

import datetime
import os

import numpy as np

from lib import plugins

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
by_month = plugins.load(_BENCH, "datasets", "tpch_by_month")
tpch = by_month.tpch

# the interface every dataset has
VOCABULARY = by_month.VOCABULARY
DATE_COLUMNS = by_month.DATE_COLUMNS
write_parquet = by_month.write_parquet
file_months = by_month.file_months

CURRENTDATE = (datetime.date(1995, 6, 17) - datetime.date(1970, 1, 1)).days


def flags(l_orderkey: np.ndarray, l_linenumber: np.ndarray,
          l_shipdate: np.ndarray, l_receiptdate: np.ndarray) -> dict:
    """`l_returnflag` and `l_linestatus` of each line by clause 4.2.3, as
    codes into VOCABULARY."""
    code = {c: {v: i for i, v in enumerate(VOCABULARY[c])}
            for c in ("l_returnflag", "l_linestatus")}
    rf, ls = code["l_returnflag"], code["l_linestatus"]
    line_id = l_orderkey * 8 + l_linenumber  # as tpch's date hashes
    returned = np.where(tpch._hashed(line_id, 7, 2) == 1, rf["R"], rf["A"])
    return {
        "l_returnflag": np.where(l_receiptdate <= CURRENTDATE, returned,
                                 rf["N"]).astype(np.int8),
        "l_linestatus": np.where(l_shipdate > CURRENTDATE, ls["O"],
                                 ls["F"]).astype(np.int8)}


def make(config: dict, seed: int, scale_factor: float) -> dict:
    """`tpch_by_month.make`'s tables, the flags set by clause 4.2.3."""
    made = by_month.make(config, seed, scale_factor)
    li = made["lineitem"]
    li.update(flags(li["l_orderkey"], li["l_linenumber"], li["l_shipdate"],
                    li["l_receiptdate"]))
    return made
