"""The plain reference of the operation `tpch_q1`: TPC-H query 1
("Pricing Summary Report", specification clause 2.4.1) over the whole
generated `lineitem`, not over the lake's files or the index. Imports
nothing of the program and takes nothing the program made.

    SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
           sum(l_extendedprice) AS sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax))
               AS sum_charge,
           avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
           avg(l_discount) AS avg_disc, count(*) AS count_order
    FROM lineitem
    WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '[DELTA]' DAY
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus

Every sum is exact: quantities are whole numbers, prices whole cents,
discounts and taxes whole hundredths (the dataset draws them so), so
each sum is an int64 sum of integers: `sum_qty` in units, `sum_base_price`
in cents, `sum_disc_price` in 10^-4 and `sum_charge` in 10^-6 dollars (a
line's charge is at most about 1.1e11 of those, 29 M lines about 3.4e18,
under 2^63). Each average is the exact quotient of two of them.

The answer is the report as ONE record: for each group present, its
count and exact sums as columns named `<flag>_<status>.<what>`, the
flag and status by their dictionary codes. The operation judges an
answer whole (any group off, the answer is wrong), and so does this
shape under the harness's row comparison.
"""

from __future__ import annotations

import numpy as np

def cutoff(delta: int) -> int:
    """DATE '1998-12-01' - DELTA days, as days since the epoch."""
    return int(np.datetime64("1998-12-01", "D").astype(np.int64)) \
        - int(delta)


def hundredths(values: np.ndarray) -> np.ndarray:
    """Two-place decimals as whole hundredths."""
    return np.rint(np.asarray(values) * 100.0).astype(np.int64)


class Reference:
    def __init__(self, tables: dict):
        self.tables = tables

    def groups(self, query: dict, params: dict) -> dict:
        """{(returnflag code, linestatus code): {count_order, sum_qty,
        sum_base_price_e2, sum_disc_price_e4, sum_charge_e6, sum_disc_e2:
        exact ints}}, the groups in key order."""
        li = self.tables[query.get("table", "lineitem")]
        keep = li["l_shipdate"] <= cutoff(params["delta"])
        group = (li["l_returnflag"][keep].astype(np.int64) * 256
                 + li["l_linestatus"][keep].astype(np.int64))
        qty = np.rint(li["l_quantity"][keep]).astype(np.int64)
        price = hundredths(li["l_extendedprice"][keep])
        disc = hundredths(li["l_discount"][keep])
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + hundredths(li["l_tax"][keep]))
        out = {}
        for g in np.unique(group):
            m = group == g
            out[(int(g) // 256, int(g) % 256)] = {
                "count_order": int(np.count_nonzero(m)),
                "sum_qty": int(qty[m].sum()),
                "sum_base_price_e2": int(price[m].sum()),
                "sum_disc_price_e4": int(disc_price[m].sum()),
                "sum_charge_e6": int(charge[m].sum()),
                "sum_disc_e2": int(disc[m].sum())}
        return out

    def answer(self, query: dict, params: dict) -> dict:
        """The report as one record (module docstring)."""
        return {f"{flag}_{status}.{name}": np.array([value], dtype=np.int64)
                for (flag, status), sums in self.groups(query, params).items()
                for name, value in sums.items()}
