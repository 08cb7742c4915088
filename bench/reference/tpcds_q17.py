"""The plain reference of the operation `tpcds_q17`: TPC-DS query 17
("quarterly store and catalog behaviour of returned items") as the
specification's SQL states it, in numpy over the generated tables alone
(strings are their codes into the dataset's sorted vocabularies, so a
code's order is its string's). Imports nothing of the program and
takes nothing the program made.

    SELECT i_item_id, i_item_desc, s_state,
           count(ss_quantity), avg(ss_quantity), stddev_samp(ss_quantity),
           stddev_samp(ss_quantity) / avg(ss_quantity),
           ... the same of sr_return_quantity and of cs_quantity
    FROM store_sales, store_returns, catalog_sales, date_dim d1,
         date_dim d2, date_dim d3, store, item
    WHERE d1.d_quarter_name = '[YEAR]Q1' AND d1.d_date_sk = ss_sold_date_sk
      AND i_item_sk = ss_item_sk AND s_store_sk = ss_store_sk
      AND ss_customer_sk = sr_customer_sk AND ss_item_sk = sr_item_sk
      AND ss_ticket_number = sr_ticket_number
      AND sr_returned_date_sk = d2.d_date_sk
      AND d2.d_quarter_name IN ('[YEAR]Q1', '[YEAR]Q2', '[YEAR]Q3')
      AND sr_customer_sk = cs_bill_customer_sk AND sr_item_sk = cs_item_sk
      AND cs_sold_date_sk = d3.d_date_sk
      AND d3.d_quarter_name IN ('[YEAR]Q1', '[YEAR]Q2', '[YEAR]Q3')
    GROUP BY i_item_id, i_item_desc, s_state
    ORDER BY i_item_id, i_item_desc, s_state
    LIMIT 100

Departures, each deliberate:
- the date predicates are applied to each fact table before the joins
  (the WHERE clause is one conjunction, so the rows are the same);
- avg and stddev_samp are computed from each group's exact integer
  moments (n, the sum S, the sum of squares Q of the integer
  quantities, Python integers, whose true division Python rounds
  correctly) and rounded once to the nearest float64:
  avg = S / n, and stddev_samp = sqrt of the variance
  (n Q - S^2) / (n (n - 1)) rounded to the nearest float64 (that is
  sum((x - mean)^2) / (n - 1) exactly), the square root rounded again.
  This is the value the SQL defines, to the nearest double; a NULL
  stddev_samp (fewer than two values) is NaN here. Each `*_quantitycov`
  is the quotient of those two doubles, one IEEE float64 division, as
  SQL divides a double by a double (NULL where stddev_samp is).
"""

from __future__ import annotations

import numpy as np

QUANTITIES = (("ss_quantity", "store_sales_quantity"),
              ("sr_return_quantity", "store_returns_quantity"),
              ("cs_quantity", "catalog_sales_quantity"))
LIMIT = 100


def _dense(columns) -> np.ndarray:
    """One int64 id per row of the key tuples in `columns` (equal tuples,
    equal ids; ids ordered as the tuples are), by pairwise densifying:
    exact for any integer keys."""
    ids = None
    for c in columns:
        _, col = np.unique(c, return_inverse=True)
        col = col.astype(np.int64)
        if ids is None:
            ids = col
        else:
            _, ids = np.unique(ids * (int(col.max()) + 1) + col,
                               return_inverse=True)
            ids = ids.astype(np.int64)
    return ids


def _join(left: list, right: list):
    """(left rows, right rows) of the inner equi-join of two key-tuple
    lists (every pair of rows with equal tuples)."""
    n = len(left[0])
    ids = _dense([np.concatenate([a, b]) for a, b in zip(left, right)])
    lid, rid = ids[:n], ids[n:]
    order = np.argsort(rid, kind="stable")
    rs = rid[order]
    lo = np.searchsorted(rs, lid, side="left")
    hi = np.searchsorted(rs, lid, side="right")
    counts = hi - lo
    li = np.repeat(np.arange(n, dtype=np.int64), counts)
    offset = np.arange(len(li), dtype=np.int64) - np.repeat(
        np.cumsum(counts) - counts, counts)
    return li, order[lo[li] + offset]


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """(probe rows that find a key, the row of the key each finds) for a
    unique-key dimension."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    at = np.minimum(np.searchsorted(sk, probe), len(sk) - 1)
    hit = sk[at] == probe
    return np.flatnonzero(hit), order[at[hit]]


def exact_avg(n: int, s: int) -> float:
    return s / n  # Python ints: one correct rounding


def exact_stddev_samp(n: int, s: int, q: int) -> float:
    if n < 2:
        return np.nan
    return float(np.sqrt((n * q - s * s) / (n * (n - 1))))


class Reference:
    def __init__(self, tables: dict):
        self.tables = tables

    def answer(self, query: dict, params: dict) -> dict:
        t = self.tables
        ss, sr, cs = t["store_sales"], t["store_returns"], t["catalog_sales"]
        d = t["date_dim"]
        quarter = d["d_quarter_name"]
        d1 = d["d_date_sk"][np.isin(quarter, params["d1_codes"])]
        d23 = d["d_date_sk"][np.isin(quarter, params["d23_codes"])]

        s_rows = np.flatnonzero(np.isin(ss["ss_sold_date_sk"], d1))
        r_rows = np.flatnonzero(np.isin(sr["sr_returned_date_sk"], d23))
        c_rows = np.flatnonzero(np.isin(cs["cs_sold_date_sk"], d23))

        a, b = _join(
            [ss[c][s_rows] for c in ("ss_customer_sk", "ss_item_sk",
                                     "ss_ticket_number")],
            [sr[c][r_rows] for c in ("sr_customer_sk", "sr_item_sk",
                                     "sr_ticket_number")])
        s_rows, r_rows = s_rows[a], r_rows[b]
        a, b = _join([sr[c][r_rows] for c in ("sr_customer_sk", "sr_item_sk")],
                     [cs[c][c_rows] for c in ("cs_bill_customer_sk",
                                              "cs_item_sk")])
        s_rows, r_rows, c_rows = s_rows[a], r_rows[a], c_rows[b]

        at, store_row = _lookup(t["store"]["s_store_sk"],
                                ss["ss_store_sk"][s_rows])
        s_rows, r_rows, c_rows = s_rows[at], r_rows[at], c_rows[at]
        state = t["store"]["s_state"][store_row].astype(np.int64)
        at, item_row = _lookup(t["item"]["i_item_sk"],
                               ss["ss_item_sk"][s_rows])
        s_rows, r_rows, c_rows = s_rows[at], r_rows[at], c_rows[at]
        state = state[at]
        item_id = t["item"]["i_item_id"][item_row].astype(np.int64)
        item_desc = t["item"]["i_item_desc"][item_row].astype(np.int64)

        values = {"ss_quantity": ss["ss_quantity"][s_rows],
                  "sr_return_quantity": sr["sr_return_quantity"][r_rows],
                  "cs_quantity": cs["cs_quantity"][c_rows]}
        group = _dense([item_id, item_desc, state])  # ordered as the keys
        order = np.argsort(group, kind="stable")
        group = group[order]
        starts = np.flatnonzero(np.concatenate([[True],
                                                group[1:] != group[:-1]]))
        starts = starts[:LIMIT]
        ends = np.append(starts[1:], np.searchsorted(group, group[starts[-1]],
                                                     side="right")) \
            if len(starts) else starts
        first = order[starts]
        out = {"i_item_id": item_id[first], "i_item_desc": item_desc[first],
               "s_state": state[first]}
        for column, alias in QUANTITIES:
            x = values[column][order].astype(np.int64)
            counts, avgs, stds = [], [], []
            for lo, hi in zip(starts, ends):
                part = [int(v) for v in x[lo:hi]]
                n, s, q = len(part), sum(part), sum(v * v for v in part)
                counts.append(n)
                avgs.append(exact_avg(n, s))
                stds.append(exact_stddev_samp(n, s, q))
            out[alias + "count"] = np.array(counts, dtype=np.int64)
            out[alias + "ave"] = np.array(avgs, dtype=np.float64)
            out[alias + "stdev"] = np.array(stds, dtype=np.float64)
            out[alias + "cov"] = out[alias + "stdev"] / out[alias + "ave"]
        return out
