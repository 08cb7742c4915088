"""The dataset `tpch_rf1`: the dataset `tpch` (loaded by name; `make`
returns its base tables unchanged for the same seed) and, made apart
from them, the rows that TPC-H's New Sales refresh function RF1 inserts:
each refresh set is SF x 1,500 new ORDERS rows (0.1% of the table) and
the 1-7 LINEITEM rows of each (the specification's pseudo-code: LOOP
(SF * 1500) TIMES insert an order, LOOP RANDOM(1, 7) TIMES insert a
line). A driver lands them after the indexes are built
(`drivers/closed_loop_refreshed.py`), one Parquet file a table a set.

What sets a count follows the key alone here too: the new order keys
are dense above the base's, N+1 .. N+sets*per_set in set order (the
specification fills the gaps its sparse keys leave), an order has
1 + key mod 7 lines, and the columns TPC-H's queries filter on are
`tpch`'s fixed hash of (key, line number). So every set's row counts
and the rows a predicate keeps are the same for every seed; the seed
sets the other payload and the order of rows within a set's file.
Columns, widths, vocabularies and date ranges are `tpch`'s, by its own
functions.

Nothing here imports the program under test.
"""

from __future__ import annotations

import os

import numpy as np

from lib import plugins

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tpch = plugins.load(_BENCH, "datasets", "tpch")

# the interface every dataset has, and what ops and readers ask of `tpch`
VOCABULARY = tpch.VOCABULARY
DATE_COLUMNS = tpch.DATE_COLUMNS
make = tpch.make
write_parquet = tpch.write_parquet
order_count = tpch.order_count
lineitem_count = tpch.lineitem_count
range_width = tpch.range_width
lines_in_range = tpch.lines_in_range

ORDERS_PER_SET_PER_SF = 1_500  # RF1: SF x 1,500 orders a set


def orders_per_set(scale_factor: float) -> int:
    return max(1, int(round(ORDERS_PER_SET_PER_SF * scale_factor)))


def set_keys(scale_factor: float, i: int) -> np.ndarray:
    """The order keys of refresh set `i` (0-based), ascending."""
    n, per = order_count(scale_factor), orders_per_set(scale_factor)
    return np.arange(n + 1 + i * per, n + 1 + (i + 1) * per, dtype=np.int64)


def _set_tables(keys: np.ndarray, scale_factor: float, rng,
                edge_every: int) -> dict:
    """One refresh set's `orders` and `lineitem` rows for `keys`, with
    `tpch`'s columns and types; rows in a seeded order."""
    n = len(keys)
    n_base = order_count(scale_factor)
    o_key = keys[rng.permutation(n)]
    lines = tpch.lines_of(keys)
    m = int(lines.sum())
    l_key = np.repeat(keys, lines)
    first = np.repeat(np.cumsum(lines) - lines, lines)
    l_line = np.arange(m, dtype=np.int64) - first + 1
    order = rng.permutation(m)
    l_key, l_line = l_key[order], l_line[order]
    line_id = l_key * 8 + l_line
    ordered = tpch._order_date(l_key)
    ship = ordered + 1 + tpch._hashed(line_id, 1, 121)
    orders = {
        "o_orderkey": o_key,
        "o_custkey": rng.integers(1, max(2, n_base // 10) + 1, n),
        "o_orderstatus": rng.integers(0, 3, n, dtype=np.int8),
        "o_totalprice": tpch._money(rng, n, 500_000.0, edge_every),
        "o_orderdate": tpch._order_date(o_key),
        "o_orderpriority": rng.integers(0, 5, n, dtype=np.int8),
        "o_clerk": rng.integers(0, 1000, n, dtype=np.int16),
        "o_shippriority": np.zeros(n, dtype=np.int64),
        "o_comment": rng.integers(0, 4096, n, dtype=np.int16),
    }
    lineitem = {
        "l_orderkey": l_key,
        "l_partkey": rng.integers(
            1, max(2, int(200_000 * scale_factor)) + 1, m),
        "l_suppkey": rng.integers(
            1, max(2, int(10_000 * scale_factor)) + 1, m),
        "l_linenumber": l_line,
        "l_quantity": rng.integers(1, 51, m).astype(np.float64),
        "l_extendedprice": tpch._money(rng, m, 105_000.0, edge_every),
        "l_discount": rng.integers(0, 11, m) / 100.0,
        "l_tax": rng.integers(0, 9, m) / 100.0,
        "l_returnflag": rng.integers(0, 3, m, dtype=np.int8),
        "l_linestatus": rng.integers(0, 2, m, dtype=np.int8),
        "l_shipdate": ship,
        "l_commitdate": ordered + 30 + tpch._hashed(line_id, 2, 61),
        "l_receiptdate": ship + 1 + tpch._hashed(line_id, 3, 30),
        "l_shipinstruct": rng.integers(0, 4, m, dtype=np.int8),
        "l_shipmode": tpch._hashed(line_id, 4, 7).astype(np.int8),
        "l_comment": rng.integers(0, 4096, m, dtype=np.int16),
    }
    return {"orders": {c: orders[c] for c in tpch.ORDERS_COLUMNS},
            "lineitem": {c: lineitem[c] for c in tpch.LINEITEM_COLUMNS}}


def refresh_sets(config: dict, seed: int, scale_factor: float) -> list:
    """The configuration's `refresh.sets` RF1 sets, in the order they
    land: [{"orders": {column: ndarray}, "lineitem": {...}}, ...] with
    the base tables' columns and dtypes."""
    return [_set_tables(set_keys(scale_factor, i), scale_factor,
                        np.random.default_rng([int(seed), 0x7C4, 0xF1, i]),
                        config["edge_every"])
            for i in range(int(config["refresh"]["sets"]))]


def land_set(columns: dict, directory: str, i: int) -> str:
    """Refresh set `i`'s rows of one table as ONE new Parquet file in
    the table's own directory, beside the base's files; its path."""
    import pyarrow.parquet as pq

    path = os.path.join(directory, f"part-rf1-{i:05d}.parquet")
    pq.write_table(tpch.to_arrow(columns), path)
    return path


def whole(base: dict, sets: list) -> dict:
    """One table's columns over the base and every landed set: what the
    plain reference answers over."""
    return {c: np.concatenate([base[c]] + [s[c] for s in sets])
            for c in base}
