"""The dataset `tpch_by_month`: the dataset `tpch`'s `lineitem`, laid out
the way an order-entry pipeline lands it: one Parquet file per month of
the line's `o_orderdate`, 80 files from 1992-01 to 1998-08, rows in
order-date order within a file.

The rows are `tpch.make_tables`' but for two columns, and only their
order and the files they land in differ. `l_discount` and `l_quantity`,
which Q6's predicate filters on, are a fixed hash of the order key and
line number (`tpch._hashed`, as `tpch` makes its dates and
`l_shipmode`), in the specification's domains: 0.00..0.10 and 1..50,
uniform. `tpch` draws them from the seed, and the program compiles its
compaction for each exact survivor count, so every seed would compile
a year's programs anew; hashed, a year's predicate keeps the same rows
for every seed. A line's `o_orderdate` is `tpch`'s fixed hash of its
order key (`tpch._order_date`), so which file a line lands in follows
the key and not the seed, and `l_shipdate` (the order date plus 1..121
days) is correlated with the layout without being a partition column:
a min/max zone over `l_shipdate` per file is what prunes a ship-date
window, and partition pruning cannot. `orders` is not made: no query
of this dataset's cells reads it.

Nothing here imports the program under test.
"""

from __future__ import annotations

import os

import numpy as np

from lib import plugins

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tpch = plugins.load(_BENCH, "datasets", "tpch")

# the interface every dataset has
VOCABULARY = tpch.VOCABULARY
DATE_COLUMNS = tpch.DATE_COLUMNS


def order_date(l_orderkey: np.ndarray) -> np.ndarray:
    """Each line's `o_orderdate` (days since the epoch), from its key."""
    return tpch._order_date(l_orderkey)


def month_of(days: np.ndarray) -> np.ndarray:
    """Months since 1970-01 of day counts since the epoch."""
    return np.asarray(days, dtype="int64").astype("datetime64[D]") \
        .astype("datetime64[M]").astype(np.int64)


def filter_columns(l_orderkey: np.ndarray, l_linenumber: np.ndarray) -> dict:
    """`l_discount` (0.00..0.10) and `l_quantity` (1..50) of each line,
    as fixed hashes of its key and line number: the same for every
    seed."""
    line_id = l_orderkey * 8 + l_linenumber  # as tpch's date hashes
    return {"l_discount": tpch._hashed(line_id, 5, 11) / 100.0,
            "l_quantity": (tpch._hashed(line_id, 6, 50) + 1)
            .astype(np.float64)}


def make(config: dict, seed: int, scale_factor: float) -> dict:
    """{"lineitem": columns} in order-date order (a stable sort, so the
    seed's order within a day stays); the columns are gathered on a few
    threads."""
    from concurrent.futures import ThreadPoolExecutor

    lineitem = tpch.make_tables(scale_factor, seed,
                                edge_every=config["edge_every"])["lineitem"]
    lineitem.update(filter_columns(lineitem["l_orderkey"],
                                   lineitem["l_linenumber"]))
    order = np.argsort(order_date(lineitem["l_orderkey"]), kind="stable")
    with ThreadPoolExecutor(max_workers=8) as pool:
        sorted_ = dict(zip(lineitem, pool.map(lambda data: data[order],
                                              lineitem.values())))
    return {"lineitem": sorted_}


def file_months(columns: dict) -> list:
    """(first row, end row, month) of each file `write_parquet` writes,
    from columns in `make`'s order: the runs of equal order months."""
    days = order_date(columns["l_orderkey"])
    first, last = month_of(days[[0, -1]])
    months = np.arange(first, last + 1)
    starts = np.searchsorted(
        days, months.astype("datetime64[M]").astype("datetime64[D]")
        .astype(np.int64))
    ends = np.r_[starts[1:], len(days)]
    return [(int(s), int(e), int(m))
            for s, e, m in zip(starts, ends, months) if e > s]


def file_name(month: int) -> str:
    """The file of one order month, named by it: `part-1994-03.parquet`."""
    y, m = divmod(month, 12)
    return f"part-{1970 + y:04d}-{m + 1:02d}.parquet"


def write_parquet(columns: dict, directory: str, n_files: int) -> int:
    """One Parquet file per month of the rows' order date (rows as `make`
    orders them), written by a few threads; `n_files` is the months the
    configuration states, and a lake with another count is refused.
    Returns the bytes written."""
    from concurrent.futures import ThreadPoolExecutor

    import pyarrow.parquet as pq

    bounds = file_months(columns)
    if len(bounds) != n_files:
        raise ValueError(f"the order dates span {len(bounds)} months; the "
                         f"configuration states {n_files} files")
    table = tpch.to_arrow(columns)
    os.makedirs(directory)

    def write(bound) -> int:
        lo, hi, month = bound
        path = os.path.join(directory, file_name(month))
        pq.write_table(table.slice(lo, hi - lo), path)
        return os.path.getsize(path)

    with ThreadPoolExecutor(max_workers=min(8, n_files)) as pool:
        return sum(pool.map(write, bounds))
