"""The dataset `tpch`: TPC-H `lineitem` and `orders`, made in bulk from
a seed. A configuration names it (`"dataset": "tpch"`); a dataset is a
module of `datasets/` with `make(config, seed, scale_factor)`,
`write_parquet`, `VOCABULARY` and `DATE_COLUMNS`.

The shapes a compiled program sees must not follow the seed (PERF.md:
every distinct row count is minutes of compilation), so everything that
sets a count is a function of the order key alone:

    o_orderkey            1..N                       (N = 1,500,000 x SF)
    lines of an order     1 + (o_orderkey mod 7)     (uniform over 1..7)

`lineitem`'s row count, every hash bucket's row count and the number of
lines in any run of 7k consecutive keys are then the same for every
seed. The columns a TPC-H query filters on (the four dates and
`l_shipmode`) are a fixed hash of the order key and line number and
do not follow the seed either: the rows a query's predicate keeps are
then as many for every seed, in the table and in every bucket (the
program compiles its compaction per exact count). The seed sets the
other payload values, the order of rows (and so which file a row lands
in), and nothing else. Columns are the specification's (clause 1.4),
all of them, at its widths; dates follow clause 4.2.3: o_orderdate
uniform over 1992-01-01..1998-08-02, l_shipdate = o_orderdate + 1..121,
l_commitdate = o_orderdate + 30..90, l_receiptdate = l_shipdate + 1..30.

Nothing here imports the program under test.
"""

from __future__ import annotations

import numpy as np

ORDERS_PER_SF = 1_500_000
LINES_PERIOD = 7  # lines of an order = 1 + key mod 7; 28 lines per 7 keys

# float64 payload has to come back to the bit. The chip's own f64 is an
# f32 pair, so these are the values any lossy carriage changes: beyond
# f32's range, subnormals, 53 significant bits, -0.0, inf, nan.
F64_EDGE = np.array([
    1e300, -1e300, np.finfo(np.float64).max, 1e-300, 5e-324,
    np.finfo(np.float64).tiny, 1e-40, -0.0, 0.1 + 0.2, 1.0 / 3.0,
    np.inf, -np.inf, np.nan])

VOCABULARY = {
    "l_returnflag": ["A", "N", "R"],
    "l_linestatus": ["F", "O"],
    "l_shipinstruct": ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                       "TAKE BACK RETURN"],
    "l_shipmode": ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"],
    "o_orderstatus": ["F", "O", "P"],
    "o_orderpriority": ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                        "5-LOW"],
}


def _text_pool(n: int, lo: int, hi: int, tag: int) -> list:
    """`n` distinct strings of lo..hi lower-case letters and spaces, the
    same in every run: the free-text columns draw from it by the seed."""
    rng = np.random.default_rng([0x7E47, tag])
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz   ", dtype="S1")
    return [f"{i:04x} " + b"".join(
        letters[rng.integers(0, len(letters), int(k))]).decode()
        for i, k in enumerate(rng.integers(lo - 5, hi - 4, n))]


# Free text (l_comment varchar(44), o_comment varchar(79)) and o_clerk
# (char(15), 1000 clerks per SF in the specification) at their widths,
# drawn from fixed pools so that making 18 M of them costs what a
# dictionary column costs.
VOCABULARY.update({
    "l_comment": _text_pool(4096, 10, 43, 1),
    "o_comment": _text_pool(4096, 19, 78, 2),
    "o_clerk": [f"Clerk#{i:09d}" for i in range(1, 1001)],
})

ORDERS_COLUMNS = (
    "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
    "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
    "o_comment")
LINEITEM_COLUMNS = (
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
    "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
    "l_shipinstruct", "l_shipmode", "l_comment")
DATE_COLUMNS = ("l_shipdate", "l_commitdate", "l_receiptdate", "o_orderdate")
_DATE_LO = 8035   # 1992-01-01 as days since the epoch
_DATE_SPAN = 2406  # ... to 1998-08-02, the specification's order dates


def order_count(scale_factor: float) -> int:
    return int(round(ORDERS_PER_SF * scale_factor))


def lines_of(keys: np.ndarray) -> np.ndarray:
    return 1 + keys % LINES_PERIOD


def lineitem_count(n_orders: int) -> int:
    return int(lines_of(np.arange(1, n_orders + 1, dtype=np.int64)).sum())


def range_width(n_orders: int, key_share: float) -> int:
    """Keys in a range of about `key_share` of all keys, rounded to a
    whole number of periods so that every such range holds the same
    number of lines, wherever it starts."""
    periods = max(1, int(round(n_orders * key_share / LINES_PERIOD)))
    return periods * LINES_PERIOD


def lines_in_range(width_keys: int) -> int:
    assert width_keys % LINES_PERIOD == 0, width_keys
    return width_keys // LINES_PERIOD * sum(range(1, LINES_PERIOD + 1))


def _money(rng, n: int, top: float, edge_every: int) -> np.ndarray:
    """Amounts with two decimals below `top`, an edge value planted in
    one row of `edge_every`."""
    out = rng.integers(0, int(top * 100), n) / 100.0
    if edge_every:
        out[::edge_every] = np.resize(F64_EDGE, len(out[::edge_every]))
    return out


def _shuffled(n: int, rng) -> np.ndarray:
    """A seeded permutation of 0..n-1 without a random gather over the
    whole table: i -> (i * a + b) mod n with a coprime to n. Rows that
    were neighbours land far apart, so every file holds keys from the
    whole range."""
    while True:
        a = int(rng.integers(n // 3 + 1, n)) if n > 3 else 1
        if np.gcd(a, n) == 1:
            break
    b = int(rng.integers(0, n))
    # a * i stays under 2**63 for n up to 3e9
    return (np.arange(n, dtype=np.int64) * a + b) % n


def _hashed(ids: np.ndarray, salt: int, n: int) -> np.ndarray:
    """0..n-1 as a fixed hash (splitmix64's finaliser) of `ids`: the
    same for every seed."""
    with np.errstate(over="ignore"):
        x = ids.astype(np.uint64) + np.uint64(
            (salt * 0x9E3779B97F4A7C15 + 1) & 0xFFFFFFFFFFFFFFFF)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return (x % np.uint64(n)).astype(np.int32)


def _order_date(keys: np.ndarray) -> np.ndarray:
    """o_orderdate as a hash of the key, so that `lineitem` derives its
    dates from the order's without a lookup table."""
    return (_DATE_LO + _hashed(keys, 0, _DATE_SPAN)).astype(np.int32)


# Position within a period of 28 lines -> offset of its key in the period's
# 7 keys (which hold 2,3,4,5,6,7,1 lines: the first key of a period is 1
# mod 7) and its line number.
_PERIOD_LINES = [1 + k % LINES_PERIOD for k in range(1, LINES_PERIOD + 1)]
_KEY_OFFSET = np.repeat(np.arange(LINES_PERIOD), _PERIOD_LINES)
_LINE_NUMBER = np.concatenate([np.arange(1, c + 1) for c in _PERIOD_LINES])


def make_tables(scale_factor: float, seed: int,
                edge_every: int = 1024) -> dict:
    """{"orders": {column: ndarray}, "lineitem": {...}}; dictionary
    and text columns are integer codes into VOCABULARY, dates int32
    days. Each column has a generator of its own (seed, column number),
    so columns are made side by side on a few threads."""
    from concurrent.futures import ThreadPoolExecutor

    seed = int(seed)
    n = order_count(scale_factor)
    m = lineitem_count(n)
    per_period = len(_KEY_OFFSET)

    def rng_of(i: int):
        return np.random.default_rng([seed, 0x7C4, i])

    def o_keys():
        return _shuffled(n, rng_of(0)) + 1

    def l_position():
        return _shuffled(m, rng_of(1))

    plan = {
        "orders": {
            "o_custkey": lambda r: r.integers(1, max(2, n // 10) + 1, n),
            "o_orderstatus": lambda r: r.integers(0, 3, n, dtype=np.int8),
            "o_totalprice": lambda r: _money(r, n, 500_000.0, edge_every),
            "o_orderpriority": lambda r: r.integers(0, 5, n, dtype=np.int8),
            "o_shippriority": lambda r: np.zeros(n, dtype=np.int64),
            "o_clerk": lambda r: r.integers(0, 1000, n, dtype=np.int16),
            "o_comment": lambda r: r.integers(0, 4096, n, dtype=np.int16),
        },
        "lineitem": {
            "l_partkey": lambda r: r.integers(
                1, max(2, int(200_000 * scale_factor)) + 1, m),
            "l_suppkey": lambda r: r.integers(
                1, max(2, int(10_000 * scale_factor)) + 1, m),
            "l_quantity": lambda r: r.integers(1, 51, m).astype(np.float64),
            "l_extendedprice": lambda r: _money(r, m, 105_000.0, edge_every),
            "l_discount": lambda r: r.integers(0, 11, m) / 100.0,
            "l_tax": lambda r: r.integers(0, 9, m) / 100.0,
            "l_returnflag": lambda r: r.integers(0, 3, m, dtype=np.int8),
            "l_linestatus": lambda r: r.integers(0, 2, m, dtype=np.int8),
            "l_shipinstruct": lambda r: r.integers(0, 4, m, dtype=np.int8),
            "l_comment": lambda r: r.integers(0, 4096, m, dtype=np.int16),
        },
    }
    jobs = [(t, c, fn, 2 + i) for i, (t, c, fn) in enumerate(
        (t, c, fn) for t, cols in plan.items() for c, fn in cols.items())]
    with ThreadPoolExecutor(max_workers=8) as pool:
        f_okey, f_pos = pool.submit(o_keys), pool.submit(l_position)
        made = {(t, c): pool.submit(lambda fn=fn, i=i: fn(rng_of(i)))
                for t, c, fn, i in jobs}
        o_key, pos = f_okey.result(), f_pos.result()
        l_key = (pos // per_period * LINES_PERIOD + 1
                 + _KEY_OFFSET[pos % per_period])
        l_line = _LINE_NUMBER[pos % per_period]
        line_id = l_key * 8 + l_line  # 1..7 lines an order
        # the columns a query filters on: hashes of the key and line alone
        hashed = {
            "o_orderdate": pool.submit(_order_date, o_key),
            "_ordered": pool.submit(_order_date, l_key),
            "_ship_lag": pool.submit(_hashed, line_id, 1, 121),
            "_commit_lag": pool.submit(_hashed, line_id, 2, 61),
            "_receipt_lag": pool.submit(_hashed, line_id, 3, 30),
            "l_shipmode": pool.submit(_hashed, line_id, 4, 7)}
        made = {k: f.result() for k, f in made.items()}
        hashed = {k: f.result() for k, f in hashed.items()}

    ship = hashed["_ordered"] + 1 + hashed["_ship_lag"]
    orders = {"o_orderkey": o_key, "o_orderdate": hashed["o_orderdate"]}
    lineitem = {"l_orderkey": l_key,
                "l_linenumber": l_line,
                "l_shipdate": ship,
                "l_commitdate": hashed["_ordered"] + 30
                + hashed["_commit_lag"],
                "l_receiptdate": ship + 1 + hashed["_receipt_lag"],
                "l_shipmode": hashed["l_shipmode"].astype(np.int8)}
    for (t, c), data in made.items():
        (orders if t == "orders" else lineitem)[c] = data
    tables = {"orders": {c: orders[c] for c in ORDERS_COLUMNS},
              "lineitem": {c: lineitem[c] for c in LINEITEM_COLUMNS}}
    return tables


def make(config: dict, seed: int, scale_factor: float) -> dict:
    """The configuration's tables: the interface every dataset has."""
    return make_tables(scale_factor, seed, edge_every=config["edge_every"])


def to_arrow(columns: dict):
    """The table as Arrow: fixed vocabularies as dictionary arrays,
    dates as date32."""
    import pyarrow as pa

    arrays, names = [], []
    for name, data in columns.items():
        if name in VOCABULARY:
            arr = pa.DictionaryArray.from_arrays(
                pa.array(data), pa.array(VOCABULARY[name]))
        elif name in DATE_COLUMNS:
            arr = pa.array(data, type=pa.int32()).cast(pa.date32())
        else:
            arr = pa.array(data)
        arrays.append(arr)
        names.append(name)
    return pa.table(arrays, names=names)


def write_parquet(columns: dict, directory: str, n_files: int) -> int:
    """`n_files` Parquet files of consecutive rows, written by a few
    threads; returns the bytes written."""
    import os
    from concurrent.futures import ThreadPoolExecutor

    import pyarrow.parquet as pq

    table = to_arrow(columns)
    os.makedirs(directory)
    per = -(-table.num_rows // n_files)

    def write(i: int) -> int:
        path = os.path.join(directory, f"part-{i:05d}.parquet")
        pq.write_table(table.slice(i * per, per), path)
        return os.path.getsize(path)

    with ThreadPoolExecutor(max_workers=min(8, n_files)) as pool:
        return sum(pool.map(write, range(n_files)))
