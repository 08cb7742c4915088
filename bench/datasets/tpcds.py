"""The dataset `tpcds`: the six TPC-DS tables that query 17 reads
(`store_sales`, `store_returns`, `catalog_sales`, `date_dim`, `store`,
`item`), made in bulk from a seed, at the specification's row counts for
scale factor 10 (TPC-DS specification clause 3, table 3-2) and its
column types (clause 2): surrogate keys int64, quantities int64, money
float64 (the specification's decimal(7,2)), `d_date` a date, `i_item_id`
char(16), `i_item_desc` up to 200 characters, `s_state` char(2),
`d_quarter_name` char(6). `customer` (500,000 rows at SF 10) is not
made: q17 does not read it; its row count is the domain of the customer
keys. Every table is written with all of its specification columns, in
the specification's order (`SCHEMA`): `make` makes the columns that q17
and the indexes of `tpcds/queries._INDEX_DEFS` read, which the
reference is handed, and `write_parquet` makes the others file by file
as it writes (`_filler`), so the lake has the specification's record
widths while the host never holds them whole. Those others are payload
in their columns' domains, with no NULLs: the fact tables' drawn from
the seed, the dimensions' fixed hashes of the surrogate key.

The shapes a compiled program sees must not follow the seed (every
distinct row count is minutes of compilation on the chip), so every
key, every date and every string is a fixed function of a row's
number, as `tpch`'s filter columns are:

    store_sales     row r -> ticket (8..16 lines a ticket, 12 on average:
                    periods of 9 tickets of 8, 9, ..., 16 lines), line;
                    a ticket's customer, store and sale date are hashes
                    of the ticket, a line's item the ticket's hash plus
                    line x a stride prime to the item count (the
                    specification's key (ss_item_sk, ss_ticket_number)
                    is unique)
    store_returns   one return per chosen sale line: the lines
                    r = (j x A + B) mod N, j < R, A prime to N; the
                    return carries the line's (customer, item, ticket)
                    and is dated 1..180 days after the sale
    catalog_sales   CATALOG_SHARE (1/2) of the returns, by a hash of the
                    return, each get 1..3 catalog purchases by the same
                    customer of the same item, 0..364 days after the
                    store sale (without them q17's third join is empty:
                    dsdgen's own rate cannot be had without dsdgen); the
                    other rows have hashed customers, items and dates
    item            business keys in revisions of 1, 2, 3 rows (so
                    51,000 `i_item_id` for 102,000 items), descriptions
                    and ids from fixed pools
    store           `s_state` from a fixed pool of 12 states
    date_dim        1900-01-02 .. 2100-01-01, d_date_sk the Julian day

So the rows q17's date predicates keep, the pairs every join places and
the groups of its aggregate are the same for every seed. The seed sets
the payload (the three quantities q17 averages, the money columns) and
the order of every fact table's rows across its files, and nothing else.
Strings are codes into VOCABULARY (sorted, so a code's order is its
string's) and are written as Arrow dictionary arrays.

Nothing here imports the program under test.
"""

from __future__ import annotations

import datetime
import os

import numpy as np

from lib import plugins

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tpch = plugins.load(_BENCH, "datasets", "tpch")

# Clause 3, table 3-2: rows at scale factor 10 (date_dim is the same at
# every scale). Other scale factors (the CPU rehearsals) scale these.
ROWS_SF10 = {
    "store_sales": 28_800_991,
    "store_returns": 2_875_432,
    "catalog_sales": 14_401_261,
    "item": 102_000,
    "store": 102,
    "customer": 500_000,
}
DATE_DIM_ROWS = 73_049
_MIN_ROWS = {"item": 100, "store": 2, "customer": 100}

JULIAN_1900_01_02 = 2_415_022          # d_date_sk of the first date_dim row
SALES_FIRST_SK, SALES_DAYS = 2_450_816, 1_827      # 1998-01-02..2003-01-02
CATALOG_FIRST_SK, CATALOG_DAYS = 2_450_815, 1_840  # 1998-01-01..2003-01-14
RETURN_LAG_DAYS = 180     # a return is dated 1..180 days after its sale
CATALOG_LAG_DAYS = 365    # a planted catalog purchase 0..364 days after
CATALOG_SHARE = 2         # one return in CATALOG_SHARE has catalog buys
LINES_PER_TICKET = tuple(range(8, 17))  # a period of 9 tickets, 108 lines
REVISIONS = (1, 2, 3)                   # item rows per business key

STATES = sorted(["AL", "GA", "IL", "KS", "KY", "MI", "MN", "NE", "OH",
                 "SD", "TN", "TX"])


def _quarter_names() -> list:
    first = datetime.date(1900, 1, 2).year
    last = (datetime.date(1900, 1, 2)
            + datetime.timedelta(days=DATE_DIM_ROWS - 1)).year
    return [f"{y}Q{q}" for y in range(first, last + 1) for q in range(1, 5)]


def _ids(keys: np.ndarray) -> np.ndarray:
    """char(16) business ids in dsdgen's style: eight letters A, then a
    key's hexadecimal digits as the letters A..P, so that the order of
    the strings is the order of the keys."""
    letters = np.frombuffer(b"ABCDEFGHIJKLMNOP", dtype="S1")
    keys = np.asarray(keys, dtype=np.int64)
    digits = np.stack([(keys >> (4 * (7 - i))) & 15 for i in range(8)], 1)
    return np.char.add("AAAAAAAA", letters[digits].view("S8").ravel()
                       .astype("U8"))


def _item_ids(n: int) -> list:
    return _ids(np.arange(n)).tolist()


def _descriptions(n: int) -> list:
    """`n` distinct item descriptions of 1..200 characters, the same in
    every run, sorted."""
    rng = np.random.default_rng([0x7D5, 1])
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", dtype="S1")
    out = set()
    while len(out) < n:
        k = int(rng.integers(1, 201))
        out.add(b"".join(letters[rng.integers(0, len(letters), k)])
                .decode().strip() or "a")
    return sorted(out)


ITEM_KEYS_SF10 = ROWS_SF10["item"] // 2  # 1 + 2 + 3 rows for 3 keys
VOCABULARY = {
    "i_item_id": _item_ids(ITEM_KEYS_SF10),
    "i_item_desc": _descriptions(4096),
    "s_state": STATES,
    "d_quarter_name": _quarter_names(),
}
# Columns that hold a day count: the dates' surrogate keys are Julian
# day numbers (a predicate or join over them is a predicate over dates),
# and d_date. Only the specification's date columns are written as dates
# (`_DATE32`).
DATE_COLUMNS = ("ss_sold_date_sk", "sr_returned_date_sk", "cs_sold_date_sk",
                "d_date_sk", "d_date")
_DATE32 = ("d_date", "i_rec_start_date", "i_rec_end_date",
           "s_rec_start_date", "s_rec_end_date")

_hashed, _shuffled, _money = tpch._hashed, tpch._shuffled, tpch._money

# Clause 2: each table's columns in the specification's order.
SCHEMA = {
    "store_sales": (
        "ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk", "ss_customer_sk",
        "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk",
        "ss_promo_sk", "ss_ticket_number", "ss_quantity", "ss_wholesale_cost",
        "ss_list_price", "ss_sales_price", "ss_ext_discount_amt",
        "ss_ext_sales_price", "ss_ext_wholesale_cost", "ss_ext_list_price",
        "ss_ext_tax", "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
        "ss_net_profit"),
    "store_returns": (
        "sr_returned_date_sk", "sr_return_time_sk", "sr_item_sk",
        "sr_customer_sk", "sr_cdemo_sk", "sr_hdemo_sk", "sr_addr_sk",
        "sr_store_sk", "sr_reason_sk", "sr_ticket_number",
        "sr_return_quantity", "sr_return_amt", "sr_return_tax",
        "sr_return_amt_inc_tax", "sr_fee", "sr_return_ship_cost",
        "sr_refunded_cash", "sr_reversed_charge", "sr_store_credit",
        "sr_net_loss"),
    "catalog_sales": (
        "cs_sold_date_sk", "cs_sold_time_sk", "cs_ship_date_sk",
        "cs_bill_customer_sk", "cs_bill_cdemo_sk", "cs_bill_hdemo_sk",
        "cs_bill_addr_sk", "cs_ship_customer_sk", "cs_ship_cdemo_sk",
        "cs_ship_hdemo_sk", "cs_ship_addr_sk", "cs_call_center_sk",
        "cs_catalog_page_sk", "cs_ship_mode_sk", "cs_warehouse_sk",
        "cs_item_sk", "cs_promo_sk", "cs_order_number", "cs_quantity",
        "cs_wholesale_cost", "cs_list_price", "cs_sales_price",
        "cs_ext_discount_amt", "cs_ext_sales_price", "cs_ext_wholesale_cost",
        "cs_ext_list_price", "cs_ext_tax", "cs_coupon_amt",
        "cs_ext_ship_cost", "cs_net_paid", "cs_net_paid_inc_tax",
        "cs_net_paid_inc_ship", "cs_net_paid_inc_ship_tax", "cs_net_profit"),
    "date_dim": (
        "d_date_sk", "d_date_id", "d_date", "d_month_seq", "d_week_seq",
        "d_quarter_seq", "d_year", "d_dow", "d_moy", "d_dom", "d_qoy",
        "d_fy_year", "d_fy_quarter_seq", "d_fy_week_seq", "d_day_name",
        "d_quarter_name", "d_holiday", "d_weekend", "d_following_holiday",
        "d_first_dom", "d_last_dom", "d_same_day_ly", "d_same_day_lq",
        "d_current_day", "d_current_week", "d_current_month",
        "d_current_quarter", "d_current_year"),
    "store": (
        "s_store_sk", "s_store_id", "s_rec_start_date", "s_rec_end_date",
        "s_closed_date_sk", "s_store_name", "s_number_employees",
        "s_floor_space", "s_hours", "s_manager", "s_market_id",
        "s_geography_class", "s_market_desc", "s_market_manager",
        "s_division_id", "s_division_name", "s_company_id",
        "s_company_name", "s_street_number", "s_street_name",
        "s_street_type", "s_suite_number", "s_city", "s_county", "s_state",
        "s_zip", "s_country", "s_gmt_offset", "s_tax_precentage"),
    "item": (
        "i_item_sk", "i_item_id", "i_rec_start_date", "i_rec_end_date",
        "i_item_desc", "i_current_price", "i_wholesale_cost", "i_brand_id",
        "i_brand", "i_class_id", "i_class", "i_category_id", "i_category",
        "i_manufact_id", "i_manufact", "i_size", "i_formulation", "i_color",
        "i_units", "i_container", "i_manager_id", "i_product_name"),
}

# Table 3-2 at SF 10: the dimensions that only payload keys point into.
DOMAINS = {"time_dim": 86_400, "customer_demographics": 1_920_800,
           "household_demographics": 7_200, "customer_address": 250_000,
           "promotion": 500, "reason": 45, "call_center": 24,
           "catalog_page": 12_000, "ship_mode": 20, "warehouse": 10}

# The strings no query of this cell reads: (pool size, shortest,
# longest) at the column's char / varchar width, from fixed pools as the
# tpch dataset's free text is; `_FLAGS` are char(1) Y/N.
_TEXT = {"i_brand": (714, 10, 50), "i_class": (100, 5, 50),
         "i_manufact": (1000, 5, 50), "i_formulation": (4096, 20, 20),
         "i_color": (92, 5, 20), "i_units": (21, 5, 10),
         "i_container": (1, 7, 10), "i_product_name": (4096, 10, 50),
         "s_store_name": (10, 5, 50),
         "s_manager": (100, 10, 40), "s_geography_class": (1, 7, 100),
         "s_market_desc": (100, 20, 100), "s_market_manager": (100, 10, 40),
         "s_division_name": (1, 7, 50), "s_company_name": (1, 7, 50),
         "s_street_name": (100, 6, 60), "s_street_type": (20, 6, 15),
         "s_suite_number": (100, 9, 10), "s_city": (50, 6, 60),
         "s_county": (30, 10, 30)}
_FLAGS = ("d_holiday", "d_weekend", "d_following_holiday", "d_current_day",
          "d_current_week", "d_current_month", "d_current_quarter",
          "d_current_year")
VOCABULARY.update({c: tpch._text_pool(n, lo, hi, 0x3600 + i)
                   for i, (c, (n, lo, hi)) in enumerate(_TEXT.items())})
VOCABULARY.update({c: ["N", "Y"] for c in _FLAGS})
_DAYS = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
         "Saturday")
VOCABULARY.update(
    d_day_name=sorted(_DAYS), s_country=["United States"],
    s_hours=["8AM-12AM", "8AM-4PM", "8AM-8AM"],
    i_category=sorted(["Books", "Children", "Electronics", "Home", "Jewelry",
                       "Men", "Music", "Shoes", "Sports", "Women"]),
    i_size=sorted(["N/A", "economy", "extra large", "large", "medium",
                   "petite", "small"]))


def row_count(table: str, scale_factor: float) -> int:
    if table == "date_dim":
        return DATE_DIM_ROWS
    return max(_MIN_ROWS.get(table, 1),
               int(round(ROWS_SF10[table] * scale_factor / 10.0)))


def _coprime(n: int, near: int) -> int:
    """The first number at or above `near` that is prime to `n`."""
    a = max(1, near)
    while np.gcd(a, n) != 1:
        a += 1
    return a


def _tickets(n: int):
    """(ticket number from 1, line number from 0) of store_sales rows
    0..n-1."""
    per = np.asarray(LINES_PER_TICKET)
    period = int(per.sum())
    ticket_of = np.repeat(np.arange(len(per)), per)
    line_of = np.concatenate([np.arange(k) for k in per])
    r = np.arange(n, dtype=np.int64)
    pos = r % period
    return (r // period * len(per) + ticket_of[pos] + 1,
            line_of[pos].astype(np.int64))


def _hash64(ids, salt: int, n: int) -> np.ndarray:
    return _hashed(ids, salt, n).astype(np.int64)


def sales_keys(scale_factor: float) -> dict:
    """store_sales' keys and dates by row number, the same for every
    seed."""
    n = row_count("store_sales", scale_factor)
    items = row_count("item", scale_factor)
    ticket, line = _tickets(n)
    stride = _coprime(items, 7919)
    return {
        "ss_ticket_number": ticket,
        "ss_customer_sk": 1 + _hash64(ticket, 11, row_count(
            "customer", scale_factor)),
        "ss_store_sk": 1 + _hash64(ticket, 12, row_count(
            "store", scale_factor)),
        "ss_sold_date_sk": SALES_FIRST_SK + _hash64(ticket, 13, SALES_DAYS),
        "ss_item_sk": 1 + (_hash64(ticket, 14, items) + line * stride)
        % items,
    }


def returned_rows(scale_factor: float) -> np.ndarray:
    """The store_sales rows that are returned, one return each."""
    n = row_count("store_sales", scale_factor)
    r = min(n, row_count("store_returns", scale_factor))
    a = _coprime(n, int(n * 0.618))
    return (np.arange(r, dtype=np.int64) * a + n // 3) % n


def catalog_plan(scale_factor: float):
    """(return of each planted catalog row, its number among that
    return's purchases): the returns one in CATALOG_SHARE of which get
    1..3 catalog purchases of the same item by the same customer."""
    j = np.arange(len(returned_rows(scale_factor)), dtype=np.int64)
    j = j[_hash64(j, 16, CATALOG_SHARE) == 0]
    k = 1 + _hash64(j, 17, 3)
    planted = np.repeat(j, k)
    first = np.repeat(np.cumsum(k) - k, k)
    return planted, np.arange(len(planted), dtype=np.int64) - first


class Table(dict):
    """{column: ndarray} of the columns `make` made, and what
    `write_parquet` needs to make the table's other columns: its name,
    the seed and the scale factor."""

    def __init__(self, name: str, seed: int, scale_factor: float,
                 columns: dict):
        super().__init__(columns)
        self.name, self.seed, self.scale_factor = name, seed, scale_factor


def make(config: dict, seed: int, scale_factor: float) -> dict:
    """{table: Table}, each holding the columns q17 and the indexes
    read; strings are codes into VOCABULARY, d_date int32 days since the
    epoch, everything else int64 or float64. The payload comes from
    generators of their own (seed, column number); keys, dates and
    strings do not follow the seed."""
    seed = int(seed)

    def rng_of(i: int):
        return np.random.default_rng([seed, 0x7D5, i])

    n_ss = row_count("store_sales", scale_factor)
    n_cs = row_count("catalog_sales", scale_factor)
    n_item = row_count("item", scale_factor)
    n_store = row_count("store", scale_factor)
    n_cust = row_count("customer", scale_factor)

    ss = sales_keys(scale_factor)
    ss["ss_quantity"] = rng_of(1).integers(1, 101, n_ss)
    ss["ss_net_profit"] = rng_of(2).integers(-1_000_000, 1_000_000,
                                             n_ss) / 100.0

    rows = returned_rows(scale_factor)
    sr = {
        "sr_returned_date_sk": ss["ss_sold_date_sk"][rows] + 1
        + _hash64(rows, 15, RETURN_LAG_DAYS),
        "sr_item_sk": ss["ss_item_sk"][rows],
        "sr_customer_sk": ss["ss_customer_sk"][rows],
        "sr_ticket_number": ss["ss_ticket_number"][rows],
        # 1 .. the sale's quantity
        "sr_return_quantity": 1 + (rng_of(3).random(len(rows))
                                   * ss["ss_quantity"][rows]).astype(np.int64),
        "sr_net_loss": rng_of(4).integers(0, 500_000, len(rows)) / 100.0,
    }

    planted, nth = catalog_plan(scale_factor)
    planted = planted[:n_cs]
    nth = nth[:len(planted)]
    other = np.arange(n_cs - len(planted), dtype=np.int64)
    sold = ss["ss_sold_date_sk"][rows[planted]]
    cs = {
        "cs_sold_date_sk": np.concatenate([
            sold + _hash64(planted * 4 + nth, 18, CATALOG_LAG_DAYS),
            CATALOG_FIRST_SK + _hash64(other, 21, CATALOG_DAYS)]),
        "cs_bill_customer_sk": np.concatenate([
            sr["sr_customer_sk"][planted], 1 + _hash64(other, 19, n_cust)]),
        "cs_item_sk": np.concatenate([
            sr["sr_item_sk"][planted], 1 + _hash64(other, 20, n_item)]),
        "cs_quantity": rng_of(5).integers(1, 101, n_cs),
    }

    sk = np.arange(1, n_item + 1, dtype=np.int64)
    period = np.repeat(np.arange(len(REVISIONS)), REVISIONS)
    business = ((sk - 1) // len(period) * len(REVISIONS)
                + period[(sk - 1) % len(period)])
    if business[-1] >= len(VOCABULARY["i_item_id"]):
        raise ValueError(f"scale factor {scale_factor} has more item ids "
                         f"than the pool holds")
    item = {"i_item_sk": sk,
            "i_item_id": business.astype(np.int32),
            "i_item_desc": _hashed(sk, 22, len(VOCABULARY["i_item_desc"]))}

    s_sk = np.arange(1, n_store + 1, dtype=np.int64)
    store = {"s_store_sk": s_sk,
             "s_state": _hashed(s_sk, 23, len(STATES))}

    date_dim = _date_dim()

    # the seed orders each fact table's rows across its files
    tables = {"store_sales": ss, "store_returns": sr, "catalog_sales": cs}
    for i, (name, cols) in enumerate(tables.items()):
        order = _shuffled(len(next(iter(cols.values()))), rng_of(10 + i))
        tables[name] = {c: v[order] for c, v in cols.items()}
    tables.update(item=item, store=store, date_dim=date_dim)
    return {name: Table(name, seed, scale_factor, cols)
            for name, cols in tables.items()}


def _date_dim() -> dict:
    first = np.datetime64("1900-01-02")
    days = first + np.arange(DATE_DIM_ROWS)
    years = days.astype("datetime64[Y]").astype(np.int64) + 1970
    months = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    qoy = (months - 1) // 3 + 1
    names = {n: i for i, n in enumerate(VOCABULARY["d_quarter_name"])}
    first_year = int(years[0])
    table = np.array([names[f"{y}Q{q}"] for y in range(first_year,
                                                       int(years[-1]) + 1)
                      for q in range(1, 5)], dtype=np.int32)
    return {
        "d_date_sk": JULIAN_1900_01_02 + np.arange(DATE_DIM_ROWS,
                                                   dtype=np.int64),
        "d_date": days.astype(np.int64).astype(np.int32),
        "d_year": years,
        "d_moy": months,
        "d_qoy": qoy,
        "d_quarter_name": table[(years - first_year) * 4 + qoy - 1],
    }


def quarter_code(name: str) -> int:
    return VOCABULARY["d_quarter_name"].index(name)


# -- the columns q17 does not read, made file by file ---------------------------


def _cents(rng, n: int, lo: int, hi: int) -> np.ndarray:
    return rng.integers(lo, hi + 1, n)


def _pricing(p: str, quantity: np.ndarray, rng) -> dict:
    """A sales line's prices in cents, as dsdgen relates them: wholesale
    cost 1.00..100.00, list price up to twice it, sales price up to the
    list price, the ext_ amounts times the quantity, tax 0..9 % of the
    sale, a coupon on one line in five, net paid after the coupon."""
    n = len(quantity)
    whole = _cents(rng, n, 100, 10_000)
    listed = whole + rng.integers(0, whole + 1)
    sales = rng.integers(0, listed + 1)
    ext_sales = quantity * sales
    tax = ext_sales * rng.integers(0, 10, n) // 100
    coupon = np.where(rng.random(n) < 0.2,
                      ext_sales * rng.integers(0, 101, n) // 100, 0)
    net_paid = ext_sales - coupon
    return {f"{p}_wholesale_cost": whole, f"{p}_list_price": listed,
            f"{p}_sales_price": sales,
            f"{p}_ext_discount_amt": quantity * (listed - sales),
            f"{p}_ext_sales_price": ext_sales,
            f"{p}_ext_wholesale_cost": quantity * whole,
            f"{p}_ext_list_price": quantity * listed, f"{p}_ext_tax": tax,
            f"{p}_coupon_amt": coupon, f"{p}_net_paid": net_paid,
            f"{p}_net_paid_inc_tax": net_paid + tax}


def _dollars(columns: dict) -> dict:
    return {c: v / 100.0 for c, v in columns.items()}


def _keys(rng, n: int, domain: str) -> np.ndarray:
    return rng.integers(1, DOMAINS[domain] + 1, n)


def _filler(table: Table, part: dict, i: int) -> dict:
    """The columns of SCHEMA[table.name] that `part` (rows of file `i`)
    lacks: the fact tables' drawn from (seed, table, file), the
    dimensions' fixed hashes of the surrogate key."""
    name, sf = table.name, table.scale_factor
    n = len(next(iter(part.values())))
    rng = np.random.default_rng([table.seed, 0x7D5, 100 + sorted(
        SCHEMA).index(name), i])
    if name == "store_sales":
        out = _dollars(_pricing("ss", part["ss_quantity"], rng))
        out.update(ss_sold_time_sk=_keys(rng, n, "time_dim") - 1,
                   ss_cdemo_sk=_keys(rng, n, "customer_demographics"),
                   ss_hdemo_sk=_keys(rng, n, "household_demographics"),
                   ss_addr_sk=_keys(rng, n, "customer_address"),
                   ss_promo_sk=_keys(rng, n, "promotion"))
        return out
    if name == "catalog_sales":
        q = part["cs_quantity"]
        cents = _pricing("cs", q, rng)
        ship = q * rng.integers(0, cents["cs_list_price"] // 2 + 1)
        cents.update(
            cs_ext_ship_cost=ship,
            cs_net_paid_inc_ship=cents["cs_net_paid"] + ship,
            cs_net_paid_inc_ship_tax=(cents["cs_net_paid_inc_tax"] + ship),
            cs_net_profit=(cents["cs_net_paid"] - ship
                           - cents["cs_ext_wholesale_cost"]))
        out = _dollars(cents)
        out.update(
            cs_sold_time_sk=_keys(rng, n, "time_dim") - 1,
            cs_ship_date_sk=part["cs_sold_date_sk"] + rng.integers(2, 91, n),
            cs_ship_customer_sk=part["cs_bill_customer_sk"],
            cs_call_center_sk=_keys(rng, n, "call_center"),
            cs_catalog_page_sk=_keys(rng, n, "catalog_page"),
            cs_ship_mode_sk=_keys(rng, n, "ship_mode"),
            cs_warehouse_sk=_keys(rng, n, "warehouse"),
            cs_promo_sk=_keys(rng, n, "promotion"),
            cs_order_number=rng.integers(1, row_count("catalog_sales", sf)
                                         // 10 + 2, n))
        for who in ("bill", "ship"):
            out.update({f"cs_{who}_cdemo_sk": _keys(
                rng, n, "customer_demographics"),
                f"cs_{who}_hdemo_sk": _keys(rng, n, "household_demographics"),
                f"cs_{who}_addr_sk": _keys(rng, n, "customer_address")})
        return out
    if name == "store_returns":
        amt = part["sr_return_quantity"] * _cents(rng, n, 100, 10_000)
        tax = amt * rng.integers(0, 10, n) // 100
        cash = amt * rng.integers(0, 101, n) // 100
        charge = (amt - cash) * rng.integers(0, 101, n) // 100
        out = _dollars({"sr_return_amt": amt, "sr_return_tax": tax,
                        "sr_return_amt_inc_tax": amt + tax,
                        "sr_fee": _cents(rng, n, 50, 10_000),
                        "sr_return_ship_cost": _cents(rng, n, 0, 50_000),
                        "sr_refunded_cash": cash,
                        "sr_reversed_charge": charge,
                        "sr_store_credit": amt - cash - charge})
        out.update(sr_return_time_sk=_keys(rng, n, "time_dim") - 1,
                   sr_cdemo_sk=_keys(rng, n, "customer_demographics"),
                   sr_hdemo_sk=_keys(rng, n, "household_demographics"),
                   sr_addr_sk=_keys(rng, n, "customer_address"),
                   sr_store_sk=1 + _hash64(part["sr_ticket_number"], 12,
                                           row_count("store", sf)),
                   sr_reason_sk=_keys(rng, n, "reason"))
        return out
    if name == "date_dim":
        return _date_dim_rest(part)
    sk = part[SCHEMA[name][0]]
    out = {c: _hashed(sk, 0x3600 + j, len(VOCABULARY[c]))
           for j, c in enumerate(SCHEMA[name])
           if c in VOCABULARY and c not in part}
    revision = _hashed(sk, 0x3700, 3)
    start = np.array([10_161, 10_892, 11_257], np.int32)[revision]
    hashed = {c: _hash64(sk, 0x3800 + j, 1_000)
              for j, c in enumerate(SCHEMA[name])}
    if name == "item":
        out.update(i_rec_start_date=start, i_rec_end_date=start + 365,
                   i_current_price=(100 + hashed["i_current_price"] * 9)
                   / 100.0,
                   i_wholesale_cost=(50 + hashed["i_wholesale_cost"] * 5)
                   / 100.0,
                   i_brand_id=1_001_001 + hashed["i_brand_id"],
                   i_class_id=1 + hashed["i_class_id"] % 16,
                   i_category_id=1 + hashed["i_category_id"] % 10,
                   i_manufact_id=1 + hashed["i_manufact_id"],
                   i_manager_id=1 + hashed["i_manager_id"] % 100)
        return out
    out.update(
        s_store_id=_ids(sk),
        s_rec_start_date=start, s_rec_end_date=start + 365,
        s_closed_date_sk=SALES_FIRST_SK + hashed["s_closed_date_sk"],
        s_number_employees=200 + hashed["s_number_employees"] % 101,
        s_floor_space=5_000_000 + 5_000 * hashed["s_floor_space"],
        s_market_id=1 + hashed["s_market_id"] % 10,
        s_division_id=np.ones(n, np.int64), s_company_id=np.ones(n, np.int64),
        s_street_number=np.array([str(v) for v in hashed["s_street_number"]]),
        s_zip=np.array([f"{30_000 + 60 * v:05d}" for v in hashed["s_zip"]]),
        s_gmt_offset=-5.0 - hashed["s_gmt_offset"] % 2,
        s_tax_precentage=(hashed["s_tax_precentage"] % 12) / 100.0)
    return out


def _date_dim_rest(part: dict) -> dict:
    """date_dim's calendar columns, from the date alone."""
    sk, days = part["d_date_sk"], part["d_date"].astype(np.int64)
    year, moy = part["d_year"], part["d_moy"]
    dates = days.astype("datetime64[D]")
    month = dates.astype("datetime64[M]")
    first = (month.astype("datetime64[D]") - dates).astype(np.int64) + sk
    last = ((month + 1).astype("datetime64[D]") - dates).astype(
        np.int64) + sk - 1
    dom = sk - first + 1
    dow = (days + 4) % 7  # 1970-01-01 was a Thursday; 0 is Sunday
    week = (sk - JULIAN_1900_01_02 + 1) // 7 + 1
    quarter = (year - 1900) * 4 + part["d_qoy"]
    holiday = ((moy == 12) & (dom == 25)) | ((moy == 1) & (dom == 1))
    names = VOCABULARY["d_day_name"]
    by_dow = np.array([names.index(d) for d in _DAYS], dtype=np.int32)
    out = {"d_date_id": _ids(sk),
           "d_month_seq": (year - 1900) * 12 + moy - 1,
           "d_week_seq": week, "d_quarter_seq": quarter, "d_dow": dow,
           "d_dom": dom, "d_fy_year": year, "d_fy_quarter_seq": quarter,
           "d_fy_week_seq": week,
           "d_day_name": by_dow[dow],
           "d_holiday": holiday.astype(np.int32),
           "d_weekend": ((dow == 0) | (dow == 6)).astype(np.int32),
           "d_following_holiday": np.roll(holiday, 1).astype(np.int32),
           "d_first_dom": first, "d_last_dom": last,
           "d_same_day_ly": sk - 365, "d_same_day_lq": sk - 91}
    out.update({c: np.zeros(len(sk), np.int32) for c in _FLAGS[3:]})
    return out


def to_arrow(columns: dict):
    """The table as Arrow: pooled strings as dictionary arrays, the
    date columns as date32."""
    import pyarrow as pa

    arrays, names = [], []
    for name, data in columns.items():
        if name in VOCABULARY:
            arr = pa.DictionaryArray.from_arrays(
                pa.array(data, type=pa.int32()), pa.array(VOCABULARY[name]))
        elif name in _DATE32:
            arr = pa.array(data, type=pa.int32()).cast(pa.date32())
        else:
            arr = pa.array(data)
        arrays.append(arr)
        names.append(name)
    return pa.table(arrays, names=names)


def write_parquet(columns: Table, directory: str, n_files: int) -> int:
    """`n_files` Parquet files of consecutive rows, each with every
    column of the table's SCHEMA in its order (those `columns` lacks made
    by `_filler` for the file's rows alone), written by a few threads;
    returns the bytes written."""
    from concurrent.futures import ThreadPoolExecutor

    import pyarrow.parquet as pq

    os.makedirs(directory)
    n = len(next(iter(columns.values())))
    per = -(-n // n_files)

    def write(i: int) -> int:
        part = {c: v[i * per:(i + 1) * per] for c, v in columns.items()}
        part.update(_filler(columns, part, i))
        path = os.path.join(directory, f"part-{i:05d}.parquet")
        pq.write_table(to_arrow({c: part[c]
                                 for c in SCHEMA[columns.name]}), path)
        return os.path.getsize(path)

    with ThreadPoolExecutor(max_workers=min(8, n_files)) as pool:
        return sum(pool.map(write, range(n_files)))
