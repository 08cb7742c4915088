"""The operation `tpcds_q17`: TPC-DS query 17 ("quarterly store and
catalog behaviour of returned items") through the covering indexes of
`tpcds/queries._INDEX_DEFS`: `store_sales` joined to `store_returns`
on (customer, item, ticket) through `idx_ss_ret` x `idx_sr_ret`, then
to `catalog_sales` (the source files) on (customer, item), to three
date_dim selections (through `idx_dd_quarter`), to `store` and to
`item`, grouped by (i_item_id, i_item_desc, s_state) with the count,
avg, stddev_samp and stddev_samp / avg (`*_quantitycov`) of the three
quantities, the first 100 groups in key order: the specification's
SELECT list, column for column. The substitution parameter is the mix's
`year` (`[YEAR]Q1` for the sale, `[YEAR]Q1..Q3` for the return and the
catalog sale). The `select` operation's `run` serves.

The comparison is this file's own, because an answer holds SQL NULLs
(stddev_samp of one value) and a column under a tolerance: every column
is handed to `lib/compare.py` as int64 (strings as their vocabulary
codes, float64 by its bits), and every float64 column beside its
validity (`<name>.valid`, 1 present / 0 NULL; a NULL's bits are 0), so a
value is equal exactly where its bits and its NULL-ness are. Counts,
keys, strings, avg and stddev_samp are exact: the configuration's
guarantee is the reference's value to the bit. The three `*_cov`
quotients take part by their NULL-ness only; their values are held to
the reference's within COV_REL_ERR (`cov_rel_err`), because the program
divides them on the device, whose float64 is an f32 pair of about 48
bits with a division that is not IEEE's (the configuration's
`guarantees` give the reason and the two readings the limit lies
between).

A query is on its lane where its operator records say: the two fact
indexes and two date_dim selections are read from index version
directories and the other three tables from their source files; every
scan takes the lane the session's `execution.min.device.rows` gives its
rows (at SF 10 `store_returns`' 2,875,432 rows the host's, the two
larger facts the device's); the fact indexes meet in ONE bucketed
sort-merge join over the configuration's buckets, on the device, by the
match the mix names; and no Exchange or Sort ran. Each query's record
keeps every scan's and join's lane (`q17`), the joins that ran as
direct-address probes (`broadcast`, as `q12_hybrid` keeps them), and
the hashed match's collision fallbacks it took (`hashed_fallbacks`,
from the program's counter `join.hashed.fallbacks`).
"""

from __future__ import annotations

import os
import time

import numpy as np

from lib import compare, plugins
from lib.lake import counters, note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FALLBACKS = "join.hashed.fallbacks"
QUANTITIES = (("ss_quantity", "store_sales_quantity"),
              ("sr_return_quantity", "store_returns_quantity"),
              ("cs_quantity", "catalog_sales_quantity"))
GROUP = ("i_item_id", "i_item_desc", "s_state")
_SERVED = ("path", "lane", "probe_rows", "build_rows")
# stddev_samp / avg: the device's float64 division is good to about 2^-44
# of the quotient, float32's to 2^-24 (the configuration's `guarantees`)
COV_REL_ERR = 2.0 ** -34


def _quarters(year: int):
    return f"{year}Q1", [f"{year}Q{q}" for q in (1, 2, 3)]


def _relation(roots) -> str:
    """The index or source table a scan reads, from its roots."""
    path = os.path.normpath(roots[0]) if roots else ""
    head, tail = os.path.split(path)
    return os.path.basename(head) if tail.startswith("v__=") else tail


def q17_lanes(metrics) -> dict:
    """Every scan's and join's lane of one query, from its
    QueryMetrics."""
    scans, joins = [], []
    for op in metrics.operators:
        d = op.detail
        if op.name == "Scan":
            roots = d.get("roots") or []
            scans.append({"relation": _relation(roots),
                          "index": any("v__=" in r for r in roots),
                          "lane": d.get("lane"), "rows": op.rows_out})
        elif op.name in ("SortMergeJoin", "BroadcastHashJoin"):
            joins.append({"op": op.name, "lane": d.get("lane"),
                          "buckets": d.get("join_buckets"),
                          "path": d.get("path"), "match": d.get("match"),
                          "keys": d.get("keys"),
                          "left_rows": d.get("left_rows",
                                             d.get("probe_rows")),
                          "right_rows": d.get("right_rows",
                                              d.get("build_rows")),
                          "rows": op.rows_out})
    return {"scans": scans, "joins": joins,
            "shuffles": [op.name for op in metrics.operators
                         if op.name in ("Exchange", "Sort")]}


def broadcast_joins(metrics) -> list:
    """The joins planned as broadcast joins, each as the program's
    record says it was served (`q12_hybrid`'s `broadcast`, which
    `broadcast_join_roofline` counts): a fused one by its event, an
    eager one on its operator record."""
    return [{k: said.get(k) for k in _SERVED} for said in (
        metrics.events_of("join", "broadcast")
        + [op.detail for op in metrics.operators
           if op.name == "BroadcastHashJoin" and "path" in op.detail])]


def _fallbacks() -> int:
    return int(counters().get(FALLBACKS, 0))


def canonical(columns: dict, vocabulary: dict) -> dict:
    """{name: int64} of an answer given as {name: (values, valid)}:
    strings by vocabulary code, float64 by bits (0 where NULL) with
    `<name>.valid` beside it, a `*_cov` column by its validity alone."""
    out = {}
    for name, (values, valid) in columns.items():
        values = np.asarray(values)
        if name.endswith("cov"):  # by NULL-ness; the value by cov_rel_err
            out[name + ".valid"] = valid.astype(np.int64)
        elif values.dtype == np.float64:
            out[name] = np.where(valid, values.view(np.int64), 0)
            out[name + ".valid"] = valid.astype(np.int64)
        else:
            out[name] = compare.column_bits(name, values, vocabulary)
    return out


def arrow_columns(table) -> dict:
    """{name: (values, valid)} of an Arrow answer."""
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        col = table.column(name).combine_chunks()
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        valid = col.is_valid().to_numpy(zero_copy_only=False)
        if pa.types.is_floating(col.type):
            values = col.fill_null(0.0).to_numpy(zero_copy_only=False)
        else:
            values = col.to_numpy(zero_copy_only=False)
            if not valid.all():
                raise ValueError(f"column {name} came back with nulls")
        out[name] = (values, valid)
    return out


def reference_columns(answer: dict) -> dict:
    """{name: (values, valid)} of the reference's answer, whose NaN is
    SQL NULL."""
    return {name: (data, ~np.isnan(data) if data.dtype == np.float64
                   else np.ones(len(data), bool))
            for name, data in answer.items()}


def cov_rel_err(got: dict, want: dict, vocabulary: dict) -> float:
    """The largest relative distance of a `*_cov` value from the
    reference's, over the rows whose group keys and cov NULL-ness agree
    (a row that differs there is a mismatched row already); {name:
    (values, valid)} on both sides."""
    def rows(columns):
        keys = zip(*(compare.column_bits(k, columns[k][0], vocabulary)
                     .tolist() for k in GROUP))
        return {key: r for r, key in enumerate(keys)}

    mine, theirs = rows(got), rows(want)
    worst = 0.0
    for key, r in mine.items():
        w = theirs.get(key)
        if w is None:
            continue
        for name in got:
            if not name.endswith("cov"):
                continue
            (gv, gok), (wv, wok) = got[name], want[name]
            if gok[r] and wok[w] and gv[r] != wv[w]:
                worst = max(worst, abs(gv[r] - wv[w]) / abs(wv[w])
                            if wv[w] else np.inf)
    return float(worst)


class Op(plugins.load(_BENCH, "ops", "select").Op):
    reference = "tpcds_q17"

    def params(self, i: int, warming: bool) -> dict:
        return self.control_params(self.query, self.dep.dataset, None, None)

    @staticmethod
    def control_params(query: dict, dataset, scale_factor, seed) -> dict:
        """The same for every query and seed: the quarters as codes."""
        d1, d23 = _quarters(query["year"])
        return {"d1_codes": [dataset.quarter_code(d1)],
                "d23_codes": [dataset.quarter_code(q) for q in d23]}

    def dataframe(self, params: dict):
        from hyperspace_tpu import col, lit

        dfs = self.dep.dfs
        d1_name, d23_names = _quarters(self.query["year"])
        ss = dfs["store_sales"].select(
            "ss_sold_date_sk", "ss_item_sk", "ss_customer_sk", "ss_store_sk",
            "ss_ticket_number", "ss_quantity")
        sr = dfs["store_returns"].select(
            "sr_returned_date_sk", "sr_item_sk", "sr_customer_sk",
            "sr_ticket_number", "sr_return_quantity")
        cs = dfs["catalog_sales"].select(
            "cs_sold_date_sk", "cs_bill_customer_sk", "cs_item_sk",
            "cs_quantity")
        dates = dfs["date_dim"]
        d1 = dates.filter(col("d_quarter_name") == lit(d1_name)) \
            .select("d_date_sk")
        d2 = dates.filter(col("d_quarter_name").isin(*d23_names)) \
            .select("d_date_sk")
        d3 = dates.filter(col("d_quarter_name").isin(*d23_names)) \
            .select("d_date_sk")
        store = dfs["store"].select("s_store_sk", "s_state")
        item = dfs["item"].select("i_item_sk", "i_item_id", "i_item_desc")

        j = ss.join(sr, on=(col("ss_customer_sk") == col("sr_customer_sk"))
                    & (col("ss_item_sk") == col("sr_item_sk"))
                    & (col("ss_ticket_number") == col("sr_ticket_number")))
        j = j.join(cs, on=(col("sr_customer_sk")
                           == col("cs_bill_customer_sk"))
                   & (col("sr_item_sk") == col("cs_item_sk")))
        kept = ["ss_item_sk", "ss_store_sk", "ss_quantity",
                "sr_return_quantity", "cs_quantity"]
        j = j.join(d1, on=col("ss_sold_date_sk") == col("d_date_sk")) \
            .select(*kept, "sr_returned_date_sk", "cs_sold_date_sk")
        j = j.join(d2, on=col("sr_returned_date_sk") == col("d_date_sk")) \
            .select(*kept, "cs_sold_date_sk")
        j = j.join(d3, on=col("cs_sold_date_sk") == col("d_date_sk")) \
            .select(*kept)
        j = j.join(store, on=col("ss_store_sk") == col("s_store_sk"))
        j = j.join(item, on=col("ss_item_sk") == col("i_item_sk"))
        aggs, out = [], list(GROUP)
        for column, alias in QUANTITIES:
            aggs += [("count", column, alias + "count"),
                     ("avg", column, alias + "ave"),
                     ("stddev", column, alias + "stdev")]
            out += [alias + "count", alias + "ave", alias + "stdev",
                    (col(alias + "stdev") / col(alias + "ave"))
                    .alias(alias + "cov")]
        return (j.group_by(*GROUP).agg(*aggs).select(*out)
                .sort(*GROUP).limit(100))

    def run(self, i: int, traced: bool = False, warming: bool = False) -> dict:
        before = _fallbacks()
        rec = super().run(i, traced, warming)
        rec["hashed_fallbacks"] = _fallbacks() - before
        return rec

    def of_metrics(self, metrics) -> dict:
        return {"q17": q17_lanes(metrics),
                "broadcast": broadcast_joins(metrics)}

    # -- the comparison ---------------------------------------------------

    def off_lane(self, rec: dict) -> bool:
        want, got = self.spec.get("lanes", {}), rec["q17"]
        threshold = self.dep.sess.conf.min_device_rows
        index = {}
        for s in got["scans"]:
            if s["index"]:
                index[s["relation"]] = index.get(s["relation"], 0) + 1
        sources = sorted(s["relation"] for s in got["scans"]
                         if not s["index"])
        lanes_ok = all(
            s["lane"] == ("host" if s["rows"] < threshold else "device")
            for s in got["scans"])
        buckets = int(self.dep.config["conf"][
            "spark.hyperspace.index.num.buckets"])
        bucketed = [j for j in got["joins"] if j["buckets"] is not None]
        join_ok = (len(bucketed) == 1 and bucketed[0]["buckets"] == buckets
                   and bucketed[0]["lane"] == "device"
                   and bucketed[0]["match"] == want.get("match"))
        return not (index == want.get("index_scans")
                    and sources == sorted(want.get("source_scans", []))
                    and lanes_ok and join_ok
                    and got["shuffles"] == list(want.get("shuffles", [])))

    def check(self, records: list) -> dict:
        """{name: [number, limit]} over every answer handed in, each
        judged against the one reference answer (the parameters never
        change); frees each answer as it goes."""
        reference = plugins.load(self.dep.bench_dir, "reference",
                                 self.spec.get("reference", self.reference)
                                 ).Reference(self.dep.tables)
        vocabulary = self.dep.dataset.VOCABULARY
        t0 = time.perf_counter()
        params = self.control_params(self.query, self.dep.dataset, None, None)
        answer = reference_columns(reference.answer(self.query, params))
        want = compare.SortedRows(canonical(answer, vocabulary))
        t1 = time.perf_counter()
        for rec in records:
            if "answer" not in rec:
                continue
            try:
                got = arrow_columns(rec.pop("answer"))
                rec["bad"] = compare.mismatched_rows(
                    canonical(got, vocabulary), want)
                rec["cov_err"] = cov_rel_err(got, answer, vocabulary)
            except (ValueError, KeyError) as e:
                note(f"op {rec['op']}: unreadable answer: {e}")
                rec["bad"], rec["cov_err"] = max(want.n, 1), 0.0
        self.firsts.clear()
        judged = [r if "bad" in r else r["same_as"] for r in records]
        bad = [r["bad"] for r in judged]
        note(f"check: reference {t1 - t0:.2f}s ({want.n} rows), "
             f"{len(records)} answers judged in "
             f"{time.perf_counter() - t1:.2f}s")
        return {
            "answers_compared": [len(bad), len(records)],
            "mismatched_rows": [int(sum(bad)), 0],
            "wrong_answers": [sum(1 for b in bad if b), 0],
            "off_lane_queries": [sum(self.off_lane(r) for r in records), 0],
            "hashed_fallbacks": [sum(r["hashed_fallbacks"] for r in records),
                                 0],
            "cov_rel_err": [max((r["cov_err"] for r in judged), default=0.0),
                            COV_REL_ERR],
        }
