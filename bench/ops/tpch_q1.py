"""The operation `tpch_q1`: TPC-H query 1 ("Pricing Summary Report",
specification clause 2.4.1) as the program's own query corpus states it
(`hyperspace_tpu.tpch.queries.q1`, the validation run's DELTA of 90),
through `DataFrame.collect`, whose filter rule serves the `lineitem`
scan from the mix's covering index on `l_shipdate` (its first indexed
column is the one the predicate names, and it includes every column the
query reads). One parameter set: every query is the same one.

Each query's record keeps what served it: the index the filter rule
applied, each scan's index and lane, and the aggregate operator's
record (`path`, `groups`, `masked`).

The comparison is this file's own: the answer's sums are float64 under
a stated tolerance, so it is not the harness's bit comparison. Every
answer is the report of the reference's groups, in key order, with
`count_order` and `sum_qty` exact (whole numbers) and every other sum
and average within a relative `Q1_REL_ERR` of the reference's exact
value (`reference/tpch_q1.py`: integer sums over the whole generated
table); `q1_rel_err` gives the worst relative miss over every answer
against that limit. A repeat must be the bytes of the first answer (the
configuration's guarantee), however near it lies. Compared besides,
each with limit 0: answers that break any of it (`wrong_answers`) and
their rows (`mismatched_rows`), and queries not served from the mix's
index by ONE scan on the mix's lane (`off_lane_queries`).
"""

from __future__ import annotations

import os
import time
from fractions import Fraction

import numpy as np

from lib import plugins
from lib.lake import note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's guarantee: about a dollar of the largest sum
# (about 1.1e12), 100 times tighter than TPC-H's own answer rule
Q1_REL_ERR = 2.0 ** -40
KEYS = ("l_returnflag", "l_linestatus")
COLUMNS = KEYS + ("sum_qty", "sum_base_price", "sum_disc_price",
                  "sum_charge", "avg_qty", "avg_price", "avg_disc",
                  "count_order")


def exact_values(sums: dict) -> dict:
    """A group's SQL values, exactly, from the reference's integer sums."""
    n = sums["count_order"]
    return {"sum_qty": Fraction(sums["sum_qty"]),
            "sum_base_price": Fraction(sums["sum_base_price_e2"], 100),
            "sum_disc_price": Fraction(sums["sum_disc_price_e4"], 10 ** 4),
            "sum_charge": Fraction(sums["sum_charge_e6"], 10 ** 6),
            "avg_qty": Fraction(sums["sum_qty"], n),
            "avg_price": Fraction(sums["sum_base_price_e2"], 100 * n),
            "avg_disc": Fraction(sums["sum_disc_e2"], 100 * n)}


def judge(table, groups: dict, vocabulary: dict):
    """(worst relative miss of the answer's float sums and averages, or
    None where the answer is not the report's shape; the rows that break
    the guarantee) of one answer against the reference's `groups`."""
    if tuple(table.column_names) != COLUMNS:
        return None, max(table.num_rows, 1)
    rows = table.to_pydict()
    codes = [tuple(vocabulary[k].index(v) if v in vocabulary[k] else -1
                   for k, v in zip(KEYS, key))
             for key in zip(*(rows[k] for k in KEYS))]
    if codes != list(groups):
        return None, max(table.num_rows, len(groups))
    worst, bad = 0.0, 0
    for i, key in enumerate(codes):
        want = exact_values(groups[key])
        row_bad = (rows["count_order"][i] != groups[key]["count_order"]
                   or rows["sum_qty"][i] != groups[key]["sum_qty"])
        for name, exact in want.items():
            got = rows[name][i]
            if got is None or not np.isfinite(got):
                row_bad, miss = True, float("inf")
            else:
                miss = float(abs(Fraction(got) - exact) / abs(exact)) \
                    if exact else abs(got)
            worst = max(worst, miss)
            row_bad = row_bad or miss > Q1_REL_ERR
        bad += row_bad
    return worst, bad


class Op(plugins.load(_BENCH, "ops", "select").Op):
    reference = "tpch_q1"

    def params(self, i: int, warming: bool) -> dict:
        return {"delta": self.query["delta"]}

    @staticmethod
    def control_params(query: dict, dataset, scale_factor, seed) -> dict:
        return {"delta": query["delta"]}

    def dataframe(self, params: dict):
        from hyperspace_tpu.tpch import queries

        if params["delta"] != 90:
            raise ValueError("tpch.queries.q1 is the validation run's "
                             "query, DELTA 90")
        return queries.q1({"lineitem": self.dep.dfs[self.query["table"]]})

    def of_metrics(self, metrics) -> dict:
        applied = [ix.get("name")
                   for e in metrics.events_of("rule", "FilterIndexRule")
                   if e.get("action") == "applied"
                   for ix in e.get("indexes", ())]
        scans = [op for op in metrics.operators if op.name == "Scan"]
        return {"served": applied,
                "scan_index": [op.detail.get("index") for op in scans],
                "scan_lane": [op.detail.get("lane") for op in scans],
                "aggregate": [{k: op.detail.get(k)
                               for k in ("path", "groups", "masked")}
                              for op in metrics.operators
                              if op.name == "Aggregate"]}

    # -- the comparison ---------------------------------------------------

    def off_lane(self, rec: dict) -> bool:
        index = list(self.spec.get("indexes", ()))
        want = self.spec.get("lanes", {})
        return not (rec["served"] == index and rec["scan_index"] == index
                    and rec["scan_lane"] == list(want.get("scan",
                                                          ["device"])))

    def check(self, records: list) -> dict:
        """{name: [number, limit]} over every answer handed in, each
        judged against the reference's exact groups (a repeat of its
        first answer's bytes takes that one's verdict); frees each
        answer as it goes."""
        reference = plugins.load(self.dep.bench_dir, "reference",
                                 self.spec.get("reference", self.reference)
                                 ).Reference(self.dep.tables)
        vocabulary = self.dep.dataset.VOCABULARY
        t0 = time.perf_counter()
        groups = {r["params"]["delta"]: None for r in records}
        groups = {d: reference.groups(self.query, {"delta": d})
                  for d in groups}
        t1 = time.perf_counter()
        firsts = {id(first) for first, _ in self.firsts.values()}
        for rec in records:
            if "answer" in rec:
                table = rec.pop("answer")
                rec["rows_out"] = table.num_rows
                rec["rel_err"], rec["bad_rows"] = judge(
                    table, groups[rec["params"]["delta"]], vocabulary)
                # not its params' first answer, nor its bytes
                if id(rec) not in firsts:
                    rec["bad_rows"] = max(table.num_rows, 1)
        self.firsts.clear()
        judged = [r if "same_as" not in r else r["same_as"] for r in records]
        wrong = [j for j in judged if j["rel_err"] is None or j["bad_rows"]]
        worst = max((j["rel_err"] for j in judged
                     if j["rel_err"] is not None), default=0.0)
        sizes = {d: [g["count_order"] for g in gs.values()]
                 for d, gs in groups.items()}
        note(f"check: reference {t1 - t0:.2f}s, lines a group by DELTA "
             f"{sizes}; worst relative miss {worst:.3g} (2^"
             f"{np.log2(worst) if worst else float('-inf'):.1f}) over "
             f"{len(records)} answers")
        return {
            "answers_compared": [len(judged), len(records)],
            "wrong_answers": [len(wrong), 0],
            "mismatched_rows": [sum(max(j["bad_rows"], 1) for j in wrong),
                                0],
            "q1_rel_err": [worst, Q1_REL_ERR],
            "off_lane_queries": [sum(self.off_lane(r) for r in records), 0],
        }
