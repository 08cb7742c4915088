"""The operation `select`: one `DataFrame.collect` of the query a traffic
file describes (`"query"`: `table`, an optional key `range`, an optional
`join` to a second table, `select`), every answer compared with the
plain reference after the window.

What is held until then does not grow with the rate: the first answer
for each distinct `params` is kept whole; a later one is compared with
that first on arrival, after its `end` is stamped, buffer by buffer
(`lib/compare.same_buffers`), and where the two are the same bytes the
record keeps a pointer to the first's record (whose verdict is then
its own) and the table is dropped. An answer that differs from its
first is kept whole and judged after the window like a first: it may
hold the same rows in another order. So a mix whose parameters never
repeat keeps every answer, and one that repeats them keeps one each.

An operation is a module of `ops/` with a class `Op(spec, deployment,
seed, spans)` that has `warm_ops`, `run(i, traced, warming) -> record`
and `check(records) -> {name: [number, limit]}`. A record holds at
least `start`, `end`, `rows` and `lanes`. An operation that asks a
different query of the program overrides `dataframe` and names its own
plain reference (`reference/<name>.py`).
"""

from __future__ import annotations

import json
import time

import numpy as np

from lib import compare, plugins
from lib.lake import lanes_of, note


def _key(params: dict) -> str:
    return json.dumps(params, sort_keys=True)


class Op:
    reference = "select"  # reference/<name>.py, unless the mix names one

    def __init__(self, spec: dict, deployment, seed: int, spans):
        self.spec = spec
        self.query = spec["query"]
        self.dep = deployment
        self.spans = spans
        self.rng = np.random.default_rng([int(seed), 0x7AF])
        self.firsts = {}  # params -> (record, table) of their first answer
        self.width, self.starts = None, []
        if "range" in self.query:
            r, ds = self.query["range"], deployment.dataset
            n = ds.order_count(deployment.scale_factor)
            self.width = ds.range_width(n, r["key_share"])
            k = int(r.get("starts", 8))
            self.starts = [1 + (2 * i + 1) * (n - self.width) // (2 * k)
                           for i in range(k)]

    @property
    def warm_ops(self) -> int:
        """Operations that warm every shape the window uses: each fixed
        start of a range once, and two of anything."""
        return max(self.spec.get("warm_ops", 2), len(self.starts))

    # -- one operation ----------------------------------------------------

    def params(self, i: int, warming: bool) -> dict:
        """The range's start: one of the mix's fixed `starts`, each once
        in order while warming up, then drawn from the seed. The program
        compiles a fused stage per literal value (`engine/fusion.py`
        keys the stage on the predicate's serialised form), so a start
        that was not warmed would compile inside the window."""
        if self.width is None:
            return {}
        if warming and i < len(self.starts):
            lo = self.starts[i]
        else:
            lo = self.starts[int(self.rng.integers(0, len(self.starts)))]
        return {"lo": lo, "hi": lo + self.width}

    @staticmethod
    def control_params(query: dict, dataset, scale_factor: float,
                       seed: int) -> dict:
        """The parameters of one query for the control, which has no
        deployment: a range anywhere among the keys."""
        if "range" not in query:
            return {}
        n = dataset.order_count(scale_factor)
        width = dataset.range_width(n, query["range"]["key_share"])
        lo = int(np.random.default_rng(seed).integers(1, n - width + 2))
        return {"lo": lo, "hi": lo + width}

    def dataframe(self, params: dict):
        """The described query as a DataFrame of the program."""
        from hyperspace_tpu import col, lit

        query, dep = self.query, self.dep
        df = dep.dfs[query["table"]]
        if "range" in query:
            c = query["range"]["column"]
            df = df.filter((col(c) >= lit(params["lo"]))
                           & (col(c) < lit(params["hi"])))
        if "join" in query:
            j = query["join"]
            left_cols = [c for c in query["select"]
                         if c in dep.tables[query["table"]]]
            right_cols = [c for c in query["select"]
                          if c in dep.tables[j["table"]]]
            right = dep.dfs[j["table"]].select(j["right_on"], *right_cols)
            df = df.select(j["left_on"], *left_cols).join(
                right, on=col(j["left_on"]) == col(j["right_on"]))
        return df.select(*query["select"])

    def run(self, i: int, traced: bool = False, warming: bool = False) -> dict:
        params = self.params(i, warming)
        df = self.dataframe(params)
        t0 = time.perf_counter()
        with self.spans.span("collect", i):
            table, metrics = df.collect(with_metrics=True)
        t1 = time.perf_counter()
        rec = {"params": params, "start": t0, "end": t1,
               "rows": table.num_rows, "lanes": lanes_of(metrics)}
        rec.update(self.of_metrics(metrics))
        with self.spans.span("settle", i):
            self.settle(rec, table)
        return rec

    def of_metrics(self, metrics) -> dict:
        """What else of the query's QueryMetrics an operation's records
        keep (nothing here)."""
        return {}

    def settle(self, rec: dict, table) -> None:
        """Between two operations, so in the rate and in no latency: the
        record holds either its `answer`, or `same_as`, the record of
        the first answer to the same `params`, whose bytes this one
        repeated."""
        first = self.firsts.setdefault(_key(rec["params"]), (rec, table))
        if first[0] is not rec and compare.same_buffers(table, first[1]):
            rec["same_as"] = first[0]
        else:
            rec["answer"] = table

    # -- the comparison ---------------------------------------------------

    def off_lane(self, rec: dict) -> bool:
        """Whether the query ran elsewhere than the mix's `lanes` say:
        served from index version directories, `scan` and `fusion` lanes
        as listed, the join's lane none of `join_not`, and no Exchange
        or Sort beyond those listed under `shuffles`."""
        want, got = self.spec.get("lanes", {}), rec["lanes"]
        return bool(
            not got["index_roots"]
            or got["shuffles"] != list(want.get("shuffles", []))
            or ("scan" in want and got["scan"] != want["scan"])
            or ("fusion" in want and got["fusion"] != want["fusion"])
            or ("join" in want and got["join"] != want["join"])
            or ("join_not" in want and (
                len(got["join"]) != 1 or got["join"][0] is None
                or got["join"][0] in want["join_not"])))

    def check(self, records: list) -> dict:
        """{name: [number, limit]} over every answer handed in: those
        the records still hold against the reference, one reference
        answer per distinct `params`; the others by the verdict on the
        first answer whose bytes they repeated. Frees each answer as it
        goes."""
        from concurrent.futures import ThreadPoolExecutor

        reference = plugins.load(
            self.dep.bench_dir, "reference",
            self.spec.get("reference", self.reference)
        ).Reference(self.dep.tables)
        vocabulary = self.dep.dataset.VOCABULARY

        def judge(rec, want) -> int:
            try:
                got = compare.arrow_columns(rec.pop("answer"), vocabulary)
            except (ValueError, KeyError) as e:
                note(f"op {rec['op']}: unreadable answer: {e}")
                return max(want.n, 1)
            return compare.mismatched_rows(got, want)

        def judge_all(held: list) -> None:
            want = compare.SortedRows(compare.reference_columns(
                reference.answer(self.query, held[0]["params"])))
            for rec in held:
                rec["bad"] = judge(rec, want)

        by_params = {}
        for rec in records:
            if "answer" in rec:
                by_params.setdefault(_key(rec["params"]), []).append(rec)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=6) as pool:
            list(pool.map(judge_all, by_params.values()))
        self.firsts.clear()
        held = sum(len(h) for h in by_params.values())
        note(f"check: answers held: {held}, for {len(by_params)} distinct "
             f"params, judged against the reference in "
             f"{time.perf_counter() - t0:.2f}s; the other "
             f"{len(records) - held} were the bytes of their first")
        bad = [r["bad"] if "bad" in r else r["same_as"]["bad"]
               for r in records]
        return {
            "answers_compared": [len(bad), len(records)],
            "mismatched_rows": [int(sum(bad)), 0],
            "wrong_answers": [sum(1 for b in bad if b), 0],
            "off_lane_queries": [sum(self.off_lane(r) for r in records), 0],
        }
