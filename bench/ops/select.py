"""The operation `select`: one `DataFrame.collect` of the query a traffic
file describes (`"query"`: `table`, an optional key `range`, an optional
`join` to a second table, `select`), the answer kept and compared with
the plain reference after the window.

An operation is a module of `ops/` with a class `Op(spec, deployment,
seed, spans)` that has `warm_ops`, `run(i, traced, warming) -> record`
and `check(records) -> {name: [number, limit]}`. A record holds at
least `start`, `end`, `rows` and `lanes`. An operation that asks a
different query of the program overrides `dataframe` and names its own
plain reference (`reference/<name>.py`).
"""

from __future__ import annotations

import time

import numpy as np

from lib import compare, plugins
from lib.lake import lanes_of, note


class Op:
    reference = "select"  # reference/<name>.py, unless the mix names one

    def __init__(self, spec: dict, deployment, seed: int, spans):
        self.spec = spec
        self.query = spec["query"]
        self.dep = deployment
        self.spans = spans
        self.rng = np.random.default_rng([int(seed), 0x7AF])
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
        if traced:
            with self.spans.span("plan", i):
                self.dep.plan(df)
        t0 = time.perf_counter()
        with self.spans.span("collect", i):
            table, metrics = df.collect(with_metrics=True)
        t1 = time.perf_counter()
        return {"params": params, "answer": table, "start": t0, "end": t1,
                "rows": table.num_rows, "lanes": lanes_of(metrics)}

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
        """{name: [number, limit]} over every answer handed in. Frees
        each answer as it goes."""
        from concurrent.futures import ThreadPoolExecutor

        reference = plugins.load(
            self.dep.bench_dir, "reference",
            self.spec.get("reference", self.reference)
        ).Reference(self.dep.tables)
        vocabulary = self.dep.dataset.VOCABULARY

        def want_of(params):
            return compare.SortedRows(compare.reference_columns(
                reference.answer(self.query, params)))

        # without a drawn range one reference answer serves every query
        t0 = time.perf_counter()
        shared = None if self.width is not None \
            else want_of(records[0]["params"])
        t_ref = time.perf_counter() - t0

        verified = []  # one answer already found equal to the reference
        hits = [0]

        def judge(rec) -> int:
            table = rec.pop("answer")
            want = shared or want_of(rec["params"])
            try:
                got = compare.arrow_columns(table, vocabulary)
            except (ValueError, KeyError) as e:
                note(f"op {rec['op']}: unreadable answer: {e}")
                return max(want.n, 1)
            # a query asked again answers, as a rule, with the same rows
            # in the same order: equal to a verified answer is verified
            if shared and verified and compare.same_columns(got, verified[0]):
                hits[0] += 1
                return 0
            bad = compare.mismatched_rows(got, want)
            if shared and not bad and not verified:
                verified.append(got)
            return bad

        t0 = time.perf_counter()
        first = [judge(records[0])] if shared else []
        t_first = time.perf_counter() - t0
        with ThreadPoolExecutor(max_workers=6) as pool:
            bad = first + list(pool.map(judge, records[len(first):]))
        note(f"check: shared reference {t_ref:.2f}s, first answer "
             f"{t_first:.2f}s, the other {len(records) - len(first)} "
             f"{time.perf_counter() - t0 - t_first:.2f}s"
             f"{'' if not shared else ', of them equal row for row to the first: ' + str(hits[0])}")
        return {
            "answers_compared": [len(records), len(records)],
            "mismatched_rows": [int(sum(bad)), 0],
            "wrong_answers": [sum(1 for b in bad if b), 0],
            "off_lane_queries": [sum(self.off_lane(r) for r in records), 0],
        }
