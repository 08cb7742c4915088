"""The operation `tpch_q6_skip`: TPC-H query 6 ("Forecasting Revenue
Change", specification clause 2.4.6) as the program's own query corpus
states it (`hyperspace_tpu.tpch.queries.q6`), served through a
data-skipping index: the op builds the configuration's skipping indexes
in its set-up with `Hyperspace.create_index(df,
DataSkippingIndexConfig(...))` (per-file min/max sketches of the
columns named, upstream's `MinMaxSketch`), and every query runs through
`DataFrame.collect`, whose filter rule restricts the `lineitem` scan to
the files the sketches cannot refute. DATE is January 1 of one of the
mix's `years`, each once in order while warming up (the program compiles
a fused stage per literal), then drawn from the seed; DISCOUNT and
QUANTITY are the validation run's (the mix's `query`).

Each query's record keeps what served it: the rule's event (`served`,
the index, `files_considered`, `bytes_pruned`) and the one scan's
record (`files_kept`, `rows_scanned`, `bytes_scanned`, `scan_lane`).

The comparison is this file's own. Every answer is one row whose
revenue must lie within one cent of the reference's exact revenue
(`reference/tpch_q6_skip.py`: integer cents times integer hundredths
over the whole table), the configuration's guarantee: `revenue_miss`
gives the worst miss in dollars against its limit of 0.01. The same
year asked again must answer the same float64 to the bit (the
configuration's guarantee too): a repeat that is not the bytes of its
year's first answer is wrong however near it lies. Compared besides,
each with limit 0: answers that break either (`wrong_answers`) and
their rows (`mismatched_rows`); queries not served by the mix's skipping index from
the source files (`unskipped_queries`); queries whose scans of
`lineitem` are not the one scan on the mix's lane (`off_lane_scans`);
and queries that kept another number of files than the layout's
expected count for their year (`wrong_files_kept`), which is counted
from the generated table alone: a month's file can hold a row of the
year where its least `l_shipdate` is before the year's end and its
greatest on or after its start.
"""

from __future__ import annotations

import os
import time
from fractions import Fraction

import numpy as np

from lib import plugins
from lib.lake import note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CENT = Fraction(1, 100)  # the configuration's guarantee, in dollars


def skipping_record(metrics) -> dict:
    """What served one query, from its QueryMetrics: the filter rule's
    skipping event and the one `Scan` operator's record."""
    applied = [ix for e in metrics.events_of("rule", "FilterIndexRule")
               if e.get("action") == "applied"
               for ix in e.get("indexes", ()) if ix.get("side") == "skipping"]
    scans = [op for op in metrics.operators if op.name == "Scan"]
    rec = {"served": [ix.get("served") for ix in applied],
           "skipping_index": [ix.get("name") for ix in applied],
           "files_considered": sum(ix.get("files_considered", 0)
                                   for ix in applied),
           "bytes_pruned": sum(ix.get("bytes_pruned", 0) for ix in applied),
           "scan_lane": [op.detail.get("lane") for op in scans],
           "scan_source": [op.detail.get("source") for op in scans]}
    rec["files_kept"] = sum(op.detail.get("files_scanned") or 0
                            for op in scans)
    rec["rows_scanned"] = sum(op.rows_out or 0 for op in scans)
    rec["bytes_scanned"] = sum(op.detail.get("bytes_scanned") or 0
                               for op in scans)
    return rec


def file_ship_ranges(lineitem: dict, dataset) -> tuple:
    """(least, greatest) `l_shipdate` of each file of the lake's month
    layout, from the generated rows alone (`lineitem` in `make`'s
    order)."""
    starts = [b[0] for b in dataset.file_months(lineitem)]
    ship = lineitem["l_shipdate"]
    return np.minimum.reduceat(ship, starts), np.maximum.reduceat(ship, starts)


def expected_files(ranges: tuple, year: int) -> int:
    """The files that can hold a row of `year`'s ship-date window: those
    whose `l_shipdate` range (`file_ship_ranges`) meets it."""
    from_ref = plugins.load(_BENCH, "reference", "tpch_q6_skip")
    lo, hi = from_ref.year_days(year)
    least, most = ranges
    return int(((least < hi) & (most >= lo)).sum())


def revenue_miss(table, revenue_e4: int):
    """|the answer's revenue - the exact one| in dollars, as a Fraction
    (exact), or None where the answer is not one row with a finite
    `revenue`."""
    if table.num_rows != 1 or table.column_names != ["revenue"]:
        return None
    value = table.column(0)[0].as_py()
    if value is None or not np.isfinite(value):
        return None
    return abs(Fraction(value) - Fraction(revenue_e4, 10 ** 4))


class Op(plugins.load(_BENCH, "ops", "select").Op):
    reference = "tpch_q6_skip"

    def __init__(self, spec: dict, deployment, seed: int, spans):
        super().__init__(spec, deployment, seed, spans)
        from hyperspace_tpu import DataSkippingIndexConfig

        self.years = list(self.query["years"])
        for name in spec.get("skipping_indexes", ()):
            index = deployment.config["skipping_indexes"][name]
            t0 = time.perf_counter()
            deployment.hs.create_index(
                deployment.dfs[index["table"]],
                DataSkippingIndexConfig(name, list(index["columns"]),
                                        sketch_types=list(
                                            index["sketch_types"])))
            note(f"create_index {name} (data skipping, "
                 f"{'+'.join(index['sketch_types'])} on "
                 f"{', '.join(index['columns'])}): wall "
                 f"{time.perf_counter() - t0:.2f}s")

    @property
    def warm_ops(self) -> int:
        """Every year once: each is a literal of its own."""
        return max(self.spec.get("warm_ops", 2), len(self.years))

    def params(self, i: int, warming: bool) -> dict:
        if warming and i < len(self.years):
            return {"year": self.years[i]}
        return {"year": self.years[int(self.rng.integers(0, len(self.years)))]}

    @staticmethod
    def control_params(query: dict, dataset, scale_factor, seed) -> dict:
        return {"year": query["years"][
            int(np.random.default_rng(seed).integers(0, len(query["years"])))]}

    def dataframe(self, params: dict):
        from hyperspace_tpu.tpch import queries

        return queries.q6({"lineitem": self.dep.dfs[self.query["table"]]},
                          params["year"])

    def of_metrics(self, metrics) -> dict:
        return skipping_record(metrics)

    # -- the comparison ---------------------------------------------------

    def unskipped(self, rec: dict) -> bool:
        want = self.spec.get("lanes", {})
        return not (rec["served"] == [want.get("served", "source")]
                    and rec["skipping_index"]
                    == list(self.spec.get("skipping_indexes", ())))

    def off_lane(self, rec: dict) -> bool:
        want = self.spec.get("lanes", {})
        return not (rec["scan_lane"] == list(want.get("scan", ["device"]))
                    and rec["scan_source"] == [self.query["table"]])

    def check(self, records: list) -> dict:
        """{name: [number, limit]} over every answer handed in, each
        judged against the reference's exact revenue for its year (a
        repeat of its first answer's bytes takes that one's verdict);
        frees each answer as it goes."""
        reference = plugins.load(self.dep.bench_dir, "reference",
                                 self.spec.get("reference", self.reference)
                                 ).Reference(self.dep.tables)
        lineitem = self.dep.tables[self.query["table"]]
        t0 = time.perf_counter()
        years = sorted({r["params"]["year"] for r in records})
        exact = {y: int(reference.answer(self.query, {"year": y})
                        ["revenue_e4"][0]) for y in years}
        ranges = file_ship_ranges(lineitem, self.dep.dataset)
        files = {y: expected_files(ranges, y) for y in years}
        t1 = time.perf_counter()
        firsts = {id(first) for first, _ in self.firsts.values()}
        for rec in records:
            if "answer" in rec:
                table = rec.pop("answer")
                rec["rows_out"] = table.num_rows
                rec["miss"] = revenue_miss(table,
                                           exact[rec["params"]["year"]])
                # not its year's first answer, nor its bytes
                rec["unrepeated"] = id(rec) not in firsts
        self.firsts.clear()
        judged = [r if "same_as" not in r else r["same_as"] for r in records]
        misses = [r["miss"] for r in judged]
        wrong = [r for r, j in zip(records, judged)
                 if j["miss"] is None or j["miss"] > CENT
                 or r.get("unrepeated")]
        worst = max((m for m in misses if m is not None), default=0)
        note(f"check: reference {t1 - t0:.2f}s for years {years} (files "
             f"kept by the layout: {files}); worst revenue miss "
             f"{float(worst):.6g} dollars over {len(records)} answers")
        return {
            "answers_compared": [len(misses), len(records)],
            "wrong_answers": [len(wrong), 0],
            "mismatched_rows": [sum(max(r.get("rows_out", 1), 1)
                                    for r in wrong), 0],
            "revenue_miss": [float(worst), float(CENT)],
            "unskipped_queries": [sum(self.unskipped(r) for r in records),
                                  0],
            "off_lane_scans": [sum(self.off_lane(r) for r in records), 0],
            "wrong_files_kept": [sum(
                r["files_kept"] != files[r["params"]["year"]]
                for r in records), 0],
        }
