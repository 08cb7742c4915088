"""The operation `build`: one `Hyperspace.create_index` of the config's
`"index"` under a fresh name, the previous build dropped and vacuumed
first, and then the operation `"then"` (default `select`: the mix's
`"query"`) through the new index, which stands for "an index that
`create_index` returned is readable by the next query". The record
keeps `built`, when `create_index` returned: the build rate counts the
time up to it, the query after it is a per-layer reading.

After the window the last index built is read straight from its files.
"""

from __future__ import annotations

import os
import time

import numpy as np

from lib import compare, plugins
from lib.lake import note


class Op:
    def __init__(self, spec: dict, deployment, seed: int, spans):
        self.spec = spec
        self.dep = deployment
        self.spans = spans
        self.then = plugins.load(
            deployment.bench_dir, "ops", spec.get("then", "select")
        ).Op(spec, deployment, seed, spans)
        self._last_build = None
        note(f"build lane of {spec['index']}: "
             f"{deployment.build_lane(spec['index'])}")

    @property
    def warm_ops(self) -> int:
        return self.then.warm_ops

    def run(self, i: int, traced: bool = False, warming: bool = False) -> dict:
        index = self.spec["index"]
        name = f"{index}_b{i}"
        t0 = time.perf_counter()
        if self._last_build is not None:
            with self.spans.span("drop", i):
                self.dep.drop_index(self._last_build)
        with self.spans.span("build", i):
            self.dep.create_index(index, name)
        self._last_build = name
        built = time.perf_counter()
        rec = self.then.run(i, traced, warming)
        # `end` stays the query's own: what follows it there is the
        # harness settling the answer
        rec.update(start=t0, built=built, name=name,
                   rows_indexed=self.dep.rows[
                       self.dep.config["indexes"][index]["table"]])
        return rec

    def check(self, records: list) -> dict:
        out = self.then.check(records)
        out.update(self._check_index_files())
        return out

    def _check_index_files(self) -> dict:
        """The last index the window built, read straight from its files
        with pyarrow: the rows of the source table's indexed and included
        columns, each exactly once, every file sorted by the key."""
        import pyarrow.parquet as pq

        spec = self.dep.config["indexes"][self.spec["index"]]
        vocabulary = self.dep.dataset.VOCABULARY
        names = list(spec["indexed"]) + list(spec["included"])
        root = self.dep.index_dir(self._last_build)
        files = sorted(
            os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
            if f.endswith(".parquet"))
        parts, unsorted_files = [], 0
        for path in files:
            cols = compare.arrow_columns(pq.read_table(path, columns=names),
                                         vocabulary)
            key = cols[spec["indexed"][0]]
            unsorted_files += bool(np.any(key[1:] < key[:-1]))
            parts.append(cols)
        got = {n: np.concatenate([p[n] for p in parts]) for n in names} \
            if parts else {n: np.zeros(0, np.int64) for n in names}
        want = compare.reference_columns(
            {n: self.dep.tables[spec["table"]][n] for n in names})
        return {"index_rows_mismatched":
                [compare.mismatched_rows(got, want), 0],
                "index_files_unsorted": [unsorted_files, 0]}
