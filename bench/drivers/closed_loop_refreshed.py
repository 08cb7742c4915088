"""The traffic kind `closed_loop_refreshed`: `closed_loop`'s one client,
back to back, over a lake that has moved on since its indexes were
built. Set-up builds the mix's indexes over the base tables, THEN lands
the configuration's refresh sets (the dataset's `refresh_sets` /
`land_set`: one new Parquet file a table a set, in the table's own
directory), reads the tables' DataFrames anew (a DataFrame keeps the
listing it was made with) and extends the deployment's `tables` and
`rows` by the landed rows, so that the plain reference answers over the
whole lake. No index is refreshed. Then the operation, as `closed_loop`
makes it; the window writes nothing.
"""

from __future__ import annotations

import os
import time

from lib import plugins
from lib.lake import note

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
closed_loop = plugins.load(_BENCH, "drivers", "closed_loop")


class Driver(closed_loop.Driver):
    def setup(self) -> None:
        dep = self.dep
        for index in self.spec.get("indexes", ()):
            wall = dep.create_index(index)
            note(f"create_index {index}: lane {dep.build_lane(index)}, "
                 f"wall {wall:.2f}s")
        self.landed = self.land_refresh_sets()
        self.op = plugins.load(dep.bench_dir, "ops", self.spec["op"]).Op(
            self.spec, dep, self.seed, self.spans)

    def land_refresh_sets(self) -> dict:
        """{table: [path, ...]}: every refresh set's file, in the order
        the sets landed."""
        dep = self.dep
        t0 = time.perf_counter()
        sets = dep.dataset.refresh_sets(dep.config, self.seed,
                                        dep.scale_factor)
        landed = {t: [] for t in dep.tables}
        for i, one in enumerate(sets):
            for t in dep.tables:
                landed[t].append(dep.dataset.land_set(
                    one[t], os.path.join(dep.work_dir, t), i))
        for t in list(dep.tables):
            dep.tables[t] = dep.dataset.whole(dep.tables[t],
                                              [one[t] for one in sets])
            dep.rows[t] = len(next(iter(dep.tables[t].values())))
            dep.dfs[t] = dep.sess.read_parquet(os.path.join(dep.work_dir, t))
        note(f"refresh: {len(sets)} sets landed after the index builds as "
             f"{ {t: len(p) for t, p in landed.items()} } files "
             f"({sum(os.path.getsize(p) for ps in landed.values() for p in ps)}"
             f" bytes) in {time.perf_counter() - t0:.2f}s; rows now "
             f"{dep.rows}; no index refreshed")
        return landed
