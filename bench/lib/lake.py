"""One deployment of the system under test: the config's tables (made by
the dataset the config names) as Parquet files, a session over them
with the config's conf, and the config's covering indexes, all through
the program's public API. This, `run.py` and the files under `ops/`
are the only files of the benchmark that import `hyperspace_tpu`."""

from __future__ import annotations

import os
import time

from lib import plugins


def note(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Deployment:
    def __init__(self, config: dict, seed: int, work_dir: str,
                 tables_used, bench_dir: str, scale=None,
                 conf_overrides=None):
        from hyperspace_tpu import (Hyperspace, HyperspaceConf,
                                    HyperspaceSession)

        self.config = config
        self.work_dir = work_dir
        self.bench_dir = bench_dir
        self.dataset = plugins.load(bench_dir, "datasets", config["dataset"])
        self.scale_factor = float(scale if scale is not None
                                  else config["scale_factor"])
        t0 = time.perf_counter()
        made = self.dataset.make(config, seed, self.scale_factor)
        self.tables = {t: made[t] for t in tables_used}
        t1 = time.perf_counter()
        written = 0
        for name, columns in self.tables.items():
            written += self.dataset.write_parquet(
                columns, os.path.join(work_dir, name),
                config["tables"][name]["files"])
        self.rows = {t: len(next(iter(c.values())))
                     for t, c in self.tables.items()}
        note(f"lake: SF {self.scale_factor:g}, rows {self.rows}, made in "
             f"{t1 - t0:.2f}s, {written} Parquet bytes written in "
             f"{time.perf_counter() - t1:.2f}s")
        conf = dict(config.get("conf", {}))
        conf.update(conf_overrides or {})
        conf["hyperspace.warehouse.dir"] = os.path.join(work_dir, "wh")
        self.sess = HyperspaceSession(HyperspaceConf(conf))
        self.hs = Hyperspace(self.sess)
        self.dfs = {t: self.sess.read_parquet(os.path.join(work_dir, t))
                    for t in self.tables}
        self.sess.enable_hyperspace()

    # -- indexes ----------------------------------------------------------

    def create_index(self, index: str, name=None) -> float:
        """`Hyperspace.create_index` of the config's `index` (under
        `name`, if given); returns the wall seconds."""
        from hyperspace_tpu import IndexConfig

        spec = self.config["indexes"][index]
        t0 = time.perf_counter()
        self.hs.create_index(
            self.dfs[spec["table"]],
            IndexConfig(name or index, list(spec["indexed"]),
                        list(spec["included"])))
        return time.perf_counter() - t0

    def drop_index(self, name: str) -> None:
        self.hs.delete_index(name)
        self.hs.vacuum_index(name)

    def build_lane(self, index: str) -> str:
        from hyperspace_tpu.io.builder import build_lane

        return build_lane(self.rows[self.config["indexes"][index]["table"]])

    def index_dir(self, name: str) -> str:
        found = {r["name"]: r["indexLocation"]
                 for _, r in self.hs.indexes().iterrows()}
        return found[name]

    def close(self) -> None:
        self.sess.close()


def lanes_of(metrics) -> dict:
    """Which lane each part of one query took, from its QueryMetrics."""
    ops = metrics.operators
    scans = [op for op in ops if op.name == "Scan"]
    return {
        "scan": [op.detail.get("lane") for op in scans],
        "index_roots": all(
            op.detail.get("roots") and all("v__=" in r
                                           for r in op.detail["roots"])
            for op in scans),
        "join": [op.detail.get("lane") for op in ops
                 if op.name == "SortMergeJoin"],
        "join_rows": [[op.detail.get("left_rows"), op.detail.get("right_rows")]
                      for op in ops if op.name == "SortMergeJoin"],
        "fusion": [e.get("lane") for e in metrics.events_of("fusion", "lane")],
        "shuffles": [op.name for op in ops
                     if op.name in ("Exchange", "Sort")],
    }


def counters() -> dict:
    from hyperspace_tpu import telemetry

    return dict(telemetry.get_registry().counters_dict())
