"""A later PR adds a configuration, a dataset, a kind of traffic, a kind
of operation with its plain reference, a traffic mix, a cell and a
per-layer metric as new files plus entries appended to BENCHMARK.json,
and edits no file that is there. Done here in a temporary copy: the
dummies resolve and the new cell runs, correct; and with the new
operation's query made wrong it runs not correct."""

import json
import os
import shutil

from conftest import BENCH, ROOT

import run as bench_run

HERE = 'os.path.dirname(os.path.dirname(os.path.abspath(__file__)))'

DUMMY_DATASET = f'''"""A dataset of its own: tpch's tables under another seed, made by a
module that a configuration names."""
import os
from lib import plugins

_tpch = plugins.load({HERE}, "datasets", "tpch")
VOCABULARY, DATE_COLUMNS = _tpch.VOCABULARY, _tpch.DATE_COLUMNS
order_count, range_width = _tpch.order_count, _tpch.range_width
write_parquet = _tpch.write_parquet
USED = []


def make(config, seed, scale_factor):
    USED.append(seed)
    return _tpch.make(config, seed + 1, scale_factor)
'''

DUMMY_DRIVER = f'''"""A kind of traffic of its own: a closed loop that thinks between
operations."""
import os, time
from lib import plugins

_base = plugins.load({HERE}, "drivers", "closed_loop").Driver
THOUGHT = []


class Driver(_base):
    def next_op(self, traced=False):
        time.sleep(self.spec["think_s"])
        THOUGHT.append(self.n_started)
        return super().next_op(traced)
'''

DUMMY_OP = f'''"""A kind of operation of its own: count the lines in a key range."""
import os
from lib import plugins

_base = plugins.load({HERE}, "ops", "select").Op


class Op(_base):
    reference = "dummy_count"

    def dataframe(self, params):
        from hyperspace_tpu import col, lit
        from hyperspace_tpu.plan.expr import when

        c = self.query["range"]["column"]
        one = when(col(c) >= lit(params["lo"]), 1).otherwise(1)
        off = int(os.environ.get("DUMMY_COUNT_OFF_BY", 0))
        return self.dep.dfs[self.query["table"]].filter(
            (col(c) >= lit(params["lo"]))
            & (col(c) < lit(params["hi"] - off))
        ).select(c).agg(("sum", one, "n"))
'''

DUMMY_REFERENCE = '''"""Its plain reference."""
import numpy as np


class Reference:
    def __init__(self, tables):
        self.tables = tables

    def answer(self, query, params):
        keys = self.tables[query["table"]][query["range"]["column"]]
        return {"n": np.array([np.count_nonzero(
            (keys >= params["lo"]) & (keys < params["hi"]))])}
'''

DUMMY_METRIC = '''def compute(run):
    return sum(r['rows'] for r in run['records'])
'''


def test_dummies_of_each_kind_resolve_and_run(tmp_path):
    from lib import plugins

    root = str(tmp_path)
    bench = os.path.join(root, "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    def add(rel, text):
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)

    manifest = bench_run.load_json(os.path.join(root, "BENCHMARK.json"))
    # a dataset, and a configuration that names it: files of their own
    config = bench_run.load_json(
        os.path.join(root, manifest["configs"][0]["file"]))
    config.update(name="dummy_sf", scale_factor=0.01, dataset="dummy_data",
                  source=config["source"] + " (dummy)")
    add("datasets/dummy_data.py", DUMMY_DATASET)
    add("configs/dummy_sf.json", json.dumps(config))
    manifest["configs"].append({
        "name": "dummy_sf", "source": config["source"],
        "file": "bench/configs/dummy_sf.json",
        "reduced": sorted(config["reduced"]), "why": "dummy"})
    # a kind of traffic, a kind of operation and its plain reference
    add("drivers/dummy_paced.py", DUMMY_DRIVER)
    add("ops/dummy_count.py", DUMMY_OP)
    add("reference/dummy_count.py", DUMMY_REFERENCE)
    # a traffic mix: a data file that names them
    traffic = bench_run.load_json(
        os.path.join(bench, "traffic/closed_loop_range.json"))
    traffic["query"]["range"].update(key_share=0.05, starts=2)
    traffic.update(driver="dummy_paced", op="dummy_count", think_s=0.01,
                   lanes={})
    add("traffic/dummy_wide.json", json.dumps(traffic))
    # a cell
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy_sf", "traffic": "dummy_wide",
        "chips": 1, "why": "dummy"})
    # a per-layer metric: a reader of its own
    add("metrics/dummy_rows.py", DUMMY_METRIC)
    manifest["per_layer"].append({
        "name": "dummy_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "API", "moves": "queries_per_s",
        "workloads": ["dummy_cell"]})
    for m in manifest["end_to_end"]:
        if m["name"] in ("queries_per_s", "query_p95_ms"):
            m["workloads"].append("dummy_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    found = bench_run.resolve(manifest, "dummy_cell", root)
    assert found["config"]["name"] == "dummy_sf"
    assert [m["name"] for m in found["per_layer"]] == ["dummy_rows"]
    result = bench_run.run_cell("dummy_cell", 3, 0.5, True, manifest=manifest,
                                root=root, need_chip=False)
    assert result["correct"] is True, result["compared"]
    # every operation of the new kind answers with one row
    assert result["metrics"]["dummy_rows"]["value"] == result["attempted"]
    assert result["compared"]["answers_compared"][0] > result["attempted"]
    # the dummies were the ones that ran
    assert plugins.load(bench, "datasets", "dummy_data").USED == [3]
    thought = plugins.load(bench, "drivers", "dummy_paced").THOUGHT
    assert len(thought) == result["compared"]["answers_compared"][0]

    # ... and the new operation's check bites
    os.environ["DUMMY_COUNT_OFF_BY"] = "1"
    try:
        result = bench_run.run_cell("dummy_cell", 4, 0.3, False,
                                    manifest=manifest, root=root,
                                    need_chip=False)
    finally:
        del os.environ["DUMMY_COUNT_OFF_BY"]
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0
    for path, data in before.items():  # nothing that was there changed
        with open(path, "rb") as fh:
            assert fh.read() == data, path
