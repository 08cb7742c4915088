"""`run.py` off the chip: it refuses the CPU; each cell, rehearsed at a
tiny scale on the CPU backend through the same `run_cell` the command
line calls, comes out correct; and with the timed path broken underneath
it comes out NOT correct. Scale, the device threshold and the chip check
are steered here: the manifest and the command line have no option for
them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

import run as bench_run  # noqa: E402

TINY = dict(scale=0.01, need_chip=False, conf_overrides={
    "spark.hyperspace.execution.min.device.rows": "0",
    "spark.hyperspace.distribution.enabled": "false"})
CELLS = [w["name"] for w in bench_run.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def test_run_py_refuses_the_cpu():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode not in (0, None)
    assert done.stdout.strip() == ""
    reasons = [l for l in done.stderr.splitlines() if "bench/run.py:" in l]
    assert len(reasons) == 1 and "no TPU" in reasons[0]


def test_run_py_exits_non_zero_without_the_program(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        "__pycache__"))
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=300)
    assert done.returncode not in (0, None)
    assert done.stdout.strip() == ""
    assert "not in this checkout" in done.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsed_on_the_cpu_is_correct(cell, trace):
    result = bench_run.run_cell(cell, 2 ** 31 + 9, 1.0, trace, **TINY)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["compared"]["answers_compared"][0] >= result["attempted"]
    json.dumps(result)
    found = bench_run.resolve(
        bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json")), cell)
    if trace:
        assert "breakdown" in result and "busy_s" in result["device"]
        # no device plane on the CPU: trace-read metrics stay out, and no
        # reader reports 0 for a share
        assert not any("roofline" in m for m in result["metrics"])
        # every start of a range was warmed: nothing compiles in the window
        if "window_compile_s" in result["metrics"]:
            assert result["metrics"]["window_compile_s"]["value"] == 0
    else:
        assert set(result["metrics"]) == {m["name"]
                                          for m in found["end_to_end"]}
        assert all(v["value"] > 0 for v in result["metrics"].values())


def test_the_stress_join_mix_is_ready_for_a_later_cell():
    """`traffic/closed_loop_join.json` (the whole lineitem-orders join
    handed to the client, proven on the chip by PR 24's first round) is
    in no cell: a later benchmark PR adds it, and the four-chip join of
    PERF.md's open questions is the same query. It still runs, correct,
    as a cell appended to the manifest."""
    import copy

    manifest = copy.deepcopy(bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json")))
    manifest["workloads"].append({
        "name": "stress_join", "config": "tpch_sf3",
        "traffic": "closed_loop_join", "chips": 1, "why": "stress"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("queries_per_s", "join_p95_ms", "optimize_ms"):
            m["workloads"].append("stress_join")
    result = bench_run.run_cell("stress_join", 11, 1.0, True,
                                manifest=manifest, **TINY)
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["answers_compared"][0] > 2
    assert "optimize_ms" in result["metrics"]


# -- answers altered on their way to the client -------------------------------


def _alter_one_value(table):
    """One float64 value's last bit flipped (or one int64 value + 1 where
    the answer has no float64): an answer altered where it is produced."""
    import pyarrow as pa

    for i, field in enumerate(table.schema):
        if pa.types.is_float64(field.type):
            data = table.column(i).to_numpy().copy()
            row = int(np.flatnonzero(np.isfinite(data) & (data != 0))[0])
            data[row] = np.nextafter(data[row], np.inf)
            return table.set_column(i, field.name, pa.array(data))
    for i, field in enumerate(table.schema):
        if pa.types.is_int64(field.type):
            data = table.column(i).to_numpy().copy()
            data[0] += 1
            return table.set_column(i, field.name, pa.array(data))
    raise AssertionError("no float64 or int64 column to alter")


def _answers_through(monkeypatch, alter):
    """Every answer the scheduler hands back goes through
    `alter(n, table)`, n counting from 1; returns the counter."""
    from hyperspace_tpu.engine import scheduler

    sched = scheduler.get_scheduler()
    real, calls = sched.collect, [0]

    def collect(df, **kw):
        table, metrics = real(df, **kw)
        calls[0] += 1
        return alter(calls[0], table), metrics

    monkeypatch.setattr(sched, "collect", collect)
    return calls


# -- what the harness holds through a window --------------------------------


RANGE = next(c for c in CELLS if "range" in c)


def _records_of(monkeypatch):
    """What the driver's records of the next `run_cell` reach when the
    window has closed (before the check frees the answers): how many
    records, distinct `params` and live Arrow tables."""
    from conftest import plug

    driver = plug("drivers", "closed_loop").Driver
    seen = {}
    real = driver.check

    def check(self):
        import pyarrow as pa

        records = self.warm_records + self.records
        seen["records"] = len(records)
        seen["tables"] = len({
            id(t) for r in records
            for t in (r.get("answer"), r.get("same_as", {}).get("answer"))
            if isinstance(t, pa.Table)})
        seen["params"] = len({json.dumps(r["params"], sort_keys=True)
                              for r in records})
        return real(self)

    monkeypatch.setattr(driver, "check", check)
    return seen


def test_a_window_keeps_one_table_per_distinct_params(monkeypatch, capsys):
    seen = _records_of(monkeypatch)
    result = bench_run.run_cell(RANGE, 21, 1.0, False, **TINY)
    assert result["correct"] is True, result["compared"]
    assert seen["records"] > seen["params"] == 8
    assert seen["tables"] <= seen["params"]
    assert result["compared"]["answers_compared"] == [seen["records"]] * 2
    # the `where` note parses, and its quarters hold the window's operations
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[bench] where: ")]
    assert len(lines) == 1
    where = json.loads(lines[0][len("[bench] where: "):])
    assert where == result["where"]
    assert sum(where["quarter_ops"]) == where["ops"] == result["attempted"]
    assert where["op_ms"]["p50"] <= where["op_ms"]["p95"] <= where["op_ms"]["max"]
    assert where["stalls"]["n"] >= 0 and where["settle_ms_p50"] > 0
    assert len(where["rss_bytes"]) == 2 and min(where["rss_bytes"]) > 0
    assert sum(where["cpu_s"]) > 0 and where["gc"]["collections"] >= 0
    assert where["fs"] != "unknown" and where["stat_us"] > 0


def test_one_wrong_repeat_is_one_wrong_answer(monkeypatch):
    """One changed bit in one column of one repeat (the 11th answer: the
    8 starts are warmed first) is kept whole, judged and counted once."""
    calls = _answers_through(
        monkeypatch, lambda n, t: _alter_one_value(t) if n == 11 else t)
    result = bench_run.run_cell(RANGE, 22, 1.0, False, **TINY)
    assert calls[0] > 11
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"] == [1, 0]
    assert result["compared"]["mismatched_rows"][0] >= 1
    assert result["compared"]["answers_compared"][0] == calls[0]


def test_a_repeat_in_another_order_is_correct(monkeypatch):
    """Every 3rd answer comes back with its rows reversed: not the bytes
    of its first, so kept and judged as a multiset, and right."""
    import pyarrow as pa

    seen = _records_of(monkeypatch)
    calls = _answers_through(
        monkeypatch, lambda n, t: t.take(pa.array(
            np.arange(t.num_rows)[::-1])) if n % 3 == 0 else t)
    result = bench_run.run_cell(RANGE, 23, 1.0, False, **TINY)
    assert result["correct"] is True, result["compared"]
    assert seen["params"] < seen["tables"] < seen["records"]
    assert result["compared"]["answers_compared"][0] == calls[0]


# -- the timed path broken underneath ---------------------------------------


@pytest.fixture
def altered_answers(monkeypatch):
    """Every 3rd answer the scheduler hands back carries one altered
    value."""
    _answers_through(
        monkeypatch, lambda n, t: _alter_one_value(t) if n % 3 == 0 else t)


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(cell, altered_answers):
    result = bench_run.run_cell(cell, 5, 1.0, False, **TINY)
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0
    assert result["compared"]["mismatched_rows"][0] > 0


def test_an_index_written_wrong_is_not_correct(monkeypatch):
    """The build cell: a value altered where the index files are
    written. The query through the index and the files' own check both
    see it."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    real = pq.write_table
    hits = [0]

    def write_table(table, where, *a, **kw):
        if "v__=" in str(where) and "l_extendedprice" in table.column_names \
                and table.num_rows:
            hits[0] += 1
            i = table.column_names.index("l_extendedprice")
            data = table.column(i).to_numpy().copy()
            data[0] = 12345.678
            table = table.set_column(i, "l_extendedprice", pa.array(data))
        return real(table, where, *a, **kw)

    monkeypatch.setattr(pq, "write_table", write_table)
    cell = next(c for c in CELLS if "build" in c)
    result = bench_run.run_cell(cell, 6, 1.0, False, **TINY)
    assert hits[0] > 0
    assert result["correct"] is False
    assert result["compared"]["index_rows_mismatched"][0] > 0


def test_half_of_the_rows_left_out_is_not_correct(monkeypatch):
    """The aggregate cell: every index file of lineitem written with half
    of its rows, so the counts are taken over the rest."""
    import pyarrow.parquet as pq

    real = pq.write_table
    hits = [0]

    def write_table(table, where, *a, **kw):
        if "v__=" in str(where) and "l_shipmode" in table.column_names \
                and table.num_rows > 1:
            hits[0] += 1
            table = table.slice(0, table.num_rows // 2)
        return real(table, where, *a, **kw)

    monkeypatch.setattr(pq, "write_table", write_table)
    cell = next(c for c in CELLS if "q12" in c)
    result = bench_run.run_cell(cell, 8, 1.0, False, **TINY)
    assert hits[0] > 0
    assert result["correct"] is False
    assert result["compared"]["wrong_answers"][0] > 0


def test_a_query_off_its_lane_is_not_correct():
    """Below the device threshold the scan takes the host lane: the
    answers are right, the cell is not the cell it is named for."""
    tiny = dict(TINY, conf_overrides={
        "spark.hyperspace.distribution.enabled": "false"})
    cell = next(c for c in CELLS if "range" in c)
    result = bench_run.run_cell(cell, 7, 1.0, False, **tiny)
    assert result["compared"]["mismatched_rows"][0] == 0
    assert result["compared"]["off_lane_queries"][0] > 0
    assert result["correct"] is False


# -- the control --------------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_is_not_correct(cell, seed):
    """The next precision down (float64 through float32, dates through
    bfloat16), in the reference put in the program's place, fails the
    comparison's limit of 0."""
    from conftest import plug
    from lib import control

    found = bench_run.resolve(
        bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json")), cell)
    traffic, config = found["traffic"], found["config"]
    dataset = plug("datasets", config["dataset"])
    op = plug("ops", traffic.get("then", traffic["op"])).Op
    reference = plug("reference", traffic.get("reference", op.reference))
    tables = dataset.make(config, seed, 0.01)
    params = op.control_params(traffic["query"], dataset, 0.01, seed)
    reading = control.control_reading(reference, tables, dataset,
                                      traffic["query"], params)
    assert reading["mismatched_rows"] > 0.5 * reading["rows"]
