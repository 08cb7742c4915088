"""The four-chip cell off the chip: rehearsed at 1/100 scale in a process
of its own with 4 virtual CPU devices and distribution ON (the rehearsal
of `test_run.py` runs every cell on one device with distribution off,
where the cell's op holds a query to the one-chip lanes). With the mesh
held it comes out correct; with the SPMD lane switched off underneath
(a fallback) or an answer altered it comes out NOT correct."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "tpch_sf3_join_x4"

REHEARSAL = r'''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
root, bench, cell, fault, trace = sys.argv[1:6]
sys.path[:0] = [root, bench]
from hyperspace_tpu.parallel.virtual import ensure_devices
ensure_devices(4)
import jax
assert len(jax.devices()) == 4
import run as bench_run

# the two row thresholds the cell's own size clears by itself
conf = {"spark.hyperspace.execution.min.device.rows": "0",
        "spark.hyperspace.distribution.min.rows": "0"}
if fault == "spmd_off":
    conf["spark.hyperspace.distribution.spmd.enabled"] = "false"
if fault == "buckets_odd":
    conf["spark.hyperspace.index.num.buckets"] = "62"
if fault == "altered":
    sys.path.insert(0, os.path.join(bench, "tests"))
    from test_run import _alter_one_value
    from hyperspace_tpu.engine import scheduler
    sched = scheduler.get_scheduler()
    real, calls = sched.collect, [0]

    def collect(df, **kw):
        table, metrics = real(df, **kw)
        calls[0] += 1
        return (_alter_one_value(table) if calls[0] % 3 == 0 else table,
                metrics)

    sched.collect = collect
result = bench_run.run_cell(cell, 2 ** 31 + 27, 1.0, trace == "1",
                            scale=0.01, need_chip=False,
                            conf_overrides=conf)
print("RESULT " + json.dumps(result))
'''


def rehearse(fault: str = "none", trace: bool = False) -> dict:
    done = subprocess.run(
        [sys.executable, "-c", REHEARSAL, ROOT, BENCH, CELL, fault,
         str(int(trace))],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    lines = [l for l in done.stdout.splitlines() if l.startswith("RESULT ")]
    assert done.returncode == 0 and lines, done.stdout[-2000:] + \
        done.stderr[-4000:]
    result = json.loads(lines[-1][len("RESULT "):])
    result["notes"] = done.stdout
    return result


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearsed_on_four_virtual_devices_is_correct(trace):
    result = rehearse(trace=trace)
    compared = result["compared"]
    assert result["correct"] is True, compared
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("mismatched_rows", "wrong_answers", "off_lane_queries",
                 "failed_ops", "spmd_fallbacks", "unsharded_indexes",
                 "idle_shards"):
        assert compared[name] == [0, 0], (name, compared)
    assert compared["answers_compared"][0] >= result["attempted"] + 2
    assert result["device"]["count"] == 4
    # the mesh was held: both indexes born sharded over the 4 devices,
    # every query on the SPMD lane with rows on every shard
    assert result["notes"].count("born sharded over 4") == 2
    assert "'join': ['spmd']" in result["notes"]
    if trace:
        metrics = result["metrics"]
        # program counters are read on the CPU too; no device plane, so
        # no reader of the device trace reports anything, least of all 0
        assert metrics["mesh_sync_ms"]["value"] > 0
        assert metrics["window_compile_s"]["value"] == 0
        assert metrics["h2d_bytes_in_window"]["value"] == 0
        assert not {"join_x4_device_ms", "join_x4_roofline",
                    "shard_busy_skew_pct", "collective_ms"} & set(metrics)
    else:
        assert set(result["metrics"]) == {"queries_per_s", "setup_s"}


def test_a_fallback_from_the_spmd_lane_is_not_correct():
    """`distribution.spmd.enabled` = false: the answers are right, from
    born-sharded indexes, by the one-chip join."""
    result = rehearse("spmd_off")
    compared = result["compared"]
    assert compared["mismatched_rows"][0] == 0
    assert compared["unsharded_indexes"][0] == 0
    assert compared["off_lane_queries"][0] > 0
    assert compared["idle_shards"][0] > 0
    assert result["correct"] is False


def test_a_counted_fallback_is_not_correct():
    """62 buckets do not divide over 4 devices: the join declines the
    lane with a mesh in hand, which the program counts
    (`spmd.fallbacks`) and each query's metrics say."""
    result = rehearse("buckets_odd")
    compared = result["compared"]
    assert compared["mismatched_rows"][0] == 0
    # a fallback event in every query, and the counter's gain on top
    assert compared["spmd_fallbacks"][0] == \
        2 * compared["answers_compared"][0]
    assert result["correct"] is False


def test_an_altered_answer_on_the_mesh_is_not_correct():
    result = rehearse("altered")
    compared = result["compared"]
    assert compared["wrong_answers"][0] > 0
    assert compared["mismatched_rows"][0] > 0
    assert compared["spmd_fallbacks"][0] == 0
    assert result["correct"] is False
