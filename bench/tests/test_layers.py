"""lib/layers.py and the readers over it, on a hand-made reduced trace:
which programs count for which operator, the idle share of one kind of
span, and a build rate that leaves out the query after each build."""

import pytest

from conftest import plug
from lib import layers


def trace():
    # window 0..10; two whole queries (1..4, 5..8) and one cut off (9..11)
    spans = [("bench.window", -1, 0.0, 10.0),
             ("bench.collect", 0, 1.0, 3.0), ("bench.collect", 1, 5.0, 3.0),
             ("bench.collect", 2, 9.0, 2.0),
             ("bench.build", 0, 0.0, 1.0), ("bench.build", 1, 4.0, 1.0)]
    programs = [("jit__counting_match_lanes", 1.1, 0.5),
                ("jit_scatter-add", 1.7, 1.0), ("jit_renamed", 2.8, 0.1),
                ("jit__counting_match_lanes", 5.1, 0.5),
                ("jit_scatter-add", 5.7, 1.0), ("jit_renamed", 6.8, 0.1),
                ("jit_outside_any_query", 4.2, 0.6),
                ("jit_scatter-add", 9.1, 1.0)]
    busy = [(s, s + d) for _, s, d in programs]
    return {"window": (0.0, 10.0), "chips": 1,
            "busy_s": sum(e - s for s, e in busy if e <= 10),
            "busy": {"/device:TPU:0": busy}, "ops": {}, "programs": programs,
            "spans": spans}


def run(programs=None):
    return {"trace": trace(),
            "traffic": {"programs": programs} if programs else {}}


def test_every_program_inside_a_whole_query_counts_without_a_pattern():
    assert layers.device_seconds_per_query(run()) == pytest.approx(1.6)
    assert layers.device_seconds_per_query(run(), "stage") == \
        pytest.approx(1.6)


def test_a_named_operator_takes_its_programs_and_the_rest_stay_in_sight():
    r = run({"join": "_counting_match_lanes"})
    assert layers.device_seconds_per_query(r, "join") == pytest.approx(0.5)
    # the stage has no pattern: all that the join's does not claim, the
    # renamed program with it
    assert layers.device_seconds_per_query(r, "stage") == pytest.approx(1.1)
    assert layers.device_seconds_per_query(r) == pytest.approx(1.6)
    assert layers.device_seconds_per_query(
        run({"join": "no_such_program"}), "join") is None


def test_idle_share_of_the_window_and_of_one_kind_of_span():
    r = run()
    assert layers.idle_pct(r) == pytest.approx(100 * (1 - 3.8 / 10))
    # the two build spans (0..1, 4..5) hold 0.6 s of device work
    assert layers.idle_pct(r, ("bench.build",)) == pytest.approx(70.0)
    assert layers.idle_pct(r, ("bench.drop",)) is None
    assert layers.idle_pct({"trace": None}) is None


def test_build_rate_counts_build_time_only():
    reader = plug("metrics", "build_rows_per_s")
    records = [{"start": 0.0, "built": 3.0, "end": 4.0, "rows_indexed": 600},
               {"start": 4.0, "built": 7.0, "end": 8.0, "rows_indexed": 600}]
    assert reader.compute({"records": records}) == pytest.approx(200.0)
    assert reader.compute({"records": []}) is None
