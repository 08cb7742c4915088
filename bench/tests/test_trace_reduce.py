"""trace_reduce.py: interval arithmetic by hand, and the reduction of a
small trace recorded on the chip (`data/tiny.xplane.pb`: two `bench.collect`
spans inside one `bench.window`, each running a sort program and a sum
program on a TPU v5 lite) against numbers worked out another way."""

import os

import pytest

from lib import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny.xplane.pb")


def test_merge_clip_total():
    merged = tr.merge([(5, 7), (1, 3), (2, 4), (7, 8), (10, 11)])
    assert merged == [(1, 4), (5, 8), (10, 11)]
    assert tr.total(merged) == 7
    assert tr.clip(merged, 2, 10.5) == [(2, 4), (5, 8), (10, 10.5)]
    assert tr.busy_within(merged, 3, 6) == 2


def test_program_name():
    assert tr.program_name("jit__perm_core(1234567890)") == "jit__perm_core"
    assert tr.program_name("jit_run") == "jit_run"


def synthetic():
    return {
        "window": (0.0, 10.0), "chips": 1, "busy_s": 4.0,
        "busy": {"/device:TPU:0": [(1.0, 2.0), (4.0, 6.0), (8.0, 9.0)]},
        "ops": {"fusion.1": 3.0, "sort.2": 1.0},
        "programs": [("jit_a", 1.0, 1.0), ("jit_b", 4.0, 2.0),
                     ("jit_a", 8.0, 1.0)],
        "spans": [("bench.window", -1, 0.0, 10.0),
                  ("bench.op", 0, 0.0, 7.0), ("bench.plan", 0, 0.0, 0.9),
                  ("bench.collect", 0, 0.9, 6.1),
                  ("bench.op", 1, 7.0, 2.5), ("bench.collect", 1, 7.5, 2.0)],
    }


def test_idle_gaps_go_to_the_innermost_span():
    gaps = dict(tr.idle_gaps(synthetic()))
    # a gap is cut where a span starts or ends inside it. 0-1: plan to
    # 0.9, then collect#0; 2-4: collect#0; 6-8: collect#0 to 7.0, op#1
    # to 7.5, collect#1 from there; 9-10: collect#1 to 9.5 (where op#1
    # ends too), then outside
    assert gaps["plan"] == pytest.approx(0.9)
    assert gaps["collect"] == pytest.approx(0.1 + 2.0 + 1.0 + 0.5 + 0.5)
    assert gaps["op"] == pytest.approx(0.5)
    assert gaps["outside"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(10.0 - 4.0)


def test_program_seconds_and_top_ops():
    s = synthetic()
    assert tr.program_seconds(s, r"jit_a") == pytest.approx(2.0)
    assert tr.program_seconds(s, r"jit_a|jit_b", 0.0, 7.0) == \
        pytest.approx(3.0)
    assert tr.top_ops(s, 1) == [["fusion.1", 3.0]]


@pytest.fixture(scope="module")
def reduced():
    if not os.path.isfile(FIXTURE):
        pytest.skip("no recorded trace beside the test")
    return tr.reduce(FIXTURE)


def sweep_union(intervals):
    """Union length by an endpoint sweep: not trace_reduce's merge."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    depth, since, covered = 0, None, 0.0
    for t, d in events:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            covered += t - since
    return covered


def test_recorded_trace_busy_union_and_per_name_sums(reduced):
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(FIXTURE)
    (plane,) = [p for p in data.planes if p.name == "/device:TPU:0"]
    ops = [e for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    lo, hi = reduced["window"]
    inside = [(max(e.start_ns * 1e-9, lo),
               min((e.start_ns + e.duration_ns) * 1e-9, hi)) for e in ops
              if (e.start_ns + e.duration_ns) * 1e-9 > lo
              and e.start_ns * 1e-9 < hi]
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(sweep_union(inside), rel=1e-9)
    assert 0 < reduced["busy_s"] < hi - lo
    by_name = {}
    for e in ops:  # an op counts whole where it touches the window
        if (e.start_ns + e.duration_ns) * 1e-9 > lo and e.start_ns * 1e-9 < hi:
            by_name[e.name] = by_name.get(e.name, 0.0) + e.duration_ns * 1e-9
    assert set(by_name) == set(reduced["ops"])
    for name, seconds in reduced["ops"].items():
        assert seconds == pytest.approx(by_name[name], rel=1e-9)
    assert sum(reduced["ops"].values()) >= reduced["busy_s"] * (1 - 1e-9)


def test_recorded_trace_spans_and_programs(reduced):
    names = [n for n, _, _, _ in reduced["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.collect") == 2
    assert sorted(op for n, op, _, _ in reduced["spans"]
                  if n == "bench.collect") == [0, 1]
    lo, hi = reduced["window"]
    # a sort and a sum in each of two spans. The device's clock leads the
    # host's by about a millisecond in this trace (the first program
    # starts 0.8 ms before the span that launched it), so programs are
    # counted over the whole trace, not by the span they fall in.
    programs = reduced["programs"]
    assert [n for n, _, _ in programs] == ["jit__lambda"] * 4
    assert tr.program_seconds(reduced, r"^jit__lambda$", 0.0, hi) == \
        pytest.approx(sum(d for _, _, d in programs))
    assert tr.program_seconds(reduced, r"^jit__lambda$") == \
        pytest.approx(sum(d for _, s, d in programs if s >= lo))
    busy = tr.busy_all_chips(reduced)
    spans = [(s, d) for n, _, s, d in reduced["spans"] if n == "bench.collect"]
    assert 0 < tr.busy_within(busy, *[spans[1][0], sum(spans[1])]) < spans[1][1]
    gaps = dict(tr.idle_gaps(reduced))
    assert gaps["collect"] > 0
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo) - reduced["busy_s"], rel=1e-6)
