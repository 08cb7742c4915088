"""lib/program_spans.py: the protobuf reader against jax's own, the idle
groups against `host_gap_ms` on a trace recorded on the chip
(`data/program_spans.xplane.pb`: three small range queries and one small
`create_index` through the program on a TPU v5 lite, under the bench's
`bench.window` / `bench.collect` / `bench.build` spans, host tracer level
1), scopes against program names, and None, never 0, where there is
nothing to read."""

import os
import shutil
import statistics

import pytest

from conftest import plug
from lib import program_spans as ps
from lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "program_spans.xplane.pb")
PARENT_FIXTURE = os.path.join(DATA, "tiny.xplane.pb")  # no `hs.*` in it
CELL = "fixture_cell"


def lay_out(root, fixture=FIXTURE, seed=1):
    """The fixture where a traced run of CELL would have left it."""
    d = os.path.join(str(root), ".bench_work", CELL, f"seed{seed}", "trace",
                     "plugins", "profile", "2026_10_01")
    os.makedirs(d)
    shutil.copy(fixture, os.path.join(d, "host.xplane.pb"))


@pytest.fixture
def run(tmp_path, monkeypatch):
    lay_out(tmp_path)
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    return {"cell": {"name": CELL}, "trace": tr.reduce(FIXTURE),
            "traffic": {}}


# -- the wire format -----------------------------------------------------


def test_varint_and_fields_by_hand():
    assert ps._varint(bytes([0x05]), 0) == (5, 1)
    assert ps._varint(bytes([0xAC, 0x02]), 0) == (300, 2)
    message = bytes([
        0x08, 0xAC, 0x02,              # 1: varint 300
        0x12, 0x03, 0x61, 0x62, 0x63,  # 2: bytes "abc"
        0x19, 0, 0, 0, 0, 0, 0, 0, 0,  # 3: fixed64, skipped
        0x25, 0, 0, 0, 0,              # 4: fixed32, skipped
        0x28, 0x01])                   # 5: varint 1
    assert [(n, v if isinstance(v, int) else bytes(v))
            for n, v in ps._fields(memoryview(message))] == [
                (1, 300), (2, b"abc"), (5, 1)]
    with pytest.raises(ValueError):
        list(ps._fields(bytes([0x0B])))  # a start-group: not in xplane


def test_scopes_of():
    assert ps.scopes_of("jit(hs_compact)/hs.compact/scatter-add:") == \
        ("hs.compact",)
    assert ps.scopes_of("jit(_run)/jit(main)/hs.predicate/ge:") == \
        ("hs.predicate",)
    assert ps.scopes_of("scatter-add:") == ()  # an eager dispatch
    assert ps.scopes_of("") == ()


def test_device_ops_agree_with_profile_data():
    """Same ops, same order, same clock as jax's own reader (which has
    the name, start and duration of an op, but not its scope)."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(FIXTURE)
    theirs = sorted(
        (e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
        for plane in data.planes if tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines if line.name == tr.OPS_LINE
        for e in line.events)
    ours = ps.read_device_ops(FIXTURE)
    assert len(ours) == len(theirs) > 100
    for (s, d, _scopes, name), (ts, td, tname) in zip(ours, theirs):
        assert name == tname
        assert abs(s - ts) < 2e-9 and abs(d - td) < 2e-9
    assert {sc for _, _, scopes, _ in ours for sc in scopes} >= {"hs.compact"}


# -- spans ---------------------------------------------------------------


def test_one_query_identifier_per_collect(run):
    found = ps.load(run)
    queries = [(s, s + d) for n, _, s, d in run["trace"]["spans"]
               if n == ps.QUERY]
    assert len(queries) == 3
    for lo, hi in queries:
        inside = [sp for sp in found["spans"]
                  if lo <= sp[2] and sp[2] + sp[3] <= hi]
        assert {sp[0] for sp in inside} >= {
            "hs.serve.admit", "hs.query", "hs.plan.optimize",
            "hs.plan.compile", "hs.op.Scan", "hs.stage.dispatch",
            "hs.stage.sync", "hs.stage.compact", "hs.serve.finish",
            "hs.to_arrow", "hs.link.d2h"}
        assert len({sp[4].get("qid") for sp in inside}) == 1
    assert ps.load(run) is found  # parsed once per run


def test_span_metrics_are_medians_over_whole_queries(run):
    found = ps.load(run)
    per_query = []
    for lo, hi in ps._whole(run, ps.QUERY):
        per_query.append(sum(d for n, _, s, d, _ in found["spans"]
                             if n == "hs.to_arrow" and lo <= s < hi))
    assert plug("metrics", "to_arrow_ms").compute(run) == \
        pytest.approx(1e3 * statistics.median(per_query))
    for name in ("serve_admit_ms", "query_epilogue_ms", "optimize_ms"):
        assert plug("metrics", name).compute(run) > 0
    # the build's three phases lie inside the bench's build span and
    # cover most of it
    build = [d for n, _, _, d in run["trace"]["spans"] if n == ps.BUILD]
    phases = sum(plug("metrics", f"build_{p}_ms").compute(run)
                 for p in ("read", "sort", "write"))
    assert 0.5 * 1e3 * build[0] < phases <= 1e3 * build[0]


# -- idle groups ---------------------------------------------------------


def test_idle_groups_sum_to_the_host_gap(run):
    """Per query to a microsecond, and as reported: the five metrics sum
    to `host_gap_ms`."""
    found, trace = ps.load(run), run["trace"]
    busy = tr.busy_all_chips(trace)
    for lo, hi in ps._whole(run, ps.QUERY):
        groups = ps.idle_by_group(busy, found["spans"], lo, hi)
        gap = (hi - lo) - tr.busy_within(busy, lo, hi)
        assert sum(groups.values()) == pytest.approx(gap, abs=1e-6)
        assert groups["stage"] > 0
    reported = {g: plug("metrics", f"idle_{g}_ms").compute(run)
                for g in ps.IDLE_GROUPS}
    assert sum(reported.values()) == pytest.approx(
        plug("metrics", "host_gap_ms").compute(run), abs=1e-3)
    assert all(v >= 0 for v in reported.values())


def test_a_gap_under_nested_spans_goes_to_the_inner():
    spans = [("hs.query", 1, 0.0, 10.0, {}),
             ("hs.op.Filter", 1, 1.0, 8.0, {}),
             ("hs.stage.sync", 1, 2.0, 1.0, {}),
             ("hs.op.Scan", 2, 1.5, 3.0, {}),      # a pool thread's
             ("hs.serve.finish", 1, 10.0, 1.0, {}),
             ("hs.to_arrow", 1, 11.0, 1.0, {}),
             ("hs.link.d2h", 1, 11.2, 0.5, {})]
    busy = [(3.0, 9.5), (11.3, 11.6)]
    got = ps.idle_by_group(busy, spans, -1.0, 12.5)
    # [-1, 3) is split at 0, 1, 1.5 and 2: nothing, the query alone
    # (both unnamed), the filter, the pool's scan (shorter than the
    # filter), the sync. [9.5, 11.3): the query alone, the epilogue,
    # to_arrow, its fetch. [11.6, 12.5): the fetch, to_arrow, nothing.
    assert got == pytest.approx({"serve": 1.0, "plan": 0.0, "stage": 2.0,
                                 "out": 0.7, "unnamed": 3.0})
    assert sum(got.values()) == pytest.approx(4.0 + 1.8 + 0.9)
    # the sync, not the operator round it, and not the pool's scan
    got = ps.idle_by_group([(0.0, 2.1), (2.9, 10.0)], spans, 0.0, 10.0)
    assert got["stage"] == pytest.approx(0.8)
    assert ps.group_of("hs.stage.sync") == "stage"
    got = ps.idle_by_group([(0.0, 11.25), (11.65, 12.0)], spans, 0.0, 12.0)
    assert got == pytest.approx({"serve": 0.0, "plan": 0.0, "stage": 0.0,
                                 "out": 0.4, "unnamed": 0.0})


def test_an_even_count_takes_the_mean_of_the_two_middle_queries(
        run, monkeypatch):
    trace = run["trace"]
    queries = [sp for sp in trace["spans"] if sp[0] == ps.QUERY]
    lo, hi = trace["window"]
    # cut the window to hold the first two queries only
    end = queries[1][2] + queries[1][3]
    trace["window"] = (lo, end + 1e-6)
    reported = sum(ps.idle_ms(run, g) for g in ps.IDLE_GROUPS)
    assert reported == pytest.approx(
        plug("metrics", "host_gap_ms").compute(run), abs=1e-3)


# -- scopes --------------------------------------------------------------


def test_a_renamed_program_still_counts_under_its_scope(run, monkeypatch):
    found = ps.load(run)
    per_query = plug("metrics", "compact_device_ms").compute(run)
    assert per_query > 0
    # by name: every scoped op of the fixture runs in `jit_hs_compact`
    programs = [(n, s, s + d) for n, s, d in run["trace"]["programs"]]
    for s, _d, scopes, _name in found["ops"]:
        if "hs.compact" in scopes:
            assert any(n == "jit_hs_compact" and lo <= s < hi
                       for n, lo, hi in programs)
    # ... and not BY its program's name: the same ops under another
    # program's scope path (a refactor moved the compaction into the
    # stage program) read the same
    renamed = {"spans": found["spans"], "ops": [
        (s, d, ps.scopes_of("jit(_run)/jit(main)/hs.compact/scatter-add:")
         if scopes else (), name) for s, d, scopes, name in found["ops"]]}
    monkeypatch.setattr(ps, "load", lambda run, root=None: renamed)
    assert plug("metrics", "compact_device_ms").compute(run) == \
        pytest.approx(per_query)
    # an op with no scope counts nowhere
    unscoped = {"spans": found["spans"], "ops": [
        (s, d, (), name) for s, d, _, name in found["ops"]]}
    monkeypatch.setattr(ps, "load", lambda run, root=None: unscoped)
    assert plug("metrics", "compact_device_ms").compute(run) is None


# -- nothing to read -----------------------------------------------------

NEW_METRICS = ["serve_admit_ms", "query_epilogue_ms", "optimize_ms",
               "to_arrow_ms", "compact_device_ms", "build_read_ms",
               "build_sort_ms", "build_write_ms"] + [
                   f"idle_{g}_ms" for g in ps.IDLE_GROUPS]


@pytest.mark.parametrize("case", ["untraced", "no_trace_dir", "two_traces",
                                  "parent_program"])
def test_readers_return_none_never_zero(case, tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    run = {"cell": {"name": CELL}, "traffic": {},
           "trace": tr.reduce(PARENT_FIXTURE if case == "parent_program"
                              else FIXTURE)}
    if case == "untraced":
        run["trace"] = None
    elif case == "two_traces":
        lay_out(tmp_path, seed=1)
        lay_out(tmp_path, seed=2)
    elif case == "parent_program":
        # a commit without the seam: the trace is there, the spans and
        # the scopes are not
        lay_out(tmp_path, PARENT_FIXTURE)
        assert ps.load(run) == {"spans": [], "ops": ps.load(run)["ops"]}
        assert ps.load(run)["ops"]
    for name in NEW_METRICS:
        assert plug("metrics", name).compute(run) is None, name
