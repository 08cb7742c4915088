"""The span seam on the build path, and off: a `create_index` under a
profiler session names its read / sort / write phases, each file on the
writer thread and the action's phases; with no session and no ring a
`collect` appends nothing anywhere and opens few spans."""

from hyperspace_tpu import IndexConfig, telemetry
from hyperspace_tpu.telemetry import profiler, trace

from span_seam_helpers import (env, hs_events, matches_table,  # noqa: F401
                               range_query)


def test_create_index_names_read_sort_write_and_the_action(env):
    hs, fact, _dim, tmp = env
    with profiler.device_trace(str(tmp / "cap")):
        hs.create_index(fact, IndexConfig("ss_b", ["key"], ["qty"]))
    events = hs_events(tmp / "cap")
    names = {e["name"] for e in events}
    assert {"hs.action.CreateAction", "hs.action.CreateAction.validate",
            "hs.action.CreateAction.begin", "hs.action.CreateAction.op",
            "hs.action.CreateAction.end", "hs.build.read", "hs.build.sort",
            "hs.build.write", "hs.build.write.file"} <= names
    assert all(matches_table(n) for n in names), sorted(names)
    by = {n: [e for e in events if e["name"] == n] for n in names}
    (op,) = by["hs.action.CreateAction.op"]
    phases = [by[n][0] for n in ("hs.build.read", "hs.build.sort",
                                 "hs.build.write")]
    assert all(op["start"] <= p["start"] and p["end"] <= op["end"]
               and p["thread"] == op["thread"] for p in phases)
    assert all(a["end"] <= b["start"] for a, b in zip(phases, phases[1:]))
    # one span per file, on the writer thread, inside the write phase
    (write,) = by["hs.build.write"]
    files = by["hs.build.write.file"]
    assert len(files) == write["stats"]["files"] == 8
    assert {f["thread"] for f in files} != {write["thread"]}
    assert all(write["start"] <= f["start"] and f["end"] <= write["end"]
               for f in files)
    assert sum(f["stats"]["rows"] for f in files) == 6000 \
        == write["stats"]["rows"]


# A warm filter collect opens this many spans at most. The seam is on the
# hot path of every query: a new span is a decision, not an accident.
MAX_SPANS_PER_COLLECT = 24


def test_off_means_nothing_is_appended_and_few_spans_open(env, monkeypatch):
    hs, fact, _dim, _tmp = env
    hs.create_index(fact, IndexConfig("ss_off", ["key"], ["qty", "price"]))
    range_query(fact).collect()  # warm: the fill and the compiles
    assert trace.tracer() is None and not profiler.annotations_enabled()
    assert not telemetry.spans_active()

    def no_sink(*a, **k):
        raise AssertionError("a sink was touched with tracing off")

    monkeypatch.setattr(profiler, "annotation", no_sink)
    monkeypatch.setattr(trace.Tracer, "complete", no_sink)
    opened = []
    enter = trace.span.__enter__

    def counting(self):
        opened.append(self.name)
        return enter(self)

    monkeypatch.setattr(trace.span, "__enter__", counting)
    table = range_query(fact).collect()
    assert table.num_rows > 0
    assert 0 < len(opened) <= MAX_SPANS_PER_COLLECT, opened
    # ... and none of them held on to anything
    sp = telemetry.span("hs.query", "query")
    with sp:
        sp.set(rows=1)
    assert sp._ring is None and sp._ann is None and "rows" not in sp.args
