"""The span seam's second sink (`telemetry/trace.py` ->
`telemetry/profiler.annotation`): under a profiler session started
through the one sanctioned seam, a filter query and a join leave their
`hs.*` spans in the session's own trace — named from `SPAN_NAMES`,
carrying the query's identifier, nested on their threads."""

from hyperspace_tpu import IndexConfig
from hyperspace_tpu.telemetry import profiler

from span_seam_helpers import (QUERY_PATH, env, hs_events,  # noqa: F401
                               matches_table, range_query)


def test_one_collect_names_its_layers_under_one_query_id(env):
    hs, fact, _dim, tmp = env
    hs.create_index(fact, IndexConfig("ss_fact", ["key"], ["qty", "price"]))
    with profiler.device_trace(str(tmp / "cap")):
        table, metrics = range_query(fact).collect(with_metrics=True)
    assert table.num_rows > 0
    events = hs_events(tmp / "cap")
    names = {e["name"] for e in events}
    assert QUERY_PATH <= names, QUERY_PATH - names
    assert all(matches_table(n) for n in names), sorted(names)
    # every span of the collect carries the query's identifier
    assert {e["stats"].get("qid") for e in events} == {metrics.query_id}
    # children lie inside hs.query on its thread, in the order of the
    # pipeline; admission before it, the epilogue and Arrow after it
    (query,) = [e for e in events if e["name"] == "hs.query"]
    inside = {"hs.plan.optimize", "hs.serve.credit", "hs.plan.compile",
              "hs.op.FusedStage", "hs.stage.dispatch", "hs.stage.sync",
              "hs.stage.compact"}
    for e in events:
        if e["name"] in inside:
            assert e["thread"] == query["thread"]
            assert query["start"] <= e["start"] and e["end"] <= query["end"]
    order = [next(e for e in events if e["name"] == n) for n in (
        "hs.serve.admit", "hs.query", "hs.serve.finish", "hs.to_arrow")]
    assert all(a["end"] <= b["start"] for a, b in zip(order, order[1:]))
    (arrow,) = [e for e in events if e["name"] == "hs.to_arrow"]
    fetches = [e for e in events if e["name"] == "hs.link.d2h"]
    assert len(fetches) == 3 and all(
        arrow["start"] <= e["start"] and e["end"] <= arrow["end"]
        and e["stats"]["bytes"] > 0 for e in fetches)
    # what a site knows rides along
    (compact,) = [e for e in events if e["name"] == "hs.stage.compact"]
    assert compact["stats"]["rows"] == table.num_rows
    (scan,) = [e for e in events if e["name"] == "hs.op.Scan"]
    assert scan["stats"]["lane"] == "device" and scan["stats"]["rows"] == 6000
    (admit,) = [e for e in events if e["name"] == "hs.serve.admit"]
    assert "queue_wait_s" in admit["stats"]


def test_pool_thread_spans_carry_the_query_id(env):
    hs, fact, dim, tmp = env
    hs.create_index(fact, IndexConfig("ss_f", ["key"], ["qty", "price"]))
    hs.create_index(dim, IndexConfig("ss_d", ["key"], ["grp"]))
    join = fact.join(dim, on="key").select("qty", "grp")
    # a first bucketed join reads its two sides on pool threads
    with profiler.device_trace(str(tmp / "cap")):
        _table, metrics = join.collect(with_metrics=True)
    events = hs_events(tmp / "cap")
    (query,) = [e for e in events if e["name"] == "hs.query"]
    elsewhere = [e for e in events if e["thread"] != query["thread"]]
    assert any(e["name"].startswith("hs.op.") for e in elsewhere)
    assert {e["stats"].get("qid") for e in events} == {metrics.query_id}
    assert any(e["name"] == "hs.op.SortMergeJoin" for e in events)
