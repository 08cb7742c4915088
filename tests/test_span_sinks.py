"""The span seam's two sinks at once (the ring's Chrome export beside
a profiler session's annotations), and `telemetry.link_transfer`: one
span, one accounting record."""

import json

from hyperspace_tpu import IndexConfig, telemetry
from hyperspace_tpu.telemetry import profiler

from span_seam_helpers import (QUERY_PATH, env, hs_events,  # noqa: F401
                               range_query)


def test_both_sinks_at_once_and_the_ring_still_exports(env):
    hs, fact, _dim, tmp = env
    hs.create_index(fact, IndexConfig("ss_ring", ["key"], ["qty", "price"]))
    telemetry.enable_tracing()
    try:
        with profiler.device_trace(str(tmp / "cap")):
            _t, metrics = range_query(fact).collect(with_metrics=True)
        info = telemetry.export_trace(str(tmp / "ring.json"))
    finally:
        telemetry.disable_tracing()
    with open(info["path"]) as f:
        ring = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"
                and (e.get("args") or {}).get("qid") == metrics.query_id]
    captured = hs_events(tmp / "cap")
    # the same spans, by name and count, in both sinks
    count = lambda names: {n: names.count(n) for n in set(names)}  # noqa
    assert count([e["name"] for e in ring]) == \
        count([e["name"] for e in captured])
    assert QUERY_PATH <= {e["name"] for e in ring}
    cats = {e["name"]: e["cat"] for e in ring}
    assert cats["hs.query"] == "query" and cats["hs.op.Scan"] == "operator"
    assert cats["hs.link.d2h"] == "link"
    assert cats["hs.stage.sync"] == "fusion"


def test_link_transfer_accounts_once_and_names_its_span():
    reg = telemetry.get_registry()
    before = {k: reg.counter(f"link.d2h.{k}").value
              for k in ("bytes", "transfers", "chunks")}
    tracer = telemetry.enable_tracing()
    try:
        with telemetry.link_transfer("d2h", 4096) as link:
            link.chunks = 3
        (event,) = [e for e in tracer.events if e["cat"] == "link"]
    finally:
        telemetry.disable_tracing()
    assert event["name"] == "hs.link.d2h"
    assert event["args"]["bytes"] == 4096 and event["args"]["chunks"] == 3
    after = {k: reg.counter(f"link.d2h.{k}").value for k in before}
    assert after["bytes"] - before["bytes"] == 4096
    assert after["transfers"] - before["transfers"] == 1
    assert after["chunks"] - before["chunks"] == 3
