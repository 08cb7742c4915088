"""Regression attribution over two `QueryMetrics` trees
(`telemetry/diff.py`: the differ an operator points at a slow-query
dump and a live re-run), and the Prometheus exposition-format
conformance of `registry.to_text()`."""

import re

import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.telemetry import diff


# ---------------------------------------------------------------------------
# The differ: telemetry-based attribution
# ---------------------------------------------------------------------------


def _tree(wall, op_walls, counters=None, events=None):
    """A minimal QueryMetrics.to_dict()-shaped tree: a linear chain of
    operators (parent -> child) with the given walls."""
    ops = []
    cum = list(op_walls)
    # wall of node i includes its children: accumulate from the leaf.
    for i, name_wall in enumerate(op_walls):
        name, self_s = name_wall
        total = sum(w for _, w in op_walls[i:])
        ops.append({"op_id": i, "parent_id": i - 1 if i else None,
                    "name": name, "label": name, "wall_s": total,
                    "rows_out": 100})
    del cum
    return {"description": "t", "wall_s": wall, "operators": ops,
            "events": events or [], "counters": counters or {},
            "index_usage": [], "peak_hbm_bytes": 0,
            "peak_hbm_per_device": {}, "compile": {}}


def test_diff_trees_attributes_compile_regression():
    """Synthetic retrace regression: same operator work, +2s of
    compile — the compile bucket must dominate and carry the cause."""
    old = _tree(1.0, [("Project", 0.2), ("Filter", 0.3), ("Scan", 0.4)],
                counters={"compile.seconds": 0.0, "plan_s": 0.05})
    new = _tree(3.1, [("Project", 0.2), ("Filter", 2.4), ("Scan", 0.4)],
                counters={"compile.seconds": 2.0, "compile.traces": 3,
                          "plan_s": 0.05},
                events=[{"category": "compile", "name": "retrace",
                         "target": "fusion.run_stage",
                         "cause": "shape/dtype: f64[4000] -> f64[8000]"}])
    qd = diff.diff_trees(old, new, name="q_retrace")
    assert qd.dominant == "compile"
    buckets = {b.name: b for b in qd.buckets}
    assert buckets["compile"].seconds == pytest.approx(2.0)
    assert buckets["compile"].detail["traces"] == 3
    assert buckets["compile"].detail["retrace_causes"][0]["cause"] \
        .startswith("shape/dtype")
    # the +2.1s of operator movement nets out the compile seconds: the
    # compute bucket holds only the genuine +0.1s
    assert buckets["compute"].seconds == pytest.approx(0.1)
    # decomposition sums exactly to the wall delta
    total = sum(b.seconds for b in qd.buckets)
    assert total == pytest.approx(qd.delta)


def test_diff_trees_attributes_link_regression():
    old = _tree(1.0, [("Join", 0.5), ("Scan", 0.4)],
                counters={"link.h2d_s": 0.1, "link.h2d_bytes": 1000})
    new = _tree(2.5, [("Join", 0.5), ("Scan", 1.9)],
                counters={"link.h2d_s": 1.6, "link.h2d_bytes": 9000})
    qd = diff.diff_trees(old, new, name="q_link")
    assert qd.dominant == "link"
    buckets = {b.name: b for b in qd.buckets}
    assert buckets["link"].seconds == pytest.approx(1.5)
    assert buckets["link"].detail["link.h2d_bytes"] == 8000


def test_diff_trees_cache_and_fallback_evidence():
    old = _tree(1.0, [("Scan", 0.9)],
                counters={"cache.parquet_read.hits": 10})
    new = _tree(1.1, [("Scan", 1.0)],
                counters={"cache.parquet_read.hits": 2,
                          "cache.parquet_read.misses": 8,
                          "resilience.fallbacks": 1},
                events=[{"category": "resilience", "name": "degraded",
                         "index": "idx", "reason": "gone"}])
    qd = diff.diff_trees(old, new, name="q_cache")
    buckets = {b.name: b for b in qd.buckets}
    assert buckets["cache"].detail["cache.parquet_read.misses"] == 8
    assert buckets["cache"].detail["cache.parquet_read.hits"] == -8
    assert buckets["fallback"].detail["fallbacks"] == 1
    # evidence buckets never claim seconds (their cost is already in
    # compute/link — no double counting)
    assert buckets["cache"].seconds == 0.0
    assert buckets["fallback"].seconds == 0.0


def test_diff_live_query_metrics_round_trip(tmp_path):
    """diff_trees accepts live QueryMetrics objects, not just dicts."""
    qm_old = telemetry.QueryMetrics("a")
    op = qm_old.start_operator("Scan")
    qm_old.finish_operator(op, rows_out=10)
    qm_old.add_seconds("plan_s", 0.01)
    qm_old.finish()
    qm_new = telemetry.QueryMetrics("a")
    op = qm_new.start_operator("Scan")
    qm_new.finish_operator(op, rows_out=10)
    qm_new.add_seconds("plan_s", 0.02)
    qm_new.finish()
    qd = diff.diff_trees(qm_old, qm_new)
    assert qd.old_wall is not None and qd.new_wall is not None
    assert {b.name for b in qd.buckets} >= {"compute", "link",
                                            "compile", "residual"}


# ---------------------------------------------------------------------------
# Prometheus exposition-format conformance (registry.to_text)
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"            # metric name
    r"(?:\{([a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\""
    r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\")*)\})?"
    r" (NaN|[+-]?(?:Inf|[0-9.eE+-]+))$")      # value


def test_prometheus_conformance():
    reg = telemetry.MetricsRegistry()
    reg.counter("fusion.stage_execs").inc(4)
    reg.counter("link.h2d.bytes").inc(1 << 20)
    reg.gauge("mesh.devices").set(8)
    reg.gauge("cache.device_batch.bytes_held").set(12345)
    h = reg.histogram("link.h2d.bytes_per_transfer")
    h.observe(100)
    h.observe(5000)
    h.observe(0)  # the "0" bucket — a label value worth escaping rules
    text = reg.to_text()
    assert text.endswith("\n")

    seen_type = {}
    seen_help = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert _NAME_RE.fullmatch(name), line
            assert name not in seen_help, f"duplicate HELP: {line}"
            seen_help.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert _NAME_RE.fullmatch(name), line
            assert kind in ("counter", "gauge", "histogram"), line
            assert name not in seen_type, f"duplicate TYPE: {line}"
            # HELP precedes TYPE for every family
            assert name in seen_help, f"TYPE before HELP: {line}"
            seen_type[name] = kind
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        base = m.group(1)
        family = re.sub(r"_(bucket|sum|count)$", "", base)
        assert family in seen_type or base in seen_type, \
            f"sample before its TYPE: {line!r}"

    # dotted names map to legal names, deterministically
    assert "# TYPE hs_fusion_stage_execs counter" in text
    assert "# HELP hs_fusion_stage_execs" in text
    assert "hyperspace metric 'fusion.stage_execs'" in text
    # histogram invariants: cumulative buckets, +Inf == count
    bucket_counts = [int(line.rsplit(" ", 1)[1])
                     for line in text.splitlines()
                     if line.startswith(
                         "hs_link_h2d_bytes_per_transfer_bucket")]
    assert bucket_counts == sorted(bucket_counts)
    assert bucket_counts[-1] == 3
    assert "hs_link_h2d_bytes_per_transfer_count 3" in text


def test_prometheus_label_escaping():
    from hyperspace_tpu.telemetry.registry import (_escape_help,
                                                   _escape_label_value)
    assert _escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert _escape_help("back\\slash\nline") == "back\\\\slash\\nline"


def test_prometheus_name_collision_disambiguated():
    reg = telemetry.MetricsRegistry()
    reg.counter("a.b").inc()
    reg.counter("a_b").inc()  # same name after sanitization
    text = reg.to_text()
    types = [line for line in text.splitlines()
             if line.startswith("# TYPE ")]
    names = [line.split()[2] for line in types]
    assert len(names) == len(set(names)), names
    assert "hs_a_b" in names and "hs_a_b_2" in names
