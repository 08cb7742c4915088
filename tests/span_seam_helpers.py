"""What the span seam's tests share (`tests/test_span_*.py`): a small
lake with a device-lane session, and a reader of a capture's `hs.*` host
events through jax's `ProfileData`.

The tests are split over small files on purpose: pytest-xdist hands
files out largest first, and this suite has order-dependent tests (the
advisor's after the skipping sketches', for one), so new tests join the
end of the queue instead of reshuffling it."""

import glob
import os
import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            col, lit, telemetry)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def env(tmp_path):
    rng = np.random.default_rng(31)
    n, n_dim = 6000, 150
    for name, table in (
            ("fact", {"key": rng.integers(0, n_dim, n).astype(np.int64),
                      "qty": rng.integers(1, 50, n).astype(np.int64),
                      "price": rng.random(n) * 100}),
            ("dim", {"key": np.arange(n_dim, dtype=np.int64),
                     "grp": rng.integers(0, 10, n_dim).astype(np.int64)})):
        (tmp_path / name).mkdir()
        pq.write_table(pa.table(table),
                       str(tmp_path / name / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.index.num.buckets": "8",
        "spark.hyperspace.execution.min.device.rows": "0",
        "spark.hyperspace.broadcast.threshold": "0",
        "spark.hyperspace.distribution.enabled": "false"}))
    hs = Hyperspace(sess)
    fact = sess.read_parquet(str(tmp_path / "fact"))
    dim = sess.read_parquet(str(tmp_path / "dim"))
    sess.enable_hyperspace()
    return hs, fact, dim, tmp_path


def range_query(fact):
    return fact.filter((col("key") >= lit(10)) & (col("key") < lit(20))
                       ).select("key", "qty", "price")


def hs_events(trace_dir):
    """Every `hs.*` host event of the capture: dicts of name, thread
    (one per host line), start, end (ns) and the annotation's stats."""
    import jax.profiler

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out, thread = [], 0
    for plane in data.planes:
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith("hs."):
                    out.append({"name": e.name, "thread": thread,
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "stats": dict(e.stats)})
    return sorted(out, key=lambda e: e["start"])


def matches_table(name: str) -> bool:
    for pattern in telemetry.SPAN_NAMES:
        rx = re.escape(pattern)
        rx = re.sub(r"<\w+>", r"[A-Za-z_][\\w.]*", rx.replace("\\<", "<")
                    .replace("\\>", ">"))
        if re.fullmatch(rx, name):
            return True
    return False


QUERY_PATH = {
    "hs.serve.admit", "hs.query", "hs.plan.optimize", "hs.serve.credit",
    "hs.plan.compile", "hs.op.FusedStage", "hs.op.Scan",
    "hs.segcache.fill", "hs.link.h2d", "hs.stage.dispatch",
    "hs.stage.sync", "hs.stage.compact", "hs.serve.finish", "hs.to_arrow",
    "hs.to_arrow.prefetch", "hs.link.d2h"}
