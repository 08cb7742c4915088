"""Whole-stage fusion (engine/fusion.py): masked-semantics equality
against eager per-operator execution, executable reuse across plan
rebuilds, and fallback behavior."""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.engine.session import HyperspaceSession
from hyperspace_tpu.plan.expr import col, lit


@pytest.fixture
def env(tmp_path):
    """Two tables: a fact (device lane forced) and a small dimension with
    nulls, strings, and a key the fact sometimes misses."""
    rng = np.random.default_rng(3)
    n = 5000
    fact_dir = tmp_path / "fact"
    dim_dir = tmp_path / "dim"
    fact_dir.mkdir()
    dim_dir.mkdir()
    fact_key = rng.integers(0, 60, n).astype(np.int64)  # dim has 0..49
    pq.write_table(pa.table({
        "k": fact_key,
        "v": rng.random(n),
        "grp": pa.array([f"g{int(x)}" for x in rng.integers(0, 7, n)]),
    }), str(fact_dir / "part-0.parquet"))
    dim_name = pa.array(
        [None if i % 13 == 0 else f"name_{i}" for i in range(50)])
    pq.write_table(pa.table({
        "k": np.arange(50, dtype=np.int64),
        "name": dim_name,
        "w": np.arange(50, dtype=np.int64) * 10,
    }), str(dim_dir / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh"),
                "spark.hyperspace.execution.min.device.rows": "0",
                "spark.hyperspace.distribution.enabled": "false"}
        conf.update(extra)
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(fact_dir), str(dim_dir)


def run_query(sess, fact, dim, how):
    fdf = sess.read_parquet(fact)
    ddf = sess.read_parquet(dim)
    q = (fdf.filter(col("k") > lit(5))
         .join(ddf.filter(col("w") < lit(400)), on=col("k") == col("k"),
               how=how))
    if how in ("left_semi", "left_anti"):
        q = q.select("k", "v")
    else:
        q = q.select("k", "v", "name", "w")
    return q.to_pandas()


def norm(df):
    return (df.sort_values(list(df.columns)).reset_index(drop=True)
            .astype({c: "float64" for c in df.columns
                     if df[c].dtype.kind in "fi"}))


@pytest.mark.parametrize("how", ["inner", "left_outer", "left_semi",
                                 "left_anti"])
def test_fused_broadcast_join_matches_eager(env, how):
    session, fact, dim = env
    fused = run_query(session(), fact, dim, how)
    eager = run_query(
        session(**{"spark.hyperspace.execution.fusion.enabled": "false"}),
        fact, dim, how)
    pd.testing.assert_frame_equal(norm(fused), norm(eager),
                                  check_dtype=False)
    assert len(fused) > 0


def test_fused_plan_shows_stage_and_reuses_executable(env):
    session, fact, dim = env
    sess = session()
    from hyperspace_tpu.engine import fusion

    def q():
        fdf = sess.read_parquet(fact)
        ddf = sess.read_parquet(dim)
        return (fdf.filter(col("k") > lit(5))
                .join(ddf, on=col("k") == col("k"))
                .select("v", "name"))

    from hyperspace_tpu.engine.executor import compile_plan
    df = q()
    phys = compile_plan(df._optimized_plan(), conf=sess.conf)
    text = phys.tree_string()
    assert "FusedStage" in text and "BroadcastHashJoin" in text
    # explain stays at the operator level (display contract).
    assert "FusedStage" not in q().explain_plans()[2].tree_string()

    q().to_pandas()  # traces + compiles the stage
    assert fusion._run_stage_jit is not None
    size_before = fusion._run_stage_jit._cache_size()
    # A REBUILT plan (fresh physical nodes) must hit the same executable:
    # the program key, not object identity, is the cache key.
    q().to_pandas()
    assert fusion._run_stage_jit._cache_size() == size_before


def test_fused_expression_projection_and_case(env):
    """Computed projections + CASE + IN + LIKE through the fused lane."""
    session, fact, dim = env
    from hyperspace_tpu.plan.expr import CaseWhen

    def build(sess):
        fdf = sess.read_parquet(fact)
        q = (fdf.filter(col("grp").like("g%")
                        & col("k").isin(*range(4, 40)))
             .with_column("bonus", CaseWhen(
                 [(col("k") > lit(30), col("v") * lit(2.0))],
                 col("v")))
             .select("k", "bonus"))
        return q.to_pandas()

    fused = build(session())
    eager = build(session(
        **{"spark.hyperspace.execution.fusion.enabled": "false"}))
    pd.testing.assert_frame_equal(norm(fused), norm(eager),
                                  check_dtype=False)
    assert len(fused) > 0


def test_host_lane_matches_eager(env):
    """With the default device threshold the sources stay host-side;
    host-lane stages route to the eager operator graph (early
    compaction beats masked full-length evaluation on numpy) and must
    agree with fusion disabled."""
    session, fact, dim = env
    fused = run_query(
        session(**{"spark.hyperspace.execution.min.device.rows":
                   str(1 << 30)}), fact, dim, "inner")
    eager = run_query(
        session(**{"spark.hyperspace.execution.min.device.rows":
                   str(1 << 30),
                   "spark.hyperspace.execution.fusion.enabled": "false"}),
        fact, dim, "inner")
    pd.testing.assert_frame_equal(norm(fused), norm(eager),
                                  check_dtype=False)


def test_fusion_falls_back_on_string_join_keys(tmp_path):
    """String join keys are ineligible for the direct-address table; the
    fused stage must fall back to the eager graph and still be right."""
    rng = np.random.default_rng(5)
    n = 2000
    a_dir, b_dir = tmp_path / "a", tmp_path / "b"
    a_dir.mkdir(), b_dir.mkdir()
    pq.write_table(pa.table({
        "s": pa.array([f"k{int(x)}" for x in rng.integers(0, 30, n)]),
        "v": rng.random(n)}), str(a_dir / "p.parquet"))
    pq.write_table(pa.table({
        "s": pa.array([f"k{i}" for i in range(30)]),
        "w": np.arange(30, dtype=np.int64)}), str(b_dir / "p.parquet"))

    def run(fusion_on):
        sess = HyperspaceSession(HyperspaceConf({
            "hyperspace.warehouse.dir": str(tmp_path / "wh"),
            "spark.hyperspace.execution.min.device.rows": "0",
            "spark.hyperspace.distribution.enabled": "false",
            "spark.hyperspace.execution.fusion.enabled":
                "true" if fusion_on else "false",
            # Force the broadcast planner path despite string keys.
            "spark.hyperspace.broadcast.threshold": str(1 << 20)}))
        adf = sess.read_parquet(str(a_dir))
        bdf = sess.read_parquet(str(b_dir))
        return (adf.join(bdf, on=col("s") == col("s"))
                .select("v", "w").to_pandas())

    pd.testing.assert_frame_equal(norm(run(True)), norm(run(False)),
                                  check_dtype=False)


def test_build_columns_defer_to_post_compaction(env):
    """Carried build-side columns must reach the runtime DEFERRED (only
    their join's hit/matched pair crosses the executable) and still
    decode to the exact eager values — including strings with nulls."""
    from hyperspace_tpu.engine import fusion

    session, fact, dim = env
    sess = session()
    # Metadata and executables retire together, as in `_execute_device`:
    # a kept executable without its metadata runs eager for the rest of
    # the process, in whatever test file this worker is handed next.
    fusion._OUT_META.clear()
    if fusion._run_stage_jit is not None:
        fusion._run_stage_jit.clear_cache()
    out = run_query(sess, fact, dim, "left_outer")
    # name/w are carried (never filtered on) -> recorded as lazy specs.
    lazy_names = {spec[0]
                  for meta in fusion._OUT_META.values()
                  for spec in meta[3]}
    assert {"name", "w"} <= lazy_names, lazy_names
    sess2 = session(**{"spark.hyperspace.execution.fusion.enabled":
                       "false"})
    want = run_query(sess2, fact, dim, "left_outer")
    import pandas as pd
    pd.testing.assert_frame_equal(norm(out), norm(want),
                                  check_dtype=False)
