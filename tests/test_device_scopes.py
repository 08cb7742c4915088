"""Every program on a query's device path carries its device scope: each
one, lowered and compiled on the CPU, has every op of its compiled HLO
under `jit(<function>)/<scope>/` in the `op_name` metadata a device
capture reads. The aggregate's reductions after its grouping sort are
one such program (`ops/aggregate._group_finish`), bit for bit the host
lane's answer."""

import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import telemetry


def _assert_scoped(program, scope, *args, **static):
    """Every op of the compiled program lies under `scope`, and the
    program keeps its function's name. Returns the ops' names."""
    name = program.__wrapped__.__name__
    hlo = program.lower(*args, **static).compile().as_text()
    assert f"jit_{name}" in hlo.splitlines()[0]
    ops = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
           if n.startswith("jit(")]
    assert ops, hlo[:2000]
    bare = [n for n in ops if not n.startswith(f"jit({name})/{scope}/")]
    assert not bare, bare
    assert scope in telemetry.DEVICE_SCOPES
    return ops


def _lanes(rng, n, width):
    import jax.numpy as jnp
    return tuple(jnp.asarray(rng.integers(0, 50, n).astype(np.int32))
                 for _ in range(width))


def test_fused_take_is_scoped():
    import jax.numpy as jnp

    from hyperspace_tpu.io import columnar

    arrays = (jnp.arange(100, dtype=jnp.int64), jnp.arange(100) % 3 == 0)
    idx = jnp.asarray([5, 1, 99], dtype=jnp.int32)
    got = columnar._fused_take(arrays, idx)
    assert np.asarray(got[0]).tolist() == [5, 1, 99]
    _assert_scoped(columnar._fused_take_jit, "hs.gather", arrays, idx)


def test_topk_threshold_is_scoped():
    import jax.numpy as jnp

    from hyperspace_tpu.ops import sort

    prefix = jnp.asarray(np.random.default_rng(1).integers(0, 1000, 500)
                         .astype(np.uint32))
    mask, count = sort._topk_threshold(prefix, 7)
    assert int(count) >= 7
    _assert_scoped(sort._topk_threshold_jit, "hs.topk", prefix, k=7)


@pytest.mark.parametrize("program", ["_counting_match_lanes",
                                     "_counting_match_lanes_hashed",
                                     "_counting_match", "_counting_expand"])
def test_the_counting_join_programs_are_scoped(program, monkeypatch):
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact, join

    rng = np.random.default_rng(2)
    if program == "_counting_match":
        args = (jnp.asarray(rng.integers(0, 40, 300).astype(np.int32)),
                jnp.asarray(rng.integers(0, 40, 200).astype(np.int32)))
        scope = "hs.join.match"
    elif program == "_counting_expand":
        # few pairs among many rows; each select, forced, traces afresh
        match = join._counting_match(
            jnp.asarray(rng.integers(0, 400, 300).astype(np.int32)),
            jnp.asarray(rng.integers(0, 4000, 200).astype(np.int32)),
            False)
        total = int(jnp.sum(match[0]))
        assert 0 < total < 300
        for select, primitive in (("sort", "/sort"), ("rank", "/gather")):
            monkeypatch.setattr(compact, "_rank_select_wins",
                                lambda rows, size: select == "rank")
            join._counting_expand.clear_cache()
            ops = _assert_scoped(join._counting_expand, "hs.join.expand",
                                 *match, total=total, left_outer=False)
            # the select's own ops sit under the scope with the rest
            assert any(n.endswith(primitive) for n in ops), ops
        join._counting_expand.clear_cache()
        return
    else:
        width = 2 if program == "_counting_match_lanes" else 4
        args = (_lanes(rng, 300, width), _lanes(rng, 200, width))
        scope = "hs.join.match"
    _assert_scoped(getattr(join, program), scope, *args, left_outer=False)


def test_group_finish_is_scoped():
    import jax.numpy as jnp

    from hyperspace_tpu.ops import aggregate

    seg = jnp.asarray(np.repeat(np.arange(5), 20).astype(np.int32))
    x = jnp.arange(100, dtype=jnp.int64)
    valid = x % 7 != 0
    plan = (("count_rows", None, "int64", False),
            ("sum", "int64", "int64", False),
            ("stddev", "float64", "float64", False),
            ("count_distinct", "int64", "int64", False),
            ("min", "int64", "int64", False))
    columns = (None, (x, valid), (x.astype(jnp.float64), None), (x, None),
               (x, valid))
    keys = ((x, None),)
    aggregate._group_finish(seg, keys, columns, plan=plan, num_groups=5)
    _assert_scoped(aggregate._group_finish, "hs.aggregate", seg, keys,
                   columns, plan=plan, num_groups=5)


# -- the fused stage ------------------------------------------------------


@pytest.fixture
def star(tmp_path):
    """A fact (device lane forced) and a small dimension; the fused
    stage's broadcast join defers the dimension's columns."""
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.engine.session import HyperspaceSession

    rng = np.random.default_rng(3)
    n = 4000
    (tmp_path / "fact").mkdir()
    (tmp_path / "dim").mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 60, n).astype(np.int64),
        "v": rng.random(n)}), str(tmp_path / "fact" / "part-0.parquet"))
    pq.write_table(pa.table({
        "k": np.arange(50, dtype=np.int64),
        "w": np.arange(50, dtype=np.int64) * 10}),
        str(tmp_path / "dim" / "part-0.parquet"))

    def session(**extra):
        conf = {"hyperspace.warehouse.dir": str(tmp_path / "wh"),
                "spark.hyperspace.execution.min.device.rows": "0",
                "spark.hyperspace.distribution.enabled": "false"}
        conf.update(extra)
        return HyperspaceSession(HyperspaceConf(conf))

    return session, str(tmp_path / "fact"), str(tmp_path / "dim")


def _star_query(sess, fact, dim, on_build_column):
    from hyperspace_tpu.plan.expr import col, lit

    q = sess.read_parquet(fact).filter(col("k") > lit(5)).join(
        sess.read_parquet(dim), on=col("k") == col("k"), how="inner")
    if on_build_column:
        # the predicate reads a deferred build-side column
        q = q.filter(col("w") > lit(120))
    return (q.select("k", "v", "w").to_pandas()
            .sort_values(["k", "v"]).reset_index(drop=True))


@pytest.mark.parametrize("on_build_column", [False, True])
def test_the_fused_stage_programs_are_scoped(star, monkeypatch,
                                             on_build_column):
    """`jit__run` under `hs.stage`, its predicate under `hs.predicate`
    inside it, the deferred gathers (`jit_run`) under `hs.stage`; the
    answer is the eager operators' (a predicate over a deferred column
    gathers it in the stage's own trace)."""
    from hyperspace_tpu.engine import fusion

    session, fact, dim = star
    seen = {}
    run_stage, finalize = fusion._run_stage, fusion._finalize_lazy

    def record_stage(prog, trees, table_args):
        seen["stage"] = (prog, trees, table_args)
        return run_stage(prog, trees, table_args)

    def record_finalize(idx, lazy_pairs, srcs, spec):
        seen["finalize"] = (idx, lazy_pairs, srcs, spec)
        return finalize(idx, lazy_pairs, srcs, spec)

    monkeypatch.setattr(fusion, "_run_stage", record_stage)
    monkeypatch.setattr(fusion, "_finalize_lazy", record_finalize)
    fused = _star_query(session(), fact, dim, on_build_column)
    eager = _star_query(
        session(**{"spark.hyperspace.execution.fusion.enabled": "false"}),
        fact, dim, on_build_column)
    pd.testing.assert_frame_equal(fused, eager, check_dtype=False)
    assert len(fused) > 0

    prog, trees, table_args = seen["stage"]
    _assert_scoped(fusion._run_stage_jit, "hs.stage", prog, trees,
                   table_args)
    hlo = fusion._run_stage_jit.lower(prog, trees,
                                      table_args).compile().as_text()
    assert "/hs.stage/jit(_run)/hs.predicate/" in hlo
    if on_build_column:
        # `w` was gathered in the stage: no build column is left deferred
        assert "finalize" not in seen
        return
    import jax.numpy as jnp
    idx, lazy_pairs, srcs, spec = seen["finalize"]
    _assert_scoped(fusion._finalize_lazy_jit, "hs.stage",
                   idx if idx is not None else jnp.zeros(0, jnp.int32),
                   lazy_pairs, srcs, spec=spec, has_idx=idx is not None)


# -- _group_finish against the host lane ----------------------------------


def _batches(dtype, nulls, one_center=False):
    """The same rows as a host batch and as a device batch (float64
    carried as its bits, as a scan places it): 7 groups of 200 pairs
    c ± d, c an integer (one for every group with `one_center`) and d a
    multiple of 1/8 (int64: the same values times 8), shuffled; nulls
    take a pair at a time, and all of the last group where there are
    nulls. So every sum, mean, deviation and sum of squared deviations
    is exact in float64: the host lane sums pairwise and the device in
    row order, and bit for bit they can only agree where neither
    rounds before the one division (and square root) both make."""
    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import ColumnBatch, DeviceColumn, carried
    from hyperspace_tpu.plan.schema import Field, Schema

    rng = np.random.default_rng(11)
    g, x, validity = [], [], []
    for group in range(7):
        c = 100 if one_center else int(rng.integers(-1000, 1000))
        d = rng.integers(0, 8000, 200) / 8.0
        ok = rng.random(200) > 0.2 if nulls else np.ones(200, bool)
        if nulls and group == 6:
            ok[:] = False
        g.append(np.full(400, group, dtype=np.int64))
        x.append(np.concatenate([c + d, c - d]))
        validity.append(np.concatenate([ok, ok]))
    order = rng.permutation(7 * 400)
    g = np.concatenate(g)[order]
    x = np.concatenate(x)[order]
    if dtype == "int64":
        x = (x * 8).astype(np.int64)
    validity = np.concatenate(validity)[order] if nulls else None
    schema = Schema([Field("g", "int64", False), Field("x", dtype, True)])

    def batch(device):
        if not device:
            return ColumnBatch(schema, {
                "g": DeviceColumn(g, "int64"),
                "x": DeviceColumn(x, dtype, validity=validity)})
        return ColumnBatch(schema, {
            "g": DeviceColumn(jnp.asarray(g), "int64"),
            "x": DeviceColumn(jnp.asarray(carried(x, dtype)), dtype,
                              validity=None if validity is None
                              else jnp.asarray(validity))})

    return schema, batch(False), batch(True)


def _values(col):
    """(value bits, validity) of an output column, fetched."""
    from hyperspace_tpu.io.columnar import fetched

    data = fetched(np.asarray(col.raw), col.dtype)
    valid = (np.ones(len(data), bool) if col.validity is None
             else np.asarray(col.validity))
    bits = data.view(np.int64) if data.dtype == np.float64 else data
    return np.where(valid, bits, 0), valid


@pytest.mark.parametrize("dtype", ["int64", "float64"])
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("func", ["count", "count_distinct", "sum", "avg",
                                  "stddev", "min", "max"])
def test_group_finish_is_the_host_lane_bit_for_bit(func, nulls, dtype):
    from hyperspace_tpu.ops.aggregate import (_host_group_aggregate,
                                              group_aggregate)
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan

    schema, host, device = _batches(dtype, nulls)
    specs = [AggSpec(func, "x", "out"), AggSpec("count", "*", "rows")]
    out_schema = Aggregate(["g"], specs, Scan(["/nx"], schema)).schema
    want = _host_group_aggregate(host, ["g"], specs, out_schema)
    got = group_aggregate(device, ["g"], specs, out_schema)
    assert not got.is_host
    for name in ("g", "out", "rows"):
        w, g = want.column(name), got.column(name)
        assert g.dtype == w.dtype, name
        w_bits, w_valid = _values(w)
        g_bits, g_valid = _values(g)
        assert np.array_equal(g_valid, w_valid), name
        assert np.array_equal(g_bits, w_bits), name
    if nulls and func not in ("count", "count_distinct"):
        assert not _values(got.column("out"))[1][-1]  # the all-null group


@pytest.mark.parametrize("dtype", ["int64", "float64"])
def test_a_global_aggregate_finishes_in_one_program(dtype):
    """No group columns: one group over every row, the same answer as
    the host lane, in ONE `_group_finish` dispatch after the exact
    moments."""
    from hyperspace_tpu.ops import aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan

    schema, host, device = _batches(dtype, True, one_center=True)
    specs = [AggSpec(f, "x", f) for f in ("sum", "avg", "stddev", "min",
                                          "max", "count_distinct")]
    specs.append(AggSpec("count", "*", "rows"))
    out_schema = Aggregate([], specs, Scan(["/nx"], schema)).schema
    want = aggregate._host_group_aggregate(host, [], specs, out_schema)
    reg = telemetry.get_registry()
    before = reg.counter("compile.aggregate.group_finish.traces").value + \
        reg.counter("compile.cache_hits").value
    got = aggregate.group_aggregate(device, [], specs, out_schema)
    assert reg.counter("compile.aggregate.group_finish.traces").value + \
        reg.counter("compile.cache_hits").value > before
    for spec in specs:
        w_bits, w_valid = _values(want.column(spec.alias))
        g_bits, g_valid = _values(got.column(spec.alias))
        assert np.array_equal(g_valid, w_valid), spec.alias
        assert np.array_equal(g_bits, w_bits), spec.alias
