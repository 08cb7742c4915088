"""The direct-address group-by (`ops/aggregate.direct_aggregate`): a
grouping whose keys all have a small known domain is reduced slot by
slot in one program, with no sort, gather or scatter, and the same
answer as the sort path; a fused filter hands it its rows uncompacted
with the predicate's mask; TPC-H Q1 through a covering index on
`l_shipdate` takes it; the operator's record says which path served
each cell's aggregate."""

import datetime

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import (Hyperspace, HyperspaceConf, HyperspaceSession,
                            IndexConfig, col, lit, telemetry)
from hyperspace_tpu.io.columnar import ColumnBatch, DeviceColumn
from hyperspace_tpu.ops import aggregate as agg
from hyperspace_tpu.plan.nodes import AggSpec
from hyperspace_tpu.plan.schema import Field, Schema
from hyperspace_tpu.tpch import queries

DEVICE = {"spark.hyperspace.execution.min.device.rows": "0",
          "spark.hyperspace.distribution.enabled": "false"}


def _days(y, m, d) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


def _key(values, dictionary, valid=None) -> DeviceColumn:
    import jax.numpy as jnp

    d = len(dictionary)
    return DeviceColumn(
        jnp.asarray(np.asarray(values, dtype=np.int32)), "string",
        jnp.asarray(valid) if valid is not None else None,
        np.asarray(dictionary),
        (jnp.zeros(d, jnp.uint32), jnp.zeros(d, jnp.uint32)))


def _batch(seed: int, n: int, domains=(3, 2), nulls: bool = False):
    """Keys of the given dictionary sizes, an int64 and a float64 column
    (carried as bits, as a scan places them), a bool key."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    fields, cols = [], {}
    for i, d in enumerate(domains):
        codes = rng.integers(0, d, n)
        valid = rng.random(n) > 0.2 if nulls else None
        if nulls:  # a scan codes every null as one value
            codes = np.where(valid, codes, 0)
        cols[f"k{i}"] = _key(codes, [f"v{j:04d}" for j in range(d)], valid)
        fields.append(Field(f"k{i}", "string", nulls))
    x = rng.integers(-50, 50, n).astype(np.int64)
    y = rng.integers(0, 10_500_000, n) / 100.0
    xv = jnp.asarray(rng.random(n) > 0.3) if nulls else None
    cols["x"] = DeviceColumn(jnp.asarray(x), "int64", xv)
    cols["y"] = DeviceColumn(jnp.asarray(y.view(np.int64)), "float64")
    cols["b"] = DeviceColumn(jnp.asarray(rng.random(n) > 0.5), "bool")
    fields += [Field("x", "int64", nulls), Field("y", "float64", False),
               Field("b", "bool", False)]
    return ColumnBatch(Schema(fields), cols)


SPECS = [AggSpec("count", "*", "n"), AggSpec("count", "x", "nx"),
         AggSpec("sum", "x", "sx"), AggSpec("sum", "y", "sy"),
         AggSpec("avg", "x", "ax"), AggSpec("avg", "y", "ay"),
         AggSpec("min", "x", "mnx"), AggSpec("max", "y", "mxy"),
         AggSpec("stddev", "x", "dx"), AggSpec("stddev", "y", "dy"),
         AggSpec("count_distinct", "x", "cdx")]


def _out_schema(batch, keys, specs) -> Schema:
    from hyperspace_tpu.plan.nodes import Aggregate, Scan

    scan = Scan(["/nowhere"], batch.schema)
    return Aggregate(keys, specs, scan).schema


def _frame(out: ColumnBatch) -> dict:
    """{column: its values as a list, nulls as None, string keys by their
    dictionary value}."""
    frame = {}
    for f in out.schema.fields:
        c = out.columns[f.name]
        data = np.asarray(c.data)
        if c.is_string:
            data = np.asarray(c.dictionary)[data]
        vals = data.astype(object)
        if c.validity is not None:
            vals[~np.asarray(c.validity)] = None
        frame[f.name] = vals.tolist()
    return frame


def _both(batch, keys, specs, mask=None):
    """(direct path's answer, sort path's answer) as frames."""
    schema = _out_schema(batch, keys, specs)
    domains = agg.direct_domains(batch, keys)
    assert domains is not None
    direct = agg.direct_aggregate(batch, keys, specs, schema, domains,
                                  row_mask=mask)
    sorted_in = batch
    if mask is not None:
        sorted_in = batch.take(np.flatnonzero(np.asarray(mask)))
    sort = agg.group_aggregate(sorted_in, keys, specs, schema)
    return _frame(direct), _frame(sort)


def _assert_same(direct: dict, sort: dict):
    """Same groups in the same order; integers and nulls exact; floats
    to a few ulps (the reductions add in another order)."""
    assert list(direct) == list(sort)
    for name in direct:
        a, b = direct[name], sort[name]
        assert len(a) == len(b), name
        for u, v in zip(a, b):
            if u is None or v is None:
                assert u is None and v is None, name
            elif isinstance(u, float) or isinstance(v, float):
                assert u == pytest.approx(v, rel=1e-12, abs=1e-9), name
            else:
                assert u == v, name


@pytest.mark.parametrize("seed", [3, 11])
def test_direct_equals_the_sort_path_on_seeded_data(seed):
    batch = _batch(seed, 5_000)
    direct, sort = _both(batch, ["k0", "k1"], SPECS)
    assert len(direct["n"]) == 6
    _assert_same(direct, sort)


def test_null_keys_and_null_values():
    """A nullable key has one slot more, its nulls first (the sort
    path's order); null inputs are skipped as SQL skips them."""
    batch = _batch(5, 4_000, nulls=True)
    direct, sort = _both(batch, ["k0", "k1"], SPECS)
    assert len(direct["n"]) == 12  # (3 + 1) x (2 + 1) slots, all used
    assert direct["k0"][:3] == [None] * 3
    _assert_same(direct, sort)


def test_a_mask_with_no_survivors_gives_no_groups():
    import jax.numpy as jnp

    batch = _batch(7, 1_000)
    direct, sort = _both(batch, ["k0"], SPECS,
                         mask=jnp.zeros(1_000, dtype=bool))
    assert len(direct["n"]) == len(sort["n"]) == 0


def test_a_mask_drops_its_rows_and_empty_slots_drop_out():
    """Rows the mask drops count nowhere; slots no surviving row holds
    are not groups (here 7 of the 12 slots of a 12-word dictionary)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    batch = _batch(9, 3_000, domains=(12,))
    codes = np.asarray(batch.columns["k0"].raw)
    mask = jnp.asarray(np.isin(codes, [0, 2, 3, 5, 8, 9, 11])
                       & (rng.random(3_000) > 0.4))
    direct, sort = _both(batch, ["k0"], SPECS, mask=mask)
    assert direct["k0"] == ["v0000", "v0002", "v0003", "v0005", "v0008",
                            "v0009", "v0011"]
    _assert_same(direct, sort)


def test_one_slot():
    batch = _batch(13, 2_000, domains=(1,))
    direct, sort = _both(batch, ["k0"], SPECS)
    assert direct["k0"] == ["v0000"]
    _assert_same(direct, sort)


def test_bool_keys_and_expression_inputs():
    """A bool key has two slots; an aggregate of an expression is
    computed inside the program, as the sort path computes it before
    its sort."""
    from hyperspace_tpu.engine.physical import AggregateExec

    batch = _batch(17, 3_000)
    specs = [AggSpec("sum", col("y") * (lit(1.0) - lit(0.25)), "e"),
             AggSpec("sum", "x", "sx")]
    schema = _out_schema(batch, ["b", "k1"], specs)
    domains = agg.direct_domains(batch, ["b", "k1"])
    assert domains == (2, 2)
    direct = agg.direct_aggregate(batch, ["b", "k1"], specs, schema,
                                  domains)
    exec_ = AggregateExec(["b", "k1"], specs, schema, child=None)
    plain, plain_specs = exec_._materialize_inputs(batch)
    sort = agg.group_aggregate(plain, ["b", "k1"], plain_specs, schema)
    _assert_same(_frame(direct), _frame(sort))


def test_an_int8_key_has_256_slots():
    import jax.numpy as jnp

    batch = _batch(23, 4_000, domains=())
    codes = np.random.default_rng(23).integers(-128, 128, 4_000)
    batch.columns["i"] = DeviceColumn(jnp.asarray(codes.astype(np.int8)),
                                      "int8")
    batch.schema = Schema(list(batch.schema.fields)
                          + [Field("i", "int8", False)])
    assert agg.direct_domains(batch, ["i"]) == (256,)
    direct, sort = _both(batch, ["i"], SPECS)
    assert direct["i"][0] == -128 and len(direct["i"]) == 256
    _assert_same(direct, sort)


def test_a_float64_avg_is_not_taken_for_an_integer_one():
    """A float64 column on the device carries its bits as int64; its avg
    is a float's, on both paths, not the exact integer moments of its
    bits (which read 1.5 for subnormals whose bits are 1 and 2)."""
    import jax.numpy as jnp

    y = np.array([5e-324, 1e-323, 5e-324, 0.0])
    batch = ColumnBatch(Schema([Field("k", "bool", False),
                                Field("y", "float64", False)]), {
        "k": DeviceColumn(jnp.asarray([True, True, False, False]), "bool"),
        "y": DeviceColumn(jnp.asarray(y.view(np.int64)), "float64")})
    direct, sort = _both(batch, ["k"], [AggSpec("avg", "y", "a")])
    for frame in (direct, sort):
        assert all(abs(v) < 1e-300 for v in frame["a"]), frame


def test_the_slot_limit_decides_the_path():
    """A grouping of exactly DIRECT_GROUP_MAX_SLOTS slots is direct;
    one slot more takes the sort path."""
    top = agg.DIRECT_GROUP_MAX_SLOTS
    at = _batch(19, 2_000, domains=(top // 4, 4))
    over = _batch(19, 2_000, domains=(top // 4 + 1, 4))
    assert agg.direct_domains(at, ["k0", "k1"]) == (top // 4, 4)
    assert agg.direct_domains(over, ["k0", "k1"]) is None
    # an int64 key has no domain known from its type
    assert agg.direct_domains(at, ["k0", "x"]) is None
    direct, sort = _both(at, ["k0", "k1"], SPECS)
    _assert_same(direct, sort)


# -- through the planner: the filter's mask rides into the aggregate ----------


def _lineitem(seed: int, n: int) -> pa.Table:
    """Q1's columns and an unread one, the flags by clause 4.2.3."""
    rng = np.random.default_rng(seed)
    ship = rng.integers(_days(1992, 1, 2), _days(1998, 12, 1), n)
    receipt = ship + rng.integers(1, 31, n)
    current = _days(1995, 6, 17)
    flag = np.where(receipt > current, "N",
                    np.where(rng.random(n) < 0.5, "R", "A"))
    status = np.where(ship > current, "O", "F")
    return pa.table({
        "l_orderkey": np.arange(n, dtype=np.int64),
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": rng.integers(0, 10_500_000, n) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": pa.array(flag).dictionary_encode(),
        "l_linestatus": pa.array(status).dictionary_encode(),
        "l_shipdate": pa.array(ship.astype(np.int32)).cast(pa.date32()),
        "l_comment": pa.array(["x"] * n)})


@pytest.fixture(scope="module")
def q1_env(tmp_path_factory):
    root = tmp_path_factory.mktemp("q1")
    table = _lineitem(42, 30_000)
    (root / "li").mkdir()
    pq.write_table(table, str(root / "li" / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf(dict(DEVICE, **{
        "hyperspace.warehouse.dir": str(root / "wh"),
        "spark.hyperspace.index.num.buckets": "8"})))
    hs = Hyperspace(sess)
    df = sess.read_parquet(str(root / "li"))
    hs.create_index(df, IndexConfig(
        "li_q1", ["l_shipdate"], ["l_returnflag", "l_linestatus",
                                  "l_quantity", "l_extendedprice",
                                  "l_discount", "l_tax"]))
    sess.enable_hyperspace()
    yield sess, df, table.to_pandas()
    sess.close()


def _aggregate_record(metrics) -> dict:
    (op,) = [op for op in metrics.operators if op.name == "Aggregate"]
    return op.detail


def test_q1_through_the_covering_index_equals_pandas(q1_env):
    """The filter rule serves Aggregate(Filter(Scan)) from the covering
    index (judged on the columns the aggregate reads); the aggregate
    takes the direct path over the stage's rows and mask, uncompacted;
    the report equals pandas'."""
    sess, df, pdf = q1_env
    before = telemetry.get_registry().counters_dict().get(
        "aggregate.direct.rows", 0)
    table, metrics = queries.q1({"lineitem": df}).collect(with_metrics=True)
    applied = [ix["name"] for e in metrics.events_of("rule",
                                                     "FilterIndexRule")
               if e.get("action") == "applied" for ix in e["indexes"]]
    assert applied == ["li_q1"]
    (scan,) = [op for op in metrics.operators if op.name == "Scan"]
    assert scan.detail["index"] == "li_q1" and scan.detail["lane"] == "device"
    assert _aggregate_record(metrics) == {"path": "direct", "groups": 4,
                                          "masked": 30_000}
    assert telemetry.get_registry().counters_dict()[
        "aggregate.direct.rows"] - before == 30_000
    names = {op.name for op in metrics.operators}
    assert "Filter" not in names  # the filter is the fused stage's
    want = queries.q1_pandas({"lineitem": pdf.astype(
        {"l_returnflag": str, "l_linestatus": str})})
    pd.testing.assert_frame_equal(
        queries.normalize_result(table.to_pandas().astype(
            {"l_returnflag": str, "l_linestatus": str})),
        queries.normalize_result(want), check_dtype=False, rtol=1e-12)


def test_q1_on_the_sort_path_gives_the_same_report(q1_env, monkeypatch):
    sess, df, _ = q1_env
    direct = queries.q1({"lineitem": df}).to_pandas()
    monkeypatch.setattr(agg, "DIRECT_GROUP_MAX_SLOTS", 0)
    table, metrics = queries.q1({"lineitem": df}).collect(with_metrics=True)
    assert _aggregate_record(metrics) == {"path": "sorted", "groups": 4,
                                          "masked": 0}
    pd.testing.assert_frame_equal(direct, table.to_pandas(),
                                  check_exact=False, rtol=1e-12)


# -- which path each cell's aggregate takes -----------------------------------


def test_q12s_and_q17s_aggregates_record_their_paths(tmp_path):
    """TPC-H Q12 groups its joined lines by `l_shipmode` (7 slots):
    direct. TPC-DS q17 groups by item id, description and state (far
    over the slot limit): the sort path. Both through their indexes."""
    from hyperspace_tpu import tpcds, tpch

    def run(module, build, scale):
        paths = module.generate(str(tmp_path / build), scale=scale)
        sess = HyperspaceSession(HyperspaceConf(dict(DEVICE, **{
            "hyperspace.warehouse.dir": str(tmp_path / build / "wh"),
            "spark.hyperspace.index.num.buckets": "8"})))
        hs = Hyperspace(sess)
        dfs = {name: sess.read_parquet(p) for name, p in paths.items()}
        module.queries.create_indexes(hs, dfs, queries=[build])
        sess.enable_hyperspace()
        table, metrics = module.QUERIES[build][0](dfs).collect(
            with_metrics=True)
        sess.close()
        return table, [op.detail for op in metrics.operators
                       if op.name == "Aggregate"]

    table, records = run(tpch, "q12", 0.2)
    assert table.num_rows == 2
    assert records == [{"path": "direct", "groups": 2, "masked": 0}]
    table, records = run(tpcds, "q17", 0.02)
    assert records[0]["path"] == "sorted"
    assert records[0]["groups"] == table.num_rows
