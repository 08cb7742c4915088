"""Columnar substrate tests: arrow <-> device round trips, dictionary
encoding invariants, nulls, batch concat with dictionary unification."""

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu.io import columnar
from hyperspace_tpu.plan.schema import Schema


def sample_table():
    return pa.table({
        "i64": np.array([3, 1, 2], dtype=np.int64),
        "i32": np.array([30, 10, 20], dtype=np.int32),
        "f64": np.array([0.3, 0.1, 0.2]),
        "s": pa.array(["banana", "apple", "cherry"]),
        "b": pa.array([True, False, True]),
    })


def test_roundtrip():
    table = sample_table()
    batch = columnar.from_arrow(table)
    assert batch.num_rows == 3
    out = columnar.to_arrow(batch)
    assert out.equals(table)


def test_string_codes_order_preserving():
    batch = columnar.from_arrow(sample_table())
    col = batch.column("s")
    codes = np.asarray(col.data)
    values = col.dictionary[codes]
    # codes compare exactly like values
    assert list(np.argsort(codes)) == list(np.argsort(values))
    assert list(col.dictionary) == sorted(col.dictionary)


def test_dict_hashes_value_identity():
    """Same value in different batches (different dictionaries) must carry
    the same hash — the bucket-stability invariant."""
    t1 = pa.table({"s": pa.array(["x", "y"])})
    t2 = pa.table({"s": pa.array(["a", "y", "z"])})
    b1 = columnar.from_arrow(t1)
    b2 = columnar.from_arrow(t2)
    h1 = dict(zip(b1.column("s").dictionary,
                  zip(np.asarray(b1.column("s").dict_hashes[0]),
                      np.asarray(b1.column("s").dict_hashes[1]))))
    h2 = dict(zip(b2.column("s").dictionary,
                  zip(np.asarray(b2.column("s").dict_hashes[0]),
                      np.asarray(b2.column("s").dict_hashes[1]))))
    assert h1["y"] == h2["y"]


def test_nulls_roundtrip():
    table = pa.table({
        "x": pa.array([1, None, 3], type=pa.int64()),
        "s": pa.array(["a", None, "c"]),
    })
    batch = columnar.from_arrow(table)
    assert batch.column("x").validity is not None
    out = columnar.to_arrow(batch)
    assert out.column("x").null_count == 1
    assert out.column("s").null_count == 1
    assert out.column("x").to_pylist() == [1, None, 3]
    assert out.column("s").to_pylist() == ["a", None, "c"]


def test_take():
    import jax.numpy as jnp
    batch = columnar.from_arrow(sample_table())
    taken = batch.take(jnp.asarray([2, 0]))
    out = columnar.to_arrow(taken)
    assert out.column("i64").to_pylist() == [2, 3]
    assert out.column("s").to_pylist() == ["cherry", "banana"]


def test_concat_unifies_dictionaries():
    t1 = pa.table({"s": pa.array(["m", "a"]), "v": np.array([1, 2], dtype=np.int64)})
    t2 = pa.table({"s": pa.array(["z", "m"]), "v": np.array([3, 4], dtype=np.int64)})
    merged = columnar.concat_batches(
        [columnar.from_arrow(t1), columnar.from_arrow(t2)])
    out = columnar.to_arrow(merged)
    assert out.column("s").to_pylist() == ["m", "a", "z", "m"]
    col = merged.column("s")
    codes = np.asarray(col.data)
    # codes still order-preserving after unification
    assert (col.dictionary[codes] == np.array(["m", "a", "z", "m"])).all()


def test_select_case_insensitive():
    batch = columnar.from_arrow(sample_table())
    sub = batch.select(["I64", "S"])
    assert sub.schema.names == ["i64", "s"]


def test_arrow_encode_matches_reference_impl():
    """Production arrow-native encoding must agree with the numpy reference
    implementation on codes, dictionary order, and hashes."""
    from hyperspace_tpu.io.columnar import (_encode_strings,
                                            _encode_strings_arrow)
    values = ["pear", "apple", None, "pear", "", "zebra", "apple"]
    arr = pa.array(values, type=pa.string())
    codes_a, dict_a, hashes_a, validity_a = _encode_strings_arrow(arr)
    codes_r, dict_r, hashes_r, mask_r = _encode_strings(
        np.array(values, dtype=object))
    assert list(dict_a) == list(dict_r)
    assert list(codes_a) == list(codes_r)
    assert list(hashes_a) == list(hashes_r)
    assert list(validity_a) == list(mask_r)


def test_dictionary_typed_input_with_duplicates_and_nulls():
    """Dictionary-typed arrow columns with duplicate or null dictionary
    entries must be normalized (equal values -> equal codes)."""
    dict_arr = pa.DictionaryArray.from_arrays(
        pa.array([0, 1, 2, 3], type=pa.int32()),
        pa.array(["x", "x", None, "y"]))
    batch = columnar.from_arrow(pa.table({"s": dict_arr}))
    col = batch.column("s")
    codes = np.asarray(col.data)
    assert codes[0] == codes[1]  # both "x"
    assert col.validity is not None
    assert list(np.asarray(col.validity)) == [True, True, False, True]
    out = columnar.to_arrow(batch)
    assert out.column("s").to_pylist() == ["x", "x", None, "y"]


def test_multicolumn_two_lane_hash_consistency():
    """All bucket-assignment paths must agree for multi-column keys where a
    non-first column has two lanes (int64/string) — the flat-lane identity."""
    from hyperspace_tpu.io.columnar import batch_to_tree
    from hyperspace_tpu.ops.build import _tree_bucket_ids
    from hyperspace_tpu.ops.hash_partition import bucket_ids
    from hyperspace_tpu.ops.pallas.hash_kernel import hash_lanes_to_buckets
    from hyperspace_tpu.ops.build import _tree_hash_lanes

    rng = np.random.default_rng(3)
    table = pa.table({
        "a": rng.integers(0, 100, 500).astype(np.int32),
        "b": rng.integers(-2**60, 2**60, 500).astype(np.int64),
        "s": pa.array([f"v{int(x)}" for x in rng.integers(0, 30, 500)]),
    })
    batch = columnar.from_arrow(table)
    keys = ["a", "b", "s"]
    eager = np.asarray(bucket_ids(batch, keys, 16))
    tree, _ = batch_to_tree(batch)
    jnp_path = np.asarray(_tree_bucket_ids(tree, tuple(keys), 16,
                                           use_pallas=False))
    lanes = [lane for k in keys for lane in _tree_hash_lanes(tree[k])]
    pallas_path = np.asarray(hash_lanes_to_buckets(lanes, 16, interpret=True))
    assert (eager == jnp_path).all()
    assert (eager == pallas_path).all()


def test_host_and_device_builds_produce_identical_layout(tmp_path):
    """The host-lane build must write the SAME bucket layout (same rows in
    the same buckets, sorted the same) as the device program — bucket
    pruning and co-bucketed joins depend on the shared hash identity."""
    import os
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from hyperspace_tpu.io import builder

    rng = np.random.default_rng(17)
    n = 3000
    table = pa.table({
        "k": rng.integers(0, 700, n).astype(np.int64),
        "s": pa.array([None if i % 31 == 0 else "v%d" % (i % 53)
                       for i in range(n)]),
        "x": rng.standard_normal(n),
    })
    host_dir, dev_dir = str(tmp_path / "host"), str(tmp_path / "dev")
    assert n < builder.BUILD_MIN_DEVICE_ROWS
    builder.write_bucketed_table(table, ["k", "s"], 16, host_dir)
    orig = builder.BUILD_MIN_DEVICE_ROWS
    builder.BUILD_MIN_DEVICE_ROWS = 0
    try:
        builder.write_bucketed_table(table, ["k", "s"], 16, dev_dir)
    finally:
        builder.BUILD_MIN_DEVICE_ROWS = orig
    host_files = sorted(os.listdir(host_dir))
    dev_files = sorted(os.listdir(dev_dir))
    assert host_files == dev_files
    for f in host_files:
        h = pq.read_table(os.path.join(host_dir, f))
        d = pq.read_table(os.path.join(dev_dir, f))
        hk = h.column("k").to_numpy()
        dk = d.column("k").to_numpy()
        assert (hk == dk).all(), f"bucket {f}: key order differs"
        assert sorted(h.column("x").to_pylist()) == \
            sorted(d.column("x").to_pylist())


def test_read_cache_serves_and_invalidates(tmp_path):
    """The decoded-read cache serves unchanged files and MISSES when a
    file is rewritten in place (stamp mismatch) — correctness must never
    depend on cache state."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from hyperspace_tpu.io import parquet as P

    f = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"x": np.arange(5, dtype=np.int64)}), f)
    P.clear_read_cache()
    t1 = P.read_table([f])
    t2 = P.read_table([f])
    assert t2 is t1  # cache hit returns the same decoded table

    import os, time
    time.sleep(0.01)
    pq.write_table(pa.table({"x": np.arange(9, dtype=np.int64)}), f)
    t3 = P.read_table([f])
    assert t3 is not t1 and t3.num_rows == 9  # stamp changed -> fresh read

    # Column projection is part of the key.
    t4 = P.read_table([f], columns=["x"])
    assert t4.num_rows == 9
    P.clear_read_cache()


# ---------------------------------------------------------------------------
# float64 is CARRIED on the device as its bit pattern (a TPU's own f64 is
# an f32 pair and would round and clamp it); only computing decodes it.
# ---------------------------------------------------------------------------

F64_EDGE = np.array([1e300, -1e300, np.finfo(np.float64).max, 1e-300,
                     5e-324, 1e-40, -0.0, 0.1 + 0.2, np.inf, -np.inf,
                     np.nan, 0.25])


def _bits(values) -> np.ndarray:
    return np.ascontiguousarray(values, dtype=np.float64).view(np.int64)


@pytest.fixture
def no_float64_decode(monkeypatch):
    """On the CPU a decode is an exact bitcast and so invisible; make it
    an error, as it would be a rounding on the chip."""
    def refuse(_bits):
        raise AssertionError("float64 decoded on a path that moves rows")
    monkeypatch.setattr(columnar, "f64_from_bits", refuse)


def test_float64_is_carried_as_bits_and_moves_exactly(no_float64_decode):
    import jax.numpy as jnp

    table = pa.table({"k": np.arange(len(F64_EDGE), dtype=np.int64),
                      "x": F64_EDGE,
                      "n": pa.array([None, *F64_EDGE[1:]], type=pa.float64())})
    batch = columnar.from_arrow(table)
    col = batch.column("x")
    assert col.carries_bits and not col.is_host
    assert np.array_equal(np.asarray(col.raw), _bits(F64_EDGE))

    def exact(got, rows):
        for name in ("x", "n"):
            want = table.column(name).take(pa.array(rows))
            assert got.column(name).null_count == want.null_count
            assert np.array_equal(
                _bits(got.column(name).fill_null(7.0).to_numpy()),
                _bits(want.fill_null(7.0).to_numpy())), name

    everything = list(range(len(F64_EDGE)))
    exact(columnar.to_arrow(batch), everything)
    rows = [10, 0, 6, 6, 4]
    exact(columnar.to_arrow(batch.take(jnp.asarray(rows, dtype=jnp.int32))),
          rows)
    # demotion / re-promotion (the segment cache's tiers): host columns
    # hold the values themselves, the device copy carries bits again
    host = columnar.batch_to_host(batch)
    assert host.column("x").raw.dtype == np.float64
    assert np.array_equal(_bits(host.column("x").raw), _bits(F64_EDGE))
    back = columnar.host_batch_to_device(host)
    assert back.column("x").carries_bits
    exact(columnar.to_arrow(back), everything)
    # a device part and a host part concatenate without a float64 H2D
    both = columnar.concat_batches([batch, host])
    assert both.column("x").carries_bits
    exact(columnar.to_arrow(both), everything + everything)
    # trees: moved columns ride in carried form, and come back as the
    # same logical column
    tree, aux = columnar.batch_to_tree(host, computes_on=("k",))
    assert tree["x"]["data"].dtype == np.int64
    rebuilt = columnar.tree_to_batch(tree, host.schema, aux)
    assert rebuilt.column("x").raw.dtype == np.float64


def test_float64_values_decode_where_an_expression_computes():
    batch = columnar.from_arrow(pa.table({"x": F64_EDGE}))
    col = batch.column("x")
    assert np.array_equal(_bits(np.asarray(col.data)), _bits(F64_EDGE))
    tree, _ = columnar.batch_to_tree(batch)  # computes on every column
    assert np.asarray(tree["x"]["data"]).dtype == np.float64


def test_arithmetic_float64_decode_matches_the_bitcast():
    """The TPU's decode (no 64-bit bitcast there) is exact wherever the
    device's f64 holds the value: on the CPU that is every finite
    double in f32's exponent range, subnormals of f32 included."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    values = np.concatenate([
        rng.random(4096), rng.normal(size=4096) * 1e30,
        rng.normal(size=4096) * 1e-30,
        [0.0, -0.0, 1.0, -1.0, 3.4e38, 1.2e-38, 1e-40, 0.1 + 0.2,
         np.inf, -np.inf, np.nan]])
    got = np.asarray(columnar._f64_from_bits_arithmetic(
        jnp.asarray(_bits(values))))
    assert np.array_equal(_bits(got), _bits(values))


def test_renaming_projection_moves_a_float64_column(no_float64_decode):
    from hyperspace_tpu.engine.compiler import ExpressionCompiler
    from hyperspace_tpu.plan import expr as E

    batch = columnar.from_arrow(pa.table({"x": F64_EDGE}))
    out = ExpressionCompiler(batch).value_column(
        E.Alias(E.Column("x"), "y"), "float64")
    assert out.carries_bits
