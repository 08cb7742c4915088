"""Device kernel tests: hashing, sort, merge join (reference test layer 3 —
kernel tests on single-device arrays)."""

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu.io import columnar
from hyperspace_tpu.ops import hash_partition, join, sort


def batch_of(**cols):
    return columnar.from_arrow(pa.table(cols))


def test_bucket_ids_deterministic_and_in_range():
    b = batch_of(k=np.arange(1000, dtype=np.int64))
    ids1 = np.asarray(hash_partition.bucket_ids(b, ["k"], 8))
    ids2 = np.asarray(hash_partition.bucket_ids(b, ["k"], 8))
    assert (ids1 == ids2).all()
    assert ids1.min() >= 0 and ids1.max() < 8
    # reasonable balance: no empty bucket at n=1000, B=8
    assert len(np.unique(ids1)) == 8


def test_bucket_ids_value_stability_across_batches():
    """Same key value must land in the same bucket regardless of batch
    composition — required for co-bucketed joins."""
    b1 = batch_of(k=np.array([5, 100, 7], dtype=np.int64))
    b2 = batch_of(k=np.array([100, 9999], dtype=np.int64))
    ids1 = np.asarray(hash_partition.bucket_ids(b1, ["k"], 16))
    ids2 = np.asarray(hash_partition.bucket_ids(b2, ["k"], 16))
    assert ids1[1] == ids2[0]


def test_string_bucket_stability():
    b1 = batch_of(s=pa.array(["apple", "pear"]))
    b2 = batch_of(s=pa.array(["zebra", "pear", "kiwi"]))
    ids1 = np.asarray(hash_partition.bucket_ids(b1, ["s"], 32))
    ids2 = np.asarray(hash_partition.bucket_ids(b2, ["s"], 32))
    assert ids1[1] == ids2[1]


def test_multicolumn_hash_differs_by_order():
    b = batch_of(a=np.array([1, 2], dtype=np.int64),
                 c=np.array([2, 1], dtype=np.int64))
    h_ac = np.asarray(hash_partition.batch_hash32(b, ["a", "c"]))
    h_ca = np.asarray(hash_partition.batch_hash32(b, ["c", "a"]))
    assert not (h_ac == h_ca).all()


def test_sort_lexicographic_multi_key():
    b = batch_of(a=np.array([2, 1, 2, 1], dtype=np.int64),
                 c=np.array([0.1, 0.9, 0.0, 0.5]))
    out = columnar.to_arrow(sort.sort_batch(b, ["a", "c"]))
    assert out.column("a").to_pylist() == [1, 1, 2, 2]
    assert out.column("c").to_pylist() == [0.5, 0.9, 0.0, 0.1]


def test_sort_strings():
    b = batch_of(s=pa.array(["pear", "apple", "kiwi"]),
                 v=np.array([1, 2, 3], dtype=np.int64))
    out = columnar.to_arrow(sort.sort_batch(b, ["s"]))
    assert out.column("s").to_pylist() == ["apple", "kiwi", "pear"]
    assert out.column("v").to_pylist() == [2, 3, 1]


def test_sort_nulls_first():
    b = columnar.from_arrow(pa.table({"x": pa.array([3, None, 1], type=pa.int64())}))
    out = columnar.to_arrow(sort.sort_batch(b, ["x"]))
    assert out.column("x").to_pylist() == [None, 1, 3]


def test_bucket_boundaries():
    import jax.numpy as jnp
    sorted_ids = jnp.asarray(np.array([0, 0, 2, 2, 2, 3], dtype=np.int32))
    starts, ends = sort.bucket_boundaries(sorted_ids, 4)
    assert list(np.asarray(starts)) == [0, 2, 2, 5]
    assert list(np.asarray(ends)) == [2, 2, 5, 6]


def test_merge_join_indices_duplicates():
    import jax.numpy as jnp
    left = jnp.asarray(np.array([1, 1, 2, 5], dtype=np.int32))
    right = jnp.asarray(np.array([1, 2, 2, 7], dtype=np.int32))
    li, ri = join.merge_join_indices(left, right)
    pairs = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
    assert pairs == [(0, 0), (1, 0), (2, 1), (2, 2)]


def test_merge_join_no_matches():
    import jax.numpy as jnp
    li, ri = join.merge_join_indices(jnp.asarray(np.array([1, 2], np.int32)),
                                     jnp.asarray(np.array([3, 4], np.int32)))
    assert len(np.asarray(li)) == 0


def test_sort_merge_join_matches_numpy(sample_parquet):
    rng = np.random.default_rng(7)
    lk = rng.integers(0, 20, 200).astype(np.int64)
    rk = rng.integers(0, 20, 80).astype(np.int64)
    left = batch_of(k=lk, v1=np.arange(200, dtype=np.int64))
    right = batch_of(k=rk, v2=np.arange(80, dtype=np.int64))
    out = columnar.to_arrow(join.sort_merge_join(left, right, ["k"], ["k"]))
    df = out.to_pandas()
    import pandas as pd
    ref = pd.DataFrame({"k": lk, "v1": np.arange(200)}).merge(
        pd.DataFrame({"k": rk, "v2": np.arange(80)}), on="k")
    cols = ["k", "v1", "v2"]
    a = df[cols].sort_values(cols).reset_index(drop=True)
    b_ = ref[cols].sort_values(cols).reset_index(drop=True)
    assert len(a) == len(b_)
    assert (a.to_numpy() == b_.to_numpy()).all()


def test_sort_merge_join_string_keys_cross_dictionary():
    left = batch_of(s=pa.array(["a", "m", "z"]), x=np.array([1, 2, 3], np.int64))
    right = batch_of(s=pa.array(["m", "q"]), y=np.array([10, 20], np.int64))
    out = columnar.to_arrow(join.sort_merge_join(left, right, ["s"], ["s"]))
    assert out.column("s").to_pylist() == ["m"]
    assert out.column("x").to_pylist() == [2]
    assert out.column("y").to_pylist() == [10]


def test_join_duplicate_output_names_get_suffix():
    left = batch_of(k=np.array([1], np.int64), v=np.array([1], np.int64))
    right = batch_of(k=np.array([1], np.int64), v=np.array([9], np.int64))
    out = columnar.to_arrow(join.sort_merge_join(left, right, ["k"], ["k"]))
    assert out.column_names == ["k", "v", "k_r", "v_r"]


def test_join_null_keys_match_nothing():
    """SQL semantics: NULL join keys never match — not even each other, and
    never the null sentinel payload (0 / empty string)."""
    left = columnar.from_arrow(pa.table({
        "k": pa.array([None, -5, 3, 0], type=pa.int64()),
        "x": pa.array([1, 2, 3, 4], type=pa.int64())}))
    right = columnar.from_arrow(pa.table({
        "k": pa.array([0, None, -5], type=pa.int64()),
        "y": pa.array([10, 20, 30], type=pa.int64())}))
    out = columnar.to_arrow(join.sort_merge_join(left, right, ["k"], ["k"]))
    pairs = sorted(zip(out.column("x").to_pylist(), out.column("y").to_pylist()))
    assert pairs == [(2, 30), (4, 10)]


def test_join_null_string_keys():
    left = batch_of(s=pa.array(["a", None, ""]), x=np.array([1, 2, 3], np.int64))
    right = batch_of(s=pa.array([None, "", "a"]), y=np.array([10, 20, 30], np.int64))
    out = columnar.to_arrow(join.sort_merge_join(left, right, ["s"], ["s"]))
    pairs = sorted(zip(out.column("x").to_pylist(), out.column("y").to_pylist()))
    assert pairs == [(1, 30), (3, 20)]


def test_bucketed_join_empty_side():
    """An empty side must yield an empty join, not a crash."""
    from hyperspace_tpu.ops.bucketed_join import bucketed_sort_merge_join
    import pyarrow as _pa
    left = columnar.from_arrow(_pa.table({
        "k": _pa.array([], type=_pa.int64()),
        "x": _pa.array([], type=_pa.int64())}))
    right = batch_of(k=np.array([1, 2], np.int64), y=np.array([5, 6], np.int64))
    out = bucketed_sort_merge_join(left, right, np.zeros(4, np.int64),
                                   np.array([1, 1, 0, 0], np.int64),
                                   ["k"], ["k"])
    assert out.num_rows == 0
    assert columnar.to_arrow(out).column_names == ["k", "x", "k_r", "y"]


def test_bucketed_left_outer_unmatched_rows_get_null():
    """Regression: unmatched left rows must emit right index -1, not an
    arbitrary right row (the outer fill used to overwrite the true match
    counts before _expand_core derived its matched mask)."""
    from hyperspace_tpu.ops.bucketed_join import bucketed_join_indices
    left = batch_of(k=np.array([1, 2, 3], np.int64),
                    x=np.array([10, 20, 30], np.int64))
    right = batch_of(k=np.array([1, 3], np.int64),
                     y=np.array([100, 300], np.int64))
    li, ri = bucketed_join_indices(left, right, np.array([3], np.int64),
                                   np.array([2], np.int64), ["k"], ["k"],
                                   how="left_outer")
    pairs = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
    assert pairs == [(0, 0), (1, -1), (2, 1)]


def test_bucketed_outer_join_null_payloads():
    """Full outer-join assembly: unmatched rows carry nulls on the other
    side, for both left_outer and right_outer, across buckets."""
    from hyperspace_tpu.ops.bucketed_join import bucketed_sort_merge_join
    left = batch_of(k=np.array([1, 2, 5, 6], np.int64),
                    x=np.array([10, 20, 50, 60], np.int64))
    right = batch_of(k=np.array([2, 5, 7], np.int64),
                     y=np.array([200, 500, 700], np.int64))
    # Two buckets: left has rows [1,2] then [5,6]; right [2] then [5,7].
    out = columnar.to_arrow(bucketed_sort_merge_join(
        left, right, np.array([2, 2], np.int64), np.array([1, 2], np.int64),
        ["k"], ["k"], how="left_outer"))
    rows = sorted(zip(out.column("x").to_pylist(), out.column("y").to_pylist()))
    assert rows == [(10, None), (20, 200), (50, 500), (60, None)]

    out = columnar.to_arrow(bucketed_sort_merge_join(
        left, right, np.array([2, 2], np.int64), np.array([1, 2], np.int64),
        ["k"], ["k"], how="right_outer"))
    rows = sorted(zip(out.column("x").to_pylist(), out.column("y").to_pylist()),
                  key=lambda t: (t[0] is None, t))
    assert rows == [(20, 200), (50, 500), (None, 700)]


def test_bucketed_left_outer_null_keys_unmatched():
    """NULL join keys never match but still appear once in a left outer."""
    from hyperspace_tpu.ops.bucketed_join import bucketed_sort_merge_join
    left = columnar.from_arrow(pa.table({
        "k": pa.array([1, None, 3], type=pa.int64()),
        "x": pa.array([10, 20, 30], type=pa.int64())}))
    right = batch_of(k=np.array([1, 3], np.int64),
                     y=np.array([100, 300], np.int64))
    out = columnar.to_arrow(bucketed_sort_merge_join(
        left, right, np.array([3], np.int64), np.array([2], np.int64),
        ["k"], ["k"], how="left_outer"))
    rows = sorted(zip(out.column("x").to_pylist(), out.column("y").to_pylist()))
    assert rows == [(10, 100), (20, None), (30, 300)]


def test_narrow_key_transport_matches_wide_path(tmp_path):
    """`_stage_key_tree`'s lo32 narrow transport must produce the exact
    same bucket layout and row order as the wide int64 path — bucket ids
    ride the same [hi=0, lo] hash lane chain."""
    import os
    import pyarrow.parquet as pq
    from hyperspace_tpu.io.builder import write_bucketed_table

    rng = np.random.default_rng(11)
    n = 5000
    table = pa.table({
        "k": rng.integers(0, 1 << 31, n).astype(np.int64),  # fits uint32
        "v": np.arange(n, dtype=np.int64),
    })
    narrow_dir = str(tmp_path / "narrow")
    wide_dir = str(tmp_path / "wide")
    write_bucketed_table(table, ["k"], 8, narrow_dir)  # narrow staging
    write_bucketed_table(table, ["k"], 8, wide_dir,
                         key_batch=columnar.from_arrow(table))  # wide lanes
    narrow_files = sorted(os.listdir(narrow_dir))
    assert narrow_files == sorted(os.listdir(wide_dir))
    for f in narrow_files:
        a = pq.read_table(os.path.join(narrow_dir, f))
        b = pq.read_table(os.path.join(wide_dir, f))
        assert a.equals(b), f

    # Values outside uint32 range must take the wide path and still work.
    big = pa.table({
        "k": (rng.integers(0, 1 << 31, 1000).astype(np.int64)
              - (1 << 30)) * 8,  # negatives + >2^32
        "v": np.arange(1000, dtype=np.int64),
    })
    big_dir = str(tmp_path / "big")
    write_bucketed_table(big, ["k"], 4, big_dir)
    rows = sum(pq.read_table(os.path.join(big_dir, f)).num_rows
               for f in os.listdir(big_dir) if f.endswith(".parquet"))
    assert rows == 1000
    for f in os.listdir(big_dir):
        if f.endswith(".parquet"):
            ks = pq.read_table(os.path.join(big_dir, f)).column("k").to_pylist()
            assert ks == sorted(ks)


def test_float_hash_identity_shared_between_paths():
    """Eager column_hash32 and the jitted build core must agree on float
    keys — on-disk bucket layout depends on one shared hash identity."""
    from hyperspace_tpu.ops.build import _tree_hash_lanes
    from hyperspace_tpu.ops.hash_partition import flat_hash32
    from hyperspace_tpu.io.columnar import batch_to_tree
    b = batch_of(f=np.array([-1.5, 0.0, 2.25, 1e300], dtype=np.float64))
    eager = np.asarray(hash_partition.column_hash32(b.column("f")))
    tree, _ = batch_to_tree(b)
    jitted = np.asarray(flat_hash32(_tree_hash_lanes(tree["f"])))
    assert (eager == jitted).all()


def _bucket_order(batch, keys, num_buckets):
    """Lay a batch out concat-in-bucket-order with per-bucket lengths."""
    import jax.numpy as jnp
    ids = np.asarray(hash_partition.bucket_ids(batch, keys, num_buckets))
    order = np.argsort(ids, kind="stable").astype(np.int32)
    lengths = np.bincount(ids, minlength=num_buckets).astype(np.int64)
    return batch.take(jnp.asarray(order)), lengths


def test_bucketed_join_hot_key_skew_falls_back_and_matches():
    """One key owning 50% of rows must not inflate the padded layout to
    O(B * rows): the skew guard routes to the global merge join, and the
    result multiset is unchanged (VERDICT r1 weak #3)."""
    from hyperspace_tpu.ops import bucketed_join as bj

    num_buckets = 64
    n = 100_000
    rng = np.random.default_rng(7)
    hot = np.full(n // 2, 42, dtype=np.int64)
    cold = rng.integers(1000, 1000 + n, n // 2).astype(np.int64)
    lkeys = np.concatenate([hot, cold])
    left = batch_of(k=lkeys, x=np.arange(n, dtype=np.int64))
    # Right: hot key appears 3x, plus a slice of the cold keys once each.
    rkeys = np.concatenate([np.full(3, 42, np.int64), cold[:1000]])
    right = batch_of(k=rkeys, y=np.arange(len(rkeys), dtype=np.int64))

    lb, ll = _bucket_order(left, ["k"], num_buckets)
    rb, rl = _bucket_order(right, ["k"], num_buckets)

    li, ri = bj.bucketed_join_indices(lb, rb, ll, rl, ["k"], ["k"])
    got_l = np.asarray(lb.column("k").data)[np.asarray(li)]
    got_r = np.asarray(rb.column("k").data)[np.asarray(ri)]
    assert (got_l == got_r).all()
    # Expected inner-join multiset: hot key 50000*3 plus 1000 cold matches
    # (cold keys are drawn with replacement -> count actual matches).
    r_counts = {}
    for k in rkeys:
        r_counts[k] = r_counts.get(k, 0) + 1
    expected_total = sum(r_counts.get(k, 0) for k in lkeys)
    assert len(np.asarray(li)) == expected_total
    # Spot-check multiset equality on the cold slice.
    got_cold = np.sort(got_l[got_l != 42])
    exp_cold = np.sort(np.concatenate(
        [np.repeat(k, r_counts.get(k, 0)) for k in cold if k in r_counts]))
    assert (got_cold == exp_cold).all()


def test_bucketed_join_skew_left_outer_matches_global():
    """Left-outer under skew: unmatched left rows emit -1 exactly once."""
    from hyperspace_tpu.ops import bucketed_join as bj

    num_buckets = 64
    n = 80_000
    lkeys = np.concatenate([np.full(n // 2, 7, np.int64),
                            np.arange(10_000, 10_000 + n // 2, dtype=np.int64)])
    left = batch_of(k=lkeys)
    right = batch_of(k=np.array([7, 10_000, 10_001], np.int64))
    lb, ll = _bucket_order(left, ["k"], num_buckets)
    rb, rl = _bucket_order(right, ["k"], num_buckets)

    li, ri = bj.bucketed_join_indices(lb, rb, ll, rl, ["k"], ["k"],
                                      how="left_outer")
    li, ri = np.asarray(li), np.asarray(ri)
    # Every left row appears exactly once (each matches <= 1 right row).
    assert len(li) == n
    assert sorted(li.tolist()) == list(range(n))
    lk = np.asarray(lb.column("k").data)
    matched = np.isin(lk[li], [7, 10_000, 10_001])
    assert ((ri >= 0) == matched).all()


def test_host_bucket_ids_match_device():
    """The host (numpy) hash mirror must agree with THE device hash
    identity for every key dtype — bucket pruning and the on-disk layout
    depend on it."""
    from hyperspace_tpu.ops.host_hash import host_bucket_ids

    rng = np.random.default_rng(13)
    n, B = 257, 32
    cases = {
        "int64": rng.integers(-2**62, 2**62, n).astype(np.int64),
        "int32": rng.integers(-2**31, 2**31 - 1, n).astype(np.int32),
        "int16": rng.integers(-2**15, 2**15 - 1, n).astype(np.int16),
        "bool": rng.integers(0, 2, n).astype(bool),
        "float64": rng.standard_normal(n) * 1e6,
        "float32": (rng.standard_normal(n) * 1e3).astype(np.float32),
        "string": np.array(["v_%d" % v for v in rng.integers(0, 50, n)]),
    }
    for dtype, vals in cases.items():
        table = pa.table({"k": pa.array(vals)})
        batch = columnar.from_arrow(table)
        dev = np.asarray(hash_partition.bucket_ids(batch, ["k"], B))
        host = host_bucket_ids([vals], [dtype], B)
        assert (dev == host).all(), f"identity mismatch for {dtype}"
    # Multi-column combine order matters: (int64, string) pair.
    table = pa.table({"a": pa.array(cases["int64"]),
                      "s": pa.array(cases["string"])})
    batch = columnar.from_arrow(table)
    dev = np.asarray(hash_partition.bucket_ids(batch, ["a", "s"], B))
    host = host_bucket_ids([cases["int64"], cases["string"]],
                           ["int64", "string"], B)
    assert (dev == host).all()


def test_stddev_aggregate_and_host_device_parity():
    """stddev (sample) on both lanes; host-lane aggregation must agree
    with the device lane bit-for-bit on grouping and SQL null semantics."""
    from hyperspace_tpu.io.columnar import from_arrow
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec
    from hyperspace_tpu.plan.schema import Schema

    rng = np.random.default_rng(3)
    n = 4000
    table = pa.table({
        "g": rng.integers(0, 37, n).astype(np.int64),
        "x": pa.array([None if i % 11 == 0 else float(v) for i, v in
                       enumerate(rng.standard_normal(n))], type=pa.float64()),
        "y": rng.integers(-100, 100, n).astype(np.int64),
    })
    schema = Schema.from_arrow(table.schema)
    specs = [AggSpec("count", "*", "cnt"), AggSpec("count", "x", "cx"),
             AggSpec("sum", "y", "sy"), AggSpec("avg", "x", "ax"),
             AggSpec("min", "y", "mny"), AggSpec("max", "y", "mxy"),
             AggSpec("stddev", "x", "sx")]
    from hyperspace_tpu.plan.nodes import Scan
    out_schema = Aggregate(["g"], specs,
                           Scan(["/nonexistent"], schema)).schema

    host = group_aggregate(from_arrow(table, device=False), ["g"], specs,
                           out_schema)
    dev = group_aggregate(from_arrow(table, device=True), ["g"], specs,
                          out_schema)
    import pandas as pd
    from hyperspace_tpu.io.columnar import to_arrow
    h = to_arrow(host).to_pandas().sort_values("g").reset_index(drop=True)
    d = to_arrow(dev).to_pandas().sort_values("g").reset_index(drop=True)
    pd.testing.assert_frame_equal(h, d, check_exact=False, rtol=1e-9)
    # Cross-check stddev against pandas (sample stddev).
    ref = (table.to_pandas().groupby("g")["x"].std()
           .reset_index(drop=True))
    assert np.allclose(h["sx"].to_numpy(), ref.to_numpy(),
                       rtol=1e-9, equal_nan=True)


def test_stddev_no_catastrophic_cancellation():
    """stddev over large-offset values (timestamp magnitude) must not
    cancel: two-pass shifted variance on both lanes."""
    from hyperspace_tpu.io.columnar import from_arrow
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    rng = np.random.default_rng(1)
    x = 1.7e15 + rng.standard_normal(1000)
    table = pa.table({"g": np.zeros(1000, np.int64), "x": x})
    schema = Schema.from_arrow(table.schema)
    specs = [AggSpec("stddev", "x", "sx")]
    out_schema = Aggregate(["g"], specs, Scan(["/nx"], schema)).schema
    expected = np.std(x, ddof=1)
    for device in (False, True):
        out = group_aggregate(from_arrow(table, device=device), ["g"],
                              specs, out_schema)
        got = float(np.asarray(out.column("sx").data)[0])
        assert abs(got - expected) < 1e-3, f"device={device}: {got}"


def test_host_join_rejects_mismatched_key_lists():
    """The host lane must enforce the same key-list validation as the
    device path instead of silently truncating via zip."""
    from hyperspace_tpu.io.columnar import from_arrow
    from hyperspace_tpu.ops.join import sort_merge_join
    from hyperspace_tpu.exceptions import HyperspaceException

    left = from_arrow(pa.table({"a": np.arange(3, dtype=np.int64),
                                "b": np.arange(3, dtype=np.int64)}),
                      device=False)
    right = from_arrow(pa.table({"a": np.arange(3, dtype=np.int64)}),
                       device=False)
    with pytest.raises(HyperspaceException):
        sort_merge_join(left, right, ["a", "b"], ["a"])


def test_host_join_empty_sides():
    """Empty build side on the host lane: outer joins emit -1, inner joins
    emit nothing — no IndexError from indexing an empty order array."""
    from hyperspace_tpu.io.columnar import from_arrow
    from hyperspace_tpu.ops.join import host_join_indices

    left = from_arrow(pa.table({"k": np.arange(3, dtype=np.int64)}),
                      device=False)
    right = from_arrow(pa.table({"k": pa.array([], type=pa.int64())}),
                       device=False)
    li, ri = host_join_indices(left, right, ["k"], ["k"], how="left_outer")
    assert li.tolist() == [0, 1, 2] and ri.tolist() == [-1, -1, -1]
    li, ri = host_join_indices(left, right, ["k"], ["k"], how="inner")
    assert len(li) == 0 and len(ri) == 0
    li, ri = host_join_indices(right, left, ["k"], ["k"], how="inner")
    assert len(li) == 0


def test_float_key_negative_zero_and_nan_uniform_across_lanes():
    """-0.0 joins 0.0 and NaN joins NaN identically on every path: the
    host packed fast path (raw float compare), the host lane-encoded
    path, and the device encode (normalized order bits) — the advisor's
    round-2 medium finding."""
    lk = np.array([-0.0, 0.0, np.nan, 1.5])
    rk = np.array([0.0, np.nan, 1.5, 2.0])
    left = batch_of(k=lk, a=np.arange(4))
    right = batch_of(k=rk, b=np.arange(4))

    # Host packed path (single numeric null-free key).
    li, ri = join.host_join_indices(left, right, ["k"], ["k"])
    packed_pairs = sorted(zip(li.tolist(), ri.tolist()))
    # -0.0 matches 0.0 (rows 0,1 -> right 0); NaN matches NaN (2 -> 1);
    # 1.5 -> 2.
    assert packed_pairs == [(0, 0), (1, 0), (2, 1), (3, 2)]

    # Host lane-encoded path (forced by adding a second key).
    left2 = batch_of(k=lk, k2=pa.array(["x"] * 4), a=np.arange(4))
    right2 = batch_of(k=rk, k2=pa.array(["x"] * 4), b=np.arange(4))
    li2, ri2 = join.host_join_indices(left2, right2, ["k", "k2"],
                                      ["k", "k2"])
    assert sorted(zip(li2.tolist(), ri2.tolist())) == packed_pairs

    # Device encode: group ids of -0.0/0.0 equal; NaNs equal across sides.
    dl = columnar.from_arrow(pa.table({"k": lk}))
    dr = columnar.from_arrow(pa.table({"k": rk}))
    out = join.sort_merge_join(dl, dr, ["k"], ["k"])
    assert out.num_rows == 4

    # Bucket hash identity: -0.0 and 0.0 land in the same bucket on the
    # host mirror (device parity is pinned by
    # test_host_bucket_ids_match_device).
    from hyperspace_tpu.ops.host_hash import host_bucket_ids
    ids = host_bucket_ids([np.array([-0.0, 0.0, np.nan, np.nan])],
                          ["float64"], 16)
    assert ids[0] == ids[1] and ids[2] == ids[3]


def test_float_group_by_negative_zero_one_group():
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    table = pa.table({"k": np.array([-0.0, 0.0, -0.0]),
                      "v": np.array([1, 2, 3], dtype=np.int64)})
    batch = columnar.from_arrow(table)
    schema = Schema.from_arrow(table.schema)
    out_schema = Aggregate(["k"], [AggSpec("sum", "v", "sv")],
                           Scan(["/nx"], schema)).schema
    out = group_aggregate(batch, ["k"], [AggSpec("sum", "v", "sv")],
                          out_schema)
    assert out.num_rows == 1
    assert int(np.asarray(out.column("sv").data)[0]) == 6


def test_staged_sort_permutation_matches_wide_sort():
    """Wide key sets (> MAX_SORT_OPERANDS) sort via staged LSD passes;
    the permutation must equal the single wide lexicographic sort (XLA's
    wide variadic comparator is the q64 compile-time explosion the
    staging exists to avoid)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import (MAX_SORT_OPERANDS,
                                         staged_sort_permutation)

    rng = np.random.default_rng(5)
    n = 5000
    k = MAX_SORT_OPERANDS * 2 + 3  # forces three chunked passes
    operands = [jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
                for _ in range(k)]
    got = staged_sort_permutation(operands)
    iota = jnp.arange(n, dtype=jnp.int32)
    want = jax.lax.sort([*operands, iota], num_keys=k,
                        is_stable=True)[-1]
    assert (np.asarray(got) == np.asarray(want)).all()
    # narrow path identity too
    got2 = staged_sort_permutation(operands[:3])
    want2 = jax.lax.sort([*operands[:3], iota], num_keys=3,
                         is_stable=True)[-1]
    assert (np.asarray(got2) == np.asarray(want2)).all()


def test_topk_matches_full_sort():
    """topk_batch == sort_batch[:n] on both lanes, across ties, nulls,
    descending keys, and low-cardinality prefixes (candidate blow-up)."""
    import numpy as np

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.sort import sort_batch, topk_batch

    rng = np.random.default_rng(5)
    n = 50_000
    import pyarrow as pa
    mask = rng.random(n) < 0.05
    table = pa.table({
        "a": pa.array(rng.integers(0, 40, n).astype(np.int64)),  # heavy ties
        "b": pa.array(rng.integers(-1000, 1000, n).astype(np.int64),
                      mask=mask),
        "c": pa.array(rng.random(n)),
        "s": pa.array(np.array(["x", "y", "zz", "w"])[
            rng.integers(0, 4, n)]),
    })
    for device in (False, True):
        batch = columnar.from_arrow(table, device=device)
        for keys in (["a", "b", "s"], ["-a", "c"], ["s", "-b"]):
            want = sort_batch(batch, keys)
            for k in (1, 100, 4096):
                got = topk_batch(batch, keys, k)
                import pandas as pd
                w = columnar.to_arrow(want).to_pandas().head(k) \
                    .reset_index(drop=True)
                g = columnar.to_arrow(got).to_pandas() \
                    .reset_index(drop=True)
                pd.testing.assert_frame_equal(g, w, check_dtype=False)


def test_topk_residency_contract():
    """The documented topk_batch residency contract (`ops/sort.py`):
    host input -> host output; device input -> HOST output on the
    threshold path, DEVICE output on the candidate-cap fallback (the
    low-cardinality prefix where the threshold stops pruning)."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops import sort as sort_mod
    from hyperspace_tpu.ops.sort import topk_batch

    rng = np.random.default_rng(9)
    n = 20_000
    table = pa.table({
        "a": rng.integers(0, 1_000_000, n).astype(np.int64),
        "v": rng.random(n),
    })
    host_batch = columnar.from_arrow(table, device=False)
    assert topk_batch(host_batch, ["a"], 10).is_host

    dev_batch = columnar.from_arrow(table, device=True)
    # Selective prefix: threshold path -> host-resident result.
    out = topk_batch(dev_batch, ["a"], 10)
    assert out.num_rows == 10 and out.is_host
    # Candidate blow-up (constant prefix, cap forced tiny): the full
    # device sort serves the query -> device-resident result.
    const = pa.table({
        "a": np.zeros(n, dtype=np.int64),
        "v": rng.random(n),
    })
    dev_const = columnar.from_arrow(const, device=True)
    old_cap = sort_mod.TOPK_CANDIDATE_CAP
    sort_mod.TOPK_CANDIDATE_CAP = 64
    try:
        out2 = topk_batch(dev_const, ["a", "v"], 10)
    finally:
        sort_mod.TOPK_CANDIDATE_CAP = old_cap
    assert out2.num_rows == 10 and not out2.is_host


def test_hashed_group_phase_matches_exact():
    """Wide (>=5-lane) groupings route through the u64 hash-lane sort;
    aggregation results must be identical to the exact full-lane sort
    path (same groups, same reductions — order may differ)."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops import aggregate as agg_mod
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.nodes import AggSpec
    from hyperspace_tpu.plan.schema import Field, Schema

    rng = np.random.default_rng(12)
    n = 30_000
    table = pa.table({
        "a": rng.integers(0, 8, n).astype(np.int64),
        "b": rng.integers(0, 7, n).astype(np.int64),
        "c": rng.integers(-5, 5, n).astype(np.int64),
        "v": rng.random(n),
    })
    batch = columnar.from_arrow(table, device=True)
    specs = [AggSpec("sum", "v", "s"), AggSpec("count", "*", "n")]
    out_schema = Schema([Field("a", "int64", True), Field("b", "int64", True),
                         Field("c", "int64", True), Field("s", "float64", True),
                         Field("n", "int64", True)])
    # 3 int64 group columns -> 6 lanes >= HASH_GROUP_MIN_LANES
    assert 6 >= agg_mod.HASH_GROUP_MIN_LANES
    got = columnar.to_arrow(group_aggregate(
        batch, ["a", "b", "c"], specs, out_schema)).to_pandas()
    # exact path for reference
    old = agg_mod.HASH_GROUP_MIN_LANES
    agg_mod.HASH_GROUP_MIN_LANES = 10**9
    try:
        want = columnar.to_arrow(group_aggregate(
            batch, ["a", "b", "c"], specs, out_schema)).to_pandas()
    finally:
        agg_mod.HASH_GROUP_MIN_LANES = old
    import pandas as pd
    key = ["a", "b", "c"]
    pd.testing.assert_frame_equal(
        got.sort_values(key).reset_index(drop=True),
        want.sort_values(key).reset_index(drop=True), check_dtype=False)


def test_hashed_group_phase_collision_fallback():
    """A colliding hash must trigger the exact-sort re-run, not a wrong
    answer: force collisions by stubbing the packed flag via a degenerate
    hash (monkeypatch _fmix32 to a constant)."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops import aggregate as agg_mod
    from hyperspace_tpu.ops import hash_partition as hp
    from hyperspace_tpu.plan.nodes import AggSpec
    from hyperspace_tpu.plan.schema import Field, Schema

    rng = np.random.default_rng(13)
    n = 5_000
    table = pa.table({
        "a": rng.integers(0, 5, n).astype(np.int64),
        "b": rng.integers(0, 4, n).astype(np.int64),
        "c": rng.integers(0, 3, n).astype(np.int64),
        "v": rng.random(n),
    })
    batch = columnar.from_arrow(table, device=True)
    specs = [AggSpec("sum", "v", "s")]
    out_schema = Schema([Field("a", "int64", True), Field("b", "int64", True),
                         Field("c", "int64", True),
                         Field("s", "float64", True)])
    orig = hp._fmix32
    agg_mod._group_phase_a_hashed.clear_cache()
    hp._fmix32 = lambda h: h * 0  # every key collides
    try:
        got = columnar.to_arrow(agg_mod.group_aggregate(
            batch, ["a", "b", "c"], specs, out_schema)).to_pandas()
    finally:
        hp._fmix32 = orig
        agg_mod._group_phase_a_hashed.clear_cache()
    want = (table.to_pandas().groupby(["a", "b", "c"], as_index=False)
            .agg(s=("v", "sum")))
    import pandas as pd
    key = ["a", "b", "c"]
    pd.testing.assert_frame_equal(
        got.sort_values(key).reset_index(drop=True),
        want.sort_values(key).reset_index(drop=True), check_dtype=False)


def test_hashed_counting_match_matches_exact():
    """Wide join keys (>=4 lanes) route through the hashed counting
    match; the join result must equal the exact multi-lane sort path."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops import join as join_mod

    rng = np.random.default_rng(21)
    n, m = 20_000, 15_000
    left = columnar.from_arrow(pa.table({
        "k1": rng.integers(0, 50, n).astype(np.int64),
        "k2": rng.integers(-20, 20, n).astype(np.int64),
        "v": rng.random(n)}), device=True)
    right = columnar.from_arrow(pa.table({
        "k1": rng.integers(0, 50, m).astype(np.int64),
        "k2": rng.integers(-20, 20, m).astype(np.int64),
        "w": rng.random(m)}), device=True)
    # marker + 2x int64 lanes = 5 >= HASH_MATCH_MIN_LANES
    assert 5 >= join_mod.HASH_MATCH_MIN_LANES
    for how in ("inner", "left_outer"):
        li, ri = join_mod.counting_join_batch_indices(
            left, right, ["k1", "k2"], ["k1", "k2"], how=how)
        old = join_mod.HASH_MATCH_MIN_LANES
        join_mod.HASH_MATCH_MIN_LANES = 10**9
        try:
            li2, ri2 = join_mod.counting_join_batch_indices(
                left, right, ["k1", "k2"], ["k1", "k2"], how=how)
        finally:
            join_mod.HASH_MATCH_MIN_LANES = old
        got = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
        want = sorted(zip(np.asarray(li2).tolist(),
                          np.asarray(ri2).tolist()))
        assert got == want, how


def test_hashed_counting_match_collision_fallback():
    """A degenerate hash (every key collides) must trigger the exact
    re-run, not a wrong join."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops import hash_partition as hp
    from hyperspace_tpu.ops import join as join_mod

    rng = np.random.default_rng(22)
    n, m = 3_000, 2_500
    left = columnar.from_arrow(pa.table({
        "k1": rng.integers(0, 20, n).astype(np.int64),
        "k2": rng.integers(0, 10, n).astype(np.int64)}), device=True)
    right = columnar.from_arrow(pa.table({
        "k1": rng.integers(0, 20, m).astype(np.int64),
        "k2": rng.integers(0, 10, m).astype(np.int64)}), device=True)
    li2, ri2 = join_mod.counting_join_batch_indices(
        left, right, ["k1", "k2"], ["k1", "k2"], how="inner")
    orig = hp._fmix32
    join_mod._counting_match_lanes_hashed.clear_cache()
    hp._fmix32 = lambda h: h * 0
    try:
        li, ri = join_mod.counting_join_batch_indices(
            left, right, ["k1", "k2"], ["k1", "k2"], how="inner")
    finally:
        hp._fmix32 = orig
        join_mod._counting_match_lanes_hashed.clear_cache()
    got = sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))
    want = sorted(zip(np.asarray(li2).tolist(), np.asarray(ri2).tolist()))
    assert got == want


def _loop_brackets(keys, side, left_outer):
    """Plain reference for `join._runs_to_counts`: walk the key runs of
    the sorted (key, side) rows one by one."""
    T = len(side)
    rights = np.zeros(T, dtype=np.int32)
    rstart = np.zeros(T, dtype=np.int32)
    first = 0
    while first < T:
        last = first
        while last + 1 < T and (keys[last + 1] == keys[first]).all():
            last += 1
        in_run = int(side[first:last + 1].sum())
        rights[first:last + 1] = in_run
        rstart[first:last + 1] = last - in_run + 1
        first = last + 1
    counts = np.where(side == 0, rights, 0).astype(np.int32)
    if left_outer:
        counts = np.where(side == 0, np.maximum(counts, 1), 0).astype(
            np.int32)
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    return counts, starts, rights, rstart


def _bracket_cases():
    """name -> (marker, value, side) of the rows a counting match sorts;
    marker 0 = valid key, 1 = left row with a null key, 2 = right row
    with one (`join._join_lane_operands`)."""
    rng = np.random.default_rng(35)
    left, right = np.zeros, np.ones
    cases = {
        "all_distinct": (np.arange(64), rng.integers(0, 2, 64)),
        "one_hot_key": (left(50), np.repeat([0, 1], [20, 30])),
        "left_only_runs": (np.repeat(np.arange(9), 5), left(45)),
        "right_only_runs": (np.repeat(np.arange(9), 5), right(45)),
        "two_rows_one_run": (np.array([0, 0]), np.array([0, 1])),
        "two_rows_two_runs": (np.array([0, 1]), np.array([1, 0])),
        "duplicate_heavy": (rng.integers(0, 40, 5000),
                            rng.integers(0, 2, 5000)),
    }
    cases = {name: (np.zeros(len(side)), value, side)
             for name, (value, side) in cases.items()}
    # single-side runs behind the valid keys, equal values among them
    cases["null_marker_runs"] = (
        np.repeat([0, 1, 2], [12, 5, 4]),
        np.concatenate([np.repeat(np.arange(4), 3), [0, 0, 0, 7, 7],
                        [0, 0, 3, 3]]),
        np.concatenate([np.tile([0, 0, 1], 4), left(5), right(4)]))
    return cases


BRACKET_CASES = _bracket_cases()


@pytest.mark.parametrize("left_outer", [False, True])
@pytest.mark.parametrize("case", sorted(BRACKET_CASES))
def test_runs_to_counts_matches_a_loop_over_runs(case, left_outer):
    import jax.numpy as jnp

    marker, value, side = BRACKET_CASES[case]
    order = np.lexsort((side, value, marker))  # as the match's sort does
    keys = np.stack([marker[order], value[order]], axis=1)
    side = side[order].astype(np.int32)
    differs = (keys[1:] != keys[:-1]).any(axis=1)
    got = join._runs_to_counts(jnp.asarray(differs), jnp.asarray(side),
                               left_outer)
    want = _loop_brackets(keys, side, left_outer)
    for name, g, w in zip(("counts", "starts", "rights", "rstart"),
                          got, want):
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


def _primitives(jaxpr, into):
    """How often each primitive appears in `jaxpr`, nested calls
    included (`into`: a `Counter`)."""
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, into)
    return into


@pytest.mark.parametrize("entry", ["lanes", "ids", "hashed", "mesh",
                                   "mesh_need_right"])
def test_counting_match_holds_no_gather(entry):
    """A gather over the sorted rows costs the chip 34-108 ms where a
    scan costs 1.75 (PERF.md, PR 35); a CPU run cannot see that, so the
    traced program is held to it. The hashed match carries its seven
    lanes (a marker and three int64 keys, q17's width) through its sort.
    The mesh match (`spmd._match`, over [S, T]) takes the same scans,
    with no flip round them; a full outer join gathers only the right
    rows' original ids."""
    import collections

    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.parallel import spmd

    ids_l, ids_r = jnp.arange(12, dtype=jnp.int32) % 5, jnp.arange(
        9, dtype=jnp.int32) % 4
    if entry.startswith("mesh"):
        S, Cl, Cr = 3, 4, 3
        no = jnp.zeros((S, Cl), bool), jnp.zeros((S, Cr), bool)
        full_outer = entry == "mesh_need_right"
        jaxpr = jax.make_jaxpr(lambda a, b, g: spmd._match(
            [a], [b], *no, *no, g, left_outer=full_outer,
            need_right=full_outer))(
            ids_l.reshape(S, Cl), ids_r.reshape(S, Cr),
            jnp.arange(S * Cr, dtype=jnp.int64).reshape(S, Cr))
    elif entry == "lanes":
        lanes_l = (jnp.zeros(12, jnp.int32), ids_l)
        lanes_r = (jnp.zeros(9, jnp.int32), ids_r)
        jaxpr = jax.make_jaxpr(lambda a, b: join._counting_match_lanes(
            a, b, left_outer=True))(lanes_l, lanes_r)
    elif entry == "hashed":
        lanes_l = (jnp.zeros(12, jnp.int32),
                   *[ids_l + k for k in range(3)],
                   *[ids_l.astype(jnp.uint32) * k for k in range(3)])
        lanes_r = (jnp.zeros(9, jnp.int32),
                   *[ids_r + k for k in range(3)],
                   *[ids_r.astype(jnp.uint32) * k for k in range(3)])
        assert len(lanes_l) == 7 >= join.HASH_MATCH_MIN_LANES
        jaxpr = jax.make_jaxpr(
            lambda a, b: join._counting_match_lanes_hashed(
                a, b, left_outer=True))(lanes_l, lanes_r)
    else:
        jaxpr = jax.make_jaxpr(lambda a, b: join._counting_match(
            a, b, left_outer=True))(ids_l, ids_r)
    found = _primitives(jaxpr.jaxpr, collections.Counter())
    assert {"sort", "cumsum", "cummax", "cummin"} <= found.keys()  # inside
    gathers = sum(n for p, n in found.items() if "gather" in p)
    assert gathers == (1 if entry == "mesh_need_right" else 0), found
    assert "rev" not in found, found


# -- the counting join's expansion ------------------------------------------


def _repeat_expand(counts, starts, rights, rstart, orig_s, total,
                   left_outer):
    """The expansion as it was before it was sized by the pairs: a
    `jnp.repeat` of the sorted row numbers over their counts, then
    gathers of every slot's row out of the sorted arrays. Kept as the
    reference the new expansion must equal, bit for bit and in order."""
    import jax.numpy as jnp

    rows = jnp.repeat(jnp.arange(counts.shape[0], dtype=jnp.int32),
                      counts, total_repeat_length=total)
    slots = jnp.arange(total, dtype=starts.dtype)
    offset = (slots - jnp.take(starts, rows)).astype(jnp.int32)
    li = jnp.take(orig_s, rows)
    r_sorted_pos = jnp.clip(jnp.take(rstart, rows) + offset, 0,
                            orig_s.shape[0] - 1)
    ri = jnp.take(orig_s, r_sorted_pos)
    if left_outer:
        ri = jnp.where(jnp.take(rights, rows) > 0, ri, jnp.int32(-1))
    return li, ri


def _expand_cases():
    """name -> (left ids, right ids) of a counting match in id space."""
    rng = np.random.default_rng(39)
    fan = rng.integers(1, 51, 40)
    return {
        "one_to_one": (rng.permutation(1000)[:100], np.arange(1000)),
        "one_to_many": (np.arange(40), np.repeat(np.arange(40), fan)),
        "many_to_many": (rng.integers(0, 30, 300), rng.integers(0, 30, 200)),
        "unmatched_left": (rng.integers(0, 100, 200),
                           rng.integers(50, 150, 100)),
        "single_matched_row": (np.arange(100), np.array([37])),
        "every_row_matched": (np.arange(64) % 16, np.arange(16)),
        "rows_under_pow2": (rng.integers(0, 400, 523),
                            rng.integers(0, 400, 500)),
        "rows_over_pow2": (rng.integers(0, 400, 525),
                           rng.integers(0, 400, 500)),
    }


EXPAND_CASES = _expand_cases()


@pytest.fixture(params=["rank", "sort_carry", "sort_gather"])
def expand_path(request, monkeypatch):
    """Each way the expansion can go, forced through its cost models
    whatever they say of test sizes; the choice is made when the
    program is traced, so it traces afresh either side."""
    from hyperspace_tpu.ops import compact
    monkeypatch.setattr(compact, "_rank_select_wins",
                        lambda rows, size: request.param == "rank")
    monkeypatch.setattr(join, "_carry_wins",
                        lambda rows, size: request.param == "sort_carry")
    join._counting_expand.clear_cache()
    yield request.param
    join._counting_expand.clear_cache()


@pytest.mark.parametrize("how", ["inner", "left_outer"])
@pytest.mark.parametrize("case", sorted(EXPAND_CASES))
def test_counting_expand_is_the_repeat_expansion(case, how, expand_path):
    import jax.numpy as jnp

    l_ids, r_ids = EXPAND_CASES[case]
    if case.startswith("rows_"):
        assert len(l_ids) + len(r_ids) in (1023, 1025)
    left_outer = how == "left_outer"
    match = join._counting_match(jnp.asarray(l_ids, jnp.int32),
                                 jnp.asarray(r_ids, jnp.int32), left_outer)
    total = int(jnp.sum(match[0]))
    got = join._counting_expand(*match, total=total, left_outer=left_outer)
    want = _repeat_expand(*match, total, left_outer)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if left_outer and case == "unmatched_left":
        assert (np.asarray(got[1]) == -1).any()


def _wide_sides(seed, n, m):
    """Two int64 keys a side: five lanes, so the hashed match serves."""
    rng = np.random.default_rng(seed)

    def side(rows):
        return columnar.from_arrow(pa.table({
            "k1": rng.integers(0, 20, rows).astype(np.int64),
            "k2": rng.integers(0, 10, rows).astype(np.int64)}), device=True)
    return side(n), side(m)


@pytest.mark.parametrize("how", ["inner", "left_outer"])
@pytest.mark.parametrize("match", ["exact", "hashed", "hashed-fallback"])
def test_counting_join_pairs_are_the_repeat_expansions(match, how):
    """Through `counting_join_batch_indices`: whichever match served it,
    the pairs are the repeat expansion of that match's counts, in
    order, and the expansion's fill is in the registry."""
    from hyperspace_tpu import telemetry
    from hyperspace_tpu.ops import hash_partition as hp

    left, right = _wide_sides(139, 1_500, 1_200)
    left_outer = how == "left_outer"
    lanes = join._join_lane_operands(left, right, ["k1", "k2"],
                                     ["k1", "k2"])
    fill = telemetry.get_registry().histogram("join.expand.fill")
    before = fill.count
    orig = hp._fmix32
    old_lanes = join.HASH_MATCH_MIN_LANES
    if match == "exact":
        join.HASH_MATCH_MIN_LANES = 10 ** 9
    elif match == "hashed-fallback":
        join._counting_match_lanes_hashed.clear_cache()
        hp._fmix32 = lambda h: h * 0  # every key has one hash
    try:
        got = join.counting_join_batch_indices(
            left, right, ["k1", "k2"], ["k1", "k2"], how=how)
        if match == "hashed":
            served = join._counting_match_lanes_hashed(*lanes, left_outer)
            assert not bool(served[-1])  # no collision
            served = served[:-1]
        else:
            served = join._counting_match_lanes(*lanes, left_outer)
    finally:
        hp._fmix32 = orig
        join.HASH_MATCH_MIN_LANES = old_lanes
        join._counting_match_lanes_hashed.clear_cache()
    total = int(np.asarray(served[0]).sum())
    want = _repeat_expand(*served, total, left_outer)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert fill.count == before + 1
    assert fill.max >= total / (1_500 + 1_200) > 0


@pytest.mark.parametrize("path", ["rank", "select"])
def test_counting_expand_moves_nothing_by_the_sorted_rows(path,
                                                           monkeypatch):
    """A scatter or a gather costs a v5e one serialised step per element
    (PERF.md section 6), so the expansion's are held to the pairs: no
    scatter of more updates and no gather of more indices than there
    are pairs, over 5,000 sorted rows for 625 pairs. A CPU run cannot
    time that, so the traced program is held to it."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact

    monkeypatch.setattr(compact, "_rank_select_wins",
                        lambda rows, size: path == "rank")
    rows = 5_000
    at = jnp.arange(rows, dtype=jnp.int32)
    counts = jnp.where(at % 16 == 0, 1 + at % 3, 0).astype(jnp.int32)
    total = int(counts.sum())
    starts = jnp.cumsum(counts) - counts
    join._counting_expand.clear_cache()  # the path is chosen as it traces
    try:
        jaxpr = jax.make_jaxpr(lambda *a: join._counting_expand(
            *a, total=total, left_outer=True))(counts, starts, counts, at, at)
    finally:
        join._counting_expand.clear_cache()
    moved = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "scatter" in eqn.primitive.name:
                moved.append(("scatter", eqn.invars[2].aval.shape[0]))
            elif eqn.primitive.name == "gather":
                moved.append(("gather", eqn.invars[1].aval.shape[0]))
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jaxpr.jaxpr)
    kinds = {kind for kind, _ in moved}
    assert kinds == ({"gather"} if path == "rank"
                     else {"gather", "scatter"}), moved
    assert all(n <= total < rows for _, n in moved), moved
