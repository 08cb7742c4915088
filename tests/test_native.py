"""Native C++ host-lane kernels vs their numpy reference semantics.

The native radix sort (`native.bucket_key_sort_perm`) IS the index-build
host lane (`io/builder._host_build_permutation`); these tests pin it
bit-for-bit to the np.lexsort reference the lane falls back to, so the
on-disk layout can never depend on which engine computed the permutation.
"""

import numpy as np
import pytest

from hyperspace_tpu import native


pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="native library unavailable")


def _ref_perm(bucket, lanes):
    return np.lexsort(tuple(reversed([bucket] + list(lanes))))


def _ref_bounds(bucket, perm, num_buckets):
    sb = bucket[perm]
    return (np.searchsorted(sb, np.arange(num_buckets), "left"),
            np.searchsorted(sb, np.arange(num_buckets), "right"))


def _check(bucket, num_buckets, lanes):
    out = native.bucket_key_sort_perm(bucket, num_buckets, lanes)
    assert out is not None
    perm, starts, ends = out
    ref = _ref_perm(bucket, lanes)
    np.testing.assert_array_equal(perm, ref)
    rs, re = _ref_bounds(bucket, ref, num_buckets)
    np.testing.assert_array_equal(starts, rs)
    np.testing.assert_array_equal(ends, re)


def test_single_int64_key_lanes():
    rng = np.random.default_rng(7)
    n = 100_000
    key = rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)
    bucket = rng.integers(0, 32, n).astype(np.int32)
    lanes = [(key >> 32).astype(np.int32),
             (key & 0xFFFFFFFF).astype(np.uint32)]
    _check(bucket, 32, lanes)


def test_small_range_keys_skip_passes():
    rng = np.random.default_rng(8)
    n = 50_000
    key = rng.integers(0, 1000, n, dtype=np.int64)  # constant hi digits
    bucket = rng.integers(0, 8, n).astype(np.int32)
    lanes = [(key >> 32).astype(np.int32),
             (key & 0xFFFFFFFF).astype(np.uint32)]
    _check(bucket, 8, lanes)


def test_stability_ties_keep_input_order():
    n = 10_000
    bucket = np.zeros(n, dtype=np.int32)
    lane = np.full(n, 42, dtype=np.uint32)
    out = native.bucket_key_sort_perm(bucket, 4, [lane])
    perm, starts, ends = out
    np.testing.assert_array_equal(perm, np.arange(n, dtype=np.int32))
    assert starts[0] == 0 and ends[0] == n and ends[3] == n


def test_odd_lane_count_with_validity():
    rng = np.random.default_rng(9)
    n = 30_000
    bucket = rng.integers(0, 16, n).astype(np.int32)
    validity = rng.random(n) > 0.1  # bool lane leads (nulls first)
    lane = rng.integers(0, 1 << 31, n).astype(np.int32)
    _check(bucket, 16, [validity, lane])


def test_multi_key_four_lanes():
    rng = np.random.default_rng(10)
    n = 40_000
    bucket = rng.integers(0, 64, n).astype(np.int32)
    k1 = rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
    k2 = rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
    lanes = [(k1 >> 32).astype(np.int32), (k1 & 0xFFFFFFFF).astype(np.uint32),
             (k2 >> 32).astype(np.int32), (k2 & 0xFFFFFFFF).astype(np.uint32)]
    _check(bucket, 64, lanes)


def test_empty_and_tiny():
    _check(np.empty(0, dtype=np.int32), 4, [np.empty(0, dtype=np.uint32)])
    _check(np.zeros(1, dtype=np.int32), 1, [np.zeros(1, dtype=np.uint32)])


def test_signed_lane_ordering():
    # Signed int32 lanes must order negatives before positives after the
    # uint32 bias — exactly lexsort's int32 order.
    bucket = np.zeros(6, dtype=np.int32)
    lane = np.array([5, -3, 0, -(1 << 31), (1 << 31) - 1, -1],
                    dtype=np.int32)
    _check(bucket, 1, [lane])


def test_builder_host_permutation_uses_native_layout():
    """End-to-end: `_host_build_permutation` (native lane) must produce
    the identical layout the lexsort reference produces."""
    import pyarrow as pa

    from hyperspace_tpu.io.builder import _host_build_permutation

    rng = np.random.default_rng(11)
    n = 25_000
    table = pa.table({
        "key": rng.integers(0, n // 3, n).astype(np.int64),
        "val": rng.random(n),
    })
    chunks, starts, ends = _host_build_permutation(table, ["key"], 16)
    assert len(chunks) == 1
    perm = np.asarray(chunks[0])

    from hyperspace_tpu.io import columnar
    from hyperspace_tpu.ops.host_hash import (host_column_hash_lanes,
                                              host_flat_hash32)
    from hyperspace_tpu.ops.keys import host_column_sort_lanes
    batch = columnar.from_arrow(table.select(["key"]), device=False)
    bucket = (host_flat_hash32(host_column_hash_lanes(batch.column("key")))
              % np.uint32(16)).astype(np.int32)
    ref = _ref_perm(bucket, host_column_sort_lanes(batch.column("key")))
    np.testing.assert_array_equal(perm, ref)


def test_library_older_than_its_source_counts_as_stale(tmp_path,
                                                       monkeypatch):
    """`get_lib` must not prefer a leftover git-ignored .so over the
    committed source: older than `hyperspace_host.cpp` means rebuild."""
    import os

    so, src = tmp_path / "lib.so", tmp_path / "host.cpp"
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    src.write_text("// source")
    assert native._stale()  # no library yet
    so.write_bytes(b"")
    os.utime(so, (1_000, 1_000))
    os.utime(src, (2_000, 2_000))
    assert native._stale()
    os.utime(so, (3_000, 3_000))
    assert not native._stale()
