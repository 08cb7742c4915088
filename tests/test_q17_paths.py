"""What TPC-DS q17 runs that no earlier cell did (PR 36): the hashed
counting match of three int64 keys and the hashed grouping sort, each
against its full-lane path and under a forced hash collision that shows
the fallback and its counter; avg and stddev_samp of integer columns
exact to the bit on both lanes; the join and scan records and spans
that say which lane and which match served a query."""

from fractions import Fraction
import math

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu import IndexConfig, col, lit, telemetry
from hyperspace_tpu.io import columnar
from hyperspace_tpu.ops import aggregate as agg_mod
from hyperspace_tpu.ops import hash_partition as hp
from hyperspace_tpu.ops import join as join_mod
from hyperspace_tpu.plan.nodes import AggSpec
from hyperspace_tpu.plan.schema import Field, Schema
from hyperspace_tpu.telemetry import profiler

from span_seam_helpers import env, hs_events  # noqa: F401


def _counter(name: str) -> int:
    return int(telemetry.get_registry().counters_dict().get(name, 0))


def _three_key_sides(seed: int, n: int, m: int):
    """Left and right of a (customer, item, ticket) join: int64 keys,
    six 32-bit lanes and a marker, so the hashed match serves."""
    rng = np.random.default_rng(seed)

    def side(rows):
        return columnar.from_arrow(pa.table({
            "c": rng.integers(0, 40, rows).astype(np.int64),
            "i": rng.integers(0, 30, rows).astype(np.int64) * 2 ** 33,
            "t": rng.integers(-9, 9, rows).astype(np.int64)}), device=True)
    return side(n), side(m)


def _pairs(li, ri):
    return sorted(zip(np.asarray(li).tolist(), np.asarray(ri).tolist()))


@pytest.mark.parametrize("how", ["inner", "left_outer"])
def test_three_key_hashed_match_equals_the_full_lane_sort(how):
    left, right = _three_key_sides(36, 4_001, 3_003)
    assert 7 >= join_mod.HASH_MATCH_MIN_LANES
    before = _counter("join.hashed.fallbacks")
    got = join_mod.counting_join_batch_indices(
        left, right, ["c", "i", "t"], ["c", "i", "t"], how=how)
    assert _counter("join.hashed.fallbacks") == before
    old = join_mod.HASH_MATCH_MIN_LANES
    join_mod.HASH_MATCH_MIN_LANES = 10 ** 9
    try:
        want = join_mod.counting_join_batch_indices(
            left, right, ["c", "i", "t"], ["c", "i", "t"], how=how)
    finally:
        join_mod.HASH_MATCH_MIN_LANES = old
    assert _pairs(*got) == _pairs(*want) and len(_pairs(*got)) > 0


def test_a_forced_collision_reruns_the_match_and_counts_it():
    left, right = _three_key_sides(37, 2_003, 1_501)
    want = join_mod.counting_join_batch_indices(
        left, right, ["c", "i", "t"], ["c", "i", "t"])
    before = _counter("join.hashed.fallbacks")
    orig = hp._fmix32
    join_mod._counting_match_lanes_hashed.clear_cache()
    hp._fmix32 = lambda h: h * 0  # every key has one hash
    try:
        got = join_mod.counting_join_batch_indices(
            left, right, ["c", "i", "t"], ["c", "i", "t"])
    finally:
        hp._fmix32 = orig
        join_mod._counting_match_lanes_hashed.clear_cache()
    assert _counter("join.hashed.fallbacks") == before + 1
    assert _pairs(*got) == _pairs(*want)


def _np_fmix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _np_hash64(lanes):
    """numpy mirror of `hash_partition.dual_hash64` over 32-bit lanes."""
    def u32(lane):
        if lane.dtype == np.int32:
            return lane.view(np.uint32) ^ np.uint32(0x80000000)
        return lane.astype(np.uint32)

    def combine(a, b):
        return a ^ (b + np.uint32(0x9E3779B9) + (a << np.uint32(6))
                    + (a >> np.uint32(2)))

    salt = np.uint32(0x6A09E667)
    h1, h2 = _np_fmix32(u32(lanes[0])), _np_fmix32(u32(lanes[0]) ^ salt)
    for lane in lanes[1:]:
        h1 = combine(h1, _np_fmix32(u32(lane)))
        h2 = combine(h2, _np_fmix32(u32(lane) ^ salt))
    return (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)


def _hashed_match_reference(lanes_l, lanes_r, left_outer: bool):
    """The hashed match's pairs in its order, in numpy: rows sorted by
    (hash, side, row), runs of equal full keys, and each left row of a
    run paired with the run's right rows in sorted order (-1 for an
    unmatched left row of a left outer join)."""
    n = len(lanes_l[0])
    lanes = [np.concatenate([np.asarray(a), np.asarray(b)])
             for a, b in zip(lanes_l, lanes_r)]
    side = np.repeat([0, 1], [n, len(lanes_r[0])])
    orig = np.concatenate([np.arange(n), np.arange(len(lanes_r[0]))])
    order = np.lexsort((orig, side, _np_hash64(lanes)))
    keys = np.stack([lane[order] for lane in lanes], axis=1)
    side, orig = side[order], orig[order]
    cuts = np.flatnonzero((keys[1:] != keys[:-1]).any(axis=1)) + 1
    li, ri = [], []
    for run in np.split(np.arange(len(side)), cuts):
        rights = orig[run][side[run] == 1].tolist()
        for row in orig[run][side[run] == 0].tolist():
            if rights:
                li.extend([row] * len(rights))
                ri.extend(rights)
            elif left_outer:
                li.append(row)
                ri.append(-1)
    return np.array(li, dtype=np.int32), np.array(ri, dtype=np.int32)


@pytest.mark.parametrize("how", ["inner", "left_outer"])
def test_hashed_match_pairs_equal_a_numpy_reference_in_order(how):
    """The same (li, ri) arrays, element by element, as the reference:
    NULL keys on both sides (marker lanes 1 and 2) and 36 keys over
    3,500 rows, runs of about 85 rows of one key."""
    rng = np.random.default_rng(40)

    def side(rows):
        def key(domain, scale=1):
            values = rng.integers(0, domain, rows).astype(np.int64) * scale
            return pa.array(values, mask=rng.random(rows) < 0.04)
        return columnar.from_arrow(pa.table({
            "c": key(4), "i": key(3, 2 ** 33), "t": key(3, -1)}),
            device=True)
    left, right = side(2_001), side(1_503)
    keys = ["c", "i", "t"]
    lanes_l, lanes_r = join_mod._join_lane_operands(left, right, keys, keys)
    assert len(lanes_l) >= join_mod.HASH_MATCH_MIN_LANES
    assert set(np.asarray(lanes_l[0]).tolist()) == {0, 1}
    assert set(np.asarray(lanes_r[0]).tolist()) == {0, 2}
    before = _counter("join.hashed.fallbacks")
    li, ri = join_mod.counting_join_batch_indices(left, right, keys, keys,
                                                  how=how)
    assert _counter("join.hashed.fallbacks") == before
    want_li, want_ri = _hashed_match_reference(lanes_l, lanes_r,
                                               how == "left_outer")
    assert len(want_li) > 50_000
    np.testing.assert_array_equal(np.asarray(li), want_li)
    np.testing.assert_array_equal(np.asarray(ri), want_ri)


def _group_batch(seed: int, n: int):
    rng = np.random.default_rng(seed)
    table = pa.table({"a": rng.integers(0, 6, n).astype(np.int64),
                      "b": rng.integers(0, 5, n).astype(np.int64),
                      "c": rng.integers(0, 4, n).astype(np.int64),
                      "q": rng.integers(1, 101, n).astype(np.int64)})
    schema = Schema([Field(x, "int64", True) for x in "abc"]
                    + [Field("n", "int64", True), Field("s", "float64", True)])
    return table, schema


def test_a_forced_collision_regroups_and_counts_it():
    table, schema = _group_batch(38, 3_001)
    batch = columnar.from_arrow(table, device=True)
    specs = [AggSpec("count", "q", "n"), AggSpec("stddev", "q", "s")]
    want = columnar.to_arrow(agg_mod.group_aggregate(
        batch, ["a", "b", "c"], specs, schema)).to_pandas()
    before = _counter("aggregate.hashed.fallbacks")
    orig = hp._fmix32
    agg_mod._group_phase_a_hashed.clear_cache()
    hp._fmix32 = lambda h: h * 0
    try:
        got = columnar.to_arrow(agg_mod.group_aggregate(
            batch, ["a", "b", "c"], specs, schema)).to_pandas()
    finally:
        hp._fmix32 = orig
        agg_mod._group_phase_a_hashed.clear_cache()
    assert _counter("aggregate.hashed.fallbacks") == before + 1
    key = ["a", "b", "c"]
    assert got.sort_values(key).reset_index(drop=True).equals(
        want.sort_values(key).reset_index(drop=True))


def _exact(values):
    n, s, q = len(values), sum(values), sum(v * v for v in values)
    avg = float(Fraction(s, n))
    std = (math.sqrt(float(Fraction(n * q - s * s, n * (n - 1))))
           if n > 1 else None)
    return avg, std


@pytest.mark.parametrize("device", [True, False])
def test_integer_avg_and_stddev_are_sqls_value_to_the_bit(device):
    """Both lanes give each group's avg and stddev_samp as its exact
    integer moments rounded once to float64 (stddev: the variance, then
    its square root), and NULL where stddev_samp has one value."""
    rng = np.random.default_rng(39)
    n = 2_000
    table = pa.table({"g": rng.integers(0, 300, n).astype(np.int64),
                      "q": rng.integers(1, 101, n).astype(np.int64)})
    schema = Schema([Field("g", "int64", True), Field("a", "float64", True),
                     Field("s", "float64", True)])
    out = columnar.to_arrow(agg_mod.group_aggregate(
        columnar.from_arrow(table, device=device), ["g"],
        [AggSpec("avg", "q", "a"), AggSpec("stddev", "q", "s")], schema))
    g = table.column("g").to_numpy()
    q = table.column("q").to_numpy()
    got = out.to_pydict()
    assert any(v is None for v in got["s"])  # groups of one value
    for key, avg, std in zip(got["g"], got["a"], got["s"]):
        want_avg, want_std = _exact([int(v) for v in q[g == key]])
        assert avg == want_avg
        assert (std is None and want_std is None) or std == want_std


def test_moments_that_leave_53_bits_take_the_float_path():
    """Values near 2**52 summed over a group leave float64's exact
    integers: the float path serves, and stays close."""
    big = np.array([2 ** 52 + 3, 2 ** 52 + 7, 2 ** 52 + 11] * 4,
                   dtype=np.int64)
    table = pa.table({"g": np.repeat(np.arange(4, dtype=np.int64), 3),
                      "q": big})
    schema = Schema([Field("g", "int64", True), Field("a", "float64", True)])
    assert agg_mod._finish_exact("avg", np.array([3]), np.array([0]),
                                 np.array([0]), 2 ** 52) is None
    out = columnar.to_arrow(agg_mod.group_aggregate(
        columnar.from_arrow(table, device=True), ["g"],
        [AggSpec("avg", "q", "a")], schema)).to_pydict()
    assert np.allclose(out["a"], float(2 ** 52 + 7), rtol=1e-12)


def test_scan_spans_say_index_or_source_and_joins_their_match(env):
    """`hs.op.Scan` spans carry the index a scan reads, or its source
    directory; a global join's record says its lane, its match and its
    expansion's select."""
    hs, fact, dim, tmp = env
    hs.create_index(fact, IndexConfig("q17_fact", ["key"], ["qty"]))
    query = fact.filter((col("key") >= lit(10)) & (col("key") < lit(20))) \
        .select("key", "qty")
    join = dim.join(fact.select("key", "price"), on="key") \
        .select("grp", "price")
    with profiler.device_trace(str(tmp / "cap")):
        query.collect()
        _table, metrics = join.collect(with_metrics=True)
    scans = [e["stats"] for e in hs_events(tmp / "cap")
             if e["name"] == "hs.op.Scan"]
    assert {s.get("index") for s in scans} >= {"q17_fact"}
    assert {s.get("source") for s in scans} >= {"dim", "fact"}
    (smj,) = [op for op in metrics.operators if op.name == "SortMergeJoin"]
    assert smj.detail["lane"] == "device"
    assert smj.detail["match"] == "exact" and smj.detail["keys"] == 1
    assert smj.detail["expand"] == "select"  # few pairs, not too few
    assert (smj.detail["left_rows"], smj.detail["right_rows"]) == (150, 6000)
