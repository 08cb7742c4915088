"""Born-sharded SPMD execution (`parallel/spmd.py`): bit-identity with
the single-device operators at 1/2/4/8 virtual devices, the in-program
mismatched-bucket repartition, the expansion sized by its match, the
per-device segment-cache read path, and the device-resident stage-flow
telemetry contract (zero D2H between stages of a warm two-stage SMJ)."""

import glob
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from hyperspace_tpu import telemetry
from hyperspace_tpu.io import columnar
from hyperspace_tpu.parallel import spmd
from hyperspace_tpu.parallel.build import distributed_build
from hyperspace_tpu.parallel.mesh import (bucket_owner, bucket_ranges,
                                          make_mesh, shard_row_segments)

from test_ops import BRACKET_CASES


def make_batch(n, seed=0, keyspace=None):
    rng = np.random.default_rng(seed)
    return columnar.from_arrow(pa.table({
        "k": rng.integers(0, keyspace or max(4, n // 8),
                          n).astype(np.int64),
        "v": rng.random(n).astype(np.float64),
    }))


def sharded_pair(n=1200, m=500, buckets=16, n_dev=8, seed=1,
                 keyspace=None):
    mesh = make_mesh(n_dev)
    left = make_batch(n, seed=seed, keyspace=keyspace)
    right = make_batch(m, seed=seed + 1, keyspace=keyspace)
    lb, ll = distributed_build(left, ["k"], buckets, mesh)
    rb, rl = distributed_build(right, ["k"], buckets, mesh)
    return (mesh, spmd.shard_bucket_ordered(lb, ll, mesh),
            spmd.shard_bucket_ordered(rb, rl, mesh), lb, rb, ll, rl)


def pairs_frame(lsh, rsh, li, ri):
    lk = np.asarray(lsh.batch.column("k").data)
    rk = np.asarray(rsh.batch.column("k").data)
    li, ri = np.asarray(li), np.asarray(ri)
    return pd.DataFrame({
        "lk": np.where(li >= 0, lk[np.clip(li, 0, None)], -1),
        "rk": np.where(ri >= 0, rk[np.clip(ri, 0, None)], -1),
    }).sort_values(["lk", "rk"]).reset_index(drop=True)


def oracle_frame(lb, rb, how):
    lpd = pd.DataFrame({"lk": np.asarray(lb.column("k").data)})
    rpd = pd.DataFrame({"rk": np.asarray(rb.column("k").data)})
    merged = lpd.assign(j=lpd.lk).merge(
        rpd.assign(j=rpd.rk), on="j",
        how={"inner": "inner", "left_outer": "left",
             "full_outer": "outer"}[how]).drop(columns="j")
    return (merged.fillna(-1).astype(np.int64)
            .sort_values(["lk", "rk"]).reset_index(drop=True))


def test_bucket_range_map_is_exact_inverse():
    for B, n in ((16, 8), (64, 8), (5, 2), (7, 3), (8, 1)):
        ranges = bucket_ranges(B, n)
        assert ranges[0][0] == 0 and ranges[-1][1] == B
        for s, (lo, hi) in enumerate(ranges):
            for b in range(lo, hi):
                assert bucket_owner(b, B, n) == s
        # contiguous, non-overlapping
        for s in range(1, n):
            assert ranges[s][0] == ranges[s - 1][1]


def test_shard_row_segments_cover_rows():
    lengths = np.asarray([3, 0, 5, 2, 7, 1, 0, 4], dtype=np.int64)
    segs = shard_row_segments(lengths, 4)
    assert segs[0][0] == 0 and segs[-1][1] == int(lengths.sum())
    for s in range(1, 4):
        assert segs[s][0] == segs[s - 1][1]


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_join_bit_identity_across_device_counts(n_dev):
    """SMJ over the born-sharded layout equals the single-chip bucketed
    join for every pair type, at every mesh size."""
    from hyperspace_tpu.ops.bucketed_join import bucketed_join_indices

    mesh, lsh, rsh, lb, rb, ll, rl = sharded_pair(n_dev=n_dev)
    for how in ("inner", "left_outer", "full_outer"):
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                           how=how)
        got = pairs_frame(lsh, rsh, li, ri)
        pd.testing.assert_frame_equal(got, oracle_frame(lb, rb, how))
    # membership
    lk = np.asarray(lb.column("k").data)
    member = np.isin(lk, np.asarray(rb.column("k").data))
    for anti in (False, True):
        idx = np.asarray(spmd.sharded_semi_anti_indices(
            lsh, rsh, ["k"], ["k"], anti=anti))
        keys = np.sort(np.asarray(lsh.batch.column("k").data)[idx])
        exp = np.sort(lk[~member if anti else member])
        assert (keys == exp).all(), f"anti={anti}"


@pytest.mark.parametrize("n_dev", [2, 8])
def test_filter_and_aggregate_bit_identity(n_dev):
    from hyperspace_tpu.engine.compiler import apply_filter
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    mesh = make_mesh(n_dev)
    batch = make_batch(2000, seed=7)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    sh = spmd.shard_bucket_ordered(built, lengths, mesh)

    pred = col("k") < lit(60)
    got = columnar.to_arrow(spmd.sharded_filter(sh, pred)).to_pandas()
    want = columnar.to_arrow(apply_filter(built, pred)).to_pandas()
    cols = list(got.columns)
    pd.testing.assert_frame_equal(
        got.sort_values(cols).reset_index(drop=True),
        want.sort_values(cols).reset_index(drop=True))

    schema = Schema.from_arrow(pa.table(
        {"k": np.zeros(1, np.int64), "v": np.zeros(1)}).schema)
    specs = [AggSpec("count", "*", "cnt"), AggSpec("sum", "v", "sv"),
             AggSpec("min", "v", "mn"), AggSpec("max", "v", "mx")]
    out_schema = Aggregate(["k"], specs, Scan(["/nx"], schema)).schema
    agg = spmd.sharded_group_aggregate(sh, ["k"], specs, out_schema)
    single = group_aggregate(built, ["k"], specs, out_schema)
    g = columnar.to_arrow(agg).to_pandas().sort_values("k") \
        .reset_index(drop=True)
    s = columnar.to_arrow(single).to_pandas().sort_values("k") \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(g, s, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_mismatched_bucket_counts_repartition_in_program():
    """The ranker's fallback: the right side arrives at HALF the bucket
    count and re-buckets over ICI inside the jitted program; results
    equal the equal-bucket join."""
    mesh = make_mesh(8)
    left = make_batch(900, seed=3)
    right = make_batch(400, seed=4)
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb8, rl8 = distributed_build(right, ["k"], 8, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh8 = spmd.shard_bucket_ordered(rb8, rl8, mesh)
    assert rsh8.num_buckets != lsh.num_buckets
    for how in ("inner", "left_outer"):
        li, ri = spmd.sharded_join_indices(lsh, rsh8, ["k"], ["k"],
                                           how=how)
        got = pairs_frame(lsh, rsh8, li, ri)
        pd.testing.assert_frame_equal(got, oracle_frame(lb, rb8, how))
    idx = np.asarray(spmd.sharded_semi_anti_indices(
        lsh, rsh8, ["k"], ["k"], anti=True))
    lk = np.asarray(lb.column("k").data)
    member = np.isin(lk, np.asarray(rb8.column("k").data))
    assert len(idx) == int((~member).sum())


def skewed_pair():
    """A hot key (70% of the left, half of the right) on one shard: the
    answer is larger than both inputs together."""
    mesh = make_mesh(4)
    n = 2000
    rng = np.random.default_rng(9)
    hot = np.where(rng.random(n) < 0.7, 7, rng.integers(0, 64, n))
    left = columnar.from_arrow(pa.table({
        "k": hot.astype(np.int64), "v": rng.random(n)}))
    right = columnar.from_arrow(pa.table({
        "k": np.where(rng.random(300) < 0.5, 7,
                      rng.integers(0, 64, 300)).astype(np.int64),
        "v": rng.random(300)}))
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 16, mesh)
    return (spmd.shard_bucket_ordered(lb, ll, mesh),
            spmd.shard_bucket_ordered(rb, rl, mesh), lb, rb)


def counter(name):
    return telemetry.get_registry().counters_dict().get(name, 0)


def fill_state():
    h = telemetry.get_registry().histogram("mesh.spmd.expand_fill")
    return h.count, h.sum


def join_spans(fn):
    """`fn()` under the ring tracer: (its result, the `hs.mesh.join.*`
    spans it emitted)."""
    telemetry.enable_tracing()
    try:
        out = fn()
        events = [e for e in telemetry.tracer().events
                  if e["name"].startswith("hs.mesh.join.")]
    finally:
        telemetry.disable_tracing()
    return out, events


def test_skewed_join_is_sized_by_its_answer():
    """A hot key whose pairs outnumber the input rows: ONE match
    dispatch, one read, and an expansion over the rung just above the
    fullest shard's total — exact, with nothing doubled and re-run."""
    lsh, rsh, lb, rb = skewed_pair()
    retries = counter("mesh.spmd.overflow_retries")
    fills = fill_state()
    (li, ri), events = join_spans(
        lambda: spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"]))
    assert counter("mesh.spmd.overflow_retries") == retries
    got = pairs_frame(lsh, rsh, li, ri)
    want = oracle_frame(lb, rb, "inner")
    pd.testing.assert_frame_equal(got, want)
    (sync,) = [e for e in events if e["name"] == "hs.mesh.join.sync"]
    (join,) = [e for e in events if e["name"] == "hs.mesh.join.spmd"]
    assert sync["args"]["attempt"] == 1
    cap, pairs = join["args"]["cap"], join["args"]["pairs"]
    hot_pairs = int((want.lk == 7).sum())
    assert hot_pairs > lsh.rows_per_shard + rsh.rows_per_shard
    assert hot_pairs <= pairs <= len(want)
    assert pairs <= cap < 2 * pairs and cap & (cap - 1) == 0
    count, total = fill_state()
    assert count == fills[0] + 1
    assert total - fills[1] == pytest.approx(pairs / cap)
    assert 0.5 < pairs / cap <= 1


@pytest.mark.parametrize("pairs,rung", [
    (1, 16), (16, 16), (17, 32), (23438, 32768), (32768, 32768),
    (32769, 65536), (3_000_000_000, 1 << 32)])
def test_the_expansions_ladder(pairs, rung):
    assert spmd._expand_rung(pairs) == rung


def _mesh_match_layout(case, S):
    """One of `test_ops.BRACKET_CASES` laid out as the mesh match's
    [S, Cl] / [S, Cr] inputs: row i on shard i % S, its side's slots
    in row order, then pad rows (two a side at least) whose lanes hold
    keys of real rows."""
    marker, value, side = (np.asarray(a) for a in BRACKET_CASES[case])
    rng = np.random.default_rng(41 + S)
    shard = np.arange(len(side)) % S
    slots = {(s, d): np.flatnonzero((shard == s) & (side == d))
             for s in range(S) for d in (0, 1)}
    Cl = max(len(slots[s, 0]) for s in range(S)) + 2
    Cr = max(len(slots[s, 1]) for s in range(S)) + 2
    lanes, null, pad = {}, {}, {}
    for d, C in ((0, Cl), (1, Cr)):
        lanes[d] = rng.choice(value, (S, C)).astype(np.int32)
        null[d] = np.zeros((S, C), bool)
        pad[d] = np.ones((S, C), bool)
        for s in range(S):
            rows = slots[s, d]
            lanes[d][s, :len(rows)] = value[rows]
            null[d][s, :len(rows)] = marker[rows] != 0
            pad[d][s, :len(rows)] = False
    r_gid = rng.permutation(S * Cr).astype(np.int64).reshape(S, Cr)
    return lanes, null, pad, r_gid


def _loop_mesh_match(lanes, null, pad, r_gid, left_outer, need_right):
    """Plain reference for `spmd._match`: per shard, sort the rows as
    its one sort does, then walk the key runs one by one."""
    S, Cl = lanes[0].shape
    Cr = lanes[1].shape[1]
    T = Cl + Cr
    out = {name: [] for name in ("starts", "rights", "rstart", "pos_s",
                                 "shard_total", "un_gid_sorted",
                                 "un_counts", "is_left", "matchable")}
    for s in range(S):
        key = np.concatenate([lanes[0][s], lanes[1][s]])
        nul = np.concatenate([null[0][s], null[1][s]])
        pd_ = np.concatenate([pad[0][s], pad[1][s]])
        side = np.repeat([0, 1], [Cl, Cr]).astype(np.int32)
        order = np.lexsort((np.arange(T), side, key, nul, pd_))
        key, nul, pd_, side = key[order], nul[order], pd_[order], side[order]
        rights = np.zeros(T, np.int32)
        rstart = np.zeros(T, np.int32)
        lefts = np.zeros(T, np.int32)
        first = 0
        while first < T:
            last = first
            while (last + 1 < T and not (nul[last] or nul[last + 1]
                                         or pd_[last] or pd_[last + 1])
                   and key[last + 1] == key[first]):
                last += 1
            in_run = int(side[first:last + 1].sum())
            rights[first:last + 1] = in_run
            rstart[first:last + 1] = last - in_run + 1
            lefts[first:last + 1] = last - first + 1 - in_run
            first = last + 1
        is_left = (side == 0) & ~pd_
        matchable = is_left & ~nul
        counts = np.where(matchable, rights, 0).astype(np.int64)
        if left_outer:
            counts = np.maximum(counts, is_left)
        out["starts"].append(np.cumsum(counts) - counts)
        out["shard_total"].append(counts.sum())
        out["rights"].append(rights)
        out["rstart"].append(rstart)
        out["pos_s"].append(order.astype(np.int32))
        out["is_left"].append(is_left)
        out["matchable"].append(matchable)
        if need_right:
            unmatched = (side == 1) & ~pd_ & (nul | (lefts == 0))
            gid = r_gid[s][np.clip(order - Cl, 0, Cr - 1)]
            un = gid[unmatched]
            out["un_gid_sorted"].append(np.concatenate(
                [un, np.full(T - len(un), -1, np.int64)]))
            out["un_counts"].append(len(un))
    return {name: np.stack(v) if v else None for name, v in out.items()}


@pytest.mark.parametrize("how", ["inner", "left_outer", "full_outer"])
@pytest.mark.parametrize("S", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(BRACKET_CASES))
def test_mesh_match_matches_a_loop_over_runs(case, S, how):
    """The mesh match's run brackets are the one-chip match's scans
    (`ops/join.run_brackets`) along each shard's row: its every output
    equals a walk over each shard's key runs, element by element, on an
    S-device mesh, with pad rows on both sides of every shard."""
    import jax

    from hyperspace_tpu.parallel.mesh import shard_rows

    lanes, null, pad, r_gid = _mesh_match_layout(case, S)
    left_outer, need_right = how != "inner", how == "full_outer"
    rows = shard_rows(make_mesh(S))
    args = jax.device_put(([lanes[0]], [lanes[1]], null[0], null[1],
                           pad[0], pad[1], r_gid), rows)
    got = jax.jit(spmd._match, static_argnums=(7, 8))(
        *args, left_outer, need_right)
    want = _loop_mesh_match(lanes, null, pad, r_gid, left_outer,
                            need_right)
    for name, g in zip(("starts", "rights", "rstart", "pos_s",
                        "shard_total", "un_gid_sorted", "un_counts",
                        "is_left", "matchable"), got):
        w = want[name]
        if w is None:
            assert g is None, name
            continue
        g = np.asarray(g)
        assert (g.shape, g.dtype) == (w.shape, w.dtype), name
        np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("how", ["inner", "full_outer"])
def test_a_join_with_no_pair_runs_no_expansion(how, monkeypatch):
    mesh = make_mesh(4)
    left = columnar.from_arrow(pa.table({
        "k": np.arange(0, 400, 2, dtype=np.int64), "v": np.zeros(200)}))
    right = columnar.from_arrow(pa.table({
        "k": np.arange(1, 241, 2, dtype=np.int64), "v": np.zeros(120)}))
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 16, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh)
    if how == "inner":
        monkeypatch.setattr(spmd, "_expand_program", None)  # never asked
    fills = fill_state()
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"], how=how)
    got = pairs_frame(lsh, rsh, li, ri)
    pd.testing.assert_frame_equal(got, oracle_frame(lb, rb, how))
    assert len(got) == (0 if how == "inner" else 320)
    if how == "inner":
        assert fill_state() == fills


def test_full_outer_keeps_the_unmatched_rights_after_the_pairs():
    mesh, lsh, rsh, lb, rb, _ll, _rl = sharded_pair(n=900, m=700,
                                                    keyspace=400)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                       how="full_outer")
    got = pairs_frame(lsh, rsh, li, ri)
    pd.testing.assert_frame_equal(got, oracle_frame(lb, rb, "full_outer"))
    li = np.asarray(li)
    unmatched = int((li < 0).sum())
    assert unmatched > 0 and (li[-unmatched:] < 0).all() \
        and (li[:-unmatched] >= 0).all()


def test_a_route_overflow_still_retries_the_match_exactly():
    """The right side re-buckets in-program and its hot key sends more
    rows to one peer than a route slab holds: the match is re-run with
    doubled slabs (the one retry left), the expansion runs once."""
    mesh = make_mesh(4)
    rng = np.random.default_rng(21)
    left = columnar.from_arrow(pa.table({
        "k": rng.integers(0, 64, 600).astype(np.int64),
        "v": rng.random(600)}))
    right = columnar.from_arrow(pa.table({
        "k": np.where(rng.random(400) < 0.7, 7,
                      rng.integers(0, 64, 400)).astype(np.int64),
        "v": rng.random(400)}))
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 8, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh)
    retries = counter("mesh.spmd.overflow_retries")
    fills = fill_state()
    (li, ri), events = join_spans(
        lambda: spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                          how="left_outer"))
    assert counter("mesh.spmd.overflow_retries") > retries
    pd.testing.assert_frame_equal(pairs_frame(lsh, rsh, li, ri),
                                  oracle_frame(lb, rb, "left_outer"))
    syncs = [e["args"]["attempt"] for e in events
             if e["name"] == "hs.mesh.join.sync"]
    assert syncs == list(range(1, len(syncs) + 1)) and len(syncs) > 1
    sized = [e["args"] for e in events
             if e["name"] == "hs.mesh.join.spmd" and "cap" in e["args"]]
    assert len(sized) == 1 and fill_state()[0] == fills[0] + 1
    before = counter("mesh.spmd.overflow_retries")
    idx = np.asarray(spmd.sharded_semi_anti_indices(
        lsh, rsh, ["k"], ["k"], anti=False))
    assert counter("mesh.spmd.overflow_retries") > before
    lk = np.asarray(lb.column("k").data)
    assert len(idx) == int(np.isin(
        lk, np.asarray(rb.column("k").data)).sum())


def test_answers_on_one_rung_share_one_compiled_expansion():
    """Two joins over the same layouts whose totals differ but fall on
    the same rung: the second traces neither a match nor an expansion."""
    mesh = make_mesh(4)
    rng = np.random.default_rng(33)
    k = rng.integers(0, 150, 1200).astype(np.int64)
    k2 = np.where(rng.random(1200) < 0.1, -1, k)  # a tenth match nothing
    left = columnar.from_arrow(pa.table({"k": k, "k2": k2}))
    right = make_batch(500, seed=34, keyspace=150)
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 16, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh)

    def run(key):
        (li, ri), events = join_spans(
            lambda: spmd.sharded_join_indices(lsh, rsh, [key], ["k"]))
        lk = np.asarray(lsh.batch.column(key).data)[np.asarray(li)]
        rk = np.asarray(rsh.batch.column("k").data)[np.asarray(ri)]
        assert (lk == rk).all()
        want = pd.DataFrame({"k": np.asarray(lb.column(key).data)}).merge(
            pd.DataFrame({"k": np.asarray(rb.column("k").data)}), on="k")
        assert len(lk) == len(want)
        (join,) = [e["args"] for e in events
                   if e["name"] == "hs.mesh.join.spmd"]
        return join["cap"], join["pairs"]

    names = ("compile.traces", "compile.mesh.spmd_join_match.traces",
             "compile.mesh.spmd_join_expand.traces",
             "compile.mesh.spmd_gather_i32.traces")
    cap, pairs = run("k")
    before = [counter(n) for n in names]
    cap2, pairs2 = run("k2")
    assert cap2 == cap and pairs2 < pairs
    traced, match, expand, gather = (
        counter(n) - b for n, b in zip(names, before))
    # the prefix gather alone is compiled per exact pair count, as ever
    assert (match, expand) == (0, 0) and traced == gather


@pytest.mark.parametrize("anti", [False, True])
def test_semi_anti_compile_no_expansion(anti, monkeypatch):
    mesh, lsh, rsh, lb, rb, _ll, _rl = sharded_pair(n=700, m=90,
                                                    keyspace=300)
    monkeypatch.setattr(spmd, "_expand_program", None)  # never asked
    fills = fill_state()
    expands = counter("compile.mesh.spmd_join_expand.traces")
    idx = np.asarray(spmd.sharded_semi_anti_indices(
        lsh, rsh, ["k"], ["k"], anti=anti))
    lk = np.asarray(lb.column("k").data)
    member = np.isin(lk, np.asarray(rb.column("k").data))
    assert len(idx) == int((~member if anti else member).sum())
    got = np.asarray(lsh.batch.column("k").data)[idx]
    assert sorted(got) == sorted(lk[~member if anti else member])
    assert fill_state() == fills
    assert counter("compile.mesh.spmd_join_expand.traces") == expands


def test_pad_blowup_guard():
    lengths = np.zeros(16, dtype=np.int64)
    lengths[3] = 1 << 17  # one hot bucket
    lengths[4:] = 1
    assert spmd.pad_blowup(lengths, 8)
    even = np.full(16, 1 << 13, dtype=np.int64)
    assert not spmd.pad_blowup(even, 8)


def test_warm_two_stage_smj_zero_d2h_between_stages():
    """Device-resident stage flow: join -> in-program repartition ->
    second join -> SPMD aggregate, with ZERO D2H link crossings between
    the stages (the engine-counted `link.d2h.*` series stays flat until
    result materialization). The aggregate's host combine IS the
    result's materialization: its [n_shards, G] partial tables cross
    once, counted as the one crossing they are (PR 27; uncounted
    before, so a mesh query's `link.d2h.bytes` read 0)."""
    from hyperspace_tpu.ops.bucketed_join import assemble_join_output
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    mesh, lsh, rsh, lb, rb, ll, rl = sharded_pair(n=1500, m=700,
                                                  seed=21)

    def pipeline(aggregate=True):
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
        joined = assemble_join_output(lsh.batch, rsh.batch, li, ri,
                                      how="inner")
        stage2 = spmd.repartition_sharded(joined, ["k"], 16, mesh)
        li2, ri2 = spmd.sharded_join_indices(stage2, rsh, ["k"], ["k"])
        j2 = assemble_join_output(stage2.batch, rsh.batch, li2, ri2,
                                  how="inner",
                                  columns=["k", "v", "v_r"])
        stage3 = spmd.repartition_sharded(j2, ["k"], 16, mesh)
        if not aggregate:
            return stage3
        schema = Schema.from_arrow(pa.table(
            {"k": np.zeros(1, np.int64), "v": np.zeros(1),
             "v_r": np.zeros(1)}).schema)
        specs = [AggSpec("count", "*", "cnt"),
                 AggSpec("sum", "v", "sv")]
        out_schema = Aggregate(["k"], specs,
                               Scan(["/nx"], schema)).schema
        return spmd.sharded_group_aggregate(stage3, ["k"], specs,
                                            out_schema)

    cold = columnar.to_arrow(pipeline()).to_pandas()
    reg = telemetry.get_registry()
    before = dict(reg.counters_dict())
    pipeline(aggregate=False)  # every stage, BEFORE materialization
    after = dict(reg.counters_dict())
    assert after.get("link.d2h.chunks", 0) == \
        before.get("link.d2h.chunks", 0), "a stage crossed D2H"
    assert after.get("link.d2h.bytes", 0) == \
        before.get("link.d2h.bytes", 0)
    warm_out = pipeline()
    final = dict(reg.counters_dict())
    assert final.get("link.d2h.transfers", 0) == \
        after.get("link.d2h.transfers", 0) + 1, "the partial tables, once"
    warm = columnar.to_arrow(warm_out).to_pandas()
    pd.testing.assert_frame_equal(
        cold.sort_values("k").reset_index(drop=True),
        warm.sort_values("k").reset_index(drop=True))


@pytest.fixture
def born_sharded_env(tmp_path, sample_parquet):
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.engine.session import HyperspaceSession
    from hyperspace_tpu.facade import Hyperspace

    conf = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "hyperspace.index.num.buckets": 8,
        "hyperspace.distribution.enabled": "true",
        "hyperspace.broadcast.threshold": -1,
    })
    session = HyperspaceSession(conf)
    return session, Hyperspace(session), sample_parquet


def test_born_sharded_build_layout_and_log_entry(born_sharded_env):
    """The mesh build writes per-device parquet shards (contiguous
    bucket ranges, shard-tagged filenames), the `_shard_layout.json`
    record, and the log entry carries the layout."""
    session, hs, src = born_sharded_env
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.io.builder import read_shard_layout

    df = session.read_parquet(src)
    hs.create_index(df, IndexConfig("born", ["clicks"], ["id"]))
    vdir = os.path.join(session.conf.system_path, "born", "v__=0")
    files = [os.path.basename(f)
             for f in glob.glob(os.path.join(vdir, "part-*.parquet"))]
    assert files and all("-s0" in f for f in files), files
    layout = read_shard_layout(vdir)
    assert layout is not None and layout["numShards"] == 8
    assert layout["bucketRanges"] == [[s, s + 1] for s in range(8)]
    entry = next(e for e in hs._manager.get_indexes()
                 if e.name == "born")
    assert entry.shard_layout == layout
    # Shard tag s matches the contiguous-range owner of the bucket id.
    from hyperspace_tpu.io.parquet import bucket_of_file
    for f in files:
        b = bucket_of_file(f)
        s = int(f.split("-s")[1][:2])
        assert bucket_owner(b, 8, 8) == s, f


def test_engine_smj_spmd_lane_and_warm_link_free(born_sharded_env):
    """The planner-selected bucketed SMJ rides the SPMD lane (counter
    pinned), warm repeats read per-device from the segment cache with
    ZERO H2D chunks, and results equal rules-off."""
    session, hs, src = born_sharded_env
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.io import segcache

    df = session.read_parquet(src)
    hs.create_index(df, IndexConfig("sjl", ["imprs"], ["id", "clicks"]))
    hs.create_index(df, IndexConfig("sjr", ["imprs"], ["score"]))
    left = df.select("imprs", "id", "clicks")
    right = df.select("imprs", "score")
    query = left.join(right, on="imprs")
    sort_cols = ["imprs", "id", "score"]

    session.disable_hyperspace()
    plain = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    session.enable_hyperspace()
    segcache.clear()
    reg = telemetry.get_registry()

    def counters():
        c = reg.counters_dict()
        return {k: c.get(k, 0) for k in
                ("mesh.spmd.join_execs", "link.h2d.chunks",
                 "cache.segments.hits")}

    c0 = counters()
    cold = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    c1 = counters()
    warm = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    c2 = counters()
    session.disable_hyperspace()

    assert c1["mesh.spmd.join_execs"] > c0["mesh.spmd.join_execs"], \
        "SPMD lane not taken"
    assert c2["link.h2d.chunks"] == c1["link.h2d.chunks"], \
        "warm per-device read crossed the link"
    assert c2["cache.segments.hits"] > c1["cache.segments.hits"]
    pd.testing.assert_frame_equal(plain, cold)
    pd.testing.assert_frame_equal(plain, warm)


def test_engine_string_smj_spmd_lane_fallback_free(born_sharded_env):
    """A STRING-keyed planner-selected SMJ runs the SPMD lane end to
    end — no per-query placement, no host fallback (`spmd.fallbacks`
    delta is 0), warm repeats link-free with remap tables served from
    the segment cache — and equals rules-off bit for bit."""
    session, hs, src = born_sharded_env
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.io import segcache

    df = session.read_parquet(src)
    hs.create_index(df, IndexConfig("strl", ["query"],
                                    ["id", "clicks"]))
    hs.create_index(df, IndexConfig("strr", ["query"], ["score"]))
    left = df.select("query", "id", "clicks")
    right = df.select("query", "score")
    query = left.join(right, on="query")
    sort_cols = ["query", "id", "score"]

    session.disable_hyperspace()
    plain = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    session.enable_hyperspace()
    segcache.clear()
    reg = telemetry.get_registry()

    def counters():
        c = reg.counters_dict()
        return {k: c.get(k, 0) for k in
                ("mesh.spmd.join_execs", "spmd.fallbacks",
                 "link.h2d.chunks", "spmd.strings.remap_cache_hits")}

    c0 = counters()
    cold = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    c1 = counters()
    warm = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    c2 = counters()
    session.disable_hyperspace()

    assert c1["mesh.spmd.join_execs"] > c0["mesh.spmd.join_execs"], \
        "string SMJ did not take the SPMD lane"
    assert c2["spmd.fallbacks"] == c0["spmd.fallbacks"], \
        "string join fell off the SPMD lane"
    assert c2["link.h2d.chunks"] == c1["link.h2d.chunks"], \
        "warm string join crossed the link"
    assert c2["spmd.strings.remap_cache_hits"] > \
        c1["spmd.strings.remap_cache_hits"]
    pd.testing.assert_frame_equal(plain, cold)
    pd.testing.assert_frame_equal(plain, warm)


def test_spmd_disabled_falls_back_to_single_chip(born_sharded_env):
    """`spark.hyperspace.distribution.spmd.enabled=false` is the
    operational escape hatch: with the legacy mesh path deleted, the
    bucketed SMJ runs single-chip, identical results."""
    session, hs, src = born_sharded_env
    from hyperspace_tpu.index.index_config import IndexConfig

    session.conf.set("spark.hyperspace.distribution.spmd.enabled",
                     "false")
    df = session.read_parquet(src)
    hs.create_index(df, IndexConfig("nsl", ["imprs"], ["id"]))
    hs.create_index(df, IndexConfig("nsr", ["imprs"], ["score"]))
    query = df.select("imprs", "id").join(df.select("imprs", "score"),
                                          on="imprs")
    session.disable_hyperspace()
    plain = query.to_pandas().sort_values(["imprs", "id", "score"]) \
        .reset_index(drop=True)
    session.enable_hyperspace()
    reg = telemetry.get_registry()
    before = reg.counters_dict().get("mesh.spmd.join_execs", 0)
    indexed = query.to_pandas().sort_values(["imprs", "id", "score"]) \
        .reset_index(drop=True)
    session.disable_hyperspace()
    assert reg.counters_dict().get("mesh.spmd.join_execs", 0) == before
    pd.testing.assert_frame_equal(plain, indexed)


def make_string_batch(n, seed=0, keyspace=80, null_frac=0.0,
                      prefix="key"):
    """String-keyed batch; `null_frac` > 0 inserts NULL keys,
    `keyspace` controls dictionary cardinality."""
    rng = np.random.default_rng(seed)
    keys = np.array([f"{prefix}{int(x):07d}"
                     for x in rng.integers(0, keyspace, n)])
    if null_frac:
        keys = np.where(rng.random(n) < null_frac, None, keys)
    return columnar.from_arrow(pa.table({
        "k": pa.array(list(keys)),
        "v": rng.random(n).astype(np.float64),
    }))


def string_sharded_pair(n_dev, n=900, m=400, buckets=16, seed=5,
                        keyspace=80, null_frac=0.0):
    mesh = make_mesh(n_dev)
    left = make_string_batch(n, seed=seed, keyspace=keyspace,
                             null_frac=null_frac)
    right = make_string_batch(m, seed=seed + 1, keyspace=keyspace)
    lb, ll = distributed_build(left, ["k"], buckets, mesh)
    rb, rl = distributed_build(right, ["k"], buckets, mesh)
    return (mesh, spmd.shard_bucket_ordered(lb, ll, mesh),
            spmd.shard_bucket_ordered(rb, rl, mesh), lb, rb, ll, rl)


def _string_values(batch, name="k"):
    col = batch.column(name)
    vals = np.asarray(col.dictionary)[np.asarray(col.data)]
    ok = (np.asarray(col.validity) if col.validity is not None
          else np.ones(len(vals), bool))
    return vals, ok


def string_pairs_frame(lsh, rsh, li, ri):
    lv, lo = _string_values(lsh.batch)
    rv, ro = _string_values(rsh.batch)
    li, ri = np.asarray(li), np.asarray(ri)
    lk = np.where(li >= 0,
                  np.where(lo[np.clip(li, 0, None)],
                           lv[np.clip(li, 0, None)], "~null"), "~none")
    rk = np.where(ri >= 0,
                  np.where(ro[np.clip(ri, 0, None)],
                           rv[np.clip(ri, 0, None)], "~null"), "~none")
    return pd.DataFrame({"lk": lk, "rk": rk}) \
        .sort_values(["lk", "rk"]).reset_index(drop=True)


def string_oracle_frame(lb, rb, how):
    lv, lo = _string_values(lb)
    rv, ro = _string_values(rb)
    lpd = pd.DataFrame({
        "lk": np.where(lo, lv, "~null"),
        "j": np.where(lo, lv, [f"__null{i}" for i in range(len(lv))])})
    rpd = pd.DataFrame({
        "rk": np.where(ro, rv, "~null"),
        "j": np.where(ro, rv,
                      [f"__rnull{i}" for i in range(len(rv))])})
    merged = lpd.merge(rpd, on="j", how={
        "inner": "inner", "left_outer": "left",
        "full_outer": "outer"}[how]).drop(columns="j")
    merged["lk"] = merged["lk"].fillna("~none")
    merged["rk"] = merged["rk"].fillna(
        "~none" if how == "left_outer" else "~none")
    # left_outer/full_outer: unmatched rows carry "~none" on the
    # missing side, EXCEPT null-key left rows which legitimately pair
    # with right "~none" too — the spmd frame reports unmatched as
    # "~none", so align: any row whose rk is NaN means no match.
    return merged.sort_values(["lk", "rk"]).reset_index(drop=True)


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_string_join_bit_identity_across_device_counts(n_dev):
    """String-keyed SMJ over born-sharded sides — per-range
    dictionaries unified by in-program rank remaps — equals the pandas
    oracle at every mesh size, NULL-bearing keys included."""
    mesh, lsh, rsh, lb, rb, ll, rl = string_sharded_pair(
        n_dev, null_frac=0.08)
    for how in ("inner", "left_outer", "full_outer"):
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                           how=how)
        got = string_pairs_frame(lsh, rsh, li, ri)
        want = string_oracle_frame(lb, rb, how)
        pd.testing.assert_frame_equal(got, want), how
    # membership (anti emits null-key left rows — NOT EXISTS)
    lv, lo = _string_values(lb)
    rv, _ro = _string_values(rb)
    member = np.isin(lv, rv) & lo
    for anti in (False, True):
        idx = np.asarray(spmd.sharded_semi_anti_indices(
            lsh, rsh, ["k"], ["k"], anti=anti))
        exp = int((~member).sum()) if anti else int(member.sum())
        assert len(idx) == exp, f"anti={anti}"


@pytest.mark.parametrize("n_dev", [2, 8])
def test_string_filter_and_aggregate_bit_identity(n_dev):
    """String predicate (code-space range test against the GLOBAL
    dictionary) and group-by-string aggregation over the sharded layout
    equal the single-device operators."""
    from hyperspace_tpu.engine.compiler import apply_filter
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    mesh = make_mesh(n_dev)
    batch = make_string_batch(1500, seed=11, keyspace=60,
                              null_frac=0.05)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    sh = spmd.shard_bucket_ordered(built, lengths, mesh)

    for pred in (col("k") < lit("key0000030"),
                 col("k") == lit("key0000007"),
                 col("k").isin("key0000001", "key0000002",
                               "no-such-key")):
        got = columnar.to_arrow(spmd.sharded_filter(sh, pred)) \
            .to_pandas()
        want = columnar.to_arrow(apply_filter(built, pred)).to_pandas()
        cols = list(got.columns)
        pd.testing.assert_frame_equal(
            got.sort_values(cols).reset_index(drop=True),
            want.sort_values(cols).reset_index(drop=True))

    schema = Schema.from_arrow(pa.table(
        {"k": np.array(["x"]), "v": np.zeros(1)}).schema)
    specs = [AggSpec("count", "*", "cnt"), AggSpec("sum", "v", "sv"),
             AggSpec("min", "v", "mn")]
    out_schema = Aggregate(["k"], specs, Scan(["/nx"], schema)).schema
    agg = spmd.sharded_group_aggregate(sh, ["k"], specs, out_schema)
    single = group_aggregate(built, ["k"], specs, out_schema)
    g = columnar.to_arrow(agg).to_pandas().sort_values("k") \
        .reset_index(drop=True)
    s = columnar.to_arrow(single).to_pandas().sort_values("k") \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(g, s, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_string_high_cardinality_dictionaries():
    """A dictionary with one entry per row (worst case for the remap
    tables) still joins exactly, through the in-program repartition
    path too (value-hash routing, not rank routing)."""
    mesh = make_mesh(4)
    _m, lsh, rsh, lb, rb, _ll, _rl = string_sharded_pair(
        4, n=1200, m=600, keyspace=1 << 20, seed=31)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
    got = string_pairs_frame(lsh, rsh, li, ri)
    pd.testing.assert_frame_equal(got,
                                  string_oracle_frame(lb, rb, "inner"))
    # mismatched bucket counts: right re-buckets in-program by VALUE
    # hash (the rank lanes are pair-local and must not route)
    right2 = make_string_batch(600, seed=32, keyspace=1 << 20)
    rb8, rl8 = distributed_build(right2, ["k"], 8, mesh)
    rsh8 = spmd.shard_bucket_ordered(rb8, rl8, mesh)
    li2, ri2 = spmd.sharded_join_indices(lsh, rsh8, ["k"], ["k"])
    got2 = string_pairs_frame(lsh, rsh8, li2, ri2)
    pd.testing.assert_frame_equal(got2,
                                  string_oracle_frame(lb, rb8, "inner"))


def test_string_warm_repeat_remaps_from_cache_zero_h2d(tmp_path):
    """The warm-repeat contract for strings: a second born-sharded read
    + string-keyed join serves BOTH the global dictionaries and the
    join's rank-remap tables from the segment cache — zero H2D chunks,
    `spmd.strings.remap_cache_hits` advancing, results identical."""
    from hyperspace_tpu.io import builder, parquet, segcache
    from hyperspace_tpu.io.segcache import SegmentRef
    from hyperspace_tpu.parallel.mesh import bucket_ranges

    mesh = make_mesh(4)
    left = make_string_batch(800, seed=41, keyspace=120,
                             null_frac=0.05)
    right = make_string_batch(300, seed=42, keyspace=120)
    roots = {}
    lengths_map = {}
    for tag, batch in (("l", left), ("r", right)):
        built, lengths = distributed_build(batch, ["k"], 16, mesh)
        root = str(tmp_path / tag)
        builder.write_bucket_ordered(built, lengths, 16, root,
                                     mesh=mesh)
        roots[tag] = root
        lengths_map[tag] = (lengths, built.schema)
        layout = builder.read_shard_layout(root)
        assert layout is not None and "dictionaries" in layout
        assert len(layout["dictionaries"]["k"]) == 4  # one per range

    segcache.clear()

    def read(tag):
        lengths, schema = lengths_map[tag]
        per_bucket = parquet.bucket_files(roots[tag])
        per_shard = [[f for b in range(lo, hi)
                      for f in per_bucket.get(b, [])]
                     for lo, hi in bucket_ranges(16, 4)]
        ref = SegmentRef(index_name=f"str_{tag}", index_root=roots[tag],
                         version=0, bucket="t")
        return spmd.read_sharded(per_shard, lengths,
                                 [f.name for f in schema.fields],
                                 schema, mesh, base_ref=ref)

    def join_once():
        lsh = read("l")
        rsh = read("r")
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
        return string_pairs_frame(lsh, rsh, li, ri)

    reg = telemetry.get_registry()
    cold = join_once()
    c0 = dict(reg.counters_dict())
    warm = join_once()
    c1 = dict(reg.counters_dict())
    assert c1.get("link.h2d.chunks", 0) == c0.get("link.h2d.chunks", 0), \
        "warm string read/join crossed the link"
    assert c1.get("spmd.strings.remap_cache_hits", 0) > \
        c0.get("spmd.strings.remap_cache_hits", 0), \
        "remap tables not served from the segment cache"
    pd.testing.assert_frame_equal(cold, warm)


def test_segcache_get_or_fill_invalidation():
    """Per-range entries ride the index-FSM invalidation hooks: a
    version commit under the same root drops them; the single-flight
    contract serves concurrent fills one decode."""
    import threading

    from hyperspace_tpu.io import segcache

    cache = segcache.SegmentCache(budget_bytes=1 << 30)
    ref = segcache.SegmentRef("idx", "/tmp/idx_root", 0, "mc")
    fills = []

    def fill():
        fills.append(1)
        return {"columns": {}, "rows": 1}, 1024

    key = ref.key + (("spmd", 0, 4, 4, 10),)
    results = []

    def worker():
        results.append(cache.get_or_fill(key, fill, ref=ref))

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(fills) == 1, "single-flight violated"
    assert all(r is results[0] for r in results)
    assert cache.get_or_fill(key, fill, ref=ref) is results[0]
    assert len(fills) == 1
    # FSM hook: a new committed version under the root evicts the range.
    cache.invalidate_index("/tmp/idx_root", keep_version=1)
    cache.get_or_fill(key, fill, ref=ref)
    assert len(fills) == 2


# ---------------------------------------------------------------------------
# Multi-slice (slice, device) topologies — PR 14
# ---------------------------------------------------------------------------


def topo_mesh(slices, ici):
    return make_mesh(slices * ici, dcn_size=slices if slices > 1 else None)


def test_slice_hierarchy_nests_exactly():
    """`slice_bucket_ranges` equals the union of each slice's flat shard
    ranges — the nesting identity layout v3 and replica residency rely
    on — and `slice_submesh` carves the right device rows."""
    from hyperspace_tpu.parallel.mesh import (mesh_device_list,
                                              slice_bucket_ranges,
                                              slice_submesh)

    for B, slices, ici in ((64, 2, 4), (64, 4, 2), (16, 2, 4), (7, 2, 2)):
        flat = bucket_ranges(B, slices * ici)
        for d, (lo, hi) in enumerate(slice_bucket_ranges(B, slices, ici)):
            assert lo == flat[d * ici][0]
            assert hi == flat[(d + 1) * ici - 1][1]
    mesh = topo_mesh(2, 4)
    full = mesh_device_list(mesh)
    for idx in range(2):
        sub = slice_submesh(mesh, idx)
        assert mesh_device_list(sub) == full[idx * 4:(idx + 1) * 4]


@pytest.mark.parametrize("slices,ici", [(1, 8), (2, 4), (4, 2)])
def test_multislice_join_bit_identity(slices, ici):
    """Join/semi/anti over a (slice, device) topology equal the flat
    oracle at every hierarchy shape — the flat mesh is the degenerate
    1-slice case, bit-identical."""
    mesh = topo_mesh(slices, ici)
    left = make_batch(1200, seed=1)
    right = make_batch(500, seed=2)
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 16, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh)
    for how in ("inner", "left_outer", "full_outer"):
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                           how=how)
        got = pairs_frame(lsh, rsh, li, ri)
        pd.testing.assert_frame_equal(got, oracle_frame(lb, rb, how))
    lk = np.asarray(lb.column("k").data)
    member = np.isin(lk, np.asarray(rb.column("k").data))
    for anti in (False, True):
        idx = np.asarray(spmd.sharded_semi_anti_indices(
            lsh, rsh, ["k"], ["k"], anti=anti))
        exp = int((~member).sum()) if anti else int(member.sum())
        assert len(idx) == exp, f"anti={anti}"


@pytest.mark.parametrize("slices,ici", [(2, 4), (4, 2)])
def test_multislice_repartition_crosses_dcn(slices, ici):
    """Mismatched bucket counts on a 2-axis mesh: the in-program
    repartition routes key lanes hierarchically (ICI within the slice,
    one DCN hop across), results equal the co-bucketed join, and the
    exchange volume is attributed to BOTH axes with the DCN share at
    the per-row hierarchy bound (~1/2, each row crosses DCN at most
    once)."""
    mesh = topo_mesh(slices, ici)
    left = make_batch(900, seed=3)
    right = make_batch(400, seed=4)
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb8, rl8 = distributed_build(right, ["k"], 8, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh8 = spmd.shard_bucket_ordered(rb8, rl8, mesh)
    reg = telemetry.get_registry()
    before = {k: reg.counters_dict().get(k, 0)
              for k in ("spmd.repartition.ici.bytes",
                        "spmd.repartition.dcn.bytes")}
    li, ri = spmd.sharded_join_indices(lsh, rsh8, ["k"], ["k"])
    got = pairs_frame(lsh, rsh8, li, ri)
    pd.testing.assert_frame_equal(got, oracle_frame(lb, rb8, "inner"))
    after = {k: reg.counters_dict().get(k, 0)
             for k in ("spmd.repartition.ici.bytes",
                       "spmd.repartition.dcn.bytes")}
    ici_b = after["spmd.repartition.ici.bytes"] \
        - before["spmd.repartition.ici.bytes"]
    dcn_b = after["spmd.repartition.dcn.bytes"] \
        - before["spmd.repartition.dcn.bytes"]
    assert ici_b > 0 and dcn_b > 0
    assert dcn_b / (ici_b + dcn_b) <= 0.6


@pytest.mark.parametrize("slices,ici", [(2, 4), (4, 2)])
def test_multislice_string_filter_aggregate(slices, ici):
    """String-keyed SMJ, predicate filter, and group aggregate over a
    2-axis mesh equal the single-device operators (string keys ride the
    same hierarchy: rank remaps in-program, value-hash routing across
    DCN)."""
    from hyperspace_tpu.engine.compiler import apply_filter
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.plan.expr import col, lit
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    mesh = topo_mesh(slices, ici)
    left = make_string_batch(900, seed=5, keyspace=80, null_frac=0.08)
    right = make_string_batch(400, seed=6, keyspace=80)
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    rb, rl = distributed_build(right, ["k"], 16, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
    got = string_pairs_frame(lsh, rsh, li, ri)
    pd.testing.assert_frame_equal(got,
                                  string_oracle_frame(lb, rb, "inner"))

    batch = make_batch(2000, seed=7)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    sh = spmd.shard_bucket_ordered(built, lengths, mesh)
    pred = col("k") < lit(60)
    gotf = columnar.to_arrow(spmd.sharded_filter(sh, pred)).to_pandas()
    want = columnar.to_arrow(apply_filter(built, pred)).to_pandas()
    cols = list(gotf.columns)
    pd.testing.assert_frame_equal(
        gotf.sort_values(cols).reset_index(drop=True),
        want.sort_values(cols).reset_index(drop=True))
    schema = Schema.from_arrow(pa.table(
        {"k": np.zeros(1, np.int64), "v": np.zeros(1)}).schema)
    specs = [AggSpec("count", "*", "cnt"), AggSpec("sum", "v", "sv")]
    out_schema = Aggregate(["k"], specs, Scan(["/nx"], schema)).schema
    g = columnar.to_arrow(spmd.sharded_group_aggregate(
        sh, ["k"], specs, out_schema)).to_pandas() \
        .sort_values("k").reset_index(drop=True)
    s = columnar.to_arrow(group_aggregate(
        built, ["k"], specs, out_schema)).to_pandas() \
        .sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(g, s, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_shard_layout_v3_records_hierarchy(tmp_path):
    """A multi-slice build's `_shard_layout.json` records the
    hierarchy: version 3, numSlices, and slice-level ranges that nest
    exactly over the flat shard map."""
    from hyperspace_tpu.io import builder
    from hyperspace_tpu.parallel.mesh import slice_bucket_ranges

    mesh = topo_mesh(2, 4)
    batch = make_batch(800, seed=9)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    root = str(tmp_path / "ms")
    builder.write_bucket_ordered(built, lengths, 16, root, mesh=mesh)
    layout = builder.read_shard_layout(root)
    assert layout["version"] == 3
    assert layout["numSlices"] == 2
    assert layout["numShards"] == 8
    assert layout["sliceBucketRanges"] == \
        [[lo, hi] for lo, hi in slice_bucket_ranges(16, 2, 4)]


# ---------------------------------------------------------------------------
# Virtual sub-shards (hot-bucket skew) — PR 14
# ---------------------------------------------------------------------------


def test_subshard_plan_geometry():
    """Segments tile the row space; every row's bucket lies inside its
    shard's bucket span (the alignment invariant the replicated right
    read relies on)."""
    lengths = np.asarray([3, 0, 120, 5, 2, 0, 7, 1], dtype=np.int64)
    plan = spmd.subshard_plan(lengths, 4)
    total = int(lengths.sum())
    assert plan.segments[0][0] == 0
    assert plan.segments[-1][1] == total
    cum = np.concatenate([[0], np.cumsum(lengths)])
    for (lo, hi), (b_lo, b_hi) in zip(plan.segments, plan.bucket_spans):
        for s in range(1, 4):
            assert plan.segments[s][0] == plan.segments[s - 1][1]
        for row in range(lo, hi):
            b = int(np.searchsorted(cum, row, side="right")) - 1
            assert b_lo <= b < b_hi


def test_skewed_key_subshard_join_bit_identity(tmp_path):
    """THE skew pin: a hot key holding most of the rows trips
    `pad_blowup`, the read splits the hot range into virtual sub-shards
    (aligned right side replicating split buckets), and
    inner/left_outer/semi/anti all equal the pandas oracle — the lane
    that used to decline to single-chip now stays SPMD and exact."""
    from hyperspace_tpu.io import builder, parquet

    mesh = make_mesh(8)
    rng = np.random.default_rng(11)
    n = 24_000
    hot = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 4096, n))
    left = columnar.from_arrow(pa.table({
        "k": hot.astype(np.int64), "v": rng.random(n)}))
    right = columnar.from_arrow(pa.table({
        "k": np.concatenate([np.full(3, 7),
                             rng.integers(0, 4096, 300)]).astype(np.int64),
        "v": rng.random(303)}))
    data = {}
    for tag, batch in (("l", left), ("r", right)):
        built, lengths = distributed_build(batch, ["k"], 16, mesh)
        root = str(tmp_path / tag)
        builder.write_bucket_ordered(built, lengths, 16, root, mesh=mesh)
        data[tag] = (root, lengths, built)
    l_root, l_lengths, l_built = data["l"]
    r_root, r_lengths, r_built = data["r"]
    assert spmd.pad_blowup(l_lengths, 8)

    plan, l_specs = spmd.plan_skew_read(
        parquet.bucket_files(l_root), l_lengths, 8)
    r_specs = spmd.plan_aligned_read(
        parquet.bucket_files(r_root), r_lengths, plan)
    cols = [f.name for f in l_built.schema.fields]
    lsh = spmd.read_sharded([], l_lengths, cols, l_built.schema, mesh,
                            shard_specs=l_specs, split_plan=plan)
    rsh = spmd.read_sharded([], r_lengths, cols, r_built.schema, mesh,
                            shard_specs=r_specs)
    assert lsh.split_plan is plan
    # The split layout stays near the true rows instead of padding out
    # to the hot range (the decline the sub-shards exist to remove).
    assert lsh.rows_per_shard * 8 <= 2 * n

    for how in ("inner", "left_outer"):
        li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                           how=how)
        got = pairs_frame(lsh, rsh, li, ri)
        pd.testing.assert_frame_equal(got,
                                      oracle_frame(l_built, r_built, how))
    lk = np.asarray(l_built.column("k").data)
    member = np.isin(lk, np.asarray(r_built.column("k").data))
    for anti in (False, True):
        idx = np.asarray(spmd.sharded_semi_anti_indices(
            lsh, rsh, ["k"], ["k"], anti=anti))
        exp = int((~member).sum()) if anti else int(member.sum())
        assert len(idx) == exp, f"anti={anti}"


def test_smj_right_only_skew_side_swap(tmp_path):
    """Right-side-ONLY skew (ISSUE 16 satellite): the planner-selected
    bucketed SMJ used to decline the SPMD lane when only the RIGHT
    scan's hot bucket tripped `pad_blowup` (replicating the left breaks
    outer/membership semantics). INNER has no unmatched-row semantics
    on either side, so the engine now swaps roles — re-reads the left
    aligned to the right's split and keeps the lane — bit-identical to
    rules-off, `mesh.spmd.side_swapped` pinned; a left_outer over the
    same shape still declines (`spmd.fallbacks`), identically correct."""
    import pyarrow.parquet as pq

    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.engine.session import HyperspaceSession
    from hyperspace_tpu.facade import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig

    rng = np.random.default_rng(19)
    left_dir = tmp_path / "left"
    right_dir = tmp_path / "right"
    left_dir.mkdir()
    right_dir.mkdir()
    pq.write_table(pa.table({
        "k": rng.integers(0, 4096, 2000).astype(np.int64),
        "v": rng.random(2000),
    }), str(left_dir / "part-0.parquet"))
    n = 24_000  # 90% on one hot key: C*S far past PAD_BLOWUP_FACTOR*n
    hot = np.where(rng.random(n) < 0.9, 7,
                   rng.integers(0, 4096, n)).astype(np.int64)
    pq.write_table(pa.table({
        "k": hot, "w": rng.random(n),
    }), str(right_dir / "part-0.parquet"))

    session = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "hyperspace.index.num.buckets": 8,
        "hyperspace.distribution.enabled": "true",
        "hyperspace.broadcast.threshold": -1,
    }))
    hs = Hyperspace(session)
    left = session.read_parquet(str(left_dir))
    right = session.read_parquet(str(right_dir))
    hs.create_index(left, IndexConfig("swl", ["k"], ["v"]))
    hs.create_index(right, IndexConfig("swr", ["k"], ["w"]))
    reg = telemetry.get_registry()
    sort_cols = ["k", "v", "w"]

    def run(how):
        q = left.join(right, on="k", how=how)
        session.disable_hyperspace()
        plain = q.to_pandas().sort_values(sort_cols) \
            .reset_index(drop=True)
        session.enable_hyperspace()
        got = q.to_pandas().sort_values(sort_cols) \
            .reset_index(drop=True)
        session.disable_hyperspace()
        session.enable_hyperspace()
        return plain, got

    c0 = reg.counters_dict().get("mesh.spmd.side_swapped", 0)
    plain, got = run("inner")
    c1 = reg.counters_dict().get("mesh.spmd.side_swapped", 0)
    assert c1 > c0, "inner right-skew join did not swap sides"
    pd.testing.assert_frame_equal(plain, got)

    f0 = reg.counters_dict().get("spmd.fallbacks", 0)
    plain, got = run("left")
    c2 = reg.counters_dict().get("mesh.spmd.side_swapped", 0)
    assert c2 == c1, "left_outer must not take the swapped lane"
    assert reg.counters_dict().get("spmd.fallbacks", 0) > f0
    pd.testing.assert_frame_equal(plain, got)


# ---------------------------------------------------------------------------
# String LIKE on the SPMD lane — PR 14
# ---------------------------------------------------------------------------


def test_sharded_filter_like_warm_link_free():
    """LIKE over the sharded layout: the dictionary-membership mask is
    computed once, cached in the segment cache, and a warm repeat is
    link-free with `spmd.strings.like_mask_cache_hits` advancing —
    results equal the host regex path bit for bit."""
    from hyperspace_tpu.engine.compiler import apply_filter
    from hyperspace_tpu.io import segcache
    from hyperspace_tpu.plan.expr import col

    mesh = make_mesh(4)
    batch = make_string_batch(1200, seed=13, keyspace=90,
                              null_frac=0.05)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    sh = spmd.shard_bucket_ordered(built, lengths, mesh)
    segcache.clear()
    pred = col("k").like("key00000_%")
    reg = telemetry.get_registry()

    want = columnar.to_arrow(apply_filter(built, pred)).to_pandas()
    cold = columnar.to_arrow(spmd.sharded_filter(sh, pred)).to_pandas()
    c0 = dict(reg.counters_dict())
    warm = columnar.to_arrow(spmd.sharded_filter(sh, pred)).to_pandas()
    c1 = dict(reg.counters_dict())
    assert c1.get("link.h2d.chunks", 0) == c0.get("link.h2d.chunks", 0), \
        "warm LIKE crossed the link"
    assert c1.get("spmd.strings.like_mask_cache_hits", 0) > \
        c0.get("spmd.strings.like_mask_cache_hits", 0)
    cols = list(want.columns)

    def norm(df):
        return df.sort_values(cols).reset_index(drop=True)

    pd.testing.assert_frame_equal(norm(cold), norm(want))
    pd.testing.assert_frame_equal(norm(warm), norm(want))


# ---------------------------------------------------------------------------
# Replica routing & coherence — PR 14
# ---------------------------------------------------------------------------


def test_replica_scope_confines_distribution_mesh():
    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.parallel import context
    from hyperspace_tpu.parallel.mesh import (dcn_size, mesh_device_list,
                                              total_shards)

    conf = HyperspaceConf({"hyperspace.distribution.enabled": "true",
                           "hyperspace.distribution.slices": 2})
    full = context.distribution_mesh(conf)
    assert dcn_size(full) == 2 and total_shards(full) == 8
    devices = mesh_device_list(full)
    with context.replica_scope(1):
        sub = context.distribution_mesh(conf)
        assert total_shards(sub) == 4
        assert mesh_device_list(sub) == devices[4:]
    assert context.active_replica() is None


def test_replica_residency_coherent_under_refresh(tmp_path):
    """Two replica slices fill INDEPENDENT cache entries for the same
    bucket ranges (device-tagged keys — no aliasing), a version
    invalidation sweeps BOTH replicas (coherence by construction), and
    re-reads serve identical data."""
    from hyperspace_tpu.io import builder, parquet, segcache
    from hyperspace_tpu.io.segcache import SegmentRef
    from hyperspace_tpu.parallel.mesh import slice_submesh

    mesh = topo_mesh(2, 4)
    batch = make_batch(1600, seed=17)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    root = str(tmp_path / "rep")
    builder.write_bucket_ordered(built, lengths, 16, root, mesh=mesh)
    per_bucket = parquet.bucket_files(root)
    cols = [f.name for f in built.schema.fields]
    segcache.clear()
    cache = segcache.get_cache()
    ref = SegmentRef(index_name="rep", index_root=root, version=0,
                     bucket="all")

    def read(slice_idx):
        sub = slice_submesh(mesh, slice_idx)
        per_shard = [[f for b in range(lo, hi)
                      for f in per_bucket.get(b, [])]
                     for lo, hi in bucket_ranges(16, 4)]
        sh = spmd.read_sharded(per_shard, lengths, cols, built.schema,
                               sub, base_ref=ref)
        df = columnar.to_arrow(
            spmd.sharded_filter(sh, _k_lt_60())).to_pandas()
        return df.sort_values(list(df.columns)).reset_index(drop=True)

    def _k_lt_60():
        from hyperspace_tpu.plan.expr import col, lit
        return col("k") < lit(60)

    r0 = read(0)
    r1 = read(1)
    pd.testing.assert_frame_equal(r0, r1)
    residency = cache.replica_residency(root)
    assert len(residency) == 2, residency  # one device tag per replica
    assert all(v == 4 for v in residency.values())  # 4 shards each
    # A committed refresh invalidates EVERY replica's entries.
    cache.invalidate_index(root, keep_version=1)
    assert cache.replica_residency(root) == {}
    pd.testing.assert_frame_equal(read(0), read(1))
    assert len(cache.replica_residency(root)) == 2


def test_least_loaded_routing_distribution_under_chaos(fault_injector):
    """Concurrent routed traffic balances across replicas (no replica
    past the 70% bar) and stays exact — including with transient faults
    injected at the parquet-read seam (the PR-7 chaos discipline): a
    retried read changes nothing about where queries land or what they
    return."""
    import threading

    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.engine.scheduler import QueryScheduler
    from hyperspace_tpu.parallel import replica as replica_mod
    from hyperspace_tpu.utils.faults import FaultRule

    conf = HyperspaceConf({"hyperspace.distribution.enabled": "true",
                           "hyperspace.distribution.slices": 2})
    mesh = topo_mesh(2, 4)
    left = make_batch(1000, seed=19)
    right = make_batch(400, seed=20)
    replica_mod.reset_router()
    router = replica_mod.get_router()
    sched = QueryScheduler()

    import tempfile

    from hyperspace_tpu.io import builder, parquet, segcache
    from hyperspace_tpu.io.segcache import SegmentRef
    from hyperspace_tpu.parallel.mesh import slice_submesh

    work = tempfile.mkdtemp(prefix="hs_chaos_route_")
    roots = {}
    for tag, batch in (("l", left), ("r", right)):
        built, lengths = distributed_build(batch, ["k"], 16, mesh)
        root = f"{work}/{tag}"
        builder.write_bucket_ordered(built, lengths, 16, root,
                                     mesh=mesh)
        roots[tag] = (root, lengths, built)
    segcache.clear()

    def read_pair(slice_idx):
        sub = slice_submesh(mesh, slice_idx)
        out = []
        for tag in ("l", "r"):
            root, lengths, built = roots[tag]
            per_bucket = parquet.bucket_files(root)
            per_shard = [[f for b in range(lo, hi)
                          for f in per_bucket.get(b, [])]
                         for lo, hi in bucket_ranges(16, 4)]
            ref = SegmentRef(index_name=f"cr_{tag}", index_root=root,
                             version=0, bucket="cr")
            out.append(spmd.read_sharded(
                per_shard, lengths,
                [f.name for f in built.schema.fields], built.schema,
                sub, base_ref=ref))
        return tuple(out)

    # Transient read faults bite the COLD per-device fills (retried by
    # the PR-4 policy); warm routed traffic then never re-pays them.
    inj = fault_injector(FaultRule("parquet.read", kind="transient",
                                   probability=0.3, times=8))
    oracle = oracle_frame(roots["l"][2], roots["r"][2], "inner")
    results = []
    errors = []

    def client(i):
        try:
            for _q in range(4):
                rep = router.route(None, conf, sched)
                assert rep in (0, 1)
                lsh, rsh = read_pair(rep)
                li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"],
                                                   ["k"])
                results.append(pairs_frame(lsh, rsh, li, ri))
        except Exception as exc:  # pragma: no cover - fail loudly
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(results) == 32
    for frame in results:
        pd.testing.assert_frame_equal(frame, oracle)
    routed = router.routed_counts()
    assert sum(routed.values()) == 32
    assert max(routed.values()) / 32 <= 0.70, routed
    assert inj.fired("parquet.read") > 0, \
        "chaos seam never fired — the test lost its teeth"
    import shutil
    shutil.rmtree(work, ignore_errors=True)


def test_engine_multislice_replica_routing(tmp_path, sample_parquet):
    """End to end through the serving plane: on a 2-slice topology the
    scheduler routes each collect to a replica slice
    (`serve.replica.<i>.routed`, per-replica admitted-byte gauges),
    execution is confined to the routed slice's submesh, and concurrent
    replica-routed joins equal the rules-off run bit for bit."""
    import threading

    from hyperspace_tpu.config import HyperspaceConf
    from hyperspace_tpu.engine.session import HyperspaceSession
    from hyperspace_tpu.facade import Hyperspace
    from hyperspace_tpu.index.index_config import IndexConfig
    from hyperspace_tpu.io import segcache
    from hyperspace_tpu.parallel import replica as replica_mod

    conf = HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "hyperspace.index.num.buckets": 8,
        "hyperspace.distribution.enabled": "true",
        "hyperspace.distribution.slices": 2,
        "hyperspace.broadcast.threshold": -1,
    })
    session = HyperspaceSession(conf)
    hs = Hyperspace(session)
    df = session.read_parquet(sample_parquet)
    hs.create_index(df, IndexConfig("msl", ["imprs"], ["id", "clicks"]))
    hs.create_index(df, IndexConfig("msr", ["imprs"], ["score"]))
    query = df.select("imprs", "id", "clicks").join(
        df.select("imprs", "score"), on="imprs")
    sort_cols = ["imprs", "id", "score"]

    session.disable_hyperspace()
    plain = query.to_pandas().sort_values(sort_cols) \
        .reset_index(drop=True)
    session.enable_hyperspace()
    segcache.clear()
    replica_mod.reset_router()
    reg = telemetry.get_registry()
    before = {k: reg.counters_dict().get(k, 0)
              for k in ("serve.replica.0.routed",
                        "serve.replica.1.routed")}
    results = []
    errors = []

    def client():
        try:
            results.append(query.to_pandas().sort_values(sort_cols)
                           .reset_index(drop=True))
        except Exception as exc:  # pragma: no cover - fail loudly
            errors.append(exc)

    threads = [threading.Thread(target=client) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    session.disable_hyperspace()
    assert not errors, errors
    for frame in results:
        pd.testing.assert_frame_equal(frame, plain)
    after = {k: reg.counters_dict().get(k, 0)
             for k in ("serve.replica.0.routed",
                       "serve.replica.1.routed")}
    routed = sum(after.values()) - sum(before.values())
    assert routed >= 4, (before, after)


def test_repartition_sharded_routes_all_rows():
    """Every input row survives the in-program re-bucket, lands on its
    bucket's contiguous-range owner, and a join over the repartitioned
    layout equals the oracle."""
    mesh = make_mesh(8)
    batch = make_batch(1000, seed=31)
    sh = spmd.repartition_sharded(batch, ["k"], 16, mesh)
    assert sh.num_rows == 1000
    rsh_mesh, lsh, rsh, lb, rb, ll, rl = sharded_pair(n_dev=8, seed=31)
    li, ri = spmd.sharded_join_indices(sh, rsh, ["k"], ["k"])
    lk = np.asarray(sh.batch.column("k").data)
    rk = np.asarray(rsh.batch.column("k").data)
    li, ri = np.asarray(li), np.asarray(ri)
    assert (lk[li] == rk[ri]).all()
    exp = pd.DataFrame({"k": np.asarray(batch.column("k").data)}).merge(
        pd.DataFrame({"k": np.asarray(rb.column("k").data)}), on="k")
    assert len(exp) == len(li)
