"""Distribution tests on the virtual 8-device CPU mesh (conftest calls
`parallel.virtual.ensure_devices(8)`) — the reference's `local[4]`
equivalent (SURVEY §4 takeaway)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from hyperspace_tpu.io import columnar
from hyperspace_tpu.parallel import spmd
from hyperspace_tpu.parallel.build import distributed_build
from hyperspace_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    import jax
    assert len(jax.devices()) >= 8, "virtual device mesh missing"
    return make_mesh(8)


def make_batch(n, seed=0, with_strings=True):
    rng = np.random.default_rng(seed)
    cols = {
        "k": rng.integers(0, max(4, n // 8), n).astype(np.int64),
        "v": rng.random(n).astype(np.float64),
    }
    if with_strings:
        cols["s"] = pa.array([f"name{int(x):03d}"
                              for x in rng.integers(0, 50, n)])
    return columnar.from_arrow(pa.table(cols))


def test_distributed_build_matches_single_chip(mesh):
    """The all_to_all build must produce the same bucket contents as the
    single-device pipeline."""
    from hyperspace_tpu.ops.build import build_sorted

    batch = make_batch(1000, seed=3)
    built, lengths = distributed_build(batch, ["k"], 16, mesh)
    assert built.num_rows == 1000
    assert int(lengths.sum()) == 1000

    single, starts, ends = build_sorted(batch, ["k"], 16)
    single_lengths = np.asarray(ends) - np.asarray(starts)
    assert (lengths == single_lengths).all()

    # identical rows per bucket (as multisets)
    dist_df = columnar.to_arrow(built).to_pandas()
    single_df = columnar.to_arrow(single).to_pandas()
    db = np.repeat(np.arange(16), lengths)
    sb = np.repeat(np.arange(16), single_lengths)
    dist_df["b"] = db
    single_df["b"] = sb
    cols = ["b", "k", "v", "s"]
    a = dist_df[cols].sort_values(cols).reset_index(drop=True)
    b = single_df[cols].sort_values(cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b)


def test_distributed_build_sorted_within_buckets(mesh):
    batch = make_batch(500, seed=4, with_strings=False)
    built, lengths = distributed_build(batch, ["k"], 8, mesh)
    k = np.asarray(built.column("k").data)
    start = 0
    for b in range(8):
        seg = k[start:start + lengths[b]]
        assert (np.diff(seg) >= 0).all(), f"bucket {b} not sorted"
        start += lengths[b]


def test_distributed_build_capacity_overflow_retry(mesh):
    """Skewed keys (all rows -> one bucket) overflow the default capacity;
    the exact-retry path must still deliver every row."""
    n = 800
    batch = columnar.from_arrow(pa.table({
        "k": np.full(n, 7, dtype=np.int64),
        "v": np.arange(n, dtype=np.float64),
    }))
    built, lengths = distributed_build(batch, ["k"], 16, mesh,
                                       capacity_factor=0.5)
    assert built.num_rows == n
    assert int(lengths.sum()) == n
    assert int(lengths.max()) == n  # all in one bucket


def _sharded_pair(mesh, left, right, buckets=16):
    lb, ll = distributed_build(left, ["k"], buckets, mesh)
    rb, rl = distributed_build(right, ["k"], buckets, mesh)
    return (spmd.shard_bucket_ordered(lb, ll, mesh),
            spmd.shard_bucket_ordered(rb, rl, mesh), lb, rb)


def test_spmd_join_matches_pandas(mesh):
    left = make_batch(600, seed=5, with_strings=False)
    right = make_batch(300, seed=6, with_strings=False)
    lsh, rsh, lb, rb = _sharded_pair(mesh, left, right)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
    lk = np.asarray(lsh.batch.column("k").data)[np.asarray(li)]
    rk = np.asarray(rsh.batch.column("k").data)[np.asarray(ri)]
    assert (lk == rk).all()
    ref = pd.DataFrame({"k": np.asarray(lb.column("k").data)}).merge(
        pd.DataFrame({"k": np.asarray(rb.column("k").data)}), on="k")
    assert len(ref) == len(np.asarray(li))


def test_spmd_full_outer_matches_pandas(mesh):
    left = make_batch(500, seed=8, with_strings=False)
    right = make_batch(260, seed=9, with_strings=False)
    lsh, rsh, lb, rb = _sharded_pair(mesh, left, right)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                       how="full_outer")
    li, ri = np.asarray(li), np.asarray(ri)
    lk_p = np.asarray(lsh.batch.column("k").data)
    rk_p = np.asarray(rsh.batch.column("k").data)
    got = pd.DataFrame({
        "lk": np.where(li >= 0, lk_p[np.clip(li, 0, None)], -1),
        "rk": np.where(ri >= 0, rk_p[np.clip(ri, 0, None)], -1)})
    lpd = pd.DataFrame({"lk": np.asarray(lb.column("k").data)})
    rpd = pd.DataFrame({"rk": np.asarray(rb.column("k").data)})
    exp = lpd.assign(j=lpd.lk).merge(rpd.assign(j=rpd.rk), on="j",
                                     how="outer").drop(columns="j")
    exp = exp.fillna(-1).astype(np.int64)
    key = ["lk", "rk"]
    pd.testing.assert_frame_equal(
        got.sort_values(key).reset_index(drop=True),
        exp[key].sort_values(key).reset_index(drop=True),
        check_dtype=False)


def test_spmd_semi_anti_matches_pandas(mesh):
    left = make_batch(500, seed=10, with_strings=False)
    right = make_batch(120, seed=11, with_strings=False)
    lsh, rsh, lb, rb = _sharded_pair(mesh, left, right)
    lk = np.asarray(lb.column("k").data)
    rset = set(np.asarray(rb.column("k").data))
    for anti in (False, True):
        li = spmd.sharded_semi_anti_indices(lsh, rsh, ["k"], ["k"],
                                            anti=anti)
        member = np.asarray([k in rset for k in lk])
        exp = int((~member if anti else member).sum())
        assert len(np.asarray(li)) == exp, f"anti={anti}"
        keys = np.asarray(lsh.batch.column("k").data)[np.asarray(li)]
        assert np.isin(keys, list(rset)).all() != anti or exp == 0


def test_spmd_join_hot_bucket_is_sized_in_one_attempt(mesh):
    """A hot key concentrating most rows in ONE bucket must still join
    exactly: the expansion's slots are sized by that shard's total once
    the match has found it, in one match dispatch (no doubling, no
    re-run), and the fill lands in (1/2, 1]."""
    from hyperspace_tpu import telemetry

    n = 1200
    hot = np.full(n - 100, 7, dtype=np.int64)
    rest = np.arange(100, dtype=np.int64) + 100
    left = columnar.from_arrow(pa.table({
        "k": np.concatenate([hot, rest]),
        "v": np.arange(n, dtype=np.float64)}))
    right = columnar.from_arrow(pa.table({
        "k": np.asarray([7, 7, 120, 150], dtype=np.int64),
        "w": np.arange(4, dtype=np.float64)}))
    lsh, rsh, lb, rb = _sharded_pair(mesh, left, right)
    reg = telemetry.get_registry()
    fill = reg.histogram("mesh.spmd.expand_fill")
    retries = reg.counters_dict().get("mesh.spmd.overflow_retries", 0)
    observed, filled = fill.count, fill.sum
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
    assert reg.counters_dict().get("mesh.spmd.overflow_retries",
                                   0) == retries
    lk = np.asarray(lsh.batch.column("k").data)[np.asarray(li)]
    rk = np.asarray(rsh.batch.column("k").data)[np.asarray(ri)]
    assert (lk == rk).all()
    # hot key expands (n-100)*2; the two singles match once each
    assert len(np.asarray(li)) == (n - 100) * 2 + 2
    assert fill.count == observed + 1
    # the hot shard holds 2,200 pairs or a few more: the 4,096 rung
    assert 2200 / 4096 <= fill.sum - filled < 2208 / 4096


def test_spmd_join_memory_is_sharded(mesh):
    """The born-sharded [S*C] layout must give every device ~1/S of the
    rows — assert the actual per-shard bytes of the resident columns."""
    left = make_batch(4000, seed=12, with_strings=False)
    right = make_batch(2000, seed=13, with_strings=False)
    lsh, _rsh, _lb, _rb = _sharded_pair(mesh, left, right)
    for name in ("k", "v"):
        arr = lsh.batch.column(name).data
        shards = arr.addressable_shards
        assert len(shards) == 8
        per_dev = max(s.data.nbytes for s in shards)
        assert per_dev <= arr.nbytes / 8 + 1024, (
            f"device holds {per_dev}B of a {arr.nbytes}B array — "
            "not sharded")
    # and the padded layout is tight: cells within 2x of true rows
    assert 8 * lsh.rows_per_shard <= 2 * left.num_rows + 8 * 16


def test_spmd_left_semi_empty_right(mesh):
    """Degenerate sides stay off the mesh at the ENGINE level
    (`ScanExec._execute_sharded` returns None for zero rows); at the
    spmd API level an all-padding right side must still answer
    membership correctly."""
    left = make_batch(300, seed=14, with_strings=False)
    empty_rows = columnar.from_arrow(pa.table({
        "k": np.zeros(1, dtype=np.int64), "v": np.zeros(1)}))
    lb, ll = distributed_build(left, ["k"], 16, mesh)
    eb, el = distributed_build(empty_rows, ["k"], 16, mesh)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh)
    esh = spmd.shard_bucket_ordered(eb, el, mesh)
    anti = spmd.sharded_semi_anti_indices(lsh, esh, ["k"], ["k"],
                                          anti=True)
    lk = np.asarray(lb.column("k").data)
    assert len(np.asarray(anti)) == int((lk != 0).sum())


def test_repartition_sharded_mismatched_counts(mesh):
    """The ranker's fallback, post-deletion form: a device-resident
    batch re-buckets to a new count entirely in-program
    (`repartition_sharded`), and a join over the result matches the
    co-bucketed layout."""
    batch = make_batch(400, seed=7, with_strings=False)
    sh = spmd.repartition_sharded(batch, ["k"], 32, mesh)
    assert sh.num_buckets == 32
    assert sh.num_rows == 400


def test_graft_entry():
    import __graft_entry__
    fn, args = __graft_entry__.entry()
    out = fn(*args)
    assert out[0].shape[0] == 4096
    __graft_entry__.dryrun_multichip(8)


def test_distributed_group_aggregate_matches_single_chip(mesh):
    """SPMD partial aggregation + host combine must equal the single-chip
    aggregation for every combinable function, incl. stddev over
    large-offset values (exact variance decomposition) and null inputs."""
    import pandas as pd
    import pyarrow as pa

    from hyperspace_tpu.io.columnar import from_arrow, to_arrow
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.parallel.aggregate import distributed_group_aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    rng = np.random.default_rng(31)
    n = 20_000
    table = pa.table({
        "g": rng.integers(0, 97, n).astype(np.int64),
        "h": pa.array([["a", "b", "c"][i % 3] for i in range(n)]),
        "x": pa.array([None if i % 13 == 0 else 1.7e6 + float(v)
                       for i, v in enumerate(rng.standard_normal(n))],
                      type=pa.float64()),
        "y": rng.integers(-1000, 1000, n).astype(np.int64),
    })
    schema = Schema.from_arrow(table.schema)
    specs = [AggSpec("count", "*", "cnt"), AggSpec("count", "x", "cx"),
             AggSpec("sum", "y", "sy"), AggSpec("avg", "x", "ax"),
             AggSpec("min", "y", "mny"), AggSpec("max", "y", "mxy"),
             AggSpec("stddev", "x", "sx")]
    out_schema = Aggregate(["g", "h"], specs,
                           Scan(["/nx"], schema)).schema
    batch = from_arrow(table)
    dist = distributed_group_aggregate(batch, ["g", "h"], specs,
                                       out_schema, mesh)
    single = group_aggregate(batch, ["g", "h"], specs, out_schema)

    d = (to_arrow(dist).to_pandas().sort_values(["g", "h"])
         .reset_index(drop=True))
    s = (to_arrow(single).to_pandas().sort_values(["g", "h"])
         .reset_index(drop=True))
    pd.testing.assert_frame_equal(d, s, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_distributed_aggregate_int64_exact(mesh):
    """int64 sum/min/max past 2^53 must stay exact under distribution
    (float64 accumulation would silently round)."""
    import pyarrow as pa

    from hyperspace_tpu.io.columnar import from_arrow, to_arrow
    from hyperspace_tpu.ops.aggregate import group_aggregate
    from hyperspace_tpu.parallel.aggregate import distributed_group_aggregate
    from hyperspace_tpu.plan.nodes import Aggregate, AggSpec, Scan
    from hyperspace_tpu.plan.schema import Schema

    big = (1 << 53) + 1
    table = pa.table({"g": np.zeros(8, np.int64),
                      "y": np.array([big, big, big, big,
                                     big + 2, big + 2, big + 2, big + 2],
                                    dtype=np.int64)})
    schema = Schema.from_arrow(table.schema)
    specs = [AggSpec("sum", "y", "sy"), AggSpec("min", "y", "mny"),
             AggSpec("max", "y", "mxy")]
    out_schema = Aggregate(["g"], specs, Scan(["/nx"], schema)).schema
    batch = from_arrow(table)
    d = to_arrow(distributed_group_aggregate(batch, ["g"], specs,
                                             out_schema, mesh)).to_pandas()
    s = to_arrow(group_aggregate(batch, ["g"], specs,
                                 out_schema)).to_pandas()
    assert int(d.sy[0]) == int(s.sy[0]) == 8 * big + 8
    assert int(d.mny[0]) == big and int(d.mxy[0]) == big + 2


def test_spmd_left_outer_join_with_nulls(mesh):
    """SPMD left_outer: unmatched and null-key left rows emit right -1;
    matches equal pandas (null keys never match — Kleene)."""
    rng = np.random.default_rng(9)
    lk = rng.integers(0, 30, 400).astype(np.float64)
    lk[::17] = np.nan  # null keys via mask below
    lmask = ~np.isnan(lk)
    left = columnar.from_arrow(pa.table({
        "k": pa.array(np.where(lmask, lk, 0).astype(np.int64),
                      mask=~lmask),
        "x": rng.random(400)}))
    right = columnar.from_arrow(pa.table({
        "k": rng.integers(10, 50, 150).astype(np.int64),
        "y": rng.random(150)}))
    lsh, rsh, lb, rb = _sharded_pair(mesh, left, right)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"],
                                       how="left_outer")
    li, ri = np.asarray(li), np.asarray(ri)
    lkey_p = np.asarray(lsh.batch.column("k").data)
    lval_p = (np.asarray(lsh.batch.column("k").validity)
              if lsh.batch.column("k").validity is not None
              else np.ones(len(lkey_p), bool))
    rkey_p = np.asarray(rsh.batch.column("k").data)
    # Matched pairs agree with pandas over the ORIGINAL layouts.
    lkey = np.asarray(lb.column("k").data)
    lval = (np.asarray(lb.column("k").validity)
            if lb.column("k").validity is not None
            else np.ones(len(lkey), bool))
    rkey = np.asarray(rb.column("k").data)
    lpd = pd.DataFrame({"k": lkey[lval]})
    rpd = pd.DataFrame({"k": rkey})
    matched = lpd.merge(rpd, on="k")
    got_matched = ri >= 0
    assert int(got_matched.sum()) == len(matched)
    assert (lkey_p[li[got_matched]] == rkey_p[ri[got_matched]]).all()
    assert lval_p[li[got_matched]].all()
    # every REAL left row appears; null/unmatched carry right -1 once
    assert len(li) == len(matched) + int((~lval).sum()) \
        + int((~np.isin(lkey, rkey) & lval).sum())


# -- two-axis (dcn x shard) mesh: multi-host topology ---------------------


@pytest.fixture(scope="module")
def mesh24():
    from hyperspace_tpu.parallel.mesh import make_mesh
    return make_mesh(8, dcn_size=2)


def test_two_axis_build_matches_single_chip(mesh24):
    from hyperspace_tpu.ops.build import build_sorted

    batch = make_batch(900, seed=21, with_strings=True)
    built, lengths = distributed_build(batch, ["k"], 16, mesh24)
    single, starts, ends = build_sorted(batch, ["k"], 16)
    sl = np.asarray(ends) - np.asarray(starts)
    assert (lengths == sl).all()
    cols = ["k", "v", "s"]
    a = columnar.to_arrow(built).to_pandas()[cols].sort_values(cols) \
        .reset_index(drop=True)
    b = columnar.to_arrow(single).to_pandas()[cols].sort_values(cols) \
        .reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b)


def test_two_axis_join_matches_pandas(mesh24):
    """Co-bucketed SPMD join over the 2-axis (dcn x shard) mesh —
    equal bucket counts need no in-program repartition, so the single
    program runs on multi-slice topologies too."""
    left = make_batch(700, seed=22, with_strings=False)
    right = make_batch(350, seed=23, with_strings=False)
    lb, ll = distributed_build(left, ["k"], 16, mesh24)
    rb, rl = distributed_build(right, ["k"], 16, mesh24)
    lsh = spmd.shard_bucket_ordered(lb, ll, mesh24)
    rsh = spmd.shard_bucket_ordered(rb, rl, mesh24)
    li, ri = spmd.sharded_join_indices(lsh, rsh, ["k"], ["k"])
    lk_p = np.asarray(lsh.batch.column("k").data)
    rk_p = np.asarray(rsh.batch.column("k").data)
    assert (lk_p[np.asarray(li)] == rk_p[np.asarray(ri)]).all()
    lk = np.asarray(lb.column("k").data)
    rk = np.asarray(rb.column("k").data)
    exp = pd.DataFrame({"k": lk}).merge(pd.DataFrame({"k": rk}), on="k")
    assert len(exp) == len(np.asarray(li))


def test_two_axis_collectives_confined_to_axes(mesh24):
    """SURVEY §2.12 "DCN only across slices": the build's heavy re-bucket
    all_to_all must be CONFINED to the inner (ICI) axis — replica groups
    {0..3},{4..7} — with only the slim cross-slice stage over DCN pairs
    {0,4},{1,5},... . Asserted on the COMPILED HLO's replica groups."""
    import re

    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import batch_to_tree
    from hyperspace_tpu.parallel.build import make_distributed_build_step

    batch = make_batch(1024, seed=24, with_strings=False)
    tree, _ = batch_to_tree(batch)
    in_tree = {name: dict(e, data=jnp.asarray(e["data"]))
               for name, e in tree.items()}
    in_tree["__valid__"] = jnp.ones(1024, dtype=bool)
    step = make_distributed_build_step(mesh24, ("k",), 16, 2.0)
    hlo = step.lower(in_tree).compile().as_text()
    groups = set(re.findall(r"replica_groups=(\{\{[0-9,{}]*\}\})", hlo))
    assert "{{0,1,2,3},{4,5,6,7}}" in groups, groups  # ICI stage
    assert "{{0,4},{1,5},{2,6},{3,7}}" in groups, groups  # DCN stage
    flat = "{{0,1,2,3,4,5,6,7}}"
    assert flat not in groups, "a collective spans the full mesh"
