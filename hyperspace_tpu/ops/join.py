"""Device equi-join kernels over columnar batches.

The reference's query-time win is Spark's SortMergeJoin with Exchange+Sort
elided thanks to bucketed relations (`index/rules/JoinIndexRule.scala:41-43`).
The device equivalent joins two key column sets entirely with vectorized
XLA primitives — no scalar merge loop (which would defeat the TPU's
vector units). The device lane is the COUNTING join, in original row
space, two programs and one host sync:

1. the match (`_counting_match_lanes`, scope `hs.join.match`): ONE sort
   of both sides' 32-bit key lanes with (side, row number) as trailing
   keys — which also makes string keys from different dictionaries and
   multi-column keys comparable — then `_runs_to_counts`: key runs from
   adjacent differences, and each run's right-row count and bracket by
   five scans over the sorted rows (two prefix sums, one `cummax`, two
   reverse `cummin`; the brackets are `run_brackets`, which the mesh
   match `parallel/spmd._match` runs along each shard's row). No
   `searchsorted` and no gather over the sorted rows: the chip scans
   them some 20-60 times faster than it gathers them (see
   `run_brackets`). Keys of `HASH_MATCH_MIN_LANES` lanes
   and more (`_counting_match_lanes_hashed`) sort by (u64 key hash,
   side, row number) instead, the key lanes carried by that same sort
   as payload, so runs come from sorted lanes there too;
2. the total pair count is the one host sync; it sizes the result, which
   is materialized anyway;
3. the expansion (`_counting_expand`, scope `hs.join.expand`), sized by
   the pairs: the sorted rows that own a pair are selected first (one
   sort of the T rows, or for very few pairs a rank select over their
   counts' running sum), then spread over their slots by a scatter of at
   most one update a pair and a running sum; past the select no step
   grows with T (`jnp.repeat` scattered one update per sorted row).

`merge_join_indices` (two `searchsorted` calls over sorted dense ids
from `encode_join_keys`) is the older formulation and no operator's
path; the host lane (numpy, native merge) is at the end of the file.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from hyperspace_tpu import telemetry
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import ColumnBatch
from hyperspace_tpu.telemetry import instrumented_jit


def encode_join_keys(left: ColumnBatch, right: ColumnBatch,
                     left_keys: Sequence[str], right_keys: Sequence[str]):
    """Map key tuples of both sides onto shared order-preserving dense int32
    group ids (equal tuples <-> equal ids, and ids sort in key order).

    SQL join-null semantics: rows with a NULL in any key column must match
    nothing. They are assigned the sentinels -1 (left) / -2 (right), which
    never compare equal across sides; because sorts place nulls first
    (validity is the leading sub-key, `ops/sort.py`), the sentinels land at
    the front of an already key-sorted batch and preserve the sortedness
    invariant `merge_join_indices` relies on.

    There is exactly ONE device key-identity implementation — the 32-bit
    lane encoder in `ops/bucketed_join.encode_group_ids` (normalized float
    order bits: -0.0 == 0.0, NaN == NaN) — so the global and bucketed
    join paths can never diverge on which tuples compare equal.
    """
    from hyperspace_tpu.ops.bucketed_join import encode_group_ids
    return encode_group_ids(left, right, left_keys, right_keys)


def _join_lane_operands(left: ColumnBatch, right: ColumnBatch,
                        left_keys: Sequence[str],
                        right_keys: Sequence[str]):
    """Per-side 32-bit lane tuples for the ONE-SORT counting join: a
    null-marker lane (0 = valid keys; 1 = left-null; 2 = right-null — so
    null keys form single-side runs and match nothing, the shared join
    null semantics) followed by the order-preserving value lanes
    (`ops/keys.py`). Strings unify onto one merged dictionary first."""
    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import unify_string_columns
    from hyperspace_tpu.ops import keys as keymod

    if len(left_keys) != len(right_keys) or not left_keys:
        raise HyperspaceException("Join requires matching key column lists.")
    n, m = left.num_rows, right.num_rows
    l_valid = jnp.ones(n, dtype=bool)
    r_valid = jnp.ones(m, dtype=bool)
    l_lanes: List = []
    r_lanes: List = []
    for lk, rk in zip(left_keys, right_keys):
        lcol, rcol = left.column(lk), right.column(rk)
        if lcol.is_string != rcol.is_string:
            raise HyperspaceException(f"Join key type mismatch: {lk} vs {rk}")
        if lcol.is_string:
            lcol, rcol = unify_string_columns(lcol, rcol)
        if lcol.validity is not None:
            l_valid = l_valid & lcol.validity
        if rcol.validity is not None:
            r_valid = r_valid & rcol.validity
        ldata, rdata = lcol.data, rcol.data
        if ldata.dtype != rdata.dtype:
            common = jnp.promote_types(ldata.dtype, rdata.dtype)
            ldata = ldata.astype(common)
            rdata = rdata.astype(common)
        l_lanes.extend(keymod.key_lanes(ldata))
        r_lanes.extend(keymod.key_lanes(rdata))
    marker_l = jnp.where(l_valid, jnp.int32(0), jnp.int32(1))
    marker_r = jnp.where(r_valid, jnp.int32(0), jnp.int32(2))
    return (marker_l, *l_lanes), (marker_r, *r_lanes)


def run_brackets(differs, side_s):
    """Each sorted row's key-run bracket, along the LAST axis: from the
    adjacent-key-difference vector `differs` ([..., T-1]) and the 0/1
    side of each sorted row `side_s` ([..., T], 1 = right), the run's
    right-row count `rights`, the sorted position of its first right
    row `rstart` and of its last row `run_last` (all int32, [..., T]).
    The one-chip match calls it over its one axis, the mesh match
    (`parallel/spmd._match`) over each shard's row of [S, T].

    Scans only, no gather over the T sorted rows: the inclusive count
    of right rows `R` and the exclusive one `R - side_s` never
    decrease, so the value at a run's first row is carried forwards
    from the run starts by a `cummax` and the value at its last row
    backwards from the run ends by a reverse `cummin` (sentinel T, at
    or above every count); `rights` is their difference. On a v5e a
    scan over 4.6 M rows costs 1.75 ms and a gather of them 34-108 ms
    (PERF.md section 6, PR 35)."""
    import jax
    import jax.numpy as jnp

    axis = side_s.ndim - 1
    T = side_s.shape[axis]
    sentinel = jnp.int32(T)
    pos = jnp.arange(T, dtype=jnp.int32)
    edge = jnp.ones(side_s.shape[:-1] + (1,), bool)
    run_start = jnp.concatenate([edge, differs], axis=axis)
    run_end = jnp.concatenate([differs, edge], axis=axis)
    R = jnp.cumsum(side_s, axis=axis)  # inclusive right-element count
    before = jax.lax.cummax(jnp.where(run_start, R - side_s, 0), axis=axis)
    upto = jax.lax.cummin(jnp.where(run_end, R, sentinel), axis=axis,
                          reverse=True)
    rights = upto - before
    run_last = jax.lax.cummin(jnp.where(run_end, pos, sentinel),
                              axis=axis, reverse=True)
    rstart = run_last - rights + 1  # first right element of the run
    return rights, rstart, run_last


def _runs_to_counts(differs, side_s, left_outer: bool):
    """Shared tail of the counting match: per-run right-counts and
    bracket starts from the (T-1) adjacent-key-difference vector over
    the sorted (key, side, orig) sequence. The brackets are
    `run_brackets`' scans (no gather); the counts are the left rows'."""
    import jax.numpy as jnp

    rights, rstart, _ = run_brackets(differs, side_s)
    counts = jnp.where(side_s == 0, rights, 0).astype(jnp.int32)
    if left_outer:
        counts = jnp.where(side_s == 0, jnp.maximum(counts, 1), 0)
    starts = jnp.cumsum(counts, axis=0) - counts
    return counts, starts, rights, rstart


@instrumented_jit("join.counting_match_lanes", scope="hs.join.match",
                  static_argnames=("left_outer",))
def _counting_match_lanes(lanes_l, lanes_r, left_outer: bool):
    """The counting match directly over raw key LANES — ONE staged sort
    of (marker, *value lanes, side, orig) replaces the earlier two-sort
    pipeline (dense-id encode sort + id/side match sort): runs come from
    adjacent lane differences in the single sorted sequence, their
    brackets from `_runs_to_counts`' scans (no gather). Orig
    indices ride as trailing sort keys (unique, so equivalent to the
    stable carried-value formulation)."""
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import _staged_sort

    n, m = lanes_l[0].shape[0], lanes_r[0].shape[0]
    lanes = [jnp.concatenate([a, b]) for a, b in zip(lanes_l, lanes_r)]
    side = jnp.concatenate([jnp.zeros(n, jnp.int32),
                            jnp.ones(m, jnp.int32)])
    orig = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.arange(m, dtype=jnp.int32)])
    _, sorted_ops = _staged_sort([*lanes, side, orig])
    side_s = sorted_ops[-2]
    orig_s = sorted_ops[-1]
    keys_sorted = sorted_ops[:-2]
    T = n + m
    differs = jnp.zeros(T - 1, dtype=bool)
    for k in keys_sorted:
        differs = differs | (k[1:] != k[:-1])
    counts, starts, rights, rstart = _runs_to_counts(differs, side_s,
                                                     left_outer)
    return counts, starts, rights, rstart, orig_s


# Wide join keys route through ONE u64-hash-lane sort instead of the
# chunked multi-lane sort (same trick, same collision fallback as
# `ops/aggregate._group_phase_a_hashed`). Below this lane count (incl.
# the null-marker lane) the narrow sort is already a single pass.
HASH_MATCH_MIN_LANES = 4


@instrumented_jit("join.counting_match_lanes_hashed", scope="hs.join.match",
                  static_argnames=("left_outer",))
def _counting_match_lanes_hashed(lanes_l, lanes_r, left_outer: bool):
    """Hashed counting match: sort by (u64 key-hash, side, orig) — three
    sort keys regardless of key width — with the key lanes carried by
    the same sort as payload operands, then derive runs from the FULL
    sorted lane differences. No gather through the permutation: on a
    v5e, over TPC-DS q17's 31.7 M rows and 7 lanes, this program takes
    459 ms where the sort and a gather a lane took 2,968 (PERF.md
    section 6). Payload adds no comparisons, so the comparator stays
    three keys wide (cf. `keys.MAX_SORT_OPERANDS`). Equal keys share a
    hash so runs stay contiguous unless two different keys collide;
    `collision` (any full-key boundary inside an equal-hash run,
    exactly the split/interleave case) tells the caller to re-run the
    exact path. Run order within a key run is (side, orig), same as the
    exact sort's trailing operands."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash_partition import dual_hash64

    n, m = lanes_l[0].shape[0], lanes_r[0].shape[0]
    T = n + m
    lanes = [jnp.concatenate([a, b]) for a, b in zip(lanes_l, lanes_r)]
    h = dual_hash64(lanes)

    side = jnp.concatenate([jnp.zeros(n, jnp.int32),
                            jnp.ones(m, jnp.int32)])
    orig = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.arange(m, dtype=jnp.int32)])
    h_s, side_s, orig_s, *lanes_s = jax.lax.sort(
        [h, side, orig, *lanes], num_keys=3, is_stable=False)
    differs = jnp.zeros(T - 1, dtype=bool)
    for k in lanes_s:
        differs = differs | (k[1:] != k[:-1])
    h_differs = h_s[1:] != h_s[:-1]
    collision = jnp.any(differs & ~h_differs)
    counts, starts, rights, rstart = _runs_to_counts(differs, side_s,
                                                     left_outer)
    return counts, starts, rights, rstart, orig_s, collision


def _match_lanes(lanes_l, lanes_r, left_outer: bool):
    """(counts, starts, rights, rstart, orig_s, collision|None): the
    hashed match for wide keys, the exact narrow sort otherwise. A None
    collision needs no verification; a device-scalar collision must be
    folded into the caller's sizing sync, and a truthy value means
    re-running via `_counting_match_lanes`."""
    if len(lanes_l) >= HASH_MATCH_MIN_LANES:
        return _counting_match_lanes_hashed(lanes_l, lanes_r, left_outer)
    return (*_counting_match_lanes(lanes_l, lanes_r, left_outer), None)


def _hashed_fallback() -> None:
    """Count one re-run of the exact match after a hash collision."""
    telemetry.get_registry().counter("join.hashed.fallbacks").inc()


def _packed_sync(value_dev, collision):
    """ONE device fetch carrying (sizing value, collision flag): returns
    (int value, collided). `value_dev` must be an int64 device scalar."""
    import jax.numpy as jnp

    packed = int(value_dev * jnp.int64(2) + collision.astype(jnp.int64))
    return packed >> 1, bool(packed & 1)


def counting_join_batch_indices(left: ColumnBatch, right: ColumnBatch,
                                left_keys: Sequence[str],
                                right_keys: Sequence[str],
                                how: str = "inner") -> Tuple:
    """Device join row-index pairs straight from the key COLUMNS: one
    fused sort+count executable and one host sync. Same null semantics
    as the id-based `counting_join_indices` (which remains for id-space
    callers); pair ORDER is deterministic per path but unspecified —
    wide keys (>= HASH_MATCH_MIN_LANES lanes) come back in hash-run
    order, narrow keys in key-sorted order."""
    import jax.numpy as jnp

    left_outer = how == "left_outer"
    n, m = left.num_rows, right.num_rows
    empty = jnp.zeros(0, dtype=jnp.int32)
    if n == 0 or (m == 0 and not left_outer):
        return empty, empty
    if m == 0:
        return (jnp.arange(n, dtype=jnp.int32),
                jnp.full(n, -1, dtype=jnp.int32))
    lanes_l, lanes_r = _join_lane_operands(left, right, left_keys,
                                           right_keys)
    counts, starts, rights, rstart, orig_s, collision = _match_lanes(
        lanes_l, lanes_r, left_outer)
    match = "exact"
    if collision is None:
        total = int(jnp.sum(counts, dtype=jnp.int64))  # the one host sync
    else:
        # One sync carries (total, collision); a collision re-runs exact.
        total, collided = _packed_sync(jnp.sum(counts, dtype=jnp.int64),
                                       collision)
        match = "hashed"
        if collided:
            _hashed_fallback()
            match = "hashed-fallback"
            counts, starts, rights, rstart, orig_s = _counting_match_lanes(
                lanes_l, lanes_r, left_outer)
            total = int(jnp.sum(counts, dtype=jnp.int64))
    telemetry.annotate(match=match, keys=len(left_keys))
    if total == 0:
        return empty, empty
    return _expand(counts, starts, rights, rstart, orig_s, total,
                   left_outer)


def counting_join_indices(l_ids, r_ids, how: str = "inner") -> Tuple:
    """Join row-index pairs over UNSORTED id arrays (original row space),
    via ONE joint sort + cumulative counting — no `searchsorted`.

    On TPU, `searchsorted` over tens of millions of rows lowers to
    log(n) serialized gather sweeps and dominated the join at TPC-DS
    scale (measured ~17-20s of a 22s 39M-row join); a flat 1-D
    `lax.sort` of the same rows runs in ~1s. So: sort (id, side,
    original index) once, derive per-id-run right-row counts and bracket
    starts by scans over the SORTED sequence (`_runs_to_counts`), and
    expand matches by `_counting_expand`. 4-5x faster end-to-end at 39M
    rows, and callers no longer pre-sort their payload batches — indices
    come back in original row space.

    Supports how='inner' and 'left_outer' (unmatched left rows appear
    once with right index -1); callers express right/full outer by
    swapping / appending as usual. Null sentinels (-1 left, -2 right)
    form single-side runs, so they match nothing.
    """
    import jax
    import jax.numpy as jnp

    left_outer = how == "left_outer"
    n, m = int(l_ids.shape[0]), int(r_ids.shape[0])
    empty = jnp.zeros(0, dtype=jnp.int32)
    if n == 0 or (m == 0 and not left_outer):
        return empty, empty
    if m == 0:
        return (jnp.arange(n, dtype=jnp.int32),
                jnp.full(n, -1, dtype=jnp.int32))
    counts, starts, rights, rstart, orig_s = _counting_match(
        l_ids, r_ids, left_outer)
    total = int(jnp.sum(counts))  # the one host sync
    if total == 0:
        return empty, empty
    return _expand(counts, starts, rights, rstart, orig_s, total,
                   left_outer)


@instrumented_jit("join.counting_match", scope="hs.join.match",
                  static_argnames=("left_outer",))
def _counting_match(l_ids, r_ids, left_outer: bool):
    """The counting match in id space: one stable sort of (id, side)
    carrying the row numbers, then `_runs_to_counts`."""
    import jax
    import jax.numpy as jnp

    n, m = l_ids.shape[0], r_ids.shape[0]
    ids2 = jnp.concatenate([l_ids, r_ids])
    side = jnp.concatenate([jnp.zeros(n, jnp.int32),
                            jnp.ones(m, jnp.int32)])
    orig = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.arange(m, dtype=jnp.int32)])
    ids_s, side_s, orig_s = jax.lax.sort([ids2, side, orig], num_keys=2,
                                         is_stable=True)
    counts, starts, rights, rstart = _runs_to_counts(
        ids_s[1:] != ids_s[:-1], side_s, left_outer)
    return counts, starts, rights, rstart, orig_s


# One v5e at the benchmark's three expansions (4.6 M sorted rows to
# 93,752 pairs, 31.7 M and 17.3 M to 2.9 M; PERF.md section 6):
# an operand the select's sort carries costs up to 0.0029 ns per padded
# sorted row per compare-exchange stage (0.0013 at 4.6 M rows), and
# gathering it back costs 13-16 ns a selected row at 2.9 M of them (3 at
# 93,752), so Q12's join gathers and q17's carry.
_CARRY_STAGE_NS = 0.0029
_GATHER_NS = 14.0


def _expand_path(rows: int, total: int) -> str:
    """Which select `_counting_expand` runs for `total` pairs over
    `rows` sorted rows: the rank select of `ops/compact.py` where its
    measured costs say it beats the sort, else the sort select."""
    from hyperspace_tpu.ops import compact
    return "rank" if compact._rank_select_wins(rows, total) else "select"


def _carry_wins(rows: int, size: int) -> bool:
    """Whether the sort select carries an operand for its `size` entries
    more cheaply than they gather it back out of the `rows` rows."""
    levels = max(rows - 1, 1).bit_length()
    carry_ns = (1 << levels) * levels * (levels + 1) / 2 * _CARRY_STAGE_NS
    return carry_ns < size * _GATHER_NS


def _expand(counts, starts, rights, rstart, orig_s, total: int,
            left_outer: bool):
    """`_counting_expand`, with the path it takes on the join's record
    (`expand`) and its fill, pairs over sorted rows, in the registry."""
    rows = counts.shape[0]
    telemetry.annotate(expand=_expand_path(rows, total))
    telemetry.get_registry().histogram("join.expand.fill").observe(
        total / rows)
    return _counting_expand(counts, starts, rights, rstart, orig_s,
                            total=total, left_outer=left_outer)


@instrumented_jit("join.counting_expand", scope="hs.join.expand",
                  static_argnames=("total", "left_outer"))
def _counting_expand(counts, starts, rights, rstart, orig_s, total: int,
                     left_outer: bool):
    """The `total` (left, right) row pairs of a counting match, in
    sorted-row order: sorted row r owns the `counts[r]` slots from
    `starts[r]`, its left row is `orig_s[r]` and its k-th slot's right
    row `orig_s[rstart[r] + k]` (-1 under `left_outer` where the row
    has no right partner, `rights[r]` 0).

    Sized by the pairs, not by the T sorted rows: `jnp.repeat` over the
    counts scattered one update per sorted row, which a TPU serialises
    (8.75 ns a row on a v5e: 40 ms of a TPC-H Q12 query for 93,752
    pairs of 4.6 M rows, 428 ms of a TPC-DS q17 query). The rows that
    own a slot are selected first, by the cheaper of `ops/compact.py`'s
    two selects for the static (T, `total`):

    * rank select: slot j belongs to the first row whose running count
      reaches j + 1, so the select places every slot itself;
    * sort select: one sort puts the owning rows first, in row order,
      keyed by their first slot; what the slots read of a row (its left
      row and the step from a slot to its right row) rides that sort
      where that is cheaper than gathering it back (`_carry_wins`).
      Each row's values are then spread over its slots by scattering
      the change from the row before at its first slot, one update a
      row, and a running sum, which is exact in integers. Where every
      row owns one slot there is nothing to spread.

    On a v5e (PERF.md section 6) it takes 7.5 ms for Q12's 93,752
    pairs, 173 and 143 ms for q17's two joins of 2.9 M, where `repeat`
    and its gathers took 45.6, 575 and 384. The pairs come in the same
    order either way: ascending sorted rows."""
    import jax.numpy as jnp
    from jax import lax

    from hyperspace_tpu.ops import compact

    rows = counts.shape[0]
    # a slot's right row sits at the slot plus its row's `step`; under
    # left_outer a row with no right partner steps before slot 0
    step = rstart - starts
    if left_outer:
        step = jnp.where(rights > 0, step, -total)
    if _expand_path(rows, total) == "rank":
        owner = compact._rank_select(compact._running(counts), total)
        li, step = (a.at[owner].get(mode="promise_in_bounds",
                                    indices_are_sorted=True)
                    for a in (orig_s, step))
    else:
        size = min(total, rows)  # the most rows that can own a slot
        if _carry_wins(rows, size):
            key, li, step = compact._sort_select(counts > 0, size, orig_s,
                                                 step, order=starts)
            start = key.astype(jnp.int32)
        else:
            key, = compact._sort_select(counts > 0, size)
            row = jnp.where(key < jnp.uint32(compact._DEAD), key, 0)
            start, li, step = (a.at[row].get(mode="promise_in_bounds",
                                             indices_are_sorted=True)
                               for a in (starts, orig_s, step))
        owns = key < jnp.uint32(compact._DEAD)
        # the rows past the owners own no slot: their updates drop
        at = jnp.where(owns, start, total)

        def spread(li, step):
            return tuple(
                compact._running(jnp.zeros(total, v.dtype).at[at].add(
                    jnp.diff(v, prepend=jnp.zeros(1, v.dtype)),
                    mode="drop", indices_are_sorted=True))
                for v in (li, step))

        if size == total:  # as many rows as slots: one each, or a spread
            li, step = lax.cond(owns[-1], lambda *v: v, spread, li, step)
        else:
            li, step = spread(li, step)
    right = jnp.arange(total, dtype=jnp.int32) + step
    ri = orig_s.at[jnp.clip(right, 0, rows - 1)].get(
        mode="promise_in_bounds")
    if left_outer:
        ri = jnp.where(right >= 0, ri, jnp.int32(-1))
    return li, ri


def merge_join_indices(left_ids, right_ids, how: str = "inner") -> Tuple:
    """Join row index pairs of two *sorted* id arrays.

    Returns (left_idx, right_idx) device arrays of equal length; for
    how='left_outer' every unmatched left row appears once with right index
    -1. One host sync (the total count) sizes the output.
    """
    import jax.numpy as jnp

    lo = jnp.searchsorted(right_ids, left_ids, side="left")
    hi = jnp.searchsorted(right_ids, left_ids, side="right")
    counts = hi - lo
    if how == "left_outer":
        counts = jnp.maximum(counts, 1)
    starts = jnp.cumsum(counts) - counts  # exclusive cumsum
    total = int(jnp.sum(counts))  # host sync — sizes the result
    if total == 0:
        empty = jnp.zeros(0, dtype=jnp.int32)
        return empty, empty
    slots = jnp.arange(total, dtype=counts.dtype)
    left_idx = jnp.searchsorted(starts, slots, side="right") - 1
    matched = jnp.take(hi, left_idx) > jnp.take(lo, left_idx)
    right_idx = jnp.take(lo, left_idx) + (slots - jnp.take(starts, left_idx))
    right_idx = jnp.where(matched, right_idx, -1)
    return left_idx.astype(jnp.int32), right_idx.astype(jnp.int32)


def unmatched_right_from_indices(ri, num_right: int):
    """Right-row indices absent from a join's right index vector `ri` —
    the rows a FULL OUTER join appends after its left_outer expansion.
    Derived by scatter from the ALREADY-COMPUTED match indices, so the
    keys are never re-encoded. Works on host (numpy) and device arrays;
    the device path costs one host sync to size the output."""
    import numpy as np_

    if isinstance(ri, np_.ndarray):
        matched = np_.zeros(num_right, dtype=bool)
        hit = ri[ri >= 0]
        matched[hit] = True
        return np_.nonzero(~matched)[0].astype(np_.int32)
    import jax.numpy as jnp

    hit = ri >= 0
    matched = jnp.zeros(num_right, dtype=bool).at[
        jnp.where(hit, ri, 0)].max(hit)
    count = int(jnp.sum(~matched))  # host sync
    if count == 0:
        return jnp.zeros(0, dtype=jnp.int32)
    (idx,) = jnp.nonzero(~matched, size=count, fill_value=0)
    return idx.astype(jnp.int32)


def semi_anti_indices(left: ColumnBatch, right: ColumnBatch,
                      left_keys: Sequence[str], right_keys: Sequence[str],
                      anti: bool = False):
    """Left-row indices for LEFT SEMI (has >= 1 match) or LEFT ANTI
    (NOT EXISTS: no match; null-key left rows are emitted) joins. Host
    batches compute in numpy; device batches in one XLA program + one
    host sync."""
    import numpy as np_

    if left.num_rows == 0:
        return np_.zeros(0, dtype=np_.int32)
    if left.is_host and right.is_host:
        if right.num_rows == 0:
            matched = np_.zeros(left.num_rows, dtype=bool)
        else:
            packed = _packed_keys(left, right, left_keys, right_keys)
            if packed is not None:
                lv, rv = packed
                rs = np_.sort(rv)
                matched = (np_.searchsorted(rs, lv, side="left")
                           < np_.searchsorted(rs, lv, side="right"))
            else:
                l_ids, r_ids = _host_encode_join_keys(
                    left, right, left_keys, right_keys)
                rs = np_.sort(r_ids)
                matched = (np_.searchsorted(rs, l_ids, side="left")
                           < np_.searchsorted(rs, l_ids, side="right"))
        mask = ~matched if anti else matched
        return np_.nonzero(mask)[0].astype(np_.int32)
    import jax.numpy as jnp

    if right.num_rows == 0:
        if anti:
            return jnp.arange(left.num_rows, dtype=jnp.int32)
        return jnp.zeros(0, dtype=jnp.int32)
    # Membership via the one-sort counting match over raw key lanes:
    # with left_outer counting, counts > 0 marks exactly the LEFT
    # elements in sorted space, and `rights` holds each element's run
    # match count. Scatter-max back to original row order (right
    # elements carry False so they never touch a left slot).
    lanes_l, lanes_r = _join_lane_operands(left, right, left_keys,
                                           right_keys)

    def membership_mask(counts, rights, orig_s):
        is_left = counts > 0
        hit = is_left & ((rights == 0) if anti else (rights > 0))
        # Right-side orig values (0..m-1) can exceed left.num_rows; they
        # carry hit=False, but drop them explicitly rather than relying
        # on JAX's default out-of-bounds scatter behavior.
        return jnp.zeros(left.num_rows, dtype=bool).at[orig_s].max(
            hit, mode="drop")

    counts, _starts, rights, _rstart, orig_s, collision = _match_lanes(
        lanes_l, lanes_r, True)
    mask = membership_mask(counts, rights, orig_s)
    if collision is None:
        count = int(jnp.sum(mask))  # host sync
    else:
        count, collided = _packed_sync(jnp.sum(mask, dtype=jnp.int64),
                                       collision)
        if collided:  # hash collision: exact re-run
            _hashed_fallback()
            counts, _starts, rights, _rstart, orig_s = \
                _counting_match_lanes(lanes_l, lanes_r, True)
            mask = membership_mask(counts, rights, orig_s)
            count = int(jnp.sum(mask))
    if count == 0:
        return jnp.zeros(0, dtype=jnp.int32)
    (idx,) = jnp.nonzero(mask, size=count, fill_value=0)
    return idx.astype(jnp.int32)


def sort_merge_join(left: ColumnBatch, right: ColumnBatch,
                    left_keys: Sequence[str], right_keys: Sequence[str],
                    how: str = "inner", columns=None):
    """Join of two batches on equi-keys (inner / left_outer / right_outer
    / full_outer). Neither side needs to be pre-sorted: the device lane
    matches unsorted group ids in original row space
    (`counting_join_indices`), the host lane sorts ids internally.

    full_outer = the left_outer expansion plus one appended row per
    unmatched right row (the index-pair machinery both outer sides share).

    Output column names are left's then right's; duplicate names get a
    `_r` suffix on the right.
    """
    import jax.numpy as jnp

    from hyperspace_tpu.ops.bucketed_join import assemble_join_output

    if left.is_host and right.is_host:
        # Adaptive host lane: both sides host-resident (small reads) —
        # the whole join runs in numpy, no device round-trips.
        import numpy as np_
        if how == "right_outer":
            ri, li = host_join_indices(right, left, right_keys, left_keys,
                                       how="left_outer")
        else:
            li, ri = host_join_indices(
                left, right, left_keys, right_keys,
                how="left_outer" if how == "full_outer" else how)
            if how == "full_outer":
                extra = unmatched_right_from_indices(ri, right.num_rows)
                li = np_.concatenate(
                    [li, np_.full(len(extra), -1, dtype=np_.int32)])
                ri = np_.concatenate([ri, extra])
        return assemble_join_output(left, right, li, ri, how=how,
                                    columns=columns)

    # Device lane: the counting join works in ORIGINAL row space over
    # raw key lanes — ONE fused sort+count executable, no dense-id
    # pre-encode, no argsort, no searchsorted.
    if how == "right_outer":
        ri, li = counting_join_batch_indices(right, left, right_keys,
                                             left_keys, how="left_outer")
    else:
        li, ri = counting_join_batch_indices(
            left, right, left_keys, right_keys,
            how="left_outer" if how == "full_outer" else how)
        if how == "full_outer":
            extra = unmatched_right_from_indices(ri, right.num_rows)
            li = jnp.concatenate(
                [li, jnp.full(extra.shape[0], -1, dtype=jnp.int32)])
            ri = jnp.concatenate([ri, extra])
    return assemble_join_output(left, right, li, ri, how=how,
                                columns=columns)


# ---------------------------------------------------------------------------
# Host lane (numpy): same join semantics, zero device round-trips.
# ---------------------------------------------------------------------------


def _host_encode_join_keys(left: ColumnBatch, right: ColumnBatch,
                           left_keys: Sequence[str],
                           right_keys: Sequence[str]):
    """Host mirror of `encode_join_keys` over numpy-backed batches:
    order-preserving dense group ids with null sentinels -1/-2."""
    import numpy as np

    from hyperspace_tpu.io.columnar import _merged_dictionary
    from hyperspace_tpu.ops.keys import host_key_lanes

    if len(left_keys) != len(right_keys) or not left_keys:
        raise HyperspaceException("Join requires matching key column lists.")
    n, m = left.num_rows, right.num_rows
    operands: List = []
    l_valid = np.ones(n, dtype=bool)
    r_valid = np.ones(m, dtype=bool)
    for lk, rk in zip(left_keys, right_keys):
        lcol, rcol = left.column(lk), right.column(rk)
        if lcol.is_string != rcol.is_string:
            raise HyperspaceException(f"Join key type mismatch: {lk} vs {rk}")
        if lcol.validity is not None:
            l_valid = l_valid & np.asarray(lcol.validity)
        if rcol.validity is not None:
            r_valid = r_valid & np.asarray(rcol.validity)
        if lcol.is_string:
            _, (remap_l, remap_r), _ = _merged_dictionary(
                [lcol.dictionary, rcol.dictionary], device=False)
            operands.append(np.concatenate([remap_l[lcol.data],
                                            remap_r[rcol.data]]))
            continue
        ldata, rdata = lcol.data, rcol.data
        if ldata.dtype != rdata.dtype:
            common = np.promote_types(ldata.dtype, rdata.dtype)
            ldata = ldata.astype(common)
            rdata = rdata.astype(common)
        for ll, rl in zip(host_key_lanes(ldata), host_key_lanes(rdata)):
            operands.append(np.concatenate([ll, rl]))
    from hyperspace_tpu.ops.keys import host_dense_group_ids

    validity_key = np.concatenate([l_valid, r_valid])
    perm, group_sorted = host_dense_group_ids([validity_key, *operands])
    groups = np.empty(n + m, dtype=np.int32)
    groups[perm] = group_sorted
    l_ids = np.where(l_valid, groups[:n], np.int32(-1))
    r_ids = np.where(r_valid, groups[n:], np.int32(-2))
    return l_ids, r_ids


def _host_merge_join_indices(left_ids, right_ids, how: str = "inner"):
    """Numpy mirror of `merge_join_indices` over sorted id arrays."""
    import numpy as np

    lo = np.searchsorted(right_ids, left_ids, side="left")
    hi = np.searchsorted(right_ids, left_ids, side="right")
    counts = hi - lo
    if how == "left_outer":
        counts = np.maximum(counts, 1)
    total = int(counts.sum())
    if total == 0:
        empty = np.zeros(0, dtype=np.int32)
        return empty, empty
    left_idx = np.repeat(np.arange(len(left_ids)), counts)
    starts = np.cumsum(counts) - counts
    offsets = np.arange(total) - starts[left_idx]
    matched = hi[left_idx] > lo[left_idx]
    right_idx = np.where(matched, lo[left_idx] + offsets, -1)
    return left_idx.astype(np.int32), right_idx.astype(np.int32)


def _packed_keys(left: ColumnBatch, right: ColumnBatch,
                 left_keys: Sequence[str], right_keys: Sequence[str]):
    """(left_vals, right_vals) int64/float arrays whose scalar order equals
    the key-tuple lexicographic order, or None when the keys are not
    packable (strings, nulls, ranges too wide). Single numeric key returns
    the values as-is; multi-key packs integer tuples into one int64 via
    per-column offsets and range products (order-preserving because every
    column contributes a non-negative bounded digit)."""
    import numpy as np

    if len(left_keys) != len(right_keys) or not left_keys:
        raise HyperspaceException("Join requires matching key column lists.")
    lvals, rvals = [], []
    for lk, rk in zip(left_keys, right_keys):
        lcol, rcol = left.column(lk), right.column(rk)
        if (lcol.is_string or rcol.is_string or lcol.validity is not None
                or rcol.validity is not None):
            return None
        ld, rd = np.asarray(lcol.data), np.asarray(rcol.data)
        if ld.dtype != rd.dtype:
            common = np.promote_types(ld.dtype, rd.dtype)
            ld, rd = ld.astype(common), rd.astype(common)
        lvals.append(ld)
        rvals.append(rd)
    if len(lvals) == 1:
        return lvals[0], rvals[0]
    if any(v.dtype.kind == "f" for v in lvals):
        return None  # float digits don't pack
    mins, ranges = [], []
    for ld, rd in zip(lvals, rvals):
        if len(ld) == 0 and len(rd) == 0:
            mins.append(0)
            ranges.append(1)
            continue
        mn = min(int(ld.min()) if len(ld) else int(rd.min()),
                 int(rd.min()) if len(rd) else int(ld.min()))
        mx = max(int(ld.max()) if len(ld) else int(rd.max()),
                 int(rd.max()) if len(rd) else int(ld.max()))
        mins.append(mn)
        ranges.append(mx - mn + 1)
    capacity = 1
    for r in ranges:
        capacity *= r
        if capacity > 1 << 62:
            return None
    lp = np.zeros(len(lvals[0]), dtype=np.int64)
    rp = np.zeros(len(rvals[0]), dtype=np.int64)
    for ld, rd, mn, r in zip(lvals, rvals, mins, ranges):
        lp = lp * r + (ld.astype(np.int64) - mn)
        rp = rp * r + (rd.astype(np.int64) - mn)
    return lp, rp


def _host_probe_join_indices(lv, rv, how: str) -> Tuple:
    """Probe join over packed scalar keys: sort ONLY the right side, then
    per-left-row match ranges via searchsorted — no sort of the (usually
    much larger) probe side."""
    import numpy as np

    r_order = np.argsort(rv, kind="stable")
    rs = rv[r_order]
    lo = np.searchsorted(rs, lv, side="left")
    hi = np.searchsorted(rs, lv, side="right")
    counts = hi - lo
    if how == "left_outer":
        counts = np.maximum(counts, 1)
    total = int(counts.sum())
    if total == 0:
        empty = np.zeros(0, dtype=np.int32)
        return empty, empty
    left_idx = np.repeat(np.arange(len(lv)), counts)
    starts = np.cumsum(counts) - counts
    offsets = np.arange(total) - starts[left_idx]
    if how == "inner":
        right_idx = r_order[lo[left_idx] + offsets]
    else:
        matched = hi[left_idx] > lo[left_idx]
        right_idx = np.where(
            matched, r_order[np.clip(lo[left_idx] + offsets, 0,
                                     max(len(rv) - 1, 0))], -1)
    return left_idx.astype(np.int32), right_idx.astype(np.int32)


def host_join_indices(left: ColumnBatch, right: ColumnBatch,
                      left_keys: Sequence[str], right_keys: Sequence[str],
                      how: str = "inner") -> Tuple:
    """Join row-index pairs computed entirely on the host (numpy) for
    host-lane batches. `how` is inner or left_outer (callers swap sides
    for right_outer). Null-free numeric keys take the probe path (only
    the build side is sorted); everything else goes through the general
    dense-group-id encode."""
    import numpy as np

    empty = np.zeros(0, dtype=np.int32)
    if left.num_rows == 0:
        return empty, empty
    if right.num_rows == 0:
        if how == "left_outer":
            return (np.arange(left.num_rows, dtype=np.int32),
                    np.full(left.num_rows, -1, dtype=np.int32))
        return empty, empty

    packed = _packed_keys(left, right, left_keys, right_keys)
    if packed is not None:
        return _host_probe_join_indices(packed[0], packed[1], how)

    l_ids, r_ids = _host_encode_join_keys(left, right, left_keys, right_keys)
    l_perm = np.argsort(l_ids, kind="stable")
    r_perm = np.argsort(r_ids, kind="stable")
    li_s, ri_s = _host_merge_join_indices(l_ids[l_perm], r_ids[r_perm],
                                          how=how)
    if len(li_s) == 0:
        return li_s, ri_s
    li = l_perm[li_s].astype(np.int32)
    ri = np.where(ri_s >= 0, r_perm[np.clip(ri_s, 0, None)],
                  -1).astype(np.int32)
    return li, ri


def host_bucketed_join_indices(left: ColumnBatch, right: ColumnBatch,
                               l_lengths, r_lengths,
                               left_keys: Sequence[str],
                               right_keys: Sequence[str],
                               how: str = "inner") -> Tuple:
    """Host join over concat-in-bucket-order sides that EXPLOITS the index
    layout: keys within each bucket arrive sorted from the bucketed write,
    so matching is per-bucket `searchsorted` — no sort, no hash table; the
    structural win the reference buys from Spark's bucketed SMJ
    (`JoinIndexRule.scala:41-43`). Fast path: single numeric null-free
    key; anything else falls back to the general host sort join."""
    import numpy as np

    packed = (None if how not in ("inner", "left_outer")
              else _packed_keys(left, right, left_keys, right_keys))
    if packed is None:
        return host_join_indices(left, right, left_keys, right_keys,
                                 how="left_outer" if how == "left_outer"
                                 else "inner")
    # Packing is monotone in key-tuple order, so within-bucket sortedness
    # of the key tuples carries over to the packed scalars.
    lkey, rkey = packed
    B = len(l_lengths)
    lb = np.concatenate([[0], np.cumsum(l_lengths)]).astype(np.int64)
    rb = np.concatenate([[0], np.cumsum(r_lengths)]).astype(np.int64)

    def _unsorted_within(key, bounds):
        if len(key) <= 1:
            return False
        in_bucket = np.ones(len(key) - 1, dtype=bool)
        boundary = bounds[1:-1]
        boundary = boundary[(boundary > 0) & (boundary < len(key))]
        in_bucket[boundary - 1] = False
        return not (key[1:][in_bucket] >= key[:-1][in_bucket]).all()

    # Sides must be sorted within each bucket (multi-run buckets from
    # incremental refresh are concatenated unsorted): one vectorized check
    # per side; repair with a per-bucket stable sort.
    r_perm = None
    if _unsorted_within(rkey, rb):
        bucket_of = np.searchsorted(rb[1:], np.arange(len(rkey)),
                                    side="right")
        r_perm = np.lexsort((rkey, bucket_of)).astype(np.int64)
        rkey = rkey[r_perm]

    # Native lane: multithreaded C++ per-bucket merge join emits the
    # (li, ri) pairs directly — no searchsorted pass, no numpy expansion
    # (the host lane's two dominant costs at millions of rows). Requires
    # the LEFT side sorted within buckets too (the index layout's
    # guarantee; repaired above only for the right), so check-and-fall-
    # through when it is not.
    if (lkey.dtype == np.int64 and rkey.dtype == np.int64
            and not _unsorted_within(lkey, lb)):
        from hyperspace_tpu import native
        pairs = native.bucketed_merge_join_i64(
            lkey, rkey, lb, rb, left_outer=(how == "left_outer"))
        if pairs is not None:
            li, ri = pairs
            if r_perm is not None and len(ri):
                ri = np.where(ri >= 0,
                              r_perm[np.clip(ri, 0, None)], -1
                              ).astype(np.int32)
            return li, ri

    lo = np.empty(len(lkey), dtype=np.int64)
    hi = np.empty(len(lkey), dtype=np.int64)
    for b in range(B):
        ls, le = lb[b], lb[b + 1]
        rs, re = rb[b], rb[b + 1]
        if le == ls:
            continue
        lo[ls:le] = rs + np.searchsorted(rkey[rs:re], lkey[ls:le], "left")
        hi[ls:le] = rs + np.searchsorted(rkey[rs:re], lkey[ls:le], "right")
    counts = hi - lo
    if how == "left_outer":
        counts = np.maximum(counts, 1)
    total = int(counts.sum())
    if total == 0:
        empty = np.zeros(0, dtype=np.int32)
        return empty, empty
    left_idx = np.repeat(np.arange(len(lkey)), counts)
    starts = np.cumsum(counts) - counts
    offsets = np.arange(total) - starts[left_idx]
    if how == "inner":
        # Zero-count rows emit nothing, so every emitted row is a match.
        right_idx = lo[left_idx] + offsets
    else:
        matched = hi[left_idx] > lo[left_idx]
        right_idx = np.where(matched, lo[left_idx] + offsets, -1)
    if r_perm is not None:
        right_idx = np.where(right_idx >= 0,
                             r_perm[np.clip(right_idx, 0, None)], -1)
    return left_idx.astype(np.int32), right_idx.astype(np.int32)
