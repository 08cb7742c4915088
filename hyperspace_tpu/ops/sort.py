"""Device sort kernels: stable multi-key (lexicographic) sort.

The reference delegates per-bucket sorting to Spark's bucketed write
(`index/DataFrameWriterExtensions.scala:49-66`); here sorting is a single
XLA `lax.sort` over all key columns at once (`num_keys` gives lexicographic
order; `is_stable` preserves input order for ties), with an iota operand to
extract the permutation that is then gathered across every payload column.
XLA lowers this to its bitonic/radix sorter tiled for the TPU VPU.

Order semantics: ascending, nulls first (validity participates as the
leading sub-key for nullable columns; False < True places nulls ahead).
String columns sort by dictionary code, which is order-preserving because
dictionaries are sorted at encode time (`io/columnar.py`).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from hyperspace_tpu.io.columnar import ColumnBatch


def _as_u32(lane, xp):
    """Order-preserving uint32 form of a sort lane (host or device).
    Signed lanes REINTERPRET (not convert) then bias: signed->unsigned
    value conversion of negatives is backend-defined on TPU, the bit
    pattern is not. (No float lanes exist: float keys always decompose
    to uint32 bit-transform lanes, on every backend.)"""
    import numpy as _np

    dt = lane.dtype
    if dt == bool:
        return lane.astype(xp.uint32)
    if xp.issubdtype(dt, xp.signedinteger):
        if xp is _np:
            return lane.astype(_np.int32).view(_np.uint32) \
                ^ _np.uint32(0x80000000)
        import jax
        return jax.lax.bitcast_convert_type(
            lane.astype(xp.int32), xp.uint32) ^ xp.uint32(0x80000000)
    return lane.astype(xp.uint32)


def _descend(lane, xp):
    """Map a sort lane to its DESCENDING-order equivalent: convert to the
    unsigned order-preserving form, then bitwise-invert. Applied to the
    validity lane too, which flips null placement to nulls-last —
    Spark's default for descending keys."""
    return ~_as_u32(lane, xp)


def _key_operands(batch: ColumnBatch, by: Sequence[str]) -> List:
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import column_sort_lanes
    from hyperspace_tpu.plan.nodes import sort_direction
    operands = []
    for spec in by:
        name, desc = sort_direction(spec)
        # 32-bit order-preserving lanes (validity first: nulls-first order).
        lanes = column_sort_lanes(batch.column(name))
        if desc:
            lanes = [_descend(lane, jnp) for lane in lanes]
        operands.extend(lanes)
    return operands


def sort_permutation(batch: ColumnBatch, by: Sequence[str],
                     leading_keys: Optional[Sequence] = None):
    """Stable lexicographic sort permutation by `by` columns; optional
    `leading_keys` (e.g. bucket ids) sort before them. Host-lane batches
    sort with np.lexsort (stable) — no device round-trip."""
    if batch.is_host and not leading_keys:
        import numpy as np

        from hyperspace_tpu import native
        from hyperspace_tpu.ops.keys import host_column_sort_lanes
        from hyperspace_tpu.plan.nodes import sort_direction
        operands = []
        for spec in by:
            name, desc = sort_direction(spec)
            lanes = host_column_sort_lanes(batch.column(name))
            if desc:
                lanes = [_descend(lane, np) for lane in lanes]
            operands.extend(lanes)
        # Native radix lane first (4-7x np.lexsort on wide TPC-DS sorts);
        # the C++ kernel is stable over packed u64 words like lexsort.
        nat = native.key_sort_perm(batch.num_rows, operands)
        if nat is not None:
            return nat
        # np.lexsort's primary key is the LAST operand.
        return np.lexsort(tuple(reversed(operands))).astype(np.int32)
    from hyperspace_tpu.ops.keys import staged_sort_permutation

    operands = list(leading_keys or []) + _key_operands(batch, by)
    return staged_sort_permutation(operands)


def sort_batch(batch: ColumnBatch, by: Sequence[str],
               leading_keys: Optional[Sequence] = None) -> ColumnBatch:
    return batch.take(sort_permutation(batch, by, leading_keys))


# ---------------------------------------------------------------------------
# Top-k (ORDER BY + LIMIT collapsed): the full wide sort is wasted work
# when only k rows survive — and its chunked-LSD executable costs
# minutes of one-time TPU compile at novel shapes. The
# device path sorts ONE packed prefix lane to find the k-th prefix value,
# keeps the candidate rows (every true top-k row has prefix <= that
# threshold, since > means at least k rows order strictly before it),
# and finishes with an exact full-key host sort of the small candidate
# set. Ties only ever grow the candidate set, never drop a winner.
# ---------------------------------------------------------------------------

# Candidate sets beyond this fall back to the full sort (low-cardinality
# leading keys: the threshold no longer prunes).
TOPK_CANDIDATE_CAP = 1 << 21

_topk_threshold_jit = None


def _jnp_empty_i32():
    import jax.numpy as jnp
    return jnp.empty(0, dtype=jnp.int32)


def _topk_threshold(prefix, k: int):
    """(mask, count) for rows whose packed prefix is <= the k-th smallest
    prefix value — ONE module-level jitted program (cached across calls;
    a per-call wrapper would recompile every execution)."""
    global _topk_threshold_jit
    if _topk_threshold_jit is None:
        import jax
        import jax.numpy as jnp
        from functools import partial

        from hyperspace_tpu.telemetry import instrumented_jit

        @partial(instrumented_jit, "sort.topk_threshold",
                 scope="hs.topk", static_argnames=("k",))
        def run(prefix, k):
            (sorted_prefix,) = jax.lax.sort([prefix], num_keys=1)
            thresh = sorted_prefix[k - 1]
            mask = prefix <= thresh
            return mask, jnp.sum(mask.astype(jnp.int64))

        _topk_threshold_jit = run
    return _topk_threshold_jit(prefix, k)


def topk_batch(batch: ColumnBatch, by: Sequence[str], n: int) -> ColumnBatch:
    """First `n` rows of `batch` ordered by `by` (stable, identical to
    sort_batch(...)[:n]).

    Residency contract (downstream lane selection keys on `is_host`):
    - host input -> HOST output (pure numpy path);
    - device input, threshold path -> HOST output: the candidate set is
      pulled to the host for the exact full-key finish, and at <= n +
      ties rows re-uploading it would only pay the link again;
    - device input, candidate-cap fallback (low-cardinality prefix; see
      TOPK_CANDIDATE_CAP) -> DEVICE output from the full device sort.
    So a device caller gets a host batch on the common path and a device
    batch on the fallback — by design, not drift: each path leaves the
    rows where its last computation put them, and TopK is a root-adjacent
    operator (ORDER BY + LIMIT) whose small output promotes or transfers
    cheaply either way. The fallback is recorded as a telemetry event
    (`topk.candidate-cap-fallback`) so lane surprises stay diagnosable."""
    import numpy as np

    if n == 0:
        return batch.take(np.empty(0, dtype=np.int32)
                          if batch.is_host else _jnp_empty_i32())
    if batch.num_rows <= n:
        return sort_batch(batch, by)
    if batch.is_host:
        perm = sort_permutation(batch, by)
        return batch.take(np.asarray(perm)[:n].astype(np.int32))

    import os
    import time as _time

    import jax.numpy as jnp

    dbg = os.environ.get("HYPERSPACE_TOPK_DEBUG")
    t0 = _time.perf_counter()
    # Only the first two prefix lanes are consumed; building all ~34
    # lanes of a wide ORDER BY would waste dozens of device dispatches.
    operands = _key_operands(batch, list(by)[:2])
    prefix = _as_u32(operands[0], jnp).astype(jnp.uint64) << jnp.uint64(32)
    if len(operands) > 1:
        prefix = prefix | _as_u32(operands[1], jnp).astype(jnp.uint64)
    mask, count_dev = _topk_threshold(prefix, n)
    count = int(count_dev)  # the one sizing sync
    t1 = _time.perf_counter()
    if count > max(TOPK_CANDIDATE_CAP, 4 * n):
        from hyperspace_tpu import telemetry
        telemetry.event("topk", "candidate-cap-fallback",
                        candidates=count, n=n, rows=batch.num_rows,
                        residency="device")
        full = sort_batch(batch, by)
        return full.take(jnp.arange(n, dtype=jnp.int32))
    # Pad the gather size to powers of two so distinct candidate counts
    # reuse a handful of compiled executables; nonzero places real hits
    # first, so the host slice [:count] drops the padding exactly.
    size = 1 << max(count - 1, 1).bit_length()
    (idx,) = jnp.nonzero(mask, size=size, fill_value=0)
    cand = batch.take(idx.astype(jnp.int32))
    t2 = _time.perf_counter()
    # Issue every candidate array's D2H before the first blocking read:
    # per-column np.asarray would pay ~40 sequential link round-trips.
    for col in cand.columns.values():
        for arr in (col.raw, col.validity, *(col.dict_hashes or ())):
            if arr is not None and hasattr(arr, "copy_to_host_async"):
                try:
                    arr.copy_to_host_async()
                except Exception:
                    pass  # best-effort prefetch only
    host_cols = {}
    from hyperspace_tpu.io.columnar import DeviceColumn, fetched
    for name, col in cand.columns.items():
        host_cols[name] = DeviceColumn(
            data=fetched(np.asarray(col.raw), col.dtype)[:count],
            dtype=col.dtype,
            validity=(np.asarray(col.validity)[:count]
                      if col.validity is not None else None),
            dictionary=col.dictionary,
            dict_hashes=(tuple(np.asarray(h) for h in col.dict_hashes)
                         if col.dict_hashes is not None else None))
    host_cand = ColumnBatch(cand.schema, host_cols)
    perm = sort_permutation(host_cand, by)
    out = host_cand.take(np.asarray(perm)[:n].astype(np.int32))
    if dbg:
        print(f"[topk] n={batch.num_rows} count={count} "
              f"threshold+sync={t1 - t0:.2f}s gather={t2 - t1:.2f}s "
              f"pull+sort={_time.perf_counter() - t2:.2f}s", flush=True)
    return out


def bucket_boundaries(sorted_bucket_ids, num_buckets: int) -> Tuple:
    """(starts, ends) of each bucket's contiguous row range in a batch sorted
    by bucket id. starts[b] == ends[b] for empty buckets."""
    import jax.numpy as jnp

    buckets = jnp.arange(num_buckets, dtype=sorted_bucket_ids.dtype)
    starts = jnp.searchsorted(sorted_bucket_ids, buckets, side="left")
    ends = jnp.searchsorted(sorted_bucket_ids, buckets, side="right")
    return starts, ends
