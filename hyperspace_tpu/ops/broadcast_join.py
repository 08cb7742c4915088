"""Broadcast-style dimension join: replicate a SMALL unique-keyed build
side and match probe rows by direct-address lookup — no Exchange, no
sort of the probe side.

The reference gets BroadcastHashJoin from Spark for free for dimension
joins: its E2E suite has to DISABLE broadcast to even exercise the
bucketed SMJ path (`E2EHyperspaceRulesTests.scala:42`), and production
Spark routes every small-side join here via
`spark.sql.autoBroadcastJoinThreshold`. This engine's general join is
the counting join (`ops/join.py`) whose cost is a joint sort of
probe+build rows — for a fact x dimension join that sort of tens of
millions of fact rows is pure overhead.

The TPU-friendly equivalent of a hash table is a dense lookup TABLE
over the build-side key range: dimension surrogate keys (TPC-DS
`d_date_sk`, `i_item_sk`, `s_store_sk`, ...) are dense integers, so
table size ~ build rows. Build: pack each build key tuple into one
int64 digit space and scatter build row ids into the table (m rows,
computed in numpy — the build side is small and usually host-resident).
Probe: ONE vectorized gather per probe row + range/validity masks —
O(n + m + range) with no sort anywhere. The table transfers to the
device once (int32, ~4B x range).

Eligibility is decided at RUN time from the build side (the planner
only sizes it): integer-family keys on both sides, key-tuple digit
space <= `_MAX_TABLE` slots, and unique non-null build key tuples.
Anything else returns None and the caller falls back to the counting
join — same results, just without the shortcut. Duplicate build keys
would need the ragged expansion machinery; real dimension keys are
unique, so the fallback (not extra complexity here) covers that case.

SQL join-null semantics match `encode_join_keys`: a NULL in any key
column on either side matches nothing.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import numpy as np

from hyperspace_tpu.io.columnar import ColumnBatch

# Integer-family dtypes whose values join by exact integer identity
# (date32/timestamp are day/us counts; bool is 0/1). Floats are excluded:
# the engine's float key identity normalizes -0.0/NaN through order lanes
# (`ops/keys.py`), which a raw int cast would diverge from.
_INT_DTYPES = ("int8", "int16", "int32", "int64", "date32", "timestamp",
               "bool")

# Table slot cap: 16M int32 slots = 64 MB — far above any dimension key
# range worth broadcasting, far below working-set sizes that matter.
_MAX_TABLE = 1 << 24


def _int_key_arrays(batch: ColumnBatch, keys: Sequence[str], to_numpy: bool):
    """Per-key int64 arrays + combined validity, or None when any key is
    outside the integer family. `to_numpy` pulls device columns to host
    (build side only — small)."""
    arrays = []
    valid = None
    for k in keys:
        col = batch.column(k)
        if col.is_string or col.dtype not in _INT_DTYPES:
            return None
        data = np.asarray(col.data) if to_numpy else col.data
        arrays.append(data)
        if col.validity is not None:
            v = np.asarray(col.validity) if to_numpy else col.validity
            valid = v if valid is None else (valid & v)
    return arrays, valid


def build_broadcast_table(build: ColumnBatch, build_keys: Sequence[str]):
    """(table, mins, ranges) for the build side, or None when ineligible.
    `table[packed_key] = build row id`, -1 elsewhere; `mins`/`ranges`
    define the per-column digit packing probe rows must mirror."""
    m = build.num_rows
    if m == 0:
        return None
    prep = _int_key_arrays(build, build_keys, to_numpy=True)
    if prep is None:
        return None
    arrays, valid = prep
    arrays = [np.asarray(a, dtype=np.int64) for a in arrays]
    if valid is not None:
        if not valid.any():
            # All build keys NULL: nothing can match — a 1-slot empty
            # table keeps the probe path uniform.
            return (np.full(1, -1, dtype=np.int32), [0] * len(arrays),
                    [1] * len(arrays))
        arrays_v = [a[valid] for a in arrays]
    else:
        arrays_v = arrays
    mins = [int(a.min()) for a in arrays_v]
    ranges = []
    capacity = 1
    for a, mn in zip(arrays_v, mins):
        r = int(a.max()) - mn + 1
        ranges.append(r)
        capacity *= r
        if capacity > _MAX_TABLE:
            return None
    packed = np.zeros(len(arrays_v[0]), dtype=np.int64)
    for a, mn, r in zip(arrays_v, mins, ranges):
        packed = packed * r + (a - mn)
    table = np.full(capacity, -1, dtype=np.int32)
    rows = (np.nonzero(valid)[0] if valid is not None
            else np.arange(m)).astype(np.int32)
    table[packed] = rows
    # Uniqueness: every valid build row must own its slot (duplicates
    # overwrote each other above — detect by occupancy count).
    if int((table >= 0).sum()) != len(rows):
        return None
    return table, mins, ranges


def _lookup(xp, arrays, valid, table, mins, maxs, ranges):
    """(build_row_or_minus1, matched) per probe row over `xp` (numpy, or
    jax.numpy inside `_broadcast_probe`)."""
    n = arrays[0].shape[0]
    ok = xp.ones(n, dtype=bool) if valid is None else valid
    idx = xp.zeros(n, dtype=np.int64)
    for i, a in enumerate(arrays):
        av = a.astype(np.int64)
        mn, r = mins[i], ranges[i]
        # Range-check on the ORIGINAL values (comparisons cannot wrap);
        # `av - mn` can wrap in int64 for adversarial probe keys near
        # INT64_MIN against builds near INT64_MAX, and a wrapped digit
        # must never slip into [0, r) as a false match. `maxs` is the
        # build max, mn + (r - 1), computed exactly in Python ints.
        ok = ok & (av >= mn) & (av <= maxs[i])
        idx = idx * r + xp.clip(av - mn, 0, r - 1)
    hit = xp.where(ok, xp.take(table, xp.where(ok, idx, 0)),
                   np.int32(-1)).astype(np.int32)
    return hit, hit >= 0


_probe_jit = None


def _device_probe(arrays, valid, table, mins, maxs, ranges):
    """`_lookup` on the device as ONE program, `jit__broadcast_probe`,
    its ops under the device scope `hs.join.broadcast` (inside a fused
    stage the call is inlined into the stage's program and the scope
    stays on its ops). The packing rides as arrays, not as constants:
    one compile per probe shape, whatever the build side holds."""
    global _probe_jit
    if _probe_jit is None:
        import jax.numpy as jnp

        from hyperspace_tpu.telemetry import instrumented_jit

        @instrumented_jit("broadcast_join.probe", scope="hs.join.broadcast")
        def _broadcast_probe(arrays, valid, table, mins, maxs, ranges):
            return _lookup(jnp, arrays, valid, table, mins, maxs, ranges)

        _probe_jit = _broadcast_probe
    return _probe_jit(tuple(arrays), valid, table,
                      *(np.asarray(v, dtype=np.int64)
                        for v in (mins, maxs, ranges)))


def _probe_lookup(probe: ColumnBatch, probe_keys: Sequence[str], table,
                  mins, ranges):
    """(build_row_or_minus1, matched) per probe row, on the probe's lane.
    None when a probe key is outside the integer family."""
    prep = _int_key_arrays(probe, probe_keys, to_numpy=probe.is_host)
    if prep is None:
        return None
    arrays, valid = prep
    maxs = [mn + (r - 1) for mn, r in zip(mins, ranges)]
    lookup = partial(_lookup, np) if probe.is_host else _device_probe
    return lookup(arrays, valid, table, mins, maxs, ranges)


def broadcast_join_indices(probe: ColumnBatch, build: ColumnBatch,
                           probe_keys: Sequence[str],
                           build_keys: Sequence[str],
                           how: str) -> Optional[Tuple]:
    """(probe_idx, build_idx) row-index pairs in original row space for
    `how` in inner/left_outer (probe plays left), or None when the
    direct-address path is ineligible. With unique build keys every probe
    row matches at most once, so no ragged expansion exists: left_outer
    is the identity on probe rows and inner one mask-compress."""
    prep = build_broadcast_table(build, build_keys)
    if prep is None:
        return None
    looked = _probe_lookup(probe, probe_keys, *prep)
    if looked is None:
        return None
    hit, matched = looked
    n = probe.num_rows
    if probe.is_host:
        if how == "left_outer":
            return np.arange(n, dtype=np.int32), hit
        li = np.nonzero(matched)[0].astype(np.int32)
        return li, hit[li]
    import jax.numpy as jnp
    if how == "left_outer":
        return jnp.arange(n, dtype=jnp.int32), hit
    count = int(jnp.sum(matched))  # host sync — sizes the result
    if count == 0:
        empty = jnp.zeros(0, dtype=jnp.int32)
        return empty, empty
    (li,) = jnp.nonzero(matched, size=count, fill_value=0)
    li = li.astype(jnp.int32)
    return li, jnp.take(hit, li)


def build_membership_table(build: ColumnBatch, build_keys: Sequence[str]):
    """(table, mins, ranges) occupancy table over the build side's valid
    key tuples (duplicates allowed — existence is all membership needs),
    or None when ineligible. All-NULL build keys yield a 1-slot empty
    table so the probe path stays uniform. Shared by the eager membership
    probe below and the fused masked lane (`engine/fusion.py`)."""
    prep = _int_key_arrays(build, build_keys, to_numpy=True)
    if prep is None:
        return None
    arrays, valid = prep
    arrays = [np.asarray(a, dtype=np.int64) for a in arrays]
    if valid is not None:
        arrays = [a[valid] for a in arrays]
        if len(arrays[0]) == 0:
            table = np.full(1, -1, dtype=np.int32)
            return table, [0] * len(build_keys), [1] * len(build_keys)
    return _membership_table(arrays)


def broadcast_membership(probe: ColumnBatch, build: ColumnBatch,
                         probe_keys: Sequence[str],
                         build_keys: Sequence[str], anti: bool):
    """Probe-row indices for LEFT SEMI (matched) / LEFT ANTI (unmatched —
    NULL-key probe rows are emitted, NOT EXISTS semantics), or None when
    ineligible. Membership tolerates DUPLICATE build keys (the table
    keeps some row per key; existence is all that matters), so only the
    table build itself can decline."""
    m = build.num_rows
    if m == 0:
        return None  # callers' empty-side fast paths are already exact
    prep2 = build_membership_table(build, build_keys)
    if prep2 is None:
        return None
    looked = _probe_lookup(probe, probe_keys, *prep2)
    if looked is None:
        return None
    _hit, matched = looked
    want = ~matched if anti else matched
    if probe.is_host:
        return np.nonzero(want)[0].astype(np.int32)
    import jax.numpy as jnp
    count = int(jnp.sum(want))  # host sync
    if count == 0:
        return jnp.zeros(0, dtype=jnp.int32)
    (idx,) = jnp.nonzero(want, size=count, fill_value=0)
    return idx.astype(jnp.int32)


def _membership_table(arrays):
    """Occupancy table over valid build keys (duplicates allowed)."""
    mins = [int(a.min()) for a in arrays]
    ranges = []
    capacity = 1
    for a, mn in zip(arrays, mins):
        r = int(a.max()) - mn + 1
        ranges.append(r)
        capacity *= r
        if capacity > _MAX_TABLE:
            return None
    packed = np.zeros(len(arrays[0]), dtype=np.int64)
    for a, mn, r in zip(arrays, mins, ranges):
        packed = packed * r + (a - mn)
    table = np.full(capacity, -1, dtype=np.int32)
    table[packed] = 1
    return table, mins, ranges
