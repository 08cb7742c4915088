"""The filter's two sizing pieces as NAMED device programs.

A filter keeps the rows whose mask is true: `compact_indices` turns the
mask into the survivors' row indices, and, for a bucketed batch,
`bucket_survivors` counts the survivors per bucket. Each is one jitted
program named for what it does (`jit_hs_compact`, `jit_hs_segsum`) with
its ops under the matching device scope (`telemetry.DEVICE_SCOPES`:
`hs.compact`, `hs.segsum`), so a reducer finds "the compaction, whatever
implements it" by name.

Neither scatters. `jnp.nonzero(size=)` is a scatter-add of one update
per mask ROW, which a TPU serialises (83 ns a row under x64: 0.5 s for
6,000,000 rows whatever the survivor count). Here whatever depends on
the data is sized by the survivors or the buckets, past one cheap pass
over the rows (the mask's int32 prefix sum, or one sort of row numbers):

* sparse masks (under about half a percent kept, on a v5e) — RANK
  SELECT: the k-th survivor is the first row whose prefix reaches k + 1,
  a binary search of `size` ranks over the prefix
  (`ceil(log2(rows + 1))` unrolled rounds of a `size`-element gather);
* denser masks — SORT SELECT: one single-operand sort of the row numbers
  with the dead rows' top bit set puts the survivors first, ascending,
  at a cost that does not depend on `size` (and no prefix sum);
* per-bucket counts: the batch is in bucket order, so a bucket's count
  is the difference of the prefix at its two ends.

Which select runs is decided from the two static shapes (`rows`,
`size`) when the program is traced, by the measured costs below; the
counting join's expansion (`ops/join._counting_expand`) chooses between
the same two selects the same way. The
indices are what `jnp.nonzero(mask, size=size, fill_value=0)` gives, to
the bit, as int32 (int64 from 2**31 rows on). The programs compile per
mask length and survivor count; the host syncs around them are the
callers'.
"""

from __future__ import annotations

from functools import partial

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu import telemetry

# One v5e, 4.2 M to 18 M rows, 0.3%-100% kept (PERF.md section 6, PR 26's
# density table): the prefix sum costs 0.26-0.31 ns a row and a
# rank-select round 6.6-7.2 ns per gathered element; the single-operand
# sort runs as a bitonic network over the row count padded to a power of
# two, 2.5-2.7 ps per element per compare-exchange stage (6.25 ms at 6 M
# rows, 11.2 at 10 M, 28.8 at 18 M; a row count just over a power of two
# sorts up to 19% faster than that, so next to the crossover, 0.3-0.9%
# kept, the choice can be that far from the better one).
_PREFIX_ROW_NS = 0.3
_GATHER_NS = 7.0
_SORT_STAGE_NS = 0.0026

_LANES = 128  # the prefix sum's block: one row of a TPU tile

_compact_jit = None
_segsum_jit = None


def _rank_select_wins(rows: int, size: int) -> bool:
    """Rank select's prefix sum and gathers against the sort's stages,
    from the static shapes alone."""
    levels = max(rows - 1, 1).bit_length()
    sort_ns = (1 << levels) * levels * (levels + 1) / 2 * _SORT_STAGE_NS
    rank_ns = rows * _PREFIX_ROW_NS + size * rows.bit_length() * _GATHER_NS
    return rank_ns <= sort_ns


def _running(x):
    """Inclusive running sum of the int vector `x`, 128 at a time: sums
    inside each row of 128, the rows' totals summed the same way, added
    back. It is the program XLA's own rewriter makes of `jnp.cumsum`'s
    one wide window, written out because that rewriter's ops come out
    without metadata, so no device scope would reach the prefix sum."""
    import jax.numpy as jnp
    from jax import lax
    n = x.shape[0]
    zero = x.dtype.type(0)
    if n <= _LANES:
        at = jnp.arange(n, dtype=jnp.int32)
        return jnp.sum(jnp.where(at[:, None] <= at[None, :], x[:, None],
                                 zero), axis=0, dtype=x.dtype)
    rows = jnp.pad(x, (0, -n % _LANES)).reshape(-1, _LANES)
    within = lax.reduce_window(rows, zero, lax.add, (1, _LANES), (1, 1),
                               ((0, 0), (_LANES - 1, 0)))
    totals = within[:, -1]
    before = _running(totals) - totals
    return (within + before[:, None]).reshape(-1)[:n]


def _prefix(mask):
    """Inclusive running count of true rows (int32 under 2**31 rows)."""
    import jax.numpy as jnp
    wide = mask.shape[0] >= 2**31
    return _running(mask.astype(jnp.int64 if wide else jnp.int32))


def _rank_select(prefix, size: int):
    """For k < `size`, the first row whose `prefix` reaches k + 1 (0
    where none does): `searchsorted(prefix, k + 1, side="left")` as its
    `rows.bit_length()` rounds, unrolled — a `while` would show in a
    capture as an op AND its body's ops, and count twice under the
    scope. Every probe lies inside the prefix, and the probes of
    ascending ranks ascend, which the gather is told."""
    import jax.numpy as jnp
    rows = prefix.shape[0]
    ranks = jnp.arange(1, size + 1, dtype=prefix.dtype)
    below = jnp.zeros(size, prefix.dtype)  # prefix[below - 1] < rank
    reach = jnp.full(size, rows, prefix.dtype)  # prefix[reach] >= rank
    for _ in range(rows.bit_length()):
        mid = below + (reach - below) // 2
        at = prefix.at[mid].get(mode="promise_in_bounds",
                                indices_are_sorted=True)
        left = ranks <= at
        below = jnp.where(left, below, mid)
        reach = jnp.where(left, mid, reach)
    return jnp.where(reach < rows, reach, 0)


_DEAD = 1 << 31  # a sort select's key bit for the rows it drops


def _sort_select(mask, size: int, *payload, order=None):
    """The first `size` entries of one sort that puts the `mask`'s rows
    first, in ascending `order` (distinct values under 2**31 where the
    mask is true; by default the row numbers): the sorted keys, which
    are each kept row's `order` value and each dropped row's number with
    the `_DEAD` bit set, and every `payload` operand carried along."""
    import jax.numpy as jnp
    from jax import lax
    row = lax.iota(jnp.uint32, mask.shape[0])
    kept = row if order is None else order.astype(jnp.uint32)
    dead = jnp.uint32(_DEAD)
    # the keys are distinct: a stable sort would carry a second operand
    # of row numbers for nothing
    ordered = lax.sort((jnp.where(mask, kept, row | dead), *payload),
                       num_keys=1, is_stable=False)
    return [operand[:size] for operand in ordered]


def compact_indices(mask, count: int):
    """Row indices of the true entries of the device `mask`, ascending:
    `count` of them (entries past the last survivor are 0)."""
    global _compact_jit
    if _compact_jit is None:
        import jax.numpy as jnp

        @partial(telemetry.instrumented_jit, "hs.compact",
                 scope="hs.compact", static_argnames=("size",))
        def hs_compact(mask, size):
            rows = mask.shape[0]
            # the sort's keys are 32 bits, one of them the dead rows',
            # and it has only `rows` entries to hand out
            sortable = rows < 2**31 and size <= rows
            if not sortable or _rank_select_wins(rows, size):
                return _rank_select(_prefix(mask), size)
            first, = _sort_select(mask, size)
            return jnp.where(first < jnp.uint32(_DEAD), first,
                             0).astype(jnp.int32)

        _compact_jit = hs_compact
    return _compact_jit(mask, size=int(count))


def bucket_survivors(mask, lengths):
    """Per-bucket counts of true `mask` rows, for a batch laid out in
    bucket order with `lengths` rows per bucket: the mask's prefix sum
    read at the buckets' ends."""
    global _segsum_jit
    if _segsum_jit is None:
        import jax.numpy as jnp

        @partial(telemetry.instrumented_jit, "hs.segsum",
                 scope="hs.segsum")
        def hs_segsum(mask, lengths):
            prefix = _prefix(mask)
            ends = _running(lengths)
            upto = jnp.where(ends > 0, prefix[jnp.maximum(ends, 1) - 1], 0)
            return jnp.diff(upto, prepend=jnp.zeros(1, upto.dtype))

        _segsum_jit = hs_segsum
    import jax.numpy as jnp
    return _segsum_jit(mask, jnp.asarray(lengths, dtype=jnp.int64))
