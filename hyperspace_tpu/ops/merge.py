"""Device k-way merge-compaction: every bucket's runs, ONE compiled program.

OptimizeAction compacts the base + incremental delta runs living side by
side in one `v__=N` dir into a single fully-sorted file per bucket
(reference roadmap `/root/reference/ROADMAP.md:66-75` — the surveyed
reference has only full rebuild). The naive implementation loops buckets in
Python and re-sorts each on the device — one fresh XLA compile per novel
bucket shape (tens of seconds on a remote-compile TPU toolchain) and a
blocking sync per bucket.

Here compaction is ONE batched program over a padded [B, L] layout, the
same trick the bucketed join uses (`ops/bucketed_join.py`):

1. key columns decompose into order-preserving 32-bit lanes
   (`ops/keys.py`) — already staged on device;
2. each bucket's rows (its runs concatenated in file order) are gathered
   into a [B, L] matrix, L = next power of two of the largest bucket so
   repeated compactions reuse the compile; padding slots carry a trailing
   pad flag that sorts last;
3. one batched stable `lax.sort` along the row axis orders every bucket at
   once;
4. the per-bucket orderings are flattened back into a single global row
   permutation, split into link-overlap chunks for the D2H fetch.

Why a batched SORT rather than a literal k-way merge loop: on TPU,
`lax.sort` IS the merge primitive — a data-dependent heap/merge loop
serializes on the scalar unit and defeats the VPU, while the bitonic-family
batched sort runs fully vectorized across all buckets simultaneously. The
asymptotic O(L log^2 L) vs O(L log k) trade buys one compile, zero scalar
control flow, and bucket-parallel execution; the runs' pre-sortedness
still helps (a stable sort over nearly-sorted lanes does minimal data
movement in the final permutation application, which is where the real
cost — the payload gather — lives, and that runs on the host in Arrow).

The payload never touches the device (the `_perm_core` lesson,
`ops/build.py`): only key lanes go over the link, and the host applies the
permutation chunk-by-chunk while later chunks are still in flight.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.ops import keys as keymod
from hyperspace_tpu.ops.build import LINK_CHUNK_ROWS, LINK_CHUNKS
# ONE padded-layout builder and pow2 rounding for every [B, L] consumer
# (join, distributed join, compaction) — they must stay in lockstep.
from hyperspace_tpu.ops.bucketed_join import _padded_layout, next_pow2
from hyperspace_tpu.telemetry import instrumented_jit


@instrumented_jit("merge.bucket_sort", scope="hs.build",
                  static_argnames=("n_chunks",))
def _bucket_sort_core(lanes, l_idx, l_valid, flat_pick, n_chunks: int):
    """Batched within-bucket sort permutation.

    lanes: tuple of [N] 32-bit key lanes (validity leading when present);
    l_idx/l_valid: [B, L] padded gather matrix + mask into the
    concat-in-bucket-order row space; flat_pick: [N] int32 positions of the
    valid cells in the row-major [B*L] flattening, in bucket order.
    Returns the [N] row permutation split into n_chunks contiguous slices.
    """
    import jax
    import jax.numpy as jnp

    B, L = l_idx.shape
    pad = (~l_valid).astype(jnp.int32)  # 0 = real row, 1 = padding
    operands = [pad]
    for lane in lanes:
        gathered = jnp.take(lane, l_idx)
        # Padding rows ride the pad flag (leading key); their lane values
        # are the safe-gather duplicates and never affect real ordering.
        operands.append(gathered)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    results = jax.lax.sort([*operands, pos], num_keys=len(operands),
                           is_stable=True, dimension=1)
    pos_sorted = results[-1]
    # original row index occupying sorted slot (b, j)
    orig = jnp.take_along_axis(l_idx, pos_sorted, axis=1).reshape(-1)
    perm = jnp.take(orig, flat_pick)
    n = perm.shape[0]
    base = n // n_chunks
    chunks = tuple(
        jax.lax.slice(perm, (i * base,),
                      ((i + 1) * base if i < n_chunks - 1 else n,))
        for i in range(n_chunks))
    return chunks


def bucket_sort_permutation(key_batch, sort_columns: Sequence[str],
                            lengths: np.ndarray) -> Tuple[List, np.ndarray,
                                                          np.ndarray]:
    """Permutation that sorts every bucket of a concat-in-bucket-order
    batch by `sort_columns`, computed in ONE compiled program across all
    buckets. `key_batch` needs only the key columns resident on device.

    Returns (device perm chunks, starts, ends) shaped exactly like
    `ops/build.build_permutation`, so `io/builder._write_sorted_runs`
    consumes the result unchanged.
    """
    lengths = np.asarray(lengths, dtype=np.int64)
    n = int(lengths.sum())
    B = len(lengths)
    L = next_pow2(max(1, int(lengths.max(initial=0))))

    lanes: List = []
    for name in sort_columns:
        lanes.extend(keymod.column_sort_lanes(key_batch.column(name)))

    l_idx, l_valid = _padded_layout(lengths, L)
    # Valid-cell positions in the row-major [B*L] flattening, bucket order:
    # after the in-row sort, the first lengths[b] slots of row b hold its
    # sorted rows (padding sorts last).
    row_base = np.repeat(np.arange(B, dtype=np.int64) * L, lengths)
    within = np.concatenate([np.arange(c, dtype=np.int64)
                             for c in lengths]) if n else np.zeros(
                                 0, dtype=np.int64)
    flat_pick = (row_base + within).astype(np.int32)

    import jax.numpy as jnp
    n_chunks = LINK_CHUNKS if n >= LINK_CHUNK_ROWS else 1
    n_chunks = max(1, min(n_chunks, max(n, 1)))
    chunks = _bucket_sort_core(tuple(lanes), jnp.asarray(l_idx),
                               jnp.asarray(l_valid),
                               jnp.asarray(flat_pick), n_chunks)
    ends = np.cumsum(lengths)
    starts = ends - lengths
    return list(chunks), starts, ends


def host_merge_runs_permutation(key: np.ndarray, run_bounds):
    """True k-way MERGE permutation for the common compaction shape: per
    bucket, one large sorted base run plus small sorted-ish delta runs,
    over a single null-free integer key column.

    Per bucket the deltas are stable-sorted together (tiny), their insert
    positions into the base run found with ONE searchsorted (side='right'
    — appended rows follow equal-key base rows, the same tie order a
    stable sort of base-then-deltas produces), and the output permutation
    assembled by prefix counting. O(n + k log k + k log n) per bucket with
    NO re-sort of the base run — the asymptotic win a re-sorting
    compaction gives up. Falls back to a bucket-local stable sort when a
    base run is not actually sorted.

    `run_bounds`: per bucket, list of (start, end) global row ranges of
    its runs in version order (base first). Returns ([perm], starts, ends)
    in the writer's shape.
    """
    lengths = np.array([sum(e - s for s, e in runs)
                        for runs in run_bounds], dtype=np.int64)
    total = int(lengths.sum())
    perm = np.empty(total, dtype=np.int64)
    out = 0
    for runs in run_bounds:
        n_bucket = sum(e - s for s, e in runs)
        if n_bucket == 0:
            continue
        (b0, b1) = runs[0]
        base = key[b0:b1]
        if len(runs) == 1:
            perm[out:out + n_bucket] = np.arange(b0, b1)
            out += n_bucket
            continue
        d_idx = np.concatenate([np.arange(s, e) for s, e in runs[1:]])
        if len(base) and not (base[1:] >= base[:-1]).all():
            # Base run unexpectedly unsorted: bucket-local stable sort.
            all_idx = np.concatenate([np.arange(b0, b1), d_idx])
            perm[out:out + n_bucket] = all_idx[
                np.argsort(key[all_idx], kind="stable")]
            out += n_bucket
            continue
        d_sorted = d_idx[np.argsort(key[d_idx], kind="stable")]
        pos = np.searchsorted(base, key[d_sorted], side="right")
        nb, kd = len(base), len(d_sorted)
        # base row i lands at i + #{deltas inserted at or before i}
        shift = np.cumsum(np.bincount(pos, minlength=nb + 1))[:nb]
        local = np.empty(n_bucket, dtype=np.int64)
        local[np.arange(nb) + shift] = np.arange(b0, b1)
        local[pos + np.arange(kd)] = d_sorted
        perm[out:out + n_bucket] = local
        out += n_bucket
    ends = np.cumsum(lengths)
    starts = ends - lengths
    return [perm], starts, ends


def host_bucket_sort_permutation(key_batch, sort_columns: Sequence[str],
                                 lengths: np.ndarray):
    """Host twin: stable sort keyed (bucket, *sort lanes) — the native C++
    radix lane when available (`native.bucket_key_sort_perm`), np.lexsort
    otherwise. Below the device-amortization row count a fresh XLA
    compile can never pay for itself (`io/builder.BUILD_MIN_DEVICE_ROWS`);
    with the native lane the host path also wins at size by skipping the
    link round-trip entirely."""
    from hyperspace_tpu import native

    lengths = np.asarray(lengths, dtype=np.int64)
    bucket_of_row = np.repeat(np.arange(len(lengths), dtype=np.int32),
                              lengths)
    sort_lanes: List = []
    for name in sort_columns:
        sort_lanes.extend(keymod.host_column_sort_lanes(
            key_batch.column(name)))
    ends = np.cumsum(lengths)
    starts = ends - lengths
    nat = native.bucket_key_sort_perm(bucket_of_row, len(lengths),
                                      sort_lanes)
    if nat is not None:
        # Only the permutation is consumed: the native starts/ends are
        # redundant here — bounds computed from `lengths` above agree
        # with the sort's by construction (rows were labeled with the
        # bucket ids those same lengths induce).
        return [nat[0]], starts, ends
    perm = np.lexsort(tuple(reversed([bucket_of_row] + sort_lanes)))
    return [perm.astype(np.int64)], starts, ends
