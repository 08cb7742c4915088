"""Jitted index-build core: hash + bucket + sort + gather in ONE XLA program.

The eager pipeline dispatches ~a dozen separately-compiled ops; on a TPU
with remote compilation each unique (op, shape) costs a compile round-trip.
Fusing the whole build into one `jax.jit` program makes the build one
compile per (schema structure, row count) — and lets XLA fuse the hash mix,
key-lane decomposition, sort, and payload gathers.

Sort keys ride 32-bit lanes (`ops/keys.py`): int64/float64 keys become two
native 32-bit operands instead of emulated 64-bit compares on the VPU.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.io.columnar import (ColumnBatch, batch_to_tree,
                                        tree_to_batch)
from hyperspace_tpu.ops import keys as keymod
from hyperspace_tpu.ops.pallas.hash_kernel import pallas_available
from hyperspace_tpu.telemetry import instrumented_jit


def _tree_hash_lanes(entry):
    """Hash-input lanes of one column tree entry (mirrors
    `ops/hash_partition.column_hash_lanes` on raw arrays): strings gather
    their dictionary value hashes; numerics decompose into 32-bit key
    lanes; null rows contribute all-zero lanes. A `lo32` entry is the
    narrow transport of an int64 column whose hi lane is provably zero
    (host-checked range): the hash still mixes the canonical [hi, lo]
    lane chain — hi synthesized as zeros — so bucket ids are bit-identical
    to the wide path."""
    import jax.numpy as jnp

    entry = _entry_assemble(entry)
    if "lo32" in entry:
        lo = entry["lo32"]
        return [jnp.zeros_like(lo), lo]
    data = entry["data"]
    if "hash_hi" in entry:
        lanes = [jnp.take(entry["hash_hi"], data),
                 jnp.take(entry["hash_lo"], data)]
    else:
        lanes = [lane.astype(jnp.uint32)
                 for lane in keymod.key_lanes(data)]
    if "validity" in entry:
        lanes = [jnp.where(entry["validity"], lane, jnp.uint32(0))
                 for lane in lanes]
    return lanes


def _entry_sort_lanes(entry):
    entry = _entry_assemble(entry)
    if "lo32" in entry:
        # hi lane is constant zero -> order is fully determined by lo.
        return [entry["lo32"]]
    lanes = []
    if "validity" in entry:
        lanes.append(entry["validity"])
    lanes.extend(keymod.key_lanes(entry["data"]))
    return lanes


def _tree_bucket_ids(tree, key_names: Tuple[str, ...], num_buckets: int,
                     use_pallas: bool):
    """Per-row bucket ids over the FLAT lane chain (the one shared hash
    identity, `ops/hash_partition.flat_hash32`) — the Pallas kernel and the
    jnp fold are bit-identical by construction. Callers pass
    `use_pallas=pallas_available()`: the kernel exactly when the backend
    is a TPU, the fold as the other backends' lowering of the same
    identity — not a switchable fallback."""
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash_partition import flat_hash32
    from hyperspace_tpu.ops.pallas.hash_kernel import hash_lanes_to_buckets

    lanes = []
    for name in key_names:
        lanes.extend(_tree_hash_lanes(tree[name]))
    if use_pallas:
        return hash_lanes_to_buckets(lanes, num_buckets)
    h = flat_hash32(lanes)
    return (h % jnp.uint32(num_buckets)).astype(jnp.int32)


@instrumented_jit("build.build_core", scope="hs.build",
                  static_argnames=("key_names", "num_buckets", "use_pallas"))
def _build_core(tree, key_names: Tuple[str, ...], num_buckets: int,
                use_pallas: bool = False):
    import jax
    import jax.numpy as jnp

    bucket = _tree_bucket_ids(tree, key_names, num_buckets, use_pallas)

    n = bucket.shape[0]
    operands = [bucket]
    for name in key_names:
        operands.extend(_entry_sort_lanes(tree[name]))
    iota = jnp.arange(n, dtype=jnp.int32)
    results = jax.lax.sort([*operands, iota], num_keys=len(operands),
                           is_stable=True)
    perm = results[-1]
    sorted_bucket = results[0]

    sorted_tree = {}
    for name, entry in tree.items():
        out = dict(entry)  # hash tables are dictionary-indexed: pass through
        out["data"] = jnp.take(entry["data"], perm, axis=0)
        if "validity" in entry:
            out["validity"] = jnp.take(entry["validity"], perm, axis=0)
        sorted_tree[name] = out

    buckets = jnp.arange(num_buckets, dtype=jnp.int32)
    starts = jnp.searchsorted(sorted_bucket, buckets, side="left")
    ends = jnp.searchsorted(sorted_bucket, buckets, side="right")
    return sorted_tree, sorted_bucket, starts, ends


# Legacy transfer policy: split transfers of >= LINK_CHUNK_ROWS rows
# into LINK_CHUNKS concurrent streams (values from a device link that
# no longer exists; unmeasured on an attached chip). H2D staging and the
# build's D2H permutation fetch now size their chunks from the transfer
# engine's byte budget (`io/transfer.py`); these remain for the
# compaction merge path (`ops/merge.py`).
LINK_CHUNK_ROWS = 1 << 19
LINK_CHUNKS = 4


def _entry_assemble(entry):
    """Reassemble a chunk-staged entry (lo32 shipped as LINK_CHUNKS
    concurrent H2D streams) into its single-array form inside the compiled
    program. Called by every entry reader so ALL consumers of a staged
    tree handle the chunked form."""
    import jax.numpy as jnp

    if "lo32_chunks" in entry:
        return {"lo32": jnp.concatenate(entry["lo32_chunks"])}
    return entry


@instrumented_jit("build.perm_core", scope="hs.build",
                  static_argnames=("key_names", "num_buckets", "n_chunks",
                                   "use_pallas"))
def _perm_core(key_tree, key_names: Tuple[str, ...], num_buckets: int,
               n_chunks: int, use_pallas: bool = False):
    """Permutation-only build core: hash + ONE stable (bucket, *keys) sort
    over the KEY columns, returning the int32 row permutation (split into
    n_chunks contiguous slices for overlapped D2H) + per-bucket ranges.

    The payload never touches the device: the D2H of gathered payload
    columns is 8+ bytes a row a column, while the permutation is one
    int32 lane (the split's gain is unmeasured on an attached chip). The
    host applies the permutation with Arrow `take` (C++) and
    streams bucket files while later chunks are still in flight.
    """
    import jax
    import jax.numpy as jnp

    bucket = _tree_bucket_ids(key_tree, key_names, num_buckets, use_pallas)
    n = bucket.shape[0]
    operands = [bucket]
    for name in key_names:
        operands.extend(_entry_sort_lanes(key_tree[name]))
    iota = jnp.arange(n, dtype=jnp.int32)
    results = jax.lax.sort([*operands, iota], num_keys=len(operands),
                           is_stable=True)
    perm = results[-1]
    sorted_bucket = results[0]
    buckets = jnp.arange(num_buckets, dtype=jnp.int32)
    starts = jnp.searchsorted(sorted_bucket, buckets, side="left")
    ends = jnp.searchsorted(sorted_bucket, buckets, side="right")
    base = n // n_chunks
    chunks = tuple(
        jax.lax.slice(perm, (i * base,),
                      ((i + 1) * base if i < n_chunks - 1 else n,))
        for i in range(n_chunks))
    return chunks, starts, ends


def permutation_from_tree(key_tree, key_names: Sequence[str], n: int,
                          num_buckets: int, n_chunks: int = 0):
    """As `build_permutation` over an already-staged device key tree."""
    if n_chunks <= 0:
        # Chunked D2H only pays off once the transfer dwarfs the
        # per-sync latency (unmeasured on an attached chip); the chunk
        # count follows the transfer engine's byte budget (int32
        # permutation),
        # so H2D and D2H pipeline at the same granularity.
        from hyperspace_tpu.io import transfer
        n_chunks = transfer.get_engine().d2h_chunk_count(n * 4)
    n_chunks = max(1, min(n_chunks, n))
    return _perm_core(key_tree, tuple(key_names), num_buckets, n_chunks,
                      use_pallas=pallas_available())


def build_permutation(batch: ColumnBatch, key_columns: Sequence[str],
                      num_buckets: int, n_chunks: int = 0):
    """Device-computed sort permutation for a bucketed build. `batch` only
    needs the key columns resident. Returns (perm chunk arrays, starts,
    ends); concatenated chunks give the full row permutation in
    (bucket, *keys) order."""
    key_names = tuple(batch.schema.field(c).name for c in key_columns)
    tree, _aux = batch_to_tree(batch.select(key_names))
    return permutation_from_tree(tree, key_names, batch.num_rows,
                                 num_buckets, n_chunks)


def build_sorted(batch: ColumnBatch, key_columns: Sequence[str],
                 num_buckets: int):
    """Bucket + lexicographically sort a batch by (bucket, *keys) in one
    compiled program. Returns (sorted batch, starts, ends) with starts/ends
    the per-bucket row ranges."""
    key_names = tuple(batch.schema.field(c).name for c in key_columns)
    tree, aux = batch_to_tree(batch, computes_on=key_names)
    sorted_tree, _sorted_bucket, starts, ends = _build_core(
        tree, key_names, num_buckets, use_pallas=pallas_available())
    return tree_to_batch(sorted_tree, batch.schema, aux), starts, ends
