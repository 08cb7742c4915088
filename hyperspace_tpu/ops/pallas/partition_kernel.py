"""Pallas TPU kernel: fused hash -> bucket id -> per-tile histogram.

The repartition primitive (`ExchangeExec.partition`, the mesh build's
capacity sizing) needs BOTH the per-row bucket id and the per-bucket
lengths. The jnp path makes two HBM passes (hash+modulo, then
segment_sum); this kernel produces both in ONE pass: each [256, 128] VMEM
tile mixes its key lanes (the same fmix32/hash-combine chain as
`ops/hash_partition.py` — bit-for-bit, asserted in interpret mode by
`tests/test_pallas.py`), writes the bucket ids, and accumulates a one-hot
histogram entirely in registers/VMEM before a single [B] store.

Like `hash_kernel.py`, chunking uses `lax.map` over fixed tiles rather
than a Pallas grid: the kernel compiles once and loops (grid form
unmeasured on the chip).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.ops.pallas.hash_kernel import pallas_available  # noqa: F401

_BLOCK_ROWS = 256
_LANES = 128
# Rows per histogram accumulation sub-block: bounds the one-hot
# intermediate at _HIST_SUB * _LANES * hist_cols int32s (1 MB at 256
# bucket columns).
_HIST_SUB = 8
# Above this bucket count even the sub-blocked accumulator churns VMEM;
# callers should take the two-pass jnp path instead (`kernel_supported`).
MAX_KERNEL_BUCKETS = 1024


def kernel_supported(num_buckets: int) -> bool:
    """True when the fused kernel path is appropriate for this bucket
    count (and Pallas is available on the backend)."""
    return pallas_available() and num_buckets <= MAX_KERNEL_BUCKETS


def _kernel(num_buckets: int, n_lanes: int, *refs):
    import jax.numpy as jnp

    in_refs = refs[:n_lanes]
    valid_ref = refs[n_lanes]
    ids_ref = refs[n_lanes + 1]
    hist_ref = refs[n_lanes + 2]

    def fmix32(h):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        return h ^ (h >> 16)

    h = fmix32(in_refs[0][:])
    for ref in in_refs[1:]:
        h2 = fmix32(ref[:])
        h = h ^ (h2 + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    bucket = (h % jnp.uint32(num_buckets)).astype(jnp.int32)
    ids_ref[:] = bucket
    valid = valid_ref[:] != 0
    # One-hot histogram accumulated over row sub-blocks: a full-tile
    # one-hot would materialize [256, 128, hist_cols] (32 MB of int32 at
    # 200+ buckets if the reduction is not fused — over a core's ~16 MB
    # VMEM); per-sub-block the intermediate is bounded at
    # _HIST_SUB*128*hist_cols. Padding rows count toward no bucket.
    masked = jnp.where(valid, bucket, jnp.int32(num_buckets))
    b_range = jnp.arange(hist_ref.shape[1], dtype=jnp.int32)

    # STATIC slices in an unrolled loop: `lax.dynamic_slice` on a value
    # has no Mosaic TC lowering (found the hard way on real hardware —
    # interpret-mode tests pass either way), and the trip count is a
    # compile-time constant anyway.
    hist = jnp.zeros(hist_ref.shape[1], dtype=jnp.int32)
    for i in range(_BLOCK_ROWS // _HIST_SUB):
        rows = masked[i * _HIST_SUB:(i + 1) * _HIST_SUB]
        onehot = (rows[:, :, None] == b_range[None, None, :])
        hist = hist + jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
    hist_ref[:] = hist[None, :]


def partition_ids_and_histogram(lanes: Sequence, num_buckets: int,
                                interpret: bool = False) -> Tuple:
    """(bucket ids int32 [n], lengths int64 [num_buckets]) in one fused
    pass over uint32 key lanes (first lane seeds, further lanes combine —
    THE hash identity)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = lanes[0].shape[0]
    per_block = _BLOCK_ROWS * _LANES
    padded = -(-n // per_block) * per_block
    n_chunks = padded // per_block
    hist_cols = -(-num_buckets // _LANES) * _LANES

    def prep(x, fill=0):
        x = x.astype(jnp.uint32)
        x = jnp.pad(x, (0, padded - n), constant_values=fill)
        return x.reshape(n_chunks, _BLOCK_ROWS, _LANES)

    tiles = [prep(x) for x in lanes]
    valid = prep(jnp.ones(n, dtype=jnp.uint32))
    kernel = functools.partial(_kernel, num_buckets, len(tiles))
    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((_BLOCK_ROWS, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, hist_cols), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * (len(tiles) + 1),
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        interpret=interpret,
    )

    if n_chunks == 1:
        ids, hist = call(*(t[0] for t in tiles), valid[0])
        return (ids.reshape(-1)[:n],
                hist.reshape(-1)[:num_buckets].astype(jnp.int64))
    ids, hists = jax.lax.map(lambda chunk: call(*chunk),
                             (*tiles, valid))
    lengths = jnp.sum(hists.reshape(n_chunks, -1), axis=0)
    return (ids.reshape(-1)[:n],
            lengths[:num_buckets].astype(jnp.int64))


def batch_partition(batch, key_columns: List[str], num_buckets: int,
                    interpret: bool = False) -> Tuple:
    """ColumnBatch -> (bucket ids, lengths) via the fused kernel, using
    the shared hash-lane decomposition (`column_hash_lanes`)."""
    from hyperspace_tpu.ops.hash_partition import column_hash_lanes

    lanes: List = []
    for name in key_columns:
        lanes.extend(column_hash_lanes(batch.column(name)))
    return partition_ids_and_histogram(lanes, num_buckets,
                                       interpret=interpret)
