"""Pallas TPU kernel: fused multi-lane murmur mix -> bucket id.

One VMEM pass computes, for every row, the fmix32/hash-combine chain over
all key lanes and the bucket modulo — the device half of the build
pipeline's hash partitioning (`ops/hash_partition.py` documents the hash
identity; this kernel MUST match it bit-for-bit, asserted by
`tests/test_pallas.py` in interpret mode).

Layout: uint32 lanes are padded to a multiple of (8, 128) and viewed as
[rows, 128] tiles (the VPU's native 8x128 lanes); the grid walks row
blocks. The same mixing is what XLA emits for the jnp path, so the win is
not arithmetic but fusion control: one HBM read per lane, one write, no
intermediate materialization — and a scaffold for the heavier Pallas
kernels (merge-path joins, radix histograms) to come.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import hyperspace_tpu._jax_config  # noqa: F401

_BLOCK_ROWS = 256
_LANES = 128


def pallas_available() -> bool:
    """The Mosaic kernels lower only for the TPU backend; a backend that
    fails to initialize raises here instead of reading as "no Pallas"."""
    import jax
    return jax.default_backend() == "tpu"


def _kernel(num_buckets: int, n_lanes: int, *refs):
    import jax.numpy as jnp

    in_refs = refs[:n_lanes]
    out_ref = refs[n_lanes]

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
    out_ref[:] = (h % jnp.uint32(num_buckets)).astype(jnp.int32)


def hash_lanes_to_buckets(lanes: Sequence, num_buckets: int,
                          interpret: bool = False):
    """lanes: uint32 [n] arrays (first lane's fmix is the seed, further
    lanes hash-combine, matching `hash_partition.batch_hash32` for
    single-lane-per-column keys). Returns int32 [n] bucket ids.

    Chunking is done with `lax.map` over fixed [BLOCK_ROWS, 128] tiles
    rather than a Pallas grid: the kernel compiles once and loops. A
    grid is the usual Pallas form; which is faster on the chip is
    unmeasured.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = lanes[0].shape[0]
    per_block = _BLOCK_ROWS * _LANES
    padded = -(-n // per_block) * per_block
    n_chunks = padded // per_block

    def prep(x):
        x = x.astype(jnp.uint32)
        return jnp.pad(x, (0, padded - n)).reshape(n_chunks, _BLOCK_ROWS,
                                                   _LANES)

    tiles = [prep(x) for x in lanes]
    kernel = functools.partial(_kernel, num_buckets, len(tiles))
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((_BLOCK_ROWS, _LANES), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(tiles),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )

    if n_chunks == 1:
        out = call(*(t[0] for t in tiles))
        return out.reshape(-1)[:n]
    out = jax.lax.map(lambda chunk: call(*chunk), tuple(tiles))
    return out.reshape(-1)[:n]
