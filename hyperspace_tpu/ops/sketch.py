"""Sketch kernels for data-skipping indexes: zone maps, blocked bloom
filters, and the Z-order clustering permutation.

Build-side math for `index/sketch.py` (blob IO) and
`actions/skipping.py` (the FSM action). Two lanes, one identity:

- DEVICE lane (batches staged through the `TransferEngine` by
  `columnar.from_arrow(device=True)`): per-column min/max/null/NaN
  reductions and the bloom bit-set run as jitted XLA programs
  (`instrumented_jit` — compile telemetry like every other entry
  point). The bloom scatter-OR is expressed as a bincount over FLAT BIT
  POSITIONS (`counts.at[flat_bits].add(1)` then a pack) because XLA has
  no scatter-or primitive.
- HOST lane (numpy mirror, used below the device-amortization row
  count): identical results bit-for-bit — the bloom words and zone
  values a query probes against must not depend on which lane built
  them (`tests/test_skipping.py` pins host == device).

Hash identity: blooms hash COLUMN VALUES through the same lanes the
bucket hash uses (`ops/hash_partition.column_hash_lanes` /
`ops/host_hash.host_column_hash_lanes` — strings contribute their
per-dictionary FNV-1a value hashes, numerics their order-preserving
32-bit key lanes, null rows all-zero lanes), mixed into a (h1, h2)
uint32 pair by a dual murmur-style mix. A plan-time literal probes with
`probe_hash_pair(value, dtype)` over the same lanes, so build and probe
can never disagree. The filter layout is a parquet-style SPLIT-BLOCK
bloom: 256-bit blocks of 8 uint32 words, block chosen by h1, one bit
per word from h2 x per-word salt.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException

# Per-word salts of the split-block bloom (parquet's constants).
_SALT = (0x47B6137B, 0x44974D91, 0x8824AD5B, 0xA2B7289D,
         0x705495C7, 0x2DF1424B, 0x9EFC4947, 0x5C6BFB31)
_SEED2 = 0x6A09E667  # second-hash derivation seed (mirrors dual_hash64)

BLOCK_BITS = 256
WORDS_PER_BLOCK = 8


def bloom_num_bits(rows: int, fpp: float, max_bytes: int) -> int:
    """Filter size in bits for `rows` distinct-ish values at target
    false-positive rate `fpp`: the standard -n*ln(p)/ln(2)^2 estimate,
    rounded UP to whole 256-bit blocks and capped at `max_bytes` (a
    huge file degrades to a higher-FPP filter, never an unbounded
    blob)."""
    rows = max(1, int(rows))
    fpp = min(max(float(fpp), 1e-6), 0.5)
    bits = int(math.ceil(-rows * math.log(fpp) / (math.log(2.0) ** 2)))
    blocks = max(1, (bits + BLOCK_BITS - 1) // BLOCK_BITS)
    max_blocks = max(1, (int(max_bytes) * 8) // BLOCK_BITS)
    return min(blocks, max_blocks) * BLOCK_BITS


# ---------------------------------------------------------------------------
# The dual hash (build and probe share it)
# ---------------------------------------------------------------------------


def _dual_mix_host(lanes: Sequence[np.ndarray]):
    """(h1, h2) uint32 pair per row from hash-input lanes (numpy)."""
    from hyperspace_tpu.ops.host_hash import _combine, _fmix32
    u0 = lanes[0].astype(np.uint32)
    h1 = _fmix32(u0)
    h2 = _fmix32(u0 ^ np.uint32(_SEED2))
    for lane in lanes[1:]:
        u = lane.astype(np.uint32)
        h1 = _combine(h1, _fmix32(u))
        h2 = _combine(h2, _fmix32(u ^ np.uint32(_SEED2)))
    return h1, h2


def _dual_mix_device(lanes):
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash_partition import _combine, _fmix32
    u0 = lanes[0].astype(jnp.uint32)
    h1 = _fmix32(u0)
    h2 = _fmix32(u0 ^ jnp.uint32(_SEED2))
    for lane in lanes[1:]:
        u = lane.astype(jnp.uint32)
        h1 = _combine(h1, _fmix32(u))
        h2 = _combine(h2, _fmix32(u ^ jnp.uint32(_SEED2)))
    return h1, h2


def probe_hash_pair(value, dtype: str) -> Tuple[int, int]:
    """(h1, h2) of ONE literal value under the bloom hash identity —
    what the plan-time rule probes membership with. Raises
    HyperspaceException when the value is not representable in the
    column's dtype (callers treat that as un-refutable)."""
    from hyperspace_tpu.ops.host_hash import _hash_lanes
    try:
        lanes = _hash_lanes([value], dtype)
    except (ValueError, TypeError, OverflowError) as exc:
        raise HyperspaceException(
            f"Unprobeable literal {value!r} for dtype {dtype}") from exc
    h1, h2 = _dual_mix_host(lanes)
    return int(h1[0]), int(h2[0])


# ---------------------------------------------------------------------------
# Bloom build (host + device) and probe
# ---------------------------------------------------------------------------


def _host_bloom_words(h1: np.ndarray, h2: np.ndarray,
                      nbits: int) -> np.ndarray:
    nblocks = nbits // BLOCK_BITS
    words = np.zeros(nblocks * WORDS_PER_BLOCK, dtype=np.uint32)
    block = (h1 % np.uint32(nblocks)).astype(np.int64)
    for j, salt in enumerate(_SALT):
        bit = (h2 * np.uint32(salt)) >> np.uint32(27)
        np.bitwise_or.at(words, block * WORDS_PER_BLOCK + j,
                         np.uint32(1) << bit)
    return words


_bloom_kernel_jit = None


def _bloom_kernel(lanes, counts_init):
    """Traceable bloom body: lanes -> (h1, h2) -> per-row flat bit
    positions -> bincount -> packed uint32 words. `counts_init` is a
    zeros array whose SHAPE carries nbits (no static args needed)."""
    import jax.numpy as jnp

    h1, h2 = _dual_mix_device(list(lanes))
    nbits = counts_init.shape[0]
    nblocks = nbits // BLOCK_BITS
    block = (h1 % jnp.uint32(nblocks)).astype(jnp.int32)
    flats = []
    for j, salt in enumerate(_SALT):
        bit = ((h2 * jnp.uint32(salt)) >> jnp.uint32(27)).astype(jnp.int32)
        flats.append(block * BLOCK_BITS + j * 32 + bit)
    flat = jnp.stack(flats, axis=1).reshape(-1)
    counts = counts_init.at[flat].add(1)
    bits = (counts > 0).reshape(nbits // 32, 32).astype(jnp.uint32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32)


def _bloom_jit():
    global _bloom_kernel_jit
    if _bloom_kernel_jit is None:
        from hyperspace_tpu.telemetry import instrumented_jit
        _bloom_kernel_jit = instrumented_jit(
            "sketch.bloom", scope="hs.sketch")(_bloom_kernel)
    return _bloom_kernel_jit


def bloom_build(col, nbits: int) -> np.ndarray:
    """Bloom words (uint32, host) over every row of one column
    (DeviceColumn, host- or device-lane). Null rows insert their
    all-zero lanes — a harmless extra member, never a false negative."""
    if col.is_host:
        from hyperspace_tpu.ops.host_hash import host_column_hash_lanes
        h1, h2 = _dual_mix_host(host_column_hash_lanes(col))
        return _host_bloom_words(h1, h2, nbits)
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash_partition import column_hash_lanes
    lanes = tuple(column_hash_lanes(col))
    words = _bloom_jit()(lanes, jnp.zeros(nbits, dtype=jnp.int32))
    return np.asarray(words)


def bloom_maybe_contains(words: np.ndarray, h1: int, h2: int) -> bool:
    """Membership probe: True = value MAY be present (bloom semantics);
    False = definitely absent."""
    nblocks = len(words) // WORDS_PER_BLOCK
    if nblocks <= 0:
        return True
    block = (int(h1) & 0xFFFFFFFF) % nblocks
    for j, salt in enumerate(_SALT):
        bit = (((int(h2) & 0xFFFFFFFF) * salt) & 0xFFFFFFFF) >> 27
        if not (int(words[block * WORDS_PER_BLOCK + j]) >> bit) & 1:
            return False
    return True


# ---------------------------------------------------------------------------
# Zone maps (host + device)
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = ("float32", "float64")

_zones_jit = None


def _zones_kernel(data, validity, nan_mask):
    """Traceable zone body: (valid_count, ok_count, min, max, has_nan)
    where ok = valid AND not-NaN. Identity fill values keep the min/max
    sound when nothing qualifies (callers gate on ok_count)."""
    import jax.numpy as jnp

    valid = validity
    ok = valid & ~nan_mask
    big = (jnp.finfo(data.dtype).max
           if jnp.issubdtype(data.dtype, jnp.floating)
           else jnp.iinfo(data.dtype).max)
    small = (jnp.finfo(data.dtype).min
             if jnp.issubdtype(data.dtype, jnp.floating)
             else jnp.iinfo(data.dtype).min)
    vmin = jnp.min(jnp.where(ok, data, big))
    vmax = jnp.max(jnp.where(ok, data, small))
    return (valid.sum(dtype=jnp.int64), ok.sum(dtype=jnp.int64),
            vmin, vmax, (valid & nan_mask).any())


def zones(col) -> dict:
    """Zone-map facts of one column (DeviceColumn, host- or
    device-lane): {"nulls", "ok" (non-null, non-NaN count), "min",
    "max" (python scalars in code space for strings; None when no row
    qualifies), "has_nan"}. String columns reduce over their
    order-preserving dictionary codes; the caller maps the code bounds
    back through the dictionary."""
    n = len(col)
    is_float = col.dtype in _FLOAT_DTYPES and not col.is_string
    is_bool = col.dtype == "bool" and not col.is_string
    if col.is_host:
        data = col.data
        if is_bool:  # min/max over ints (no iinfo for bool)
            data = data.astype(np.int32)
        valid = (col.validity if col.validity is not None
                 else np.ones(n, dtype=bool))
        nan = np.isnan(data) if is_float else np.zeros(n, dtype=bool)
        ok = valid & ~nan
        cnt_valid = int(valid.sum())
        cnt_ok = int(ok.sum())
        vmin = data[ok].min() if cnt_ok else None
        vmax = data[ok].max() if cnt_ok else None
        has_nan = bool((valid & nan).any())
    else:
        import jax.numpy as jnp

        global _zones_jit
        if _zones_jit is None:
            from hyperspace_tpu.telemetry import instrumented_jit
            _zones_jit = instrumented_jit(
                "sketch.zones", scope="hs.sketch")(_zones_kernel)
        data = col.data
        if is_bool:
            data = data.astype(jnp.int32)
        valid = (col.validity if col.validity is not None
                 else jnp.ones(n, dtype=bool))
        nan = (jnp.isnan(data) if is_float
               else jnp.zeros(n, dtype=bool))
        cv, co, vmin, vmax, hn = _zones_jit(data, valid, nan)
        cnt_valid, cnt_ok = int(cv), int(co)
        has_nan = bool(hn)
        vmin = np.asarray(vmin)[()] if cnt_ok else None
        vmax = np.asarray(vmax)[()] if cnt_ok else None
    return {"nulls": n - cnt_valid, "ok": cnt_ok,
            "min": None if vmin is None else vmin.item(),
            "max": None if vmax is None else vmax.item(),
            "has_nan": has_nan}


# ---------------------------------------------------------------------------
# Z-order clustering permutation
# ---------------------------------------------------------------------------

# Quantile resolution per column: 16 bits (65536 quantiles) is plenty
# for file-level clustering and keeps up to 4 interleaved columns in
# one uint64 z-value.
_Z_BITS_MAX = 16


def zorder_permutation(batch, columns: Sequence[str]) -> np.ndarray:
    """Stable row permutation clustering `batch` by the Z-order
    (Morton) interleave of `columns`. Each column is RANK-normalized
    first (dense quantiles via its order-preserving sort lanes, nulls
    first) so low-entropy or skewed value ranges still interleave
    meaningfully, then the quantile bits are woven MSB-first. One
    column degenerates to a plain sort. Host-side: the build's row
    gather and parquet encode are host work already, and the rank pass
    is one lexsort per column."""
    from hyperspace_tpu.ops.keys import host_column_sort_lanes

    n = batch.num_rows
    if n == 0:
        return np.arange(0, dtype=np.int64)
    k = max(1, len(columns))
    bits = min(_Z_BITS_MAX, 64 // k)
    quantized: List[np.ndarray] = []
    for name in columns:
        lanes = host_column_sort_lanes(batch.column(name))
        order = np.lexsort(tuple(reversed([np.asarray(l) for l in lanes])))
        rank = np.empty(n, dtype=np.uint64)
        rank[order] = np.arange(n, dtype=np.uint64)
        quantized.append((rank * np.uint64(1 << bits))
                         // np.uint64(n))
    z = np.zeros(n, dtype=np.uint64)
    for i in range(bits):
        shift = np.uint64(bits - 1 - i)
        for q in quantized:
            z = (z << np.uint64(1)) | ((q >> shift) & np.uint64(1))
    return np.argsort(z, kind="stable").astype(np.int64)
