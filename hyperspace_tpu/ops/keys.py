"""Sort-key transforms: everything becomes 32-bit lanes, order preserved.

TPU VPU lanes are 32-bit; int64/float64 arithmetic is emulated. Sorting and
hashing therefore decompose every key column into one or two 32-bit arrays
whose lexicographic order equals the source order:

- int64  -> (hi: int32 arithmetic-shift — sign order preserved,
             lo: uint32 — unsigned order of the low word)
- float64 -> order-preserving bit transform (negatives: all bits flipped;
             positives: sign bit set) -> uint64 -> (hi, lo) uint32
- float32 -> same transform -> one uint32
- int32/int16/int8/bool/date32 -> one int32
- string -> dictionary code (int32; order-preserving by construction)

`ops/hash_partition.py` mixes the same lanes, so hashing and sorting share
one decomposition.
"""

from __future__ import annotations

from typing import List

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.io.columnar import DeviceColumn
from hyperspace_tpu.telemetry import instrumented_jit


def _float_order_bits(data, int_dtype, uint_dtype, sign_bit):
    """IEEE total-order transform: monotone map float -> unsigned int
    (negatives flip all bits; positives set the sign bit).

    Floats are normalized first — -0.0 -> +0.0 and every NaN bit pattern
    -> one canonical quiet NaN — so sort order, bucket hash, and join/group
    key identity agree with numeric equality on every lane (Spark's
    NormalizeFloatingNumbers; NaNs group together and sort last)."""
    import jax
    import jax.numpy as jnp
    zero = jnp.zeros((), data.dtype)
    data = jnp.where(data == zero, zero, data)
    data = jnp.where(jnp.isnan(data), jnp.full((), jnp.nan, data.dtype),
                     data)
    bits = jax.lax.bitcast_convert_type(data, int_dtype).astype(uint_dtype)
    sign = (bits >> (sign_bit - 1)) & uint_dtype(1)
    mask = jnp.where(sign == 1, ~uint_dtype(0), uint_dtype(1) << (sign_bit - 1))
    return bits ^ mask


def _can_bitcast64() -> bool:
    """False on a TPU: it holds an f64 as a pair of f32, so a 64-bit
    bitcast there cannot reinterpret bits. With libtpu 0.0.34 it compiles
    and runs as a lossy CONVERSION (int64 bits -> the value rounded to 48
    bits, 1e300 -> inf, the largest double -> nan; `chip_smoke.py`'s
    probe prints it), so IEEE order bits taken from a device f64 would be
    those of a different number. Exact bits exist only on the host, or in
    a column still carried as int64 (`io/columnar.py`)."""
    import jax
    return jax.default_backend() != "tpu"


def key_lanes(data) -> List:
    """Decompose one key array into order-preserving 32-bit lanes. On
    backends that cannot bitcast 64-bit types (TPU x64 emulation),
    float64 lanes come from HOST bit decomposition for concrete arrays
    and raise for tracers — see the float64 branch."""
    import jax
    import jax.numpy as jnp

    dtype = data.dtype
    if dtype == jnp.int64:
        hi = (data >> 32).astype(jnp.int32)
        lo = (data & 0xFFFFFFFF).astype(jnp.uint32)
        return [hi, lo]
    if dtype == jnp.float64:
        if not _can_bitcast64():
            # The TPU's f64 is an f32 pair: a bitcast is a lossy
            # conversion and raw f64 comparisons are demoted, so exact
            # order lanes must come from HOST bits.
            # Concrete arrays pay one device->host read of the key column;
            # inside a compiled program there is no correct lowering —
            # fail loudly rather than mis-sort.
            import numpy as np

            from jax.core import Tracer
            if isinstance(data, Tracer):
                from hyperspace_tpu.exceptions import HyperspaceException
                raise HyperspaceException(
                    "float64 sort/bucket keys are not supported inside "
                    "compiled programs on TPU backends (no exact 64-bit "
                    "decomposition); use an integer or string key, or run "
                    "on the host lane.")
            return [jnp.asarray(lane)
                    for lane in host_key_lanes(np.asarray(data))]
        bits = _float_order_bits(data, jnp.int64, jnp.uint64, 64)
        return [(bits >> 32).astype(jnp.uint32),
                (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)]
    if dtype == jnp.float32:
        return [_float_order_bits(data, jnp.int32, jnp.uint32, 32)]
    if dtype == jnp.bool_:
        return [data.astype(jnp.int32)]
    if dtype in (jnp.int8, jnp.int16, jnp.int32):
        return [data.astype(jnp.int32)]
    if dtype == jnp.uint32:
        return [data]
    return [data]


def column_sort_lanes(col: DeviceColumn) -> List:
    """32-bit sort lanes for a column; validity (nulls-first) leads."""
    lanes: List = []
    if col.validity is not None:
        lanes.append(col.validity)
    lanes.extend(key_lanes(col.data))
    return lanes


def host_key_lanes(data) -> List:
    """Host (numpy) mirror of `key_lanes`: same order-preserving
    decomposition with zero device traffic, for the adaptive host lane."""
    import numpy as np

    dtype = data.dtype
    if dtype == np.int64:
        return [(data >> 32).astype(np.int32),
                (data & 0xFFFFFFFF).astype(np.uint32)]
    if dtype == np.float64:
        from hyperspace_tpu.ops.host_hash import _float_order_bits
        bits = _float_order_bits(data, np.uint64, 64)
        return [(bits >> np.uint64(32)).astype(np.uint32),
                (bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)]
    if dtype == np.float32:
        from hyperspace_tpu.ops.host_hash import _float_order_bits
        return [_float_order_bits(data, np.uint32, 32)]
    if dtype == np.bool_:
        return [data.astype(np.int32)]
    if dtype in (np.int8, np.int16, np.int32):
        return [data.astype(np.int32)]
    return [data]


def host_column_sort_lanes(col: DeviceColumn) -> List:
    lanes: List = []
    if col.validity is not None:
        lanes.append(col.validity)
    lanes.extend(host_key_lanes(col.data))
    return lanes


def host_dense_group_ids(keys):
    """Stable dense group encoding on the host: a stable sort over the key
    arrays (primary key first), then adjacent-difference ids in sorted
    order. Returns (perm, sorted_group_ids); original-order ids are
    `out[perm] = sorted_group_ids`. Shared by the host join encode and the
    host aggregation so the grouping invariants live in one place. The
    sort permutation comes from the native C++ radix lane when the keys
    decompose to packable lanes (4-7x np.lexsort on wide key sets);
    np.lexsort otherwise. Both are stable, and for int/bool/string keys
    they produce the SAME permutation; float keys only agree up to NaN
    placement — the native lane orders by the normalized IEEE
    total-order bit transform while the np.lexsort fallback sorts the
    RAW floats (numpy puts every NaN last, ignoring payload/sign bits) —
    so the two lanes may interleave NaN rows differently. Group CONTENT
    is unaffected either way (equal keys stay contiguous and NaNs group
    together under the normalized lane identity); only the permutation,
    which no grouping consumer depends on, can differ."""
    import numpy as np

    keys = [np.asarray(k) for k in keys]
    perm = None
    n = len(keys[0]) if keys else 0
    if keys and n:
        from hyperspace_tpu import native
        lanes = []
        for k in keys:
            if k.dtype == np.object_ or k.dtype.kind == "U":
                lanes = None
                break
            lanes.extend(host_key_lanes(k))
        if lanes is not None:
            perm = native.key_sort_perm(n, lanes)
    if perm is None:
        perm = np.lexsort(tuple(reversed(keys)))
    n = len(perm)
    differs = np.zeros(n, dtype=np.int32)
    for k in keys:
        ks = k[perm]
        differs[1:] |= (ks[1:] != ks[:-1]).astype(np.int32)
    return perm, np.cumsum(differs, dtype=np.int32)


# XLA's variadic sort builds an O(k^2)-sized comparator; past ~15 key
# operands (TPC-DS q64 groups by 15 columns = ~25 lanes) compile time on
# TPU explodes from seconds to tens of minutes. Above this width the
# lexicographic sort runs as stable LSD passes of narrow sorts instead —
# compile cost stays bounded and every pass reuses one cached
# narrow-comparator executable.
MAX_SORT_OPERANDS = 8


def _staged_sort(operands):
    """Traceable body: (permutation, sorted operands), stable
    lexicographic, via chunked LSD passes (or one narrow sort that
    yields the sorted operands for free). Call under jit so ALL passes
    fuse into ONE executable — every separate executable is a
    separate compile."""
    import jax
    import jax.numpy as jnp

    n = operands[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if len(operands) <= MAX_SORT_OPERANDS:
        results = jax.lax.sort([*operands, iota],
                               num_keys=len(operands), is_stable=True)
        return results[-1], tuple(results[:-1])
    chunks = [operands[i:i + MAX_SORT_OPERANDS]
              for i in range(0, len(operands), MAX_SORT_OPERANDS)]
    perm = iota
    for chunk in reversed(chunks):
        gathered = [jnp.take(lane, perm) for lane in chunk]
        results = jax.lax.sort([*gathered, perm], num_keys=len(chunk),
                               is_stable=True)
        perm = results[-1]
    return perm, tuple(jnp.take(op, perm) for op in operands)


def _staged_perm(operands):
    return _staged_sort(operands)[0]


@instrumented_jit("keys.staged_perm", scope="hs.sort")
def _staged_perm_jit(operands):
    return _staged_perm(list(operands))


def staged_sort_permutation(operands):
    """Stable lexicographic sort permutation over `operands` (primary key
    first). Narrow key sets sort in ONE `lax.sort`; wide ones run
    least-significant-chunk-first stable passes (LSD radix over chunks),
    whose composition equals the single wide sort — XLA's wide variadic
    comparator explodes TPU compile time (TPC-DS q64's 15-column
    grouping). One jitted executable either way."""
    import jax.numpy as jnp

    return _staged_perm_jit(tuple(jnp.asarray(o) for o in operands))
