"""Columnar substrate: Arrow tables <-> device-resident column batches.

The reference's data plane rides Spark's JVM row/columnar batches; here the
on-device representation is one jax array per column (HBM-resident), which is
what XLA fuses predicate scans over and what the Pallas kernels consume.

Strings are dictionary-encoded on the host with a *sorted* dictionary so
device-side int32 codes are order-preserving (sort/compare on codes ==
lexicographic on values), and each dictionary entry carries a precomputed
64-bit value hash placed on device, so bucket assignment hashes the *value*
(stable across files/batches with different dictionaries), never the code.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401  (enables x64)
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.plan.schema import Field as SchemaField, Schema

_NUMERIC_NP = {
    "bool": np.bool_,
    "int8": np.int8, "int16": np.int16, "int32": np.int32, "int64": np.int64,
    "float32": np.float32, "float64": np.float64,
    "date32": np.int32, "timestamp": np.int64,
}

# Logical dtype -> host numpy dtype, incl. the string code representation.
# THE map for host-lane columns; aggregate lanes import it rather than
# keeping copies.
HOST_NP_DTYPES = {**_NUMERIC_NP, "string": np.int32}


def _jnp():
    import jax.numpy as jnp
    return jnp


_fused_take_jit = None


def _fused_take(arrays, indices):
    """All columns' row gather as ONE jitted executable (see
    ColumnBatch.take)."""
    global _fused_take_jit
    if _fused_take_jit is None:
        import jax.numpy as jnp

        from hyperspace_tpu.telemetry import instrumented_jit

        @instrumented_jit("columnar.fused_take", scope="hs.gather")
        def _take_all(arrs, idx):
            return tuple(jnp.take(a, idx, axis=0) for a in arrs)

        _fused_take_jit = _take_all
    return _fused_take_jit(arrays, indices)


def _string_hash64(values: np.ndarray) -> np.ndarray:
    """FNV-1a 64-bit over utf-8 bytes of each value (host side, once per
    dictionary entry — O(dictionary), not O(rows)). Uses the native C++
    batch kernel when available (`hyperspace_tpu/native`); the Python loop
    below is the reference implementation and fallback — both MUST produce
    identical hashes (device bucket layout depends on them)."""
    if len(values) >= 64:
        from hyperspace_tpu import native
        hashed = native.string_hash64(values)
        if hashed is not None:
            return hashed
    out = np.empty(len(values), dtype=np.uint64)
    fnv_offset = np.uint64(0xCBF29CE484222325)
    fnv_prime = np.uint64(0x100000001B3)
    for i, v in enumerate(values):
        h = fnv_offset
        for b in str(v).encode("utf-8"):
            h = np.uint64((int(h) ^ b) * int(fnv_prime) & 0xFFFFFFFFFFFFFFFF)
        out[i] = h
    return out


def _split_hashes(hashes: np.ndarray, device: bool = True):
    """uint64 value hashes -> (hi, lo) uint32 pair (device or host)."""
    hi = (hashes >> np.uint64(32)).astype(np.uint32)
    lo = (hashes & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    if not device:
        return hi, lo
    import jax.numpy as jnp
    return jnp.asarray(hi), jnp.asarray(lo)


def _merged_dictionary(dictionaries, device: bool = True):
    """Merge sorted dictionaries and build remap tables + value hashes.
    Returns (merged, [remap array per input], (hi, lo))."""
    merged = np.unique(np.concatenate(list(dictionaries)))
    remaps = [np.searchsorted(merged, d).astype(np.int32)
              for d in dictionaries]
    if device:
        import jax.numpy as jnp
        remaps = [jnp.asarray(r) for r in remaps]
    return merged, remaps, _split_hashes(_string_hash64(merged),
                                         device=device)


# ---------------------------------------------------------------------------
# float64 across the device
#
# A TPU holds an f64 as a pair of f32: a float64 array that crosses the
# link as float64 comes back rounded to 48 bits and clamped to f32's
# exponent range (1e300 -> inf, 1e-300 -> 0). int64 is exact through H2D,
# gathers, collectives and D2H. So a float64 column is CARRIED on the
# device as its IEEE bit pattern (int64) on every backend, and decoded to
# the device's own f64 only where an expression computes on it.
# ---------------------------------------------------------------------------


def carried(data: np.ndarray, dtype: str) -> np.ndarray:
    """The host array that crosses the link for a column of logical
    `dtype`: float64 values go as their int64 bit patterns (a view, no
    copy for contiguous input); everything else as it is."""
    if dtype == "float64":
        return np.ascontiguousarray(data, dtype=np.float64).view(np.int64)
    return data


def carried_np_dtype(dtype: str):
    """numpy dtype of `carried(...)` for a logical dtype."""
    return np.int64 if dtype == "float64" else HOST_NP_DTYPES[dtype]


def fetched(data: np.ndarray, dtype: str) -> np.ndarray:
    """Inverse of `carried` after D2H: the column's values."""
    if dtype == "float64" and data.dtype == np.int64:
        return data.view(np.float64)
    return data


def _pow2_f64(k):
    """Exact float64 2**k for int32 k in [-126, 127], built as an f32 (a
    32-bit bitcast lowers on every backend)."""
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(
        ((k + 127) << 23).astype(jnp.int32), jnp.float32
    ).astype(jnp.float64)


def _f64_from_bits_arithmetic(bits):
    """int64 IEEE bit patterns -> the device's float64, by arithmetic on
    sign, exponent and fraction (no 64-bit bitcast). Exact wherever the
    device's f64 can hold the value; on a TPU that is 48 bits of
    significand within f32's normal exponent range, and values beyond
    it become inf / 0 like a float64 H2D makes them."""
    import jax.numpy as jnp

    exp = ((bits >> 52) & 0x7FF).astype(jnp.int32)
    frac = bits & jnp.int64((1 << 52) - 1)
    mant = jnp.where(exp == 0, frac,
                     frac | jnp.int64(1 << 52)).astype(jnp.float64)
    # value = mant * 2**e; 2**e is applied as two exact f32 powers.
    e = jnp.clip(jnp.where(exp == 0, -1074, exp - 1075), -252, 254)
    half = e >> 1
    mag = mant * _pow2_f64(half) * _pow2_f64(e - half)
    # A finite input that overflows the device's f64 must read inf: the
    # TPU's pair arithmetic yields nan there (seen on the chip, PR 22).
    mag = jnp.where(jnp.isnan(mag), jnp.inf, mag)
    mag = jnp.where(exp == 0x7FF,
                    jnp.where(frac == 0, jnp.inf, jnp.nan), mag)
    return jnp.where(bits < 0, -mag, mag)


def f64_from_bits(bits):
    """Decode a carried float64 column (device int64 bit patterns) to
    float64 values on the device: a bitcast where the backend has real
    64-bit floats, arithmetic on a TPU (whose f64 is an f32 pair, so a
    bitcast there is a lossy conversion, not a reinterpretation)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import _can_bitcast64
    if _can_bitcast64():
        return jax.lax.bitcast_convert_type(bits, jnp.float64)
    return _f64_from_bits_arithmetic(bits)


class DeviceColumn:
    """One column, on the device or (host lane) in host memory.

    `raw`: the stored array — numeric payload, or int32 dictionary codes
    for strings. A HOST payload (numpy) is always the values themselves.
    A DEVICE float64 payload is either the int64 IEEE bit patterns a scan
    placed (`carries_bits`; exact through every gather, collective and
    transfer) or a float64 array an expression computed on the device.
    `data`: the VALUES — `raw`, decoded on the device when it carries
    bits. Code that only moves rows (take, concat, route, fetch) uses
    `raw`; code that computes uses `data`.
    `validity`: optional bool array (True = present).
    `dictionary`: host numpy array of unique values, sorted ascending, for
    string columns. `dict_hashes`: uint32x2 (hi, lo) per dictionary
    entry — value hashes for bucket assignment.
    """

    def __init__(self, data, dtype: str, validity=None,
                 dictionary: Optional[np.ndarray] = None,
                 dict_hashes=None):
        self.raw = data
        self.dtype = dtype
        self.validity = validity
        self.dictionary = dictionary
        self.dict_hashes = dict_hashes

    def __repr__(self) -> str:
        return (f"DeviceColumn({self.dtype}, rows={len(self)}, "
                f"{'host' if self.is_host else 'device'}"
                f"{', bits' if self.carries_bits else ''})")

    @property
    def carries_bits(self) -> bool:
        return (self.dtype == "float64" and not self.is_host
                and self.raw.dtype == np.int64)

    @property
    def data(self):
        return f64_from_bits(self.raw) if self.carries_bits else self.raw

    @property
    def carry(self):
        """`raw` in the form that may cross the link or sit beside device
        arrays: a host float64 payload as its int64 bits."""
        return carried(self.raw, self.dtype) if self.is_host else self.raw

    def with_raw(self, raw, validity=None) -> "DeviceColumn":
        """The same logical column over moved rows."""
        return DeviceColumn(raw, self.dtype, validity, self.dictionary,
                            self.dict_hashes)

    @property
    def is_string(self) -> bool:
        return self.dictionary is not None

    @property
    def is_host(self) -> bool:
        """True when the payload lives in host memory (numpy). Host-lane
        columns flow through the same operators; numpy-aware ops stay on
        host, jnp ops transparently promote to the device."""
        return isinstance(self.raw, np.ndarray)

    def __len__(self) -> int:
        return int(self.raw.shape[0])


@dataclass
class ColumnBatch:
    """A batch of columns (same length) on device, with its logical schema."""

    schema: Schema
    columns: Dict[str, DeviceColumn]

    @property
    def num_rows(self) -> int:
        if not self.columns:
            return 0
        return len(next(iter(self.columns.values())))

    def column(self, name: str) -> DeviceColumn:
        f = self.schema.field(name)  # case-insensitive resolve + validation
        return self.columns[f.name]

    def select(self, names: Sequence[str]) -> "ColumnBatch":
        schema = self.schema.select(names)
        return ColumnBatch(schema, {f.name: self.columns[f.name]
                                    for f in schema.fields})

    @property
    def is_host(self) -> bool:
        return all(c.is_host for c in self.columns.values())

    def take(self, indices) -> "ColumnBatch":
        """Row gather by index array. Host-lane batches gather with numpy
        (no device round-trip) when the indices are host-side too. Device
        batches gather every column (+validity) through ONE jitted
        executable — per-column eager takes would each pay their own
        compile at novel shapes."""
        host = (isinstance(indices, np.ndarray)
                and all(c.is_host for c in self.columns.values()))
        if host:
            out = {}
            for name, col in self.columns.items():
                out[name] = col.with_raw(
                    np.take(col.raw, indices, axis=0),
                    (np.take(col.validity, indices, axis=0)
                     if col.validity is not None else None))
            return ColumnBatch(self.schema, out)
        jnp = _jnp()
        arrays = []
        for col in self.columns.values():
            # A host column promoted here crosses the link in its
            # carried form, so the gather moves float64 exactly.
            arrays.append(jnp.asarray(col.carry))
            if col.validity is not None:
                arrays.append(jnp.asarray(col.validity))
        gathered = list(_fused_take(tuple(arrays), jnp.asarray(indices)))
        out = {}
        for name, col in self.columns.items():
            data = gathered.pop(0)
            validity = gathered.pop(0) if col.validity is not None else None
            out[name] = col.with_raw(data, validity)
        return ColumnBatch(self.schema, out)


def _encode_strings(values: np.ndarray):
    """Reference implementation of sorted-unique dictionary encoding over a
    numpy array; `_encode_strings_arrow` is the production path and
    `tests/test_columnar.py` asserts they agree (codes, dictionary, hashes).
    Returns (codes int32, dictionary, hashes uint64, mask)."""
    import pandas as pd
    mask = ~np.asarray(pd.isna(values))
    filled = np.where(mask, values, "")
    dictionary, codes = np.unique(filled.astype(str), return_inverse=True)
    return codes.astype(np.int32), dictionary, _string_hash64(dictionary), mask


def _encode_strings_arrow(arr):
    """Arrow-native sorted-dictionary encode: dictionary_encode + dictionary
    sort + code remap all run in Arrow C++; per-value hashing runs on the
    packed Arrow buffers in the native library. Returns
    (codes int32, dictionary np[str], hashes uint64, validity|None)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if hasattr(arr, "combine_chunks"):
        arr = arr.combine_chunks()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.chunk(0) if arr.num_chunks == 1 else pa.concat_arrays(
            arr.chunks)
    if pa.types.is_dictionary(arr.type):
        # Incoming dictionaries may hold duplicates or nulls; decode and
        # re-encode so the sorted-unique invariants hold.
        arr = arr.cast(pa.string())
    validity = None
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
        arr = arr.fill_null("")
    encoded = pc.dictionary_encode(arr)
    raw_dict = encoded.dictionary
    indices = encoded.indices.to_numpy(zero_copy_only=False).astype(np.int32)
    sort_idx = pc.sort_indices(raw_dict).to_numpy().astype(np.int32)
    rank = np.empty(len(raw_dict), dtype=np.int32)
    rank[sort_idx] = np.arange(len(raw_dict), dtype=np.int32)
    codes = rank[indices]
    sorted_dict = raw_dict.take(pa.array(sort_idx))
    from hyperspace_tpu import native
    hashes = native.arrow_string_hash64(sorted_dict)
    dictionary = np.asarray(sorted_dict.to_numpy(zero_copy_only=False),
                            dtype=str)
    if hashes is None:
        hashes = _string_hash64(dictionary)
    return codes, dictionary, hashes, validity


def _decode_numeric(arr, f: SchemaField):
    """Decode one non-string Arrow column to its RAW host values + null
    mask (no target-dtype cast yet — the cast is the step the transfer
    engine performs into reused staging buffers). Returns
    (np_vals, np_dtype, mask|None)."""
    np_dtype = _NUMERIC_NP.get(f.dtype)
    if np_dtype is None:
        raise HyperspaceException(f"Unsupported dtype: {f.dtype}")
    chunk = arr.combine_chunks() if hasattr(arr, "combine_chunks") else arr
    if f.dtype == "timestamp":
        chunk = chunk.cast("int64")
    elif f.dtype == "date32":
        chunk = chunk.cast("int32")
    mask = None
    if chunk.null_count > 0:
        # Sentinel-fill in Arrow, at the column's own type: valid values
        # (inf, nan, int64 beyond 2**53) come through untouched.
        mask = np.asarray(chunk.is_valid())
        chunk = chunk.fill_null(False if f.dtype == "bool" else 0)
    np_vals = chunk.to_numpy(zero_copy_only=False)
    return np.asarray(np_vals), np_dtype, mask


def _decode_device_column(arr, f: SchemaField) -> dict:
    """Transfer-engine job body for one column (runs on the staging
    pool): decode to host form and name what must be placed. ndarray /
    HostCast values cross the link; Host(...) values stay host."""
    from hyperspace_tpu.io import transfer

    if f.dtype == "string":
        codes, dictionary, hashes, validity = _encode_strings_arrow(arr)
        hi, lo = _split_hashes(hashes, device=False)
        return {"data": codes, "validity": validity,
                "dictionary": transfer.Host(dictionary),
                "hash_hi": hi, "hash_lo": lo}
    np_vals, np_dtype, mask = _decode_numeric(arr, f)
    if f.dtype == "float64":
        data = carried(np_vals, "float64")
    elif np_vals.dtype == np_dtype:
        data = np.ascontiguousarray(np_vals)
    else:
        data = transfer.HostCast(np_vals, np_dtype)
    return {"data": data, "validity": mask}


def from_arrow(table, schema: Optional[Schema] = None,
               device: bool = True,
               transfer_tag: Optional[str] = None) -> ColumnBatch:
    """Arrow table -> ColumnBatch. Nulls become validity masks with
    sentinel-filled payloads (0 / empty string). `device=False` keeps the
    columns in host memory (numpy) for the adaptive host lane — small
    batches where a device round-trip would dominate the work.

    The device path is THE scan-side H2D site and runs STREAMED through
    the pipelined transfer engine (`io/transfer.py`): column decodes run
    on the staging pool while earlier columns' puts are in flight, large
    columns ship as byte-budgeted chunks cast into reused staging
    buffers, and the whole batch lands as one chunk-counted transfer
    record in the link telemetry."""
    if schema is None:
        schema = Schema.from_arrow(table.schema)
    if device:
        from functools import partial

        from hyperspace_tpu.io import transfer

        jobs = [partial(_decode_device_column, table.column(f.name), f)
                for f in schema.fields]
        placed = transfer.get_engine().put_group(jobs, tag=transfer_tag)
        columns: Dict[str, DeviceColumn] = {}
        for f, entry in zip(schema.fields, placed):
            if f.dtype == "string":
                columns[f.name] = DeviceColumn(
                    data=entry["data"], dtype="string",
                    validity=entry.get("validity"),
                    dictionary=entry["dictionary"],
                    dict_hashes=(entry["hash_hi"], entry["hash_lo"]))
            else:
                columns[f.name] = DeviceColumn(
                    data=entry["data"], dtype=f.dtype,
                    validity=entry.get("validity"))
        return ColumnBatch(schema, columns)

    columns = {}
    for f in schema.fields:
        arr = table.column(f.name)
        if f.dtype == "string":
            codes, dictionary, hashes, validity = _encode_strings_arrow(arr)
            columns[f.name] = DeviceColumn(
                data=np.asarray(codes), dtype="string",
                validity=(np.asarray(validity)
                          if validity is not None else None),
                dictionary=dictionary,
                dict_hashes=_split_hashes(hashes, device=False))
        else:
            np_vals, np_dtype, mask = _decode_numeric(arr, f)
            columns[f.name] = DeviceColumn(
                data=np_vals.astype(np_dtype), dtype=f.dtype,
                validity=(np.asarray(mask) if mask is not None else None))
    return ColumnBatch(schema, columns)


def to_arrow(batch: ColumnBatch):
    """Device ColumnBatch -> Arrow table (decodes dictionary codes).

    All device->host copies are issued asynchronously first (transfer
    engine prefetch — failures are counted, not silently swallowed) so
    the per-column transfers overlap (whether d2h latency dominates on
    an attached chip is unmeasured); the per-column np.asarray below then hits the ready copies.
    """
    import pyarrow as pa

    from hyperspace_tpu import telemetry
    from hyperspace_tpu.io import transfer

    engine = transfer.get_engine()
    with telemetry.span("hs.to_arrow.prefetch", "api"):
        for col in batch.columns.values():
            engine.prefetch(col.raw, *((col.validity,)
                                       if col.validity is not None
                                       else ()))

    import time as _time

    arrays = []
    names = []
    d2h_bytes = 0
    d2h_s = 0.0
    d2h_chunks = 0
    for f in batch.schema.fields:
        col = batch.columns[f.name]
        # Result-side D2H: device arrays cross the link in these
        # np.asarray calls (the async prefetch above may already have
        # landed them — near-zero wall for the same bytes = overlap).
        if col.is_host:
            data = fetched(np.asarray(col.raw), f.dtype)
            validity = (np.asarray(col.validity)
                        if col.validity is not None else None)
        else:
            # One span per column's blocking fetch; ONE accounting
            # record for the table, below.
            with telemetry.span("hs.link.d2h", "link", direction="d2h",
                                column=f.name) as link:
                t0 = _time.perf_counter()
                data = fetched(np.asarray(col.raw), f.dtype)
                validity = (np.asarray(col.validity)
                            if col.validity is not None else None)
                d2h_s += _time.perf_counter() - t0
                nbytes = data.nbytes + (validity.nbytes
                                        if validity is not None else 0)
                link.set(bytes=nbytes)
            d2h_bytes += nbytes
            d2h_chunks += 1 if validity is None else 2
        if col.is_string:
            values = col.dictionary[data]
            arr = pa.array(values, type=pa.string(),
                           mask=(~validity if validity is not None else None))
        else:
            pa_type = Schema([f]).to_arrow().field(0).type
            if f.dtype == "timestamp":
                arr = pa.array(data.astype("int64"),
                               mask=(~validity if validity is not None else None)
                               ).cast(pa_type)
            elif f.dtype == "date32":
                arr = pa.array(data.astype("int32"),
                               mask=(~validity if validity is not None else None)
                               ).cast(pa_type)
            else:
                arr = pa.array(data,
                               mask=(~validity if validity is not None else None))
        arrays.append(arr)
        names.append(f.name)
    if d2h_bytes:
        telemetry.record_link_transfer("d2h", d2h_bytes, d2h_s,
                                       chunks=d2h_chunks)
    return pa.table(dict(zip(names, arrays)))


def _owned_host(arr: np.ndarray) -> np.ndarray:
    """An OWNING host copy of a fetched array. On zero-copy backends
    (CPU PJRT) `np.asarray(device_array)` is a view whose base pins the
    device buffer — a demoted entry built from views would keep its
    "evicted" HBM alive, and re-promoting the view re-aliases it into
    an unbounded buffer chain (the leak-sentinel test for the tiered
    cache caught exactly this). A view materializes; an already-owning
    array (real-accelerator D2H lands in fresh host memory) passes
    through uncopied."""
    return np.array(arr, copy=True) if arr.base is not None else arr


def batch_to_host(batch: ColumnBatch) -> ColumnBatch:
    """Device ColumnBatch -> fully host-resident copy (numpy payloads,
    numpy dict hashes) — the segment cache's DEMOTION form: everything
    needed to rebuild the device batch WITHOUT re-reading or re-decoding
    parquet, at the cost of one D2H fetch per column now and one H2D put
    at re-promotion. Fetches ride the transfer engine (d2h telemetry);
    already-host columns pass through untouched. Every payload OWNS its
    memory (`_owned_host`) so the demoted entry releases, not pins, the
    device residency it replaced."""
    from hyperspace_tpu.io import transfer

    engine = transfer.get_engine()
    for col in batch.columns.values():
        engine.prefetch(col.raw, *((col.validity,)
                                   if col.validity is not None else ()))
    out: Dict[str, DeviceColumn] = {}
    for name, col in batch.columns.items():
        hashes = col.dict_hashes
        if hashes is not None:
            hashes = (_owned_host(np.asarray(hashes[0])),
                      _owned_host(np.asarray(hashes[1])))
        out[name] = DeviceColumn(
            data=fetched(_owned_host(engine.fetch(col.raw)), col.dtype),
            dtype=col.dtype,
            validity=(_owned_host(engine.fetch(col.validity))
                      if col.validity is not None else None),
            dictionary=col.dictionary,
            dict_hashes=hashes)
    return ColumnBatch(batch.schema, out)


def host_batch_to_device(batch: ColumnBatch,
                         transfer_tag: Optional[str] = None
                         ) -> ColumnBatch:
    """Host ColumnBatch (the demoted form above) -> device-resident
    batch via the pipelined transfer engine — the segment cache's
    RE-PROMOTION: H2D cost paid, parquet decode skipped. `transfer_tag`
    rides the same lane accounting as fills (`tag="fill"` lands in
    `transfer.fill.*`)."""
    from hyperspace_tpu.io import transfer

    def job(col: DeviceColumn):
        def run() -> dict:
            produced = {"data": np.asarray(col.carry)}
            if col.validity is not None:
                produced["validity"] = np.asarray(col.validity)
            if col.dict_hashes is not None:
                produced["hash_hi"] = np.asarray(col.dict_hashes[0])
                produced["hash_lo"] = np.asarray(col.dict_hashes[1])
            return produced
        return run

    cols = [batch.columns[f.name] for f in batch.schema.fields]
    placed = transfer.get_engine().put_group([job(c) for c in cols],
                                             tag=transfer_tag)
    out: Dict[str, DeviceColumn] = {}
    for f, col, entry in zip(batch.schema.fields, cols, placed):
        hashes = None
        if "hash_hi" in entry:
            hashes = (entry["hash_hi"], entry["hash_lo"])
        out[f.name] = DeviceColumn(
            data=entry["data"], dtype=col.dtype,
            validity=entry.get("validity"),
            dictionary=col.dictionary, dict_hashes=hashes)
    return ColumnBatch(batch.schema, out)


def concat_batches(batches: List[ColumnBatch]) -> ColumnBatch:
    """Concatenate batches row-wise. String columns are re-unified through a
    merged sorted dictionary so codes stay order-preserving and comparable.
    All-host inputs concatenate on the host lane; any device input promotes
    the result to the device."""
    if not batches:
        raise HyperspaceException("Cannot concat zero batches.")
    if len(batches) == 1:
        return batches[0]
    host = all(b.is_host for b in batches)
    xp = np if host else _jnp()
    schema = batches[0].schema
    out: Dict[str, DeviceColumn] = {}
    for f in schema.fields:
        cols = [b.columns[f.name] for b in batches]
        any_validity = any(c.validity is not None for c in cols)
        validity = None
        if any_validity:
            validity = xp.concatenate([
                c.validity if c.validity is not None
                else xp.ones(len(c), dtype=bool) for c in cols])
        if f.dtype == "string":
            merged, remaps, hashes = _merged_dictionary(
                [c.dictionary for c in cols], device=not host)
            remapped = [xp.take(remap, c.data)
                        for remap, c in zip(remaps, cols)]
            out[f.name] = DeviceColumn(xp.concatenate(remapped), "string",
                                       validity, merged, hashes)
        else:
            out[f.name] = DeviceColumn(
                xp.concatenate(_same_form(cols, host)), f.dtype, validity)
    return ColumnBatch(schema, out)


def _same_form(cols: List[DeviceColumn], host: bool) -> list:
    """The columns' payloads in ONE representation, for a row-wise
    concatenation: host values as they are; on the device a float64
    column stays carried bits unless a part was computed there (then
    every part decodes to values)."""
    if host or cols[0].dtype != "float64":
        return [c.raw for c in cols]
    if any(not c.is_host and not c.carries_bits for c in cols):
        return [c.data for c in cols]
    return [c.carry for c in cols]


def unify_string_columns(a: DeviceColumn, b: DeviceColumn):
    """Re-map two string columns onto one merged sorted dictionary so their
    codes are mutually comparable (used by the join path)."""
    import jax.numpy as jnp

    merged, (remap_a, remap_b), hashes = _merged_dictionary(
        [a.dictionary, b.dictionary])

    def remap(col: DeviceColumn, table) -> DeviceColumn:
        return DeviceColumn(jnp.take(table, col.data), "string",
                            col.validity, merged, hashes)

    return remap(a, remap_a), remap(b, remap_b)


def batch_to_tree(batch: ColumnBatch,
                  computes_on: Optional[Sequence[str]] = None):
    """ColumnBatch -> (jit-traversable pytree of arrays, host aux).

    The tree holds per-column {"data", "validity", "hash_hi", "hash_lo"}
    (absent entries omitted so jit caching keys on structure); aux carries
    the host-side dictionaries needed to rebuild the batch.

    `computes_on` names the columns whose tree entries the consumer reads
    as VALUES (key lanes, arithmetic); `None` means all of them. Every
    other column's "data" is its carried form (`DeviceColumn.raw`; host
    float64 as int64 bits), which the consumer may only move —
    `tree_to_batch` rebuilds either form into the same logical column.
    """
    tree = {}
    aux = {}
    for f in batch.schema.fields:
        col = batch.columns[f.name]
        if computes_on is None or f.name in computes_on:
            entry = {"data": col.data}
        else:
            entry = {"data": col.carry}
        if col.validity is not None:
            entry["validity"] = col.validity
        if col.is_string:
            entry["hash_hi"], entry["hash_lo"] = col.dict_hashes
        tree[f.name] = entry
        aux[f.name] = col.dictionary
    return tree, aux


def tree_to_batch(tree, schema: Schema, aux) -> ColumnBatch:
    columns = {}
    for f in schema.fields:
        entry = tree[f.name]
        dict_hashes = None
        if "hash_hi" in entry:
            dict_hashes = (entry["hash_hi"], entry["hash_lo"])
        data = entry["data"]
        if isinstance(data, np.ndarray):
            data = fetched(data, f.dtype)  # host payloads are values
        columns[f.name] = DeviceColumn(
            data=data, dtype=f.dtype,
            validity=entry.get("validity"),
            dictionary=aux.get(f.name),
            dict_hashes=dict_hashes)
    return ColumnBatch(schema, columns)
