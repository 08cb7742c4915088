"""Loader for the native host library (ctypes, no pybind11).

The shared library is built from `hyperspace_host.cpp` on first use (g++ is
part of the toolchain); every native entry point has a pure-Python fallback,
so a missing compiler only costs performance, never correctness.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
# ABI version in the filename: a .so built from older sources simply
# never matches the load path (no in-place overwrite of a possibly
# mmapped stale library, no dlopen returning the cached stale handle).
_ABI_VERSION = 4
_SO_PATH = os.path.join(_HERE, f"libhyperspace_host_v{_ABI_VERSION}.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


_SRC_PATH = os.path.join(_HERE, "hyperspace_host.cpp")


def _build() -> bool:
    """Compile the library next to its source. The output lands under a
    per-process temp name and is renamed into place, so concurrent
    builders (test workers) never load a half-written file."""
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-pthread",
             "-o", tmp, _SRC_PATH],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        logger.warning("Native host library build failed (falling back to "
                       "Python): %s", exc)
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def _stale() -> bool:
    """No library yet, or one older than the committed source: what runs
    is built from what git commits, never a leftover git-ignored .so."""
    try:
        return os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if _stale() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
            for suffix, off_t in (("i32", ctypes.c_int32),
                                  ("i64", ctypes.c_int64)):
                fn = getattr(lib, f"fnv1a64_batch_{suffix}")
                fn.restype = None
                fn.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_int64, ctypes.c_void_p]
            lib.bucketed_merge_join_count_i64.restype = None
            lib.bucketed_merge_join_count_i64.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                ctypes.c_int, ctypes.c_void_p]
            lib.bucketed_merge_join_fill_i64.restype = None
            lib.bucketed_merge_join_fill_i64.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p]
            lib.bucket_key_sort_perm.restype = None
            lib.bucket_key_sort_perm.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p]
            lib.key_sort_perm_u64.restype = None
            lib.key_sort_perm_u64.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int32,
                ctypes.c_void_p]
            _lib = lib
        except (OSError, AttributeError) as exc:
            # AttributeError = missing symbol (a hand-built .so from other
            # sources at the versioned path): fall back to numpy.
            logger.warning("Native host library load failed: %s", exc)
        return _lib


def arrow_string_hash64(arr) -> Optional["numpy.ndarray"]:
    """FNV-1a 64 over each element of an Arrow string array, operating
    directly on its packed offset/data buffers (zero per-value Python).
    Returns None if the library is unavailable or the array has nulls."""
    import numpy as np
    import pyarrow as pa

    lib = get_lib()
    if lib is None:
        return None
    if hasattr(arr, "combine_chunks"):
        arr = arr.combine_chunks()
    if arr.null_count:
        return None
    large = pa.types.is_large_string(arr.type)
    buffers = arr.buffers()  # [validity, offsets, data]
    offsets_buf, data_buf = buffers[1], buffers[2]
    off_dtype = np.int64 if large else np.int32
    # Offset values index the shared data buffer absolutely, so a sliced
    # array only shifts where we START reading the offsets buffer.
    offsets = np.frombuffer(offsets_buf, dtype=off_dtype, count=len(arr) + 1,
                            offset=arr.offset * np.dtype(off_dtype).itemsize)
    out = np.empty(len(arr), dtype=np.uint64)
    data_ptr = data_buf.address if data_buf is not None else 0
    fn = lib.fnv1a64_batch_i64 if large else lib.fnv1a64_batch_i32
    fn(ctypes.c_void_p(data_ptr),
       offsets.ctypes.data_as(ctypes.c_void_p),
       ctypes.c_int64(len(arr)),
       out.ctypes.data_as(ctypes.c_void_p))
    return out


def string_hash64(values) -> Optional["numpy.ndarray"]:
    """FNV-1a 64 over a numpy array of strings (U-dtype fast path avoids
    per-value Python objects). None when the native library is missing."""
    import numpy as np
    import pyarrow as pa

    if get_lib() is None:
        return None
    values = np.asarray(values)
    if values.dtype.kind != "U":
        values = values.astype(object)
    return arrow_string_hash64(pa.array(values, type=pa.string()))


def pack_sort_words(lanes):
    """Pack order-preserving uint32 sort lanes (most significant first)
    into uint64 words for `bucket_key_sort_perm`. Accepts the lane dtypes
    `ops/keys.host_column_sort_lanes` produces: bool validity (False =
    null sorts first), signed int32 (biased to order-equivalent uint32),
    and uint32. Returns a list of C-contiguous uint64 arrays, or None when
    a lane's dtype can't be mapped (caller falls back to np.lexsort)."""
    import numpy as np

    u32 = []
    for lane in lanes:
        lane = np.asarray(lane)
        if lane.dtype == np.bool_:
            u32.append(lane.astype(np.uint32))
        elif lane.dtype == np.int32:
            u32.append(lane.view(np.uint32) ^ np.uint32(0x80000000))
        elif lane.dtype == np.uint32:
            u32.append(lane)
        elif lane.dtype in (np.int8, np.int16):
            u32.append(lane.astype(np.int32).view(np.uint32)
                       ^ np.uint32(0x80000000))
        else:
            return None
    if len(u32) % 2:
        u32.insert(0, None)  # zero-pad the most significant word's hi lane
    words = []
    for hi, lo in zip(u32[0::2], u32[1::2]):
        w = lo.astype(np.uint64)
        if hi is not None:
            w |= hi.astype(np.uint64) << np.uint64(32)
        words.append(np.ascontiguousarray(w))
    return words


def key_sort_perm(n: int, lanes):
    """Stable ascending sort permutation over `lanes` alone (no bucket
    grouping) via the native radix — the plain-sort entry the host sort
    and group-encode lanes share. Calls the dedicated no-bucket kernel:
    no O(n) dummy bucket-id allocation, no final counting pass. Returns
    an int32 permutation or None (library unavailable, unsupported lane
    dtype, or n >= 2^31)."""
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    if n >= 1 << 31:
        return None  # int32 permutation indices would wrap
    words = pack_sort_words(lanes)
    if words is None:
        return None
    perm = np.empty(n, dtype=np.int32)
    word_ptrs = (ctypes.c_void_p * len(words))(
        *[w.ctypes.data_as(ctypes.c_void_p).value for w in words])
    lib.key_sort_perm_u64(ctypes.c_int64(n), word_ptrs,
                          ctypes.c_int32(len(words)),
                          perm.ctypes.data_as(ctypes.c_void_p))
    return perm


def bucket_key_sort_perm(bucket_ids, num_buckets: int, lanes):
    """Stable (bucket, *lanes) ascending sort permutation + per-bucket
    bounds via the native radix sort — the index build's host lane.
    Returns (perm int32, starts int64, ends int64) or None when the
    library is unavailable or a lane dtype is unsupported."""
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    bucket_ids = np.ascontiguousarray(bucket_ids, dtype=np.int32)
    n = len(bucket_ids)
    if n >= 1 << 31:
        # int32 permutation indices would wrap; callers fall back to the
        # lexsort/device lanes, which carry int64 permutations.
        return None
    words = pack_sort_words(lanes)
    if words is None:
        return None
    perm = np.empty(n, dtype=np.int32)
    starts = np.empty(num_buckets, dtype=np.int64)
    ends = np.empty(num_buckets, dtype=np.int64)
    word_ptrs = (ctypes.c_void_p * len(words))(
        *[w.ctypes.data_as(ctypes.c_void_p).value for w in words])
    lib.bucket_key_sort_perm(
        bucket_ids.ctypes.data_as(ctypes.c_void_p), ctypes.c_int64(n),
        ctypes.c_int64(num_buckets), word_ptrs, ctypes.c_int32(len(words)),
        perm.ctypes.data_as(ctypes.c_void_p),
        starts.ctypes.data_as(ctypes.c_void_p),
        ends.ctypes.data_as(ctypes.c_void_p))
    return perm, starts, ends


def bucketed_merge_join_i64(lkey, rkey, lbounds, rbounds,
                            left_outer: bool = False):
    """Multithreaded per-bucket sorted merge join over int64 keys in the
    bucket-major index layout. `lbounds`/`rbounds` are the B+1 cumulative
    bucket boundaries; both sides must be sorted within each bucket.
    Returns (li, ri) int32 row-index pairs (ri -1 for unmatched left rows
    under left_outer), or None when the native library is unavailable —
    callers fall back to the numpy path (`ops/join.py`)."""
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    lkey = np.ascontiguousarray(lkey, dtype=np.int64)
    rkey = np.ascontiguousarray(rkey, dtype=np.int64)
    lbounds = np.ascontiguousarray(lbounds, dtype=np.int64)
    rbounds = np.ascontiguousarray(rbounds, dtype=np.int64)
    B = len(lbounds) - 1
    n_threads = min(os.cpu_count() or 1, 16)
    counts = np.zeros(B, dtype=np.int64)

    def p(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    lib.bucketed_merge_join_count_i64(
        p(lkey), p(rkey), p(lbounds), p(rbounds), ctypes.c_int64(B),
        ctypes.c_int(1 if left_outer else 0), ctypes.c_int(n_threads),
        p(counts))
    offsets = np.zeros(B, dtype=np.int64)
    if B > 1:
        np.cumsum(counts[:-1], out=offsets[1:])
    total = int(counts.sum())
    li = np.empty(total, dtype=np.int32)
    ri = np.empty(total, dtype=np.int32)
    if total:
        lib.bucketed_merge_join_fill_i64(
            p(lkey), p(rkey), p(lbounds), p(rbounds), ctypes.c_int64(B),
            ctypes.c_int(1 if left_outer else 0), ctypes.c_int(n_threads),
            p(offsets), p(li), p(ri))
    return li, ri
