"""The comparison that decides `correct`: a query's answer against the
reference's as a multiset of rows, every column exact, float64 by bit
pattern (so -0.0, nan and values the chip's own f64 cannot hold count).
"""

from __future__ import annotations

import numpy as np


def column_bits(name: str, data, vocabulary: dict) -> np.ndarray:
    """Any column as int64, order-preserving only where it has to be
    equal: float64 by its bits, strings by their code in the dataset's
    fixed `vocabulary` ({column: [value, ...]}; -1 for a value outside
    it)."""
    data = np.asarray(data)
    if data.dtype == np.float64:
        return data.view(np.int64)
    if data.dtype.kind in "OUS":
        vocab = {v: i for i, v in enumerate(vocabulary.get(name, ()))}
        return np.array([vocab.get(v, -1) for v in data], dtype=np.int64)
    return data.astype(np.int64)


def arrow_columns(table, vocabulary: dict) -> dict:
    """An Arrow answer as {column: int64 bits}."""
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        col = table.column(name)
        if col.null_count:
            raise ValueError(f"column {name} came back with nulls")
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        out[name] = column_bits(
            name, col.combine_chunks().to_numpy(zero_copy_only=False),
            vocabulary)
    return out


def reference_columns(answer: dict) -> dict:
    """The reference's {column: ndarray} in the same int64 form (its
    dictionary columns are already codes, its dates already days)."""
    return {name: (data.view(np.int64) if data.dtype == np.float64
                   else data.astype(np.int64))
            for name, data in answer.items()}


_ODD = (0x9E3779B97F4A7C15, 0xBF58476D1CE4E5B9, 0x94D049BB133111EB,
        0xD6E8FEB86659FD93, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9)


def _row_hash(columns: dict, names) -> np.ndarray:
    """One uint64 per row: each column's bits multiplied by an odd
    constant and folded (so that columns of cents and small integers,
    whose bits are far from random, do not cancel), summed, and mixed
    once more. Only an ordering key: rows are compared column by column
    afterwards, so a collision costs time, never the verdict. Two
    buffers, few passes: 18 M rows a column."""
    with np.errstate(over="ignore"):
        h = np.zeros(len(columns[names[0]]), dtype=np.uint64)
        x = np.empty_like(h)
        y = np.empty_like(h)
        for i, name in enumerate(names):
            k = np.uint64(_ODD[i % len(_ODD)] + 2 * (i // len(_ODD)))
            np.multiply(columns[name].view(np.uint64), k, out=x)
            np.right_shift(x, np.uint64(32), out=y)
            x ^= y
            x *= np.uint64(_ODD[(i + 1) % len(_ODD)])
            h += x
        np.right_shift(h, np.uint64(29), out=y)
        h ^= y
        h *= np.uint64(_ODD[2])
    return h


class SortedRows:
    """A row set in a canonical order: sorted by a hash of the whole row
    (rows that hash alike are, but for a collision, the same row)."""

    def __init__(self, columns: dict):
        self.names = sorted(columns)
        self.n = len(columns[self.names[0]]) if self.names else 0
        hashes = _row_hash(columns, self.names) if self.n \
            else np.zeros(0, dtype=np.uint64)
        order = np.argsort(hashes)
        self.hashes = hashes[order]
        self.columns = {n: columns[n][order] for n in self.names}

    def differing(self, other: "SortedRows") -> np.ndarray:
        """Mask of the positions at which the two differ."""
        bad = self.hashes != other.hashes
        for name in self.names:
            bad |= self.columns[name] != other.columns[name]
        return bad


def _same_array(x, y) -> bool:
    import pyarrow as pa

    t = x.type
    if t != y.type or len(x) != len(y) or x.offset != y.offset \
            or x.null_count or y.null_count:
        return False
    if pa.types.is_dictionary(t):
        if not _same_array(x.dictionary, y.dictionary):
            return False
    elif pa.types.is_boolean(t) or not (
            pa.types.is_primitive(t) or pa.types.is_string(t)
            or pa.types.is_binary(t)):
        return False  # bit-packed or nested: not read here
    # buffer 0 is the validity bitmap: no nulls on either side
    return all(p is not None and q is not None and p.equals(q)
               for p, q in zip(x.buffers()[1:], y.buffers()[1:]))


def same_buffers(a, b) -> bool:
    """Whether two Arrow tables are the same bytes: schema, chunking,
    no nulls, and every data buffer of every chunk equal (a memcmp, so
    nan equals itself and -0.0 is not 0.0, which `Table.equals` has the
    other way round). False is no verdict: the same rows may sit in
    other chunks, in another order or in a type not read here, and the
    multiset comparison decides."""
    if a.schema != b.schema or a.num_rows != b.num_rows:
        return False
    for ca, cb in zip(a.columns, b.columns):
        if ca.num_chunks != cb.num_chunks or not all(
                _same_array(x, y) for x, y in zip(ca.chunks, cb.chunks)):
            return False
    return True


def mismatched_rows(got: dict, want) -> int:
    """Rows by which `got` differs from the reference row set `want`
    (a SortedRows or a column dict): 0 exactly when the two hold the
    same rows the same number of times."""
    if not isinstance(want, SortedRows):
        want = SortedRows(want)
    if sorted(got) != want.names:
        return max(want.n, 1)
    have = SortedRows(got)
    if have.n != want.n:
        return max(abs(have.n - want.n), 1)
    bad = have.differing(want)
    if not bad.any():
        return 0
    # Different rows that share a hash come in either order. Where every
    # difference lies in such a run, order the runs' rows fully and look
    # again; otherwise settle the whole set exactly.
    tie = want.hashes[1:] == want.hashes[:-1]
    tied = np.zeros(want.n, dtype=bool)
    tied[1:] |= tie
    tied[:-1] |= tie
    if not (bad & ~tied).any():
        rows = np.flatnonzero(tied)
        a, b = (rows[np.lexsort([s.columns[n][rows] for n in s.names]
                                + [s.hashes[rows]])] for s in (have, want))
    else:
        a, b = (np.lexsort([s.columns[n] for n in s.names])
                for s in (have, want))
    return int(np.count_nonzero(np.logical_or.reduce(
        [have.columns[n][a] != want.columns[n][b] for n in want.names])))
