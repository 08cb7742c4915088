"""Expression -> XLA compiler.

Compiles IR expression trees (`plan/expr.py`) into jax computations over a
ColumnBatch. This replaces the reference's reliance on Spark's
WholeStageCodegen for predicate evaluation: XLA fuses the whole predicate
into one vectorized kernel over HBM-resident columns.

Null semantics follow SQL as the reference inherits them from Spark:
comparisons involving null are not-true (rows filtered out), IS [NOT] NULL
consults validity.

String comparisons against literals are translated to *code-space*
comparisons: because dictionaries are sorted (`io/columnar.py`), value
predicates become integer range tests on codes — `x > "m"` is
`code >= searchsorted(dict, "m", right)` — so string filters run at integer
scan speed on device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import ColumnBatch, DeviceColumn
from hyperspace_tpu.plan import expr as E


def _col_and_validity(batch: ColumnBatch, name: str):
    col = batch.column(name)
    return col, col.validity


def _string_literal_compare(op: str, col: DeviceColumn, value: str, xp):
    d = col.dictionary
    left = int(np.searchsorted(d, value, side="left"))
    right = int(np.searchsorted(d, value, side="right"))
    present = left < right
    code = col.data
    if op == "eq":
        return (code == left) if present else xp.zeros(code.shape, bool)
    if op == "ne":
        return (code != left) if present else xp.ones(code.shape, bool)
    if op == "lt":
        return code < left
    if op == "le":
        return code < right
    if op == "gt":
        return code >= right
    if op == "ge":
        return code >= left
    raise HyperspaceException(f"Unsupported string comparison: {op}")


_CMP = {"eq": "__eq__", "ne": "__ne__", "lt": "__lt__", "le": "__le__",
        "gt": "__gt__", "ge": "__ge__"}


class ExpressionCompiler:
    """Compiles expressions over a batch. The array module (`xp`) follows
    the batch's residence: host batches evaluate with numpy (zero device
    round-trips — the adaptive host lane for small reads), device batches
    with jax.numpy (XLA-fused)."""

    def __init__(self, batch: ColumnBatch):
        self.batch = batch
        if batch.is_host:
            self.xp = np
        else:
            import jax.numpy as jnp
            self.xp = jnp

    # -- value expressions ------------------------------------------------

    def value(self, e: E.Expression) -> Tuple[object, Optional[object]]:
        """Compile to (array, validity|None). Strings yield their codes and
        may only feed comparisons handled in `predicate`."""
        if isinstance(e, E.Alias):
            return self.value(e.child)
        if isinstance(e, E.Column):
            col, validity = _col_and_validity(self.batch, e.name)
            return col.data, validity
        if isinstance(e, E.Literal):
            return e.value, None
        if isinstance(e, E.NullLiteral):
            n = self.batch.num_rows
            from hyperspace_tpu.io.columnar import HOST_NP_DTYPES
            zeros = self.xp.zeros(n, dtype=HOST_NP_DTYPES.get(e.dtype,
                                                              np.int64))
            return zeros, self.xp.zeros(n, dtype=bool)
        if isinstance(e, (E.Add, E.Sub, E.Mul, E.Div)):
            lv, lval = self.value(e.left)
            rv, rval = self.value(e.right)
            # Widen BEFORE computing (infer_dtype's rule: ints accumulate
            # as int64, any float promotes to float64, Div is float64) —
            # narrow int32/int16 operands must not wrap at their own
            # width.
            lv = self.xp.asarray(lv)
            rv = self.xp.asarray(rv)
            floats = (type(e).op == "div"
                      or lv.dtype.kind == "f" or rv.dtype.kind == "f")
            wide = self.xp.float64 if floats else self.xp.int64
            ops = {"add": self.xp.add, "sub": self.xp.subtract,
                   "mul": self.xp.multiply, "div": self.xp.divide}
            out = ops[type(e).op](lv.astype(wide), rv.astype(wide))
            return out, self._merge_validity(lval, rval)
        if isinstance(e, E.CaseWhen):
            return self._case_when(e)
        if isinstance(e, E.Floor):
            v, valid = self.value(e.child)
            arr = self.xp.asarray(v)
            return self.xp.floor(arr.astype(self.xp.float64)).astype(
                self.xp.int64), valid
        if isinstance(e, E.ScalarSubquery):
            # Resolved by the executor's subquery phase; compiles as the
            # value it produced (NULL for an empty subquery).
            return self.value(e.literal())
        raise HyperspaceException(f"Unsupported value expression: {e!r}")

    def _case_when(self, e: "E.CaseWhen"):
        """Numeric/bool CASE: one fused chain of `where`s, evaluated last
        branch first so the FIRST matching WHEN wins (SQL). A condition
        that is NULL does not match (Kleene not-true). Rows no branch
        matches take the ELSE value, or NULL when there is none — the
        conditional-aggregation idiom (`sum(CASE WHEN ... THEN x END)`)
        relies on sum/avg skipping those NULLs."""
        from hyperspace_tpu.plan.expr import infer_dtype

        xp = self.xp
        n = self.batch.num_rows
        out_dtype = infer_dtype(e, self.batch.schema)
        if out_dtype == "string":
            raise HyperspaceException(
                "String-valued CASE is not supported yet.")
        wide = {"bool": xp.bool_, "int64": xp.int64,
                "float64": xp.float64}[out_dtype]

        def as_wide(v):
            arr = xp.asarray(v)
            if arr.ndim == 0:
                arr = xp.full(n, arr)
            return arr.astype(wide)

        def as_mask(v):
            if v is None:
                return xp.ones(n, dtype=bool)
            arr = xp.asarray(v)
            return xp.full(n, arr) if arr.ndim == 0 else arr

        if e.otherwise_value is not None:
            data, validity = self.value(e.otherwise_value)
            data, validity = as_wide(data), as_mask(validity)
        else:
            data = xp.zeros(n, dtype=wide)
            validity = xp.zeros(n, dtype=bool)
        for cond, val in reversed(e.branches):
            t, _known = self.predicate3(cond)
            v_data, v_valid = self.value(val)
            data = xp.where(t, as_wide(v_data), data)
            validity = xp.where(t, as_mask(v_valid), validity)
        # all-valid result -> drop the mask (the common no-null fast path)
        if e.otherwise_value is not None:
            host_valid = validity if isinstance(validity, np.ndarray) else None
            if host_valid is not None and host_valid.all():
                return data, None
        return data, validity

    def string_column(self, e: E.Expression) -> Optional[DeviceColumn]:
        """Evaluate a string-VALUED expression to a dict-encoded column
        (sorted dictionary, so code-space comparisons stay valid), or None
        when `e` is not string-valued. Substr transforms the DICTIONARY —
        O(dictionary), not O(rows) — then re-sorts and remaps codes."""
        if isinstance(e, E.Alias):
            return self.string_column(e.child)
        if isinstance(e, E.Column):
            col = self.batch.column(e.name)
            return col if col.is_string else None
        if isinstance(e, E.NullLiteral) and e.dtype == "string":
            # All-NULL string column (ROLLUP's coarser granularities).
            return self._const_string_column("", valid=False)
        if isinstance(e, E.Literal) and isinstance(e.value, str):
            # Constant string column (q5/q33/q56-style channel tags): a
            # one-entry dictionary with all codes 0.
            return self._const_string_column(e.value, valid=True)
        if isinstance(e, E.Substr):
            child = self.string_column(e.child)
            if child is None:
                raise HyperspaceException(
                    f"SUBSTR over non-string expression: {e.child!r}")
            return self._substr(child, e.start, e.length)
        return None

    def _const_string_column(self, value: str, valid: bool) -> DeviceColumn:
        """One-entry-dictionary string column: every row carries `value`
        (valid=True) or NULL (valid=False)."""
        from hyperspace_tpu.io.columnar import _split_hashes, _string_hash64

        d = np.array([value])
        n = self.batch.num_rows
        host = self.xp is np
        return DeviceColumn(
            self.xp.zeros(n, dtype=np.int32), "string",
            None if valid else self.xp.zeros(n, dtype=bool), d,
            _split_hashes(_string_hash64(d), device=not host))

    def _substr(self, col: DeviceColumn, start: int,
                length: int) -> DeviceColumn:
        from hyperspace_tpu.io.columnar import (_split_hashes,
                                                _string_hash64)
        d = col.dictionary
        sliced = np.array([v[start - 1:start - 1 + length] for v in d])
        new_dict, inverse = np.unique(sliced, return_inverse=True)
        remap = inverse.astype(np.int32)
        if col.is_host:
            codes = remap[np.asarray(col.data)]
        else:
            import jax.numpy as jnp
            codes = jnp.take(jnp.asarray(remap), col.data)
        hashes = _split_hashes(_string_hash64(new_dict),
                               device=not col.is_host)
        return DeviceColumn(codes, "string", col.validity, new_dict, hashes)

    def value_column(self, e: E.Expression, out_dtype: str) -> DeviceColumn:
        """Evaluate a value expression to a full DeviceColumn of the given
        logical dtype (the projection entry point)."""
        from hyperspace_tpu.io.columnar import HOST_NP_DTYPES
        bare = e
        while isinstance(bare, E.Alias):
            bare = bare.child
        if isinstance(bare, E.Column):
            col = self.batch.column(bare.name)
            if col.dtype == out_dtype:
                return col  # renamed, not computed: the column moves as is
        s = self.string_column(e)
        if s is not None:
            if out_dtype != "string":
                raise HyperspaceException(
                    f"Expression {e!r} is string-valued; expected "
                    f"{out_dtype}.")
            return s
        data, validity = self.value(e)
        np_dtype = HOST_NP_DTYPES[out_dtype]
        data = self.xp.asarray(data)
        if data.ndim == 0:  # literal broadcast
            data = self.xp.full(self.batch.num_rows, data)
        return DeviceColumn(data.astype(np_dtype), out_dtype,
                            validity=validity)

    @staticmethod
    def _merge_validity(a, b):
        if a is None:
            return b
        if b is None:
            return a
        return a & b

    def _column_of(self, e: E.Expression) -> Optional[DeviceColumn]:
        if isinstance(e, E.Column):
            return self.batch.column(e.name)
        return None

    # -- predicates -------------------------------------------------------
    #
    # SQL three-valued (Kleene) logic: each predicate compiles to a pair
    # (true_mask, known) where `true_mask` marks rows DEFINITELY true
    # (so true_mask implies known; `known & ~true_mask` is definitely
    # false; `~known` is NULL/unknown). `known is None` means all-known —
    # the common null-free fast path stays two fused vector ops per node.
    # NOT flips definite truth within the known rows, so NULL stays NULL
    # and a filter never passes it (the reference inherits exactly this
    # from Spark; previously `~mask` wrongly passed null rows).

    def predicate(self, e: E.Expression):
        """Compile to a bool mask (True = row DEFINITELY passes; SQL's
        not-true rows, including NULLs, are False)."""
        mask, _known = self.predicate3(e)
        return mask

    def predicate3(self, e: E.Expression):
        """Compile to (true_mask, known); known=None means all rows known."""
        xp = self.xp
        n = self.batch.num_rows
        if isinstance(e, E.And):
            lt, lk = self.predicate3(e.left)
            rt, rk = self.predicate3(e.right)
            mask = lt & rt
            if lk is None and rk is None:
                return mask, None
            # Known iff both known, or either side is definitely false.
            lk_ = xp.ones(n, bool) if lk is None else lk
            rk_ = xp.ones(n, bool) if rk is None else rk
            return mask, (lk_ & rk_) | (lk_ & ~lt) | (rk_ & ~rt)
        if isinstance(e, E.Or):
            return self._or3(self.predicate3(e.left),
                             self.predicate3(e.right), n, xp)
        if isinstance(e, E.Not):
            t, k = self.predicate3(e.child)
            if k is None:
                return ~t, None
            return k & ~t, k
        if isinstance(e, E.IsNull):
            col = self._column_of(e.child)
            if col is None:
                raise HyperspaceException("IS NULL requires a column.")
            if col.validity is None:
                return xp.zeros(n, bool), None
            return ~col.validity, None
        if isinstance(e, E.IsNotNull):
            col = self._column_of(e.child)
            if col is None:
                raise HyperspaceException("IS NOT NULL requires a column.")
            if col.validity is None:
                return xp.ones(n, bool), None
            return col.validity, None
        if isinstance(e, E.In):
            # Set-membership fast path: integer column IN (int literals...)
            # is ONE vectorized isin instead of an O(values) fold of
            # EqualTo masks — the hybrid-scan lineage exclusion can carry
            # hundreds of deleted-file ids. Kleene semantics match the
            # fold exactly for integers: a NULL row is unknown, everything
            # else is definitely known.
            col = self._column_of(e.child)
            int_vals = [v.value for v in e.values
                        if isinstance(v, E.Literal)
                        and type(v.value) is int]
            if (col is not None and e.values
                    and len(int_vals) == len(e.values)
                    and col.dtype in ("int8", "int16", "int32", "int64")):
                member = xp.isin(xp.asarray(col.data),
                                 xp.asarray(int_vals, dtype=np.int64))
                if col.validity is None:
                    return member, None
                return member & col.validity, col.validity
            folded = None
            for v in e.values:
                term = self.predicate3(E.EqualTo(e.child, v))
                folded = term if folded is None else (
                    self._or3(folded, term, n, xp))
            if folded is None:
                return xp.zeros(n, bool), None
            return folded
        if isinstance(e, E.Like):
            # LIKE in DICTIONARY space. Device lane: the per-dictionary
            # membership bitmask comes from the segment cache
            # (`parallel/spmd.string_like_mask` — host regex paid once
            # per (dictionary, pattern), mask resident in HBM), so the
            # row test is ONE take and a warm repeat is link-free
            # instead of re-running the regex and shipping a fresh
            # code list every evaluation. Host lane: numpy end to end,
            # no device round-trip (the adaptive small-read path).
            import re as _re
            s = self.string_column(e.child)
            if s is None:
                raise HyperspaceException(
                    f"LIKE requires a string operand: {e!r}")
            if xp is not np and len(s.dictionary):
                from hyperspace_tpu.parallel.spmd import string_like_mask
                mask_d = string_like_mask(s, e.regex())
                member = xp.take(xp.asarray(mask_d),
                                 xp.clip(xp.asarray(s.data), 0,
                                         len(s.dictionary) - 1))
            else:
                rx = _re.compile(e.regex(), _re.DOTALL)
                d = np.asarray(s.dictionary)
                codes = np.nonzero([rx.fullmatch(str(v)) is not None
                                    for v in d])[0]
                member = xp.isin(xp.asarray(s.data),
                                 xp.asarray(codes.astype(np.int32)))
            if s.validity is None:
                return member, None
            return member & s.validity, s.validity
        if isinstance(e, (E.EqualTo, E.NotEqualTo, E.LessThan,
                          E.LessThanOrEqual, E.GreaterThan,
                          E.GreaterThanOrEqual)):
            return self._comparison(e)
        if isinstance(e, E.Literal):
            if isinstance(e.value, bool):
                return xp.full(n, e.value, dtype=bool), None
            raise HyperspaceException(f"Non-boolean literal predicate: {e!r}")
        raise HyperspaceException(f"Unsupported predicate: {e!r}")

    @staticmethod
    def _or3(a, b, n, xp):
        """Kleene OR over (true_mask, known) pairs: known iff both known,
        or either side is definitely true."""
        at, ak = a
        bt, bk = b
        mask = at | bt
        if ak is None and bk is None:
            return mask, None
        ak_ = xp.ones(n, bool) if ak is None else ak
        bk_ = xp.ones(n, bool) if bk is None else bk
        return mask, (ak_ & bk_) | mask

    def _comparison(self, e):
        # Resolved scalar subqueries compare as the literal they produced
        # (so the string code-space fast path still applies).
        left = (e.left.literal() if isinstance(e.left, E.ScalarSubquery)
                else e.left)
        right = (e.right.literal() if isinstance(e.right, E.ScalarSubquery)
                 else e.right)
        if left is not e.left or right is not e.right:
            e = type(e)(left, right)
        op = type(e).op
        ls = (None if isinstance(e.left, E.Literal)
              else self.string_column(e.left))
        rs = (None if isinstance(e.right, E.Literal)
              else self.string_column(e.right))
        # string expression vs string literal -> code-space range test
        if ls is not None and isinstance(e.right, E.Literal):
            mask = _string_literal_compare(op, ls, str(e.right.value),
                                           self.xp)
            return self._with_validity(mask, ls.validity, None)
        if rs is not None and isinstance(e.left, E.Literal):
            flipped = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                       "eq": "eq", "ne": "ne"}[op]
            mask = _string_literal_compare(flipped, rs,
                                           str(e.left.value), self.xp)
            return self._with_validity(mask, rs.validity, None)
        if ls is not None and rs is not None:
            # String col-to-col compare: remap both onto one merged sorted
            # dictionary, then compare codes (order-preserving).
            lc, rc = self._unified_codes(ls, rs)
            mask = getattr(self.xp.asarray(lc), _CMP[op])(rc)
            return self._with_validity(mask, ls.validity, rs.validity)
        if ls is not None or rs is not None:
            raise HyperspaceException(
                f"Cannot compare a string expression with a non-string "
                f"operand: {e!r}")
        lv, lval = self.value(e.left)
        rv, rval = self.value(e.right)
        mask = getattr(self.xp.asarray(lv), _CMP[op])(rv)
        return self._with_validity(mask, lval, rval)

    def _unified_codes(self, a: DeviceColumn, b: DeviceColumn):
        from hyperspace_tpu.io.columnar import _merged_dictionary
        host = self.xp is np
        _, (ra, rb), _ = _merged_dictionary([a.dictionary, b.dictionary],
                                            device=not host)
        if host:
            return ra[np.asarray(a.data)], rb[np.asarray(b.data)]
        import jax.numpy as jnp
        return jnp.take(ra, a.data), jnp.take(rb, b.data)

    @staticmethod
    def _with_validity(mask, lval, rval):
        """(raw compare, operand validity) -> (true_mask, known)."""
        validity = ExpressionCompiler._merge_validity(lval, rval)
        if validity is None:
            return mask, None
        return mask & validity, validity


def compile_predicate(expression: E.Expression, batch: ColumnBatch):
    """The predicate's mask over `batch`, on its lane. Carries no device
    scope: a fused stage's program names its predicate `hs.predicate`
    (`engine/fusion._interpret`); an eager filter's ops have none."""
    return ExpressionCompiler(batch).predicate(expression)


def apply_filter(batch: ColumnBatch, expression: E.Expression) -> ColumnBatch:
    """Filter a batch: fused mask eval + one compaction gather. On the
    device lane the row count is the single host sync (it sizes the
    result); on the host lane everything is numpy — no device traffic.

    Compile accounting: the expressions compiled here carry no jit
    entry point of their own — host batches evaluate eagerly in numpy,
    and device batches either run op-by-op (dispatch cost, no trace) or
    inside `engine/fusion.py`'s instrumented stage executable, where
    the `compile.*` counters and retrace events are recorded."""
    from hyperspace_tpu import telemetry

    mask = compile_predicate(expression, batch)
    if isinstance(mask, np.ndarray):
        return batch.take(np.nonzero(mask)[0].astype(np.int32))
    import jax.numpy as jnp

    from hyperspace_tpu.ops.compact import compact_indices

    with telemetry.span("hs.stage.sync", "operator"):
        count = int(jnp.sum(mask))  # host sync — sizes the output
    # The sync is a true span boundary: input + mask + output are all
    # device-resident here — fold an HBM sample into the watermark.
    telemetry.memory.maybe_sample()
    with telemetry.span("hs.stage.compact", "operator", rows=count):
        return batch.take(compact_indices(mask, count))
