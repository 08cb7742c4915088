"""Device group-by aggregation: direct-address slots, or sort-based
segment reductions.

The reference leaves aggregation to Spark SQL's hash/sort aggregates.
Here a grouping whose keys all have a small domain known from their
type (a dictionary's codes, a bool, an int8; a nullable key has one
value more) and whose domains multiply to at most
`DIRECT_GROUP_MAX_SLOTS` is reduced by DIRECT ADDRESS: a row's group is
the mixed-radix code of its keys, computed on the device, and every
aggregate is reduced slot by slot by masked reductions, which XLA
lowers to trees, in ONE program (`_direct_reduce`): no sort, no row
gather, no scatter. Rows may come with a mask (a filter's selection
handed over uncompacted); a masked row belongs to no slot. The one
host sync is the slots' row counts; empty slots are dropped after it,
and the groups come out in key order, as the sort path gives them.

Every other grouping is formed by ONE stable multi-key sort (32-bit
lanes) and reduced with XLA segment ops, one host sync (the group
count) sizing the output. One group (a global aggregate) is reduced by
plain reductions instead: a segment op into one slot is a scatter that
adds every row into it in turn.

SQL null semantics: sum/min/max/avg ignore null inputs; count(col) counts
non-null; count(*) counts rows; a group whose inputs are all null yields
null (validity False) for sum/min/max/avg and 0 for count.

avg and stddev of an INTEGER column are exact on both lanes: each
group's integer moments (count, sum, sum of squares) are summed in int64,
which no lane rounds, and finished on the host in IEEE float64, one
correctly rounded division (and one square root) from integers that
float64 holds exactly (`_finish_exact`). The chip's own float64 is an
f32 pair of about 48 bits whose division is not correctly rounded, so
computing these on the device would differ from the host lane, and from
SQL's value, in the last bits. Where the moments could overflow or
leave float64's 53 exact bits, the float path below serves.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import (HOST_NP_DTYPES, ColumnBatch,
                                        DeviceColumn)
from hyperspace_tpu.plan.nodes import AggSpec
from hyperspace_tpu.plan.schema import Schema
from hyperspace_tpu import telemetry
from hyperspace_tpu.telemetry import instrumented_jit


@instrumented_jit("aggregate.group_phase_a", scope="hs.aggregate")
def _group_phase_a(operands):
    """(sort permutation, sorted-space segment ids) of the group-key
    lanes, fused into one executable (staged sort + adjacent-difference
    segmenting; the narrow path's sort yields the sorted lanes for
    free — no re-gather)."""
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import _staged_sort

    ops = list(operands)
    n = ops[0].shape[0]
    perm, sorted_ops = _staged_sort(ops)
    differs = jnp.zeros(n, dtype=jnp.int32)
    for k in sorted_ops:
        differs = differs | jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32),
             (k[1:] != k[:-1]).astype(jnp.int32)])
    segment_ids = jnp.cumsum(differs, dtype=jnp.int32)
    return perm, segment_ids


# Wide groupings (q64's 15 columns -> ~25 lanes) pay the chunked-LSD
# sort's data movement AND its minutes-long one-time XLA compile at each
# novel shape. Above this lane count the HASHED
# phase sorts ONE u64 hash lane instead and verifies no collision split
# a group (fallback: the full sort). 64-bit hash over ~10^7 rows makes
# the fallback astronomically rare; correctness never depends on it.
HASH_GROUP_MIN_LANES = 5


@instrumented_jit("aggregate.group_phase_a_hashed", scope="hs.aggregate")
def _group_phase_a_hashed(operands):
    """(perm, segment ids, collision flag) via ONE u64-hash-lane sort.
    Equal keys share a hash, so a stable hash sort puts every group in
    one contiguous run unless two DIFFERENT keys collide; `collision` is
    true iff any adjacent-row group boundary (full-lane difference)
    occurs INSIDE an equal-hash run — exactly the split-group case. The
    caller re-runs the exact full-lane sort when it fires. The last
    output packs (num_segments, collision) into one int64 scalar so the
    caller's sizing sync is a single fetch."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.hash_partition import dual_hash64

    ops = list(operands)
    n = ops[0].shape[0]
    h = dual_hash64(ops)
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_h, perm = jax.lax.sort([h, iota], num_keys=1, is_stable=True)
    zero = jnp.zeros(1, dtype=jnp.int32)
    differs = zero
    for k in ops:
        ks = jnp.take(k, perm)
        differs = differs | jnp.concatenate(
            [zero, (ks[1:] != ks[:-1]).astype(jnp.int32)])
    h_differs = jnp.concatenate(
        [zero, (sorted_h[1:] != sorted_h[:-1]).astype(jnp.int32)])
    collision = jnp.any((differs == 1) & (h_differs == 0))
    segment_ids = jnp.cumsum(differs, dtype=jnp.int32)
    packed = (segment_ids[-1].astype(jnp.int64) * jnp.int64(2)
              + collision.astype(jnp.int64))
    return perm, segment_ids, packed


@instrumented_jit("aggregate.exact_moments", scope="hs.aggregate",
                  static_argnames=("num_groups",))
def _exact_moments(values, valid, segment_ids, num_groups: int):
    """Per-group count, sum and sum of squares of an integer column in
    int64, and the largest magnitude in it (which says on the host
    whether the squares overflowed)."""
    return _moments(values, valid, segment_ids, _SegmentReduce(num_groups))


def _moments(values, valid, ids, reduce):
    import jax.numpy as jnp

    x = jnp.where(valid, values, 0).astype(jnp.int64)
    moments = [reduce.sum(y, ids)
               for y in (valid.astype(jnp.int64), x, x * x)]
    return (*moments, jnp.max(jnp.abs(x).astype(jnp.uint64)))


_EXACT = 1 << 53  # integers float64 holds exactly


def _exact_candidate(func: str, dtype: str, out_dtype: str):
    """avg / stddev of an integer column with a float64 result (by the
    logical dtype: a float64 column carries its bits as int64)."""
    return (func in ("avg", "stddev") and out_dtype == "float64"
            and dtype != "string"
            and np.dtype(HOST_NP_DTYPES[dtype]).kind in "iu")


def _finish_exact(func: str, n, total, squares, amax: int):
    """avg = total / n, stddev_samp = sqrt((n sq - total^2) / (n (n-1)))
    from exact int64 moments, each division (and the square root) one
    IEEE rounding of integers below 2**53, so both lanes give SQL's value
    to the nearest double. None where the moments may have overflowed
    or left the exact range: the caller's float path serves."""
    n_max = int(n.max()) if len(n) else 0
    amax = int(amax)
    if amax * n_max >= _EXACT:
        return None
    with np.errstate(divide="ignore", invalid="ignore"):
        if func == "avg":
            return total.astype(np.float64) / np.maximum(n, 1).astype(
                np.float64)
        if max(amax, 1) ** 2 * n_max ** 2 >= _EXACT:
            return None
        num = n * squares - total * total
        den = n * (n - 1)
        return np.sqrt(num.astype(np.float64)
                       / np.maximum(den, 1).astype(np.float64))


def group_aggregate(batch: ColumnBatch, group_columns: Sequence[str],
                    aggregates: Sequence[AggSpec],
                    out_schema: Schema) -> ColumnBatch:
    """The sort path (`path` on the operator's record: `sorted`, `hashed`
    for a grouping of HASH_GROUP_MIN_LANES lanes and more, `global` for
    none) and the host lane's mirror of it."""
    telemetry.annotate(path="sorted" if group_columns else "global")
    if batch.is_host and batch.num_rows > 0:
        return _host_group_aggregate(batch, group_columns, aggregates,
                                     out_schema)
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import column_sort_lanes

    n = batch.num_rows
    _NP_OF = {"int64": jnp.int64, "float64": jnp.float64, "int32": jnp.int32,
              "float32": jnp.float32, "int8": jnp.int8, "int16": jnp.int16,
              "bool": jnp.bool_, "date32": jnp.int32, "timestamp": jnp.int64,
              "string": jnp.int32}

    if n == 0:
        if not group_columns:
            # SQL: a GLOBAL aggregate over zero rows is ONE row —
            # count/count_distinct 0, everything else NULL. (The
            # cross-join scalar-assembly queries rely on this: an empty
            # bucket must not collapse the whole product to zero rows.)
            columns = {}
            for spec in aggregates:
                f = out_schema.field(spec.alias)
                if (spec.column != "*"
                        and batch.column(spec.column).is_string
                        and spec.func not in ("count", "count_distinct")):
                    # Same contract as the non-empty path: surface the
                    # unsupported case here, not as a downstream crash on a
                    # dictionary-less string column.
                    raise HyperspaceException(
                        f"Aggregate {spec.func} over string column "
                        f"{spec.column} is not supported.")
                if spec.func in ("count", "count_distinct"):
                    columns[f.name] = DeviceColumn(
                        jnp.zeros(1, dtype=jnp.int64), "int64")
                else:
                    columns[f.name] = DeviceColumn(
                        jnp.zeros(1, dtype=_NP_OF[f.dtype]), f.dtype,
                        validity=jnp.zeros(1, dtype=bool))
            return ColumnBatch(out_schema, columns)
        columns = {}
        for f in out_schema.fields:
            src = (batch.column(f.name)
                   if f.name in [batch.schema.field(c).name
                                 for c in group_columns] else None)
            columns[f.name] = DeviceColumn(
                data=jnp.zeros(0, dtype=_NP_OF[f.dtype]), dtype=f.dtype,
                dictionary=src.dictionary if src is not None else None,
                dict_hashes=src.dict_hashes if src is not None else None)
        return ColumnBatch(out_schema, columns)

    if group_columns:
        operands: List = []
        for name in group_columns:
            operands.extend(column_sort_lanes(batch.column(name)))
        # ONE fused executable: hash-lane sort for wide groupings (full
        # staged sort re-run on the astronomically-rare collision),
        # staged narrow-pass sort otherwise + segment-id derivation.
        # Separate eager ops would each pay their own compile.
        ops = tuple(jnp.asarray(op) for op in operands)
        if len(ops) >= HASH_GROUP_MIN_LANES:
            telemetry.annotate(path="hashed")
            perm, segment_ids, packed = _group_phase_a_hashed(ops)
            packed = int(packed)  # the one host sync
            if packed & 1:  # hash collision split a group: exact re-run
                telemetry.get_registry().counter(
                    "aggregate.hashed.fallbacks").inc()
                perm, segment_ids = _group_phase_a(ops)
                num_groups = int(segment_ids[-1]) + 1
            else:
                num_groups = (packed >> 1) + 1
        else:
            perm, segment_ids = _group_phase_a(ops)
            num_groups = int(segment_ids[-1]) + 1  # the one host sync
        sorted_batch = batch.take(perm)
    else:
        segment_ids = jnp.zeros(n, dtype=jnp.int32)
        num_groups = 1
        sorted_batch = batch

    # Integer avg / stddev: exact moments on the device, ONE fetch of
    # all of them, finished on the host (module docstring).
    moments, funcs = {}, {}
    for spec in aggregates:
        if spec.column == "*":
            continue
        src = sorted_batch.column(spec.column)
        if _exact_candidate(spec.func, src.dtype,
                            out_schema.field(spec.alias).dtype):
            valid = (src.validity if src.validity is not None
                     else jnp.ones(n, dtype=bool))
            funcs[spec.alias] = spec.func
            moments[spec.alias] = _exact_moments(src.raw, valid, segment_ids,
                                                 num_groups=num_groups)
    exact = {alias: _finish_exact(funcs[alias], *fetched)
             for alias, fetched in jax.device_get(moments).items()}

    plan, specs_columns = [], []
    for spec in aggregates:
        out_field = out_schema.field(spec.alias)
        if spec.func == "count" and spec.column == "*":
            plan.append(("count_rows", None, "int64", False))
            specs_columns.append(None)
            continue
        src = sorted_batch.column(spec.column)
        if src.is_string and spec.func not in ("count", "count_distinct"):
            raise HyperspaceException(
                f"Aggregate {spec.func} over string column {spec.column} "
                "is not supported.")
        plan.append((spec.func, src.dtype, out_field.dtype,
                     exact.get(spec.alias) is not None))
        specs_columns.append((src.raw, src.validity))
    keys = [sorted_batch.column(name) for name in group_columns]
    out_keys, out_specs = _group_finish(
        segment_ids, tuple((k.raw, k.validity) for k in keys),
        tuple(specs_columns), plan=tuple(plan), num_groups=num_groups)

    columns = {}
    for name, src, (raw, validity) in zip(group_columns, keys, out_keys):
        columns[batch.schema.field(name).name] = src.with_raw(raw, validity)
    for spec, (data, validity) in zip(aggregates, out_specs):
        out_field = out_schema.field(spec.alias)
        if data is None:
            # float64 as its bits: exact through every later move
            data = jnp.asarray(exact[spec.alias].view(np.int64))
        dtype = ("int64" if spec.func in ("count", "count_distinct")
                 else out_field.dtype)
        columns[out_field.name] = DeviceColumn(data, dtype,
                                               validity=validity)
    return ColumnBatch(out_schema, columns)


# Direct address serves a grouping of at most this many slots (the
# product of its keys' domains). On a TPU v5e at 59 M rows, one float64
# sum and a count (PERF.md section 6): direct 19 ms at 4 slots, 164 at
# 256, 625 at 1024; the sort path 14.4 s whatever the slots, so the two
# cross near 20,000. 256 stays far under that and serves an int8 key.
DIRECT_GROUP_MAX_SLOTS = 256


def _key_domain(col: DeviceColumn):
    """The values a group key can take, known from its type (a null one
    more), or None."""
    if col.is_string:
        domain = len(col.dictionary)
    elif col.dtype == "bool":
        domain = 2
    elif col.dtype == "int8":
        domain = 256
    else:
        return None
    return domain + (col.validity is not None)


def direct_domains(batch: ColumnBatch, group_columns: Sequence[str]):
    """Each group column's domain where direct address serves the
    grouping of `batch` (device lane, every key of known domain, at most
    DIRECT_GROUP_MAX_SLOTS slots in all); else None."""
    if not group_columns or batch.num_rows == 0 or batch.is_host:
        return None
    domains = tuple(_key_domain(batch.column(c)) for c in group_columns)
    if None in domains or int(np.prod(domains)) > DIRECT_GROUP_MAX_SLOTS:
        return None
    return domains


class _DirectPlan:
    """What shapes one direct-address program, hashable for jit by
    `key`: the input's schema and dictionaries (`aux`), each group key's
    (name, domain, nullable), and each aggregate's (func, input, dtype,
    out_dtype, exact), its input a column name, "*" or an Expression
    computed inside the program."""

    def __init__(self, batch: ColumnBatch, group_columns, domains,
                 aggregates, out_schema):
        import json

        self.schema = batch.schema
        self.aux = {f.name: batch.columns[f.name].dictionary
                    for f in batch.schema.fields}
        self.keys = tuple(
            (batch.schema.field(c).name, d,
             batch.column(c).validity is not None)
            for c, d in zip(group_columns, domains))
        self.slots = int(np.prod(domains))
        self.specs = []
        for spec in aggregates:
            out_dtype = out_schema.field(spec.alias).dtype
            if not spec.is_expression and spec.column == "*":
                self.specs.append(("count_rows", "*", None, "int64", False))
                continue
            dtype = spec.input_dtype(batch.schema)
            if not spec.is_expression:
                src = batch.column(spec.column)
                if src.is_string and spec.func not in ("count",
                                                       "count_distinct"):
                    raise HyperspaceException(
                        f"Aggregate {spec.func} over string column "
                        f"{spec.column} is not supported.")
            self.specs.append((spec.func, spec.column, dtype, out_dtype,
                               _exact_candidate(spec.func, dtype,
                                                out_dtype)))
        # an expression over a string column bakes its dictionary into
        # the program: keyed by content (a union's dictionary is a new
        # array every query)
        read = set()
        for spec in aggregates:
            if spec.is_expression:
                read |= {batch.schema.field(c).name
                         for c in spec.references()}
        self.key = json.dumps([
            [(f.name, f.dtype,
              batch.columns[f.name].validity is not None,
              _dictionary_key(batch.columns[f.name].dictionary)
              if f.name in read else None)
             for f in batch.schema.fields],
            self.keys,
            [(func, c.to_dict() if hasattr(c, "to_dict") else c, d, o, e)
             for func, c, d, o, e in self.specs]], sort_keys=True)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, _DirectPlan) and other.key == self.key


def _dictionary_key(dictionary):
    if dictionary is None:
        return None
    import hashlib
    return hashlib.blake2b("\x1f".join(map(str, dictionary)).encode(),
                           digest_size=16).hexdigest()


@instrumented_jit("aggregate.direct_reduce", scope="hs.aggregate",
                  static_argnames=("plan",))
def _direct_reduce(plan: _DirectPlan, tree, row_mask):
    """The direct-address group-by as ONE program: each row's slot (the
    mixed-radix code of its keys, first key most significant, a null
    key 0; a row `row_mask` drops goes to no slot), each expression
    input, and every aggregate per slot (`_SlotReduce`). Returns (the
    rows of each slot, the aggregates as `_reduce_plan` gives them in
    float, the exact integer moments of each `exact` spec)."""
    import jax.numpy as jnp

    from hyperspace_tpu.engine.compiler import ExpressionCompiler
    from hyperspace_tpu.io.columnar import tree_to_batch

    batch = tree_to_batch(tree, plan.schema, plan.aux)
    n = batch.num_rows
    slot = jnp.zeros(n, dtype=jnp.int32)
    for name, domain, nullable in plan.keys:
        col = batch.column(name)
        code = col.raw.astype(jnp.int32) + (128 if col.dtype == "int8"
                                            else 0)
        if nullable:
            code = jnp.where(col.validity, code + 1, 0)
        slot = slot * domain + code
    if row_mask is not None:
        slot = jnp.where(row_mask, slot, plan.slots)
    reduce = _SlotReduce(plan.slots)
    compiler = ExpressionCompiler(batch)
    columns, specs, moments = [], [], []
    for func, column, dtype, out_dtype, exact in plan.specs:
        specs.append((func, dtype, out_dtype, False))
        if func == "count_rows":
            columns.append(None)
            continue
        src = (compiler.value_column(column, dtype)
               if not isinstance(column, str) else batch.column(column))
        valid = src.validity
        if row_mask is not None:
            valid = row_mask if valid is None else valid & row_mask
        columns.append((src.raw, valid))
        if exact:
            moments.append(_moments(
                src.data, valid if valid is not None
                else jnp.ones(n, dtype=bool), slot, reduce))
    rows = reduce.sum(jnp.ones(n, dtype=jnp.int32), slot)
    return rows, _reduce_plan(tuple(specs), columns, slot, reduce), moments


@instrumented_jit("aggregate.direct_slots", scope="hs.aggregate")
def _take_slots(outputs, used):
    """The used slots of every per-slot output."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.take(a, used), outputs)


def direct_aggregate(batch: ColumnBatch, group_columns: Sequence[str],
                     aggregates: Sequence[AggSpec], out_schema: Schema,
                     domains, row_mask=None) -> ColumnBatch:
    """The group-by of `batch` by direct address (module docstring), for
    the `domains` `direct_domains` gave. An aggregate's input may be an
    Expression (computed inside the program); `row_mask` (bool, one a
    row) drops the rows it is False for: a filter's selection handed
    over uncompacted. The one host sync is each slot's row count (with
    the exact moments); the used slots, in key order, are the groups."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import batch_to_tree

    needed = {batch.schema.field(c).name for c in group_columns}
    for spec in aggregates:
        needed |= {batch.schema.field(c).name for c in spec.references()}
    sub = batch.select([f.name for f in batch.schema.fields
                        if f.name in needed])
    plan = _DirectPlan(sub, group_columns, domains, aggregates, out_schema)
    tree, _ = batch_to_tree(sub, computes_on=())
    rows, outputs, moments = _direct_reduce(plan, tree, row_mask)
    rows, moments = jax.device_get((rows, moments))  # the one host sync
    telemetry.annotate(path="direct")
    telemetry.get_registry().counter("aggregate.direct.rows").inc(
        batch.num_rows)
    used = np.flatnonzero(rows)
    outputs = _take_slots(outputs, jnp.asarray(used.astype(np.int32)))
    moments = iter(moments)

    columns = {}
    digits = used
    for name, domain, nullable in reversed(plan.keys):
        src = batch.column(name)
        code = digits % domain
        digits = digits // domain
        valid = None
        if nullable:
            valid, code = code > 0, np.maximum(code - 1, 0)
        if src.dtype == "int8":
            code = code - 128
        columns[name] = src.with_raw(
            jnp.asarray(code.astype(HOST_NP_DTYPES[src.dtype])),
            jnp.asarray(valid) if valid is not None else None)
    for spec, (func, _, _, out_dtype, exact), (data, validity) in zip(
            aggregates, plan.specs, outputs):
        if exact:
            count, total, squares, amax = (np.asarray(m)[used]
                                           if np.ndim(m) else m
                                           for m in next(moments))
            finished = _finish_exact(func, count, total, squares, amax)
            if finished is not None:  # else the float value serves
                data = jnp.asarray(finished.view(np.int64))
        dtype = ("int64" if func in ("count", "count_distinct", "count_rows")
                 else out_dtype)
        columns[out_schema.field(spec.alias).name] = DeviceColumn(
            data, dtype, validity=validity)
    return ColumnBatch(out_schema, {f.name: columns[f.name]
                                    for f in out_schema.fields})


@instrumented_jit("aggregate.group_finish", scope="hs.aggregate",
                  static_argnames=("plan", "num_groups"))
def _group_finish(segment_ids, keys, columns, plan, num_groups: int):
    """Every reduction of the group-by after its grouping sort, as ONE
    program: each group's first row (a `searchsorted` of the sorted
    segment ids) and its keys out of `keys`, then each aggregate of
    `plan` over its `columns` entry (`_reduce_plan`)."""
    import jax.numpy as jnp

    firsts = jnp.searchsorted(segment_ids,
                              jnp.arange(num_groups, dtype=jnp.int32),
                              side="left")
    out_keys = tuple(
        (jnp.take(raw, firsts),
         jnp.take(validity, firsts) if validity is not None else None)
        for raw, validity in keys)
    if num_groups == 1:
        # One group (a global aggregate): a reduction a column, which
        # XLA lowers to a tree. A scatter into one slot adds the rows in
        # turn, which on the chip is serial and, in its f32-pair
        # float64, loses about 1e-12 of a sum of a million products
        # (a tree: about 1e-15).
        reduce = _GlobalReduce()
    else:
        reduce = _SegmentReduce(num_groups)
    return out_keys, _reduce_plan(plan, columns, segment_ids, reduce)


class _GlobalReduce:
    """One group: plain reductions."""

    def sum(self, x, ids):
        import jax.numpy as jnp
        return jnp.sum(x, axis=0, keepdims=True)

    def min(self, x, ids):
        import jax.numpy as jnp
        return jnp.min(x, axis=0, keepdims=True)

    def max(self, x, ids):
        import jax.numpy as jnp
        return jnp.max(x, axis=0, keepdims=True)

    def back(self, per_group, ids):
        """Each row's group's value."""
        import jax.numpy as jnp
        return jnp.take(per_group, ids)


class _SegmentReduce(_GlobalReduce):
    """Sorted segment ids: XLA's segment ops."""

    def __init__(self, num_groups: int):
        self.n = num_groups

    def sum(self, x, ids):
        import jax
        return jax.ops.segment_sum(x, ids, num_segments=self.n)

    def min(self, x, ids):
        import jax
        return jax.ops.segment_min(x, ids, num_segments=self.n)

    def max(self, x, ids):
        import jax
        return jax.ops.segment_max(x, ids, num_segments=self.n)


class _SlotReduce(_GlobalReduce):
    """Direct-address slot ids in any order, `slots` of them; a row whose
    id is `slots` (masked) belongs to none. Each slot is a masked
    reduction over all rows, which XLA lowers to a tree."""

    def __init__(self, slots: int):
        self.n = slots

    def _per_slot(self, fn, x, ids, fill):
        import jax.numpy as jnp
        hit = ids[None, :] == jnp.arange(self.n, dtype=ids.dtype)[:, None]
        return fn(jnp.where(hit, x[None, :], fill), axis=1)

    def sum(self, x, ids):
        import jax.numpy as jnp
        return self._per_slot(jnp.sum, x, ids, jnp.zeros((), x.dtype))

    def min(self, x, ids):
        import jax.numpy as jnp
        return self._per_slot(jnp.min, x, ids, _dtype_max(x.dtype))

    def max(self, x, ids):
        import jax.numpy as jnp
        return self._per_slot(jnp.max, x, ids, _dtype_min(x.dtype))

    def back(self, per_group, ids):
        import jax.numpy as jnp
        padded = jnp.concatenate([per_group, jnp.zeros(1, per_group.dtype)])
        return jnp.take(padded, ids)


def _reduce_plan(plan, columns, ids, reduce):
    """Each aggregate of `plan` — `(func, dtype, out_dtype, exact)` a
    spec, func `count_rows` for count(*) — over its `columns` entry,
    the (raw, validity) of its column, grouped by `ids` through
    `reduce`. An `exact` spec (integer avg / stddev) returns only its
    validity; the host has finished its value (`_finish_exact`). SQL
    null semantics as the module docstring gives them."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.io.columnar import HOST_NP_DTYPES
    from hyperspace_tpu.ops.keys import column_sort_lanes

    rows = ids.shape[0]
    out = []
    for (func, dtype, out_dtype, exact), entry in zip(plan, columns):
        if func == "count_rows":
            out.append((reduce.sum(jnp.ones(rows, dtype=jnp.int64), ids),
                        None))
            continue
        src = DeviceColumn(entry[0], dtype, entry[1])
        valid = (src.validity if src.validity is not None
                 else jnp.ones(rows, dtype=bool))
        counts = reduce.sum(valid.astype(jnp.int64), ids)
        if func == "count":
            out.append((counts, None))
            continue
        if func == "count_distinct":
            # Distinct non-null values per group: ONE more device sort
            # keyed (group, invalid-last, *value lanes), then count run
            # starts at valid rows. Strings count by dictionary code
            # (dictionaries are sorted+unique, so code identity is value
            # identity); nulls sort after the valid block so a shared
            # masked value can never swallow a valid run start.
            lanes = column_sort_lanes(src)
            invalid = (~valid).astype(jnp.int32)
            # Bounded width (one column: <= 5 operands) — the single
            # fused sort also returns the sorted lanes.
            res = jax.lax.sort([ids, invalid, *lanes],
                               num_keys=2 + len(lanes))
            seg_s, inv_s, lanes_s = res[0], res[1], res[2:]
            differs = seg_s[1:] != seg_s[:-1]
            for lane in lanes_s:
                differs = differs | (lane[1:] != lane[:-1])
            run_start = jnp.concatenate(
                [jnp.ones(1, dtype=bool), differs])
            data = reduce.sum((run_start & (inv_s == 0)).astype(jnp.int64),
                              seg_s)
            out.append((data, None))
            continue
        validity_out = counts > 0
        if exact:
            out.append((None, validity_out if func == "avg"
                        else counts > 1))
            continue
        values = src.data
        if func in ("sum", "avg"):
            acc_dtype = (jnp.float64 if out_dtype == "float64"
                         else jnp.int64)
            total = reduce.sum(
                jnp.where(valid, values, 0).astype(acc_dtype), ids)
            if func == "sum":
                data = total
            else:
                data = total.astype(jnp.float64) / jnp.maximum(counts, 1)
        elif func == "stddev":
            # Sample stddev (SQL stddev_samp) via TWO passes: per-group
            # mean, then squared deviations — the one-pass sum-of-squares
            # identity catastrophically cancels in float64 when
            # mean^2 >> variance (ids, timestamps). Null when fewer than
            # 2 non-null inputs.
            x = jnp.where(valid, values, 0).astype(jnp.float64)
            cnt = counts.astype(jnp.float64)
            mu = reduce.sum(x, ids) / jnp.maximum(cnt, 1)
            dev = jnp.where(valid, x - reduce.back(mu, ids), 0.0)
            var = reduce.sum(dev * dev, ids) / jnp.maximum(cnt - 1, 1)
            data = jnp.sqrt(jnp.maximum(var, 0.0))
            validity_out = counts > 1
        elif func == "min":
            big = _dtype_max(values.dtype)
            data = reduce.min(jnp.where(valid, values, big), ids)
        else:  # max
            small = _dtype_min(values.dtype)
            data = reduce.max(jnp.where(valid, values, small), ids)
        # Validity is attached unconditionally: deciding with
        # `bool(any(~validity_out))` would cost one blocking device sync
        # per aggregate; an all-True mask is semantically identical.
        out.append((data.astype(HOST_NP_DTYPES[out_dtype]), validity_out))
    return tuple(out)


def _dtype_max(dtype):
    import jax.numpy as jnp
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dtype).max


def _dtype_min(dtype):
    import jax.numpy as jnp
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _host_group_aggregate(batch: ColumnBatch,
                          group_columns: Sequence[str],
                          aggregates: Sequence[AggSpec],
                          out_schema: Schema) -> ColumnBatch:
    """Host-lane (numpy) mirror of the device aggregation: same grouping
    (stable lexicographic sort, nulls first) and the same SQL null
    semantics, with contiguous-segment `ufunc.reduceat` reductions."""
    from hyperspace_tpu.ops.keys import host_column_sort_lanes

    _HOST_NP = {"int64": np.int64, "float64": np.float64, "int32": np.int32,
                "float32": np.float32, "int8": np.int8, "int16": np.int16,
                "bool": np.bool_, "date32": np.int32, "timestamp": np.int64,
                "string": np.int32}
    from hyperspace_tpu.ops.keys import host_dense_group_ids

    n = batch.num_rows
    if group_columns:
        operands = []
        for name in group_columns:
            operands.extend(host_column_sort_lanes(batch.column(name)))
        perm, segment_ids = host_dense_group_ids(operands)
        perm = perm.astype(np.int32)
        num_groups = int(segment_ids[-1]) + 1
        sorted_batch = batch.take(perm)
        starts = np.searchsorted(segment_ids, np.arange(num_groups),
                                 side="left")
    else:
        segment_ids = np.zeros(n, dtype=np.int32)
        num_groups = 1
        sorted_batch = batch
        starts = np.zeros(1, dtype=np.int64)

    columns = {}
    for name in group_columns:
        src = sorted_batch.column(name)
        f = batch.schema.field(name)
        columns[f.name] = DeviceColumn(
            data=np.asarray(src.data)[starts], dtype=src.dtype,
            validity=(np.asarray(src.validity)[starts]
                      if src.validity is not None else None),
            dictionary=src.dictionary, dict_hashes=src.dict_hashes)

    for spec in aggregates:
        out_field = out_schema.field(spec.alias)
        if spec.func == "count" and spec.column == "*":
            data = np.bincount(segment_ids,
                               minlength=num_groups).astype(np.int64)
            columns[out_field.name] = DeviceColumn(data, "int64")
            continue
        src = sorted_batch.column(spec.column)
        if src.is_string and spec.func not in ("count", "count_distinct"):
            raise HyperspaceException(
                f"Aggregate {spec.func} over string column {spec.column} "
                "is not supported.")
        valid = (np.asarray(src.validity) if src.validity is not None
                 else np.ones(n, dtype=bool))
        counts = np.bincount(segment_ids, weights=valid,
                             minlength=num_groups).astype(np.int64)
        if spec.func == "count":
            columns[out_field.name] = DeviceColumn(counts, "int64")
            continue
        if spec.func == "count_distinct":
            # Mirror of the device lane: lexsort (segment, invalid-last,
            # *value lanes), count run starts at valid rows.
            lanes = [np.asarray(lane)
                     for lane in host_column_sort_lanes(src)]
            inv = (~valid).astype(np.int8)
            order = np.lexsort(tuple(reversed(
                [segment_ids, inv] + lanes)))
            seg_s = segment_ids[order]
            differs = seg_s[1:] != seg_s[:-1]
            for lane in lanes:
                lane_s = lane[order]
                differs = differs | (lane_s[1:] != lane_s[:-1])
            run_start = np.concatenate([[True], differs])
            data = np.bincount(
                seg_s, weights=(run_start & valid[order]),
                minlength=num_groups).astype(np.int64)
            columns[out_field.name] = DeviceColumn(data, "int64")
            continue
        values = np.asarray(src.data)
        validity_out = counts > 0
        data = None
        if _exact_candidate(spec.func, src.dtype, out_field.dtype):
            # the device lane's exact moments, in numpy
            x = np.where(valid, values, 0).astype(np.int64)
            data = _finish_exact(spec.func, counts,
                                 np.add.reduceat(x, starts),
                                 np.add.reduceat(x * x, starts),
                                 np.abs(x).astype(np.uint64).max())
        if data is not None:
            if spec.func == "stddev":
                validity_out = counts > 1
        elif spec.func in ("sum", "avg"):
            acc = (np.float64 if out_field.dtype == "float64" else np.int64)
            total = np.add.reduceat(
                np.where(valid, values, 0).astype(acc), starts)
            data = (total if spec.func == "sum"
                    else total.astype(np.float64) / np.maximum(counts, 1))
        elif spec.func == "stddev":
            # Two-pass shifted variance; see the device lane for why the
            # one-pass identity is numerically unsafe.
            x = np.where(valid, values, 0).astype(np.float64)
            cnt = counts.astype(np.float64)
            mu = np.add.reduceat(x, starts) / np.maximum(cnt, 1)
            dev = np.where(valid, x - mu[segment_ids], 0.0)
            var = np.add.reduceat(dev * dev, starts) / np.maximum(
                cnt - 1, 1)
            data = np.sqrt(np.maximum(var, 0.0))
            validity_out = counts > 1
        elif spec.func == "min":
            big = (np.inf if np.issubdtype(values.dtype, np.floating)
                   else np.iinfo(values.dtype).max)
            data = np.minimum.reduceat(np.where(valid, values, big), starts)
        else:  # max
            small = (-np.inf if np.issubdtype(values.dtype, np.floating)
                     else np.iinfo(values.dtype).min)
            data = np.maximum.reduceat(np.where(valid, values, small), starts)
        columns[out_field.name] = DeviceColumn(
            data.astype(_HOST_NP[out_field.dtype]), out_field.dtype,
            validity=validity_out)
    return ColumnBatch(out_schema, columns)
