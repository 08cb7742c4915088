"""Set-operation kernels: INTERSECT / EXCEPT with SQL DISTINCT semantics.

Output = DISTINCT rows of the left side present in (intersect) / absent
from (except) the right side. Row identity treats NULL as equal to NULL
(SQL set-op semantics — joins do the opposite), so validity participates
as a leading key lane and null slots' payloads are zeroed to one
canonical value before lane decomposition.

Device path: ONE fused executable — joint staged sort of both sides'
lanes -> dense group ids -> right-presence scatter + first-left-occurrence
scatter -> selection mask — plus the single host sync that sizes the
output. Host path is the numpy mirror over `host_dense_group_ids`.

The reference serializes Catalyst Intersect/Except for exactly these
queries (`index/serde/package.scala:64-167`); execution there is Spark's.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

import hyperspace_tpu._jax_config  # noqa: F401
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import ColumnBatch, unify_string_columns
from hyperspace_tpu.telemetry import instrumented_jit


def _zeroed(xp, data, valid):
    """Null slots -> one canonical payload so all NULLs compare equal."""
    if valid is None:
        return data
    return xp.where(valid, data, xp.zeros((), data.dtype))


def _device_lanes(left: ColumnBatch, right: ColumnBatch,
                  names: Sequence[str]) -> List:
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import key_lanes

    lanes: List = []
    for name in names:
        lcol, rcol = left.column(name), right.column(name)
        if lcol.is_string != rcol.is_string:
            raise HyperspaceException(
                f"Set-op column type mismatch: {name}")
        if lcol.is_string:
            lcol, rcol = unify_string_columns(lcol, rcol)
        lv = (jnp.ones(left.num_rows, bool) if lcol.validity is None
              else jnp.asarray(lcol.validity))
        rv = (jnp.ones(right.num_rows, bool) if rcol.validity is None
              else jnp.asarray(rcol.validity))
        lanes.append(jnp.concatenate([lv, rv]).astype(jnp.int32))
        ldata, rdata = jnp.asarray(lcol.data), jnp.asarray(rcol.data)
        if ldata.dtype != rdata.dtype:
            common = jnp.promote_types(ldata.dtype, rdata.dtype)
            ldata, rdata = ldata.astype(common), rdata.astype(common)
        ldata = _zeroed(jnp, ldata, None if lcol.validity is None
                        else jnp.asarray(lcol.validity))
        rdata = _zeroed(jnp, rdata, None if rcol.validity is None
                        else jnp.asarray(rcol.validity))
        for ll, rl in zip(key_lanes(ldata), key_lanes(rdata)):
            lanes.append(jnp.concatenate([ll, rl]))
    return lanes


@instrumented_jit("setops.membership", scope="hs.setop",
                  static_argnames=("n", "anti"))
def _setop_core(lanes, n: int, anti: bool):
    import jax.numpy as jnp

    from hyperspace_tpu.ops.keys import _staged_sort

    total = lanes[0].shape[0]
    perm, sorted_ops = _staged_sort(list(lanes))
    differs = jnp.zeros(total, dtype=jnp.int32)
    for k in sorted_ops:
        differs = differs | jnp.concatenate(
            [jnp.zeros(1, dtype=jnp.int32),
             (k[1:] != k[:-1]).astype(jnp.int32)])
    gid_sorted = jnp.cumsum(differs, dtype=jnp.int32)
    groups = jnp.zeros(total, dtype=jnp.int32).at[perm].set(gid_sorted)
    l_ids, r_ids = groups[:n], groups[n:]
    present_r = jnp.zeros(total, dtype=bool).at[r_ids].set(True)
    member = jnp.take(present_r, l_ids)
    first = jnp.full(total, n, dtype=jnp.int32).at[l_ids].min(
        jnp.arange(n, dtype=jnp.int32))
    keep = jnp.arange(n, dtype=jnp.int32) == jnp.take(first, l_ids)
    mask = keep & (~member if anti else member)
    return mask, jnp.sum(mask.astype(jnp.int64))


def _host_indices(left: ColumnBatch, right: ColumnBatch,
                  names: Sequence[str], anti: bool) -> np.ndarray:
    from hyperspace_tpu.io.columnar import _merged_dictionary
    from hyperspace_tpu.ops.keys import host_dense_group_ids, host_key_lanes

    n, m = left.num_rows, right.num_rows
    lanes: List = []
    for name in names:
        lcol, rcol = left.column(name), right.column(name)
        if lcol.is_string != rcol.is_string:
            raise HyperspaceException(
                f"Set-op column type mismatch: {name}")
        if lcol.is_string:
            _, (rl, rr), _ = _merged_dictionary(
                [lcol.dictionary, rcol.dictionary], device=False)
            ldata = rl[np.asarray(lcol.data)]
            rdata = rr[np.asarray(rcol.data)]
        else:
            ldata, rdata = np.asarray(lcol.data), np.asarray(rcol.data)
            if ldata.dtype != rdata.dtype:
                common = np.promote_types(ldata.dtype, rdata.dtype)
                ldata, rdata = ldata.astype(common), rdata.astype(common)
        lv = (np.ones(n, bool) if lcol.validity is None
              else np.asarray(lcol.validity))
        rv = (np.ones(m, bool) if rcol.validity is None
              else np.asarray(rcol.validity))
        lanes.append(np.concatenate([lv, rv]).astype(np.int32))
        ldata = _zeroed(np, ldata, lv if lcol.validity is not None else None)
        rdata = _zeroed(np, rdata, rv if rcol.validity is not None else None)
        for ll, rl_ in zip(host_key_lanes(ldata), host_key_lanes(rdata)):
            lanes.append(np.concatenate([ll, rl_]))
    perm, gid_sorted = host_dense_group_ids(lanes)
    groups = np.empty(n + m, dtype=np.int32)
    groups[perm] = gid_sorted
    l_ids, r_ids = groups[:n], groups[n:]
    present_r = np.zeros(n + m, dtype=bool)
    present_r[r_ids] = True
    member = present_r[l_ids]
    first = np.full(n + m, n, dtype=np.int64)
    np.minimum.at(first, l_ids, np.arange(n))
    keep = np.arange(n) == first[l_ids]
    mask = keep & (~member if anti else member)
    return np.nonzero(mask)[0].astype(np.int32)


def set_op_indices(left: ColumnBatch, right: ColumnBatch,
                   names: Sequence[str], anti: bool):
    """Left-row indices of the set-op result, in first-occurrence order.
    `anti=False` -> INTERSECT, `anti=True` -> EXCEPT."""
    import jax.numpy as jnp

    if left.num_rows == 0:
        return np.zeros(0, dtype=np.int32)
    if right.num_rows == 0 and not anti:
        return np.zeros(0, dtype=np.int32)
    if left.is_host and right.is_host:
        return _host_indices(left, right, names, anti)
    lanes = _device_lanes(left, right, names)
    mask, cnt = _setop_core(tuple(lanes), left.num_rows, anti)
    count = int(cnt)  # the one host sync
    if count == 0:
        return jnp.zeros(0, dtype=jnp.int32)
    (idx,) = jnp.nonzero(mask, size=count, fill_value=0)
    return idx.astype(jnp.int32)
