"""The filter's two sizing pieces as NAMED device programs.

A filter keeps the rows whose mask is true: `compact_indices` turns the
mask into the survivors' row indices (`jnp.nonzero(size=)`), and, for a
bucketed batch, `bucket_survivors` counts the survivors per bucket (a
segment sum of the mask). Both were dispatched eagerly, primitive by
primitive, so a device capture showed them as a dozen `jit_<primitive>`
programs whose HLO lines change with the next change to them. Each is
one jitted program now, named for what it does (`jit_hs_compact`,
`jit_hs_segsum`) with its ops under the matching device scope
(`telemetry.DEVICE_SCOPES`: `hs.compact`, `hs.segsum`), so a reducer
finds "the compaction, whatever implements it" by name. What is
computed, its order and the host syncs around it are unchanged; the
programs compile per mask length and survivor count, as the eager
primitives did.
"""

from __future__ import annotations

from functools import partial

from hyperspace_tpu import telemetry

_compact_jit = None
_segsum_jit = None


def compact_indices(mask, count: int):
    """Row indices of the `count` true entries of the device `mask`,
    ascending (the index dtype is `jnp.nonzero`'s own)."""
    global _compact_jit
    if _compact_jit is None:
        import jax.numpy as jnp

        @partial(telemetry.instrumented_jit, "hs.compact",
                 static_argnames=("size",))
        @telemetry.device_scoped("hs.compact")
        def hs_compact(mask, size):
            (idx,) = jnp.nonzero(mask, size=size, fill_value=0)
            return idx

        _compact_jit = hs_compact
    return _compact_jit(mask, size=int(count))


def bucket_survivors(mask, lengths, num_buckets: int):
    """Per-bucket counts of true `mask` rows, for a batch laid out in
    bucket order with `lengths` rows per bucket: one device segment sum
    (row -> bucket via searchsorted over the running lengths)."""
    global _segsum_jit
    if _segsum_jit is None:
        import jax
        import jax.numpy as jnp

        @partial(telemetry.instrumented_jit, "hs.segsum",
                 static_argnames=("num_segments",))
        @telemetry.device_scoped("hs.segsum")
        def hs_segsum(mask, lengths, num_segments):
            csum = jnp.cumsum(lengths)
            row_bucket = jnp.searchsorted(
                csum, jnp.arange(mask.shape[0], dtype=jnp.int64),
                side="right")
            return jax.ops.segment_sum(
                mask.astype(jnp.int32), row_bucket.astype(jnp.int32),
                num_segments=num_segments)

        _segsum_jit = hs_segsum
    import jax.numpy as jnp
    return _segsum_jit(mask, jnp.asarray(lengths, dtype=jnp.int64),
                       num_segments=int(num_buckets))
