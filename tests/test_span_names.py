"""Names on the device and in the benchmark's reader: the filter's two
sizing programs are named, scoped and exact; `bench/lib/program_spans.py`
groups by names `telemetry.SPAN_NAMES` has."""

import glob
import os
import re
import sys

import numpy as np

from hyperspace_tpu import telemetry

from span_seam_helpers import REPO_ROOT


def _scoped_ops(program, scope, args, static, needs):
    """The compiled program is named, holds no scatter and no `while`,
    and every op of the kinds `needs` sits under the device scope (the
    chip's own program, its window reductions included, is read in
    `test_tpu_compile.py`: the CPU's compiler rewrites those)."""
    import jax

    fn = program.__wrapped__
    hlo = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()
    assert f"jit_{fn.__name__}" in hlo.splitlines()[0]
    names = re.findall(r'op_name="([^"]*)"', hlo)
    kinds = {n.rsplit("/", 1)[-1] for n in names}
    assert not [k for k in kinds if "scatter" in k or k == "while"], kinds
    under = f"jit({fn.__name__})/{scope}/"
    for kind in needs:
        mine = [n for n in names if n.endswith("/" + kind)]
        assert mine and all(n.startswith(under) for n in mine), (kind, names)
        # none of the kind outside the scope ("sort" alone also names
        # the comparator's two parameters)
        assert kind == "sort" or kind not in names, (kind, names)


def test_the_filter_sizing_programs_are_named_and_exact(monkeypatch):
    """`hs_compact` / `hs_segsum`: one named program each, their ops
    under the device scope, the same numbers as numpy, and no scatter
    over the mask's rows on either side of the compaction's selection."""
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact

    rng = np.random.default_rng(2)
    mask = rng.random(5000) < 0.1
    lengths = np.array([1000, 0, 1500, 2500], dtype=np.int64)
    idx = np.asarray(compact.compact_indices(jnp.asarray(mask),
                                             int(mask.sum())))
    assert np.array_equal(idx, np.nonzero(mask)[0])
    got = np.asarray(compact.bucket_survivors(jnp.asarray(mask), lengths))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    want = [int(mask[bounds[i]:bounds[i + 1]].sum()) for i in range(4)]
    assert got.tolist() == want
    # The names reach the compiled program's op metadata (what a device
    # capture shows): with this package's one-frame-per-location setting
    # a bare `jax.named_scope` would leave the gathers bare. Each side of
    # the compaction's selection (rank select: the prefix sum and
    # gathers; sort select) in a fresh trace.
    for sparse, needs in ((True, ("gather",)), (False, ("sort",))):
        monkeypatch.setattr(compact, "_rank_select_wins",
                            lambda rows, size, sparse=sparse: sparse)
        monkeypatch.setattr(compact, "_compact_jit", None)
        assert np.array_equal(idx, np.asarray(compact.compact_indices(
            jnp.asarray(mask), int(mask.sum()))))
        _scoped_ops(compact._compact_jit, "hs.compact",
                    (jnp.asarray(mask),), {"size": 7}, needs)
    _scoped_ops(compact._segsum_jit, "hs.segsum",
                (jnp.asarray(mask), jnp.asarray(lengths)), {}, ("gather",))
    assert set(telemetry.DEVICE_SCOPES) >= {"hs.compact", "hs.segsum",
                                            "hs.predicate"}


def test_the_bench_reader_groups_by_names_the_table_has():
    """`bench/lib/program_spans.py` imports nothing of the program: the
    prefixes it groups idle time by and the names its metric files read
    are checked against `SPAN_NAMES` here."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "bench"))
    try:
        from lib import program_spans
    finally:
        sys.path.pop(0)
    prefixes = [p for _, ps in program_spans._GROUP_PREFIXES for p in ps]
    for prefix in prefixes + list(program_spans._UNNAMED):
        assert any(n.startswith(prefix) for n in telemetry.SPAN_NAMES), prefix
    assert [program_spans.group_of(n) for n in (
        "hs.serve.finish", "hs.plan.compile", "hs.op.Scan",
        "hs.stage.sync", "hs.segcache.fill", "hs.link.d2h", "hs.to_arrow",
        "hs.query", None, "hs.mesh.filter")] == [
            "serve", "plan", "stage", "stage", "stage", "out", "out",
            "unnamed", "unnamed", "stage"]
    read = set()
    for path in glob.glob(os.path.join(REPO_ROOT, "bench", "metrics",
                                       "*.py")):
        with open(path) as f:
            read |= set(re.findall(r'"(hs\.[a-z_.]+)"', f.read()))
    assert read and read <= set(telemetry.SPAN_NAMES) | set(
        telemetry.DEVICE_SCOPES), read
