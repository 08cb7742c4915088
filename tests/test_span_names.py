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


def test_the_filter_sizing_programs_are_named_and_exact():
    """`hs_compact` / `hs_segsum`: one named program each, their ops
    under the device scope, the same numbers as numpy."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact

    rng = np.random.default_rng(2)
    mask = rng.random(5000) < 0.1
    lengths = np.array([1000, 0, 1500, 2500], dtype=np.int64)
    idx = np.asarray(compact.compact_indices(jnp.asarray(mask),
                                             int(mask.sum())))
    assert np.array_equal(idx, np.nonzero(mask)[0])
    got = np.asarray(compact.bucket_survivors(jnp.asarray(mask), lengths, 4))
    bounds = np.concatenate([[0], np.cumsum(lengths)])
    want = [int(mask[bounds[i]:bounds[i + 1]].sum()) for i in range(4)]
    assert got.tolist() == want
    # The names reach the compiled program's op metadata (what a device
    # capture shows): with this package's one-frame-per-location setting
    # a bare `jax.named_scope` would leave `scatter-add` bare.
    for program, scope, args, static in (
            (compact._compact_jit, "hs.compact", (jnp.asarray(mask),),
             {"size": 7}),
            (compact._segsum_jit, "hs.segsum",
             (jnp.asarray(mask), jnp.asarray(lengths)),
             {"num_segments": 4})):
        fn = program.__wrapped__
        hlo = jax.jit(fn, static_argnames=tuple(static)).lower(
            *args, **static).compile().as_text()
        assert f"jit_{fn.__name__}" in hlo.splitlines()[0]
        scatters = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
                    if n.endswith("/scatter-add")]
        assert scatters and all(
            n.startswith(f"jit({fn.__name__})/{scope}/") for n in scatters)
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
