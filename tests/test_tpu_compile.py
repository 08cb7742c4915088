"""Compiles for a described (not attached) v5e chip, at real widths.

Interpret-mode tests cannot see what the chip's compiler refuses — a
slice off the tiling, too much VMEM, a 64-bit op with no lowering. These
compile the main path's Pallas kernels, one fused filter stage and the
float64 decode for a described `v5e:2x2` chip; nothing runs. The sort programs (`_perm_core`,
`_counting_match_lanes`) take minutes to compile at any size, so they are
guarded by `chip_smoke.py` on the chip, not here.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file. Keep all such tests in THIS file (a second file could land on
another worker, whose fixture would then skip).
"""

import os

import jax
import jax.numpy as jnp
import pytest

ROWS = 4_194_304  # MIN_DEVICE_ROWS_DEFAULT: the smallest device-lane scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip executable can be written to the persistent cache
    but not read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("num_buckets", [64, 1024])
@pytest.mark.parametrize("n_lanes", [1, 2])
@pytest.mark.parametrize("kernel", ["hash", "partition"])
def test_pallas_kernel_compiles_for_v5e(one_chip, no_compile_cache, kernel,
                                        n_lanes, num_buckets):
    from hyperspace_tpu.ops.pallas.hash_kernel import hash_lanes_to_buckets
    from hyperspace_tpu.ops.pallas.partition_kernel import (
        partition_ids_and_histogram)

    fn = (hash_lanes_to_buckets if kernel == "hash"
          else partition_ids_and_histogram)
    lane = jax.ShapeDtypeStruct((ROWS,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lambda *lanes: fn(list(lanes), num_buckets)).lower(
        *[lane] * n_lanes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_float64_decode_compiles_for_v5e(one_chip, no_compile_cache):
    """float64 columns are carried on the device as int64 bit patterns;
    an expression that computes on one decodes it by arithmetic (the
    TPU's f64 emulation cannot reinterpret 64 bits). Here: the decode
    feeding a compare and a sum, as a filter or an aggregate would."""
    from hyperspace_tpu.io.columnar import _f64_from_bits_arithmetic

    bits = jax.ShapeDtypeStruct((ROWS,), jnp.int64, sharding=one_chip)

    def stage(b):
        x = _f64_from_bits_arithmetic(b)
        return jnp.sum(x < 0.5), jnp.sum(x)

    compiled = jax.jit(stage).lower(bits).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == ROWS * 8


def test_build_program_cache_key_names_no_caller_file(one_chip):
    """The Pallas kernel rides in `_perm_core`'s custom call as an opaque
    payload that jax cannot strip of locations before hashing it for the
    compile cache. It must name kernel source only — not this file, not
    an entry script — or any edited line up the call stack recompiles the
    build program; and by its path INSIDE the checkout, or the same commit
    checked out elsewhere recompiles it too (`_jax_config.py`)."""
    import base64
    import re

    import hyperspace_tpu.ops.pallas as kernels
    from hyperspace_tpu.ops.build import _perm_core

    tree = {"key": {"data": jax.ShapeDtypeStruct((ROWS,), jnp.int64,
                                                 sharding=one_chip)}}
    text = _perm_core.lower(tree, ("key",), 64, 8, use_pallas=True).as_text()
    body = base64.b64decode(
        re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text).group(1))
    files = {s.decode() for s in re.findall(rb"[\x20-\x7e]+\.py", body)}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    kernel_dir = os.path.relpath(
        os.path.dirname(os.path.abspath(kernels.__file__)), repo)
    assert files and all(os.path.dirname(f) == kernel_dir for f in files), \
        files


def test_fused_filter_stage_compiles_for_v5e(one_chip, no_compile_cache,
                                             tmp_path, monkeypatch):
    """The smoke's range filter — int64 key compare over an
    (int64, int64, float64, int64) index scan — as the real
    `fusion.run_stage` program, re-lowered at device-lane width."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu import (Hyperspace, HyperspaceConf,
                                HyperspaceSession, IndexConfig, col, lit)
    from hyperspace_tpu.engine import fusion

    n = 4096
    rng = np.random.default_rng(0)
    src = tmp_path / "fact"
    src.mkdir()
    pq.write_table(pa.table({
        "key": rng.integers(0, n, n).astype(np.int64) * 1_000_003,
        "id": np.arange(n, dtype=np.int64),
        "measure": rng.random(n),
        "k2": rng.integers(0, 100, n).astype(np.int64),
    }), str(src / "part-0.parquet"))
    sess = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        # one device, device lane: the conftest's 8 virtual CPU devices
        # would otherwise hand the filter to the mesh scan
        "spark.hyperspace.distribution.enabled": "false",
        "spark.hyperspace.execution.min.device.rows": "0"}))
    df = sess.read_parquet(str(src))
    Hyperspace(sess).create_index(
        df, IndexConfig("f", ["key"], ["id", "measure", "k2"]))
    sess.enable_hyperspace()

    captured = []
    run_stage = fusion._run_stage
    monkeypatch.setattr(
        fusion, "_run_stage",
        lambda prog, trees, tables: (captured.append((prog, trees, tables))
                                     or run_stage(prog, trees, tables)))
    lo, hi = 400 * 1_000_003, 410 * 1_000_003
    (df.filter((col("key") >= lit(lo)) & (col("key") < lit(hi)))
     .select("key", "id", "measure", "k2").collect())
    sess.close()
    (prog, trees, tables), = captured

    wide = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((ROWS,) + a.shape[1:], a.dtype,
                                       sharding=one_chip), trees)
    wide_prog = fusion._StageProgram(
        prog.key + "#wide", prog.region,
        [(schema, aux, ROWS) for schema, aux, _ in prog.source_meta],
        prog.tables_meta)
    compiled = fusion._run_stage_jit.lower(wide_prog, wide, tables).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == ROWS * 32


@pytest.mark.parametrize("program,side", [("hs_compact", "sparse"),
                                          ("hs_compact", "dense"),
                                          ("hs_segsum", "sparse")])
def test_filter_sizing_programs_are_scoped_on_v5e(one_chip, no_compile_cache,
                                                  monkeypatch, program, side):
    """What a device capture will show of `ops/compact.py`, at the range
    cell's shape (60,004 of 6,000,000 rows): every op of the chip's own
    program under the device scope — the prefix sum's window reductions
    too, which `jnp.cumsum` would leave bare — and no scatter and no
    `while` on either side of the compaction's selection (the dense side
    at 65,536 rows: a sort of millions takes several seconds to compile)."""
    import re

    from hyperspace_tpu.ops import compact

    rows, size = (6_000_000, 60_004) if side == "sparse" else (65_536, 60_004)
    monkeypatch.setattr(compact, "_rank_select_wins",
                        lambda rows, size: side == "sparse")
    monkeypatch.setattr(compact, "_compact_jit", None)  # fresh traces
    monkeypatch.setattr(compact, "_segsum_jit", None)
    compact.compact_indices(jnp.zeros(8, bool), 1)
    compact.bucket_survivors(jnp.zeros(8, bool), [8])
    mask = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    if program == "hs_compact":
        scope = "hs.compact"
        lowered = jax.jit(compact._compact_jit.__wrapped__,
                          static_argnames=("size",)).lower(mask, size=size)
    else:
        scope = "hs.segsum"
        lowered = jax.jit(compact._segsum_jit.__wrapped__).lower(
            mask, jax.ShapeDtypeStruct((64,), jnp.int64, sharding=one_chip))
    hlo = lowered.compile().as_text()
    assert f"jit_{program}" in hlo.splitlines()[0]
    entry = hlo[hlo.index("\nENTRY "):]
    timed = [line for line in entry.splitlines() if re.search(
        r" (fusion|reduce-window|gather|sort|scatter|while)\(", line)]
    kinds = {re.search(r" ([a-z-]+)\(", line).group(1) for line in timed}
    assert not kinds & {"scatter", "while"}, kinds
    assert ("sort" in kinds) == (side == "dense"), kinds
    assert side == "dense" or {"reduce-window", "fusion"} <= kinds, kinds
    bare = [line.split("=")[0].strip() for line in timed
            if f'op_name="jit({program})/{scope}/' not in line]
    assert timed and not bare, bare


def test_broadcast_probe_is_named_and_scoped_on_v5e(one_chip,
                                                    no_compile_cache):
    """What a device capture will show of `ops/broadcast_join.py`'s probe
    at the hybrid cell's shape (17,999,998 int64 keys against a
    36,000-slot table): one program named `jit__broadcast_probe`, its
    gather under the device scope `hs.join.broadcast`, and the table's
    packing as arguments (a second build side compiles nothing)."""
    import re

    import numpy as np

    from hyperspace_tpu.ops import broadcast_join

    broadcast_join._device_probe(  # builds the jitted program
        [jnp.zeros(8, jnp.int64)], None, jnp.zeros(4, jnp.int32), [0], [3],
        [4])

    def shape(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    packing = [shape(1, jnp.int64)] * 3
    lowered = jax.jit(broadcast_join._probe_jit.__wrapped__).lower(
        (shape(17_999_998, jnp.int64),), None, shape(36_000, jnp.int32),
        *packing)
    hlo = lowered.compile().as_text()
    assert "jit__broadcast_probe" in hlo.splitlines()[0]
    entry = hlo[hlo.index("\nENTRY "):]
    timed = [line for line in entry.splitlines()
             if re.search(r" (fusion|gather|sort|scatter|while)\(", line)]
    assert timed and not [
        line.split("=")[0].strip() for line in timed
        if 'op_name="jit(_broadcast_probe)/hs.join.broadcast/' not in line]
    # the probe finds what numpy finds, out-of-range keys and all
    table = np.array([2, -1, 0, 1], dtype=np.int32)
    keys = np.array([10, 11, 12, 13, 9, 14, -2 ** 63], dtype=np.int64)
    hit, matched = broadcast_join._device_probe(
        [jnp.asarray(keys)], None, table, [10], [13], [4])
    assert np.asarray(hit).tolist() == [2, -1, 0, 1, -1, -1, -1]
    assert np.asarray(matched).tolist() == [True, False, True, True,
                                            False, False, False]
