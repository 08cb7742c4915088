"""`ops/compact.py` is exact: the survivors' indices are `np.nonzero`'s
and the per-bucket counts numpy's, on both sides of the compaction's
selection (rank select for sparse masks, sort select for dense ones),
and a device-lane filter returns what the host lane returns."""

import numpy as np
import pytest

from hyperspace_tpu import (HyperspaceConf, HyperspaceSession, IndexConfig,
                            col, lit)

from span_seam_helpers import env, range_query  # noqa: F401

ROWS = 3001  # not a multiple of the prefix sum's 128-row blocks


def _mask(kind: str) -> np.ndarray:
    mask = np.zeros(ROWS, dtype=bool)
    if kind == "all":
        mask[:] = True
    elif kind == "first":
        mask[0] = True
    elif kind == "last":
        mask[-1] = True
    elif kind != "empty":
        mask = np.random.default_rng(7).random(ROWS) < float(kind)
    return mask


@pytest.fixture(params=["sparse", "dense"])
def side(request, monkeypatch):
    """Both sides of the selection at test sizes: rank select (sparse)
    or sort select (dense) whatever the measured costs say of so few
    rows. The choice is made when the program is traced, so each side
    traces afresh."""
    from hyperspace_tpu.ops import compact
    monkeypatch.setattr(compact, "_rank_select_wins",
                        lambda rows, size: request.param == "sparse")
    monkeypatch.setattr(compact, "_compact_jit", None)
    return request.param


@pytest.mark.parametrize("extra", [0, 5])
@pytest.mark.parametrize("kind", ["empty", "all", "first", "last", "0.01",
                                  "0.25", "0.5", "1.0"])
def test_compact_indices_are_numpys(side, kind, extra):
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact

    mask = _mask(kind)
    count = int(mask.sum())
    got = compact.compact_indices(jnp.asarray(mask), count + extra)
    assert got.dtype == jnp.int32 and got.shape == (count + extra,)
    got = np.asarray(got)
    assert np.array_equal(got[:count], np.nonzero(mask)[0])  # ascending
    assert not got[count:].any()  # a request past the survivors: zeros


def test_the_selection_follows_the_measured_costs():
    """By PERF.md's density table (PR 26): Q12's 0.5% of 18 M rows is
    rank select's, as is 0.3% of 6 M; from the range cell's 1% on the
    sort, whose time does not grow with the survivors, has won."""
    from hyperspace_tpu.ops import compact

    assert compact._rank_select_wins(17_999_998, 93_752)
    assert compact._rank_select_wins(6_000_000, 20_000)
    assert not compact._rank_select_wins(6_000_000, 60_004)
    assert not compact._rank_select_wins(6_000_000, 6_000_000)


@pytest.mark.parametrize("kind", ["empty", "all", "0.01", "0.5"])
@pytest.mark.parametrize("lengths", [
    [0, 1000, 1001, 1000], [1000, 0, 1001, 1000], [1000, 1001, 1000, 0],
    [0, 0, ROWS, 0], [1, 2999, 1], [ROWS]])
def test_bucket_survivors_are_numpys(kind, lengths):
    import jax.numpy as jnp

    from hyperspace_tpu.ops import compact

    mask = _mask(kind)
    assert sum(lengths) == ROWS
    got = compact.bucket_survivors(jnp.asarray(mask),
                                   np.asarray(lengths, dtype=np.int64))
    ends = np.cumsum(lengths)
    want = [int(mask[e - n:e].sum()) for e, n in zip(ends, lengths)]
    assert got.dtype == jnp.int32 and np.asarray(got).tolist() == want


def test_device_filters_return_the_host_lanes_rows(side, env,  # noqa: F811
                                                   monkeypatch):
    """A bucketed filter under a join (`FilterExec.execute_bucketed`:
    per-bucket counts, then the compaction) and a fused range stage, on
    the device lane, against the same plans on the host lane (numpy)."""
    from hyperspace_tpu.engine import fusion, physical
    from hyperspace_tpu.ops import compact

    hs, fact, dim, tmp_path = env
    hs.create_index(fact, IndexConfig("c_fact", ["key"], ["qty", "price"]))
    hs.create_index(dim, IndexConfig("c_dim", ["key"], ["grp"]))

    bucketed = []
    inner = physical.FilterExec.execute_bucketed

    def spy(self, num_buckets):
        batch, lengths = inner(self, num_buckets)
        bucketed.append((batch.is_host, np.asarray(lengths).tolist()))
        return batch, lengths

    monkeypatch.setattr(physical.FilterExec, "execute_bucketed", spy)
    calls = []

    def counting(module, name):
        fn = getattr(module, name)
        monkeypatch.setattr(
            module, name, lambda *args: calls.append(name) or fn(*args))

    counting(compact, "bucket_survivors")
    counting(compact, "compact_indices")  # the unfused filters' import
    counting(fusion, "compact_indices")  # the fused stage's own name

    def answers(fact, dim):
        join = (fact.filter(col("qty") < lit(10)).join(dim, on="key")
                .select("key", "qty", "price", "grp"))
        return [q.collect().to_pandas().sort_values(list(q.columns))
                .reset_index(drop=True) for q in (join, range_query(fact))]

    device = answers(fact, dim)
    assert sorted(calls) == ["bucket_survivors", "compact_indices",
                             "compact_indices"]
    host = HyperspaceSession(HyperspaceConf({
        "hyperspace.warehouse.dir": str(tmp_path / "wh"),
        "spark.hyperspace.index.num.buckets": "8",
        "spark.hyperspace.execution.min.device.rows": str(1 << 40),
        "spark.hyperspace.broadcast.threshold": "0",
        "spark.hyperspace.distribution.enabled": "false"}))
    host.enable_hyperspace()
    want = answers(host.read_parquet(str(tmp_path / "fact")),
                   host.read_parquet(str(tmp_path / "dim")))
    host.close()
    assert len(calls) == 3  # the host lane is numpy's
    for got, expected in zip(device, want):
        assert len(expected) > 0 and got.equals(expected)
    # the bucketed filter ran on both lanes and sized its buckets alike
    (dev,) = [lengths for is_host, lengths in bucketed if not is_host]
    (hst,) = [lengths for is_host, lengths in bucketed if is_host]
    assert dev == hst and len(dev) == 8 and sum(dev) == len(want[0])
