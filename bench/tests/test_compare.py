"""compare.py: the multiset comparison that decides `correct`."""

import numpy as np
import pytest

from lib import compare


def rows(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    price = rng.integers(0, 1000, n) / 100.0
    price[::7] = np.nan
    price[1::7] = -0.0
    return {"a": rng.integers(1, 8, n),
            "p": price.view(np.int64),
            "t": (rng.integers(0, 50, n) / 100.0).view(np.int64)}


def shuffled(cols, seed=1):
    order = np.random.default_rng(seed).permutation(len(cols["a"]))
    return {k: v[order] for k, v in cols.items()}


@pytest.fixture(params=["hash", "colliding"])
def hashing(request, monkeypatch):
    """The real row hash, and one that collides all the time: the
    verdict may not depend on it."""
    if request.param == "colliding":
        real = compare._row_hash
        monkeypatch.setattr(
            compare, "_row_hash",
            lambda cols, names: real(cols, names) % np.uint64(64))


def test_equal_row_sets_in_any_order_match(hashing):
    want = rows()
    assert compare.mismatched_rows(shuffled(want), want) == 0
    assert compare.mismatched_rows(shuffled(want), compare.SortedRows(want)) == 0


def test_one_changed_bit_is_a_mismatch(hashing):
    want = rows()
    got = shuffled(want)
    got["p"] = got["p"].copy()
    got["p"][17] ^= 1
    assert compare.mismatched_rows(got, want) >= 1


def test_negative_zero_is_not_zero_and_counts_matter(hashing):
    want = {"x": np.array([0.0, 1.0, 1.0]).view(np.int64)}
    assert compare.mismatched_rows(
        {"x": np.array([-0.0, 1.0, 1.0]).view(np.int64)}, want) >= 1
    assert compare.mismatched_rows(
        {"x": np.array([0.0, 0.0, 1.0]).view(np.int64)}, want) >= 1
    assert compare.mismatched_rows(
        {"x": np.array([0.0, 1.0]).view(np.int64)}, want) >= 1
    assert compare.mismatched_rows(
        {"y": np.array([0.0, 1.0, 1.0]).view(np.int64)}, want) >= 1


def arrow(cols):
    import pyarrow as pa

    t = pa.table({"a": pa.array(cols["a"]),
                  "p": pa.array(cols["p"].view(np.float64)),
                  "d": pa.array(cols["a"].astype(np.int32)).cast(pa.date32())})
    return t.append_column(
        "m", pa.array(["MAIL", "SHIP"] * (t.num_rows // 2)).dictionary_encode())


def test_same_buffers_is_the_same_bytes():
    a = rows()
    assert compare.same_buffers(arrow(a), arrow({k: v.copy()
                                                 for k, v in a.items()}))
    assert not compare.same_buffers(arrow(a), arrow(shuffled(a)))
    assert not compare.same_buffers(arrow(a), arrow(a).slice(1))
    assert not compare.same_buffers(arrow(a), arrow(a).drop_columns(["d"]))


@pytest.mark.parametrize("was, now", [(-0.0, 0.0), (1.5, np.nextafter(1.5, 2))])
def test_same_buffers_sees_one_bit(was, now):
    """nan equals itself and -0.0 is not 0.0: what `Table.equals` has
    the other way round."""
    a = rows()
    p = a["p"].view(np.float64).copy()
    p[2] = was
    b = dict(a, p=p.copy().view(np.int64))
    a = dict(a, p=p.view(np.int64))
    assert compare.same_buffers(arrow(a), arrow(b))
    assert not arrow(a).equals(arrow(b))  # the nans
    b["p"].view(np.float64)[2] = now
    assert not compare.same_buffers(arrow(a), arrow(b))


def test_same_buffers_gives_no_verdict_on_what_it_does_not_read():
    import pyarrow as pa

    nulls = pa.table({"x": pa.array([1, None, 3])})
    flags = pa.table({"x": pa.array([True, False, True])})
    lists = pa.table({"x": pa.array([[1], [2, 3]])})
    for t in (nulls, flags, lists):
        assert not compare.same_buffers(t, t)
