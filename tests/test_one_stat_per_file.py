"""`storage.one_stat_per_file`: inside the block a local path is
stat'ed once and later stats of it read that result; outside it, and
for a `fresh` stat, every call stats; a failure is never remembered;
`forget_stats` makes the next stat look again; and the re-stat after a
read still sees a rewrite made during it, so the read cache never keeps
new bytes under the stamp the query began with."""

import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu.io import parquet
from hyperspace_tpu.utils import storage


@pytest.fixture
def counted(monkeypatch):
    calls = []
    real = os.stat

    def counting(path, *a, **k):
        calls.append(str(path))
        return real(path, *a, **k)
    monkeypatch.setattr(os, "stat", counting)
    return calls


def _write(path, values):
    pq.write_table(pa.table({"x": values}), str(path))


@pytest.mark.parametrize("inside", [True, False])
def test_a_path_is_stated_once_inside_the_block_only(tmp_path, counted,
                                                     inside):
    f = tmp_path / "a.parquet"
    _write(f, [1, 2, 3])
    if inside:
        with storage.one_stat_per_file():
            stamps = [parquet._file_stamp(str(f)) for _ in range(3)]
    else:
        stamps = [parquet._file_stamp(str(f)) for _ in range(3)]
    assert len(set(stamps)) == 1
    assert counted == [str(f)] * (1 if inside else 3)


def test_fresh_failed_and_forgotten_stats_go_to_the_file(tmp_path,
                                                          counted):
    f, missing = tmp_path / "a.parquet", tmp_path / "gone.parquet"
    _write(f, [1])
    with storage.one_stat_per_file():
        first = storage.stat(str(f))
        _write(f, [1, 2, 3, 4, 5, 6, 7, 8])
        assert storage.stat(str(f)) is first
        assert storage.stat(str(f), fresh=True).st_size != first.st_size
        assert storage.stat(str(f)) is first  # fresh remembers nothing
        for _ in range(2):
            with pytest.raises(FileNotFoundError):
                storage.stat(str(missing))
        storage.forget_stats()
        assert storage.stat(str(f)).st_size != first.st_size
    assert counted == [str(f), str(f), str(missing), str(missing), str(f)]


def test_the_block_ends_with_its_query(tmp_path, counted):
    f = tmp_path / "a.parquet"
    _write(f, [1])
    with storage.one_stat_per_file():
        storage.stat(str(f))
        with storage.one_stat_per_file():  # a nested query stats anew
            storage.stat(str(f))
        storage.stat(str(f))
    storage.stat(str(f))
    assert len(counted) == 3


def test_a_rewrite_after_the_first_stat_is_never_cached_under_it(
        tmp_path, monkeypatch):
    f = tmp_path / "a.parquet"
    _write(f, [1, 2, 3])
    monkeypatch.setattr(parquet, "READ_CACHE_BYTES", 1 << 20)
    parquet.clear_read_cache()
    with storage.one_stat_per_file():
        old = parquet._file_stamp(str(f))
        _write(f, [4, 5, 6, 7])
        table = parquet.read_table([str(f)])
    assert table.column("x").to_pylist() == [4, 5, 6, 7]
    assert not any(stamps == (old,)
                   for stamps, _ in parquet._read_cache.values())
    assert parquet.read_table([str(f)]).column("x").to_pylist() == [
        4, 5, 6, 7]
    parquet.clear_read_cache()
