"""roofline.py against hand counts."""

import pytest

from lib import roofline


def test_join_bytes_by_hand():
    # 10 + 4 keys of 8 bytes read, 10 pairs of 4-byte indices written
    assert roofline.join_min_bytes(10, 4, 10) == 14 * 8 + 10 * 8
    # the join cell: 17,999,998 x 4,500,000 -> 17,999,998 rows
    assert roofline.join_min_bytes(17_999_998, 4_500_000, 17_999_998) == \
        22_499_998 * 8 + 17_999_998 * 8


def test_stage_bytes_by_hand():
    # 100 rows of an 8-byte predicate column, 5 survivors of 52 bytes
    # read and written
    assert roofline.stage_min_bytes(100, 8, 5, 52) == 800 + 2 * 5 * 52


def test_share_of_the_hbm_roofline():
    # 819 GB at 819 GB/s is one second: two seconds of device time is 50%
    assert roofline.share_pct(819_000_000_000, 2.0, "TPU v5 lite") == \
        pytest.approx(50.0)
    assert roofline.share_pct(1, 0.0, "TPU v5 lite") is None


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_column_bytes():
    from conftest import plug

    tpch = plug("datasets", "tpch")
    assert roofline.column_bytes("l_orderkey", tpch) == 8
    assert roofline.column_bytes("l_shipdate", tpch) == 4
    assert roofline.column_bytes("l_shipmode", tpch) == 4
