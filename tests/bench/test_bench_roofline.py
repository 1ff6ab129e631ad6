"""A kernel's share of its roofline, bound by FLOPs or by bytes."""

import pytest

from bench import roofline

PEAK = {"bf16_flop_per_s": 200e12, "hbm_bytes_per_s": 800e9}


def test_flops_bound():
    # 2e12 FLOPs need 10 ms, 4e9 bytes 5 ms: FLOPs bound; 40 ms taken.
    assert roofline.least_s(2e12, 4e9, PEAK) == pytest.approx(0.010)
    assert roofline.share(0.040, 2e12, 4e9, PEAK) == pytest.approx(25.0)


def test_bytes_bound():
    # 1e12 FLOPs need 5 ms, 8e9 bytes 10 ms: bytes bound; 20 ms taken.
    assert roofline.share(0.020, 1e12, 8e9, PEAK) == pytest.approx(50.0)


def test_at_the_roofline_reads_100():
    assert roofline.share(0.010, 2e12, 8e9, PEAK) == pytest.approx(100.0)


def test_nothing_ran_gives_no_share():
    assert roofline.share(0.0, 1e12, 1e9, PEAK) is None


def test_a_device_without_peaks_is_an_error():
    with pytest.raises(ValueError):
        roofline.share(0.01, 1e12, 1e9, None)
