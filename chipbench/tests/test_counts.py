"""The roofline's count functions and the peaks table."""

import pytest

from harness import counts


def test_counts_of_one_scan():
    # 8 views of 1248x960 into 512^3: 2^30 updates, one volume pass.
    assert counts.updates(512, 8) == 8 * 2 ** 27
    assert counts.flops(512, 8) == 25 * 8 * 2 ** 27
    assert counts.bytes_moved(512, 1248, 960, 8, 1) == \
        4 * (8 * 1248 * 960 + 2 * 2 ** 27)


def test_memory_roofline():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 100 bytes at 10 B/s take 10 s of the 20 s measured.
    assert counts.roofline(100.0, 20.0, peak) == 50.0
    assert counts.intensity(512, 1248, 960, 8, 1) == \
        counts.flops(512, 8) / counts.bytes_moved(512, 1248, 960, 8, 1)


def test_peaks_table():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
