"""The byte counts of ``bench/work/gf2_bmvm.py`` against hand-worked ones."""
import pytest

from bench.work import gf2_bmvm as w

PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}


def test_lut_bytes():
    # (C, 2^k, R) uint32, C = R = n/k
    assert w.lut_shape(4096, 8) == (512, 256, 512)
    assert w.lut_bytes(4096, 8) == 512 * 256 * 512 * 4 == 268_435_456
    assert w.lut_shape(24576, 8) == (3072, 256, 3072)
    assert w.lut_bytes(24576, 8) == 3072 * 256 * 3072 * 4 == 9_663_676_416
    assert w.lut_shape(64, 8) == (8, 256, 8)
    assert w.lut_bytes(64, 8) == 8 * 256 * 8 * 4 == 65_536


def test_rows_selected():
    assert w.rows_selected(256, 1) == pytest.approx(1.0)
    assert w.rows_selected(256, 2) == pytest.approx(1 + 255 / 256)
    # M = 1024 draws of 256 rows leave (255/256)^1024 = e^-4.00782 = 1.8173% untouched
    assert w.rows_selected(256, 1024) == pytest.approx(256 * (1 - 0.018173), rel=1e-5)
    assert w.rows_selected(256, 10 ** 6) == pytest.approx(256)


def test_min_bytes():
    # n=64, M=1: one LUT row of 8 words per column tile, 8 index words, 8 output words
    assert w.min_bytes(64, 8, 1) == pytest.approx(8 * 8 * 4 + 8 * 4 + 8 * 4) == 320
    # n=4096, M=1: 512 rows of 512 words, 512 + 512 words in and out
    assert w.min_bytes(4096, 8, 1) == pytest.approx(512 * 512 * 4 + 2 * 512 * 4)
    # n=4096, M=1024: 98.18% of the LUT, 2 MiB of index words, 2 MiB out
    got = w.min_bytes(4096, 8, 1024)
    assert got == pytest.approx(0.981827 * 268_435_456 + 2 * 2 ** 21, rel=1e-5)
    assert got < w.lut_bytes(4096, 8) + 2 * 2 ** 21
    # n=24576, M=128: (255/256)^128 = e^-0.50098 leaves 60.594% of the LUT
    # unread; index and output words 128 x 3072 x 4 B = 1.5 MiB each way
    got = w.min_bytes(24576, 8, 128)
    assert got == pytest.approx(0.394063 * 9_663_676_416 + 2 * 1_572_864, rel=1e-5)


def test_all_to_all_bytes():
    # each of 4 chips keeps 128 of the 512 output words of its (1024, 512) partial
    assert w.all_to_all_bytes(4096, 8, 1024, 4) == 4 * 1024 * 384 * 4 == 6_291_456
    assert w.all_to_all_bytes(4096, 8, 1024, 1) == 0
    assert w.all_to_all_bytes(64, 8, 16, 4) == 3 * 16 * 8 * 4


def test_min_step_seconds():
    # 3.8112 GB at 819 GB/s
    assert w.min_step_seconds(24576, 8, 128, 1, PEAKS) == pytest.approx(4.6535e-3, rel=1e-4)
    one = w.min_step_seconds(4096, 8, 1024, 1, PEAKS)
    assert one == pytest.approx(w.min_bytes(4096, 8, 1024) / 819e9)
    assert one == pytest.approx(3.2692e-4, rel=1e-4)
    # four chips share the HBM term; the interconnect term (7.9 us) is smaller
    four = w.min_step_seconds(4096, 8, 1024, 4, PEAKS)
    assert four == pytest.approx(one / 4)
    assert 6_291_456 / (4 * 200e9) < four
