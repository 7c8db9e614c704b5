"""The least bytes of an epoch and the table of peaks."""
import pytest

from bench import roofline


def test_epoch_bytes_counts_each_input_once():
    N, J, R, g = 3, 5, 2, 7
    want = (4 * N * J + N * J + 4 * N * R + 2 * 4 * J * R + 2 * 4 * N
            + 2 * 4 * g)
    assert roofline.epoch_bytes(N, J, R, g) == want


def test_epoch_bytes_ignore_padding_and_steps():
    # a fleet-size epoch: real shapes only, independent of the 2048x16384
    # bucket the program pads to
    b = roofline.epoch_bytes(2040, 12583, 2, 10000)
    assert b == 5 * 2040 * 12583 + 16 * 2040 + 16 * 12583 + 8 * 10000


def test_peaks_of_the_v5e_and_unknown_kinds():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu")
