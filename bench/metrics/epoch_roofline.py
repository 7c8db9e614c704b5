"""Percent of the HBM roofline: the least bytes the window's epochs must
move (``bench/roofline.py``) over the chip's peak bandwidth, divided by the
device time of the epoch programs."""

from bench import roofline


def read(ctx):
    dev = ctx.epoch_device_s()
    if dev is None:
        return None
    bw = roofline.peak(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * ctx.epoch_min_bytes() / bw / dev
