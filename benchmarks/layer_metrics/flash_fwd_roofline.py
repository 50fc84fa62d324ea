"""Mosaic flash forward: roofline-least time over measured time per call."""

from benchmarks.lib import readers


def read(obs):
    return readers.flash_fwd_roofline(obs)
