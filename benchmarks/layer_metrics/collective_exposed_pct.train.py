"""Collective time with no compute on the device, over the traced window."""

from benchmarks.lib import readers


def read(obs):
    return readers.collective_exposed_pct(obs)
