"""Share of the traced window in which no operation ran on the device."""

from benchmarks.lib import readers


def read(obs):
    return readers.device_idle_pct(obs)
