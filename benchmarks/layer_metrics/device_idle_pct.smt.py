"""Share of the traced window in which no operation ran on the device; of a
program that counts a ring's cells only."""

from benchmarks.lib import readers, ring_readers


def read(obs):
    return readers.device_idle_pct(obs) if ring_readers.counted(obs) else None
