"""Share of the traced window in which no operation ran on the device; of a
program that counts the delta rule's steps only."""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    return (readers.device_idle_pct(obs) if gdn_readers.counted(obs)
            else None)
