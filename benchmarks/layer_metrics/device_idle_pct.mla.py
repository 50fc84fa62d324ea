"""Share of the traced window in which no operation ran on the device; of a
program that counts latent cells only."""

from benchmarks.lib import latent_readers, readers


def read(obs):
    return (readers.device_idle_pct(obs) if latent_readers.counted(obs)
            else None)
