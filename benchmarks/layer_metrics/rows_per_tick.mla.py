"""Tokens delivered per decode tick: mean occupied rows (srv.stats(), window
only); of a program that counts latent cells only."""

from benchmarks.lib import latent_readers, readers


def read(obs):
    if not latent_readers.counted(obs):
        return None
    return readers.ratio(obs, "generated", "rounds")
