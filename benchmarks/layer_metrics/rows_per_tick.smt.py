"""Tokens delivered per decode tick: mean occupied rows (srv.stats(), window
only); of a program that counts a ring's cells only."""

from benchmarks.lib import readers, ring_readers


def read(obs):
    if not ring_readers.counted(obs):
        return None
    return readers.ratio(obs, "generated", "rounds")
