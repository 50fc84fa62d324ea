"""Blocking host fetches per delivered token (srv.stats(), window only): the
routing counts of the expert layers ride the fetches a wave and a scan
already make; of a program that counts a ring's cells only."""

from benchmarks.lib import readers, ring_readers


def read(obs):
    if not ring_readers.counted(obs):
        return None
    return readers.ratio(obs, "syncs", "generated")
