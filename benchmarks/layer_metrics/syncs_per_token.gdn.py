"""Blocking host fetches per delivered token (srv.stats(), window only): the
routing counts of the expert layers ride the fetches a wave and a scan
already make; of a program that counts the delta rule's steps only."""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    if not gdn_readers.counted(obs):
        return None
    return readers.ratio(obs, "syncs", "generated")
