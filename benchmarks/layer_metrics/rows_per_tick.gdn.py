"""Tokens delivered per decode tick: mean occupied rows (srv.stats(), window
only); of a program that counts the delta rule's steps only."""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    if not gdn_readers.counted(obs):
        return None
    return readers.ratio(obs, "generated", "rounds")
