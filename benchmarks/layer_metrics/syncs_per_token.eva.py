"""Blocking host fetches per delivered byte (srv.stats(), window only)."""

from benchmarks.lib import readers


def read(obs):
    return readers.ratio(obs, "syncs", "generated")
