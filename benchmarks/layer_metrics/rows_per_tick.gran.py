"""Tokens delivered per decode tick: mean occupied rows (srv.stats(), window only)."""

from benchmarks.lib import readers


def read(obs):
    return readers.ratio(obs, "generated", "rounds")
