"""Blocking host fetches per delivered token (srv.stats(), window only): the
routing counts of the expert layers ride the fetches a wave and a scan
already make, so this reads what a model without them reads."""

from benchmarks.lib import readers


def read(obs):
    return readers.ratio(obs, "syncs", "generated")
