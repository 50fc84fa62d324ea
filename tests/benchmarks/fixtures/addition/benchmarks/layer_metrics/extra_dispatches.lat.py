"""A later PR's per-layer metric: a reader of its own, nothing edited."""

from benchmarks.lib import readers


def read(obs):
    return readers.ratio(obs, "dispatches", "generated")
