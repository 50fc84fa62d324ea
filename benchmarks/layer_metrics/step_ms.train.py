"""Median host-clock time of one training step ending in a loss fetch."""

from benchmarks.lib import readers


def read(obs):
    return readers.step_ms(obs)
