"""Seconds jax spent lowering, compiling and reading the persistent cache (jax.monitoring); moves setup_s."""

from benchmarks.lib import readers


def read(obs):
    return readers.counter(obs, "compile_s")
