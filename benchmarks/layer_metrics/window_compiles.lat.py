"""Programs the backend compiled inside the window (jax.monitoring); must read 0."""

from benchmarks.lib import readers


def read(obs):
    return readers.counter(obs, "window_compiles")
