"""Seconds inside jax's backend-compile timer in the sited programs of this
start (`recompile.setup()`: `sited.backend_ns`): the compile, or the read
where the persistent cache held the program (the read is a part of it, never
added to it); moves setup_s. A program without the set-up ledger reads
nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited_seconds(obs, "backend_ns")
