"""Seconds of lowering jaxprs to MLIR modules in the sited programs of this
start (`recompile.setup()`: `sited.lower_ns`); moves setup_s. A program
without the set-up ledger reads nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited_seconds(obs, "lower_ns")
