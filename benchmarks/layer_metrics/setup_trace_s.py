"""Seconds of Python tracing in the sited programs of this start
(`recompile.setup()`: `sited.trace_ns`, the outermost trace span of each
program, its nested traces folded in and counted once; `compile_s` leaves
tracing out); moves setup_s. A program without the set-up ledger reads
nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited_seconds(obs, "trace_ns")
