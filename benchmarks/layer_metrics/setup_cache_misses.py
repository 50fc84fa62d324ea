"""Sited programs of this start that asked the persistent cache and were not
found (`recompile.setup()`: `sited.cache_misses`): compiled and written
instead of read. 0 on a warm start; the number that tells a cold or thrashed
cache from a slower program; moves setup_s. A program without the set-up
ledger reads nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited(obs, "cache_misses")
