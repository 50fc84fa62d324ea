"""Programs this start compiled or read inside a site of the recompile sentinel
(`recompile.setup()`: `sited.programs`; the harness's `cache_requests` less
the unsited programs): a PR that adds a program or a bucket shows here;
moves setup_s. A program without the set-up ledger reads nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited(obs, "programs")
