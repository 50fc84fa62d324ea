"""Model FLOP/s utilization: tokens/s x FLOPs per token / (chips x peak)."""

from benchmarks.lib import readers


def read(obs):
    return readers.mfu_pct(obs)
