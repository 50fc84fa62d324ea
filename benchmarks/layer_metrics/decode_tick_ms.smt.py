"""The serving/decode span (upload, scan dispatch, fetch) per decode tick,
mean over the window (stats(): decode_ns / rounds); of a program that
counts a ring's cells only."""

from benchmarks.lib import phase_readers, ring_readers


def read(obs):
    if not ring_readers.counted(obs):
        return None
    return phase_readers.mean_ms(obs, "decode_ns", "rounds")
