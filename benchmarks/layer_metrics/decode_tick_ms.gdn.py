"""The serving/decode span (upload, scan dispatch, fetch) per decode tick,
mean over the window (stats(): decode_ns / rounds); of a program that
counts the delta rule's steps only."""

from benchmarks.lib import gdn_readers, phase_readers


def read(obs):
    if not gdn_readers.counted(obs):
        return None
    return phase_readers.mean_ms(obs, "decode_ns", "rounds")
