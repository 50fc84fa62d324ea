"""The serving/decode span (upload, scan dispatch, fetch) per decode tick,
mean over the window (stats(): decode_ns / rounds).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.mean_ms(obs, "decode_ns", "rounds")
