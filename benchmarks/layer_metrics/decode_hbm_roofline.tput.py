"""Decode scan: least time for the bytes its ticks must read (parameters +
committed KV cells, from shapes) at the published bandwidth, over the
host's decode spans (decode_ns, not device time); mean over the window.
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.decode_hbm_roofline(obs)
