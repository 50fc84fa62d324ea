"""Decode scan: least time for the bytes its ticks must read (the parameters
outside the experts and the head, the experts that received a pair that
tick, the active rows' committed cells in the layers without a window and
min(committed, window) in the window layers; from shapes and the device's
own count of touched experts: decode_least_bytes) at the published
bandwidth, over the host's decode spans (decode_ns, not device time, hence
no roofline in the name); mean over the window. Only a program that counts
a ring's cells has this number. `better: higher` holds at a given load
only: more rows a tick or longer contexts raise the least bytes beside the
same parameters; read it beside `rows_per_tick.smt`.
"""

from benchmarks.lib import phase_readers, ring_readers


def read(obs):
    if not ring_readers.counted(obs):
        return None
    return phase_readers.decode_hbm_roofline(obs)
