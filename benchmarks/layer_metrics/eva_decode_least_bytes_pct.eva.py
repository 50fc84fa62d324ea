"""Decode scan: least time for the bytes its ticks must read (parameters,
live window cells and visible summaries, from shapes: decode_least_bytes) at
the published bandwidth, over the host's decode spans (decode_ns, not device
time, hence no roofline in the name); mean over the window. Only a program
that counts the eva layout's cells has this number. `better: higher` holds
at a given load only: more rows a tick and longer contexts raise the least
bytes beside the same parameters, so admission alone moves it; read it
beside `rows_per_tick.eva`.
"""

from benchmarks.lib import phase_readers, readers


def read(obs):
    if readers.counter(obs, "eva_window_cells_read") is None:
        return None
    return phase_readers.decode_hbm_roofline(obs)
