"""Decode scan: least time for the bytes its ticks must read (the parameters
outside the experts, the held experts that received a pair that tick, twice
the active rows' state-space state, their committed K/V cells; from shapes
and the device's own count of touched experts: decode_least_bytes) at the
published bandwidth, over the host's decode spans (decode_ns, not device
time, hence no roofline in the name); mean over the window. Only a program
that counts the hybrid layout has this number. `better: higher` holds at a
given load only: more rows a tick raise the least bytes beside the same
parameters; read it beside `rows_per_tick.gran`.
"""

from benchmarks.lib import phase_readers, readers


def read(obs):
    if readers.counter(obs, "ssm_state_bytes_touched") is None:
        return None
    return phase_readers.decode_hbm_roofline(obs)
