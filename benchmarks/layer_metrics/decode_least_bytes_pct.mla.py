"""Decode scan: least time for the bytes its ticks must read (the parameters
outside the experts, the head among them; of the experts held, those that
received a pair that tick; the active rows' committed latent cells at 1,152
B a layer and position; from shapes and the device's own count of touched
experts: decode_least_bytes) at the published bandwidth, over the host's
decode spans (decode_ns, not device time, hence no roofline in the name);
mean over the window. Only a program that counts latent cells has this
number. `better: higher` holds at a given load only: more rows a tick or
longer contexts raise the least bytes beside the same parameters; read it
beside `rows_per_tick.mla` and `kv_latent_share_pct.mla`.
"""

from benchmarks.lib import latent_readers, phase_readers


def read(obs):
    if not latent_readers.counted(obs):
        return None
    return phase_readers.decode_hbm_roofline(obs)
