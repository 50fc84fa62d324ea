"""Decode scan: least time for the bytes its ticks must move (the dense
weights, the head among them, a tick; of the experts held, those that
received a pair that tick, from the device's own count; the active rows'
committed K/V cells; their delta-rule state and tails read and written:
`lib/gdn_flops.py::decode_least_bytes` over stats()'s rounds,
moe_experts_touched, kv_cell_bytes and gdn_state_bytes) at the published
bandwidth, over the host's decode spans (decode_ns, not device time, hence
no roofline in the name); mean over the window. Only a program that counts
the delta rule's steps has this number. `better: higher` holds at a given
load only: more rows a tick raise the state's bytes beside the same
weights; read it beside `rows_per_tick.gdn` and
`cache_state_share_pct.gdn`.
"""

from benchmarks.lib import gdn_flops, gdn_readers, readers
from benchmarks.lib.peaks import peaks_for


def read(obs):
    ticks = readers.counter(obs, "rounds")
    span_ns = readers.counter(obs, "decode_ns")
    if not gdn_readers.counted(obs) or not ticks or not span_ns:
        return None
    least = gdn_flops.decode_least_bytes(
        obs["config"], ticks,
        readers.counter(obs, "moe_experts_touched") or 0,
        readers.counter(obs, "kv_cell_bytes") or 0,
        readers.counter(obs, "gdn_state_bytes") or 0)
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (span_ns / 1e9 * peak)
