"""The prefill waves' model FLOPs over the serving/prefill spans at the
chip's published peak: the cell's share of a whole step (stats():
prefill_tokens real tokens through the mixers' projections, the delta rule
counted in its recurrence, the routers and shared experts;
kv_pairs_prefilled attended pairs at their TRUE lengths in the attention
layers, 16,384 FLOP a pair and layer at the published widths; and the
pairs routed to experts held here; `lib/gdn_flops.py` counts the
mathematics, whatever implements it). The held pairs of the prefills are
the window's held pairs (moe_pairs_held, counted on the device over waves
and ticks alike) times the prefills' share of all pairs routed: every real
token makes `num_experts_per_tok` pairs a layer. The span is the host's
(pack, the zero template, dispatch, the first-token fetch), so the share
reads under the device's own. A program without the counters reads
nothing.
"""

from benchmarks.lib import gdn_flops, readers
from benchmarks.lib.peaks import peaks_for


def read(obs):
    tokens = readers.counter(obs, "prefill_tokens")
    span_ns = readers.counter(obs, "prefill_ns")
    attended = readers.counter(obs, "kv_pairs_prefilled")
    routed = readers.counter(obs, "moe_pairs")
    held = readers.counter(obs, "moe_pairs_held")
    if attended is None or not tokens or not span_ns or not routed:
        return None
    cfg = obs["config"]
    mine = tokens * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
    flops = gdn_flops.prefill_flops(cfg, tokens, attended,
                                    (held or 0) * min(mine / routed, 1.0))
    peak = peaks_for(obs["device_kind"])["bf16_flops"]
    return 100.0 * flops / (span_ns / 1e9 * peak)
