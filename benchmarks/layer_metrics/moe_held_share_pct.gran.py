"""Of the (token, choice) pairs the expert layers routed, the share whose
expert this chip holds (stats(): 100 x moe_pairs_held / moe_pairs, counted
on the device over the real tokens of prefill waves and decode ticks): about
the held share of the experts on seeded weights. A program without the
counters reads nothing.
"""

from benchmarks.lib import readers


def read(obs):
    share = readers.ratio(obs, "moe_pairs_held", "moe_pairs")
    return None if share is None else 100.0 * share
