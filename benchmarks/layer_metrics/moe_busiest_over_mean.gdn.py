"""How uneven the routing is over the held experts: the busiest held expert's
pairs over the mean of the held experts' (stats(): moe_pairs_busiest, the
maximum over the held experts taken per layer and per program, prefill wave
or decode tick, then summed; over moe_pairs_held / experts held, the
configuration's `num_experts`). 1.0 is an even split; a tick of 48 rows
lays some 240 pairs over 256 held experts, so there the busiest of a layer
stands several times over a mean below one. A program that does not count
the delta rule's steps reads nothing.
"""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    busiest = readers.counter(obs, "moe_pairs_busiest")
    held = readers.counter(obs, "moe_pairs_held")
    if busiest is None or not held or not gdn_readers.counted(obs):
        return None
    return busiest * obs["config"]["num_experts"] / held
