"""How uneven the routing is over the experts: the busiest expert's pairs
over the mean of all experts' (stats(): moe_pairs_busiest, the maximum
over the held experts taken per layer and per program, prefill wave or
decode tick, then summed; over moe_pairs_held / experts held, here all
`moe_num_primary_experts`). 1.0 is an even split; the grouped matmul's
time follows the busiest. A program that does not count a ring's cells
reads nothing.
"""

from benchmarks.lib import readers, ring_readers


def read(obs):
    busiest = readers.counter(obs, "moe_pairs_busiest")
    held = readers.counter(obs, "moe_pairs_held")
    if busiest is None or not held or not ring_readers.counted(obs):
        return None
    return busiest * obs["config"]["moe_num_primary_experts"] / held
