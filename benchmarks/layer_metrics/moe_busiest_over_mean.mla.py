"""How uneven the routing is over the held experts: the busiest held expert's
pairs over the mean of the held experts' (stats(): moe_pairs_busiest, the
maximum over the held experts taken per layer and per program, prefill wave
or decode tick, then summed; over moe_pairs_held / experts held, the
configuration's `num_experts`). 1.0 is an even split; the grouped matmul's
time follows the busiest. A program that does not count latent cells reads
nothing.
"""

from benchmarks.lib import latent_readers, readers


def read(obs):
    busiest = readers.counter(obs, "moe_pairs_busiest")
    held = readers.counter(obs, "moe_pairs_held")
    if busiest is None or not held or not latent_readers.counted(obs):
        return None
    return busiest * obs["config"]["num_experts"] / held
