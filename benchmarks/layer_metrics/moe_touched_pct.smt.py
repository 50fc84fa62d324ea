"""Of the experts a call of an expert layer could have read, the share that
received a pair (stats(): 100 x moe_experts_touched / (experts x expert
layers x calls); the device counts the experts with a pair per layer and
per call, a call being one decode tick or one prefill wave: rounds +
prefill_waves of them). A tick of 32 rows touches about 61 of 64, a wave
all of them; what a tick reads of the experts' weights follows it. A
program that does not count a ring's cells reads nothing.
"""

from benchmarks.lib import readers, ring_readers


def read(obs):
    touched = readers.counter(obs, "moe_experts_touched")
    calls = (readers.counter(obs, "rounds") or 0) + (
        readers.counter(obs, "prefill_waves") or 0)
    if touched is None or not calls or not ring_readers.counted(obs):
        return None
    cfg = obs["config"]
    return 100.0 * touched / (cfg["moe_num_primary_experts"]
                              * cfg["num_hidden_layers"] * calls)
