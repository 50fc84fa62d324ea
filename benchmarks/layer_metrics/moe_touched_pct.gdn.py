"""Of the held experts a call of an expert layer could have read, the share
that received a pair (stats(): 100 x moe_experts_touched / (experts held x
layers x calls); the device counts the held experts with a pair per layer
and per call, a call being one decode tick or one prefill wave: rounds +
prefill_waves of them). A tick of 48 rows lays some 240 pairs over 256 held
experts and touches well under all of them, a wave touches every one; what
a tick reads of the experts' weights follows it. A program that does not
count the delta rule's steps reads nothing.
"""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    touched = readers.counter(obs, "moe_experts_touched")
    calls = (readers.counter(obs, "rounds") or 0) + (
        readers.counter(obs, "prefill_waves") or 0)
    if touched is None or not calls or not gdn_readers.counted(obs):
        return None
    cfg = obs["config"]
    return 100.0 * touched / (cfg["num_experts"] * cfg["num_hidden_layers"]
                              * calls)
