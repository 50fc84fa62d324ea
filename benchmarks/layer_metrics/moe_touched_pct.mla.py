"""Of the held experts a call of an expert layer could have read, the share
that received a pair (stats(): 100 x moe_experts_touched / (experts held x
routed layers x calls); the device counts the held experts with a pair per
layer and per call, a call being one decode tick or one prefill wave:
rounds + prefill_waves of them). A tick of ten rows lays some twenty pairs
over 32 held experts, a wave touches all of them; what a tick reads of the
experts' weights follows it. A program that does not count latent cells
reads nothing.
"""

from benchmarks.lib import latent_readers, readers


def read(obs):
    touched = readers.counter(obs, "moe_experts_touched")
    calls = (readers.counter(obs, "rounds") or 0) + (
        readers.counter(obs, "prefill_waves") or 0)
    if touched is None or not calls or not latent_readers.counted(obs):
        return None
    cfg = obs["config"]
    return 100.0 * touched / (cfg["num_experts"]
                              * latent_readers.routed_layers(cfg) * calls)
