"""The serving/prefill spans per 1,024 real prompt tokens, mean over the
window (stats(): prefill_ns x 1024 / prefill_tokens); padding to the bucket
and the wave's first-token fetch are inside the span. A wave's attention
grows with the square of its length, so this moves with the mix's lengths
as well as with the program; of a program that counts latent cells only."""

from benchmarks.lib import latent_readers, readers


def read(obs):
    per_token_ns = readers.ratio(obs, "prefill_ns", "prefill_tokens")
    if per_token_ns is None or not latent_readers.counted(obs):
        return None
    return per_token_ns * 1024 / 1e6
