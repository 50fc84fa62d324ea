"""The serving/prefill spans per 1,024 real prompt tokens, mean over the
window (stats(): prefill_ns x 1024 / prefill_tokens); padding to the bucket
and the wave's first-token fetch are inside the span. Three layers of four
cost the same a token whatever the prompt's length and the fourth grows
with it, so this moves with the mix's lengths less than a cell of
attention layers does; of a program that counts the delta rule's steps
only."""

from benchmarks.lib import gdn_readers, readers


def read(obs):
    per_token_ns = readers.ratio(obs, "prefill_ns", "prefill_tokens")
    if per_token_ns is None or not gdn_readers.counted(obs):
        return None
    return per_token_ns * 1024 / 1e6
