"""The serving/prefill spans per 1,024 real prompt tokens, mean over the
window (stats(): prefill_ns x 1024 / prefill_tokens); padding to the bucket
and the wave's first-token fetch are inside the span; of a program that
counts a ring's cells only."""

from benchmarks.lib import readers, ring_readers


def read(obs):
    per_token_ns = readers.ratio(obs, "prefill_ns", "prefill_tokens")
    if per_token_ns is None or not ring_readers.counted(obs):
        return None
    return per_token_ns * 1024 / 1e6
