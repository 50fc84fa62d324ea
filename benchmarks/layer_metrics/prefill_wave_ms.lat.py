"""One group wave of prefill, template to first-token fetch, mean over the
window's waves (stats(): prefill_ns / prefill_waves).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.mean_ms(obs, "prefill_ns", "prefill_waves")
