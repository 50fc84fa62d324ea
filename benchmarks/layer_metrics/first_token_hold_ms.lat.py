"""A first token on the host until the step that fetched it returns: the
decode round a streaming client waits out, mean over the requests admitted
in the window (stats(): first_token_hold_ns / admitted).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.mean_ms(obs, "first_token_hold_ns", "admitted")
