"""Submit to the start of the prefill wave that took the request, mean over
the requests admitted in the window (stats(): queue_wait_ns / admitted).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.mean_ms(obs, "queue_wait_ns", "admitted")
