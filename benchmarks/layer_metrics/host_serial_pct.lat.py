"""Share of step() in which the host is not blocked on the device, mean over
the window (stats(): 100 x (step_ns - device_wait_ns) / step_ns).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.host_serial_pct(obs)
