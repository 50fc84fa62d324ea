"""Of the cells the decode ticks attended to, the share that are chunk
summaries (stats(): 100 x eva_summary_cells_read / (eva_summary_cells_read +
eva_window_cells_read), from the host's committed counts per scan): whether
the traffic works the mechanism. A program without the counters reads nothing.
"""

from benchmarks.lib import readers


def read(obs):
    remote = readers.counter(obs, "eva_summary_cells_read")
    local = readers.counter(obs, "eva_window_cells_read")
    if remote is None or local is None or not remote + local:
        return None
    return 100.0 * remote / (remote + local)
