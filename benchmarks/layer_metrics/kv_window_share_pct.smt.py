"""Of the K/V cells the decode ticks read, the share that window layers'
rings hold (stats(): 100 x kv_window_cells_read / (kv_window_cells_read +
kv_full_cells_read); per scan, depth x the cells its active rows hold at
its start, a layer's cell at a time: every committed one in a layer
without a window, min(committed, window) in a window layer). Six window
layers to two without: 75 while every row is inside its window, less as
contexts pass it. A program without the counters reads nothing.
"""

from benchmarks.lib import readers


def read(obs):
    window = readers.counter(obs, "kv_window_cells_read")
    full = readers.counter(obs, "kv_full_cells_read")
    if window is None or full is None or not window + full:
        return None
    return 100.0 * window / (window + full)
