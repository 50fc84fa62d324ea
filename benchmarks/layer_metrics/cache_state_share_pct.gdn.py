"""Of the live cache the decode ticks work over, the share that is
delta-rule state and not K/V cells (stats(): 100 x gdn_state_bytes /
(gdn_state_bytes + kv_cell_bytes); both are what a scan's active rows hold,
summed over its ticks: a row's three states and tails, 6.44 MB whatever
its length, and its committed cells of the one attention layer at 2,048 B
a token). It says which of the two kinds of cache sets a tick's bytes: a
row's cells pass its state at 3,144 tokens. A program without the
counters reads nothing.
"""

from benchmarks.lib import readers


def read(obs):
    state = readers.counter(obs, "gdn_state_bytes")
    cells = readers.counter(obs, "kv_cell_bytes")
    if state is None or cells is None or not state + cells:
        return None
    return 100.0 * state / (state + cells)
