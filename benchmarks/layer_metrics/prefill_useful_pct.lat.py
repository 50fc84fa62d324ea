"""Real prompt tokens among the cells the prefill waves computed (padded rows
x bucket), over the window (stats(): 100 x prefill_tokens / prefill_cells).
"""

from benchmarks.lib import phase_readers


def read(obs):
    return phase_readers.share_pct(obs, "prefill_tokens", "prefill_cells")
