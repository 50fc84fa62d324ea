"""Of the bytes the decode ticks cannot avoid reading, the share that is
latent cells (stats(): 100 x latent_cells_read x the cell's bytes /
decode_least_bytes; per scan, depth x the committed cells of its active
rows, a layer's cell at a time, at 2 x (kv_lora_rank + qk_rope_head_dim) B
in bfloat16). It says when the cache, not the experts' and the other
weights, sets the tick: sixteen rows at 32,768 positions are 3.0 GB of
cells beside about 5 GB of weights a tick reads. A program without the
counters reads nothing.
"""

from benchmarks.lib import latent_readers, readers


def read(obs):
    cells = readers.counter(obs, "latent_cells_read")
    least = readers.counter(obs, "decode_least_bytes")
    if cells is None or not least:
        return None
    return 100.0 * cells * latent_readers.cell_bytes(obs["config"]) / least
