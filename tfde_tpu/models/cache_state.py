"""What a layer keeps in the decode cache, said once by the module that
keeps it.

Each state-keeping module of models/transformer.py gives a `CacheState`
beside its `self.variable("cache", ...)` calls; a model gives one per
layer for a batcher of `max_len` positions a row (`GPT.cache_layout`).
The batcher's ledger (observability/capacity.py) and its refusals
(inference/server.py `_refuse_stateful`) are built from that list and ask
neither the model's fields nor the cache's leaf names. tests/
test_cache_state.py holds every description to the cache it describes, to
the byte: a module that caches something else without saying so fails
there.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class CacheState:
    """One layer's cached state of one row.

    `kind` is the arithmetic of a row that has committed `n` tokens:
    - 'kv', 'latent': a cell per position; it holds and a tick reads n;
    - 'ring': `cells` slots, slot = position mod `cells`: min(n, cells);
    - 'eva': a window in progress of `window` positions and a summary per
      `chunk`: n mod W live cells and n // C summaries held; a tick reads
      the live cells and the summaries of the windows already closed,
      (n // W)(W / C) of them (those of the window in progress are
      written and not yet read);
    - 'state': no cell at all, `fixed_bytes` (a running state and its
      convolution tail) held from admission on, read and written every
      tick; `chunk` is the positions of one triangular system of the
      delta rule's chunked prefill (0: a state without one).
    `cells` is what the row is allocated, `cell_bytes` one cell's bytes
    (int8 cells count their scales). `not_by_position` is None where the
    state is one cell per position, which is what the block pool, int8
    cells, the prefix cache, the primed hand-off and speculation need, and
    otherwise the reason the refusal prints."""

    kind: str
    cells: int = 0
    cell_bytes: int = 0
    fixed_bytes: int = 0
    window: int = 0
    chunk: int = 0
    not_by_position: Optional[str] = None

    def attended(self, n: int) -> tuple:
        """(live window cells, visible summaries) of an 'eva' row at `n`."""
        return (n % self.window,
                n // self.window * (self.window // self.chunk))

    def held_cells(self, n: int) -> int:
        if self.kind == "state":
            return 0
        if self.kind == "ring":
            return min(n, self.cells)
        if self.kind == "eva":
            return n % self.window + n // self.chunk
        return n

    def read_cells(self, n: int) -> int:
        return (sum(self.attended(n)) if self.kind == "eva"
                else self.held_cells(n))

    def held_bytes(self, n: int) -> int:
        return self.held_cells(n) * self.cell_bytes + self.fixed_bytes

    def read_bytes(self, n: int) -> int:
        return self.read_cells(n) * self.cell_bytes + 2 * self.fixed_bytes

    @property
    def row_bytes(self) -> int:
        """What a row is allocated: the layer's leaves, over the rows."""
        return self.cells * self.cell_bytes + self.fixed_bytes


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """A model's cache under a batcher: `layers`, one `CacheState` a layer
    in the tree's order (empty: the model describes nothing, and is taken
    for slabs of one cell per position), and `uncapped_experts`: None, or
    where expert layers route without a capacity (the ledger then accounts
    for the experts held) what the `feed_pad` leaf they keep forbids,
    models/moe.py `FEED_PAD_UNSHARED`: the prefix cache's refusal."""

    layers: tuple = ()
    uncapped_experts: Optional[str] = None

    @property
    def rings(self) -> bool:
        return any(s.kind == "ring" for s in self.layers)

    @property
    def not_by_position(self) -> Optional[str]:
        """The first layer's reason that is not a cell per position."""
        return next((s.not_by_position for s in self.layers
                     if s.not_by_position is not None), None)


def layout_of(model, max_len: Optional[int] = None) -> CacheLayout:
    """`model.cache_layout(max_len)`; `max_len` None asks about some
    length a batcher could be given (every window then keeps a ring)."""
    describe = getattr(model, "cache_layout", None)
    return describe(max_len) if describe is not None else CacheLayout()
