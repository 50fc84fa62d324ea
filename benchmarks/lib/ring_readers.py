"""What the readers of a cell whose window layers keep a ring share: the
`.smt` metrics are listed for that cell alone, and each reads nothing
(None, and raises nothing) from a program that does not count the ring's
cells, whichever cell it is handed."""

from __future__ import annotations

from benchmarks.lib import readers


def counted(obs: dict) -> bool:
    """Did the program count a ring's cells (`stats()`:
    `kv_window_cells_read`, `capacity.RingCapacityLedger`)?"""
    return readers.counter(obs, "kv_window_cells_read") is not None
