"""What the readers of a cell whose layers keep a delta-rule state share:
the `.gdn` metrics are listed for that cell alone, and each reads nothing
(None, and raises nothing) from a program that does not count the rule's
steps, whichever cell it is handed."""

from __future__ import annotations

from benchmarks.lib import readers


def counted(obs: dict) -> bool:
    """Did the program count the delta rule's steps (`stats()`:
    `gdn_steps`, `capacity.DeltaCapacityLedger`)?"""
    return readers.counter(obs, "gdn_steps") is not None
