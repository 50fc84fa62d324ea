"""The host clock. A timed span ends at a host fetch of a jit output: a
synchronization no backend can answer before the work is done (the idea of
`bench.py::_Clock`; PERF.md records how `block_until_ready` compares)."""

from __future__ import annotations

import time

now = time.perf_counter


def fetch(x):
    """Bring a device value to the host; returns when it is really there."""
    import jax

    return jax.device_get(x)
