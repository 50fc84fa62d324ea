"""Readers of the batcher's own account of its step: the integers
`ContinuousBatcher.stats()` keeps (`*_ns` added by the program's spans on
one clock, counts added at the same boundaries), as differences over the
window, which is how the serve driver hands every `int` of `stats()` to a
reader. Every value is a mean over the window. A program that keeps no
such account (one older than the ledger) hands over no such counter: the
reader then returns None and raises nothing."""

from __future__ import annotations

from benchmarks.lib import readers
from benchmarks.lib.peaks import peaks_for


def mean_ms(obs: dict, ns: str, n: str):
    """Milliseconds per `n`: nanoseconds under `ns` over the count."""
    value = readers.ratio(obs, ns, n)
    return None if value is None else value / 1e6


def share_pct(obs: dict, part: str, whole: str):
    value = readers.ratio(obs, part, whole)
    return None if value is None else 100.0 * value


def host_serial_pct(obs: dict):
    """The share of the batcher's steps in which the host is not blocked
    on the device: everything but the two blocking fetches."""
    waited = share_pct(obs, "device_wait_ns", "step_ns")
    return None if waited is None else 100.0 - waited


def decode_hbm_roofline(obs: dict):
    """The time the chip's memory needs at its published bandwidth for
    the bytes the decode ticks cannot avoid reading (`decode_least_bytes`:
    parameters and committed KV cells, computed from shapes by the
    program, not measured), over the time the decode spans took. That is
    the host's span around the scan (table repair, upload, dispatch,
    fetch), not the scan's device time: it also holds device work that
    admission left behind and the fetch's latency, so the share reads
    under the kernel's own and moves with admission load too."""
    per_ns = readers.ratio(obs, "decode_least_bytes", "decode_ns")
    if per_ns is None:
        return None
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_ns * 1e9 / peak
