"""Lightweight span timers feeding the metric registry.

    with span("train/data_wait"):
        batch = next(host_iter)

Each span observes its wall duration into `registry.histogram(name)` —
that is the step-time-breakdown substrate: the train loop wraps its
phases (data wait, step dispatch, device sync, summary write, checkpoint
save, eval) and `goodput.py` reads the histogram sums back out to
classify the run's wall-clock.

When a profiler trace is active (profiler.py's `profile_trace` or
`StepWindowProfiler` window), every span additionally opens a
`jax.profiler.TraceAnnotation` region, so the SAME names appear on the
XProf timeline — one vocabulary across metrics and traces. The
TraceAnnotation is only constructed while tracing (the
`set_trace_active` flag, flipped by profiler.py at start/stop), keeping
the steady-state span cost to a clock read and a locked histogram add.

When the request-trace ring (trace.py) is active, every span ALSO lands
as a duration event on that timeline, tagged with the thread's bound
trace id (`trace.bind`) — so training-phase spans and serving request
waterfalls share one vocabulary and one viewer. The event carries the
name of the span that encloses it (`parent`), so a span knows the span
that caused it.

A call site that keeps its own account hands the span a `ledger` (a dict
of integers): the span then adds its integer nanoseconds under `ns` and,
where `n` is given, one to the count under `n` — always, whatever the
flags above say. That is how `ContinuousBatcher.step()` accounts for its
own time (`stats()` returns the ledger). `histogram=False` keeps a leaf
span out of the registry: it feeds the ledger, the annotation and the
ring only. The span object is what `with` binds: `t0_ns`/`t1_ns` are its
two clock reads, for call sites that stamp what happened at a boundary
with the boundary's own time instead of reading the clock again.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from tfde_tpu.observability import metrics
from tfde_tpu.observability import trace as reqtrace

#: the clock every span reads; call sites that stamp a moment (a submit, a
#: deadline) and later take its distance from a span's boundary read this
#: one too, so both ends of the interval are on one clock
now_ns = time.perf_counter_ns

_trace_active = False
# jax is resolved ONCE when profiler tracing first activates — span()
# used to re-run the import machinery on every traced span
_jax = None


def set_trace_active(active: bool) -> None:
    """Flipped by profiler.py when a jax.profiler trace starts/stops; spans
    emit TraceAnnotations only while True."""
    global _trace_active, _jax
    _trace_active = bool(active)
    if _trace_active and _jax is None:
        import jax

        _jax = jax


def trace_active() -> bool:
    return _trace_active


# the span open on this thread, kept only while the request ring is on
_tls = threading.local()


class span:
    """Time the enclosed block into `histogram(name)` (seconds); mirror it
    as a TraceAnnotation when a profiler trace is running. Duration is
    recorded even when the block raises — a failing phase still spent the
    wall-clock. With a `ledger`, add the integer nanoseconds to
    `ledger[ns]` and count the span in `ledger[n]`."""

    __slots__ = ("name", "t0_ns", "t1_ns", "wall", "_registry", "_ledger",
                 "_ns", "_n", "_histogram", "_ann", "_parent")

    def __init__(self, name: str,
                 registry: Optional[metrics.Registry] = None, *,
                 ledger: Optional[dict] = None, ns: Optional[str] = None,
                 n: Optional[str] = None, histogram: bool = True):
        self.name = name
        self._registry = registry
        self._ledger, self._ns, self._n = ledger, ns, n
        self._histogram = histogram
        self._ann = None
        self.wall = None
        self.t1_ns = None

    def __enter__(self) -> "span":
        if _trace_active:
            self._ann = _jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        if reqtrace.active():
            self.wall = time.time()
            self._parent = getattr(_tls, "name", None)
            _tls.name = self.name
        self.t0_ns = now_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = now_ns()
        dt_ns = self.t1_ns - self.t0_ns
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._ledger is not None:
            self._ledger[self._ns] += dt_ns
            if self._n is not None:
                self._ledger[self._n] += 1
        if self._histogram:
            (self._registry or metrics.default_registry()).histogram(
                self.name).observe(dt_ns * 1e-9)
        if self.wall is not None:
            # same name, same timeline: picks up the thread's bound
            # request id (trace.bind) automatically via current()
            _tls.name = self._parent
            if self._parent is None:
                reqtrace.event(self.name, ts=self.wall, dur=dt_ns * 1e-9)
            else:
                reqtrace.event(self.name, ts=self.wall, dur=dt_ns * 1e-9,
                               parent=self._parent)

    @property
    def dur_ns(self) -> int:
        """Nanoseconds between the two clock reads (after the block)."""
        return self.t1_ns - self.t0_ns


def record(name: str, seconds: float,
           registry: Optional[metrics.Registry] = None) -> None:
    """Observe an externally measured duration under a span name — for
    call sites that already hold a timer (the prefetch generator times its
    own blocking pulls) and can't wrap a `with` block around the wait."""
    (registry or metrics.default_registry()).histogram(name).observe(seconds)
