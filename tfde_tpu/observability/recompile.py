"""Recompile sentinel: jit-cache-miss detection for the hot entry points.

Recompiles are a silent perf hazard: a recompile that lands inside a
timed window is read as step time, and the serving pad-ladder can
churn buckets into fresh compilations with nothing counting them. The
pjit-on-TPUv4 experience is that compile time is a first-class budget at
scale — so it gets the same treatment as wall-clock: measured, attributed,
and gated.

Mechanism
---------
`jax.monitoring` fires a ``/jax/core/compile/backend_compile_duration``
event on every *actual* XLA compilation and nothing on a cache hit (the
C++ fast path never re-enters Python). One process-global listener
(installed lazily, idempotent) turns those events into:

- ``compile/seconds_total`` / ``compile/process_compiles`` — process-wide
  compile time and count, site or no site. Diff ``process_compiles()``
  around a window to assert it was compile-free.
- per-**site** attribution via a thread-local: a `Site` wraps one hot jit
  entry point (train step, a serving pad-ladder bucket); every call runs
  under ``site.watch(*fingerprint)`` and any compile event fired during
  the call is charged to that site's ``compile/<site>/{cache_hits,misses,
  seconds_total}`` counters. The *fingerprint* (shape-bucket, dtype,
  static-arg tuple — whatever the call site says shapes the program)
  classifies each miss: a **novel** fingerprint is an expected first
  compile; a miss on an already-seen fingerprint (cache thrash, a
  donation/weak-type bug) or past a declared signature budget is
  **unexpected**.
- every miss leaves a flight-recorder breadcrumb and (when the PR-9 ring
  is on) a ``compile/miss`` trace event carrying the victim request ids —
  a mid-serve recompile shows up in the waterfall that paid for it.
- ``storm_threshold`` unexpected misses on one site escalate once through
  the sentry-style supervisor warn path: loud log + ``recompile_storm``
  flight breadcrumb + ``compile/storms`` counter. Never raises —
  observability must not take serving down.

The listener and the bookkeeping are a dict lookup and two counter adds
per call; sites are safe to wrap around per-token paths.
"""

from __future__ import annotations

import contextlib
import logging
import threading
from typing import Dict, Iterator, Optional

from tfde_tpu.observability import flightrec, metrics
from tfde_tpu.observability import trace as _trace

log = logging.getLogger(__name__)

#: unexpected misses on one site before the storm escalation fires
STORM_THRESHOLD = 8

_EVENT_PREFIX = "/jax/core/compile/"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()
_lock = threading.Lock()
_sites: Dict[str, "Site"] = {}
_installed = False
_install_failed = False


def install() -> bool:
    """Register the process-global compile-event listener (idempotent).
    Returns False when this JAX has no monitoring hook — sites then
    count fingerprint novelty only (misses inferred, seconds zero)."""
    global _installed, _install_failed
    with _lock:
        if _installed:
            return True
        if _install_failed:
            return False
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_event)
        except Exception as e:  # noqa: BLE001 — degrade, don't crash
            _install_failed = True
            log.warning("recompile sentinel: no jax.monitoring listener "
                        "(%s); falling back to fingerprint novelty", e)
            return False
        _installed = True
        return True


def _on_event(event: str, duration: float, **_kw) -> None:
    """The jax.monitoring listener: fires once per actual compile stage
    (jaxpr trace, MLIR lowering, backend compile), never on a cache
    hit. Attribution: whatever Site the calling thread is inside."""
    if not event.startswith(_EVENT_PREFIX):
        return
    if getattr(_tls, "suppress", 0):
        # memwatch's own ledger interrogation (eval_shape / AOT compile)
        # must not read as a recompile of the program it is measuring
        metrics.counter("compile/memwatch_seconds_total").incr(duration)
        return
    metrics.counter("compile/seconds_total").incr(duration)
    if event == _BACKEND_EVENT:
        metrics.counter("compile/process_compiles").incr()
    pending = getattr(_tls, "pending", None)
    if pending is not None:
        pending[1] += duration
        if event == _BACKEND_EVENT:
            pending[0] += 1


@contextlib.contextmanager
def suppress() -> Iterator[None]:
    """Compile events in this block are counted as ledger overhead
    (``compile/memwatch_seconds_total``), not as process compiles or
    site misses. memwatch.py wraps its interrogation in this."""
    prev = getattr(_tls, "suppress", 0)
    _tls.suppress = prev + 1
    try:
        yield
    finally:
        _tls.suppress = prev


class Site:
    """One watched jit entry point. Create through `site()` so every
    caller naming the same site shares one fingerprint set."""

    def __init__(self, name: str, stable: bool = False,
                 expect: Optional[int] = None,
                 storm_threshold: int = STORM_THRESHOLD,
                 registry: Optional[metrics.Registry] = None):
        self.name = name
        #: stable sites additionally treat every signature past `expect`
        #: as unexpected (the bucket-churn failure mode); non-stable
        #: sites only flag re-compiles of an already-seen fingerprint
        self.stable = bool(stable)
        self.expect = expect
        self.storm_threshold = int(storm_threshold)
        self._reg = registry or metrics.default_registry()
        self._fingerprints: set = set()
        self.hits = 0
        self.misses = 0
        self.seconds = 0.0
        self.unexpected = 0
        self._storm_reported = False
        self._c_hits = self._reg.counter(f"compile/{name}/cache_hits")
        self._c_miss = self._reg.counter(f"compile/{name}/misses")
        self._c_secs = self._reg.counter(f"compile/{name}/seconds_total")
        self._g_sigs = self._reg.gauge(f"compile/{name}/signatures")

    @contextlib.contextmanager
    def watch(self, *fingerprint, traces=None) -> Iterator[None]:
        """Run one call to the wrapped entry point under this site.
        `fingerprint` is the call's program signature (shape bucket,
        dtype, static args); `traces` optionally carries the request
        trace ids a miss would have stalled."""
        install()
        prev_site = getattr(_tls, "site", None)
        prev_pending = getattr(_tls, "pending", None)
        _tls.site = self
        _tls.pending = pending = [0, 0.0]
        try:
            yield
        finally:
            _tls.site = prev_site
            _tls.pending = prev_pending
            self._settle(tuple(fingerprint), pending[0], pending[1],
                         traces)

    def _settle(self, key, compiles: int, secs: float, traces) -> None:
        with _lock:
            novel = key not in self._fingerprints
            self._fingerprints.add(key)
            nsigs = len(self._fingerprints)
        self._g_sigs.set(nsigs)
        if compiles == 0 and (_installed or not novel):
            # no monitoring hook: fall back to novelty as the miss signal
            self.hits += 1
            self._c_hits.incr()
            return
        self.misses += 1
        self._c_miss.incr()
        self.seconds += secs
        if secs:
            self._c_secs.incr(secs)
        unexpected = (not novel) or (
            self.stable and self.expect is not None and nsigs > self.expect
        )
        flightrec.record(
            "recompile", site=self.name, fingerprint=repr(key),
            seconds=round(secs, 4), novel=bool(novel),
            unexpected=bool(unexpected),
        )
        if _trace.active():
            _trace.event("compile/miss", traces=traces, dur=secs or None,
                         site=self.name, fingerprint=repr(key))
        if unexpected:
            self.unexpected += 1
            self._reg.counter(f"compile/{self.name}/unexpected").incr()
            if (self.unexpected >= self.storm_threshold
                    and not self._storm_reported):
                self._storm_reported = True
                self._escalate()

    def _escalate(self) -> None:
        """The sentry->supervisor warn path (observability/sentry.py's
        action='warn' shape): loud log + flight breadcrumb + counter.
        Deliberately never raises."""
        self._reg.counter("compile/storms").incr()
        flightrec.record(
            "recompile_storm", site=self.name, misses=self.misses,
            unexpected=self.unexpected, signatures=len(self._fingerprints),
            seconds=round(self.seconds, 3),
        )
        log.error(
            "recompile storm on site %s: %d unexpected misses "
            "(%d total, %d signatures, %.2fs compiling) — a supposedly "
            "shape-stable program is churning the jit cache; see "
            "WORKFLOWS.md §15",
            self.name, self.unexpected, self.misses,
            len(self._fingerprints), self.seconds,
        )
        try:
            # a storm is exactly when an XProf timeline answers "what shape
            # keeps changing" — ask the trigger hub for a bounded capture
            from tfde_tpu.observability import profiler

            profiler.trigger(
                "recompile_storm", key=f"recompile_storm:{self.name}",
                site=self.name, unexpected=self.unexpected,
                signatures=len(self._fingerprints),
            )
        except Exception:  # escalation must never raise into the hot path
            pass

    def snapshot(self) -> dict:
        with _lock:
            nsigs = len(self._fingerprints)
        return {
            "hits": self.hits,
            "misses": self.misses,
            "seconds": self.seconds,
            "signatures": nsigs,
            "unexpected": self.unexpected,
        }


def site(name: str, stable: bool = False, expect: Optional[int] = None,
         storm_threshold: int = STORM_THRESHOLD,
         registry: Optional[metrics.Registry] = None) -> Site:
    """Get-or-create the process-wide site `name`. Keyword arguments
    apply on first creation only (a site's policy is set by its owner)."""
    with _lock:
        s = _sites.get(name)
        if s is None:
            s = Site(name, stable=stable, expect=expect,
                     storm_threshold=storm_threshold, registry=registry)
            _sites[name] = s
        return s


def sites() -> Dict[str, dict]:
    """{site name: snapshot} — what tools/memgate.py reads out."""
    with _lock:
        items = list(_sites.items())
    return {name: s.snapshot() for name, s in items}


def process_compiles() -> int:
    """Actual XLA compiles observed process-wide (site or not) — the
    number to diff around a window that must be compile-free."""
    return int(metrics.counter("compile/process_compiles").value)


def seconds_total() -> float:
    return float(metrics.counter("compile/seconds_total").value)


def reset(registry: Optional[metrics.Registry] = None) -> None:
    """Drop every site and the compile/* metrics — test isolation hook.
    The monitoring listener stays installed (it cannot be unregistered)
    but re-created counters restart from zero."""
    with _lock:
        _sites.clear()
    (registry or metrics.default_registry()).reset("compile/")
