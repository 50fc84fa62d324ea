"""Recompile sentinel and set-up ledger: jit-cache-miss detection for the
hot entry points, and an account of what every compile of the process
cost, by stage, site and program.

Recompiles are a silent perf hazard: a recompile that lands inside a
timed window is read as step time, and the serving pad-ladder can
churn buckets into fresh compilations with nothing counting them. The
pjit-on-TPUv4 experience is that compile time is a first-class budget at
scale — so it gets the same treatment as wall-clock: measured, attributed,
and gated.

Mechanism
---------
`jax.monitoring` hands a process-global listener (installed lazily,
idempotent, the package's only one) three kinds of event, all on the
thread that compiles:

- a **time span** (start, end, ``fun_name``) for each of
  ``/jax/core/compile/jaxpr_trace_duration`` (``fun_name`` ``<name>``),
  ``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
  (``jit(<name>)``). A cache hit of the C++ fast path fires nothing.
- a **count** for ``/jax/compilation_cache/compile_requests_use_cache``,
  ``cache_hits`` and ``cache_misses`` (no ``fun_name``: they fall between
  the lowering and the backend event of the program that asked);
- a **duration** for ``/jax/compilation_cache/cache_retrieval_time_sec``.

One top-level program's trace, lowering and backend compile (or cache
read) make an **episode**, kept as integer nanoseconds: ``trace_ns``,
``lower_ns``, ``backend_ns``, ``cache_read_ns`` (a part of ``backend_ns``:
jax reads the cache inside the backend timer, so it is never added to
it), ``cache_hit`` (None where the program never asked the persistent
cache) and ``nested_traces``. A trace span that lies inside a later one by
its start and end (an inner ``jax.jit``, jax's own jitted helpers: they end
first and so arrive first) is folded into it: only the outermost span adds
time, the others are counted. Trace spans that no lowering follows
(``jax.eval_shape``) and a lowering that no backend compile follows
(``.lower()``) add their time to the same totals and make no episode. The
one thing counted twice is an episode that runs to its end INSIDE another
program's trace (``jax.ensure_compile_time_eval``): its own stages and,
again, the outer trace span that holds them.

Attribution, two ways:

- **by watch**: a `Site` wraps one hot jit entry point (a serving
  pad-ladder bucket, the decode scan); every call runs under
  ``site.watch(*fingerprint)`` and an episode that ends during the call is
  the site's, kept under (fingerprint, ``fun_name``) with the call's
  ``wall_ns`` and ``t0_ns`` (`spans.now_ns`, the clock every span reads):
  what a first call costs beyond jax's three stages (running the program
  once, its transfers) is ``wall_ns`` less the stages. The *fingerprint*
  (shape-bucket, dtype, static-arg tuple — whatever the call site says
  shapes the program) also classifies each miss: a **novel** fingerprint
  is an expected first compile; a miss on an already-seen fingerprint
  (cache thrash, a donation/weak-type bug) or past a declared signature
  budget is **unexpected**.
- **by name**: ``site(name).claim(fun_name)`` takes the top-level episodes
  of that name that end outside every watch, whatever thread runs them
  (the jitted train step, which no wrapper may slow or hide ``.lower()``
  of). A nested trace of the same name stays a nested trace.
- everything else (eager ``jnp`` calls, glue) is **unsited**: totals by
  stage and a count, so that the ledger's sum is the process's.
- under `suppress()` (memwatch's and hlolint's interrogation of a program
  they were handed) a lowering and a compile are the interrogation's
  overhead (``compile/memwatch_seconds_total``) and make no program; the
  trace is credited as above, since the interrogated program's own call
  finds it in jax's trace cache and pays microseconds: with
  ``TFDE_MEMWATCH=on`` that is where every watched serve program is
  traced. The totals get a trace that made no program when it ends (this
  one, or `jax.eval_shape(init_fn)` before `jit(init_fn)`); it is also
  held under its name until a program of that name is filed, whose own
  line then shows it.

`setup()` returns all of it; ``compile/seconds_total``,
``compile/process_compiles`` and, for what a site's watches saw,
``compile/<site>/{cache_hits,misses,seconds_total}`` are the same integers
as registry series (the seconds are the three stages' sum, a nested trace
counted once; a claimed program is in no per-site series, because the
goodput ledger sums those beside the spans that time unwatched work). Diff
``process_compiles()`` around a window to assert it was compile-free.

- every miss leaves a flight-recorder breadcrumb and (when the PR-9 ring
  is on) a ``compile/miss`` trace event carrying the victim request ids —
  a mid-serve recompile shows up in the waterfall that paid for it.
- ``storm_threshold`` unexpected misses on one site escalate once through
  the sentry-style supervisor warn path: loud log + ``recompile_storm``
  flight breadcrumb + ``compile/storms`` counter. Never raises —
  observability must not take serving down.

A watched call that compiles nothing costs two clock reads and a few
attribute writes; a compile event costs a list look and integer adds.
Sites are safe to wrap around per-token paths. The listeners run inside
jax's own compile: a fault of theirs is logged and drops that event from
the ledger (`_never_raises`), and a stage jax timed across a clock that
stepped back reads 0, never below.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
from typing import Dict, Iterator, Optional

from tfde_tpu.observability import flightrec, metrics
from tfde_tpu.observability import trace as _trace
from tfde_tpu.observability.spans import now_ns

log = logging.getLogger(__name__)

#: unexpected misses on one site before the storm escalation fires
STORM_THRESHOLD = 8

_EVENT_PREFIX = "/jax/core/compile/"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: count event -> `cache_hit` of the program that asked: it asked and is
#: not found (yet), it was found, it was compiled and written
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": False,
    "/jax/compilation_cache/cache_hits": True,
    "/jax/compilation_cache/cache_misses": False,
}

#: an episode's integer nanoseconds
_STAGES = ("trace_ns", "lower_ns", "backend_ns", "cache_read_ns")
_TOTAL_KEYS = _STAGES + ("first_call_ns", "programs", "cache_misses",
                         "nested_traces")


class _Thread(threading.local):
    """What the listener keeps for the thread that compiles. Nothing here
    grows with the number of events: a child span leaves `spans` when its
    parent arrives, and an episode leaves `episode` when it ends."""

    def __init__(self):
        self.suppress = 0
        self.call = None      # the innermost open watch: a _Call
        self.spans = []       # trace spans no parent or lowering has taken
        self.episode = None   # lowered, not yet compiled
        self.cache_hit = None
        self.cache_read_ns = 0
        # name -> [trace_ns, nested_traces] of traces that made no program
        # (`suppress()`, `jax.eval_shape`), until a program of that name
        # is filed: one entry a name traced and not compiled since
        self.traced = {}


class _Call:
    """One watched call and what compiled inside it."""

    __slots__ = ("site", "key", "prev", "t0_ns", "compiles", "ns", "entries")

    def __init__(self, site: "Site", key: tuple, prev: Optional["_Call"]):
        self.site = site
        self.key = key
        self.prev = prev
        self.compiles = 0
        self.ns = 0
        self.entries = []
        self.t0_ns = now_ns()


_thread = _Thread()
_lock = threading.Lock()
_sites: Dict[str, "Site"] = {}
_claims: Dict[str, "Site"] = {}
_sited = dict.fromkeys(_TOTAL_KEYS, 0)
_unsited = dict.fromkeys(_TOTAL_KEYS, 0)
_installed = False
_install_failed = False


def install() -> bool:
    """Register the process-global compile-event listeners (idempotent).
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

            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_time_span_listener(_on_span)
            monitoring.register_event_listener(_on_count)
        except Exception as e:  # noqa: BLE001 — degrade, don't crash
            _install_failed = True
            log.warning("recompile sentinel: no jax.monitoring listener "
                        "(%s); falling back to fingerprint novelty", e)
            return False
        _installed = True
        return True


def _never_raises(fn):
    """A listener runs inside jax's own compile and `_settle_pending` inside
    every watched call: a fault of the ledger is logged and costs the
    ledger that event, never the compile or the call around it."""
    @functools.wraps(fn)
    def guarded(*args, **kw):
        try:
            return fn(*args, **kw)
        except Exception:  # noqa: BLE001 — observability must not raise
            log.warning("recompile ledger: %s dropped an event",
                        fn.__name__, exc_info=True)
    return guarded


def _span_ns(start: float, end: float) -> int:
    """jax times its stages on `time.time()`, which a clock correction can
    step back: a span is never negative (a counter refuses to go down)."""
    return max(0, int((end - start) * 1e9))


def _stage_ns(d: dict) -> int:
    """Tracing, lowering and backend time; the cache read is inside the
    last."""
    return d["trace_ns"] + d["lower_ns"] + d["backend_ns"]


def _episode(fun_name: str, start: float) -> dict:
    ep = dict.fromkeys(_STAGES, 0)
    ep.update(fun_name=fun_name, cache_hit=None, nested_traces=0,
              start=start)
    return ep


@_never_raises
def _on_span(event: str, start: float, end: float, fun_name: str = "",
             **_kw) -> None:
    """One compile stage of one function has ended (seconds on jax's
    clock). Spans arrive in the order they end, so whatever is pending
    and started no earlier than this one lies inside it."""
    if not event.startswith(_EVENT_PREFIX):
        return
    t = _thread
    spans = t.spans
    nested = 0
    while spans and spans[-1][0] >= start:
        nested += 1 + spans.pop()[3]
    if event == _TRACE_EVENT:
        spans.append((start, end, fun_name, nested))
        return
    ns = _span_ns(start, end)
    if event == _LOWER_EVENT:
        # its own trace is the newest outermost span of its name
        name, own = _traced_name(fun_name), None
        for i in range(len(spans) - 1, -1, -1):
            if spans[i][2] == name:
                own = spans.pop(i)
                break
        _settle_pending(t)
        ep = t.episode = _episode(fun_name, start)
        ep["lower_ns"] = ns
        ep["nested_traces"] = nested
        if own is not None:
            ep["start"] = own[0]
            ep["trace_ns"] = _span_ns(own[0], own[1])
            ep["nested_traces"] += own[3]
    elif event == _BACKEND_EVENT:
        ep = t.episode
        if ep is not None and ep["fun_name"] == fun_name:
            t.episode = None
        else:   # compiled from an earlier lowering (`.lower()`, `.compile()`)
            ep = _episode(fun_name, start)
        _settle_pending(t)
        ep["backend_ns"] = ns
        ep["nested_traces"] += nested
        ep["cache_hit"], t.cache_hit = t.cache_hit, None
        ep["cache_read_ns"], t.cache_read_ns = t.cache_read_ns, 0
        ep["t0_ns"] = now_ns() - _span_ns(ep.pop("start"), end)
        _credit(t, ep, program=True)


@_never_raises
def _on_count(event: str, **_kw) -> None:
    hit = _CACHE_EVENTS.get(event)
    if hit is not None:
        _thread.cache_hit = hit


@_never_raises
def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _CACHE_READ_EVENT:
        _thread.cache_read_ns += max(0, int(duration * 1e9))


def _traced_name(fun_name: str) -> str:
    """`jit(step)` on the lowering and backend events is `step` on the
    trace event."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


@_never_raises
def _settle_pending(t: _Thread) -> None:
    """Credit what will not become a program: trace spans that no lowering
    followed (`jax.eval_shape`) and a lowering that no backend compile
    followed (`.lower()`)."""
    spans = t.spans
    while spans:
        start, end, name, nested = spans.pop()
        ep = _episode(name, start)
        ep["trace_ns"] = _span_ns(start, end)
        ep["nested_traces"] = nested
        _credit(t, ep)
    if t.episode is not None:
        ep, t.episode = t.episode, None
        _credit(t, ep)


def _credit(t: _Thread, ep: dict, program: bool = False) -> None:
    """Add one episode (`program`), or stage time that made none, to whom
    it belongs: the open watch's site, else the site that claims its
    name, else nobody's."""
    name = _traced_name(ep["fun_name"])
    if t.suppress:
        # memwatch's own ledger interrogation (eval_shape / AOT compile)
        # must not read as a recompile of the program it is measuring:
        # its lowering and its compile are the ledger's overhead. Its
        # TRACE is the program's own: jax keeps it, and the call that
        # follows finds it traced (a trace event of microseconds), so
        # hiding it here would hide most of a start's Python
        metrics.counter("compile/memwatch_seconds_total").incr(
            (ep["lower_ns"] + ep["backend_ns"]) * 1e-9)
        if not ep["trace_ns"]:
            return
        ep = dict(ep, lower_ns=0, backend_ns=0, cache_read_ns=0)
        program = False
    if not program and ep["trace_ns"]:
        # a trace that made no program (an interrogation's, or
        # `jax.eval_shape` before the jit): the totals get it now, and
        # the line of the program that reuses it will show it
        held = t.traced.setdefault(name, [0, 0])
        held[0] += ep["trace_ns"]
        held[1] += ep["nested_traces"]
    ns = _stage_ns(ep)
    call = t.call
    site = call.site if call is not None else _claims.get(name)
    with _lock:
        for totals in ((_unsited,) if site is None
                       else (_sited, site._totals)):
            for k in _STAGES:
                totals[k] += ep[k]
            totals["nested_traces"] += ep["nested_traces"]
            if program:
                totals["programs"] += 1
                totals["cache_misses"] += ep["cache_hit"] is False
        if program:
            # the totals already hold the trace its interrogation paid;
            # the program's own line shows it too
            held = t.traced.pop(name, (0, 0))
            if site is not None:
                ep["trace_ns"] += held[0]
                ep["nested_traces"] += held[1]
                entry = site._keep(call.key if call is not None else (), ep)
                if call is not None:
                    entry["t0_ns"] = call.t0_ns
                    call.entries.append(entry)
    metrics.counter("compile/seconds_total").incr(ns * 1e-9)
    if program:
        metrics.counter("compile/process_compiles").incr()
    if call is not None:
        # the site's registry series are its watches': goodput's compile
        # bucket sums them and must stay apart from the spans that time an
        # unwatched compile, so a claimed program shows in `setup()` alone
        if ns:
            site._c_secs.incr(ns * 1e-9)
        call.ns += ns
        call.compiles += program


@contextlib.contextmanager
def suppress() -> Iterator[None]:
    """Lowerings and compiles in this block are counted as ledger overhead
    (``compile/memwatch_seconds_total``), not as process compiles or
    site misses; a trace in it is credited as any other, because the
    program's own call reuses it. memwatch.py wraps its interrogation in
    this."""
    t = _thread
    _settle_pending(t)
    t.suppress += 1
    try:
        yield
    finally:
        _settle_pending(t)
        t.suppress -= 1


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
        #: (fingerprint, fun_name) -> the episode(s) of that program
        self._episodes: Dict[tuple, dict] = {}
        self._totals = dict.fromkeys(_TOTAL_KEYS, 0)
        self.hits = 0
        self.misses = 0
        self.unexpected = 0
        self._storm_reported = False
        self._c_hits = self._reg.counter(f"compile/{name}/cache_hits")
        self._c_miss = self._reg.counter(f"compile/{name}/misses")
        self._c_secs = self._reg.counter(f"compile/{name}/seconds_total")
        self._g_sigs = self._reg.gauge(f"compile/{name}/signatures")

    @property
    def seconds(self) -> float:
        """Tracing, lowering and backend time of everything credited to
        this site; a nested trace counts once."""
        return _stage_ns(self._totals) * 1e-9

    def claim(self, fun_name: str) -> "Site":
        """Top-level programs traced under `fun_name` that compile outside
        every watch are this site's from now on, whatever thread compiles
        them. For an entry point no wrapper may touch (the jitted train
        step): nothing runs per call."""
        install()
        with _lock:
            _claims[fun_name] = self
        return self

    def _keep(self, fingerprint: tuple, ep: dict) -> dict:
        """File one finished episode (under `_lock`); a program that
        compiles again under its key adds to what is there."""
        key = (fingerprint, ep["fun_name"])
        entry = self._episodes.get(key)
        if entry is None:
            ep.update(fingerprint=fingerprint, wall_ns=None, compiles=1)
            self._episodes[key] = entry = ep
        else:
            for k in _STAGES:
                entry[k] += ep[k]
            entry["nested_traces"] += ep["nested_traces"]
            entry["cache_hit"] = ep["cache_hit"]
            entry["compiles"] += 1
        return entry

    @contextlib.contextmanager
    def watch(self, *fingerprint, traces=None) -> Iterator[None]:
        """Run one call to the wrapped entry point under this site.
        `fingerprint` is the call's program signature (shape bucket,
        dtype, static args); `traces` optionally carries the request
        trace ids a miss would have stalled."""
        if not _installed:
            install()
        t = _thread
        if t.spans or t.episode is not None:
            _settle_pending(t)   # what came before the call is not its own
        call = t.call = _Call(self, tuple(fingerprint), t.call)
        try:
            yield
        finally:
            if t.spans or t.episode is not None:
                _settle_pending(t)
            wall_ns = now_ns() - call.t0_ns
            t.call = call.prev
            self._settle(call.key, call.compiles, call.ns * 1e-9, traces,
                         wall_ns, call.entries)

    def _settle(self, key, compiles: int, secs: float, traces,
                wall_ns: int = 0, entries=()) -> None:
        with _lock:
            novel = key not in self._fingerprints
            self._fingerprints.add(key)
            nsigs = len(self._fingerprints)
            if compiles:
                _sited["first_call_ns"] += wall_ns
                self._totals["first_call_ns"] += wall_ns
                for entry in entries:
                    entry["wall_ns"] = wall_ns
        self._g_sigs.set(nsigs)
        if compiles == 0 and (_installed or not novel):
            # no monitoring hook: fall back to novelty as the miss signal
            self.hits += 1
            self._c_hits.incr()
            return
        self.misses += 1
        self._c_miss.incr()
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
        """The five counts every reader knows, the site's stage integers
        and one dict a (fingerprint, `fun_name`) it compiled: the four
        `_ns`, `cache_hit`, `nested_traces`, `compiles`, and the watched
        call's `wall_ns` and `t0_ns` (a claimed program has no call: None
        and the episode's own start)."""
        with _lock:
            nsigs = len(self._fingerprints)
            totals = dict(self._totals)
            episodes = [dict(e) for e in self._episodes.values()]
        return {
            "hits": self.hits,
            "misses": self.misses,
            "seconds": self.seconds,
            "signatures": nsigs,
            "unexpected": self.unexpected,
            **totals,
            "episodes": episodes,
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


def setup() -> dict:
    """The set-up ledger: what compiling has cost this process so far.
    `sited` sums every site, `unsited` is the rest (eager calls, glue), each
    {trace_ns, lower_ns, backend_ns, cache_read_ns, first_call_ns, programs,
    cache_misses, nested_traces}; `sites` is `sites()`. `first_call_ns` is
    the wall time of the watched calls that compiled; `cache_misses` counts
    the programs that asked the persistent cache and were not found."""
    with _lock:
        out = {"sited": dict(_sited), "unsited": dict(_unsited)}
    out["sites"] = sites()
    return out


def process_compiles() -> int:
    """Actual XLA compiles observed process-wide (site or not) — the
    number to diff around a window that must be compile-free."""
    with _lock:
        return _sited["programs"] + _unsited["programs"]


def seconds_total() -> float:
    """Tracing, lowering and backend seconds of the whole process, sited
    and unsited."""
    with _lock:
        return (_stage_ns(_sited) + _stage_ns(_unsited)) * 1e-9


def reset(registry: Optional[metrics.Registry] = None) -> None:
    """Drop every site, claim and total and the compile/* metrics — test
    isolation hook. The monitoring listeners stay installed (they cannot
    be unregistered) and count on from zero."""
    with _lock:
        _sites.clear()
        _claims.clear()
        for tot in (_sited, _unsited):
            tot.update(dict.fromkeys(_TOTAL_KEYS, 0))
    t = _thread   # and what this thread had pending
    t.spans.clear()
    t.traced.clear()
    t.episode = None
    t.cache_hit = None
    t.cache_read_ns = 0
    (registry or metrics.default_registry()).reset("compile/")
