"""Central registry of every ``TFDE_*`` environment knob.

Every environment variable the framework reads is declared here once —
name, type, allowed values, default, and a doc string — so that:

- a typo'd **value** warns and falls back to the default instead of
  silently changing behavior (the ``TFDE_FLASH`` pattern from
  `ops/attention.py`, now the house rule for every knob);
- a typo'd **name** (``TFDE_GRAD_TRANSPRT=int8``) is caught at import
  by :func:`warn_unknown_env`, instead of being ignored forever;
- the project lint (`tools/tfdelint.py`) can cross-check every
  ``os.environ`` read of a ``TFDE_*`` literal in the tree against this
  registry and fail on unregistered knobs;
- the README knob table is generated (:func:`table_md`), not
  hand-maintained.

Read sites keep their module-local grammar where one exists (the
``TFDE_TRACE`` capacity spec, the ``TFDE_PROFILE`` window, the
``TFDE_PREFIX_CACHE`` byte budget) — those are registered with
``kind='spec'`` and validated by their owners — but scalar knobs route
through the accessors below (:func:`env_str` / :func:`env_int` /
:func:`env_float` / :func:`env_choice` / :func:`env_flag`), which warn
once per (name, bad value) and return the registered default.

This module deliberately imports nothing from the rest of the package:
any tfde_tpu module may import it without cycles.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import warnings
from typing import Any, Dict, Optional, Tuple

__all__ = [
    "Knob", "REGISTRY", "is_registered", "canonical_names",
    "env_str", "env_int", "env_float", "env_choice", "env_flag",
    "warn_unknown_env", "table_md",
]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered environment knob."""

    #: full env-var name (``TFDE_GRAD_TRANSPORT``) — or, for a family,
    #: the shared prefix ending in ``_`` with ``prefix=True``
    #: (``TFDE_SLO_`` covers ``TFDE_SLO_TTFT_MS`` etc. in audits, but
    #: well-known members are registered individually too).
    name: str
    #: value shape: 'choice' (one of `choices`), 'int', 'float', 'flag'
    #: (boolean-ish on/off spellings), 'str' (free-form: paths, URLs),
    #: or 'spec' (module-local grammar; the owner validates).
    kind: str
    #: value used when the variable is unset OR unparseable (after a
    #: warning). None means "feature off / derive elsewhere".
    default: Any = None
    #: allowed spellings for kind='choice' (canonical first).
    choices: Tuple[str, ...] = ()
    #: one-line operator doc; rendered into the README table.
    doc: str = ""
    #: where the value is consumed (module path, for the table).
    owner: str = ""
    #: True when `name` is a family prefix (``TFDE_RETRY_``).
    prefix: bool = False


REGISTRY: Dict[str, Knob] = {}

_warn_lock = threading.Lock()
_warned: set = set()  # (name, raw-value) pairs already warned about


def _register(*knobs: Knob) -> None:
    for k in knobs:
        REGISTRY[k.name] = k


_register(
    # --- parallel ---------------------------------------------------------
    Knob("TFDE_GRAD_TRANSPORT", "choice", "fp32", ("fp32", "int8"),
         "Default gradient exchange: full-precision psum or blockwise-"
         "quantized int8 transport with error feedback.",
         "parallel/comms.py"),
    Knob("TFDE_OPT_SHARDING", "choice", "replicated", ("replicated", "shard"),
         "Default optimizer-state placement: replicated, or ZeRO row-"
         "sharded weight update (reduce-scatter grads, all-gather params).",
         "parallel/zero.py"),
    # --- ops --------------------------------------------------------------
    Knob("TFDE_FLASH", "spec", "auto",
         ("auto", "on", "off", "<int min_seq>"),
         "Flash-attention dispatch threshold: 'off' never, 'on'/'1' always "
         "(min_seq=1024 legacy spelling), 'auto'/'' the built-in ladder, an "
         "integer sets min_seq explicitly.",
         "ops/attention.py"),
    # --- training / runtime ----------------------------------------------
    Knob("TFDE_PROFILE", "spec", None,
         ("<start>", "<start>:<stop>", "every:N", "every:N:S"),
         "XLA profiler step window (traces land under the run's "
         "model_dir): one window of global steps ('100:110', or '100' "
         "for 10 steps) or a repeating capture ('every:1000:5').",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_", "spec", None, (),
         "Trigger-driven profiling family prefix (see members below).",
         "observability/profiler.py", prefix=True),
    Knob("TFDE_PROFILE_TRIGGERS", "flag", True, (),
         "Allow anomaly signals (SLO burn, straggler, recompile storm, "
         "sentry trip) to auto-arm bounded XProf captures; 'off' keeps "
         "the trigger hub silent.",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_COOLDOWN_S", "float", 120.0, (),
         "Minimum seconds between any two trigger-driven captures.",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_DEDUPE_S", "float", 600.0, (),
         "Per-reason re-fire suppression window, seconds — the same "
         "anomaly cannot arm a second capture within it.",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_SPAN", "int", 8, (),
         "Default capture span for triggered windows: train steps "
         "(StepWindowProfiler.arm) or serving decode rounds "
         "(RoundWindowProfiler).",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_RETAIN", "int", 8, (),
         "Profile artifacts retained under <model_dir>/debug/profiles/ "
         "before the oldest capture (meta + trace dir) is pruned.",
         "observability/profiler.py"),
    Knob("TFDE_PROFILE_BURN_THRESHOLD", "float", 10.0, (),
         "Fast-window SLO burn rate at which the tracker asks the "
         "trigger hub for a capture; <= 0 disables the burn trigger.",
         "observability/slo.py"),
    Knob("TFDE_METRICS_PORT", "int", None, (),
         "Fixed port for the chief's /metrics+/push HTTP server (unset or "
         "0 = ephemeral; workers then cannot derive a push URL).",
         "training/lifecycle.py, runtime/cluster.py"),
    Knob("TFDE_METRICS_PUSH_URL", "str", None, (),
         "Explicit aggregator endpoint for non-chief metric pushes; "
         "overrides the coordinator-host + TFDE_METRICS_PORT derivation.",
         "runtime/cluster.py"),
    Knob("TFDE_DATA_DIR", "str", None, (),
         "Local dataset cache directory searched before ~/.keras/datasets "
         "and /tmp/data.",
         "data/datasets.py"),
    Knob("TFDE_NATIVE_CACHE", "str", None, (),
         "Build cache directory for the native C++ loader "
         "(default ~/.cache/tfde_tpu).",
         "native/__init__.py"),
    # --- cluster identity -------------------------------------------------
    Knob("TFDE_NUM_PROCESSES", "int", None, (),
         "Native cluster contract: world size. Takes precedence over "
         "TF_CONFIG when set.",
         "runtime/cluster.py"),
    Knob("TFDE_PROCESS_ID", "int", None, (),
         "Native cluster contract: this host's rank (default 0).",
         "runtime/cluster.py, observability/flightrec.py"),
    Knob("TFDE_COORDINATOR", "str", None, (),
         "Native cluster contract: coordinator host[:port].",
         "runtime/cluster.py"),
    Knob("TFDE_COORD_PORT", "int", None, (),
         "Override for the derived jax.distributed coordinator port.",
         "runtime/cluster.py"),
    # --- resilience (family: validated by policy_from_env, which raises
    # loudly on garbage — pinned by tests/test_resilience_policy.py) ------
    Knob("TFDE_RETRY_", "spec", None, (),
         "Retry-policy family prefix (see members below).",
         "resilience/policy.py", prefix=True),
    Knob("TFDE_RETRY_MAX_ATTEMPTS", "int", 4, (),
         "Retry budget for library I/O paths; 1 disables retries.",
         "resilience/policy.py"),
    Knob("TFDE_RETRY_INITIAL_BACKOFF", "float", 0.5, (),
         "First backoff sleep, seconds.", "resilience/policy.py"),
    Knob("TFDE_RETRY_MAX_BACKOFF", "float", 30.0, (),
         "Backoff ceiling, seconds.", "resilience/policy.py"),
    Knob("TFDE_RETRY_DEADLINE", "float", None, (),
         "Total retry wall-clock budget, seconds (unset = attempts only).",
         "resilience/policy.py"),
    # --- elastic training -------------------------------------------------
    Knob("TFDE_ELASTIC", "flag", False, (),
         "Elastic topology-change handling in the supervisor: a failure "
         "classified TOPOLOGY shrinks the cluster to the surviving hosts "
         "and resumes from the latest checkpoint instead of dying.",
         "resilience/elastic.py"),
    Knob("TFDE_ELASTIC_", "spec", None, (),
         "Elastic-training family prefix (see members below).",
         "resilience/elastic.py", prefix=True),
    Knob("TFDE_ELASTIC_MAX_CHANGES", "int", 4, (),
         "Topology changes allowed across one supervised run before the "
         "supervisor aborts.",
         "resilience/elastic.py"),
    Knob("TFDE_ELASTIC_DETECT_TIMEOUT_S", "float", 5.0, (),
         "Heartbeat-staleness age, seconds, at which a silent host is "
         "registered as a topology suspect.",
         "resilience/elastic.py, resilience/health.py"),
    Knob("TFDE_ELASTIC_PRESUME_LOST", "flag", True, (),
         "When a collective dies with no identified peer, presume every "
         "other rank lost and shrink to self (a scheduler env rewrite "
         "always wins over presumption).",
         "resilience/elastic.py"),
    Knob("TFDE_ELASTIC_MIN_WORLD", "int", 1, (),
         "Abort instead of resuming when the surviving world size is "
         "smaller than this.",
         "resilience/elastic.py"),
    # --- observability ----------------------------------------------------
    Knob("TFDE_TRACE", "spec", None, ("off", "on", "<int capacity>"),
         "Per-request distributed tracing: off (default), on (default "
         "ring capacity), or an integer ring capacity.",
         "observability/trace.py"),
    Knob("TFDE_MEMWATCH", "choice", "on", ("on", "off", "full"),
         "Per-program memory ledger: estimate-only ('on'), disabled, or "
         "AOT-compiled measurement ('full'/'measured').",
         "observability/memwatch.py"),
    Knob("TFDE_SLO_", "spec", None, (),
         "SLO-objective family prefix (see members below).",
         "observability/slo.py", prefix=True),
    Knob("TFDE_SLO_TTFT_MS", "float", 500.0, (),
         "Time-to-first-token SLO threshold, milliseconds.",
         "observability/slo.py"),
    Knob("TFDE_SLO_TPOT_MS", "float", 200.0, (),
         "Time-per-output-token SLO threshold, milliseconds.",
         "observability/slo.py"),
    Knob("TFDE_SLO_OBJECTIVE", "float", 0.99, (),
         "Attainment objective in (0, 1) for burn-rate math.",
         "observability/slo.py"),
    # --- inference --------------------------------------------------------
    Knob("TFDE_PREFIX_CACHE", "spec", None, ("off", "on", "<int bytes>"),
         "Serving prefix-KV cache default for every ContinuousBatcher: "
         "off (default), on (default budget), or an integer byte budget.",
         "inference/prefix_cache.py"),
    Knob("TFDE_PAGED_KV", "flag", False, (),
         "Paged KV serving: replace the dense per-row KV slabs with one "
         "block-granular pool shared by the prefix trie and active decode "
         "rows (inference/paged.py). Off (default) keeps the dense path "
         "byte-identical.",
         "inference/paged.py, inference/server.py"),
    Knob("TFDE_KV_BLOCK", "int", 16, (),
         "KV block size in tokens — the single source of truth for both "
         "the prefix trie's chunk length and the paged pool's block "
         "granularity. Any positive value works; 16 matches the trie's "
         "historical chunking.",
         "inference/paged.py, inference/prefix_cache.py"),
    Knob("TFDE_PAGED_PREFILL_CHUNK", "int", 64, (),
         "Token chunk width of the single paged prefill program; cold "
         "and warm admission feed prompts through it chunk-by-chunk so "
         "one static program covers every (prompt length, rows) shape "
         "(clamped to max_len at batcher construction).",
         "inference/paged.py, inference/server.py"),
    Knob("TFDE_KV_QUANT", "choice", "fp", ("fp", "int8"),
         "KV-cache storage format for every ContinuousBatcher: fp "
         "(default, byte-identical full precision) or int8 — quantized "
         "payload + per-(position, kv-head) fp32 scale sidecars in "
         "every cache layout (dense slab, paged pool, prefix trie), "
         "dequantized inside the attention program "
         "(ops/quant.kv_quantize). ~2x KV headroom at bf16, ~3.8x at "
         "fp32, same static program count.",
         "models/transformer.py, inference/server.py"),
    Knob("TFDE_KV_DEFRAG_THRESHOLD", "float", 0.5, (),
         "Paged-pool fragmentation ratio (holes / occupied span of live "
         "block ids) above which an admission stall triggers one bounded "
         "defrag pass (pool compaction + device permute + table/trie "
         "remap). 0 disables stall-triggered defrag.",
         "inference/server.py, inference/paged.py"),
    Knob("TFDE_ADMIT_", "spec", None, (),
         "Serving admission-control family prefix (see members below); "
         "all caps default off, so admission control is opt-in.",
         "inference/admission.py", prefix=True),
    Knob("TFDE_ADMIT_MAX_QUEUE", "int", 0, (),
         "Max QUEUED requests per batcher before submit() answers "
         "QueueFull/429 (0 = unlimited; active rows don't count).",
         "inference/admission.py"),
    Knob("TFDE_ADMIT_MAX_QUEUED_TOKENS", "int", 0, (),
         "Max queued output-token backlog per batcher before submit() "
         "answers QueueFull/429 (0 = unlimited).",
         "inference/admission.py"),
    Knob("TFDE_ADMIT_TTFT_DEADLINE_MS", "float", 0.0, (),
         "Default TTFT deadline applied to requests that don't bring "
         "their own: a request still queued past it is shed at dequeue "
         "instead of prefilled (0 = no deadline shedding).",
         "inference/admission.py"),
    Knob("TFDE_BROWNOUT_", "spec", None, (),
         "Router brownout family prefix (see members below).",
         "inference/router.py", prefix=True),
    Knob("TFDE_BROWNOUT_BURN", "float", 8.0, (),
         "Fast-window TTFT burn rate at which the router starts shedding "
         "best_effort traffic (0 = brownout off).",
         "inference/router.py"),
    Knob("TFDE_BROWNOUT_BURN_BATCH", "float", 16.0, (),
         "Fast-window TTFT burn rate at which the router also sheds "
         "batch traffic; interactive is never brownout-shed.",
         "inference/router.py"),
    Knob("TFDE_ADMIT_KV_HEADROOM", "int", 0, (),
         "Minimum KV headroom, in rows, admission requires: submit() "
         "answers QueueFull/429 with a kv payload when the capacity "
         "model's headroom_rows falls below it (0 = memory gate off).",
         "inference/admission.py"),
    Knob("TFDE_BOOT_", "spec", None, (),
         "Boot & readiness observability family prefix (see members "
         "below).",
         "observability/boot.py, inference/router.py", prefix=True),
    Knob("TFDE_BOOT_READY_REQUIRE", "flag", True, (),
         "Router readiness gate: place traffic only on replicas whose "
         "/load reports state 'ready' (a replica the router has never "
         "snapshotted fails open). 'off' restores pre-readiness "
         "placement on any live replica.",
         "inference/router.py"),
    Knob("TFDE_BOOT_READY_GRACE_S", "float", 120.0, (),
         "Seconds a never-ready (still booting) replica may push stale "
         "or report not-ready before staleness is allowed to declare it "
         "down; a booting replica mid-compile-storm is busy, not dead.",
         "inference/router.py"),
    Knob("TFDE_USAGE_LOG", "spec", None, ("off", "on", "<path>"),
         "Per-request usage metering JSONL: off (default), on (write "
         "model_dir/metrics/usage_<host>.jsonl on each ReplicaServer), "
         "or an explicit file path.",
         "observability/capacity.py"),
    Knob("TFDE_CAPACITY_", "spec", None, (),
         "KV-capacity observability family prefix (see members below).",
         "observability/capacity.py", prefix=True),
    Knob("TFDE_CAPACITY_BUDGET_BYTES", "int", 0, (),
         "KV memory budget the headroom model folds against (0 = derive "
         "capacity from the dense slab itself: headroom is the free "
         "rows and their cells).",
         "observability/capacity.py"),
    Knob("TFDE_CAPACITY_USAGE_LOG_BYTES", "int", 8388608, (),
         "Byte bound on one usage JSONL log; an append that would "
         "overflow it drops the oldest records so the newest half of "
         "the bound survives.",
         "observability/capacity.py"),
    # --- static analysis / gates -----------------------------------------
    Knob("TFDE_HLOLINT", "flag", False, (),
         "Arm the lowered-program linter's collection seam: programs "
         "registered with memwatch/recompile are also offered to "
         "analysis.hlolint for interrogation (tools/lintgate.py sets it).",
         "tfde_tpu/analysis/hlolint.py"),
    Knob("TFDE_MEMGATE_INJECT", "flag", False, (),
         "Memgate self-test: seed a deliberate extra compile so the gate "
         "must fail (tests/test_recompile.py uses it to prove the gate "
         "bites).",
         "tools/memgate.py"),
    Knob("TFDE_LINTGATE_INJECT", "flag", False, (),
         "Lintgate self-test: lint two seeded-broken programs (a stray "
         "host callback, a dropped donation) so the gate must fail.",
         "tools/lintgate.py"),
)


def is_registered(name: str) -> bool:
    """True when `name` is a registered knob or a member of a registered
    prefix family (``TFDE_RETRY_FOO`` matches the ``TFDE_RETRY_`` family)."""
    if name in REGISTRY:
        return True
    return any(k.prefix and name.startswith(k.name) and name != k.name
               for k in REGISTRY.values())


def canonical_names() -> Tuple[str, ...]:
    """All registered knob names (families listed by their prefix)."""
    return tuple(sorted(REGISTRY))


def _warn_once(name: str, raw: str, why: str, fallback: Any) -> None:
    key = (name, raw, why)
    with _warn_lock:
        if key in _warned:
            return
        _warned.add(key)
    warnings.warn(
        f"{name}={raw!r} {why}; falling back to {fallback!r}",
        stacklevel=3,
    )


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Free-form string knob (paths, URLs). Empty string counts as unset."""
    knob = REGISTRY.get(name)
    if default is None and knob is not None:
        default = knob.default
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return raw


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """Integer knob; a non-integer value warns once and yields `default`."""
    knob = REGISTRY.get(name)
    if default is None and knob is not None:
        default = knob.default
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    try:
        return int(raw)
    except ValueError:
        _warn_once(name, raw, "is not an integer", default)
        return default


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    """Float knob; a non-numeric value warns once and yields `default`."""
    knob = REGISTRY.get(name)
    if default is None and knob is not None:
        default = knob.default
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    try:
        return float(raw)
    except ValueError:
        _warn_once(name, raw, "is not a number", default)
        return default


def env_choice(name: str, default: Optional[str] = None,
               choices: Tuple[str, ...] = ()) -> Optional[str]:
    """Enumerated knob; an unrecognized spelling warns once and yields the
    default. Matching is case-insensitive on the stripped value."""
    knob = REGISTRY.get(name)
    if knob is not None:
        default = knob.default if default is None else default
        choices = choices or knob.choices
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    v = raw.strip().lower()
    if v in choices:
        return v
    _warn_once(name, raw, f"is not one of {choices}", default)
    return default


_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("", "0", "false", "no", "off")


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean-ish knob; unrecognized spellings warn once and yield the
    default."""
    knob = REGISTRY.get(name)
    if knob is not None and knob.default is not None:
        default = bool(knob.default)
    raw = os.environ.get(name)
    if raw is None:
        return default
    v = raw.strip().lower()
    if v in _TRUTHY:
        return True
    if v in _FALSY:
        return False
    _warn_once(name, raw, "is not a recognized on/off spelling", default)
    return default


_unknown_warned = False


def warn_unknown_env() -> Tuple[str, ...]:
    """Warn once per process about ``TFDE_*`` names in the environment that
    no knob registers — the ``TFDE_GRAD_TRANSPRT=int8`` typo class, which
    otherwise silently runs fp32. Returns the offending names (for tests).

    Called from ``tfde_tpu/__init__.py`` so any import of the package
    surfaces the typo immediately.
    """
    global _unknown_warned
    unknown = tuple(sorted(
        n for n in os.environ
        if n.startswith("TFDE_") and not is_registered(n)
    ))
    if unknown and not _unknown_warned:
        _unknown_warned = True
        known = ", ".join(n for n in canonical_names())
        warnings.warn(
            f"unrecognized TFDE_* environment variable(s): "
            f"{', '.join(unknown)} — not read by any registered knob "
            f"(registered: {known})",
            stacklevel=2,
        )
    return unknown


def table_md() -> str:
    """Markdown knob table for the README (generated, not hand-kept)."""
    lines = [
        "| Knob | Values | Default | Consumed by | Purpose |",
        "| --- | --- | --- | --- | --- |",
    ]
    for name in sorted(REGISTRY):
        k = REGISTRY[name]
        if k.prefix:
            continue  # members are listed individually
        vals = ", ".join(f"`{c}`" for c in k.choices) if k.choices else f"({k.kind})"
        default = "unset" if k.default is None else f"`{k.default}`"
        lines.append(
            f"| `{k.name}` | {vals} | {default} | `{k.owner}` | {k.doc} |")
    return "\n".join(lines)
