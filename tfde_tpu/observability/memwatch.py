"""Measured memory ledger: what each compiled program actually costs.

The framework's memory story used to be analytic — `opt/state_bytes` was
computed from leaf shapes, never from what XLA allocates. At production
scale peak-HBM is a budget tracked per program, not guessed, so this
module interrogates every registered executable and publishes, per named
program (train step, prefill wave, decode scan, ZeRO update)::

    mem/<name>/peak_bytes            arg + out + temp + code - aliased
    mem/<name>/argument_bytes        input buffers
    mem/<name>/output_bytes          result buffers
    mem/<name>/temp_bytes            XLA scratch (0 under estimate mode)
    mem/<name>/generated_code_bytes  executable size (0 under estimate)
    mem/<name>/measured              1 = XLA memory_analysis, 0 = estimate

plus live device-buffer totals (``jax.live_arrays()``) sampled on the
existing metrics cadence — a registry collector refreshes ``mem/live/*``
at the top of every `snapshot()`, so the numbers ride /metrics, the JSONL
log, the TB bridge, and the cross-host push without a new loop.

Modes (``TFDE_MEMWATCH``):

- ``off``   — no ledger, no sampler; registration is a no-op.
- ``on``    — the default: **estimate** mode. Argument/output bytes come
  from the avals (one `jax.eval_shape` trace, no XLA compile), aliasing
  from the donated args the call site names, temp/code are 0. Free of
  compile-time cost, exact for the dominant arg/output terms.
- ``full``  — AOT-lower and compile each registered program
  (`jax.stages.Compiled.memory_analysis()` / `cost_analysis()`) for
  XLA-measured temp/code/alias bytes. Costs one extra compile per
  program; the mode for a TPU capture, not the default. On backends
  whose memory_analysis is degenerate (CPU reports temp = code = 0) the
  estimate fills in aliasing, so tier-1 exercises the full path.

Ledger interrogation runs under `recompile.suppress()` — measuring a
program must never read as a recompile of it.

`device_bytes(tree)` is the measured counterpart of the ZeRO layer's
analytic accounting: per-device bytes actually resident for a pytree of
committed arrays, from each leaf's addressable shards (max over devices;
replicated leaves count fully on every device).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import os
import threading
import time
from typing import Dict, Optional

from tfde_tpu.observability import metrics

log = logging.getLogger(__name__)

ENV_MEMWATCH = "TFDE_MEMWATCH"
MODES = ("off", "on", "full")
TOP_K = 8
#: min seconds between debug/memwatch.json rewrites when armed
DUMP_INTERVAL_S = 5.0

_FIELDS = ("peak_bytes", "argument_bytes", "output_bytes", "temp_bytes",
           "generated_code_bytes")


def resolve(value: Optional[str] = None) -> str:
    """Normalize the TFDE_MEMWATCH knob to one of MODES (default 'on')."""
    v = (value if value is not None
         else os.environ.get(ENV_MEMWATCH, "on")).strip().lower()
    if v in ("", "1", "true", "yes", "on"):
        return "on"
    if v in ("0", "false", "no", "off"):
        return "off"
    if v in ("full", "measured"):
        return "full"
    log.warning("%s=%r not understood; using 'on'", ENV_MEMWATCH, v)
    return "on"


def enabled() -> bool:
    return resolve() != "off"


@dataclasses.dataclass
class ProgramMemory:
    """One registered program's memory interrogation result."""

    name: str
    peak_bytes: int = 0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    alias_bytes: int = 0
    flops: float = 0.0
    measured: bool = False  # True = XLA memory_analysis was authoritative

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _leaf_bytes(leaf) -> int:
    """Bytes of one pytree leaf: works for committed arrays, numpy, and
    aval-ish objects (ShapeDtypeStruct); non-array leaves count zero."""
    try:
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            return int(nb)
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return 0
        import numpy as np

        n = 1
        for d in shape:
            n *= int(d)
        return int(n * np.dtype(dtype).itemsize)
    except Exception:  # noqa: BLE001 — a weird leaf must not sink the ledger
        return 0


def _tree_bytes(tree) -> int:
    if tree is None:
        return 0
    import jax

    return sum(_leaf_bytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def device_bytes(tree) -> int:
    """MEASURED per-device bytes for a pytree of committed arrays: sum of
    each device's actually-allocated shard bytes, max over devices.
    Replicated leaves count fully on every device (each holds a copy);
    abstract / host leaves count as replicated. The cross-check against
    `parallel/zero.state_bytes`'s analytic number."""
    import jax

    dev_totals: Dict = collections.defaultdict(int)
    replicated = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            try:
                for sh in shards:
                    key = getattr(sh.device, "id", sh.device)
                    dev_totals[key] += int(sh.data.nbytes)
                continue
            except Exception:  # noqa: BLE001 — deleted/abstract mid-walk
                pass
        replicated += _leaf_bytes(leaf)
    if not dev_totals:
        return replicated
    return max(dev_totals.values()) + replicated


def _cost_flops(compiled) -> float:
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001
        return 0.0
    try:
        return float(cost.get("flops", 0.0))
    except Exception:  # noqa: BLE001
        return 0.0


class MemoryLedger:
    """The per-process program registry. Use the module-level helpers
    (`register`, `sample_live`, ...) unless a test needs isolation."""

    def __init__(self, registry: Optional[metrics.Registry] = None):
        self._reg = registry or metrics.default_registry()
        self._lock = threading.Lock()
        self._programs: Dict[str, ProgramMemory] = {}
        self._warned: set = set()
        self._dump_path: Optional[str] = None
        self._last_dump = 0.0
        self._collector_installed = False

    # -- registration --------------------------------------------------------
    def register(self, name: str, fn=None, args=(), kwargs=None,
                 donated=None, compiled=None,
                 mode: Optional[str] = None) -> Optional[ProgramMemory]:
        """Interrogate one program and publish its `mem/<name>/*` gauges.

        Give either a `compiled` (`jax.stages.Compiled`) or the jitted
        `fn` plus the call's `args`/`kwargs`; `donated` names the
        pytree(s) the program donates (aliased buffers — subtracted from
        the peak estimate). Returns None when the ledger is off or the
        interrogation failed (logged once per name, never raised: the
        ledger must not take the caller down)."""
        mode = resolve(mode)
        if mode == "off":
            return None
        try:
            pm = self._interrogate(name, fn, args, kwargs or {}, donated,
                                   compiled, mode)
        except Exception as e:  # noqa: BLE001 — observability-only path
            if name not in self._warned:
                self._warned.add(name)
                log.warning("memwatch: could not register %s: %s", name, e)
            return None
        with self._lock:
            self._programs[name] = pm
        self._publish(pm)
        return pm

    def _interrogate(self, name, fn, args, kwargs, donated, compiled,
                     mode) -> ProgramMemory:
        import jax

        from tfde_tpu.observability import recompile

        with recompile.suppress():
            if (compiled is None and mode == "full"
                    and hasattr(fn, "lower")):
                compiled = fn.lower(*args, **kwargs).compile()
            stats = None
            if compiled is not None:
                stats = compiled.memory_analysis()
            arg_bytes = _tree_bytes((args, kwargs))
            alias_bytes = _tree_bytes(donated)
            if stats is not None and stats.output_size_in_bytes:
                out_bytes = int(stats.output_size_in_bytes)
                arg_bytes = int(stats.argument_size_in_bytes) or arg_bytes
            else:
                out_bytes = _tree_bytes(jax.eval_shape(fn, *args, **kwargs))
        temp = int(getattr(stats, "temp_size_in_bytes", 0) or 0)
        code = int(getattr(stats, "generated_code_size_in_bytes", 0) or 0)
        xla_alias = int(getattr(stats, "alias_size_in_bytes", 0) or 0)
        # CPU's memory_analysis zeroes temp/code/alias — fall back to the
        # donated-aval estimate for aliasing so the peak stays honest
        measured = stats is not None and (temp or code or xla_alias)
        alias = xla_alias if measured else alias_bytes
        peak = max(arg_bytes, out_bytes,
                   arg_bytes + out_bytes + temp + code - alias)
        return ProgramMemory(
            name=name, peak_bytes=int(peak), argument_bytes=int(arg_bytes),
            output_bytes=int(out_bytes), temp_bytes=temp,
            generated_code_bytes=code, alias_bytes=int(alias),
            flops=_cost_flops(compiled) if compiled is not None else 0.0,
            measured=bool(measured),
        )

    def _publish(self, pm: ProgramMemory) -> None:
        for field in _FIELDS:
            self._reg.gauge(f"mem/{pm.name}/{field}").set(
                getattr(pm, field))
        self._reg.gauge(f"mem/{pm.name}/measured").set(
            1.0 if pm.measured else 0.0)

    def programs(self) -> Dict[str, ProgramMemory]:
        with self._lock:
            return dict(self._programs)

    def get(self, name: str) -> Optional[ProgramMemory]:
        with self._lock:
            return self._programs.get(name)

    # -- live device buffers -------------------------------------------------
    def sample_live(self, top_k: int = TOP_K) -> dict:
        """One `jax.live_arrays()` sweep: total bytes, buffer count, and
        the top-K largest live buffers (bytes/shape/dtype)."""
        import jax

        total = 0
        rows = []
        for arr in jax.live_arrays():
            try:
                nb = int(arr.nbytes)
                shape = tuple(arr.shape)
                dtype = str(arr.dtype)
            except Exception:  # noqa: BLE001 — deleted mid-sweep
                continue
            total += nb
            rows.append((nb, shape, dtype))
        rows.sort(key=lambda r: -r[0])
        return {
            "ts": time.time(),
            "bytes": total,
            "buffers": len(rows),
            "top": [{"bytes": nb, "shape": list(shape), "dtype": dtype}
                    for nb, shape, dtype in rows[:top_k]],
        }

    def publish_live(self, top_k: int = TOP_K) -> dict:
        """sample_live + publish `mem/live/*` gauges (+ the armed JSON
        side-file for obs_dump --mem's top-K table)."""
        sample = self.sample_live(top_k)
        self._reg.gauge("mem/live/bytes").set(sample["bytes"])
        self._reg.gauge("mem/live/buffers").set(sample["buffers"])
        self._reg.gauge("mem/live/largest_bytes").set(
            sample["top"][0]["bytes"] if sample["top"] else 0)
        self._maybe_dump(sample)
        return sample

    def _collect(self) -> None:
        """The Registry collector: refresh mem/live/* on every snapshot —
        'sampled on the existing metrics cadence'."""
        self.publish_live()

    def install_collector(self) -> None:
        """Hook the live sampler into the registry's snapshot cadence
        (idempotent)."""
        with self._lock:
            if self._collector_installed:
                return
            self._collector_installed = True
        self._reg.add_collector(self._collect)

    # -- armed side-file (obs_dump --mem) ------------------------------------
    def arm(self, model_dir: str) -> None:
        """Write ``<model_dir>/debug/memwatch.json`` (programs + latest
        live sample + top-K buffers) on the sampling cadence, throttled
        to one rewrite per DUMP_INTERVAL_S."""
        from tfde_tpu.utils import fs

        d = fs.join(model_dir, "debug")
        fs.makedirs(d)
        self._dump_path = fs.join(d, "memwatch.json")
        self._last_dump = 0.0

    def _maybe_dump(self, sample: dict) -> None:
        path = self._dump_path
        if path is None or time.time() - self._last_dump < DUMP_INTERVAL_S:
            return
        self._last_dump = time.time()
        try:
            from tfde_tpu.utils import fs

            body = {
                "live": sample,
                "programs": {n: p.as_dict()
                             for n, p in self.programs().items()},
            }
            fs.write_bytes(path, json.dumps(body, sort_keys=True).encode())
        except Exception as e:  # noqa: BLE001 — dump is best-effort
            log.debug("memwatch dump failed: %s", e)

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self._warned.clear()
            self._dump_path = None
        self._reg.reset("mem/")


_default = MemoryLedger()


def default_ledger() -> MemoryLedger:
    return _default


def register(name: str, fn=None, args=(), kwargs=None, donated=None,
             compiled=None, mode: Optional[str] = None):
    return _default.register(name, fn=fn, args=args, kwargs=kwargs,
                             donated=donated, compiled=compiled, mode=mode)


def sample_live(top_k: int = TOP_K) -> dict:
    return _default.sample_live(top_k)


def publish_live(top_k: int = TOP_K) -> dict:
    return _default.publish_live(top_k)


def install_collector() -> None:
    _default.install_collector()


def arm(model_dir: str) -> None:
    _default.arm(model_dir)


def programs() -> Dict[str, ProgramMemory]:
    return _default.programs()


def reset() -> None:
    _default.reset()
