"""KV-capacity observability: occupancy ledger, headroom model, usage meter.

ROADMAP item 1 claims paged-KV will unlock 4-8x serving concurrency by
eliminating pad-ladder waste — but nothing measured that waste, so the
win could be neither sized in advance nor proven after. This module is
the capacity half of the observability stack, three legs:

- **CapacityLedger** — the occupancy picture, one subclass per cache
  layout. The dense base reports the per-row slab: the batcher feeds
  committed cells (the true per-row index) per decode round and
  pad-ladder allocation per admission wave; the ledger publishes the
  ``kv/{allocated_bytes,used_bytes,waste_frac,rows_active,rows_free}``
  gauges plus per-bucket pad-waste counters and a unit-interval waste
  histogram. ``kv/used_bytes`` is exact against
  `memwatch.device_bytes` over the live cache cells (tests pin 20%),
  because the per-cell cost is derived from the slab's own leaf bytes.
  `PagedCapacityLedger` re-bases the same gauges on the block pool
  (``TFDE_PAGED_KV``): allocated bytes are the blocks actually held,
  so ``kv/waste_frac`` collapses to intra-block slack — the measured
  statement of what paging reclaimed — and the ``kv/pool_blocks_*``
  gauges split the pool into active/trie/free.
- **CapacityModel** — headroom: memory budget (``TFDE_CAPACITY_BUDGET_
  BYTES``, 0 = slab-derived) folded with the measured per-row cost into
  ``kv/headroom_rows`` / ``kv/headroom_tokens``. `ReplicaServer /load`
  and the Router's saturation gate consume these (behind
  ``TFDE_ADMIT_KV_HEADROOM``) so admission can reject on *memory*
  before queue depth collapses.
- **UsageMeter** — per-request prompt tokens, generated tokens, and
  KV-residency (token·seconds of slab occupancy, the capacity-cost unit
  the Gemma-on-TPU serving study sizes fleets by), stamped with the
  priority class, counted under ``usage/*`` and appended to a bounded
  JSONL log (``TFDE_USAGE_LOG``) — the metering seam multi-tenant
  adapters will key by tenant id.

Thread-safety: the ledger and meter are written from the batcher's step
loop under `ReplicaServer.lock` but *read* from HTTP handler threads
(`/load`'s kv block, tests), so each carries its own lock and is listed
in `tools/tfdelint.py` LOCKED_CLASSES — every shared-state access holds
it (the PR 14 guarded-attrs rule).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional

from tfde_tpu import knobs
from tfde_tpu.observability import metrics

#: cache-pytree bookkeeping leaves (prefix_cache.INDEX_LEAVES, and the
#: per-call `feed_pad` of attention='eva') — never K/V bytes; named here
#: too so observability never imports inference
_INDEX_LEAVES = ("cache_index", "position_index", "feed_pad")

#: unit-interval buckets for pad-waste fractions — the default registry
#: ladder is a seconds scale and would collapse every observation into
#: its first bucket
WASTE_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9,
                 0.95, 1.0)

DEFAULT_USAGE_LOG_BYTES = 8 * 1024 * 1024


def _is_index_path(path) -> bool:
    return str(getattr(path[-1], "key", path[-1])) in _INDEX_LEAVES


def kv_slab_bytes(cache) -> int:
    """Total K/V bytes of a dense batcher cache (index leaves excluded):
    the ledger's allocated-bytes baseline AND the denominator of its
    per-cell cost model."""
    import jax

    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if _is_index_path(path):
            continue
        total += int(leaf.nbytes)
    return total


def _kv_layer_cells(cache) -> list:
    """Cells a row holds in each layer that caches keys by position, in
    the tree's order: the second axis of every `cached_key` leaf."""
    import jax

    return [int(leaf.shape[1]) for path, leaf in
            jax.tree_util.tree_leaves_with_path(cache)
            if str(getattr(path[-1], "key", path[-1])) == "cached_key"]


def _leaves_named(cache, name: str) -> int:
    """How many leaves of a cache tree are called `name`: one a layer that
    keeps such a leaf."""
    import jax

    return sum(1 for path, _ in jax.tree_util.tree_leaves_with_path(cache)
               if str(getattr(path[-1], "key", path[-1])) == name)


def _latent_layers(cache) -> int:
    """Layers of a cache that keep one latent cell per position (models/
    transformer.py `LatentAttention`): its `cached_latent` leaves."""
    return _leaves_named(cache, "cached_latent")


def kv_dtype_census(cache) -> dict:
    """Dtype split of a KV cache tree (index leaves and block tables
    excluded): payload vs scale-sidecar bytes, the payload leaf dtype,
    and the fp32-equivalent payload cost — what the same cells would
    occupy unquantized at fp32 (the quantized-vs-fp delta obs_dump
    reports; for a bf16 model halve it mentally). Scale leaves
    are the ``*_scale`` sidecars the int8 KV cache rides
    (models/transformer.py); an fp cache has none, so its split is all
    payload and ``kv_dtype`` names the storage float type."""
    import jax

    payload = scale = payload_elems = 0
    dtype = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if _is_index_path(path):
            continue
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "block_table":
            continue
        if name.endswith("_scale"):
            scale += int(leaf.nbytes)
        else:
            payload += int(leaf.nbytes)
            payload_elems += int(leaf.size)
            dtype = str(leaf.dtype)
            bits = int(leaf.dtype.itemsize) * 8
    return {
        "kv_dtype": dtype or "none",
        "kv_quant_bits": bits if dtype else 0,
        "kv_payload_bytes": payload,
        "kv_scale_bytes": scale,
        "kv_fp32_equiv_bytes": payload_elems * 4,
    }


class CapacityLedger:
    """Dense-slab KV occupancy and pad-ladder waste accounting.

    One ledger per batcher cache. `observe` is fed the host-side
    committed counts every decode round / stats publish;
    `note_admission` is fed every admitted request's (bucket, true
    prompt length) at wave time. Listed in tools/tfdelint.py
    LOCKED_CLASSES: all shared state under `_lock`.
    """

    def __init__(self, batch_size: int, cells_per_row: int,
                 slab_bytes: int,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        if batch_size < 1 or cells_per_row < 1:
            raise ValueError(
                f"need batch_size/cells_per_row >= 1, got "
                f"{batch_size}/{cells_per_row}"
            )
        self._lock = threading.Lock()
        self._b = int(batch_size)
        self._cells = int(cells_per_row)
        self._slab_bytes = int(slab_bytes)
        #: dtype split of the slab (kv_dtype_census) — prices the
        #: quantized-vs-fp delta; empty when the builder predates it
        self._census = dict(census or {})
        #: measured per-cell cost: the slab's own bytes over its cells,
        #: so used_bytes sums exactly to the slab when every row is full
        self._cell_bytes = self._slab_bytes / float(self._b * self._cells)
        self._reg = registry or metrics.default_registry()
        self._used_cells = 0
        self._rows_active = 0
        self._pad_alloc_tokens = 0
        self._pad_waste_tokens = 0
        self._bucket_alloc: Dict[int, int] = {}
        self._bucket_waste: Dict[int, int] = {}

    @classmethod
    def from_cache(cls, cache, batch_size: int, cells_per_row: int,
                   registry: Optional[metrics.Registry] = None,
                   model=None, params=None) -> "CapacityLedger":
        """Build a ledger from a freshly-initialized dense slab. The
        served `model`, where given, says which layout the slab has:
        attention='eva' gets the ledger of windows and summaries, a cache
        in which some layer holds fewer cells a row than `cells_per_row`
        (a window layer's ring) the ledger that counts a layer's cells at
        a time, a cache of latent cells (`cached_latent` leaves beside
        their `cached_rope_key`: a cell per position like a K/V slab's,
        `latent + rope` values wide with no head axis) the ledger that
        counts them by layer, a model
        with state-space layers (whose rows cost the same whatever their
        length) or with experts routed without a capacity the hybrid
        one, which reads the experts' bytes off `params`."""
        if getattr(model, "attention", "full") == "eva":
            return EvaCapacityLedger.of_model(cache, batch_size, model,
                                              registry=registry)
        if any(n < cells_per_row for n in _kv_layer_cells(cache)):
            # some layer keeps a ring shorter than the row: the cache's
            # own leaves say which, and how long
            return RingCapacityLedger.of_model(
                cache, batch_size, cells_per_row, params, registry=registry)
        if _latent_layers(cache):
            return LatentCapacityLedger.of_model(
                cache, batch_size, cells_per_row, params, registry=registry)
        if "gated_delta" in (getattr(model, "mixers", None) or ()):
            return DeltaCapacityLedger.of_model(
                cache, batch_size, cells_per_row, params,
                chunk=model.gdn.chunk, registry=registry)
        if "mamba" in (getattr(model, "mixers", None) or ()) or (
                getattr(model, "num_experts", 0)
                and getattr(model, "moe_capacity_factor", 1.0) is None):
            return HybridCapacityLedger.of_model(
                cache, batch_size, cells_per_row, params, registry=registry)
        return cls(batch_size, cells_per_row, kv_slab_bytes(cache),
                   registry=registry, census=kv_dtype_census(cache))

    # -- read surface --------------------------------------------------------
    @property
    def cell_bytes(self) -> float:
        return self._cell_bytes

    @property
    def row_bytes(self) -> float:
        """Per-row slab cost — the headroom model's admission unit."""
        return self._cell_bytes * self._cells

    @property
    def slab_bytes(self) -> int:
        return self._slab_bytes

    @property
    def cells_per_row(self) -> int:
        return self._cells

    @property
    def census(self) -> dict:
        """The slab/pool dtype split (kv_dtype_census); {} when unknown."""
        return dict(self._census)

    # -- what a row of `n` committed tokens holds and reads ------------------
    def row_cells(self, n: int) -> int:
        """Cells of a row's slab that hold live state once it has
        committed `n` tokens: one per token here."""
        return int(n)

    def read_cells(self, n: int) -> int:
        """Cells one decode tick of such a row cannot avoid reading:
        every committed one here."""
        return int(n)

    # -- what the layout adds to the batcher's account ------------------------
    @property
    def counters(self) -> dict:
        """Counters of this layout's own events, which the batcher hands
        on in `stats()`: a slab of one cell per position has none."""
        return {}

    def note_commit(self, before: int, after: int,
                    decoding: bool = True) -> None:
        """A row went from `before` to `after` committed tokens, in a
        decode scan or (`decoding` false) by its prefill."""

    def note_scan(self, committed, depth: int) -> None:
        """A decode scan of `depth` ticks starts over active rows at
        these `committed` counts."""

    def note_routed(self, routed) -> None:
        """What the expert layers of one program counted on the device
        ([pairs, pairs held, experts touched, busiest expert's pairs],
        summed over layers and ticks): nothing to a layout without
        experts."""

    def scan_least_bytes(self, param_bytes: int, read_bytes: int,
                         depth: int, routed=None) -> int:
        """Bytes `depth` decode ticks cannot avoid reading: every
        parameter and the rows' cells (`read_bytes`, a tick's), a tick."""
        return depth * (int(param_bytes) + int(read_bytes))

    def _publish_census(self) -> dict:
        """Gauge + stats-dict surface of the dtype split: obs_dump's
        --capacity quantized-vs-fp columns read these (the dtype string
        itself rides the /load kv dict; kv/quant_bits is its numeric
        twin for metrics-snapshot readers)."""
        if not self._census:
            return {}
        g = self._reg.gauge
        g("kv/quant_bits").set(self._census.get("kv_quant_bits", 0))
        g("kv/payload_bytes").set(self._census.get("kv_payload_bytes", 0))
        g("kv/scale_bytes").set(self._census.get("kv_scale_bytes", 0))
        g("kv/fp32_equiv_bytes").set(
            self._census.get("kv_fp32_equiv_bytes", 0))
        return dict(self._census)

    # -- the per-round report ------------------------------------------------
    def observe(self, committed, req) -> dict:
        """Fold one host-bookkeeping snapshot (`committed` [B] counts,
        `req` [B] request-id-or-None) into the occupancy gauges; returns
        the stats dict (`/load`'s kv block)."""
        used = 0
        active = 0
        for r in range(self._b):
            if req[r] is not None:
                active += 1
                used += self.row_cells(int(committed[r]))
        with self._lock:
            self._used_cells = used
            self._rows_active = active
        used_bytes = used * self._cell_bytes
        waste = 1.0 - used / float(self._b * self._cells)
        g = self._reg.gauge
        g("kv/allocated_bytes").set(self._slab_bytes)
        g("kv/used_bytes").set(used_bytes)
        g("kv/waste_frac").set(waste)
        g("kv/rows_active").set(active)
        g("kv/rows_free").set(self._b - active)
        out = {
            "allocated_bytes": self._slab_bytes,
            "used_bytes": used_bytes,
            "used_cells": used,
            "waste_frac": waste,
            "rows_active": active,
            "rows_free": self._b - active,
        }
        out.update(self._publish_census())
        return out

    # -- the per-wave report -------------------------------------------------
    def note_admission(self, kind: str, bucket: int, used_tokens: int
                       ) -> None:
        """One admitted request's pad-ladder cost: `bucket` cells were
        computed/written (the prefill program's shape), `used_tokens` of
        them are real prompt (or suffix) — the rest is the pad waste the
        paged-KV refactor reclaims. Counted per bucket so obs_dump can
        name the worst pad-ladder cell."""
        bucket = int(bucket)
        used = min(int(used_tokens), bucket)
        waste = bucket - used
        with self._lock:
            self._pad_alloc_tokens += bucket
            self._pad_waste_tokens += waste
            self._bucket_alloc[bucket] = (
                self._bucket_alloc.get(bucket, 0) + bucket)
            self._bucket_waste[bucket] = (
                self._bucket_waste.get(bucket, 0) + waste)
        c = self._reg.counter
        c("kv/pad_alloc_tokens").incr(bucket)
        if waste:
            c("kv/pad_waste_tokens").incr(waste)
        c(f"kv/pad_alloc_tokens/bucket_{bucket}").incr(bucket)
        c(f"kv/pad_waste_tokens/bucket_{bucket}").incr(waste)
        self._reg.histogram(
            "kv/pad_waste_frac", buckets=WASTE_BUCKETS
        ).observe(waste / bucket if bucket else 0.0)

    def pad_stats(self) -> dict:
        """Cumulative pad-ladder accounting (tests + obs_dump)."""
        with self._lock:
            return {
                "pad_alloc_tokens": self._pad_alloc_tokens,
                "pad_waste_tokens": self._pad_waste_tokens,
                "per_bucket": {
                    b: {"alloc": self._bucket_alloc[b],
                        "waste": self._bucket_waste.get(b, 0)}
                    for b in sorted(self._bucket_alloc)
                },
            }


class EvaCapacityLedger(CapacityLedger):
    """Occupancy of the attention='eva' layout (models/transformer.py
    `_eva_attention`): a row's slab is one window buffer of W positions
    and a table of one summary per chunk of C positions, and a cell of
    either kind is the same bytes (a key and a value of every head, in
    every layer), so both count in one unit. A row that has committed
    `n` tokens holds n mod W live window cells (the window is handed
    over at every multiple of W) and n // C summaries; a decode tick
    attends to the live window cells and to the summaries of the windows
    already closed, (n // W) W / C of them: the summaries of the window
    in progress are written and not yet read.

    `counters` (EVA_KEYS): chunk summaries written (prefill and decode,
    one per chunk and row, not per layer), windows handed over in
    decode, and per scan, depth x what its active rows attend to at the
    scan's start: live window positions and visible summaries."""

    EVA_KEYS = ("eva_summaries_written", "eva_window_turns",
                "eva_window_cells_read", "eva_summary_cells_read")

    def __init__(self, batch_size: int, window_cells: int,
                 summary_cells: int, slab_bytes: int, window: int,
                 chunk: int, registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        super().__init__(batch_size, window_cells + summary_cells,
                         slab_bytes, registry=registry, census=census)
        self._window = int(window)
        self._chunk = int(chunk)
        self._counters = dict.fromkeys(self.EVA_KEYS, 0)

    @classmethod
    def of_model(cls, cache, batch_size: int, model,
                 registry: Optional[metrics.Registry] = None
                 ) -> "EvaCapacityLedger":
        """From a freshly-initialized batch cache of `model`: the two
        tables' lengths are read off one layer's leaves, window and
        chunk off the model's fields."""
        import jax

        lengths = {str(getattr(path[-1], "key", path[-1])): leaf.shape[1]
                   for path, leaf in jax.tree_util.tree_leaves_with_path(
                       cache) if leaf.ndim > 1}
        return cls(batch_size, lengths["eva_window_key"],
                   lengths["eva_summary_key"], kv_slab_bytes(cache),
                   model.eva_window, model.eva_chunk, registry=registry,
                   census=kv_dtype_census(cache))

    def attended(self, n: int) -> tuple:
        """(live window cells, visible summaries) of a row at `n`."""
        n = int(n)
        return (n % self._window,
                n // self._window * (self._window // self._chunk))

    def row_cells(self, n: int) -> int:
        return int(n) % self._window + int(n) // self._chunk

    def read_cells(self, n: int) -> int:
        return sum(self.attended(n))

    @property
    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def note_commit(self, before: int, after: int,
                    decoding: bool = True) -> None:
        """The chunk summaries that completed, and when `decoding` the
        windows that were handed over (a prefill keeps the window its
        true length ends in and hands none over)."""
        with self._lock:
            self._counters["eva_summaries_written"] += (
                after // self._chunk - before // self._chunk)
            if decoding:
                self._counters["eva_window_turns"] += (
                    after // self._window - before // self._window)

    def note_scan(self, committed, depth: int) -> None:
        local = remote = 0
        for n in committed:
            live, visible = self.attended(n)
            local += live
            remote += visible
        with self._lock:
            self._counters["eva_window_cells_read"] += depth * local
            self._counters["eva_summary_cells_read"] += depth * remote


class HybridCapacityLedger(CapacityLedger):
    """Occupancy of a cache in which some layers keep a running state per
    row (models/transformer.py `Mamba2Mixer`: `ssm_state`, `conv_tail`;
    `GatedDeltaMixer`: `delta_state`, `conv_tail`; the same bytes whatever
    the row's length) beside layers that keep a
    K/V cell per position, and the account of expert layers that route
    without a capacity (models/moe.py).

    The unit is one position's K/V over the attention layers; a row's
    state counts as the `state_cells` such cells its bytes come to, live
    from admission on. A decode tick reads every committed K/V cell and
    reads and writes the state: `read_cells(n)` = n + 2 x state cells.
    Of the parameters a tick cannot avoid those outside the experts and,
    of the held experts, the ones that received a pair that tick
    (`scan_least_bytes`, from the device's own count).

    `counters` (HYBRID_KEYS): per scan, depth x (twice the active rows'
    state bytes; their committed K/V cells), and what the expert layers
    counted of real tokens in prefill waves and scans alike, summed over
    layers and ticks: pairs routed, pairs whose expert is held, held
    experts with at least one pair, the busiest held expert's pairs; and,
    of every token they were handed, the rows of the hidden width they
    copied into sorted order and fetched back from it, and their passes
    over the held experts' weights (a call's blocks: one a layer for a
    tick, more for a wave longer than `moe.token_block` gives a block)."""

    HYBRID_KEYS = ("ssm_state_bytes_touched", "kv_cells_read", "moe_pairs",
                   "moe_pairs_held", "moe_experts_touched",
                   "moe_pairs_busiest", "moe_rows_moved",
                   "moe_weight_passes")
    _STATE_LEAVES = ("ssm_state", "delta_state", "conv_tail")

    def __init__(self, batch_size: int, positions: int, slab_bytes: int,
                 state_row_bytes: int, expert_bytes: int,
                 expert_slots: int,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        state_total = int(state_row_bytes) * int(batch_size)
        #: all position-indexed layers' bytes of one position of one row
        per_position = self._position_bytes = (
            int(slab_bytes) - state_total) / float(batch_size * positions)
        # no attention layer: the one cell of a row is its state
        self._state_cells = (int(round(state_row_bytes / per_position))
                             if per_position else 1)
        self._positions = int(positions) if per_position else 0
        super().__init__(batch_size, self._state_cells + self._positions,
                         slab_bytes, registry=registry, census=census)
        self._state_row_bytes = int(state_row_bytes)
        self._expert_bytes = int(expert_bytes)
        #: bytes of one expert of one layer
        self._slot_bytes = (int(expert_bytes) / expert_slots
                            if expert_slots else 0.0)
        self._counters = dict.fromkeys(self.HYBRID_KEYS, 0)

    @classmethod
    def of_model(cls, cache, batch_size: int, positions: int, params,
                 registry: Optional[metrics.Registry] = None
                 ) -> "HybridCapacityLedger":
        """From a freshly-initialized batch cache and the served
        parameters: the state's bytes are those of the leaves named
        `ssm_state` / `delta_state` / `conv_tail`, the experts' those of
        the leaves
        `experts_*` (their first axis counts the experts held)."""
        return cls(batch_size, positions, kv_slab_bytes(cache),
                   *cls._state_and_experts(cache, batch_size, params),
                   registry=registry, census=kv_dtype_census(cache))

    @classmethod
    def _state_and_experts(cls, cache, batch_size: int, params) -> tuple:
        """(a row's state bytes, the experts' bytes, how many (layer,
        expert) slots they are) off the leaves' names, as `of_model`
        says."""
        import jax

        name = lambda path: str(getattr(path[-1], "key", path[-1]))
        state = sum(int(leaf.nbytes) for path, leaf in
                    jax.tree_util.tree_leaves_with_path(cache)
                    if name(path) in cls._STATE_LEAVES)
        experts = [(leaf.shape[0], int(leaf.size) * leaf.dtype.itemsize)
                   for path, leaf in
                   jax.tree_util.tree_leaves_with_path(params or {})
                   if name(path).startswith("experts_")]
        layers = sum(1 for path, _ in
                     jax.tree_util.tree_leaves_with_path(params or {})
                     if name(path) == "experts_fc1")
        held = experts[0][0] if experts else 0
        return (state // batch_size, sum(b for _, b in experts),
                layers * held)

    def row_cells(self, n: int) -> int:
        return self._state_cells + (int(n) if self._positions else 0)

    def read_cells(self, n: int) -> int:
        return 2 * self._state_cells + (int(n) if self._positions else 0)

    @property
    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def note_scan(self, committed, depth: int) -> None:
        rows = len(committed)
        cells = sum(self.row_cells(n) - self._state_cells for n in committed)
        with self._lock:
            self._counters["ssm_state_bytes_touched"] += (
                depth * 2 * rows * self._state_row_bytes)
            self._counters["kv_cells_read"] += depth * cells

    def note_routed(self, routed) -> None:
        if routed is None:
            return
        with self._lock:
            for key, n in zip(self.HYBRID_KEYS[2:], routed):
                self._counters[key] += int(n)

    def scan_least_bytes(self, param_bytes: int, read_bytes: int,
                         depth: int, routed=None) -> int:
        if routed is None:
            return super().scan_least_bytes(param_bytes, read_bytes, depth)
        return int(depth * (int(param_bytes) - self._expert_bytes
                            + int(read_bytes))
                   + int(routed[2]) * self._slot_bytes)


class DeltaCapacityLedger(HybridCapacityLedger):
    """Occupancy of a cache in which delta-rule layers keep one matrix per
    value head and a convolution tail a row (models/transformer.py
    `GatedDeltaMixer`: `delta_state` [rows, Hv, K, V] float32,
    `conv_tail`) beside attention layers that keep a K/V cell per
    position; the cells, the state, the tick's least bytes and the expert
    layers are the parent's account.

    `counters` adds GDN_KEYS to the parent's. Summed over a scan's ticks,
    what its active rows HOLD: their state (`gdn_state_bytes`: depth x
    rows x a row's state and tails) and their committed K/V cells
    (`kv_cell_bytes`: depth x cells x a cell's bytes), so that the one
    over the sum of both is the state's share of the live cache over the
    window. What the two forms of the rule worked: `gdn_steps`, a scan's
    depth x its active rows x the delta-rule layers (row-steps of the
    one-step form); `gdn_chunks`, per admitted request its bucket's
    chunks x those layers (systems solved by the chunked form; a wave's
    ladder padding repeats a row and is not counted). And the (query,
    cell) pairs the prefills attended causally in the attention layers at
    the rows' TRUE lengths, n (n + 1) / 2 a layer (`kv_pairs_prefilled`)."""

    GDN_KEYS = ("gdn_state_bytes", "kv_cell_bytes", "gdn_steps",
                "gdn_chunks", "kv_pairs_prefilled")

    def __init__(self, batch_size: int, positions: int, slab_bytes: int,
                 state_row_bytes: int, expert_bytes: int, expert_slots: int,
                 delta_layers: int, kv_layers: int, chunk: int,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        super().__init__(batch_size, positions, slab_bytes, state_row_bytes,
                         expert_bytes, expert_slots, registry=registry,
                         census=census)
        self._delta_layers = int(delta_layers)
        self._kv_layers = int(kv_layers)
        self._chunk = int(chunk)
        self._counters.update(dict.fromkeys(self.GDN_KEYS, 0))

    @classmethod
    def of_model(cls, cache, batch_size: int, positions: int, params,
                 chunk: int = 64,
                 registry: Optional[metrics.Registry] = None
                 ) -> "DeltaCapacityLedger":
        """From a freshly-initialized batch cache and the served
        parameters: every `delta_state` leaf is one delta-rule layer,
        every `cached_key` leaf one attention layer, `chunk` the
        positions of one triangular system; the rest as the parent reads
        it."""
        return cls(batch_size, positions, kv_slab_bytes(cache),
                   *cls._state_and_experts(cache, batch_size, params),
                   _leaves_named(cache, "delta_state"),
                   len(_kv_layer_cells(cache)), chunk,
                   registry=registry, census=kv_dtype_census(cache))

    def note_admission(self, kind: str, bucket: int, used_tokens: int
                       ) -> None:
        super().note_admission(kind, bucket, used_tokens)
        with self._lock:
            self._counters["gdn_chunks"] += (
                self._delta_layers * -(-int(bucket) // self._chunk))

    def note_commit(self, before: int, after: int,
                    decoding: bool = True) -> None:
        if decoding:
            return
        with self._lock:
            self._counters["kv_pairs_prefilled"] += (
                self._kv_layers * (int(after) * (int(after) + 1)
                                   - int(before) * (int(before) + 1)) // 2)

    def note_scan(self, committed, depth: int) -> None:
        super().note_scan(committed, depth)
        rows = len(committed)
        with self._lock:
            self._counters["gdn_state_bytes"] += (
                depth * rows * self._state_row_bytes)
            self._counters["kv_cell_bytes"] += int(
                depth * self._position_bytes
                * sum(int(n) for n in committed))
            self._counters["gdn_steps"] += depth * rows * self._delta_layers


class RingCapacityLedger(HybridCapacityLedger):
    """Occupancy of a cache whose window layers keep a ring of `window`
    cells a row (models/transformer.py `_rolling_attention`: slot =
    position mod window) beside the slabs of the layers without a window.

    The unit is ONE layer's K and V of one position. A row that has
    committed `n` tokens holds, and a decode tick reads, n cells in each
    layer without a window and min(n, ring) in each window layer: which
    layers are which, and each ring's length, are read off the cache's own
    leaves (`of_model`). State-space state beside them and expert layers
    routed without a capacity are the parent's account.

    `counters` adds RING_KEYS to the parent's: per scan, depth x the cells
    its active rows hold at its start, summed over the layers without a
    window (`kv_full_cells_read`) and over the window layers
    (`kv_window_cells_read`; the two make up `kv_cells_read`), and the
    rows of a scan whose next write lands on a cell one window back: whose
    shortest ring has turned (`kv_window_wraps`)."""

    RING_KEYS = ("kv_full_cells_read", "kv_window_cells_read",
                 "kv_window_wraps")

    def __init__(self, batch_size: int, positions: int, layer_cells,
                 slab_bytes: int, state_row_bytes: int = 0,
                 expert_bytes: int = 0, expert_slots: int = 0,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        layer_cells = [int(n) for n in layer_cells]
        self._full_layers = sum(n >= positions for n in layer_cells)
        self._rings = tuple(n for n in layer_cells if n < positions)
        super().__init__(batch_size, sum(layer_cells), slab_bytes,
                         state_row_bytes, expert_bytes, expert_slots,
                         registry=registry, census=census)
        self._counters.update(dict.fromkeys(self.RING_KEYS, 0))

    @classmethod
    def of_model(cls, cache, batch_size: int, positions: int, params,
                 registry: Optional[metrics.Registry] = None
                 ) -> "RingCapacityLedger":
        """From a freshly-initialized batch cache and the served
        parameters: every `cached_key` leaf gives one layer's cells a
        row (`positions` of them in a layer without a window), the rest
        as the parent reads it."""
        return cls(batch_size, positions, _kv_layer_cells(cache),
                   kv_slab_bytes(cache),
                   *cls._state_and_experts(cache, batch_size, params),
                   registry=registry, census=kv_dtype_census(cache))

    def _kv_cells(self, n: int) -> tuple:
        """(cells of the layers without a window, cells of the window
        layers) a row holds once it has committed `n` tokens."""
        return (self._full_layers * int(n),
                sum(min(int(n), ring) for ring in self._rings))

    def row_cells(self, n: int) -> int:
        return self._state_cells + sum(self._kv_cells(n))

    def read_cells(self, n: int) -> int:
        return 2 * self._state_cells + sum(self._kv_cells(n))

    def note_scan(self, committed, depth: int) -> None:
        super().note_scan(committed, depth)
        full = window = wraps = 0
        for n in committed:
            a, b = self._kv_cells(n)
            full, window = full + a, window + b
            wraps += int(n) >= min(self._rings, default=n + 1)
        with self._lock:
            self._counters["kv_full_cells_read"] += depth * full
            self._counters["kv_window_cells_read"] += depth * window
            self._counters["kv_window_wraps"] += wraps


class LatentCapacityLedger(HybridCapacityLedger):
    """Occupancy of a cache whose attention layers keep ONE LATENT CELL per
    position (models/transformer.py `LatentAttention`: `cached_latent`
    [rows, positions, latent] and `cached_rope_key` [rows, positions,
    rope], the latent and the one rotary key, no head axis and no value
    leaf): a cell per position as in a K/V
    slab, with a size of its own (1,152 B at 512 + 64 values in bfloat16,
    where 64 heads of K and V are 40,960).

    The unit is one layer's cell of one position. A row that has committed
    `n` tokens holds, and a decode tick reads, n cells in each such layer.
    Expert layers routed without a capacity (and state-space state, where
    a model had both) are the parent's account, so `scan_least_bytes`
    counts the parameters outside the experts, the experts touched among
    those held and the committed cells at their own size.

    `counters` adds LATENT_KEYS to the parent's, all from the rows' TRUE
    lengths, never `max_len`: cells written by prefills and decode ticks
    (`latent_cells_committed`), per scan depth x the committed cells of
    its active rows (`latent_cells_read`), and the (query, cell) pairs the
    prefills attended causally, n (n + 1) / 2 a layer for a prompt of n
    (`latent_pairs_prefilled`)."""

    LATENT_KEYS = ("latent_cells_committed", "latent_cells_read",
                   "latent_pairs_prefilled")

    def __init__(self, batch_size: int, positions: int, layers: int,
                 slab_bytes: int, state_row_bytes: int = 0,
                 expert_bytes: int = 0, expert_slots: int = 0,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        self._layers = int(layers)
        super().__init__(batch_size, int(positions) * self._layers,
                         slab_bytes, state_row_bytes, expert_bytes,
                         expert_slots, registry=registry, census=census)
        self._counters.update(dict.fromkeys(self.LATENT_KEYS, 0))

    @classmethod
    def of_model(cls, cache, batch_size: int, positions: int, params,
                 registry: Optional[metrics.Registry] = None
                 ) -> "LatentCapacityLedger":
        """From a freshly-initialized batch cache and the served
        parameters: every `cached_latent` leaf is one layer, the rest as
        the parent reads it."""
        return cls(batch_size, positions, _latent_layers(cache),
                   kv_slab_bytes(cache),
                   *cls._state_and_experts(cache, batch_size, params),
                   registry=registry, census=kv_dtype_census(cache))

    def row_cells(self, n: int) -> int:
        return self._state_cells + self._layers * int(n)

    def read_cells(self, n: int) -> int:
        return 2 * self._state_cells + self._layers * int(n)

    def note_commit(self, before: int, after: int,
                    decoding: bool = True) -> None:
        with self._lock:
            self._counters["latent_cells_committed"] += (
                self._layers * (int(after) - int(before)))
            if not decoding:
                self._counters["latent_pairs_prefilled"] += (
                    self._layers * (int(after) * (int(after) + 1)
                                    - int(before) * (int(before) + 1)) // 2)

    def note_scan(self, committed, depth: int) -> None:
        super().note_scan(committed, depth)
        with self._lock:
            self._counters["latent_cells_read"] += (
                depth * self._layers * sum(int(n) for n in committed))


class PagedCapacityLedger(CapacityLedger):
    """Block-pool KV occupancy (``TFDE_PAGED_KV``).

    The dense ledger's denominator is the whole pre-carved slab, so
    ``kv/waste_frac`` charges every cell a short request never touches.
    Under paging a row only holds the blocks it was granted, so the
    honest denominator is the blocks ACTUALLY HELD (active rows + trie)
    and the remaining waste is intra-block slack plus not-yet-decoded
    lifetime blocks — the ISSUE's acceptance bound. `snapshot` is a
    duck-typed callable (observability never imports inference)
    returning::

        {"total": .., "free": .., "active": ..,   # BlockPool.stats()
         "trie_blocks": ..,                        # trie-held (refs)
         "shared_cells": ..}                       # sum over rows of
                                                   # trie-shared pre_len

    ``used_bytes`` counts each resident token once: row-committed cells
    minus the trie-shared cells they'd double-count, plus the trie's own
    blocks. A block the trie evicted while a row still holds it is
    undercounted by that row's shared cells — waste reads slightly high,
    never low. Inherits `note_admission` (fed fresh-block cells per
    admission, so the pad-waste histogram measures intra-block slack)
    and the dense lock discipline.
    """

    def __init__(self, batch_size: int, cells_per_row: int,
                 pool_bytes: int, num_blocks: int, block: int,
                 snapshot,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        super().__init__(batch_size, cells_per_row, pool_bytes,
                         registry=registry, census=census)
        if num_blocks < 2 or block < 1:
            raise ValueError(
                f"need num_blocks >= 2 and block >= 1, got "
                f"{num_blocks}/{block}"
            )
        self._block = int(block)
        self._blocks_total = int(num_blocks) - 1  # null block excluded
        # per-cell cost re-based on the POOL's geometry (the null block
        # included in the denominator: it is real allocated HBM)
        self._cell_bytes = pool_bytes / float(num_blocks * block)
        self._snapshot = snapshot

    @property
    def block(self) -> int:
        return self._block

    @property
    def block_bytes(self) -> float:
        return self._cell_bytes * self._block

    @property
    def row_bytes(self) -> float:
        """Worst-case per-row cost: a full block table — the headroom
        model's conservative admission unit."""
        blocks_per_row = -(-self._cells // self._block)
        return self.block_bytes * blocks_per_row

    def observe(self, committed, req) -> dict:
        used = 0
        active = 0
        for r in range(self._b):
            if req[r] is not None:
                active += 1
                used += int(committed[r])
        snap = self._snapshot()
        trie_blocks = int(snap.get("trie_blocks", 0))
        shared = int(snap.get("shared_cells", 0))
        held = int(snap["active"])  # rows + trie, refcount-deduped
        free = int(snap["free"])
        used_cells = max(used - shared, 0) + trie_blocks * self._block
        with self._lock:
            self._used_cells = used_cells
            self._rows_active = active
        allocated = held * self.block_bytes
        used_bytes = used_cells * self._cell_bytes
        waste = (1.0 - used_bytes / allocated) if allocated else 0.0
        g = self._reg.gauge
        g("kv/allocated_bytes").set(allocated)
        g("kv/used_bytes").set(used_bytes)
        g("kv/waste_frac").set(waste)
        g("kv/rows_active").set(active)
        g("kv/rows_free").set(self._b - active)
        g("kv/pool_blocks_total").set(self._blocks_total)
        g("kv/pool_blocks_free").set(free)
        g("kv/pool_blocks_active").set(held - trie_blocks)
        g("kv/pool_blocks_trie").set(trie_blocks)
        out = {
            "allocated_bytes": allocated,
            "used_bytes": used_bytes,
            "used_cells": used_cells,
            "waste_frac": waste,
            "rows_active": active,
            "rows_free": self._b - active,
            "pool_blocks_total": self._blocks_total,
            "pool_blocks_free": free,
            "pool_blocks_active": held - trie_blocks,
            "pool_blocks_trie": trie_blocks,
        }
        out.update(self._publish_census())
        return out


class CapacityModel:
    """Headroom: how many more rows/tokens fit before the memory budget.

    budget_bytes = 0 (the default, ``TFDE_CAPACITY_BUDGET_BYTES``)
    derives capacity from the dense slab itself: the slab is
    pre-allocated, so headroom is simply the free rows (and their
    cells). A positive budget models a tighter external constraint —
    the forced-low-budget drill, or a real HBM envelope shared with the
    params — and headroom_rows is what still fits under it at the
    ledger's measured per-row cost.
    """

    def __init__(self, ledger: CapacityLedger,
                 budget_bytes: Optional[int] = None,
                 registry: Optional[metrics.Registry] = None):
        if budget_bytes is None:
            budget_bytes = knobs.env_int("TFDE_CAPACITY_BUDGET_BYTES", 0)
        self._ledger = ledger
        self.budget_bytes = int(budget_bytes or 0)
        self._reg = registry or metrics.default_registry()

    def headroom(self, occ: dict) -> dict:
        """Headroom rows/tokens for an `observe()` stats dict; publishes
        the kv/headroom_* gauges and returns the two fields (merged into
        the /load kv block)."""
        rows_free = int(occ["rows_free"])
        if self.budget_bytes <= 0:
            rows = rows_free
            tokens = rows_free * self._ledger.cells_per_row
        else:
            spare = self.budget_bytes - float(occ["used_bytes"])
            rows = min(rows_free,
                       max(0, int(spare // self._ledger.row_bytes)))
            tokens = min(rows_free * self._ledger.cells_per_row,
                         max(0, int(spare // self._ledger.cell_bytes)))
        g = self._reg.gauge
        g("kv/headroom_rows").set(rows)
        g("kv/headroom_tokens").set(tokens)
        return {"headroom_rows": rows, "headroom_tokens": tokens}


class PagedCapacityModel(CapacityModel):
    """Headroom over a block pool: the admission currency is BLOCKS.

    With no byte budget, what fits is whatever the free list (plus
    nothing — trie slack is the admission gate's business) can grant:
    ``headroom_tokens`` is the free blocks' cells and ``headroom_rows``
    conservatively prices a row at a full block table (the worst case a
    request may claim; the actual per-request block gate lives in the
    batcher's `_admit_capacity`). A positive ``TFDE_CAPACITY_BUDGET_
    BYTES`` first caps the grantable blocks at what the budget buys —
    the same-envelope dense-vs-paged comparison.
    """

    def headroom(self, occ: dict) -> dict:
        ledger = self._ledger
        rows_free = int(occ["rows_free"])
        free_blocks = int(occ.get("pool_blocks_free", 0))
        if self.budget_bytes > 0:
            held = int(occ.get("pool_blocks_active", 0)
                       + occ.get("pool_blocks_trie", 0))
            affordable = int(self.budget_bytes // ledger.block_bytes)
            free_blocks = min(free_blocks, max(0, affordable - held))
        blocks_per_row = -(-ledger.cells_per_row // ledger.block)
        rows = min(rows_free, free_blocks // blocks_per_row)
        tokens = free_blocks * ledger.block
        g = self._reg.gauge
        g("kv/headroom_rows").set(rows)
        g("kv/headroom_tokens").set(tokens)
        return {"headroom_rows": rows, "headroom_tokens": tokens}


# -- usage metering -----------------------------------------------------------
class UsageLog:
    """Bounded append-only JSONL usage log.

    One line per finished request. The byte bound (``TFDE_CAPACITY_
    USAGE_LOG_BYTES``) is enforced by compaction: when an append would
    overflow, the oldest lines are dropped until the newest half of the
    bound remains — so the file never grows past the bound and always
    holds the most recent records. Local paths only (the replica's
    model_dir/metrics); listed in tools/tfdelint.py LOCKED_CLASSES.
    """

    def __init__(self, path: str, max_bytes: Optional[int] = None):
        if max_bytes is None:
            max_bytes = knobs.env_int("TFDE_CAPACITY_USAGE_LOG_BYTES",
                                      DEFAULT_USAGE_LOG_BYTES)
        self._lock = threading.Lock()
        self.path = str(path)
        self.max_bytes = int(max_bytes or DEFAULT_USAGE_LOG_BYTES)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with self._lock:
            self._f = open(self.path, "a")
            self._bytes = self._f.tell()

    def write(self, rec: dict) -> None:
        line = json.dumps(rec, sort_keys=True) + "\n"
        with self._lock:
            if self._f is None:
                return
            if self._bytes + len(line) > self.max_bytes:
                self._compact_locked(len(line))
            self._f.write(line)
            self._f.flush()
            self._bytes += len(line)

    def _compact_locked(self, incoming: int) -> None:
        """Drop oldest lines until newest `max_bytes // 2` (minus the
        incoming line) remain. Called with the lock held."""
        self._f.close()
        keep_budget = max(self.max_bytes // 2 - incoming, 0)
        with open(self.path) as f:
            lines = f.readlines()
        kept: list = []
        size = 0
        for line in reversed(lines):
            if size + len(line) > keep_budget:
                break
            kept.append(line)
            size += len(line)
        kept.reverse()
        with open(self.path, "w") as f:
            f.writelines(kept)
        self._f = open(self.path, "a")
        self._bytes = size

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


def resolve_usage_log(model_dir: Optional[str] = None
                      ) -> Optional[UsageLog]:
    """Normalize ``TFDE_USAGE_LOG``: unset/``off`` -> None; ``on`` ->
    ``<model_dir>/metrics/usage_<host>.jsonl`` (None when no model_dir
    to anchor it — the ReplicaServer re-arms with its model_dir);
    anything else is an explicit path."""
    spec = (knobs.env_str("TFDE_USAGE_LOG") or "").strip()
    if spec.lower() in ("", "off", "0", "false", "no"):
        return None
    if spec.lower() in ("on", "1", "true", "yes"):
        if model_dir is None:
            return None
        from tfde_tpu.observability.flightrec import _host_id

        return UsageLog(os.path.join(
            model_dir, "metrics", f"usage_{int(_host_id())}.jsonl"))
    return UsageLog(spec)


class UsageMeter:
    """Per-request usage accounting: prompt/generated tokens and
    KV-residency token·seconds, stamped with priority and outcome.

    Residency integrates slab occupancy over the request's resident
    window [admit, finish] with the trapezoid of its token count
    (prompt at admit, prompt+generated at finish) — the billing-grade
    capacity-cost unit. Requests finished before admission (queue-side
    shed/cancel) occupied no slab and meter zero residency. Listed in
    tools/tfdelint.py LOCKED_CLASSES: all shared state under `_lock`.
    """

    def __init__(self, registry: Optional[metrics.Registry] = None,
                 log: Optional[UsageLog] = None):
        self._lock = threading.Lock()
        self._reg = registry or metrics.default_registry()
        self._log = log if log is not None else resolve_usage_log(None)
        self._open: Dict[int, dict] = {}
        self._totals = {"requests": 0, "prompt_tokens": 0,
                        "generated_tokens": 0, "kv_token_seconds": 0.0}

    def arm(self, model_dir: Optional[str]) -> None:
        """Late-bind the JSONL log once a model_dir exists (the
        ReplicaServer construction path). First successful arm wins."""
        log = resolve_usage_log(model_dir)
        with self._lock:
            if self._log is None:
                self._log = log
            elif log is not None:
                log.close()

    @property
    def log_path(self) -> Optional[str]:
        with self._lock:
            return self._log.path if self._log is not None else None

    def begin(self, rid: int, prompt_tokens: int, priority: str) -> None:
        rec = {"rid": int(rid), "prompt_tokens": int(prompt_tokens),
               "priority": str(priority),
               "t_submit": time.perf_counter(), "t_admit": None}
        with self._lock:
            self._open[int(rid)] = rec

    def admitted(self, rid: int) -> None:
        now = time.perf_counter()
        with self._lock:
            rec = self._open.get(int(rid))
            if rec is not None and rec["t_admit"] is None:
                rec["t_admit"] = now

    def finish(self, rid: int, generated_tokens: int,
               outcome: str = "ok") -> Optional[dict]:
        """Close one request's meter; idempotent (an unknown/already-
        closed rid is a no-op). Returns the usage record."""
        now = time.perf_counter()
        with self._lock:
            rec = self._open.pop(int(rid), None)
        if rec is None:
            return None
        prompt = int(rec["prompt_tokens"])
        gen = int(generated_tokens)
        t_admit = rec["t_admit"]
        resident_s = (now - t_admit) if t_admit is not None else 0.0
        # trapezoid: prompt cells at admit, prompt+generated at finish
        residency = (prompt + (prompt + gen)) / 2.0 * resident_s
        out = {
            "ts": time.time(),
            "rid": int(rid),
            "priority": rec["priority"],
            "outcome": str(outcome),
            "prompt_tokens": prompt,
            "generated_tokens": gen,
            "kv_token_seconds": round(residency, 6),
            "queue_wait_s": round(
                (t_admit - rec["t_submit"])
                if t_admit is not None else now - rec["t_submit"], 6),
            "resident_s": round(resident_s, 6),
        }
        with self._lock:
            self._totals["requests"] += 1
            self._totals["prompt_tokens"] += prompt
            self._totals["generated_tokens"] += gen
            self._totals["kv_token_seconds"] += residency
            log = self._log
        c = self._reg.counter
        c("usage/requests").incr()
        c(f"usage/requests/{rec['priority']}").incr()
        c(f"usage/requests/{outcome}").incr()
        c("usage/prompt_tokens").incr(prompt)
        c("usage/generated_tokens").incr(gen)
        c("usage/kv_token_seconds").incr(residency)
        if log is not None:
            log.write(out)
        return out

    def totals(self) -> dict:
        """Cumulative sums across finished requests (the bit-exactness
        pin: prompt/generated totals equal the per-request emissions)."""
        with self._lock:
            return dict(self._totals)

    def close(self) -> None:
        with self._lock:
            log, self._log = self._log, None
        if log is not None:
            log.close()
