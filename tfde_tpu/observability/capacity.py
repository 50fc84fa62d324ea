"""KV-capacity observability: occupancy ledger, headroom model, usage meter.

ROADMAP item 1 claims paged-KV will unlock 4-8x serving concurrency by
eliminating pad-ladder waste — but nothing measured that waste, so the
win could be neither sized in advance nor proven after. This module is
the capacity half of the observability stack, three legs:

- **CapacityLedger** — the occupancy picture. The dense ledger reports
  the per-row slab, summed from the layers' own descriptions of what
  they cache (models/cache_state.py, handed in; this module imports
  nothing of models/ or inference/ and asks no leaf its name beyond what
  `kv_slab_bytes` / `kv_dtype_census` leave out): the batcher feeds
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

import collections
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


class _Slabs:
    """The description a ledger works from where every layer keeps a slab
    of one cell per position, and where it was given none (the pool's
    ledger; a model that describes nothing): a position of all the layers
    is ONE cell, `cell_bytes` wide, the unit of `/load`'s headroom_tokens
    and of the block pool. It has the arithmetic of the 'kv' descriptions
    it stands for (models/cache_state.py `CacheState`)."""

    kind = "kv"

    def __init__(self, cells: int, cell_bytes: float):
        self.cells, self.cell_bytes = cells, cell_bytes

    def held_cells(self, n: int) -> int:
        return n

    def held_bytes(self, n: int) -> float:
        return n * self.cell_bytes

    read_cells, read_bytes = held_cells, held_bytes

    @property
    def row_bytes(self) -> float:
        return self.cells * self.cell_bytes


class CapacityLedger:
    """Dense-cache occupancy, pad-ladder waste and the counters of what
    the layers keep, from the layers' own descriptions.

    One ledger per batcher cache. `states` is one description a layer
    (models/cache_state.py `CacheState`, duck-typed: `kind`, `cells`,
    `window`, `chunk`, `row_bytes`, and of a row at `n` committed tokens
    `held_cells`, `read_cells`, `held_bytes`, `read_bytes`, which the
    ledger sums and never works out again), `experts` the held experts'
    (`bytes`, `slots`) of a
    model that routes without a capacity, else None. What is summed across
    kinds is bytes; what counts cells counts (layer, position) cells, but
    for a model of slabs alone (`_Slabs`). `observe` is fed the host-side
    committed counts every decode round / stats publish; `note_admission`
    every admitted request's (bucket, true prompt length) at wave time;
    `note_commit` / `note_scan` / `note_routed` what the rows committed,
    what a scan starts over and what the expert layers counted. Listed in
    tools/tfdelint.py LOCKED_CLASSES: all shared state under `_lock`.

    `counters`, which the batcher hands on in `stats()`: a family of keys
    for each kind of layer that is present, every number from the rows'
    TRUE lengths, never `max_len`. A model of slabs alone has none.

    EVA_KEYS ('eva'; a count of the row's positions, not of its layers'):
    chunk summaries written (prefill and decode, one per chunk and row),
    windows handed over in decode (a prefill keeps the window its true
    length ends in and hands none over), and per scan, depth x what its
    active rows attend to at the scan's start: live window positions and
    visible summaries.

    HYBRID_KEYS (a ring, a latent layer, a state or `experts`; one family,
    zeros included: the readers tell a program by the keys it has): per
    scan, depth x (twice the active rows' state bytes, read and written;
    the (layer, position) cells they hold), and what the expert layers
    counted of real tokens in prefill waves and scans alike, summed over
    layers and ticks: pairs routed, pairs whose expert is held, held
    experts with at least one pair, the busiest held expert's pairs; and,
    of every token they were handed, the rows of the hidden width they
    copied into sorted order and fetched back from it, and their passes
    over the held experts' weights (a call's blocks: one a layer for a
    tick, more for a wave longer than `moe.token_block` gives a block).

    RING_KEYS ('ring'): per scan, depth x the cells its active rows hold
    at its start, summed over the slab layers (`kv_full_cells_read`) and
    over the rings (`kv_window_cells_read`: min(n, ring) a layer), and the
    rows of a scan whose next write lands on a cell one window back: whose
    shortest ring has turned (`kv_window_wraps`).

    LATENT_KEYS ('latent'): cells written by prefills and decode ticks
    (`latent_cells_committed`), per scan depth x the committed cells of
    its active rows (`latent_cells_read`), and the (query, cell) pairs the
    prefills attended causally, n (n + 1) / 2 a layer for a prompt of n
    (`latent_pairs_prefilled`).

    GDN_KEYS (a 'state' with a `chunk`, the delta rule's). Summed over a
    scan's ticks, what its active rows HOLD: their state
    (`gdn_state_bytes`: depth x rows x a row's states and tails) and their
    committed cells (`kv_cell_bytes`), so that the one over the sum of
    both is the state's share of the live cache over the window. What the
    two forms of the rule worked: `gdn_steps`, a scan's depth x its active
    rows x the delta-rule layers (row-steps of the one-step form);
    `gdn_chunks`, per admitted request its bucket's chunks x those layers
    (systems solved by the chunked form; a wave's ladder padding repeats a
    row and is not counted). And the pairs the prefills attended causally
    in the slab layers, as the latent family counts them
    (`kv_pairs_prefilled`).
    """

    EVA_KEYS = ("eva_summaries_written", "eva_window_turns",
                "eva_window_cells_read", "eva_summary_cells_read")
    HYBRID_KEYS = ("ssm_state_bytes_touched", "kv_cells_read", "moe_pairs",
                   "moe_pairs_held", "moe_experts_touched",
                   "moe_pairs_busiest", "moe_rows_moved",
                   "moe_weight_passes")
    RING_KEYS = ("kv_full_cells_read", "kv_window_cells_read",
                 "kv_window_wraps")
    LATENT_KEYS = ("latent_cells_committed", "latent_cells_read",
                   "latent_pairs_prefilled")
    GDN_KEYS = ("gdn_state_bytes", "kv_cell_bytes", "gdn_steps",
                "gdn_chunks", "kv_pairs_prefilled")

    def __init__(self, batch_size: int, positions: int, slab_bytes: int,
                 states=None, experts=None,
                 registry: Optional[metrics.Registry] = None,
                 census: Optional[dict] = None):
        if batch_size < 1 or positions < 1:
            raise ValueError(
                f"need batch_size/positions >= 1, got "
                f"{batch_size}/{positions}"
            )
        self._lock = threading.Lock()
        self._b = int(batch_size)
        self._positions = int(positions)
        self._slab_bytes = int(slab_bytes)
        #: dtype split of the slab (kv_dtype_census) — prices the
        #: quantized-vs-fp delta; empty when the builder predates it
        self._census = dict(census or {})
        states = tuple(states or ())
        if all(s.kind == "kv" for s in states):
            # measured per-cell cost: the slab's own bytes over its cells,
            # so used_bytes sums exactly to the slab when every row is full
            states = (_Slabs(self._positions, self._slab_bytes
                             / float(self._b * self._positions)),)
        #: (description, how many layers gave it): a row's account is
        #: summed a distinct description at a time, not a layer at a time
        self._states = tuple(collections.Counter(states).items())
        #: layers of each kind; the 'eva' layers' one window and chunk;
        #: the shortest ring
        self._layers = collections.Counter(s.kind for s in states)
        self._eva = next((s for s in states if s.kind == "eva"), None)
        self._ring = min((s.cells for s in states if s.kind == "ring"),
                         default=None)
        #: a state-only model's one cell of a row is its state
        self._cells = sum(k * s.cells for s, k in self._states) or 1
        self._row_bytes = sum(k * s.row_bytes for s, k in self._states)
        self._cell_bytes = self._row_bytes / self._cells
        self._experts = experts
        #: bytes of one expert of one layer
        self._slot_bytes = (experts.bytes / experts.slots
                            if experts is not None and experts.slots
                            else 0.0)
        self._delta_layers = sum(k for s, k in self._states
                                 if s.kind == "state" and s.chunk)
        kinds = self._layers
        keys = self.EVA_KEYS if kinds["eva"] else ()
        if (kinds["ring"] or kinds["latent"] or kinds["state"]
                or experts is not None):
            keys += self.HYBRID_KEYS
        keys += self.RING_KEYS if kinds["ring"] else ()
        keys += self.LATENT_KEYS if kinds["latent"] else ()
        keys += self.GDN_KEYS if self._delta_layers else ()
        self._keys = keys
        self._counters = dict.fromkeys(keys, 0)
        self._reg = registry or metrics.default_registry()
        self._used_cells = 0
        self._rows_active = 0
        self._pad_alloc_tokens = 0
        self._pad_waste_tokens = 0
        self._bucket_alloc: Dict[int, int] = {}
        self._bucket_waste: Dict[int, int] = {}

    # -- read surface --------------------------------------------------------
    @property
    def cell_bytes(self) -> float:
        """A row's bytes, a state's among them, over its cells: one
        cell's where all are alike."""
        return self._cell_bytes

    @property
    def row_bytes(self) -> float:
        """Per-row slab cost — the headroom model's admission unit."""
        return self._row_bytes

    @property
    def positions(self) -> int:
        """Tokens a row was allocated for: what the headroom model counts
        in, whatever the layers keep of a token."""
        return self._positions

    @property
    def token_bytes(self) -> float:
        """The slab's bytes over the positions it was allocated for."""
        return self._slab_bytes / float(self._b * self._positions)

    @property
    def slab_bytes(self) -> int:
        return self._slab_bytes

    @property
    def cells_per_row(self) -> int:
        return self._cells

    @property
    def kinds(self) -> frozenset:
        """The kinds of layer the ledger was built from."""
        return frozenset(self._layers)

    @property
    def census(self) -> dict:
        """The slab/pool dtype split (kv_dtype_census); {} when unknown."""
        return dict(self._census)

    # -- what rows of `n` committed tokens hold and read ----------------------
    def _sum(self, what: str, committed, *kinds: str) -> float:
        """A description's formula `what` of a row (`held_cells`,
        `read_cells`, `held_bytes`, `read_bytes`), summed over rows at
        these `committed` counts and over the layers: of these `kinds`,
        where any is named."""
        counts = [int(n) for n in committed]
        return sum(k * sum(getattr(s, what)(n) for n in counts)
                   for s, k in self._states if not kinds or s.kind in kinds)

    def row_cells(self, n: int) -> int:
        """Cells of a row that hold live state once it has committed `n`
        tokens."""
        return self._sum("held_cells", [n])

    def read_cells(self, n: int) -> int:
        """Cells one decode tick of such a row cannot avoid reading."""
        return self._sum("read_cells", [n])

    def read_bytes(self, committed) -> float:
        """Bytes one decode tick of rows at these `committed` counts cannot
        avoid reading: the cells each kind's formula gives, and every
        state once and back."""
        return self._sum("read_bytes", committed)

    # -- what the layers add to the batcher's account --------------------------
    @property
    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def _count(self, **amounts) -> None:
        """Add to the counters of the families this ledger keeps."""
        with self._lock:
            for key, n in amounts.items():
                if key in self._counters:
                    self._counters[key] += int(n)

    def note_commit(self, before: int, after: int,
                    decoding: bool = True) -> None:
        """A row went from `before` to `after` committed tokens, in a
        decode scan or (`decoding` false) by its prefill."""
        if not self._keys:
            return
        before, after = int(before), int(after)
        layers, eva = self._layers, self._eva
        pairs = (0 if decoding
                 else (after * (after + 1) - before * (before + 1)) // 2)
        self._count(
            eva_summaries_written=(
                after // eva.chunk - before // eva.chunk if eva else 0),
            eva_window_turns=(
                after // eva.window - before // eva.window
                if eva and decoding else 0),
            latent_cells_committed=layers["latent"] * (after - before),
            latent_pairs_prefilled=layers["latent"] * pairs,
            kv_pairs_prefilled=layers["kv"] * pairs)

    def note_scan(self, committed, depth: int) -> None:
        """A decode scan of `depth` ticks starts over active rows at
        these `committed` counts."""
        if not self._keys:
            return
        committed = [int(n) for n in committed]
        rows = len(committed)
        # what the rows hold at the scan's start, a kind at a time
        cells, held = (collections.Counter({
            kind: self._sum(what, committed, kind) for kind in self._layers})
            for what in ("held_cells", "held_bytes"))
        live, visible = (map(sum, zip(*(self._eva.attended(n)
                                        for n in committed)))
                         if self._eva and rows else (0, 0))
        self._count(
            eva_window_cells_read=depth * live,
            eva_summary_cells_read=depth * visible,
            ssm_state_bytes_touched=depth * self._sum(
                "read_bytes", committed, "state"),
            kv_cells_read=depth * sum(cells.values()),
            kv_full_cells_read=depth * cells["kv"],
            kv_window_cells_read=depth * cells["ring"],
            kv_window_wraps=sum(n >= self._ring for n in committed
                                ) if self._ring else 0,
            latent_cells_read=depth * cells["latent"],
            gdn_state_bytes=depth * held["state"],
            kv_cell_bytes=depth * (sum(held.values()) - held["state"]),
            gdn_steps=depth * rows * self._delta_layers)

    def note_routed(self, routed) -> None:
        """What the expert layers of one program counted on the device
        ([pairs, pairs held, experts touched, busiest expert's pairs, rows
        moved, weight passes], summed over layers and ticks): nothing to
        a ledger without the hybrid family."""
        if routed is not None:
            self._count(**dict(zip(self.HYBRID_KEYS[2:], routed)))

    def scan_least_bytes(self, param_bytes: int, read_bytes: int,
                         depth: int, routed=None) -> int:
        """Bytes `depth` decode ticks cannot avoid reading: the rows'
        cells and states (`read_bytes`, a tick's) and every parameter, a
        tick; where the expert layers counted (`routed`), of the held
        experts only the ones that received a pair that tick."""
        if routed is None or self._experts is None:
            return depth * (int(param_bytes) + int(read_bytes))
        return int(depth * (int(param_bytes) - self._experts.bytes
                            + int(read_bytes))
                   + int(routed[2]) * self._slot_bytes)

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
        live = [committed[r] for r in range(self._b) if req[r] is not None]
        active = len(live)
        used = self._sum("held_cells", live)
        with self._lock:
            self._used_cells = used
            self._rows_active = active
        used_bytes = self._sum("held_bytes", live)
        waste = 1.0 - used_bytes / self._slab_bytes
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
        self._count(gdn_chunks=sum(
            k * -(-bucket // s.chunk) for s, k in self._states
            if s.kind == "state" and s.chunk))
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
        self._states = ((_Slabs(self._cells, self._cell_bytes), 1),)
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
            tokens = rows_free * self._ledger.positions
        else:
            spare = self.budget_bytes - float(occ["used_bytes"])
            rows = min(rows_free,
                       max(0, int(spare // self._ledger.row_bytes)))
            tokens = min(rows_free * self._ledger.positions,
                         max(0, int(spare // self._ledger.token_bytes)))
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
