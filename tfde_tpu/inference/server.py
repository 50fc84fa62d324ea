"""Continuous batching — the serving loop that keeps every batch row busy.

`generate` (inference/decode.py) serves one batch to completion: rows that
finish early ride along as padding until the slowest row ends, and new
requests wait for the whole batch. A serving deployment wants the modern
alternative: a FIXED decode batch where a finished row is immediately
re-used for the next queued request while the other rows keep decoding —
continuous batching (the vLLM/Orca scheduling idea, re-built on this
framework's primitives).

What makes it cheap here: the per-row KV-cache machinery built for
batched speculative decoding (models/transformer.py `_decode_attention`
vector branch + per-row `position_index`) already lets every batch row
sit at a DIFFERENT sequence position with its own validity horizon.
Admission is then per-row cache surgery:

- one compiled DECODE SCAN serves the whole batch for K ticks: the model
  forward, the sampler (temperature/top-k/top-p/min-p/repetition
  penalty, `seen`-mask update included), per-row EOS/budget masking and
  index bookkeeping all live inside ONE jitted `lax.scan`, so the host
  pays one dispatch and one sync per K tokens per row instead of three
  or more per token;
- finished rows freeze mid-scan: they feed `pad_id`, their index stops
  advancing, and their sampled output is masked — on-device, no host
  round-trip (a frozen row's final pad writes land beyond its committed
  count and stay unreachable, the stale-K/V invariant);
- one compiled PREFILL per distinct prompt BUCKET admits every freed row
  of that bucket at once ([R, Pbucket] prompts, first tokens sampled
  inside the same program), and one multi-row cache scatter lands all of
  them (`.at[rows].set`) — admission cost amortizes over the wave
  instead of paying a prefill + scatter round-trip per row;
- EOS, budget, and queue bookkeeping are per-row host state, replayed
  from the scan's [B, K] token/emitted output after the single fetch.

Greedy determinism: each request's output equals a solo
`generate(model, params, prompt)` run token for token regardless of what
shares the batch or the scan depth K (rows are independent through
attention's per-row validity masks; tests/test_server.py asserts it
across staggered admissions and scan depths). Temperature>0 draws ride a
shared key stream — distributionally correct per request, draw values
batch-dependent.

Scan-depth adaptation: `scan_depth` is the K ceiling. When the queue is
non-empty K drops toward the soonest row completion (host-known budget;
EOS is not host-predictable) so a freed row admits without waiting out a
long scan; when the queue is empty K is capped by the longest remaining
budget so a draining batch never runs dead ticks. K is chosen from the
power-of-two ladder {1, 2, 4, ..., scan_depth} to bound compile count at
O(log scan_depth).

Prompt-length compiles: prompts are right-padded to the smallest of
`prompt_buckets` that fits (powers of two up to max_len by default), so
the prefill compiles once per BUCKET (x the power-of-two wave-size
ladder), not per length — the first-token logits are read at each row's
true last position, and the admission-time index rewind makes the pad
K/V unreachable.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tfde_tpu import knobs
from tfde_tpu.inference import admission as _admission
from tfde_tpu.inference import paged as _paged
from tfde_tpu.inference.decode import (
    _decode_clone,
    init_cache,
    sample_logits,
    validate_budget,
)
from tfde_tpu.inference.prefix_cache import (
    DEFAULT_BLOCK,
    is_index_leaf,
    leaf_name,
    resolve as _resolve_prefix,
)
from tfde_tpu.inference.speculative import _set_index_counters
from tfde_tpu.models import moe as _moe
from tfde_tpu.models.cache_state import layout_of as _layout_of
from tfde_tpu.analysis import hlolint as _hlolint
from tfde_tpu.observability import boot as _boot
from tfde_tpu.observability import capacity as _capacity
from tfde_tpu.observability import flightrec
from tfde_tpu.observability import memwatch as _memwatch
from tfde_tpu.observability import metrics
from tfde_tpu.observability import recompile as _recompile
from tfde_tpu.observability import trace as _trace
from tfde_tpu.observability.spans import now_ns, span
from tfde_tpu.utils.summary import _count as _count_params

#: per-batcher fingerprint tag: distinct batcher instances hold distinct
#: static model objects, so the SAME (kind, key, wave) signature compiles
#: separately per instance — the recompile sentinel's fingerprints carry
#: this tag so a second batcher's first wave reads as a novel compile,
#: not as an unexpected recompile of the first batcher's site
_BATCHER_TAGS = itertools.count()


#: The batcher's own account of its step, all integers and cumulative:
#: every `*_ns` key is nanoseconds on `spans.now_ns`, added by the span at
#: that boundary; the others count what the boundary handled. `stats()`
#: returns them beside the counts it always had.
_PHASE_KEYS = (
    # spans (key of the time, key of the count)
    "step_ns", "steps",                  # serving/step: all of step()
    "admit_ns",                          # serving/admit: _admit()
    "prefill_ns", "prefill_waves",       # serving/prefill: one group wave
    "prefill_pack_ns",                   # .../pack: prompts packed on host
    "prefill_template_ns",               # .../template: fresh zero rows
    "prefill_run_ns",                    # .../run: operands + dispatch
    "prefill_scatter_ns",                # .../scatter: rows into the slab
    "device_wait_ns",                    # .../fetch of both: blocked on device
    "decode_ns", "scans",                # serving/decode: repair..fetch
    "decode_upload_ns", "uploads",       # .../upload: loop state to device
    "decode_dispatch_ns",                # .../scan: dispatch of the scan
    "emit_ns",                           # serving/emit: fetch's return..step's
    # counters at the same boundaries
    "admitted", "queue_wait_ns", "first_token_hold_ns",
    "prefill_rows_padded", "prefill_tokens", "prefill_cells",
    "decode_least_bytes",
)


def _refuse_stateful(model, what: str,
                     max_len: Optional[int] = None) -> None:
    """Features that rewind, share or re-encode cached state BY POSITION
    have nothing to hold on to in a layout that is not a cell per
    position (a summary folds 16 positions into one cell, a window slot
    is reused every 2,048, a state-space layer keeps one state, a ring's
    cell is overwritten one window on). The layers say which they are
    (models/cache_state.py): the first that is not gives the reason."""
    why = _layout_of(model, max_len).not_by_position
    if why is not None:
        raise NotImplementedError(
            f"{what} is not built for this model: {why}, not a cell per "
            f"position")


def _set_feed_pad(cache, pad):
    """Tell layers that derive state from the fed tokens how many
    trailing tokens of the next call are padding, per row: every
    `feed_pad` leaf becomes `pad` ([rows] int32). A cache without such a
    leaf (position-indexed K/V, where the index rewind alone hides the
    padding) comes back as it was, and the program is unchanged."""

    def fix(path, leaf):
        if str(getattr(path[-1], "key", path[-1])) == "feed_pad":
            return jnp.asarray(pad, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _sown_counters(mutated):
    """The sum of what the layers sowed into "counters" in one apply (the
    expert layers' routing counts, models/moe.py), or None for a model
    that sows nothing: the program is then unchanged."""
    leaves = jax.tree_util.tree_leaves(mutated.get("counters", {}))
    return sum(leaves[1:], leaves[0]) if leaves else None


def _fetch(tree):
    """THE host sync: one blocking device->host fetch for everything the
    host loop needs this round. Kept as a module-level seam so tests can
    count syncs (tests/test_server.py's dispatch-budget regression guard)
    and so no call site is tempted to sprinkle per-array np.asarray
    fetches back onto the hot path."""
    return jax.device_get(tree)


@functools.partial(
    jax.jit,
    static_argnames=("model", "depth", "temperature", "top_k", "top_p",
                     "min_p", "repetition_penalty", "eos_id", "pad_id"),
    donate_argnums=(1, 3, 4, 5, 6, 7),
)
def _decode_scan(model, cache, params, tok, idx, budget, done, seen, rng,
                 depth, temperature, top_k, top_p, min_p,
                 repetition_penalty, eos_id, pad_id):
    """K = `depth` fused decode ticks for the whole batch, device-resident.

    Carry per row r: `tok[r]` the pending (sampled, unfed) token, `idx[r]`
    the committed token count (cache index), `budget[r]` remaining output
    tokens, `done[r]` frozen flag, plus the optional [B, V] `seen`
    presence mask and the sampling key. Each tick feeds the pending
    token, samples the next one with the FULL sampling config in-program
    (no separate sample_logits dispatch, no host `.at[]` seen update),
    and applies EOS/budget masking on device: a finishing row emits its
    last token, flips `done`, and thereafter feeds `pad_id` with a frozen
    index (its pad K/V lands beyond the committed count — unreachable;
    a layer that derives state from what it is fed is told through
    `_set_feed_pad`).

    Returns (cache, tok, idx, budget, done, seen, rng, toks [B, K],
    emitted [B, K], counters): `toks[r]` masked to `pad_id` where not
    emitted; `emitted[r]` is a True-prefix per row (rows freeze
    monotonically), so the host replays exactly `emitted[r].sum()` tokens
    into its bookkeeping after the ONE fetch, which also brings
    `counters`: what the layers sowed, summed over layers and ticks
    (`_sown_counters`; None for a model that sows nothing).

    The greedy path (temperature == 0.0) carries `rng=None` and performs
    no `jax.random.split` at all — dead device work the per-tick loop
    used to pay on every step.
    """

    @jax.named_scope("decode_tick")
    def body(carry, _):
        cache, tok, idx, budget, done, seen, rng = carry
        # index surgery each tick instead of trusting the model's own
        # advance: frozen rows must NOT advance, and writing the [B]
        # vector here keeps the carry shape stable from tick one
        cache = _set_index_counters(cache, idx)
        # a frozen row's feed is padding: it completes no chunk summary
        cache = _set_feed_pad(cache, done)
        feed = jnp.where(done, jnp.int32(pad_id), tok)
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, feed[:, None], train=False,
            mutable=["cache", "counters"],
        )
        cache = mutated["cache"]
        logits = logits[:, -1].astype(jnp.float32)
        if temperature != 0.0:
            rng, sub = jax.random.split(rng)
        else:
            sub = rng  # greedy: sample_logits is argmax, rng untouched
        nxt = sample_logits(
            logits, sub, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p, repetition_penalty=repetition_penalty, seen=seen,
        )
        live = ~done
        nxt = jnp.where(done, jnp.int32(pad_id), nxt)
        if seen is not None:
            ar = jnp.arange(nxt.shape[0])
            seen = jnp.where(done[:, None], seen,
                             seen.at[ar, nxt].set(True))
        # feeding tok committed it; the new sample is now pending
        idx = idx + live.astype(jnp.int32)
        budget = budget - live.astype(jnp.int32)
        fin = budget <= 0
        if eos_id is not None:
            fin = fin | (nxt == eos_id)
        done = done | (live & fin)
        tok = jnp.where(live, nxt, tok)
        return ((cache, tok, idx, budget, done, seen, rng),
                (nxt, live, _sown_counters(mutated)))

    carry = (cache, tok, idx, budget, done, seen, rng)
    carry, (toks, emitted, counters) = jax.lax.scan(body, carry,
                                                    length=depth)
    cache, tok, idx, budget, done, seen, rng = carry
    if counters is not None:
        counters = counters.sum(0)
    return (cache, tok, idx, budget, done, seen, rng,
            jnp.moveaxis(toks, 0, 1), jnp.moveaxis(emitted, 0, 1), counters)


@functools.partial(
    jax.jit,
    static_argnames=("model", "temperature", "top_k", "top_p", "min_p",
                     "repetition_penalty"),
    donate_argnums=(1,),
)
def _prefill_rows(model, row_cache, params, prompts, last, valid, rng,
                  temperature, top_k, top_p, min_p, repetition_penalty):
    """Prefill R rows of one bucket in ONE call and sample each row's
    first token inside the same program.

    prompts: [R, Pbucket] right-padded prompt batch; `last` [R] the true
    last position per row (so bucketing never changes the first sampled
    token); `valid` [R, Pbucket] marks real (non-pad) prompt positions —
    only consulted when the repetition penalty is on, where it keeps pad
    slots out of the presence mask. Compiled per (bucket length, wave
    size); the admission ladder pads the wave to a power of two by
    REPEATING a real row (identical content, so the duplicate scatter
    writes are idempotent) to bound compile count.

    `row_cache` is DONATED: the mutated cache aliases the input buffers
    instead of paying a device-side copy of every K/V leaf per admission
    wave (tests/test_server.py pins the aliasing in the lowered HLO), so
    callers must hand in a FRESH zero tree each wave — `_row_template`
    runs the width's one compiled zero-fill program (`_zero_rows`), whose
    outputs are new buffers every call.

    The model applies its head at `last` alone (`GPT.__call__`'s `last`):
    the logits of the other positions, which nobody reads, are never
    computed.

    Returns (filled row cache, first tokens [R], seen rows [R, V] or
    None, counters: what the layers sowed, `_sown_counters`, or None).
    Pad correctness rides the per-row index machinery: pad K/V
    lands beyond each row's committed count once the admission rewind
    sets it to the TRUE prompt length. State that is DERIVED from the
    tokens (attention='eva': which window is in progress, which chunks
    are complete; a state-space layer's running state) cannot be hidden
    by a rewind, so the true lengths reach such layers before the
    forward (`_set_feed_pad`)."""
    row_cache = _set_feed_pad(row_cache, prompts.shape[1] - 1 - last)
    with jax.named_scope("prefill_rows"):
        logits, mutated = model.apply(
            {"params": params, "cache": row_cache}, prompts, train=False,
            last=last, mutable=["cache", "counters"],
        )
    r = prompts.shape[0]
    ar = jnp.arange(r)
    logits = logits[:, 0].astype(jnp.float32)
    row_seen = None
    if repetition_penalty != 1.0:
        hits = jnp.zeros((r, model.vocab_size), jnp.int32)
        hits = hits.at[ar[:, None], prompts].add(valid.astype(jnp.int32))
        row_seen = hits > 0
    tok = sample_logits(
        logits, rng, temperature=temperature, top_k=top_k, top_p=top_p,
        min_p=min_p, repetition_penalty=repetition_penalty, seen=row_seen,
    )
    if row_seen is not None:
        row_seen = row_seen.at[ar, tok].set(True)
    return mutated["cache"], tok, row_seen, _sown_counters(mutated)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(cache, rows_cache, rows):
    """Write an R-row prefill cache's leaves into batch rows `rows`
    ([R] int32) in ONE donated update — the multi-row generalization of
    the old per-row `.at[row].set` round-trip. Every leaf whose first
    axis is the row lands whole: K/V slabs, int8 scale sidecars, and for
    attention='eva' the window in progress, the summary table and
    `feed_pad`. Index counters pass through (the decode scan rewrites
    them from the host's committed counts every tick). Wave padding
    duplicates a real row verbatim, so duplicate indices in `rows` write
    identical values and the scatter stays deterministic."""

    def merge(path, big, small):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("cache_index", "position_index"):
            return big
        return big.at[rows].set(small.astype(big.dtype))

    return jax.tree_util.tree_map_with_path(merge, cache, rows_cache)


@functools.partial(
    jax.jit,
    static_argnames=("model", "temperature", "top_k", "top_p", "min_p",
                     "repetition_penalty"),
    donate_argnums=(1,),
)
def _prefill_suffix(model, row_cache, params, prefix_kv, suffixes, last,
                    fullp, valid, rng, temperature, top_k, top_p, min_p,
                    repetition_penalty):
    """Warm admission: land a cached prefix and prefill only the suffix,
    in ONE program.

    prefix_kv: {leaf-name: [R, L, ...]} — L cached prefix tokens of K/V
    per row (prefix_cache.py trie segments, stacked per wave). They are
    written at positions [:L], the index counters are set to L (the
    speculative-decoding arbitrary-start contract), and the model then
    consumes `suffixes` [R, Sbucket] as a normal feed starting at
    position L — bit-identical to having prefilled the whole prompt
    (tests/test_prefix_cache.py pins it). `last` [R] is the suffix-local
    last position; `fullp`/`valid` [R, Fbucket] carry the FULL padded
    prompt for the repetition-penalty presence mask (None when the
    penalty is off). `row_cache` is donated, as in `_prefill_rows`.

    Returns (filled row cache, first tokens [R], seen rows or None)."""
    some = next(iter(prefix_kv.values()))
    pre_len = some.shape[1]

    def put(path, big):
        if is_index_leaf(path):
            return big
        seg = prefix_kv[leaf_name(path)]
        return big.at[:, :pre_len].set(seg.astype(big.dtype))

    row_cache = jax.tree_util.tree_map_with_path(put, row_cache)
    row_cache = _set_index_counters(row_cache, jnp.int32(pre_len))
    logits, mutated = model.apply(
        {"params": params, "cache": row_cache}, suffixes, train=False,
        mutable=["cache"],
    )
    r = suffixes.shape[0]
    ar = jnp.arange(r)
    logits = logits[ar, last].astype(jnp.float32)
    row_seen = None
    if repetition_penalty != 1.0:
        hits = jnp.zeros((r, model.vocab_size), jnp.int32)
        hits = hits.at[ar[:, None], fullp].add(valid.astype(jnp.int32))
        row_seen = hits > 0
    tok = sample_logits(
        logits, rng, temperature=temperature, top_k=top_k, top_p=top_p,
        min_p=min_p, repetition_penalty=repetition_penalty, seen=row_seen,
    )
    if row_seen is not None:
        row_seen = row_seen.at[ar, tok].set(True)
    return mutated["cache"], tok, row_seen


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_primed_rows(cache, kv, rows):
    """Land primed rows — prompts whose prefill ran on ANOTHER replica
    (the prefill/decode role split) — into batch rows `rows` in one
    donated update. kv: {leaf-name: [R, Pbucket, ...]} right-padded
    primed K/V; positions past each row's true prompt length carry
    zeros, which land beyond the committed count and stay unreachable
    (the stale-K/V invariant). Index counters pass through, exactly as
    in `_scatter_rows`."""

    def merge(path, big):
        if is_index_leaf(path):
            return big
        seg = kv[leaf_name(path)]
        return big.at[rows, :seg.shape[1]].set(seg.astype(big.dtype))

    return jax.tree_util.tree_map_with_path(merge, cache)


@functools.partial(jax.jit, static_argnames=("model",), donate_argnums=(1,))
def _paged_prefill_chunk(model, cache, params, tokens, idx, take, last_in,
                         prev):
    """ONE chunk of paged prefill over the FULL batch — the pad-ladder
    compile collapse.

    The dense path compiles a prefill per (prompt bucket, wave width)
    cell; under paging the writes scatter through each row's block
    table, so admission instead feeds prompts through this single
    [B, C] program chunk-by-chunk: `tokens` carries chunk j of each
    admitting row's suffix (pad elsewhere), `idx` [B] the chunk's start
    position per row — an admitting row's `pre_len + j*C`, an exhausted
    or non-wave row's committed count. Any shape of (prompt length,
    admitting rows) is just a different DATA pattern, so the program
    compiles ONCE per batcher (tests/test_paged.py pins it).

    Junk discipline: rows not writing real tokens this chunk still
    write C pad K/V cells, all beyond their committed count — into
    their own allocated-uncommitted cells (overwritten position-exactly
    before any validity mask reaches them) or the null block. `take`
    marks rows whose TRUE last prompt position falls in this chunk (at
    chunk-local `last_in`); their final-position logits replace their
    slot in the `prev` [B, V] carry, so after the last chunk every
    admitting row's first-token logits are in hand without per-bucket
    gather programs. Donates `cache` like every prefill."""
    cache = _set_index_counters(cache, idx)
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, tokens, train=False,
        mutable=["cache"],
    )
    ar = jnp.arange(tokens.shape[0])
    out = jnp.where(take[:, None],
                    logits[ar, last_in].astype(jnp.float32), prev)
    return mutated["cache"], out


@functools.partial(
    jax.jit,
    static_argnames=("temperature", "top_k", "top_p", "min_p",
                     "repetition_penalty"),
)
def _sample_first(logits, rng, seen, temperature, top_k, top_p, min_p,
                  repetition_penalty):
    """First-token sampling for a paged admission wave: the chunk loop
    above hands back last-position logits; this samples them with the
    full config (presence mask included — `seen` rows are rebuilt host-
    side from prompt ids, the primed-wave idiom). Compiled per padded
    wave width on the usual ladder; tiny (no cache, no model)."""
    tok = sample_logits(
        logits, rng, temperature=temperature, top_k=top_k, top_p=top_p,
        min_p=min_p, repetition_penalty=repetition_penalty, seen=seen,
    )
    if seen is not None:
        seen = seen.at[jnp.arange(tok.shape[0]), tok].set(True)
    return tok, seen


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_primed_blocks(cache, kv, blk):
    """Paged twin of `_scatter_primed_rows`: land shipped host K/V
    (re-chunked to [R, NB, block, ...], dense leaf names) into the pool
    blocks `blk` [R, NB] in one donated update. Slots past a row's
    prompt blocks carry the null block and zero payload — identical-
    value duplicate writes, so scatter order never matters. Block
    tables and index counters pass through (the host uploaded tables
    already)."""

    def merge(path, big):
        name = str(getattr(path[-1], "key", path[-1]))
        if is_index_leaf(path) or name == "block_table":
            return big
        seg = kv[_paged.pool_leaf_name(leaf_name(path))]
        return big.at[blk].set(seg.astype(big.dtype))

    return jax.tree_util.tree_map_with_path(merge, cache)


@dataclasses.dataclass
class PrimedRequest:
    """A prefill-role replica's hand-off unit: everything a decode
    replica needs to admit the request without running the prompt
    forward itself. `kv` holds HOST arrays ({leaf-name: [P, ...]}), so
    the object is process-portable — inference/router.py ships it as
    JSON between replica processes. Greedy decoding of a primed request
    is bit-identical to a locally-admitted one; at temperature > 0 the
    first token was drawn from the PREFILL replica's key stream."""

    prompt: np.ndarray          # [P] int32 token ids
    first_token: int            # sampled at prefill time (pending, unfed)
    max_new_tokens: int
    kv: dict                    # leaf-name -> np.ndarray [P, ...]


def _zeros_program(shapes):
    """ONE compiled, argument-free program that returns a zero tree of
    `shapes` (a pytree of ShapeDtypeStruct): a single launch whose
    outputs the runtime allocates inside that execution, where mapping
    `jnp.zeros` over the leaves issues a program (and an allocation) per
    leaf — some 330 for a 36-layer row cache, 61 ms of host time a wave
    with the chip idle. Every call returns NEW buffers (XLA keeps the
    entry computation's outputs distinct), so the result can be donated."""
    return jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes))


def _normalize_buckets(buckets, max_len: int) -> tuple:
    """Sorted prefill bucket lengths; default powers of two up to
    max_len. Every prompt pads up to the smallest bucket that fits."""
    if buckets is None:
        buckets, b = [], 8
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
    # clamp to max_len: a larger bucket would pad past the row cache and
    # fail at ADMISSION (after the request left the queue), not here
    out = tuple(sorted({min(int(b), max_len) for b in buckets}))
    if not out or out[-1] < max_len:
        raise ValueError(
            f"prompt_buckets must cover max_len {max_len}; got {out}"
        )
    return out


def _bucketed(prompt: np.ndarray, buckets: tuple, pad_id: int):
    """(padded [1, bucket] int32 prompt, true-last-position index)."""
    p = prompt.size
    bucket = next(b for b in buckets if b >= p)
    padded = np.full((1, bucket), pad_id, np.int32)
    padded[0, :p] = prompt
    return jnp.asarray(padded), p - 1


def _ladder_depth(cap: int, bound: int) -> int:
    """Scan depth for this round: the largest value from the ladder
    {1, 2, 4, ..., cap} (cap always included) that is <= bound. Host
    bookkeeping picks `bound` from remaining budgets, so compiles stay
    O(log cap) while K still shrinks to 1 near a row completion."""
    bound = min(cap, max(1, bound))
    if bound >= cap:
        return cap
    k = 1
    while k * 2 <= bound:
        k *= 2
    return k


class _PriorityDeque:
    """The batcher's request queue: one FIFO lane per priority class,
    drained highest-priority-first (`interactive` > `batch` >
    `best_effort`, FIFO within a class). Presents the deque surface the
    admission/accounting code already speaks — truthiness, `len`,
    iteration (in drain order), `popleft` — so single-class traffic
    behaves exactly like the plain deque it replaces."""

    def __init__(self):
        self._lanes = collections.OrderedDict(
            (p, collections.deque()) for p in _admission.PRIORITIES
        )

    def append(self, item,
               priority: str = _admission.DEFAULT_PRIORITY) -> None:
        self._lanes[priority].append(item)

    def appendleft(self, item,
                   priority: str = _admission.DEFAULT_PRIORITY) -> None:
        """Put a dequeued item BACK at the front of its lane — the
        capacity-gate requeue (the item keeps its FIFO slot; nothing
        behind it in the lane overtakes it)."""
        self._lanes[priority].appendleft(item)

    def popleft(self):
        for lane in self._lanes.values():
            if lane:
                return lane.popleft()
        raise IndexError("pop from an empty priority queue")

    def remove_rid(self, rid: int) -> bool:
        """Drop the queued item with request id `rid` (cancel path)."""
        for lane in self._lanes.values():
            for i, item in enumerate(lane):
                if item[0] == rid:
                    del lane[i]
                    return True
        return False

    def depths(self) -> dict:
        """Per-class queue depth (the /load snapshot detail)."""
        return {p: len(lane) for p, lane in self._lanes.items()}

    def __len__(self) -> int:
        return sum(len(lane) for lane in self._lanes.values())

    def __bool__(self) -> bool:
        return any(self._lanes.values())

    def __iter__(self):
        for lane in self._lanes.values():
            yield from lane


def _pad_wave(r: int, cap: int) -> int:
    """Admission wave sizes ride their own power-of-two ladder (capped at
    the batch size) so `_prefill_rows` compiles O(log B) per bucket, not
    O(B)."""
    k = 1
    while k < r:
        k *= 2
    return min(k, cap)


class _BatcherBase:
    """Machinery shared by `ContinuousBatcher` and
    `SpeculativeContinuousBatcher`: the request queue, per-row host
    bookkeeping (`_take_token`), batched bucket admission (`_admit`
    drives the subclass `_prefill_wave` hook), stats publication, and
    the dispatch/sync accounting that `stats()` hands to the benchmark's
    readers and the regression-guard test.

    Invariant per active row r (the speculative-decoding contract): the
    cache holds K/V for exactly `committed[r]` tokens and `tok[r]` is the
    last generated-but-unfed token.
    """

    _metrics_prefix = "serving/batcher"

    def __init__(self, model, params, batch_size: int, max_len: int,
                 eos_id, pad_id: int, rng, prompt_buckets,
                 role: str = "both", admission_ctl=None):
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {role!r}"
            )
        self._buckets = _normalize_buckets(prompt_buckets, max_len)
        self._model = model
        self._params = params
        self._b = batch_size
        self._max_len = int(max_len)
        # a layer whose window is shorter than `max_len` keeps a ring of
        # `window` cells beside the other layers' slabs: slot = position
        # mod window under the per-row indices the slab uses (what rewinds,
        # shares or re-encodes cells by position is refused for such a
        # model, `_refuse_stateful`); a window no row can outgrow leaves
        # the slab, its band mask and all of those as they were
        self._ring = _layout_of(model, self._max_len).rings
        self._eos = eos_id
        self._pad = pad_id
        self._rng = rng if rng is not None else jax.random.key(0)
        self._role = role

        self._req = [None] * batch_size          # request id or None
        self._out = [[] for _ in range(batch_size)]
        self._budget = np.zeros(batch_size, np.int64)
        self._committed = np.zeros(batch_size, np.int64)
        self._tok = np.full(batch_size, pad_id, np.int64)
        # queue items: (rid, prompt [P] np.int64, budget, primed|None) —
        # `primed` set only for submit_primed() entries (K/V in hand).
        # Drained highest-priority-first; FIFO within a class.
        self._queue: _PriorityDeque = _PriorityDeque()
        # admission policy: caps + drain-rate estimate (defaults read
        # TFDE_ADMIT_*; everything off unless configured)
        self._admission = (admission_ctl if admission_ctl is not None
                           else _admission.AdmissionController())
        self._priority: dict = {}       # rid -> priority class
        self._shed: set = set()         # rids deadline-shed at dequeue
        # times below are integer nanoseconds on spans.now_ns
        self._deadline_at: dict = {}    # rid -> absolute TTFT deadline
        self._submitted_at: dict = {}   # rid -> submit time (TTFT)
        self._first_at: dict = {}       # rid -> first-token time (TPOT)
        # rid -> request trace id; populated ONLY while the trace ring is
        # active AND the submitter handed one over, so the off path pays
        # an empty-dict truthiness check and nothing else
        self._trace_ids: dict = {}
        self._next_id = 0
        self._rounds = 0         # decode ticks run
        self._generated = 0      # every delivered token (incl. prefill 1st)
        self._dispatches = 0     # jitted-program / eager-op invocations
        self._syncs = 0          # blocking device->host fetches
        # the step's own account (_PHASE_KEYS): spans add the times, the
        # boundaries add the counts; stats() returns it
        self._phase = dict.fromkeys(_PHASE_KEYS, 0)
        if role != "both":
            _refuse_stateful(model, f"role={role!r} (the primed hand-off ships "
                             f"K/V by position)", self._max_len)
        # what one decode tick cannot avoid reading of the parameters
        self._param_bytes = _count_params(params)[1]
        # first tokens fetched in this step and not yet handed back: how
        # many, and the sum of the times they reached the host
        self._held_n = 0
        self._held_at_ns = 0
        # per-request incremental delivery (router/SSE): off by default —
        # run()/step() consumers read completions, not partials, and an
        # unread stream entry would leak
        self._track_progress = False
        self._stream: dict = {}  # rid -> {"tokens", "taken", "done"}
        # paged KV (TFDE_PAGED_KV): only ContinuousBatcher implements the
        # block-pool layout; the flag lives on the base so the shared
        # admission/step machinery can branch safely from any subclass
        self._paged = False
        # recompile-sentinel fingerprint tag + the memory-ledger program
        # names this instance already registered (one interrogation per
        # pad-ladder bucket, not per wave)
        self._rc_tag = next(_BATCHER_TAGS)
        self._mem_programs: set = set()
        # the scan is traced where the memory ledger interrogates it, just
        # before its watch opens: by name that trace is serve/decode's too
        _recompile.site("serve/decode", stable=True).claim(
            _decode_scan.__name__)
        # (model, wave width, cache length, kv_quant) -> the compiled
        # zero-fill program of that row cache (`_zero_rows`)
        self._zero_programs: dict = {}
        # KV-capacity observability (observability/capacity.py): the
        # ledger/headroom pair is built by the subclass once its slab
        # exists (`_init_capacity`); the usage meter is per-batcher and
        # live immediately (its JSONL log arms lazily via TFDE_USAGE_LOG
        # or the owning ReplicaServer's model_dir)
        self._ledger = None
        self._cap_model = None
        self._usage = _capacity.UsageMeter()
        # serving-side bounded capture: armed via attach_profiler /
        # POST /profile, driven once per step from the decode-round hook
        self._profiler = None

    def attach_profiler(self, profiler) -> None:
        """Give this batcher a RoundWindowProfiler (observability/profiler);
        armed windows open/close on decode-round boundaries."""
        self._profiler = profiler

    def _profiler_round(self, traced) -> None:
        if self._profiler is not None:
            self._profiler.on_round(self._rounds, traces=traced or None)

    #: subclasses that implement `_primed_wave` + `prime` flip this
    _accepts_primed = False

    # -- public -------------------------------------------------------------
    @property
    def idle(self) -> bool:
        return not self._queue and all(r is None for r in self._req)

    @property
    def free_rows(self) -> int:
        return sum(r is None for r in self._req)

    @property
    def role(self) -> str:
        return self._role

    @property
    def outstanding_tokens(self) -> int:
        """Remaining output-token budget across active rows plus the
        queue — the router's least-loaded placement signal (exported as
        a serving gauge via `_publish_stats`)."""
        active = sum(
            int(self._budget[r]) for r in range(self._b)
            if self._req[r] is not None
        )
        return active + sum(int(b) for _rid, _p, b, _pr in self._queue)

    @property
    def queued_tokens(self) -> int:
        """Output-token backlog of QUEUED requests only (active rows are
        already paid for) — the admission cap's and the drain-rate
        estimate's unit."""
        return sum(int(b) for _rid, _p, b, _pr in self._queue)

    @property
    def admission(self) -> "_admission.AdmissionController":
        return self._admission

    @property
    def usage(self) -> "_capacity.UsageMeter":
        return self._usage

    def arm_usage_log(self, model_dir=None) -> None:
        """Late-bind the usage JSONL log (TFDE_USAGE_LOG=on needs a
        model_dir to anchor the file; the ReplicaServer calls this with
        its own)."""
        self._usage.arm(model_dir)

    def _init_capacity(self, cache, decode_model,
                       cells_per_row: Optional[int] = None) -> None:
        """Build the KV occupancy ledger + headroom model from the
        freshly-initialized dense slab and what `decode_model`, the clone
        that made it, says its layers keep there (subclass constructors
        call this once the cache exists). `cells_per_row` defaults to
        max_len; the speculative batcher's slab carries draft slack
        beyond it."""
        cells = int(cells_per_row if cells_per_row is not None
                    else self._max_len)
        layout = _layout_of(decode_model, cells)
        self._ledger = _capacity.CapacityLedger(
            self._b, cells, _capacity.kv_slab_bytes(cache), layout.layers,
            (_moe.held_experts(self._params) if layout.uncapped_experts
             else None),
            census=_capacity.kv_dtype_census(cache))
        self._cap_model = _capacity.CapacityModel(self._ledger)

    def kv_stats(self) -> dict:
        """Current KV occupancy + headroom (the /load and 429 `kv`
        block); refreshes the kv/* gauges as a side effect. Empty dict
        until a subclass wired its slab."""
        if self._ledger is None:
            return {}
        s = self._ledger.observe(self._committed, self._req)
        s.update(self._cap_model.headroom(s))
        return s

    def was_shed(self, rid: int) -> bool:
        """True exactly once for a request that was deadline-shed at
        dequeue — the HTTP layer reads this to turn the empty completion
        into an explicit shed event on the stream."""
        if rid in self._shed:
            self._shed.discard(rid)
            return True
        return False

    def submit(self, prompt, max_new_tokens: int,
               trace: Optional[str] = None,
               priority: Optional[str] = None,
               ttft_deadline_ms: Optional[float] = None) -> int:
        """Queue a request; returns its id. prompt: 1-D int token ids.
        `trace`: the request's distributed-trace id (X-Tfde-Trace),
        recorded on every span event the request generates.
        `priority`: admission class ('interactive' > 'batch' >
        'best_effort'; default interactive) — the queue drains
        highest-priority-first. `ttft_deadline_ms`: shed the request at
        dequeue if its queue wait alone already blew this budget
        (default: the controller's TFDE_ADMIT_TTFT_DEADLINE_MS).
        Raises `admission.QueueFull` when a configured cap is hit."""
        if self._role == "prefill":
            raise RuntimeError(
                "prefill-only replica: use prime() and hand the result to "
                "a decode replica's submit_primed()"
            )
        prompt = self._check_request(prompt, max_new_tokens)
        pr = _admission.validate_priority(priority)
        self._admission_check(int(max_new_tokens))
        rid = self._enqueue(prompt, int(max_new_tokens), None, trace,
                            priority=pr, ttft_deadline_ms=ttft_deadline_ms)
        return rid

    def submit_primed(self, primed: PrimedRequest,
                      trace: Optional[str] = None,
                      priority: Optional[str] = None,
                      ttft_deadline_ms: Optional[float] = None) -> int:
        """Queue a request whose prefill already ran on a prefill-role
        replica (`prime()`); only the K/V scatter and decode happen
        here. Returns the local request id."""
        if not self._accepts_primed:
            raise RuntimeError(
                f"{type(self).__name__} does not accept primed requests"
            )
        _refuse_stateful(self._model, "submit_primed() (K/V shipped by "
                         "position)", self._max_len)
        if self._role == "prefill":
            raise RuntimeError("prefill-only replica cannot decode")
        prompt = self._check_request(primed.prompt, primed.max_new_tokens)
        pr = _admission.validate_priority(priority)
        self._admission_check(int(primed.max_new_tokens))
        return self._enqueue(prompt, int(primed.max_new_tokens), primed,
                             trace, priority=pr,
                             ttft_deadline_ms=ttft_deadline_ms)

    def _admission_check(self, budget: int) -> None:
        """One admission gate for both submit paths: queue caps plus —
        when a ledger is wired and TFDE_ADMIT_KV_HEADROOM set — the
        memory gate, with the kv snapshot riding any rejection and the
        outstanding decode backlog as the Retry-After basis when
        headroom (not queue depth) binds."""
        if (self._ledger is not None
                and self._admission.min_headroom_rows):
            kv = self.kv_stats()
            self._admission.check(
                len(self._queue), self.queued_tokens, budget,
                headroom_rows=kv.get("headroom_rows"), kv=kv,
                drain_tokens=self.outstanding_tokens)
        else:
            self._admission.check(len(self._queue), self.queued_tokens,
                                  budget)

    def enable_progress(self) -> None:
        """Track per-request incremental tokens for `take_progress` (the
        router's SSE feed). Applies to requests submitted after the
        call."""
        self._track_progress = True

    def take_progress(self, rid: int):
        """(new tokens since the last take, done flag) for an in-flight
        request. Requires `enable_progress()` before submit. A finished
        request's entry is dropped by the take that drains it."""
        ent = self._stream[rid]
        toks = ent["tokens"][ent["taken"]:]
        ent["taken"] += len(toks)
        if ent["done"] and ent["taken"] == len(ent["tokens"]):
            del self._stream[rid]
        return toks, ent["done"]

    def run(self) -> list:
        """Step until idle; returns every completion in finish order."""
        done = []
        while not self.idle:
            done.extend(self.step())
        return done

    def cancel(self, rid: int) -> bool:
        """Abandon a request whose consumer is gone (router client
        disconnect): drop it from the queue, or free its row so the
        decode scan stops spending ticks on it. Partial output is
        discarded. Returns whether the request was found in flight."""
        self._stream.pop(rid, None)
        self._submitted_at.pop(rid, None)
        self._first_at.pop(rid, None)
        self._priority.pop(rid, None)
        self._deadline_at.pop(rid, None)
        self._shed.discard(rid)
        tid = self._trace_ids.pop(rid, None)
        if tid is not None:
            _trace.event("serve/cancelled", trace=tid, rid=rid)
        if self._queue.remove_rid(rid):
            self._usage.finish(rid, 0, outcome="cancelled")
            return True
        for r in range(self._b):
            if self._req[r] == rid:
                self._usage.finish(rid, len(self._out[r]),
                                   outcome="cancelled")
                self._release_row(r)
                self._req[r] = None
                self._out[r] = []
                self._budget[r] = 0
                self._committed[r] = 0
                self._tok[r] = self._pad
                # the device loop state still thinks the row is live;
                # force a rebuild so its done flag flips before the next
                # scan
                self._mark_dirty()
                return True
        return False

    def _check_request(self, prompt, max_new_tokens: int) -> np.ndarray:
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens > self._max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the batcher's max_len "
                f"{self._max_len}"
            )
        self._validate_submit(prompt, max_new_tokens)
        return prompt

    def _enqueue(self, prompt: np.ndarray, budget: int, primed,
                 trace: Optional[str] = None,
                 priority: str = _admission.DEFAULT_PRIORITY,
                 ttft_deadline_ms: Optional[float] = None) -> int:
        rid = self._next_id
        self._next_id += 1
        self._queue.append((rid, prompt, budget, primed), priority=priority)
        _boot.note_first_admit()
        now = now_ns()
        self._submitted_at[rid] = now
        self._priority[rid] = priority
        self._usage.begin(rid, int(prompt.size), priority)
        dl = (float(ttft_deadline_ms) if ttft_deadline_ms is not None
              else self._admission.ttft_deadline_ms)
        if dl and dl > 0:
            self._deadline_at[rid] = now + int(dl * 1e6)
        if self._track_progress:
            self._stream[rid] = {"tokens": [], "taken": 0, "done": False}
        if trace is not None and _trace.active():
            self._trace_ids[rid] = trace
            _trace.event("serve/queued", trace=trace, rid=rid,
                         prompt_tokens=int(prompt.size), budget=int(budget),
                         primed=primed is not None, priority=priority,
                         queue_depth=len(self._queue))
        return rid

    def serve_metrics(self, port: int = 0, aggregator=None):
        """Start a /metrics endpoint next to this batcher (exposition.py);
        returns the MetricsServer (read `.port` back when port=0). Pass a
        ClusterAggregator to also accept worker pushes at /push — the
        multi-host serving deployment's one-scrape fleet view."""
        from tfde_tpu.observability.exposition import serve_metrics

        return serve_metrics(port=port, aggregator=aggregator)

    def _publish_stats(self) -> None:
        """Mirror stats() into the metric registry so serving throughput
        rides the /metrics and JSONL exposition paths."""
        reg = metrics.default_registry()
        for k, v in self.stats().items():
            reg.gauge(f"{self._metrics_prefix}/{k}").set(v)
        reg.gauge(f"{self._metrics_prefix}/queue_depth").set(len(self._queue))
        reg.gauge(f"{self._metrics_prefix}/free_rows").set(self.free_rows)
        reg.gauge(f"{self._metrics_prefix}/outstanding_tokens").set(
            self.outstanding_tokens
        )
        reg.gauge(f"{self._metrics_prefix}/queued_tokens").set(
            self.queued_tokens
        )
        reg.gauge(f"{self._metrics_prefix}/drain_rate_tps").set(
            self._admission.drain_rate_tps
        )
        # occupancy + headroom ride every stats publication (including
        # idle steps), so the kv/* gauges track the slab per round
        self.kv_stats()

    # -- the step and its account -------------------------------------------
    def _span(self, name: str, ns: str, n: Optional[str] = None,
              histogram: bool = False) -> span:
        """A span that adds its time to this batcher's ledger under `ns`
        (and counts itself under `n`): the one timer the serving loop
        has. Leaf spans keep out of the registry's histograms."""
        return span(name, ledger=self._phase, ns=ns, n=n,
                    histogram=histogram)

    def step(self) -> list:
        """Admit into free rows, then run one decode round for the whole
        batch (`_round`: a fused scan of up to `scan_depth` ticks, or one
        speculative round); returns [(request_id, tokens 1-D np.int32),
        ...] that finished now."""
        with self._span("serving/step", "step_ns", "steps") as whole:
            with self._span("serving/admit", "admit_ns", histogram=True):
                finished = self._admit()
            active = [r for r in range(self._b) if self._req[r] is not None]
            if active:
                self._round(active, finished)
            else:
                with self._span("serving/emit", "emit_ns"):
                    self._publish_stats()
        if self._held_n:
            # a streaming client sees a first token when the step that
            # fetched it returns: the decode round it waits out
            self._phase["first_token_hold_ns"] += (
                self._held_n * whole.t1_ns - self._held_at_ns)
            self._held_n = self._held_at_ns = 0
        return finished

    def _round(self, active: list, finished: list) -> None:
        """One decode round over the `active` rows inside a
        `serving/decode` span, then their tokens taken inside a
        `serving/emit` span that ends with `_publish_stats`; completions
        are appended to `finished`."""
        raise NotImplementedError

    def _close_round(self, decode: span, traced: list, depth: int,
                     rows: int, n_emitted: int) -> None:
        """What a round reports once its tokens are taken: the time from
        the decode span's start to now, before `_publish_stats`."""
        dt = (now_ns() - decode.t0_ns) * 1e-9
        if traced:
            _trace.event("serve/decode_round", traces=traced, dur=dt,
                         depth=depth, rows=rows, emitted=n_emitted)
        if n_emitted:
            metrics.default_registry().histogram(
                "serving/ms_per_token"
            ).observe(dt * 1e3 / n_emitted)
            self._admission.note_drain(n_emitted, dt)

    def _fetch_first(self, tok) -> np.ndarray:
        """A wave's one blocking fetch: its rows' first tokens."""
        with self._span("serving/prefill/fetch", "device_wait_ns"):
            tok_np = _fetch(tok)
        self._syncs += 1
        return tok_np

    def _zero_rows(self, model, rp: int, length: int, kv_quant=None):
        """FRESH zero row cache of `model` ([rp, length] budget) for a
        donated prefill call (the donation consumed the last one —
        reusing it would hand jit a deleted buffer): one launch of the
        program compiled for this (model, width, length, kv_quant) at
        its first wave."""
        with self._span("serving/prefill/template", "prefill_template_ns"):
            key = (model, rp, length, kv_quant)
            program = self._zero_programs.get(key)
            if program is None:
                program = self._zero_programs[key] = _zeros_program(
                    jax.eval_shape(functools.partial(
                        init_cache, model, rp, length,
                        rolling=self._ring, kv_quant=kv_quant)))
            self._dispatches += 1
            return program()

    def _kv_read_bytes(self, active: list) -> int:
        """Bytes of the cached state a decode tick of the `active` rows
        cannot avoid reading (every committed cell of a K/V slab; the live
        window and the visible summaries of attention='eva'; a state and
        back), from shapes."""
        return int(round(self._ledger.read_bytes(self._committed[active])))

    # -- hooks --------------------------------------------------------------
    def _validate_submit(self, prompt: np.ndarray,
                         max_new_tokens: int) -> None:
        validate_budget(self._model, int(prompt.size), max_new_tokens)

    def _prefill_wave(self, prompts: np.ndarray, last: np.ndarray,
                      rows: np.ndarray, plens: np.ndarray,
                      n: int) -> np.ndarray:
        """Prefill + scatter one padded admission wave; returns the [R]
        first sampled tokens (host ints). Rows past `n` are ladder
        padding (duplicates of row 0). Subclass-specific: which model(s),
        which caches, which sampling config."""
        raise NotImplementedError

    def _release_row(self, r: int) -> None:
        """Row `r` just left the batch (completion / cancel) — return
        any per-row cache resources. The dense slab has none; the paged
        batcher frees the row's pool blocks and re-points its table at
        the null block."""

    def _admission_cost(self, item) -> int:
        """Pool blocks queue `item` will claim at admission (0 for the
        dense slab, whose per-row cost is the row itself)."""
        return 0

    def _admit_capacity(self, need: int) -> bool:
        """Can the cache grant `need` more blocks right now (free list +
        evictable trie)? The dense slab always can — a free row IS the
        capacity. On False the item goes back to the FRONT of its lane
        and admission stalls until a completion frees blocks."""
        return True

    def _on_capacity_stall(self) -> None:
        """Admission just stalled on cache capacity — a subclass may use
        the pause for bounded maintenance (the paged batcher's
        stall-triggered pool defrag). The dense slab has nothing to
        compact."""

    def _admission_cells(self, kind: str, key, item) -> tuple:
        """(allocated cells, real tokens) one admitted request cost the
        prefill — the ledger's pad-waste unit. Dense: the pad-ladder
        bucket vs the true prompt (suffix for warm groups). The paged
        batcher overrides with block-granular numbers."""
        _rid, prompt, _budget, _pr, _x = item
        if kind == "warm":
            return int(key[1]), int(prompt.size) - int(key[0])
        return int(key), int(prompt.size)

    # -- internals ----------------------------------------------------------
    def _take_token(self, r: int, t: int) -> list:
        """Record a sampled token for row r; frees the row on completion."""
        self._out[r].append(t)
        self._budget[r] -= 1
        self._tok[r] = t
        self._generated += 1
        ent = self._stream.get(self._req[r]) if self._track_progress else None
        if ent is not None:
            ent["tokens"].append(int(t))
        if self._budget[r] <= 0 or (self._eos is not None and t == self._eos):
            if ent is not None:
                ent["done"] = True
            rid = self._req[r]
            n = len(self._out[r])
            t1 = self._first_at.pop(rid, None)
            if t1 is not None and n > 1:
                # decode-side TPOT: first token -> last token, per decode
                # step (the SLO layer's second latency axis)
                tpot_ms = (now_ns() - t1) / 1e6 / (n - 1)
                metrics.default_registry().histogram(
                    "serving/tpot_ms").observe(tpot_ms)
                tid = self._trace_ids.get(rid)
                if tid is not None:
                    _trace.note_exemplar("serving/tpot_ms", tpot_ms, tid)
            tid = self._trace_ids.pop(rid, None)
            if tid is not None:
                _trace.event("serve/done", trace=tid, rid=rid, tokens=n,
                             eos=bool(self._eos is not None
                                      and t == self._eos))
            self._priority.pop(rid, None)
            self._deadline_at.pop(rid, None)
            self._usage.finish(rid, n, outcome="ok")
            done = (rid, np.asarray(self._out[r], np.int32))
            self._release_row(r)
            self._req[r] = None
            self._out[r] = []
            self._committed[r] = 0
            self._tok[r] = self._pad
            return [done]
        return []

    def _plan_wave(self, wave) -> list:
        """Partition one admission wave into prefill groups:
        [(kind, key, items)] where each item is (rid, prompt, budget,
        primed, extra). Base kinds: 'cold' (full prefill) grouped by
        prompt bucket, and 'primed' (K/V in hand — scatter only) also by
        bucket. `ContinuousBatcher` adds 'warm' prefix-cache groups, with
        the matched K/V as `extra`."""
        cold: dict = collections.OrderedDict()
        primed: dict = collections.OrderedDict()
        for rid, prompt, budget, pr in wave:
            bucket = next(b for b in self._buckets if b >= prompt.size)
            dst = primed if pr is not None else cold
            dst.setdefault(bucket, []).append(
                (rid, prompt, budget, pr, None)
            )
        plans = [("cold", b, g) for b, g in cold.items()]
        plans += [("primed", b, g) for b, g in primed.items()]
        return plans

    def _admit_group(self, kind: str, key, group, rows) -> np.ndarray:
        """Run one admission group under the recompile sentinel: every
        prefill wave is a watched jit entry point fingerprinted by
        (batcher, group key, padded wave width), so a mid-serve recompile
        lands in the compile/serve/prefill_<kind>/* counters, the flight
        ring, and — when the wave carries traced requests — the PR-9
        waterfall."""
        rp = _pad_wave(len(group), self._b)
        traces = None
        if self._trace_ids:
            tids = [t for it in group
                    if (t := self._trace_ids.get(it[0])) is not None]
            traces = tids or None
        site = _recompile.site(f"serve/prefill_{kind}")
        with site.watch(self._rc_tag, kind, key, rp, traces=traces):
            return self._run_group(kind, key, group, rows)

    def _run_group(self, kind: str, key, group, rows) -> np.ndarray:
        """Dispatch one admission group to its wave implementation —
        the seam subclasses extend with new admission kinds (the
        sentinel wrapper above stays shared)."""
        if kind == "cold":
            return self._cold_wave(key, group, rows)
        if kind == "primed":
            return self._primed_wave(key, group, rows)
        raise ValueError(f"unknown admission kind {kind!r}")

    def _mem_register(self, name: str, fn, args, donated=None) -> None:
        """Register one serving program with the memory ledger, once per
        (program name, shape signature) per batcher — publishes the
        mem/<name>/* peak/argument/output gauges for every pad-ladder
        bucket the server actually compiles."""
        if name in self._mem_programs:
            return
        self._mem_programs.add(name)
        # the linter rides the same seam: every pad-ladder bucket the
        # server compiles is offered for interrogation (no-op unless
        # armed — tools/lintgate.py / TFDE_HLOLINT)
        _hlolint.offer(name, fn, args=args, donated=donated)
        if _memwatch.enabled():
            _memwatch.register(name, fn, args=args, donated=donated)

    def _cold_wave(self, bucket: int, group, rows) -> np.ndarray:
        n = len(group)
        rp = _pad_wave(n, self._b)
        with self._span("serving/prefill/pack", "prefill_pack_ns"):
            prompts = np.full((rp, bucket), self._pad, np.int32)
            last = np.zeros(rp, np.int32)
            plens = np.zeros(rp, np.int32)
            rows_pad = np.asarray(rows + [rows[0]] * (rp - n), np.int32)
            for i in range(rp):
                # wave padding repeats row 0's request verbatim: the
                # duplicate prefill K/V is bit-identical (prefill is
                # row-independent and deterministic), so the duplicate
                # cache-scatter writes never race on ordering
                _rid, prompt, _budget, _pr, _x = group[i if i < n else 0]
                prompts[i, :prompt.size] = prompt
                last[i] = prompt.size - 1
                plens[i] = prompt.size
        return self._prefill_wave(prompts, last, rows_pad, plens, n)

    def _primed_wave(self, bucket: int, group, rows) -> np.ndarray:
        raise NotImplementedError(
            "primed admission requires a subclass with _accepts_primed"
        )

    def _admit(self) -> list:
        """Fill free rows from the queue, a GROUP WAVE at a time: every
        freed row whose next request shares an admission group (cold
        prompt bucket / warm prefix length / primed bucket) prefills in
        one call and lands with one multi-row scatter. The prefill
        samples each row's first token in-program (generate's prefill
        contract), so every active row uniformly holds one pending token
        afterwards. A request finishing on its first token (budget 1 /
        instant EOS) frees its row for the next queued request within
        the same call."""
        finished = []
        reg = metrics.default_registry()
        stalled = False
        while self._queue and self.free_rows and not stalled:
            free = [r for r in range(self._b) if self._req[r] is None]
            wave = []
            reserved = 0
            while self._queue and len(wave) < len(free):
                item = self._queue.popleft()
                # deadline shed happens HERE, at dequeue: a request whose
                # queue wait alone already blew its TTFT budget is dead
                # on arrival to the client — prefilling it would spend a
                # wave on tokens nobody is waiting for
                if self._maybe_shed(item):
                    continue
                # block-capacity gate (paged only): a request whose
                # lifetime blocks don't fit the pool right now goes BACK
                # to the front of its lane — admission stalls (head-of-
                # line, deliberately: skipping ahead would starve big
                # requests forever) until completions free blocks
                need = self._admission_cost(item)
                if need and not self._admit_capacity(reserved + need):
                    self._requeue_front(item)
                    reg.counter("serving/admit_capacity_stall").incr()
                    self._on_capacity_stall()
                    stalled = True
                    break
                reserved += need
                wave.append(item)
            taken = 0
            for kind, key, group in self._plan_wave(wave):
                n = len(group)
                rows = free[taken:taken + n]
                taken += n
                with self._span("serving/prefill", "prefill_ns",
                                "prefill_waves", histogram=True) as wave:
                    toks = self._admit_group(kind, key, group, rows)
                # admission waves in the flight ring: one event per wave
                # (not per request), enough to reconstruct the admit/queue
                # rhythm in a serving post-mortem
                flightrec.record(
                    "admit", rows=n, group=kind,
                    key=list(key) if isinstance(key, tuple) else int(key),
                    queue_depth=len(self._queue),
                )
                # the wave's two clock reads stamp everything it admitted
                t_wave, now = wave.t0_ns, wave.t1_ns
                if self._trace_ids:
                    tids = [self._trace_ids.get(it[0]) for it in group]
                    if any(tids):
                        # one wave slice tagged with EVERY member trace:
                        # the waterfall shows who shared the prefill
                        _trace.event(
                            f"serve/prefill_{kind}", traces=tids,
                            ts=wave.wall, dur=wave.dur_ns * 1e-9, rows=n,
                            key=list(key) if isinstance(key, tuple)
                            else int(key),
                        )
                # pad-ladder accounting: the prefill program computed/
                # wrote `alloc` cells per row (the group's bucket; for
                # warm groups only the SUFFIX bucket — the prefix K/V
                # landed unpadded; for paged groups the FRESH BLOCKS
                # granted, so the histogram reads intra-block slack), of
                # which each request's true token count is real — the
                # rest is the waste the ledger sizes paged-KV's win by
                phase, rp = self._phase, _pad_wave(n, self._b)
                phase["admitted"] += n
                phase["prefill_rows_padded"] += rp
                # the first tokens are on the host; the step's return
                # hands them back (first_token_hold_ns)
                self._held_n += n
                self._held_at_ns += n * now
                for i, (rid, prompt, budget, _pr, _x) in enumerate(group):
                    r = rows[i]
                    self._req[r] = rid
                    self._out[r] = []
                    self._budget[r] = budget
                    self._committed[r] = prompt.size
                    alloc, used = self._admission_cells(kind, key, group[i])
                    if self._ledger is not None:
                        self._ledger.note_admission(kind, alloc, int(used))
                        self._ledger.note_commit(0, int(prompt.size),
                                                 decoding=False)
                    phase["prefill_tokens"] += int(used)
                    # ladder padding repeats row 0, cells and all
                    copies = 1 + rp - n if i == 0 else 1
                    phase["prefill_cells"] += copies * int(alloc)
                    self._usage.admitted(rid)
                    t0 = self._submitted_at.pop(rid, None)
                    self._first_at[rid] = now
                    # cold-start edge: the boot ledger's first served
                    # token (idempotent after the first request)
                    _boot.note_first_token()
                    if t0 is not None:
                        # the TTFT decomposition:
                        # queue_wait (submit -> wave start) + prefill
                        # (the serving/prefill span) = first token
                        phase["queue_wait_ns"] += t_wave - t0
                        queue_ms = (t_wave - t0) / 1e6
                        ttft_ms = (now - t0) / 1e6
                        reg.histogram("serving/queue_wait_ms").observe(
                            queue_ms
                        )
                        reg.histogram("serving/ttft_ms").observe(ttft_ms)
                        tid = self._trace_ids.get(rid)
                        if tid is not None:
                            _trace.event(
                                "serve/first_token", trace=tid, rid=rid,
                                kind=kind, ttft_ms=round(ttft_ms, 3),
                                queue_wait_ms=round(queue_ms, 3),
                            )
                            _trace.note_exemplar("serving/ttft_ms",
                                                 ttft_ms, tid)
                    finished.extend(self._take_token(r, int(toks[i])))
            self._mark_dirty()
        return finished

    def _requeue_front(self, item) -> None:
        """Put a dequeued-but-not-admittable item back at the head of
        its priority lane (capacity stall — nothing overtakes it)."""
        self._queue.appendleft(
            item,
            priority=self._priority.get(item[0],
                                        _admission.DEFAULT_PRIORITY),
        )

    def _maybe_shed(self, item) -> bool:
        """Deadline/TTL shedding: True when `item`'s queue wait already
        exceeds its TTFT deadline — the request is dropped (no prefill),
        its stream entry flips to done+shed, and `was_shed` answers once
        so the HTTP layer can report it explicitly."""
        rid, _prompt, budget, _pr = item
        dl = self._deadline_at.get(rid)
        if dl is None:
            return False
        now = now_ns()
        if now <= dl:
            return False
        pr = self._priority.pop(rid, _admission.DEFAULT_PRIORITY)
        self._deadline_at.pop(rid, None)
        t0 = self._submitted_at.pop(rid, None)
        self._first_at.pop(rid, None)
        waited_ms = (now - t0) / 1e6 if t0 is not None else None
        self._shed.add(rid)
        ent = self._stream.get(rid)
        if ent is not None:
            ent["done"] = True
            ent["shed"] = True
        reg = metrics.default_registry()
        reg.counter("serving/shed_expired").incr()
        reg.counter(f"serving/shed_{pr}").incr()
        reg.counter("serving/shed_tokens").incr(int(budget))
        self._usage.finish(rid, 0, outcome="shed")
        tid = self._trace_ids.pop(rid, None)
        if tid is not None:
            _trace.event("serve/shed", trace=tid, rid=rid, priority=pr,
                         waited_ms=round(waited_ms, 3)
                         if waited_ms is not None else None)
        flightrec.record("shed", rid=rid, priority=pr,
                         waited_ms=waited_ms, budget=int(budget))
        return True

    def _mark_dirty(self) -> None:
        """Admission invalidated the device-resident loop state (if the
        subclass keeps any)."""


class ContinuousBatcher(_BatcherBase):
    """Fixed-batch continuous serving loop over a causal LM.

    model/params: a decode-capable model (GPT family) and its params.
    batch_size: resident decode rows. max_len: per-row cache budget
    (prompt + generated must fit). scan_depth: ceiling K on fused decode
    ticks per host round-trip (see the module docstring; 1 restores the
    one-tick-per-step behavior). The sampling config is fixed per
    batcher, as for `generate`.

    prefix_cache: a `prefix_cache.PrefixCache`, True/int (default
    budget / byte budget), or None to defer to ``TFDE_PREFIX_CACHE`` —
    admissions whose prompt prefix is cached skip straight to suffix
    prefill (`_warm_wave`), bit-identical under greedy decoding.
    role: 'both' (default), 'prefill' (serve `prime()` only — the
    hand-off producer of the prefill/decode split), or 'decode'
    (refuses `prime()`; accepts `submit_primed()` hand-offs alongside
    plain submits). inference/router.py wires these across processes.

    Usage::

        srv = ContinuousBatcher(model, params, batch_size=4, max_len=256)
        rid = srv.submit(prompt_1d, max_new_tokens=64)
        while not srv.idle:
            for req_id, tokens in srv.step():
                ...   # finished requests, completion order

    `step()` admits queued requests into free rows (bucketed wave
    prefill) and runs ONE fused decode scan of up to `scan_depth` ticks;
    it returns the requests finishing on that call. `run()` drains
    everything.
    """

    _metrics_prefix = "serving/batcher"

    def __init__(
        self,
        model,
        params,
        batch_size: int,
        max_len: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: float = 1.0,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        rng: Optional[jax.Array] = None,
        prompt_buckets: Optional[tuple] = None,
        scan_depth: int = 4,
        prefix_cache=None,
        role: str = "both",
        admission_ctl=None,
        paged: Optional[bool] = None,
        pool_blocks: Optional[int] = None,
        kv_quant: Optional[str] = None,
    ):
        if repetition_penalty <= 0.0:
            raise ValueError(
                f"repetition_penalty must be > 0 (1.0 = off), got "
                f"{repetition_penalty}"
            )
        if scan_depth < 1:
            raise ValueError(f"scan_depth must be >= 1, got {scan_depth}")
        super().__init__(model, params, batch_size, max_len, eos_id,
                         pad_id, rng, prompt_buckets, role=role,
                         admission_ctl=admission_ctl)
        # quantized KV cache (TFDE_KV_QUANT, ops/quant.kv_quantize): int8
        # payload + fp32 scale sidecars in every cache layout this batcher
        # builds — the batch slab/pool, the row templates, the prefix trie
        # slices and the primed hand-off all inherit the leaf set from
        # init_cache, so ONE resolution here covers them all. 'fp' (the
        # default) keeps every tree and program byte-identical to before.
        kvq = (knobs.env_choice("TFDE_KV_QUANT") if kv_quant is None
               else str(kv_quant))
        self._kv_quant = None if kvq == "fp" else kvq
        if self._kv_quant is not None:
            _refuse_stateful(model, f"kv_quant={self._kv_quant!r}",
                             self._max_len)
        self._decode_model = _decode_clone(model, rolling=self._ring,
                                           kv_quant=self._kv_quant)
        self._sampling = dict(
            temperature=float(temperature),
            top_k=top_k, top_p=top_p, min_p=min_p,
            repetition_penalty=float(repetition_penalty),
        )
        self._scan_depth = int(scan_depth)
        # presence mask for the repetition penalty (per row, prompt ids
        # included — the generate() convention); lives ON DEVICE and is
        # threaded through the fused scan, so steady-state ticks ship no
        # [B, vocab] host copies and no host-driven scatters
        self._seen = (
            jnp.zeros((batch_size, model.vocab_size), bool)
            if repetition_penalty != 1.0 else None
        )
        self._vocab = model.vocab_size

        # paged KV (TFDE_PAGED_KV, inference/paged.py): swap the dense
        # per-row slab for the shared block pool + per-row block tables.
        # `paged=None` defers to the knob; the dense path below stays
        # byte-identical when off. `self._decode_model` remains the
        # DENSE clone either way — prime() and the row templates speak
        # the dense layout (the primed hand-off is layout-agnostic);
        # only the resident batch cache and its programs go paged.
        self._paged = (knobs.env_flag("TFDE_PAGED_KV") if paged is None
                       else bool(paged))
        if self._paged:
            _refuse_stateful(model, "paged=True (the block pool)",
                             self._max_len)
            block = DEFAULT_BLOCK
            self._kv_block = int(block)
            # +1 cell: the decode scan writes one-past-committed for
            # frozen rows, so a full row still has a mapped (or null)
            # slot to take the junk write
            self._nmax = -(-(self._max_len + 1) // block)
            self._chunk = min(
                max(1, knobs.env_int("TFDE_PAGED_PREFILL_CHUNK")),
                self._max_len,
            )
            # default pool: every row can hold a full table, plus the
            # null block — capacity-neutral vs the dense slab; size it
            # DOWN to serve more rows than the dense
            # slab could under the same byte envelope
            nblocks = (int(pool_blocks) if pool_blocks is not None
                       else batch_size * self._nmax + 1)
            if nblocks < self._nmax + 1:
                raise ValueError(
                    f"pool_blocks={nblocks} cannot hold even one "
                    f"max-length row ({self._nmax} blocks + null)"
                )
            self._paged_model = _decode_clone(
                model, paged_blocks=nblocks, kv_block=block,
                kv_quant=self._kv_quant)
            raw = init_cache(model, batch_size, self._max_len,
                             paged_blocks=nblocks, kv_block=block,
                             kv_quant=self._kv_quant)
            self._pool = _paged.BlockPool(nblocks, block)
            self._tables = np.zeros((batch_size, self._nmax), np.int32)
            self._row_blocks: list = [[] for _ in range(batch_size)]
            self._shared_cells = np.zeros(batch_size, np.int64)
            self._tables_dirty = False
            # dense batch shapes (abstract — never materialized) still
            # seed the row templates below: prime() prefills on the
            # dense row layout
            raw_shapes = jax.eval_shape(functools.partial(
                init_cache, model, batch_size, self._max_len,
                kv_quant=self._kv_quant))
        else:
            self._paged_model = None
            self._pool = None
            raw = init_cache(model, batch_size, self._max_len,
                             rolling=self._ring, kv_quant=self._kv_quant)
            raw_shapes = raw
        # the decode scan's model: paged clone when on, dense otherwise
        self._scan_model = self._paged_model or self._decode_model
        # index leaves become [B] vectors ONCE, so the scan carry shape is
        # stable from the first tick (the per-row decode-attention branch)
        self._cache = _set_index_counters(
            raw, np.zeros(batch_size, np.int32)
        )
        # row-cache SHAPES for every admission-wave width on the pad
        # ladder, derived AT CONSTRUCTION: init_cache is a full flax
        # eval_shape trace (~50ms) — paid lazily it lands in the first
        # wave's TTFT. One extra rp=1 trace identifies the batch-carrying
        # leaves (their shapes differ from the batch cache's); the other
        # widths are pure shape substitution. _prefill_rows /
        # _prefill_suffix DONATE their cache argument (no device-side K/V
        # copy per wave), so each wave materializes fresh zeros into the
        # donated slot instead of reusing a live template: one compiled
        # zero-fill program per width (`_zero_rows`), built here from the
        # shapes and compiled at the width's first wave.
        one = jax.eval_shape(functools.partial(
            init_cache, model, 1, self._max_len, rolling=self._ring,
            kv_quant=self._kv_quant))
        rp = 1
        while True:
            self._zero_programs[
                (model, rp, self._max_len, self._kv_quant)
            ] = _zeros_program(jax.tree.map(
                lambda s1, ab, _rp=rp: s1 if s1.shape == ab.shape
                else jax.ShapeDtypeStruct(
                    (_rp,) + s1.shape[1:], s1.dtype
                ),
                one, raw_shapes,
            ))
            if rp >= batch_size:
                break
            rp = min(rp * 2, batch_size)
        # prefix-KV cache: None = every admission cold. Paged mode
        # builds the trie over the POOL (block ids, zero-copy sharing)
        # and registers it as the pool's eviction valve — allocation
        # pressure drains cached prefixes LRU-first
        if self._paged:
            block_bytes = _paged.pool_bytes(self._cache) / float(nblocks)
            self._prefix = _paged.resolve_paged(
                prefix_cache, self._pool, block_bytes)
            if self._prefix is not None:
                self._pool.set_evictor(self._prefix.evict)
        else:
            self._prefix = _resolve_prefix(prefix_cache)
        if self._prefix is not None:
            _refuse_stateful(model, "the prefix cache", self._max_len)
            why = _layout_of(model, self._max_len).uncapped_experts
            if why is not None:
                raise NotImplementedError(
                    f"the prefix cache is not built for this model: {why}")
        # device-resident loop state (tok/idx/budget/done); rebuilt from
        # host bookkeeping whenever admission desyncs it
        self._dev = None
        self._init_capacity(self._cache, self._decode_model)

    # -- public -------------------------------------------------------------
    def stats(self) -> dict:
        """Serving throughput and host-overhead accounting: decode ticks
        run, tokens delivered, tokens/round (mean occupied rows per
        tick), and the per-token host cost — jitted dispatches and
        blocking syncs per generated token (the O(1/K) bound the fused
        scan exists for; tests/test_server.py guards it).

        Beside them the step's own account (`_PHASE_KEYS`), every value
        an int that never falls. Nanoseconds, added by the span at that
        boundary: `step_ns` (all of step(); `steps` of them), `admit_ns`,
        `prefill_ns` (`prefill_waves` group waves) with its leaves
        `prefill_pack_ns`, `prefill_template_ns`, `prefill_run_ns`,
        `prefill_scatter_ns`; `decode_ns` (`scans`) with its leaves
        `decode_upload_ns` (`uploads`) and `decode_dispatch_ns`;
        `device_wait_ns` (both blocking fetches: the only time the host
        waits for the device); `emit_ns` (from the scan's fetch to
        step()'s return). admit + decode + emit make up a step. Counts:
        `admitted` requests (the real rows of the waves) and their
        `queue_wait_ns` (submit to wave start) and `first_token_hold_ns`
        (first token on the host to the return of the step that fetched
        it); `prefill_rows_padded` ladder rows; `prefill_tokens` real prompt
        or suffix tokens of `prefill_cells` computed or written (rows x
        bucket; granted blocks under paging); `decode_least_bytes`, bytes:
        per scan, depth x (the parameters handed to the scan + the
        committed KV cells of its active rows), what the ticks cannot
        avoid reading, computed from shapes and not measured: what the
        rows read is each layer's own formula (models/cache_state.py
        `CacheState.read_bytes`: a slab's committed cells, a ring's
        min(committed, ring), the live window and the visible summaries of
        attention='eva', a state once and back). Each kind of layer that
        is present adds its family of counters, all documented in ONE
        place, `observability/capacity.py` `CapacityLedger` (`EVA_KEYS`,
        `HYBRID_KEYS`, `RING_KEYS`, `LATENT_KEYS`, `GDN_KEYS`); a model of
        slabs alone adds none."""
        g = max(self._generated, 1)
        return {
            "rounds": self._rounds,
            "generated": self._generated,
            "tokens_per_round": self._generated / max(self._rounds, 1),
            "dispatches": self._dispatches,
            "syncs": self._syncs,
            "dispatches_per_token": self._dispatches / g,
            "syncs_per_token": self._syncs / g,
            **self._phase,
            **self._ledger.counters,
        }

    def _round(self, active: list, finished: list) -> None:
        """One fused decode scan (up to `scan_depth` ticks)."""
        depth = self._pick_depth(active)
        traced = (
            [self._trace_ids[rid] for r in active
             if (rid := self._req[r]) in self._trace_ids]
            if self._trace_ids else []
        )
        with self._span("serving/decode", "decode_ns", "scans",
                        histogram=True) as decode:
            if self._paged and self._tables_dirty:
                # a released row's DEVICE table still points at its old
                # blocks, and the frozen row keeps writing pad K/V at
                # its stale position every tick — re-point it at the
                # null block BEFORE any compiled program runs, or a
                # reallocated block would take those writes
                self._cache = _paged.set_block_tables(
                    self._cache, self._tables)
                self._tables_dirty = False
                self._dispatches += 1
            if self._dev is None:
                with self._span("serving/decode/upload", "decode_upload_ns",
                                "uploads"):
                    self._upload_state()
            tok, idx, budget, done = self._dev
            rng = self._rng if self._sampling["temperature"] != 0.0 else None
            read_bytes = self._kv_read_bytes(active)
            self._ledger.note_scan(self._committed[active], depth)
            with self._span("serving/decode/scan", "decode_dispatch_ns"):
                self._mem_register(
                    f"serve/decode/k{depth}",
                    functools.partial(
                        _decode_scan, self._scan_model, depth=depth,
                        eos_id=self._eos, pad_id=self._pad,
                        **self._sampling,
                    ),
                    (self._cache, self._params, tok, idx, budget, done,
                     self._seen, rng),
                    donated=(self._cache, tok, idx, budget, done,
                             self._seen),
                )
                # steady-state decode is the shape-stable site: the depth
                # ladder gives O(log scan_depth) expected signatures, and
                # any repeat-fingerprint miss is an unexpected recompile
                # (the per-token-recompile pathology memgate pins)
                rc = _recompile.site("serve/decode", stable=True)
                with rc.watch(self._rc_tag, depth, traces=traced or None):
                    out = _decode_scan(
                        self._scan_model, self._cache, self._params, tok,
                        idx, budget, done, self._seen, rng, depth=depth,
                        eos_id=self._eos, pad_id=self._pad,
                        **self._sampling,
                    )
            self._dispatches += 1
            (self._cache, tok, idx, budget, done, self._seen, rng,
             toks, emitted, routed) = out
            self._dev = (tok, idx, budget, done)
            if rng is not None:
                self._rng = rng
            with self._span("serving/decode/fetch", "device_wait_ns"):
                # what the layers counted rides the scan's one fetch
                toks_np, emitted_np, *routed = _fetch(
                    (toks, emitted) if routed is None
                    else (toks, emitted, routed))
                routed = routed[0] if routed else None
            self._syncs += 1
            self._ledger.note_routed(routed)
            self._phase["decode_least_bytes"] += (
                self._ledger.scan_least_bytes(self._param_bytes, read_bytes,
                                              depth, routed))
        with self._span("serving/emit", "emit_ns"):
            self._rounds += depth
            self._profiler_round(traced)
            n_emitted = 0
            for r in active:
                row = toks_np[r][emitted_np[r]]
                if row.size == 0:
                    continue
                n_emitted += int(row.size)
                # feeding each pending token committed it; the row's last
                # sample stays pending
                before = int(self._committed[r])
                self._committed[r] += int(row.size)
                self._ledger.note_commit(before, before + int(row.size))
                for t in row:
                    finished.extend(self._take_token(r, int(t)))
            self._close_round(decode, traced, depth, len(active), n_emitted)
            self._publish_stats()

    # -- internals ----------------------------------------------------------
    def _validate_submit(self, prompt, max_new_tokens) -> None:
        if self._seen is not None and (
                prompt.min() < 0 or prompt.max() >= self._vocab):
            # queue-time, not admission-time (the _normalize_buckets rule):
            # jnp .at scatters DROP out-of-bounds updates silently, so an
            # over-vocab id would simply go un-penalized and a negative id
            # would mark the wrong entry via wraparound — no crash, just
            # quietly wrong sampling; refuse here instead
            raise ValueError(
                f"prompt ids must lie in [0, {self._vocab}) when "
                f"repetition_penalty is on; got "
                f"[{int(prompt.min())}, {int(prompt.max())}]"
            )
        if self._paged:
            need = _paged.blocks_for(
                int(prompt.size) + int(max_new_tokens) + 1,
                self._kv_block)
            if need > self._pool.num_blocks - 1:
                # queue-time, like the vocab check: the capacity gate
                # would requeue this request at the lane head forever
                raise ValueError(
                    f"request needs {need} KV blocks but the pool holds "
                    f"{self._pool.num_blocks - 1}; raise pool_blocks or "
                    f"shrink the request"
                )
        super()._validate_submit(prompt, max_new_tokens)

    def _pick_depth(self, active) -> int:
        """K for this scan. Queue waiting: bound by the SOONEST possible
        row completion so admission latency never exceeds one short scan.
        Queue empty: bound by the LONGEST remaining budget so the
        draining tail runs no dead ticks. (EOS completions are not
        host-predictable; a mid-scan EOS freezes the row on device and
        wastes at most K-1 of its ticks.)"""
        if self._scan_depth == 1:
            return 1
        remaining = [int(self._budget[r]) for r in active]
        bound = min(remaining) if self._queue else max(remaining)
        return _ladder_depth(self._scan_depth, bound)

    def _mark_dirty(self) -> None:
        self._dev = None

    def _upload_state(self) -> None:
        """Rebuild the device loop state from host bookkeeping (after
        admission; steady state reuses the scan's own carry outputs)."""
        self._dev = (
            jnp.asarray(self._tok, jnp.int32),
            jnp.asarray(self._committed, jnp.int32),
            jnp.asarray(self._budget, jnp.int32),
            jnp.asarray(np.asarray([r is None for r in self._req])),
        )
        self._dispatches += 1  # the four small host->device transfers

    def _row_template(self, rp: int):
        """FRESH zero row cache (dense layout, this batcher's kv_quant)
        for a donated prefill wave of `rp` rows."""
        return self._zero_rows(self._model, rp, self._max_len,
                               self._kv_quant)

    def _prefill_wave(self, prompts, last, rows, plens, n) -> np.ndarray:
        rp, bucket = prompts.shape
        valid = None
        if self._seen is not None:
            valid = jnp.asarray(
                np.arange(bucket)[None, :] < plens[:, None]
            )
        rng = None
        if self._sampling["temperature"] != 0.0:
            self._rng, rng = jax.random.split(self._rng)
        tmpl = self._row_template(rp)
        with self._span("serving/prefill/run", "prefill_run_ns"):
            prompts_dev = jnp.asarray(prompts)
            last_dev = jnp.asarray(last)
            self._mem_register(
                f"serve/prefill/b{bucket}r{rp}",
                functools.partial(_prefill_rows, self._decode_model,
                                  **self._sampling),
                (tmpl, self._params, prompts_dev, last_dev, valid, rng),
                donated=tmpl,
            )
            row_cache, tok, row_seen, routed = _prefill_rows(
                self._decode_model, tmpl, self._params,
                prompts_dev, last_dev, valid, rng,
                **self._sampling,
            )
            self._dispatches += 1
        with self._span("serving/prefill/scatter", "prefill_scatter_ns"):
            if self._prefix is not None:
                # cold admissions SEED the prefix cache: store each real
                # row's complete prompt blocks before the scatter consumes
                # our interest in row_cache (slices are fresh buffers, so
                # the donated-output aliasing never bites)
                for i in range(n):
                    self._prefix.insert(prompts[i, :plens[i]], row_cache, i)
            self._scatter_wave(row_cache, row_seen, rows, n)
        if routed is None:
            return self._fetch_first(tok)
        # what the layers counted rides the wave's one fetch
        tok, routed = self._fetch_first((tok, routed))
        self._ledger.note_routed(routed)
        return tok

    def _scatter_wave(self, row_cache, row_seen, rows, n: int) -> None:
        """Land a prefilled wave (`rows` padded to the ladder, `n` of them
        real) in the batch cache, and its seen rows in the seen mask."""
        rows_dev = jnp.asarray(rows)
        self._cache = _scatter_rows(self._cache, row_cache, rows_dev)
        self._dispatches += 1
        if row_seen is not None:
            rp = len(rows)
            if rp > n:
                # a ladder-padding row's K/V duplicates row 0 bit-exactly,
                # but its sampled-first-token seen bit can differ under
                # temperature>0 (independent categorical draw per row) —
                # gather duplicates back to row 0's seen so the duplicate
                # scatter indices below write identical values
                sel = np.arange(rp)
                sel[n:] = 0
                row_seen = row_seen[jnp.asarray(sel)]
            self._seen = self._seen.at[rows_dev].set(row_seen)
            self._dispatches += 1

    # -- prefix cache (warm admission) ---------------------------------------
    _accepts_primed = True

    @property
    def prefix_cache(self):
        return self._prefix

    def _plan_wave(self, wave) -> list:
        if self._paged:
            return self._plan_paged_wave(wave)
        if self._prefix is None:
            return super()._plan_wave(wave)
        cold: dict = collections.OrderedDict()
        warm: dict = collections.OrderedDict()
        primed: dict = collections.OrderedDict()
        for rid, prompt, budget, pr in wave:
            bucket = next(b for b in self._buckets if b >= prompt.size)
            if pr is not None:
                primed.setdefault(bucket, []).append(
                    (rid, prompt, budget, pr, None)
                )
                continue
            pre_len, kv = self._prefix.lookup(
                prompt, trace=self._trace_ids.get(rid)
            )
            # the suffix feeds at cache position pre_len, so its bucket
            # must ALSO fit the row: pre_len + sbucket <= max_len, or the
            # transformer's clamped dynamic_update_slice would silently
            # overwrite the scattered prefix K/V. Shorten the used prefix
            # (whole blocks) until a bucket fits; pre_len 0 is a cold
            # admission, whose full-prompt bucket always fits.
            matched, sbucket = pre_len, None
            while pre_len:
                suffix = prompt.size - pre_len
                sbucket = next(
                    (b for b in self._buckets
                     if b >= suffix and pre_len + b <= self._max_len),
                    None,
                )
                if sbucket is not None:
                    break
                pre_len -= self._prefix.block
            if pre_len:
                if pre_len < matched:
                    kv = {name: a[:pre_len] for name, a in kv.items()}
                # the full-prompt bucket only shapes the program when the
                # repetition penalty needs the whole prompt's presence
                # mask; keying on it otherwise would split waves for no
                # compile reason
                fbucket = bucket if self._seen is not None else 0
                warm.setdefault((pre_len, sbucket, fbucket), []).append(
                    (rid, prompt, budget, None, kv)
                )
            else:
                cold.setdefault(bucket, []).append(
                    (rid, prompt, budget, None, None)
                )
        plans = [("cold", b, g) for b, g in cold.items()]
        plans += [("warm", k, g) for k, g in warm.items()]
        plans += [("primed", b, g) for b, g in primed.items()]
        return plans

    def _run_group(self, kind: str, key, group, rows) -> np.ndarray:
        if kind == "warm":
            return self._warm_wave(key, group, rows)
        if kind == "paged":
            return self._paged_wave(key, group, rows)
        return super()._run_group(kind, key, group, rows)

    # -- paged KV (TFDE_PAGED_KV) --------------------------------------------
    @property
    def paged(self) -> bool:
        return self._paged

    @property
    def block_pool(self):
        """The shared BlockPool (None when dense) — tests read its
        stats; nothing else should allocate from it."""
        return self._pool

    def _init_capacity(self, cache, decode_model, cells_per_row=None
                       ) -> None:
        if not self._paged:
            return super()._init_capacity(cache, decode_model,
                                          cells_per_row)
        cells = int(cells_per_row if cells_per_row is not None
                    else self._max_len)
        self._ledger = _capacity.PagedCapacityLedger(
            self._b, cells, _paged.pool_bytes(cache),
            self._pool.num_blocks, self._kv_block, self._paged_snapshot,
            census=_capacity.kv_dtype_census(cache),
        )
        self._cap_model = _capacity.PagedCapacityModel(self._ledger)

    def _paged_snapshot(self) -> dict:
        """The paged ledger's duck-typed pool view (observability never
        imports inference): pool stats + the trie/sharing split."""
        snap = self._pool.stats()
        snap["trie_blocks"] = (self._prefix.segments
                               if self._prefix is not None else 0)
        snap["shared_cells"] = int(self._shared_cells.sum())
        return snap

    def _release_row(self, r: int) -> None:
        if not self._paged:
            return
        if self._row_blocks[r]:
            self._pool.free(self._row_blocks[r])
            self._row_blocks[r] = []
        self._tables[r, :] = 0
        self._shared_cells[r] = 0
        # the device copy of this table still points at the freed
        # blocks; step()/the next wave re-uploads before any program
        # runs (the freed-row junk-write hazard)
        self._tables_dirty = True

    def _admission_cost(self, item) -> int:
        if not self._paged:
            return 0
        _rid, prompt, budget, _pr = item
        # full lifetime, sharing ignored: a warm match only lowers the
        # real claim, so the gate errs toward stalling one wave early,
        # never toward PoolExhausted mid-wave
        return _paged.blocks_for(int(prompt.size) + int(budget) + 1,
                                 self._kv_block)

    def _admit_capacity(self, need: int) -> bool:
        if not self._paged:
            return True
        evictable = (self._prefix.evictable_blocks()
                     if self._prefix is not None else 0)
        return self._pool.available(evictable) >= need

    def _on_capacity_stall(self) -> None:
        """Admission stalled on the pool: spend the pause compacting.

        Fixed-size blocks can never fragment *allocatability* (any free
        block serves any request), so this is purely a locality pass —
        it squeezes live ids toward the bottom of the pool so gathers
        walk a dense span.  Safe exactly here because the stall breaks
        out of wave COLLECTION, before _plan_paged_wave claims warm
        blocks: the only id holders are _row_blocks, the trie nodes and
        the host tables, and all three are rewritten below.  The device
        block_table copies go stale, so _tables_dirty forces a
        re-upload before any program runs."""
        if not self._paged:
            return
        thr = knobs.env_float("TFDE_KV_DEFRAG_THRESHOLD")
        if not thr or thr <= 0:
            return
        frag = self._pool.fragmentation()
        if frag < thr:
            return
        plan = self._pool.defrag()
        if not plan:
            return
        self._cache, self._tables = _paged.apply_defrag(
            self._cache, self._tables, plan)
        self._row_blocks = [[plan.get(int(b), int(b)) for b in row]
                            for row in self._row_blocks]
        if self._prefix is not None:
            self._prefix.remap(plan)
        self._tables_dirty = True
        metrics.default_registry().counter("kv/pool_defrags").incr()
        flightrec.record("kv_defrag", moved=len(plan),
                         frag=round(float(frag), 3),
                         free=self._pool.free_blocks)

    def _admission_cells(self, kind: str, key, item) -> tuple:
        if not self._paged:
            return super()._admission_cells(kind, key, item)
        _rid, prompt, _budget, _pr, extra = item
        pre = int(extra[0]) if (kind == "paged" and extra is not None) else 0
        block = self._kv_block
        alloc = (_paged.blocks_for(int(prompt.size), block)
                 - pre // block) * block
        return alloc, int(prompt.size) - pre

    def _plan_paged_wave(self, wave) -> list:
        """Paged admission planning: cold and warm collapse into ONE
        'paged' group — the chunk program is shape-blind to prompt
        length and wave membership, so there is nothing to group BY
        except the chunk width (its only static). Primed hand-offs keep
        their per-bucket grouping (the shipped K/V stack is shaped by
        the bucket). Warm lookups CLAIM their matched blocks here at
        plan time (incref), so nothing between plan and wave — another
        item's allocation draining the trie included — can invalidate
        the ids; the claim is the row's own reference, released with
        the rest of its blocks. A same-wave duplicate prompt still
        misses (its twin's blocks enter the trie only after the wave) —
        the dense intra-wave semantics."""
        items: list = []
        primed: dict = collections.OrderedDict()
        for rid, prompt, budget, pr in wave:
            if pr is not None:
                bucket = next(b for b in self._buckets
                              if b >= prompt.size)
                primed.setdefault(bucket, []).append(
                    (rid, prompt, budget, pr, None)
                )
                continue
            pre_len, ids = 0, None
            if self._prefix is not None:
                pre_len, ids = self._prefix.lookup(
                    prompt, trace=self._trace_ids.get(rid), claim=True)
            items.append((rid, prompt, budget, None, (pre_len, ids)))
        plans = [("paged", self._chunk, items)] if items else []
        plans += [("primed", b, g) for b, g in primed.items()]
        return plans

    def _paged_wave(self, chunk: int, group, rows) -> np.ndarray:
        """Admit a paged wave: point each row's table at its claimed
        trie blocks plus freshly-allocated lifetime blocks, then feed
        every suffix through the ONE full-batch chunk program — warm
        admission's prefix cost is the incref, not a scatter.

        Non-wave rows ride along as pad feeds at their committed index
        (their junk lands in their own uncommitted cells or the null
        block); exhausted wave rows pad at their prompt end. After the
        last chunk each admitting row's true last-position logits sit
        in the [B, V] carry; one small ladder-width program samples the
        first tokens. Cold rows then seed the trie by ADOPTING their
        own complete prompt blocks (incref — zero copy)."""
        n = len(group)
        block = self._kv_block
        starts = np.zeros(n, np.int64)
        plens = np.zeros(n, np.int64)
        for i, (rid, prompt, budget, _pr, extra) in enumerate(group):
            r = rows[i]
            pre_len, ids = extra if extra is not None else (0, None)
            shared = [int(b) for b in ids] if ids else []
            nblk = _paged.blocks_for(prompt.size + budget + 1, block)
            fresh = self._pool.alloc(nblk - len(shared))
            held = shared + fresh
            self._row_blocks[r] = held
            self._tables[r, :len(held)] = held
            self._tables[r, len(held):] = 0
            self._shared_cells[r] = pre_len
            starts[i] = pre_len
            plens[i] = prompt.size
        self._cache = _paged.set_block_tables(self._cache, self._tables)
        self._tables_dirty = False
        self._dispatches += 1
        nchunks = -(-int((plens - starts).max()) // chunk)
        prev = jnp.zeros((self._b, self._vocab), jnp.float32)
        for j in range(nchunks):
            tokens = np.full((self._b, chunk), self._pad, np.int32)
            idx = np.asarray(self._committed, np.int32)
            take = np.zeros(self._b, bool)
            last_in = np.zeros(self._b, np.int32)
            for i in range(n):
                r = rows[i]
                prompt = group[i][1]
                s = int(starts[i]) + j * chunk
                e = min(int(plens[i]), s + chunk)
                if s < plens[i]:
                    tokens[r, :e - s] = prompt[s:e]
                    idx[r] = s
                    if e == plens[i]:
                        take[r] = True
                        last_in[r] = e - 1 - s
                else:
                    idx[r] = plens[i]  # exhausted: pads beyond own prompt
            args = (self._cache, self._params, jnp.asarray(tokens),
                    jnp.asarray(idx), jnp.asarray(take),
                    jnp.asarray(last_in), prev)
            self._mem_register(
                f"serve/prefill_paged/c{chunk}",
                functools.partial(_paged_prefill_chunk, self._paged_model),
                args, donated=self._cache,
            )
            self._cache, prev = _paged_prefill_chunk(
                self._paged_model, *args)
            self._dispatches += 1
        rp = _pad_wave(n, self._b)
        pick = np.asarray([rows[i if i < n else 0] for i in range(rp)],
                          np.int32)
        wave_logits = prev[jnp.asarray(pick)]
        self._dispatches += 1
        seen_dev = None
        if self._seen is not None:
            seen_rows = np.zeros((rp, self._vocab), bool)
            for i in range(rp):
                seen_rows[i, group[i if i < n else 0][1]] = True
            seen_dev = jnp.asarray(seen_rows)
        rng = None
        if self._sampling["temperature"] != 0.0:
            self._rng, rng = jax.random.split(self._rng)
        tok, seen_out = _sample_first(wave_logits, rng, seen_dev,
                                      **self._sampling)
        self._dispatches += 1
        if seen_out is not None:
            rows_pad = np.asarray(
                list(rows) + [rows[0]] * (rp - n), np.int32)
            if rp > n:
                # the dense dup-row rule: duplicate scatter targets must
                # carry identical values (padding rows drew their own
                # first token under temperature > 0)
                sel = np.arange(rp)
                sel[n:] = 0
                seen_out = seen_out[jnp.asarray(sel)]
            self._seen = self._seen.at[jnp.asarray(rows_pad)].set(seen_out)
            self._dispatches += 1
        tok_np = self._fetch_first(tok)
        if self._prefix is not None:
            for i in range(n):
                _rid, prompt, _budget, _pr, extra = group[i]
                if extra is not None and extra[0]:
                    continue  # warm rows don't re-insert (dense parity)
                nb = prompt.size // block
                if nb:
                    self._prefix.insert(
                        prompt, self._row_blocks[rows[i]][:nb])
        return tok_np

    def _primed_paged_wave(self, bucket: int, group, rows) -> np.ndarray:
        """Primed hand-off under paging: allocate each row's lifetime
        blocks, re-chunk the shipped host K/V (dense leaf names,
        layout-agnostic [P, ...] segments) to block granularity, and
        land it with ONE donated pool scatter — still zero model flops
        on the decode replica. Compiled per (bucket, wave width) like
        the dense primed path: the K/V stack is shipped data; there is
        no program to collapse."""
        n = len(group)
        block = self._kv_block
        rp = _pad_wave(n, self._b)
        nb_bucket = _paged.blocks_for(bucket, block)
        blk = np.zeros((rp, nb_bucket), np.int32)
        toks = np.zeros(rp, np.int64)
        seen_rows = (
            np.zeros((rp, self._vocab), bool)
            if self._seen is not None else None
        )
        sample = group[0][3].kv
        stacked = {
            _paged.pool_leaf_name(name): np.zeros(
                (rp, nb_bucket, block) + arr.shape[1:], arr.dtype)
            for name, arr in sample.items()
        }
        for i in range(rp):
            _rid, prompt, budget, pr, _x = group[i if i < n else 0]
            if i < n:
                r = rows[i]
                nblk = _paged.blocks_for(prompt.size + budget + 1, block)
                fresh = self._pool.alloc(nblk)
                self._row_blocks[r] = fresh
                self._tables[r, :nblk] = fresh
                self._tables[r, nblk:] = 0
                self._shared_cells[r] = 0
                nbp = _paged.blocks_for(prompt.size, block)
                blk[i, :nbp] = fresh[:nbp]
                for name, arr in pr.kv.items():
                    dst = stacked[_paged.pool_leaf_name(name)]
                    flat = dst[i].reshape(
                        (nb_bucket * block,) + arr.shape[1:])
                    flat[:arr.shape[0]] = arr
            # padding rows (i >= n) keep null targets AND zero payload:
            # every duplicate write to block 0 lands the same zeros, so
            # scatter order never matters
            toks[i] = pr.first_token
            if seen_rows is not None:
                seen_rows[i, prompt] = True
                seen_rows[i, pr.first_token] = True
        self._cache = _paged.set_block_tables(self._cache, self._tables)
        self._tables_dirty = False
        self._dispatches += 1
        kv_dev = {name: jnp.asarray(b) for name, b in stacked.items()}
        blk_dev = jnp.asarray(blk)
        self._mem_register(
            f"serve/prefill_primed/b{bucket}r{rp}",
            _scatter_primed_blocks,
            (self._cache, kv_dev, blk_dev),
            donated=self._cache,
        )
        self._cache = _scatter_primed_blocks(self._cache, kv_dev, blk_dev)
        self._dispatches += 1
        if seen_rows is not None:
            rows_pad = np.asarray(
                list(rows) + [rows[0]] * (rp - n), np.int32)
            self._seen = self._seen.at[jnp.asarray(rows_pad)].set(
                jnp.asarray(seen_rows))
            self._dispatches += 1
        return toks  # first tokens are host-known: no sync on this path

    def _warm_wave(self, key, group, rows) -> np.ndarray:
        """Admit rows whose prompt prefix is cached: land the prefix K/V
        and prefill ONLY the suffix, one donated program per (prefix
        length, suffix bucket) group — the shared-system-prompt fast
        path the prefix cache exists for."""
        pre_len, sbucket, fbucket = key
        # _plan_wave guarantees the suffix bucket fits the row past the
        # scattered prefix; a violation here would clamp the cache write
        # and corrupt the prefix K/V silently
        assert pre_len + sbucket <= self._max_len, (pre_len, sbucket)
        n = len(group)
        rp = _pad_wave(n, self._b)
        with self._span("serving/prefill/pack", "prefill_pack_ns"):
            suffixes = np.full((rp, sbucket), self._pad, np.int32)
            last = np.zeros(rp, np.int32)
            fullp = plens = None
            if self._seen is not None:
                fullp = np.full((rp, fbucket), self._pad, np.int32)
                plens = np.zeros(rp, np.int32)
            kv_rows = []
            for i in range(rp):
                _rid, prompt, _budget, _pr, kv = group[i if i < n else 0]
                suffix = prompt[pre_len:]
                suffixes[i, :suffix.size] = suffix
                last[i] = suffix.size - 1
                if fullp is not None:
                    fullp[i, :prompt.size] = prompt
                    plens[i] = prompt.size
                kv_rows.append(kv)
            rows_pad = np.asarray(rows + [rows[0]] * (rp - n), np.int32)
        rng = None
        if self._sampling["temperature"] != 0.0:
            self._rng, rng = jax.random.split(self._rng)
        tmpl = self._row_template(rp)
        with self._span("serving/prefill/run", "prefill_run_ns"):
            kv_stack = {
                name: jnp.stack([k[name] for k in kv_rows])
                for name in kv_rows[0]
            }
            valid = None
            if fullp is not None:
                valid = jnp.asarray(
                    np.arange(fbucket)[None, :] < plens[:, None])
                fullp = jnp.asarray(fullp)
            suffixes_dev = jnp.asarray(suffixes)
            last_dev = jnp.asarray(last)
            self._mem_register(
                f"serve/prefill_warm/p{pre_len}s{sbucket}r{rp}",
                functools.partial(_prefill_suffix, self._decode_model,
                                  **self._sampling),
                (tmpl, self._params, kv_stack, suffixes_dev, last_dev,
                 fullp, valid, rng),
                donated=tmpl,
            )
            row_cache, tok, row_seen = _prefill_suffix(
                self._decode_model, tmpl, self._params,
                kv_stack, suffixes_dev, last_dev, fullp,
                valid, rng, **self._sampling,
            )
            # the per-wave kv stack + the fused prefill
            self._dispatches += 2
        with self._span("serving/prefill/scatter", "prefill_scatter_ns"):
            self._scatter_wave(row_cache, row_seen, rows_pad, n)
        return self._fetch_first(tok)

    # -- prefill/decode role split -------------------------------------------
    def prime(self, prompt, max_new_tokens: int,
              trace: Optional[str] = None) -> PrimedRequest:
        """Run ONLY the prefill for one request and return the hand-off
        payload (host K/V + pending first token) for a decode replica's
        `submit_primed()` — the prefill half of the role split. Touches
        no decode row and no queue, so a prefill-role replica can serve
        long-prompt admissions without ever stalling a decode scan."""
        if self._role == "decode":
            raise RuntimeError("decode-only replica cannot prime")
        _refuse_stateful(self._model, "prime() (K/V shipped by position)",
                         self._max_len)
        t_prime = now_ns()
        prompt = self._check_request(prompt, max_new_tokens)
        bucket = next(b for b in self._buckets if b >= prompt.size)
        prompts = np.full((1, bucket), self._pad, np.int32)
        prompts[0, :prompt.size] = prompt
        last = np.asarray([prompt.size - 1], np.int32)
        valid = None
        if self._seen is not None:
            valid = jnp.asarray(np.arange(bucket)[None, :] < prompt.size)
        rng = None
        if self._sampling["temperature"] != 0.0:
            self._rng, rng = jax.random.split(self._rng)
        row_cache, tok, _, _ = _prefill_rows(
            self._decode_model, self._row_template(1), self._params,
            jnp.asarray(prompts), jnp.asarray(last), valid, rng,
            **self._sampling,
        )
        self._dispatches += 1
        if self._prefix is not None and not self._paged:
            # the paged trie holds POOL BLOCK IDS; prime() runs on the
            # dense row layout (the hand-off is layout-agnostic), so
            # its segments have no block to adopt — only locally
            # admitted prompts seed the paged trie
            self._prefix.insert(prompts[0, :prompt.size], row_cache, 0)
        kv = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(row_cache):
            if is_index_leaf(path):
                continue
            kv[leaf_name(path)] = leaf[0, :prompt.size]
        kv_np, tok_np = _fetch((kv, tok))
        self._syncs += 1
        if trace is not None and _trace.active():
            # the prefill half of the primed hand-off: the decode
            # replica's serve/queued(primed=True) is the other half
            _trace.event("serve/prime", trace=trace,
                         dur=(now_ns() - t_prime) * 1e-9,
                         prompt_tokens=int(prompt.size))
        return PrimedRequest(
            prompt=prompt.astype(np.int32),
            first_token=int(tok_np[0]),
            max_new_tokens=int(max_new_tokens),
            kv=kv_np,
        )

    def _primed_wave(self, bucket: int, group, rows) -> np.ndarray:
        """Admit rows primed on another replica: stack the shipped host
        K/V, one donated multi-row scatter, zero model flops here — the
        decode scan never waits behind a long-prompt prefill."""
        if self._paged:
            return self._primed_paged_wave(bucket, group, rows)
        n = len(group)
        rp = _pad_wave(n, self._b)
        rows_pad = np.asarray(rows + [rows[0]] * (rp - n), np.int32)
        sample = group[0][3].kv
        stacked = {
            name: np.zeros((rp, bucket) + arr.shape[1:], arr.dtype)
            for name, arr in sample.items()
        }
        toks = np.zeros(rp, np.int64)
        seen_rows = (
            np.zeros((rp, self._vocab), bool)
            if self._seen is not None else None
        )
        for i in range(rp):
            _rid, prompt, _budget, pr, _x = group[i if i < n else 0]
            for name, arr in pr.kv.items():
                stacked[name][i, :arr.shape[0]] = arr
            toks[i] = pr.first_token
            if seen_rows is not None:
                # rebuild the presence mask from ids — cheaper to recompute
                # than to ship a [vocab] row across processes
                seen_rows[i, prompt] = True
                seen_rows[i, pr.first_token] = True
        kv_dev = {name: jnp.asarray(b) for name, b in stacked.items()}
        rows_dev = jnp.asarray(rows_pad)
        self._mem_register(
            f"serve/prefill_primed/b{bucket}r{rp}",
            _scatter_primed_rows,
            (self._cache, kv_dev, rows_dev),
            donated=self._cache,
        )
        self._cache = _scatter_primed_rows(self._cache, kv_dev, rows_dev)
        self._dispatches += 1
        if seen_rows is not None:
            self._seen = self._seen.at[rows_dev].set(jnp.asarray(seen_rows))
            self._dispatches += 1
        return toks  # first tokens are host-known: no sync on this path


class SpeculativeContinuousBatcher(_BatcherBase):
    """Continuous batching accelerated by a draft model — the two serving
    levers composed: every round, the draft proposes `num_draft` tokens
    per row and ONE target forward verifies all of them
    (inference/speculative.py's batch-generic round, per-row acceptance),
    while finished rows admit queued requests mid-flight exactly like
    `ContinuousBatcher` — including the bucketed wave admission: both
    caches prefill every freed row of a bucket in one call each and land
    with one multi-row scatter per cache.

    temperature == 0 (default): deterministic rounds — each request's
    output equals its solo greedy `generate(model, params, prompt)` run.
    temperature > 0: speculative SAMPLING rounds (the Leviathan
    acceptance, inference/speculative.py) — committed tokens are
    distributed exactly as target-model sampling at that temperature per
    request, with draw values batch-dependent (rows share the key
    stream). Per-round commits vary between 1 and num_draft+1 tokens per
    row with draft quality; `stats()` reports the realized tokens/round
    and draft acceptance rate.
    """

    _metrics_prefix = "serving/speculative"

    def __init__(
        self,
        model,
        draft_model,
        params,
        draft_params,
        batch_size: int,
        max_len: int,
        num_draft: int = 4,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        pad_id: int = 0,
        rng: Optional[jax.Array] = None,
        prompt_buckets: Optional[tuple] = None,
    ):
        if num_draft < 1:
            raise ValueError(f"num_draft must be >= 1, got {num_draft}")
        for m in (model, draft_model):
            _refuse_stateful(m, "SpeculativeContinuousBatcher (a rejected "
                             "draft rewinds the cache by position)", max_len)
        super().__init__(model, params, batch_size, max_len, eos_id,
                         pad_id, rng, prompt_buckets)
        from tfde_tpu.inference.speculative import (
            _spec_round,
            _spec_round_sampled,
        )

        self._spec_round = _spec_round
        self._spec_round_sampled = _spec_round_sampled
        self._temperature = float(temperature)
        self._draft = draft_model
        self._tgt = _decode_clone(model)
        self._drf = _decode_clone(draft_model)
        self._dparams = draft_params
        self._dparam_bytes = _count_params(draft_params)[1]
        self._nd = int(num_draft)
        # the speculative cache invariant: each round feeds at most
        # num_draft+1 tokens past a row's committed count before the
        # rewind (inference/speculative.py cache sizing)
        self._cache_len = self._max_len + self._nd + 1
        self._tgt_cache = init_cache(model, batch_size, self._cache_len)
        self._drf_cache = init_cache(draft_model, batch_size,
                                     self._cache_len)
        # the ledger tracks the TARGET slab (the draft cache is a cost
        # of speculation, not serving capacity)
        self._init_capacity(self._tgt_cache, self._tgt,
                            cells_per_row=self._cache_len)
        self._round_tokens = 0   # tokens produced by speculative rounds
        self._draft_proposed = 0  # num_draft per active row per round
        self._draft_accepted = 0  # committed beyond the guaranteed token

    def stats(self) -> dict:
        """Speculation effectiveness: tokens/round is per ROW per round
        (1.0 = no draft ever accepted, num_draft+1 = perfect draft);
        acceptance_rate is the fraction of proposed draft tokens the
        target committed. dispatches/syncs mirror ContinuousBatcher's
        host-overhead accounting, and the step's own account has the keys
        `ContinuousBatcher.stats` lists: a scan here is one speculative
        round, an upload its rewind of both caches' index counters, and
        `decode_least_bytes` counts the target's parameters once and the
        draft's `num_draft` times a round."""
        return {
            "rounds": self._rounds,
            "generated": self._generated,
            "tokens_per_round": (
                self._round_tokens / max(self._rounds * self._b, 1)
            ),
            "acceptance_rate": (
                self._draft_accepted / max(self._draft_proposed, 1)
            ),
            "dispatches": self._dispatches,
            "syncs": self._syncs,
            **self._phase,
        }

    def _validate_submit(self, prompt, max_new_tokens) -> None:
        super()._validate_submit(prompt, max_new_tokens)
        validate_budget(self._draft, int(prompt.size), max_new_tokens)

    def _prefill_wave(self, prompts, last, rows, plens, n) -> np.ndarray:
        rp = prompts.shape[0]
        rng = None
        if self._temperature > 0.0:
            self._rng, rng = jax.random.split(self._rng)
        tgt_tmpl = self._zero_rows(self._model, rp, self._cache_len)
        drf_tmpl = self._zero_rows(self._draft, rp, self._cache_len)
        with self._span("serving/prefill/run", "prefill_run_ns"):
            prompts_dev = jnp.asarray(prompts)
            last_dev = jnp.asarray(last)
            tgt_rows, tok, _, _ = _prefill_rows(
                self._tgt, tgt_tmpl, self._params, prompts_dev, last_dev,
                None, rng, temperature=self._temperature, top_k=None,
                top_p=None, min_p=None, repetition_penalty=1.0,
            )
            # the draft prefill only needs its cache filled; its sampled
            # token is discarded (greedy argmax — no rng consumed)
            drf_rows, _, _, _ = _prefill_rows(
                self._drf, drf_tmpl, self._dparams, prompts_dev, last_dev,
                None, None, temperature=0.0, top_k=None, top_p=None,
                min_p=None, repetition_penalty=1.0,
            )
            self._dispatches += 2
        with self._span("serving/prefill/scatter", "prefill_scatter_ns"):
            rows_dev = jnp.asarray(rows)
            self._tgt_cache = _scatter_rows(self._tgt_cache, tgt_rows,
                                            rows_dev)
            self._drf_cache = _scatter_rows(self._drf_cache, drf_rows,
                                            rows_dev)
            self._dispatches += 2
        return self._fetch_first(tok)

    def _round(self, active: list, finished: list) -> None:
        """ONE speculative round for the whole batch."""
        self._rounds += 1
        with self._span("serving/decode", "decode_ns", "scans",
                        histogram=True) as decode:
            # per-round rewind is unconditional: acceptance lengths diverge
            # every round (host ints/np arrays — own buffer per index leaf,
            # across BOTH donated caches)
            with self._span("serving/decode/upload", "decode_upload_ns",
                            "uploads"):
                committed = self._committed.astype(np.int32)
                self._tgt_cache = _set_index_counters(self._tgt_cache,
                                                      committed)
                self._drf_cache = _set_index_counters(self._drf_cache,
                                                      committed)
                self._dispatches += 2
            self._phase["decode_least_bytes"] += (
                self._param_bytes + self._nd * self._dparam_bytes
                + self._kv_read_bytes(active))
            with self._span("serving/decode/scan", "decode_dispatch_ns"):
                if self._temperature > 0.0:
                    self._rng, sub = jax.random.split(self._rng)
                    (self._tgt_cache, self._drf_cache, round_toks, n_new,
                     _pending, _rng_out) = self._spec_round_sampled(
                        self._tgt, self._drf, self._tgt_cache,
                        self._drf_cache, self._params, self._dparams,
                        jnp.asarray(self._tok, jnp.int32), sub, self._nd,
                        self._pad, self._temperature,
                    )
                else:
                    (self._tgt_cache, self._drf_cache, round_toks, n_new,
                     _pending) = self._spec_round(
                        self._tgt, self._drf, self._tgt_cache,
                        self._drf_cache, self._params, self._dparams,
                        jnp.asarray(self._tok, jnp.int32), self._nd,
                        self._pad,
                    )
                self._dispatches += 1
            with self._span("serving/decode/fetch", "device_wait_ns"):
                round_np, n_np = _fetch((round_toks, n_new))
            self._syncs += 1
        traced = (
            [self._trace_ids[rid] for r in active
             if (rid := self._req[r]) in self._trace_ids]
            if self._trace_ids else []
        )
        with self._span("serving/emit", "emit_ns"):
            self._profiler_round(traced)
            n_emitted = 0
            for r in active:
                toks = round_np[r, : int(n_np[r])].tolist()
                taken = 0
                for t in toks:
                    if self._req[r] is None:
                        break  # row finished mid-round; overshoot discarded
                    self._round_tokens += 1
                    finished.extend(self._take_token(r, int(t)))
                    taken += 1
                n_emitted += taken
                # acceptance bookkeeping: each round proposes num_draft
                # per active row; a row's commits beyond the guaranteed
                # target token are accepted draft proposals (capped by
                # num_draft — the +1'th commit is the bonus token, not a
                # draft)
                self._draft_proposed += self._nd
                self._draft_accepted += min(max(taken - 1, 0), self._nd)
                if self._req[r] is not None:
                    # row still active: tok_last + accepted tokens are now
                    # in both caches (the pending one stays unfed) — the
                    # generate_speculative commit bookkeeping
                    self._committed[r] += taken
            self._close_round(decode, traced, self._nd, len(active),
                              n_emitted)
            self._publish_stats()
