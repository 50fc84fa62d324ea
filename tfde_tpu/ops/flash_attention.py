"""Flash attention — Pallas TPU kernel for the long-sequence regime.

Hot-op kernel scope (the reference delegates all kernels to TF's C++ library,
SURVEY.md §2b "Dense/conv/BN kernel library"; here the transformer configs'
attention gets a hand kernel where XLA's default fusion stops helping).

Forward is a Pallas kernel (per /opt/skills/guides/pallas_guide.md), in one
of two arrangements of the same online softmax (float32 scores and
statistics, p rounded to the input dtype before p V, the [Sq, Sk] score
matrix never materialized: O(S) memory). `_flash_forward` decides from
its operands, as `_bwd` does: whole K/V heads tiling the 128 lanes
(`_bwd_heads_per_block`), as many of them as Q heads or, under
grouped-query, each a lane block of its own (heads of 128 or 256), and a
lane block's K and V inside VMEM take the lane kernel; grouped-query with
heads of 64, head widths no lane block tiles and longer sequences keep the
grid kernel. The values may have a width of their own (v [B, S, Kv, Dv]: a
latent layer scores over 192 columns a head and carries 128): the lane
kernel takes D and Dv apart where both are whole lane blocks, so V^T p, the
accumulator and V's fetch cost Dv and no more; any other unequal pair is
padded to one width inside `_flash_forward` (forward only: `_bwd` refuses).
- the lane kernel (`_fwd_lane_kernel`): grid (batch, K/V lane block, Q
  tile) with K and V of the lane block resident in VMEM (fetched once a
  block) and an in-kernel loop over the K tiles `_tile_in_band` keeps
  (`_k_tile_range`), so out-of-band tiles cost nothing and the body is
  compiled once; scores are K-major (z^T = K Q^T, [bk, bq]), so the
  running max and sum are [1, bq] lane vectors reduced down the sublanes
  and the accumulator is kept transposed. Multi-head attention reads q,
  k, v and writes out as blocks of the free [B, S, H*D] view of the
  model's [B, S, H, D] layout, so nothing is transposed in HBM; a block
  of whole heads fills the lanes (two heads at D=64, each head's Q with
  the other's lanes zeroed). Grouped-query attention takes the
  [B, H, S, D] view and all the query heads of a K/V head a step (seven
  at 28 over 4), so each K and V tile is read once for the group. lse
  leaves it as the lane vectors the fused backward reads.
- the grid kernel (`_fwd_kernel`) works in BHSD (three `swapaxes` in, one
  out): grid (batch, heads, Sq/block_q, Sk/block_k) with K minor, one Q
  tile and one K/V tile VMEM-resident per step (VMEM stays O(block) at any
  S), the online-softmax state in VMEM scratch across the K steps of an
  output block; out-of-band K tiles are predicated off (pl.when) with
  their DMA elided; grouped-query heads fold onto their K/V head in the
  index maps, a K/V tile fetched again for each of them. It stays for
  what the lane kernel cannot hold: grouped-query with heads of 64 (a
  K/V head is half a lane block), head widths that tile no lane block,
  and K and V past the VMEM budget.
- tiles default to the largest MXU multiple of 512/256/128 dividing S
  (`_auto_block`: the r04 hardware sweep measured 512-edge tiles
  1.25-1.45x over 128 at every shape tried; the lane kernel again, PR 30).
- `scale` and `logit_cap` (Gemma-2 tanh softcapping) and the sliding
  window apply inside both, so capped/scaled/windowed models stay fused.
Measured at the training cells' shape ([2, 4096, 16, 64] bf16, causal, 512
tiles, one v5e chip; my chip runs, PR 30, 40 calls back to back on the
host's clock): the grid kernel with its copies 3.09 ms a call, the lane
kernel 1.30 ms; the in-kernel loop with resident K/V alone, on the BHSD
kernel, 1.73. As device time inside the GPT-2-medium step: 2.75 -> 1.16
ms a call, 30 % of the forward's FLOP roofline.

Backward of causal multi-head attention is ONE fused Pallas kernel
(`_bwd_kernel`, `pallas_call(name="flash_bwd")`): for every in-band
(K tile, Q tile) pair it recomputes the scores from the saved logsumexp
once and updates dV, dK and dQ from them — five matmuls and one exp a
score, the score tiles never leave VMEM.
- it reads and writes the model's [B, S, H, D] layout through the free
  [B, S, H*D] view: a block of whole heads fills the 128 lanes (two heads
  at D=64, each head's K/V with the other's lanes zeroed), so nothing is
  transposed in HBM;
- scores are K-major (z^T = K Q^T, [bk, bq]): dV += p^T dO and dK += ds^T Q
  are plain contractions and lse/delta are [1, bq] lane vectors;
- grid (batch, head block, K tile); Q, dO and the float32 dQ accumulator
  of the head block stay in VMEM whole, dQ is written once; an in-kernel
  loop walks the live Q tiles of the K tile (`_q_tile_range`), so out-of-
  band pairs cost nothing and the body is compiled once;
- same arithmetic as the recurrence: bf16 operands into the MXU, float32
  accumulation, p and ds rounded to the input dtype before their second
  matmuls; window and logit cap run inside it.
`_bwd` decides from its operands: causal, as many K/V heads as Q heads,
whole heads tiling the lanes, a head block's working set inside VMEM take
the kernel; grouped-query, non-causal and what does not fit keep the
blockwise-JAX recurrences (`_bwd_pair_scan` over the statically enumerated
in-band pairs for causal, the full K-tile scan `_bwd_blockwise*` for
non-causal). Measured at the training
cells' shape ([2, 4096, 16, 64] bf16, causal, 512 tiles, one v5e chip; my
chip run, PR 27, 40 calls back to back on the host's clock): the pair scan
6.67 ms a call, the kernel 2.55 ms with the same gradients to the bit, the
forward 2.90 ms. The two-kernel dK/dV + dQ pair it replaces (seven matmuls,
two exp a score, lse/delta broadcast to 128 lanes in HBM) was at parity
with the scan.

The band membership predicate (`_tile_in_band`) is shared by the forward
kernel, every backward path, the DMA-eliding index maps, and the roofline
tile-visit counter (ops/roofline.py) — one source of truth, so a counter
regression in tier-1 means the kernels' schedule actually changed.

Ring attention (ops/ring_attention.py) composes with this by construction:
its per-device block computation is the same recurrence, so the flash kernel
can serve as its local step on TPU.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30

# Trace-time tile-visit recorder (see `record_tile_visits`). None when
# disabled; a dict while a recording context is open.
_TILE_COUNTS = None


@contextlib.contextmanager
def record_tile_visits():
    """Record the tile schedule of flash calls traced inside the context.

    Yields a dict that the forward/backward builders populate at TRACE
    time with the statically-known schedule: number of grid steps, number
    of in-band (executed) tile visits per pass, and the resolved tile
    sizes, `fwd_path` ("lane" or "grid") and `bwd_path` ("kernel" or
    "recurrence"). Because `pl.when` predication, the lane forward's
    K-tile loop, the fused backward's Q-tile loop and the pair-scan length
    are decided by the same `_tile_in_band` predicate recorded here, these
    numbers are exactly the tiles the compiled kernels execute. The
    causal backward additionally bumps `bwd_steps_executed` from inside
    its loop via `jax.debug.callback` (the scan's body; the kernel's, one
    block of heads, when interpreted), and the interpreted lane forward
    `fwd_steps_executed` likewise: a runtime-executed corroboration of
    the static plan.

    Recording happens when the call is traced — call the kernels directly
    (or with fresh shapes) inside the context rather than through an
    already-warm jit cache."""
    global _TILE_COUNTS
    prev = _TILE_COUNTS
    _TILE_COUNTS = {}
    try:
        yield _TILE_COUNTS
    finally:
        _TILE_COUNTS = prev


def _first_block_counter(counts: dict, key: str):
    """Host callback for an interpreted kernel's loop body: counts under
    `key` the steps one block of heads ran, beside the static plan (Mosaic
    lowers no host callback, so only interpreted calls are given it)."""
    def bump(first_block):
        if first_block:
            counts[key] = counts.get(key, 0) + 1
    return bump


def _auto_block(s: int) -> int:
    """Default tile edge: the largest MXU-multiple that divides S.

    The r04 hardware sweep (v5e, causal fwd+bwd, h=12 d=64) measured
    512x512 tiles 1.25-1.45x faster than the original 128x128 at every
    shape tried (b1-b4, S=2048-8192, windowed, GQA) — fewer grid steps
    amortize the per-tile online-softmax state updates, and a 512-row
    MXU operand keeps the systolic array busier. Explicit block_q/block_k
    still override (tests use small tiles to exercise multi-block paths
    at small S).

    A sliding window does NOT cap the edge: a tile wider than the band
    runs more in-band columns per Q row (~block + window), but the
    hardware A/B at the worst case (window=128, S=4096) still put 512
    tiles ahead — 2.32 vs 3.13 ms forward-only, 6.69 vs 7.26 ms fwd+bwd
    — per-tile efficiency outweighs the extra span on this chip."""
    for bl in (512, 256, 128):
        if s % bl == 0:
            return bl
    return min(s, 128)


def _resolve_block(block, s: int) -> int:
    """The one resolution rule for every kernel entry point: None -> the
    measured auto default; explicit -> clamped to S. Keeping this single
    prevents forward/backward tile defaults from silently diverging."""
    return _auto_block(s) if block is None else min(block, s)


def _tile_in_band(qi, kb, block_q: int, block_k: int, causal, window):
    """Whether tile (qi, kb) holds any unmasked (row, col) pair.

    THE band predicate: the forward kernel's `pl.when`, both backward
    paths, the DMA-eliding index maps, and the roofline counter all derive
    from this one function. Works on Python ints (static planning) and on
    traced scalars (inside kernels) alike. A K tile is live iff its first
    column is not strictly past the Q tile's last row, and — with a
    sliding window — its last column is not entirely older than the
    oldest position the Q tile's first row can see."""
    if not causal:
        return True
    live = kb * block_k <= (qi + 1) * block_q - 1
    if window is not None:
        live = (kb * block_k + block_k - 1 >= qi * block_q - (window - 1)) & live
    return live


def _band_tile_pairs(s: int, block_q: int, block_k: int, causal: bool,
                     window) -> list:
    """Statically enumerate the in-band (qi, kb) tile pairs for an S x S
    attention. Plain-causal yields ~half the grid; a sliding window yields
    O(window / block_k) + O(1) pairs per Q tile. The causal backward scans
    exactly this list, so its length IS the executed tile-visit count."""
    n_q, n_k = s // block_q, s // block_k
    return [
        (qi, kb)
        for qi in range(n_q)
        for kb in range(n_k)
        if bool(_tile_in_band(qi, kb, block_q, block_k, causal, window))
    ]


def bwd_tile_plan(s: int, block_q=None, block_k=None, causal: bool = True,
                  window=None) -> dict:
    """Public schedule introspection for tools/tests (roofline counter).

    Returns the resolved tile sizes, the full grid size per pass, and the
    in-band pairs the causal backward will actually scan — computed from
    the same `_tile_in_band` predicate the kernels branch on."""
    bq = _resolve_block(block_q, s)
    bk = _resolve_block(block_k, s)
    pairs = _band_tile_pairs(s, bq, bk, causal, window)
    n_q, n_k = s // bq, s // bk
    per_q = [0] * n_q
    per_k = [0] * n_k
    for qi, kb in pairs:
        per_q[qi] += 1
        per_k[kb] += 1
    return {
        "block_q": bq,
        "block_k": bk,
        "grid": n_q * n_k,
        "visits": len(pairs),
        "pairs": pairs,
        "max_visits_per_q_tile": max(per_q) if per_q else 0,
        "max_visits_per_k_tile": max(per_k) if per_k else 0,
    }


def _apply_cap(z, logit_cap):
    """tanh softcapping (Gemma-2): c = cap * tanh(z / cap). Returns the
    capped logits and tanh(z/cap) (needed by the backward chain rule:
    dc/dz = 1 - tanh^2)."""
    t = jnp.tanh(z / logit_cap)
    return logit_cap * t, t


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, causal, scale, window, logit_cap,
):
    # BHSD layout, grid (B, H, Sq/bq, Sk/bk) with the K dimension minor:
    # q_ref [1, 1, bq, D]; k_ref/v_ref [1, 1, bk, D] — only one K/V tile is
    # VMEM-resident at a time, so VMEM stays O(block) at any S. The online-
    # softmax state (acc/m/l) lives in VMEM scratch, which persists across
    # the kb grid steps that revisit the same (b, h, qi) output block.
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    num_kb = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _step():
        q = q_ref[0, 0]  # [bq, D]
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if logit_cap is not None:
            s, _ = _apply_cap(s, logit_cap)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            keep = rows >= cols
            if window is not None:
                # sliding band: row i sees cols in (i - window, i]
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, _NEG)
        m_prev = m_ref[:, 0:1]  # [bq, 1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # K-tiles strictly past this Q-tile's last row contribute nothing;
        # with a sliding window, neither do tiles entirely older than the
        # oldest position the tile's first row can see
        # An interior/diagonal split (mask only the straddling tiles) was
        # measured 3-4% SLOWER at 512 tiles on v5e — the duplicated step
        # body costs more than the iota/select it saves; keep one body.
        pl.when(_tile_in_band(qi, kb, bq, bk, True, window))(_step)
    else:
        _step()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-20)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0:1] + jnp.log(l)


def _k_tile_range(qi, block_q: int, block_k: int, n_k: int, causal, window):
    """First and last K tile `_tile_in_band` keeps for Q tile `qi`: both
    of its conditions are monotone in the K tile, so the live tiles are
    one run (all of them when not causal). Python ints or traced scalars,
    as `_tile_in_band`."""
    if not causal:
        return 0, n_k - 1
    hi = ((qi + 1) * block_q - 1) // block_k
    if window is None:
        return 0, hi
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k, hi


def _fwd_lane_vmem_bytes(s: int, heads: int, width: int, itemsize: int,
                         block_q: int, block_k: int, group: int = 1,
                         value_width=None) -> int:
    """What `_fwd_lane_kernel` keeps in VMEM for one K/V lane block of
    `width` lanes (`heads` K/V heads; V and out `value_width` lanes where
    the values have a width of their own) and the `group` query heads of
    each: K and V whole and double-buffered, the Q and out tiles (`group`
    of them) double-buffered, the float32 accumulator, lse and the
    statistics padded to 8 sublanes, and the float32 score tiles: every
    query head's at once beside the compiler's own (a dozen at two
    heads)."""
    w = max(width, 128)
    wv = w if value_width is None else max(value_width, 128)
    whole = 2 * s * (w + wv) * itemsize
    tiles = block_q * group * (2 * (w + wv) * itemsize + 4 * wv)
    rows = 4 * 8 * block_q * 4 * group
    return (whole + tiles + rows
            + (8 + 2 * heads * group) * block_q * block_k * 4)


# The lane forward runs where a head block's working set stays under this
# (the same room the fused backward is given, below).
_FWD_KERNEL_VMEM_BUDGET = 96 << 20


def _fwd_lane_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
    *, causal, scale, window, logit_cap, heads, block_k, on_step=None,
):
    # grid (B, Kv/heads, S/bq); k/v are whole in S (their block index does
    # not move with the Q tile, so they are fetched once a block) and
    # q/out one Q tile of the query heads that read them, in one of two
    # views of the model's [B, S, H, D]:
    # - multi-head: blocks of the free [B, S, H*D] view, `heads` whole
    #   heads side by side in the lanes, q/out [1, bq, w] over the same
    #   lanes as k/v [1, S, w];
    # - grouped-query with heads of whole lane blocks: the [B, H, S, D]
    #   view, q/out [1, group, bq, d] the query heads of the one K/V head
    #   k/v [1, S, d], so each K and V tile is read from VMEM once for the
    #   whole group.
    # v and out may have a width of their own (`dv` a head: whole lane
    # blocks, as d then is), so values narrower than the scores cost
    # V^T p, the accumulator and V's fetch their own width and no more;
    # q, k, the scores, the statistics and lse know nothing of it.
    # Scores are K-major, z^T = K Q^T [bk, bq]: the running max and sum
    # are [1, bq] lane vectors reduced down the sublanes, and the
    # accumulator is kept transposed [query heads * dv, bq] so the
    # correction broadcasts along its sublanes; one transpose a Q tile
    # puts the output back in the operands' layout.
    qi = pl.program_id(2)
    grouped = len(q_ref.shape) == 4
    bq, w = q_ref.shape[-2], q_ref.shape[-1]
    bk = block_k
    n_k = k_ref.shape[1] // bk
    d, dv = w // heads, v_ref.shape[-1] // heads

    first_block = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    q2 = q_ref[0]
    if grouped:
        qs = [q2[g] for g in range(q2.shape[0])]
    elif heads > 1:
        # heads side by side in the lanes: a head's Q with the other
        # heads' lanes zeroed contracts over the whole block against the
        # unmasked K, and each head's output keeps its own rows of V^T p
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // d
        qs = [jnp.where(lane_head == h, q2, 0) for h in range(heads)]
    else:
        qs = [q2]
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    col0 = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)

    def step(kb, stats):
        if on_step is not None:  # the interpreted recorder's runtime count
            jax.debug.callback(on_step, first_block)
        ks = pl.multiple_of(kb * bk, bk)
        k2 = k_ref[0, pl.ds(ks, bk), :]      # [bk, w]
        v2 = v_ref[0, pl.ds(ks, bk), :]      # [bk, heads * dv]
        if causal:
            cols = ks + col0
            keep = rows >= cols
            if window is not None:
                # sliding band: row i sees cols in (i - window, i]
                keep = jnp.logical_and(keep, rows - cols < window)
        # every head's K Q_h^T before any softmax: the next head's matmul
        # then runs under this head's exponentials (1.43 -> 1.30 ms a call
        # at the training cells' shape against one head after the other)
        zs = [
            jax.lax.dot_general(k2, q_h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for q_h in qs
        ]                                    # [bk, bq] each
        new = []
        for h in range(len(qs)):
            z = zs[h] * scale
            if logit_cap is not None:
                z, _ = _apply_cap(z, logit_cap)
            if causal:
                z = jnp.where(keep, z, _NEG)
            m_prev, l_prev = stats[h]        # [1, bq]
            m_new = jnp.maximum(m_prev, jnp.max(z, axis=0, keepdims=True))
            p = jnp.exp(z - m_new)
            corr = jnp.exp(m_prev - m_new)
            new.append(
                (m_new, l_prev * corr + jnp.sum(p, axis=0, keepdims=True)))
            pv = jax.lax.dot_general(        # V^T p: [heads * dv, bq]
                v2, p.astype(v2.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            own = slice(h * dv, (h + 1) * dv)
            acc_ref[own, :] = acc_ref[own, :] * corr + (
                pv if grouped else pv[own, :])
        return tuple(new)

    lo, hi = _k_tile_range(qi, bq, bk, n_k, causal, window)
    stats = jax.lax.fori_loop(lo, hi + 1, step, tuple(
        (jnp.full((1, bq), _NEG, jnp.float32),
         jnp.zeros((1, bq), jnp.float32))
        for _ in range(len(qs))))
    for h, (m, l) in enumerate(stats):
        own = slice(h * dv, (h + 1) * dv)
        l = jnp.maximum(l, 1e-20)
        if grouped:
            o_ref[0, h] = (acc_ref[own, :] / l).T.astype(o_ref.dtype)
        else:
            acc_ref[own, :] = acc_ref[own, :] / l
        lse_ref[0, 0, 0, h:h + 1, :] = m + jnp.log(l)
    if not grouped:
        o_ref[0] = acc_ref[...].T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "causal", "block_q", "block_k", "interpret", "window", "scale",
    "logit_cap", "on_step"))
def _flash_forward_lanes(q, k, v, *, heads: int, causal: bool, block_q: int,
                         block_k: int, interpret: bool, window, scale: float,
                         logit_cap, on_step=None):
    """The forward with K and V of a lane block (`heads` whole K/V heads)
    resident in VMEM and an in-kernel loop over the K tiles
    `_tile_in_band` keeps for the Q tile.

    Multi-head attention reads q, k, v and writes out as blocks of the
    free [B, S, H*D] view of the model's layout: nothing is transposed in
    HBM. Grouped-query attention (heads of whole lane blocks: `heads` is
    1) takes the [B, H, S, D] view, a K/V head's whole group of query
    heads a step, so K and V are fetched once a K/V head and each of their
    tiles read once for the group. The `swapaxes` around it are the grid
    kernel's: in the serving prefill XLA lays q and out heads-outside
    around the call, so they are bitcasts there, where the [B, S, H*D]
    view cost a transposing copy of q and of out a layer (PERF.md
    section 6, PR 35).

    `v` may have a width of its own ([B, S, Kv, Dv], both widths whole
    lane blocks): its blocks, the out tile and the accumulator are then Dv
    wide and nothing else changes.

    Returns out [B, S, H, Dv] and lse as the lane vectors the kernel
    wrote, [B, lane blocks, S/block_q, query heads a block, block_q],
    query heads in order: what the fused backward reads as it is
    (multi-head), and `_lse_bhs` puts in order for the recurrences.

    Jitted on its own so that the layers of a program, and the programs
    of a process, that call it on one shape share a trace and a lowering:
    a group of seven unrolled is a body seven times as long to lower, and
    a serving start lowers it some seventy times (`setup_s`). `on_step`
    is the interpreted recorder's callback (`_first_block_counter`)."""
    b, s, h, d = q.shape
    kv, dv = k.shape[2], v.shape[3]
    group = h // kv
    from jax.experimental.pallas import tpu as pltpu

    # a lane block of q and k, and of v and out (a group's K/V head is a
    # lane block of its own: `heads` is 1 there)
    n_q, w, wv = s // block_q, heads * d, heads * dv
    if group > 1:
        operands = [jnp.swapaxes(t, 1, 2) for t in (q, k, v)]
        tile = lambda width: pl.BlockSpec(
            (1, group, block_q, width), lambda bi, hi, qi: (bi, hi, qi, 0))
        whole = lambda width: pl.BlockSpec(
            (1, None, s, width), lambda bi, hi, qi: (bi, hi, 0, 0))
        out_shape = (b, h, s, dv)
    else:
        operands = [t.reshape(b, s, -1) for t in (q, k, v)]
        tile = lambda width: pl.BlockSpec(
            (1, block_q, width), lambda bi, hi, qi: (bi, qi, hi))
        whole = lambda width: pl.BlockSpec(
            (1, s, width), lambda bi, hi, qi: (bi, 0, hi))
        out_shape = (b, s, h * dv)
    members = heads * group
    out, lse = pl.pallas_call(
        functools.partial(_fwd_lane_kernel, causal=causal, scale=scale,
                          window=window, logit_cap=logit_cap, heads=heads,
                          block_k=block_k, on_step=on_step),
        grid=(b, kv // heads, n_q),
        in_specs=[tile(w), whole(w), whole(wv)],
        out_specs=[
            tile(wv),
            pl.BlockSpec((1, 1, 1, members, block_q),
                         lambda bi, hi, qi: (bi, hi, qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, q.dtype),
            jax.ShapeDtypeStruct((b, kv // heads, n_q, members, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((members * dv, block_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_fwd_lane_vmem_bytes(
                s, heads, w, q.dtype.itemsize, block_q, block_k, group, wv),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    if group > 1:
        return jnp.swapaxes(out, 1, 2), lse
    return out.reshape(b, s, h, dv), lse


def _lse_bhs(lse):
    """lse as [B, H, S]: the grid forward's as it is, the lane forward's
    rows [B, lane blocks, S/block_q, query heads a block, block_q] put
    back in order."""
    if lse.ndim == 3:
        return lse
    b, blocks, n_q, heads, bq = lse.shape
    return lse.swapaxes(2, 3).reshape(b, blocks * heads, n_q * bq)


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool, block_q: int, block_k: int, interpret: bool,
    window=None, scale=None, logit_cap=None,
) -> Tuple[jax.Array, jax.Array]:
    b, s, h, d = q.shape
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"k {k.shape} and v {v.shape} must match in batch, length and "
            f"heads (the last dimension, the values' width, is v's own)")
    kv, dv = k.shape[2], v.shape[3]
    if k.shape[0] != b or k.shape[1] != s or k.shape[3] != d:
        # All tiling below derives from q.shape; a cross-attention call with
        # longer K/V would silently attend over the wrong range (ADVICE r1).
        raise ValueError(
            f"flash_attention requires self-attention shapes: q {q.shape}, "
            f"k {k.shape}, v {v.shape}; use impl='reference' for "
            f"cross-attention (Sk != Sq)"
        )
    if h % kv:
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {kv} (GQA)"
        )
    group = h // kv
    auto_blocks = block_q is None and block_k is None
    block_q = _resolve_block(block_q, s)
    block_k = _resolve_block(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"sequence length {s} is not divisible by the kernel tile "
            f"sizes ({block_q}, {block_k})"
            + (" chosen automatically — flash attention needs S to be a "
               "multiple of 128; pad the sequence or use impl='reference'"
               if auto_blocks else " — pass block_q/block_k that divide S")
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    if logit_cap is not None and logit_cap <= 0:
        raise ValueError(f"logit_cap={logit_cap} must be positive")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    from tfde_tpu.observability import counters

    # The lane kernel where it applies, the grid kernel elsewhere, decided
    # from the operands as `_bwd` decides: whole K/V heads tiling the 128
    # lanes, as many of them as Q heads (a lane block holds its heads' own
    # K/V) or each a lane block of its own under a group of Q heads, and a
    # lane block's working set (K and V whole) inside VMEM. Values of a
    # width of their own stay at it where that kernel takes them (both
    # widths whole lane blocks); any other unequal pair runs at one width,
    # padded here and nowhere else (zeros add nothing to a score, and the
    # padded output columns are dropped).
    heads = _bwd_heads_per_block(kv, d)
    lanes = (
        heads is not None and (group == 1 or d % 128 == 0)
        and (dv == d or d % 128 == dv % 128 == 0)
        and _fwd_lane_vmem_bytes(s, heads, heads * d, q.dtype.itemsize,
                                 block_q, block_k, group, heads * dv)
        <= _FWD_KERNEL_VMEM_BUDGET
    )
    if dv != d and not lanes:
        fill = lambda t: jnp.pad(
            t, ((0, 0),) * 3 + ((0, max(d, dv) - t.shape[3]),))
        out, lse = _flash_forward(
            fill(q), fill(k), fill(v), causal, block_q, block_k, interpret,
            window, scale, logit_cap)
        return out[..., :dv], lse
    counters.incr("flash/fwd_lane_traces" if lanes
                  else "flash/fwd_grid_traces")
    if dv != d:
        counters.incr("flash/fwd_two_width_traces")
    if _TILE_COUNTS is not None:
        n_q, n_k = s // block_q, s // block_k
        _TILE_COUNTS["fwd_path"] = "lane" if lanes else "grid"
        _TILE_COUNTS["fwd_grid"] = n_q * n_k
        _TILE_COUNTS["fwd_visits"] = len(
            _band_tile_pairs(s, block_q, block_k, causal, window)
        )
        _TILE_COUNTS["block_q"] = block_q
        _TILE_COUNTS["block_k"] = block_k
    if lanes:
        on_step = None
        if _TILE_COUNTS is not None and interpret:
            on_step = _first_block_counter(_TILE_COUNTS,
                                           "fwd_steps_executed")
        return _flash_forward_lanes(
            q, k, v, heads=heads, causal=causal, block_q=block_q,
            block_k=block_k, interpret=interpret, window=window, scale=scale,
            logit_cap=logit_cap, on_step=on_step)
    # The grid stays per QUERY head; under grouped-query each q head's K/V
    # index map folds onto its serving KV head (hi // group), so the kernel
    # body never sees the grouping and the [B,S,H,D] K/V expansion of a
    # repeat-then-attend formulation never exists in HBM.
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               window=window, logit_cap=logit_cap)
    # BSHD -> BHSD so the S/D dims are the TPU-tiled trailing pair
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    from jax.experimental.pallas import tpu as pltpu

    if causal:
        # skipped K-tiles (strictly past the Q-tile's last row, or — with a
        # sliding window — entirely older than the band) must not spend
        # DMA: point their index map at an in-band tile the pipeline will
        # need anyway; repeat fetches are elided, so masked-off steps cost
        # ~nothing instead of a dead K/V copy
        def kv_idx(bi, hi, qi, kb):
            run = kb * block_k <= (qi + 1) * block_q - 1
            if window is None:
                first = 0
            else:
                pre_band = (
                    kb * block_k + block_k - 1 < qi * block_q - (window - 1)
                )
                first = jnp.maximum(
                    (qi * block_q - (window - 1)) // block_k, 0
                )
                # post-diagonal skipped steps park on the just-used diagonal
                # tile (fetch elided), NOT on first(qi) — that tile already
                # passed, so pointing back at it would issue one dead
                # block_k x d DMA per Q-row; pre-band skipped steps park on
                # first(qi), the tile the first in-band step needs anyway
                diag = ((qi + 1) * block_q - 1) // block_k
                return (
                    bi, hi // group,
                    jnp.where(run, jnp.where(pre_band, first, kb), diag),
                    0,
                )
            return (bi, hi // group, jax.lax.select(run, kb, first), 0)
    else:
        def kv_idx(bi, hi, qi, kb):
            return (bi, hi // group, kb, 0)

    grid = (b, h, s // block_q, s // block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, kb: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_idx),
            pl.BlockSpec((1, 1, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, kb: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, kb: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # m (col 0 used)
            pltpu.VMEM((block_q, 128), jnp.float32),  # l (col 0 used)
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


def _bwd_pair_scan(res, g, *, block_q: int, block_k: int, window=None,
                   scale=None, logit_cap=None):
    """Causal/windowed backward: lax.scan over the statically enumerated
    in-band (Q-tile, K-tile) pairs, skipping strictly-future and
    out-of-band tiles entirely — compute AND the q/k/v/dO tile loads drop
    to ~half for plain causal and to O(S * window) for windowed, in both
    the dq and dk/dv accumulations (one scan serves both).

    Handles MHA and GQA uniformly: q is viewed [B,S,Kv,Grp,D] (Grp = 1 for
    MHA); dK/dV sum over each KV head's query group inside the contraction
    so the [B,S,H,D] K/V expansion never materializes. The carry holds the
    full fp32 dq/dk/dv; each step read-modify-writes one tile via
    dynamic_slice / dynamic_update_slice."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    kv = k.shape[2]
    grp = h // kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    pairs = _band_tile_pairs(s, block_q, block_k, True, window)

    qf = q.astype(jnp.float32).reshape(b, s, kv, grp, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32).reshape(b, s, kv, grp, d)
    # delta[b,c,g,i] = rowsum(dO * O); lse arrives [b,h,s] -> [b,c,g,s]
    delta = jnp.einsum(
        "bscgd,bscgd->bcgs", gf,
        out.astype(jnp.float32).reshape(b, s, kv, grp, d),
    )
    lse4 = lse.reshape(b, kv, grp, s)

    counts = _TILE_COUNTS
    if counts is not None:
        counts["bwd_grid"] = (s // block_q) * (s // block_k)
        counts["bwd_dq_visits"] = len(pairs)
        counts["bwd_dkv_visits"] = len(pairs)
        counts["bwd_pairs"] = len(pairs)

        def _bump():
            counts["bwd_steps_executed"] = (
                counts.get("bwd_steps_executed", 0) + 1
            )

    def step(carry, pair):
        dq, dk, dv = carry
        if counts is not None:
            jax.debug.callback(_bump)
        qs = pair[0] * block_q
        ks = pair[1] * block_k
        qt = jax.lax.dynamic_slice_in_dim(qf, qs, block_q, axis=1)
        gt = jax.lax.dynamic_slice_in_dim(gf, qs, block_q, axis=1)
        lt = jax.lax.dynamic_slice_in_dim(lse4, qs, block_q, axis=3)
        dt = jax.lax.dynamic_slice_in_dim(delta, qs, block_q, axis=3)
        kt = jax.lax.dynamic_slice_in_dim(kf, ks, block_k, axis=1)
        vt = jax.lax.dynamic_slice_in_dim(vf, ks, block_k, axis=1)
        z = jnp.einsum("bqcgd,bkcd->bcgqk", qt, kt,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            logits, t = _apply_cap(z, logit_cap)
        else:
            logits = z
        rows = qs + jnp.arange(block_q)
        cols = ks + jnp.arange(block_k)
        keep = rows[:, None] >= cols[None, :]
        if window is not None:
            keep = jnp.logical_and(
                keep, rows[:, None] - cols[None, :] < window
            )
        logits = jnp.where(keep, logits, _NEG)
        p = jnp.exp(logits - lt[..., None])  # [b,c,g,bq,bk]
        dv_t = jnp.einsum("bcgqk,bqcgd->bkcd", p, gt)
        dp = jnp.einsum("bqcgd,bkcd->bcgqk", gt, vt)
        ds = p * (dp - dt[..., None])
        if logit_cap is not None:
            # chain rule through c = cap * tanh(z / cap): dc/dz = 1 - t^2
            # (masked entries have p = 0, hence ds = 0, regardless of t)
            ds = ds * (1.0 - t * t)
        dq_t = jnp.einsum("bcgqk,bkcd->bqcgd", ds, kt) * scale
        dk_t = jnp.einsum("bcgqk,bqcgd->bkcd", ds, qt) * scale
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, qs, block_q, axis=1) + dq_t,
            qs, axis=1,
        )
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, ks, block_k, axis=1) + dk_t,
            ks, axis=1,
        )
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, ks, block_k, axis=1) + dv_t,
            ks, axis=1,
        )
        return (dq, dk, dv), None

    carry0 = (
        jnp.zeros((b, s, kv, grp, d), jnp.float32),
        jnp.zeros((b, s, kv, d), jnp.float32),
        jnp.zeros((b, s, kv, d), jnp.float32),
    )
    (dq, dk, dv), _ = jax.lax.scan(
        step, carry0, jnp.asarray(pairs, dtype=jnp.int32)
    )
    return (
        dq.reshape(b, s, h, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


def _bwd_blockwise(res, g, *, causal: bool, block_q=None, block_k=None,
                   window=None, scale=None, logit_cap=None):
    """Blockwise JAX backward: recompute P tile-by-tile from the saved
    logsumexp (standard flash-attention backward), O(S) memory. Causal
    (and windowed) routes to the in-band pair scan, which never visits
    out-of-band tiles; non-causal keeps the measured full K-tile scan."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_k = _resolve_block(block_k, s)
    if causal:
        with jax.named_scope("flash_bwd_pair_scan"):
            return _bwd_pair_scan(
                res, g, block_q=_resolve_block(block_q, s), block_k=block_k,
                window=window, scale=scale, logit_cap=logit_cap,
            )
    if k.shape[2] != h:
        return _bwd_blockwise_grouped(res, g, block_k=block_k, scale=scale,
                                      logit_cap=logit_cap)

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    # delta[b,h,i] = rowsum(dO * O)
    delta = jnp.einsum("bshd,bshd->bhs", gf, out.astype(jnp.float32))

    def step(carry, kb):
        dq = carry
        sl = jax.lax.dynamic_slice_in_dim(kf, kb * block_k, block_k, axis=1)
        vl = jax.lax.dynamic_slice_in_dim(vf, kb * block_k, block_k, axis=1)
        z = jnp.einsum("bqhd,bkhd->bhqk", qf, sl,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            logits, t = _apply_cap(z, logit_cap)
        else:
            logits = z
        p = jnp.exp(logits - lse[..., None])  # [b,h,Sq,bk]
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vl)
        ds = p * (dp - delta[..., None])  # [b,h,Sq,bk]
        if logit_cap is not None:
            ds = ds * (1.0 - t * t)
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, sl) * scale
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        return dq, (dk, dv)

    n_kb = s // block_k
    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(n_kb))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_blockwise_grouped(res, g, *, block_k: int, scale=None,
                           logit_cap=None):
    """GQA twin of the non-causal `_bwd_blockwise` scan: q [B,S,H,D]
    against k/v [B,S,Kv,D] with H = Kv * groups. Query heads carry an
    explicit group axis through the einsums (`c` = kv head, `g` = group
    member), so dK/dV sum over each KV head's query group inside the
    contraction and the [B,S,H,D] K/V expansion never materializes —
    mirroring grouped_attention (ops/attention.py). Causal/windowed GQA
    goes through `_bwd_pair_scan` instead (same grouped einsums, in-band
    tiles only)."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    kv = k.shape[2]
    grp = h // kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # block_k arrives already resolved by _bwd_blockwise (the only caller)

    qf = q.astype(jnp.float32).reshape(b, s, kv, grp, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32).reshape(b, s, kv, grp, d)
    # delta[b,c,g,i] = rowsum(dO * O); lse arrives [b,h,s] -> [b,c,g,s]
    delta = jnp.einsum(
        "bscgd,bscgd->bcgs", gf,
        out.astype(jnp.float32).reshape(b, s, kv, grp, d),
    )
    lse5 = lse.reshape(b, kv, grp, s)

    def step(carry, kb):
        dq = carry
        sl = jax.lax.dynamic_slice_in_dim(kf, kb * block_k, block_k, axis=1)
        vl = jax.lax.dynamic_slice_in_dim(vf, kb * block_k, block_k, axis=1)
        z = jnp.einsum("bqcgd,bkcd->bcgqk", qf, sl,
                       preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            logits, t = _apply_cap(z, logit_cap)
        else:
            logits = z
        p = jnp.exp(logits - lse5[..., None])  # [b,c,g,Sq,bk]
        dv = jnp.einsum("bcgqk,bqcgd->bkcd", p, gf)
        dp = jnp.einsum("bqcgd,bkcd->bcgqk", gf, vl)
        ds = p * (dp - delta[..., None])
        if logit_cap is not None:
            ds = ds * (1.0 - t * t)
        dq = dq + jnp.einsum("bcgqk,bkcd->bqcgd", ds, sl) * scale
        dk = jnp.einsum("bcgqk,bqcgd->bkcd", ds, qf) * scale
        return dq, (dk, dv)

    n_kb = s // block_k
    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, jnp.arange(n_kb))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, s, kv, d)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, s, kv, d)
    return (
        dq.reshape(b, s, h, d).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


def _bwd_heads_per_block(h: int, d: int):
    """How many heads share one lane block of the [B, S, H*D] view the
    fused backward reads, or None where no block of whole heads tiles the
    128 lanes: D a multiple of 128 takes one head, a head axis of at most
    128 lanes goes whole (the block is the full dimension), otherwise
    128/D heads sit side by side (two at D=64)."""
    if d % 128 == 0:
        return 1
    if h * d <= 128:
        return h
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return None


def _bwd_kernel_vmem_bytes(s: int, width: int, itemsize: int,
                           block_q: int, block_k: int) -> int:
    """What `_bwd_kernel` keeps in VMEM for one block of heads: Q, dO and
    the dQ output whole and double-buffered, the float32 dQ accumulator,
    the K/V/dK/dV tiles with their accumulators, lse and delta padded to
    8 sublanes, and a dozen float32 score tiles of the compiler's."""
    w = max(width, 128)
    whole = s * w * (3 * 2 * itemsize + 4)
    tiles = block_k * w * (4 * 2 * itemsize + 2 * 4)
    rows = 2 * 2 * 8 * s * 4
    return whole + tiles + rows + 12 * block_q * block_k * 4


# The fused backward runs where a head block's working set stays under
# this; v5e has 128 MiB of VMEM, and the kernel asks for what it needs.
_BWD_KERNEL_VMEM_BUDGET = 96 << 20


def _q_tile_range(kb, block_q: int, block_k: int, n_q: int, window):
    """First and last Q tile `_tile_in_band` keeps for K tile `kb`: both
    of its conditions are monotone in the Q tile, so the live tiles are
    one run. Python ints or traced scalars, as `_tile_in_band`."""
    lo = (kb * block_k) // block_q
    if window is None:
        return lo, n_q - 1
    hi = (kb * block_k + block_k + window - 2) // block_q
    return lo, jnp.minimum(hi, n_q - 1)


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
    *, scale, window, logit_cap, heads, block_q, on_pair=None,
):
    # grid (B, H/heads, S/bk), the K tile minor. Operands are blocks of
    # the [B, S, H*D] view the model has: q/dO/dq whole in S for this
    # block of heads (fetched once, dQ accumulates in scratch and is
    # written once), k/v/dk/dv one K tile. Scores are K-major, z^T =
    # K Q^T [bk, bq]: dV += p^T dO and dK += ds^T Q are plain
    # contractions and lse/delta are [1, bq] lane vectors.
    kb = pl.program_id(2)
    bk, w = k_ref.shape[1], k_ref.shape[2]
    bq = block_q
    n_q = q_ref.shape[1] // bq
    d = w // heads

    first_block = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    k2 = k_ref[0]
    v2 = v_ref[0]
    # heads side by side in the lanes: a head's K and V with the other
    # heads' lanes zeroed contract over the whole block against the
    # unmasked Q/dO, and each head's dK/dV keeps its own lanes
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // d
    own = [lane_head == h for h in range(heads)]    # [1, w] each
    if heads > 1:
        ks = [jnp.where(m, k2, 0) for m in own]
        vs = [jnp.where(m, v2, 0) for m in own]
    else:
        ks, vs = [k2], [v2]
    cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)

    def nt(a, b):  # contract the lanes of both: a b^T
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def pair(qi, carry):
        if on_pair is not None:  # the interpreted recorder's runtime count
            jax.debug.callback(on_pair, first_block)
        qs = pl.multiple_of(qi * bq, bq)
        q2 = q_ref[0, pl.ds(qs, bq), :]      # [bq, w]
        do2 = do_ref[0, pl.ds(qs, bq), :]
        rows = qs + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        keep = rows >= cols
        if window is not None:
            keep = jnp.logical_and(keep, rows - cols < window)
        dq = None
        for h in range(heads):
            z = nt(ks[h], q2) * scale        # [bk, bq]
            if logit_cap is not None:
                z, t = _apply_cap(z, logit_cap)
            z = jnp.where(keep, z, _NEG)
            p = jnp.exp(z - lse_ref[0, 0, qi, h:h + 1, :])
            ds = p * (nt(vs[h], do2) - delta_ref[0, 0, qi, h:h + 1, :])
            if logit_cap is not None:
                ds = ds * (1.0 - t * t)
            p = p.astype(do2.dtype)
            ds = ds.astype(q2.dtype)
            dv_h = jnp.dot(p, do2, preferred_element_type=jnp.float32)
            dk_h = jnp.dot(ds, q2, preferred_element_type=jnp.float32)
            dq_h = jax.lax.dot_general(      # ds^T^T K_h: [bq, w]
                ds, ks[h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if h == 0:
                dv_n, dk_n, dq = dv_h, dk_h, dq_h
            else:
                dv_n = jnp.where(own[h], dv_h, dv_n)
                dk_n = jnp.where(own[h], dk_h, dk_n)
                dq = dq + dq_h
        dq_acc[pl.ds(qs, bq), :] += dq
        dk_acc[...] += dk_n
        dv_acc[...] += dv_n
        return carry

    lo, hi = _q_tile_range(kb, bq, bk, n_q, window)
    jax.lax.fori_loop(lo, hi + 1, pair, 0)
    # the scale of z = scale * q k^T reaches dq and dk through ds: applied
    # once to the sums, as the recurrence does
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_fused(res, g, *, heads: int, block_q: int, block_k: int,
               interpret: bool, window, scale: float, logit_cap):
    """Causal multi-head backward as one kernel: every in-band (K tile,
    Q tile) pair computes z, p, dp and ds once, in VMEM, and updates dV,
    dK and dQ from them. Reads and writes the [B, S, H, D] layout the
    model has; nothing but the outputs is written to HBM. lse comes as
    the lane forward's rows (same heads a block and block_q, both from the
    operands) or as the grid forward's [B, H, S]."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    from jax.experimental.pallas import tpu as pltpu

    counts, on_pair = _TILE_COUNTS, None
    if counts is not None:
        visits = len(_band_tile_pairs(s, block_q, block_k, True, window))
        counts["bwd_grid"] = (s // block_q) * (s // block_k)
        counts["bwd_dq_visits"] = visits
        counts["bwd_dkv_visits"] = visits
        if interpret:
            on_pair = _first_block_counter(counts, "bwd_steps_executed")

    n_q, w = s // block_q, heads * d
    # delta[b,h,s] = rowsum(dO * O), fp32 — cheap elementwise, stays in JAX
    delta = jnp.einsum(
        "bshd,bshd->bhs", g.astype(jnp.float32), out.astype(jnp.float32)
    )

    def rows(x):  # [B, H, S] -> [B, H/heads, n_q, heads, bq] lane vectors
        return x.reshape(b, h // heads, heads, n_q, block_q).swapaxes(2, 3)

    if lse.ndim == 3:  # the grid forward's; the lane forward wrote rows
        lse = rows(lse)
    flat = lambda x: x.reshape(b, s, h * d)
    whole = pl.BlockSpec((1, s, w), lambda bi, hi, kb: (bi, 0, hi))
    tile = pl.BlockSpec((1, block_k, w), lambda bi, hi, kb: (bi, kb, hi))
    row = pl.BlockSpec((1, 1, n_q, heads, block_q),
                       lambda bi, hi, kb: (bi, hi, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, window=window,
                          logit_cap=logit_cap, heads=heads, block_q=block_q,
                          on_pair=on_pair),
        grid=(b, h // heads, s // block_k),
        in_specs=[whole, tile, tile, whole, row, row],
        out_specs=[whole, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, s, h * d), k.dtype),
            jax.ShapeDtypeStruct((b, s, h * d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((s, w), jnp.float32),        # dQ, whole
            pltpu.VMEM((block_k, w), jnp.float32),  # dK of this K tile
            pltpu.VMEM((block_k, w), jnp.float32),  # dV
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_bwd_kernel_vmem_bytes(
                s, w, q.dtype.itemsize, block_q, block_k),
        ),
        interpret=interpret,
        name="flash_bwd",
    )(flat(q), flat(k), flat(v), flat(g), lse, rows(delta))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q=None,
    block_k=None,
    interpret: bool = False,
    window=None,
    scale=None,
    logit_cap=None,
) -> jax.Array:
    """softmax(cap(QK^T * scale))V over q, k [B, S, H, D] and v
    [B, S, H, Dv] -> [B, S, H, Dv], O(S) memory.

    Dv may differ from D (a latent layer's heads score over 192 columns
    and carry values of 128), in the forward only: where both are whole
    lane blocks the lane kernel runs V^T p, its accumulator and V's fetch
    at Dv, any other unequal pair is padded to one width inside
    `_flash_forward`; the backward refuses unequal widths by name.

    GQA: k/v may carry fewer heads [B, S, Kv, D] with H a multiple of Kv —
    query head h reads K/V head h // (H / Kv) and the repeat-expanded K/V
    never exists in HBM: heads of whole lane blocks run a K/V head's group
    a step of the lane forward against its resident K and V, narrower ones
    the per-query-head grid whose K/V DMA folds onto the serving head. The
    backward under GQA is the recurrence, whichever forward ran.

    window: sliding-window band (requires causal) — position i attends the
    last `window` positions inclusive; out-of-band K tiles are skipped
    entirely (compute AND DMA) in BOTH the forward and the backward, so
    fwd+bwd cost drops to O(S * window).

    scale: logit multiplier, default 1/sqrt(D).
    logit_cap: Gemma-2 tanh softcapping — logits become
    cap * tanh(logits / cap) inside the kernels (forward and backward),
    before masking."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                            window, scale, logit_cap)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, window, scale,
         logit_cap):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                              window, scale, logit_cap)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, window, scale, logit_cap,
         res, g):
    from tfde_tpu.observability import counters

    # The fused kernel where it applies, the recurrences elsewhere, decided
    # from the operands: causal, as many K/V heads as Q heads (the kernel's
    # dK/dV blocks are per query head; grouped-query would need a reduction
    # across heads), whole heads tiling the 128 lanes, and a head block's
    # working set inside VMEM.
    q, k, v = res[:3]
    s, h, d = q.shape[1:]
    if v.shape[3] != d:
        raise NotImplementedError(
            f"flash_attention: only the forward takes values of a width of "
            f"their own (q and k {d} wide, v {v.shape[3]}); no backward "
            f"reads two widths. Pad v to {d} for a gradient, or use "
            f"impl='reference'")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = _resolve_block(block_q, s), _resolve_block(block_k, s)
    heads = _bwd_heads_per_block(h, d)
    kernel = (
        causal and k.shape[2] == h and heads is not None
        and _bwd_kernel_vmem_bytes(s, heads * d, q.dtype.itemsize, bq, bk)
        <= _BWD_KERNEL_VMEM_BUDGET
    )
    counters.incr("flash/bwd_kernel_traces" if kernel
                  else "flash/bwd_recurrence_traces")
    if _TILE_COUNTS is not None:
        _TILE_COUNTS["bwd_path"] = "kernel" if kernel else "recurrence"
    if kernel:
        return _bwd_fused(res, g, heads=heads, block_q=bq, block_k=bk,
                          interpret=interpret, window=window, scale=scale,
                          logit_cap=logit_cap)
    return _bwd_blockwise((*res[:4], _lse_bhs(res[4])), g, causal=causal,
                          block_q=block_q, block_k=block_k, window=window,
                          scale=scale, logit_cap=logit_cap)


flash_attention.defvjp(_fwd, _bwd)
