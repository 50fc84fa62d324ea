"""EVA chunked linear attention (Zheng et al., "Efficient Attention via
Control Variates", ICLR 2023; the EvaByte release): an exact softmax over
the keys of the query's own aligned window, beside one learned summary per
chunk of everything before that window, under ONE normaliser.

One head, width d, scale s, window W, chunk C (C divides W), q and k
already rotated at their absolute positions; w(i) = i // W:

- chunk summary of chunk c (positions cC .. cC+C-1):
  a_j = softmax_{j in c}(s k_j . phi), kbar_c = sum_j a_j k_j + mu,
  vbar_c = sum_j a_j v_j, with phi and mu learned per head;
- query i sees L_i = {j : w(j) = w(i), j <= i} exactly and, of what lies
  before its window, only R_i = {c : cC + C - 1 < w(i) W};
- o_i = (sum_L e^{s q_i.k_j} v_j + sum_R e^{s q_i.kbar_c} vbar_c)
        / (sum_L e^{s q_i.k_j} + sum_R e^{s q_i.kbar_c}).

Pure functions over [B, S, H, D]; the cache variables and the dispatch
live in models/transformer.py::MultiHeadAttention. Three entry points,
each under the `jax.named_scope` of its name so a device trace can tell
them apart: `chunk_summaries` (eva_summarise), `prefill` (eva_prefill),
`decode_step` (eva_decode). Plain XLA: there is no kernel here yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunk_summaries(k: jax.Array, v: jax.Array, phi: jax.Array,
                    mu: jax.Array, scale: float, chunk: int) -> tuple:
    """k, v [B, S, H, D] with S a multiple of `chunk`; phi, mu [H, D].
    Returns (kbar, vbar) [B, S/chunk, H, D] in float32. Products and sums
    stay elementwise in float32 (a chunk is 16 keys: nothing for the MXU,
    and a default-precision dot would round the pooling weights)."""
    with jax.named_scope("eva_summarise"):
        b, s, h, d = k.shape
        kc = k.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
        vc = v.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
        logits = scale * jnp.sum(kc * phi.astype(jnp.float32), axis=-1)
        a = jax.nn.softmax(logits, axis=2)[..., None]   # [B, n, C, H, 1]
        kbar = jnp.sum(a * kc, axis=2) + mu.astype(jnp.float32)
        vbar = jnp.sum(a * vc, axis=2)
        return kbar, vbar


def _merged_softmax(s_loc, s_rem, ok_loc, ok_rem, v_loc, v_rem):
    """softmax over the local and the remote scores under one normaliser
    (the two parts merged by their common log-sum-exp; no concatenated
    score tensor), times the values. s_* [B, H, Q, K*] float32, ok_*
    broadcastable masks, v_* [B, K*, H, D]."""
    low = jnp.finfo(jnp.float32).min
    s_loc = jnp.where(ok_loc, s_loc, low)
    s_rem = jnp.where(ok_rem, s_rem, low)
    top = jnp.maximum(s_loc.max(-1), s_rem.max(-1))[..., None]
    e_loc = jnp.exp(s_loc - top)
    e_rem = jnp.exp(s_rem - top)
    den = e_loc.sum(-1) + e_rem.sum(-1)                  # [B, H, Q]
    out = jnp.einsum("bhqk,bkhd->bqhd", e_loc.astype(v_loc.dtype), v_loc,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bhqn,bnhd->bqhd", e_rem.astype(v_rem.dtype),
                           v_rem, preferred_element_type=jnp.float32)
    return out / jnp.swapaxes(den, 1, 2)[..., None]


def prefill(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
            mu: jax.Array, lengths: jax.Array, *, window: int, chunk: int,
            scale: float, block_q: int = 512) -> tuple:
    """The whole layer over positions 0 .. S-1 of right-padded rows.

    q, k, v [B, S, H, D] (rotated), `lengths` [B] the true length of each
    row. Returns (out [B, S, H, D] in q's dtype, kbar, vbar
    [B, ceil(S/W) W / C, H, D] in k's dtype): the summary of a chunk that
    the true length does not fill is zero, so a padded tail lands in no
    summary. Outputs at padded positions are finite and meaningless.

    Computed a block of queries at a time against its own window's keys
    and the summary table, so the largest score tensor is
    [B, H, block_q, W + S/C] and never [S, S]."""
    if window % chunk:
        raise ValueError(
            f"eva window {window} must be a multiple of chunk {chunk}")
    with jax.named_scope("eva_prefill"):
        b, s, h, d = q.shape
        nw = -(-s // window)
        sp = nw * window
        if sp != s:
            grow = ((0, 0), (0, sp - s), (0, 0), (0, 0))
            q, k, v = (jnp.pad(t, grow) for t in (q, k, v))
        kbar, vbar = chunk_summaries(k, v, phi, mu, scale, chunk)
        nc = sp // chunk
        full = (jnp.arange(1, nc + 1) * chunk)[None, :] <= lengths[:, None]
        kbar = jnp.where(full[..., None, None], kbar, 0.0).astype(k.dtype)
        vbar = jnp.where(full[..., None, None], vbar, 0.0).astype(v.dtype)

        bq = block_q if window % block_q == 0 else window
        per_w = window // chunk
        cols = jnp.arange(window)
        chunks = jnp.arange(nc)

        def block(i):
            w = (i * bq) // window
            qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
            kw = jax.lax.dynamic_slice_in_dim(k, w * window, window, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(v, w * window, window, axis=1)
            s_loc = scale * jnp.einsum(
                "bqhd,bkhd->bhqk", qi, kw,
                preferred_element_type=jnp.float32)
            s_rem = scale * jnp.einsum(
                "bqhd,bnhd->bhqn", qi, kbar,
                preferred_element_type=jnp.float32)
            rows = i * bq - w * window + jnp.arange(bq)
            return _merged_softmax(
                s_loc, s_rem, cols[None, :] <= rows[:, None],
                chunks < w * per_w, vw, vbar)

        out = jax.lax.map(block, jnp.arange(sp // bq))   # [n, B, bq, H, D]
        out = jnp.moveaxis(out, 0, 1).reshape(b, sp, h, d)[:, :s]
        return out.astype(q.dtype), kbar, vbar


def _rows_slice(x, start, size: int):
    """x [B, N, ...] -> [B, size, ...] from each row's own `start` [B]."""
    return jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(
        row, i, size, axis=0))(x, start)


def _rows_update(x, new, start):
    """Write new [B, n, ...] into x [B, N, ...] at each row's `start`."""
    return jax.vmap(lambda row, part, i: jax.lax.dynamic_update_slice_in_dim(
        row, part, i, axis=0))(x, new.astype(x.dtype), start)


def live_window(k: jax.Array, lengths: jax.Array, window: int,
                size: int) -> jax.Array:
    """Of prefilled keys or values [B, S, H, D], each row's window in
    progress: the `size` positions from (lengths // W) W on, slot = position
    mod W. Slots at or past the true length hold the padded tail and are
    never read: the decode mask derives the live slots from the index."""
    s = k.shape[1]
    nw = -(-s // window)
    if nw * window != s:
        k = jnp.pad(k, ((0, 0), (0, nw * window - s), (0, 0), (0, 0)))
    start = jnp.clip(lengths // window, 0, nw - 1) * window
    return _rows_slice(k, start, size)


def decode_step(q: jax.Array, k: jax.Array, v: jax.Array,
                win_k: jax.Array, win_v: jax.Array, sum_k: jax.Array,
                sum_v: jax.Array, phi: jax.Array, mu: jax.Array,
                pos: jax.Array, live: jax.Array, *, window: int, chunk: int,
                scale: float) -> tuple:
    """One token per row. q, k, v [B, 1, H, D] rotated at `pos` [B], the
    token's absolute position; `live` [B] false for a row whose feed is
    padding (a finished row held at a frozen index).

    The key and value go to window slot pos mod W (the slot of position
    pos - W: reaching a multiple of W hands the window over). When this
    is a live row's C-th key of a chunk, the chunk's summary is computed
    from the window buffer and written to the table; a padded feed
    completes no chunk. The query then sees slots 0 .. pos mod W and the
    (pos // W) W / C summaries of the windows already closed.

    Returns (out [B, 1, H, D], win_k, win_v, sum_k, sum_v)."""
    with jax.named_scope("eva_decode"):
        slot = pos % window
        win_k = _rows_update(win_k, k, slot)
        win_v = _rows_update(win_v, v, slot)
        first = slot // chunk * chunk
        kbar, vbar = chunk_summaries(
            _rows_slice(win_k, first, chunk), _rows_slice(win_v, first, chunk),
            phi, mu, scale, chunk)                        # [B, 1, H, D]
        done = (live & ((pos + 1) % chunk == 0))[:, None, None, None]
        at = pos // chunk
        sum_k = _rows_update(
            sum_k, jnp.where(done, kbar, _rows_slice(sum_k, at, 1)), at)
        sum_v = _rows_update(
            sum_v, jnp.where(done, vbar, _rows_slice(sum_v, at, 1)), at)
        s_loc = scale * jnp.einsum("bqhd,bkhd->bhqk", q, win_k,
                                   preferred_element_type=jnp.float32)
        s_rem = scale * jnp.einsum("bqhd,bnhd->bhqn", q, sum_k,
                                   preferred_element_type=jnp.float32)
        ok_loc = jnp.arange(win_k.shape[1])[None, :] <= slot[:, None]
        ok_rem = (jnp.arange(sum_k.shape[1])[None, :]
                  < (pos // window * (window // chunk))[:, None])
        out = _merged_softmax(s_loc, s_rem, ok_loc[:, None, None, :],
                              ok_rem[:, None, None, :], win_v, sum_v)
        return out.astype(q.dtype), win_k, win_v, sum_k, sum_v
