"""Mamba-2 state-space mixing (Dao & Gu, "Transformers are SSMs", 2024; the
`granitemoehybrid` release): a per-head scalar-decay recurrence over a
[head_dim, state] matrix, fed through a short causal depthwise
convolution.

One head of width P with state size N; x_t [P], B_t and C_t [N] (shared by
the heads of a group), dt_t > 0, A < 0, D a scalar skip:

    a_t = exp(A dt_t)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T              S_t [P, N]
    y_t = S_t C_t + D x_t

x, B and C are the three parts of one `xBC` stream after the convolution
xBC_t = silu(sum_{k<K} w_k xBC_{t-K+1+k} + b) per channel, so beside S a
row's running state is the convolution's tail, the last K-1 raw inputs.

Pure functions; the cache variables and the projections live in
models/transformer.py::Mamba2Mixer. Three entry points: `causal_conv`
(the convolution and the tail it leaves), `prefill` (chunked scan over
right-padded rows, under `jax.named_scope("ssm_prefill")`) and
`decode_step` (one token, `ssm_decode`). Plain XLA: no kernel here yet.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SSMShape:
    """The mixer's own widths (the release's `mamba_*` keys)."""

    heads: int          # mamba_n_heads
    head_dim: int       # mamba_d_head
    state: int          # mamba_d_state
    groups: int = 1     # mamba_n_groups: B and C are shared by heads/groups
    conv: int = 4       # mamba_d_conv
    chunk: int = 256    # mamba_chunk_size
    conv_bias: bool = True

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_channels(self) -> int:
        return self.inner + 2 * self.groups * self.state

    @property
    def in_features(self) -> int:
        """[z, xBC, dt] as one projection."""
        return self.inner + self.conv_channels + self.heads


def causal_conv(xbc: jax.Array, tail: jax.Array, kernel: jax.Array, bias,
                lengths: jax.Array) -> tuple:
    """silu(depthwise causal convolution) of xbc [B, S, C] continued from
    `tail` [B, K-1, C] (the raw inputs just before position 0; zeros at a
    row's start). kernel [K, C], bias [C] or None. Returns (out [B, S, C],
    the tail a row of true length `lengths` [B] leaves: its last K-1 raw
    inputs, reaching back into `tail` where the row is shorter)."""
    k = kernel.shape[0]
    s = xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    w = kernel.astype(jnp.float32)
    out = sum(full[:, i:i + s].astype(jnp.float32) * w[i] for i in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    # the K-1 inputs ending at the true length, full[len : len + K-1], as
    # a one-hot product (exact: one term a sum) and not a gather
    at = lengths[:, None] + jnp.arange(k - 1, dtype=lengths.dtype)[None, :]
    pick = at[:, :, None] == jnp.arange(full.shape[1])[None, None, :]
    new_tail = jnp.einsum("bjt,btc->bjc", pick.astype(full.dtype), full,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return jax.nn.silu(out).astype(xbc.dtype), new_tail.astype(tail.dtype)


def _split(xbc: jax.Array, shape: SSMShape) -> tuple:
    """xBC [..., C] -> x [..., H, P], B and C [..., G, N]."""
    gn = shape.groups * shape.state
    x, b, c = jnp.split(xbc, [shape.inner, shape.inner + gn], axis=-1)
    lead = xbc.shape[:-1]
    return (x.reshape(lead + (shape.heads, shape.head_dim)),
            b.reshape(lead + (shape.groups, shape.state)),
            c.reshape(lead + (shape.groups, shape.state)))


def prefill(xbc: jax.Array, dt: jax.Array, a_log: jax.Array, d: jax.Array,
            state: jax.Array, lengths: jax.Array, shape: SSMShape) -> tuple:
    """The recurrence over positions 0 .. S-1 of right-padded rows, a
    chunk of `shape.chunk` positions at a time under a `lax.scan` that
    carries the state: within a chunk the pairwise decays are one
    [H, Q, Q] tensor and the work is matmuls; across chunks only
    [B, H, P, N] lives.

    xbc [B, S, C] after the convolution, dt [B, S, H] float32 after the
    softplus, `state` [B, H, P, N] float32 at position 0, `lengths` [B]
    the true lengths: past them dt is 0 (decay 1, no input), so the state
    returned is the one at the true length. Returns (y [B, S, H, P] in
    xbc's dtype, D x included; state)."""
    with jax.named_scope("ssm_prefill"):
        bsz, s, _ = xbc.shape
        g, r = shape.groups, shape.heads // shape.groups
        q = min(shape.chunk, s)
        grown = -(-s // q) * q
        real = jnp.arange(grown)[None, :] < lengths[:, None]
        dt = jnp.where(real[..., None],
                       jnp.pad(dt.astype(jnp.float32),
                               ((0, 0), (0, grown - s), (0, 0))), 0.0)
        xbc = jnp.pad(xbc, ((0, 0), (0, grown - s), (0, 0)))
        x, bm, cm = _split(xbc, shape)
        n = grown // q
        neg_a = -jnp.exp(a_log.astype(jnp.float32)).reshape(g, r)
        skip = d.astype(jnp.float32).reshape(g, r, 1)
        dtype = xbc.dtype

        def chunks(t, *tail):   # [B, n q, ...] -> [n, B, q, *tail]
            return jnp.moveaxis(t.reshape((bsz, n, q) + tail), 1, 0)

        lower = jnp.tril(jnp.ones((q, q), bool))

        def one(carry, inp):
            # heads as [G, R]: B and C are shared by the R heads of a group
            xc, bc, cc, dtc = inp   # [B,q,G,R,P] [B,q,G,N] x2 [B,q,G,R]
            cum = jnp.cumsum(dtc * neg_a, axis=1)              # [B,q,G,R] <= 0
            cum_h = jnp.moveaxis(cum, 1, -1)                   # [B,G,R,q]
            # decay from j (exclusive) to i (inclusive), i >= j
            seg = jnp.exp(jnp.where(
                lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
            cb = jnp.einsum("bign,bjgn->bgij", cc, bc,
                            preferred_element_type=jnp.float32)
            m = cb[:, :, None] * seg                           # [B,G,R,q,q]
            xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(dtype)
            y = jnp.einsum("bgrij,bjgrp->bigrp", m.astype(dtype), xdt,
                           preferred_element_type=jnp.float32)
            # what the state at the chunk's start still contributes
            y = y + jnp.einsum(
                "bign,bgrpn->bigrp", cc.astype(jnp.float32), carry,
                preferred_element_type=jnp.float32) * jnp.exp(cum)[..., None]
            # the state at the chunk's end
            left = jnp.exp(cum[:, -1:] - cum)                  # [B,q,G,R]
            grow = jnp.einsum(
                "bjgrp,bjgn->bgrpn",
                (xdt.astype(jnp.float32) * left[..., None]).astype(dtype),
                bc, preferred_element_type=jnp.float32)
            carry = carry * jnp.exp(cum[:, -1])[..., None, None] + grow
            y = y + xc.astype(jnp.float32) * skip
            return carry, y.astype(dtype)

        hp = (g, r, shape.head_dim)
        state, y = jax.lax.scan(
            one, state.astype(jnp.float32).reshape((bsz,) + hp
                                                   + (shape.state,)),
            (chunks(x, *hp), chunks(bm, g, shape.state),
             chunks(cm, g, shape.state),
             chunks(dt, g, r)))
        y = jnp.moveaxis(y, 0, 1).reshape(bsz, grown, shape.heads,
                                          shape.head_dim)
        return y[:, :s], state.reshape(bsz, shape.heads, shape.head_dim,
                                       shape.state)


def decode_step(xbc: jax.Array, dt: jax.Array, a_log: jax.Array,
                d: jax.Array, state: jax.Array, live: jax.Array,
                shape: SSMShape) -> tuple:
    """One position: xbc [B, C] after the convolution, dt [B, H] float32,
    state [B, H, P, N] float32. A row that is not `live` [B] keeps its
    state (its feed is padding). Returns (y [B, H, P], state)."""
    with jax.named_scope("ssm_decode"):
        x, bm, cm = _split(xbc, shape)
        dt = dt.astype(jnp.float32)
        decay = jnp.exp(-jnp.exp(a_log.astype(jnp.float32)) * dt)  # [B,H]
        xf = x.astype(jnp.float32)
        rep = shape.heads // shape.groups
        bh = jnp.repeat(bm.astype(jnp.float32), rep, axis=1)       # [B,H,N]
        ch = jnp.repeat(cm.astype(jnp.float32), rep, axis=1)
        new = (state * decay[..., None, None]
               + (xf * dt[..., None])[..., None] * bh[:, :, None, :])
        y = jnp.sum(new * ch[:, :, None, :], axis=-1)
        y = y + xf * d.astype(jnp.float32)[:, None]
        state = jnp.where(live[:, None, None, None], new, state)
        return y.astype(xbc.dtype), state


def gated_rms_norm(y: jax.Array, z: jax.Array, gain: jax.Array,
                   eps: float) -> jax.Array:
    """rmsnorm(y * silu(z)) * gain over the last axis (one group: all the
    mixer's channels), in float32."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32)
