"""The gated delta rule (Yang et al., "Gated Delta Networks", 2024; the
`qwen3_next` release's `linear_attention` layers): a recurrence over one
[key_dim, value_dim] matrix per value head in which every token decays the
state, reads it at its key, and writes back the DIFFERENCE between its
value and what it read.

One value head with key width K and value width V; q_t and k_t [K]
l2-normalised (q times K^-0.5; a key head serves `value_heads /
key_heads` consecutive value heads), v_t [V], beta_t in (0, 1), g_t <= 0:

    S' = exp(g_t) S_{t-1}                              S [K, V] float32
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

q, k and v are the three parts of one stream after the causal depthwise
convolution of `ops/ssm.py::causal_conv` (no bias, SiLU), so beside S a
row's running state is the convolution's tail.

Within a chunk of Q positions the corrections u depend on each other
through a unit lower triangular system. With c_i the running sum of g
inside the chunk, D_ij = exp(c_i - c_j) for i >= j, S the state at the
chunk's start:

    (I + strict_lower(diag(beta) (K K^T * D))) [W, U]
        = diag(beta) [K * exp(c), V]
    U' = U - W S
    O  = (Q * exp(c)) S + lower(Q K^T * D) U'
    S <- exp(c_Q) S + (K * exp(c_Q - c))^T U'

The system does not read S, so `prefill` inverts the systems of `_GROUP`
chunks at once (`inverse`: forward substitution, row i of the inverse from
the rows above it, every system of the group side by side in the minor
axis, so that a step is one multiply and sum over all of them; not
`triangular_solve`, which XLA expands into the same 64 dependent row
updates ONE system at a time, and not the product
(I - N)(I + N^2)(I + N^4)... of the strict part N, whose powers grow
before they vanish and cancel in float32 where keys repeat), multiplies
the right-hand side by the inverse, and then walks the chunks with the
state (two `lax.scan`s, the outer over groups so that only one group's W
and U live; a group is cut out of the wave's arrays where they lie and its
output written into place). Pure functions; the cache variables
and the projections live in models/transformer.py::GatedDeltaMixer. Two
entry points beside `ssm.causal_conv`: `prefill` (chunked, over
right-padded rows, continuing from a cached state, under
`jax.named_scope("gdn_prefill")`; a traced one bumps the counter
`gdn/block_inverse_traces`) and `decode_step` (the four equations on
one token, `gdn_decode`). Plain XLA, float32 throughout: every matrix
product at `Precision.HIGHEST`, the steps of `inverse` float32
multiplies and sums on the vector unit. No kernel here yet.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from tfde_tpu.observability import counters

#: chunks whose triangular systems one step of the outer scan inverts
_GROUP = 16
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaShape:
    """The mixer's own widths (the release's `linear_*` keys)."""

    key_heads: int      # linear_num_key_heads
    value_heads: int    # linear_num_value_heads
    key_dim: int        # linear_key_head_dim
    value_dim: int      # linear_value_head_dim
    conv: int = 4       # linear_conv_kernel_dim
    chunk: int = 64     # the published implementation's; no config key

    def __post_init__(self):
        if self.key_heads <= 0 or self.value_heads % self.key_heads:
            raise ValueError(
                f"value_heads={self.value_heads} must be a multiple of "
                f"key_heads={self.key_heads}")

    @property
    def key_width(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def value_width(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def conv_channels(self) -> int:
        """[q, k, v]: what passes the convolution."""
        return 2 * self.key_width + self.value_width

    @property
    def in_features(self) -> int:
        """[q, k, v, z] as one projection."""
        return self.conv_channels + self.value_width


def _l2(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _split(qkv: jax.Array, shape: GatedDeltaShape) -> tuple:
    """qkv [..., C] after the convolution -> q and k [..., Hk, K]
    l2-normalised in float32 (q times K^-0.5), v [..., Hk, R, V] float32:
    key head j serves value heads j R .. j R + R - 1."""
    q, k, v = jnp.split(qkv, [shape.key_width, 2 * shape.key_width], axis=-1)
    lead = qkv.shape[:-1]
    heads = lead + (shape.key_heads, shape.key_dim)
    rep = shape.value_heads // shape.key_heads
    return (_l2(q.reshape(heads)) * shape.key_dim ** -0.5,
            _l2(k.reshape(heads)),
            v.reshape(lead + (shape.key_heads, rep, shape.value_dim)
                      ).astype(jnp.float32))


def inverse(system: jax.Array) -> jax.Array:
    """[..., n, n] unit lower triangular, float32 -> its inverse, by
    forward substitution over the whole batch at once: row i of the
    inverse is e_i - sum_{j < i} t_ij x_j, n - 1 dependent steps. The
    batch goes to the minor axis first, so that a step is one float32
    multiply and sum on the vector unit over all systems side by side,
    where `triangular_solve` walks the rows of one system at a time. The
    block recursion [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1,
    B^-1]] over blocks of 8 to 32 was tried on top of it, as vector
    products and as batched einsums on a nearly empty MXU, and is not
    kept: PERF.md section 6, PR 43, has the readings. Any n."""
    n = system.shape[-1]
    t = jnp.moveaxis(system.reshape((-1, n, n)), 0, -1)         # [n, n, N]
    eye = jnp.eye(n, dtype=t.dtype)[:, :, None]

    def row(i, x):      # rows from i on are still e's
        below = jnp.where(jnp.arange(n)[:, None] < i, t[i], 0.0)
        return jax.lax.dynamic_update_index_in_dim(
            x, eye[i] - jnp.sum(below[:, None] * x, axis=0), i, 0)

    x = jax.lax.fori_loop(1, n, row, jnp.broadcast_to(eye, t.shape))
    return jnp.moveaxis(x, -1, 0).reshape(system.shape)


def prefill(qkv: jax.Array, beta: jax.Array, g: jax.Array, state: jax.Array,
            lengths: jax.Array, shape: GatedDeltaShape, gate=None) -> tuple:
    """The recurrence over positions 0 .. S-1 of right-padded rows, a chunk
    of `shape.chunk` positions at a time, continued from `state`.

    qkv [B, S, C] after the convolution, beta [B, S, Hv] after the sigmoid
    and g [B, S, Hv] <= 0 (both float32), `state` [B, Hv, K, V] float32 at
    position 0, `lengths` [B] the true lengths: past them g and beta are 0
    (decay 1, nothing written), so the state returned is the one at the
    true length. `gate` = (z [B, S, Hv V], gain [V], eps): the rule's
    output goes through `norm_then_gate` a chunk at a time where it is
    made (a wave's o in float32, laid out once more for the norm, is two
    arrays of its length the chip has no room for). Returns (o
    [B, S, Hv, V] in qkv's dtype, normed and gated where `gate` is given;
    state)."""
    counters.incr("gdn/block_inverse_traces")
    with jax.named_scope("gdn_prefill"):
        bsz, s, _ = qkv.shape
        hk, dk, dv = shape.key_heads, shape.key_dim, shape.value_dim
        rep = shape.value_heads // hk
        size = min(shape.chunk, s)
        grown = -(-s // size) * size
        n = grown // size
        group = max(d for d in range(1, _GROUP + 1) if n % d == 0)
        real = jnp.arange(grown)[None, :] < lengths[:, None]

        def padded(t):
            return jnp.pad(t, ((0, 0), (0, grown - s))
                           + ((0, 0),) * (t.ndim - 2))

        def masked(t):      # [B, S, Hv] -> [B, S', Hk, R], 0 past the length
            t = jnp.where(real[..., None], padded(t.astype(jnp.float32)), 0.0)
            return t.reshape(bsz, grown, hk, rep)

        qkv, beta, g = padded(qkv), masked(beta), masked(g)
        if gate is not None:
            z, gain, eps = gate
            z = padded(z)
        span = group * size

        lower = jnp.tril(jnp.ones((size, size), bool))
        strict = jnp.tril(jnp.ones((size, size), bool), -1)
        eye = jnp.eye(size, dtype=jnp.float32)

        def some(carry, i):
            # B rows, G chunks, heads as [H key heads, R value heads each].
            # The group is cut out of the wave's arrays where they lie and
            # its output written into place: no copy of a wave's length is
            # laid out for the scan, and q, k, v take their float32 form a
            # group at a time
            state, out = carry

            def cut(t):         # [B, S', ...] -> [B, G, Q, ...]
                t = jax.lax.dynamic_slice_in_dim(t, i * span, span, 1)
                return t.reshape((bsz, group, size) + t.shape[2:])

            qc, kc, vc = _split(cut(qkv), shape)  # [B,G,Q,H,K] x2 [B,G,Q,H,R,V]
            bc, gc = cut(beta), cut(g)            # [B,G,Q,H,R]
            cum = jnp.cumsum(gc, axis=2)                       # <= 0
            cum_h = jnp.moveaxis(cum, 2, -1)                   # [B,G,H,R,Q]
            # decay from j (exclusive) to i (inclusive), i >= j
            seg = jnp.exp(jnp.where(
                lower, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
            kk = jnp.einsum("bgihk,bgjhk->bghij", kc, kc, precision=_HIGHEST)
            qk = jnp.einsum("bgihk,bgjhk->bghij", qc, kc, precision=_HIGHEST)
            beta_h = jnp.moveaxis(bc, 2, -1)[..., None]        # [B,G,H,R,Q,1]
            system = eye + jnp.where(
                strict, beta_h * kk[:, :, :, None] * seg, 0.0)
            into = jnp.moveaxis(jnp.exp(cum), 2, -1)[..., None]
            k_h = jnp.moveaxis(kc, 2, 3)[:, :, :, None]        # [B,G,H,1,Q,K]
            v_h = jnp.moveaxis(vc, 2, 4)                       # [B,G,H,R,Q,V]
            wu = jnp.einsum(
                "bghrij,bghrjw->bghriw", inverse(system),
                beta_h * jnp.concatenate([k_h * into, v_h], -1),
                precision=_HIGHEST)
            attend = jnp.where(lower, qk[:, :, :, None] * seg, 0.0)
            q_in = jnp.moveaxis(qc, 2, 3)[:, :, :, None] * into
            left = jnp.moveaxis(jnp.exp(cum[:, :, -1:] - cum), 2, -1)[..., None]
            k_out = k_h * left                                 # [B,G,H,R,Q,K]
            keep = jnp.exp(cum[:, :, -1])[..., None, None]     # [B,G,H,R,1,1]

            def one(state, chunk):
                w, u, attend, q_in, k_out, keep, *z_c = chunk
                u = u - jnp.einsum("bhrik,bhrkv->bhriv", w, state,
                                   precision=_HIGHEST)
                o = (jnp.einsum("bhrik,bhrkv->bhriv", q_in, state,
                                precision=_HIGHEST)
                     + jnp.einsum("bhrij,bhrjv->bhriv", attend, u,
                                  precision=_HIGHEST))
                state = state * keep + jnp.einsum(
                    "bhrjk,bhrjv->bhrkv", k_out, u, precision=_HIGHEST)
                # positions first, as the caller lays them out: the
                # stacked chunks then need no transpose at full length
                o = jnp.moveaxis(o, 3, 1).reshape(
                    bsz, size, shape.value_heads, dv)
                if gate is not None:
                    o = norm_then_gate(o, z_c[0].reshape(o.shape), gain, eps)
                return state, o.astype(qkv.dtype)

            first = lambda t: jnp.moveaxis(t, 1, 0)            # chunks first
            state, o = jax.lax.scan(
                one, state,
                (first(wu[..., :dk]), first(wu[..., dk:]), first(attend),
                 first(q_in), first(k_out), first(keep))
                + ((first(cut(z)),) if gate is not None else ()))
            o = jnp.moveaxis(o, 0, 1).reshape((bsz, span) + o.shape[3:])
            return (state, jax.lax.dynamic_update_slice_in_dim(
                out, o, i * span, 1)), None

        (state, o), _ = jax.lax.scan(
            some,
            (state.astype(jnp.float32).reshape(bsz, hk, rep, dk, dv),
             jnp.zeros((bsz, grown, shape.value_heads, dv), qkv.dtype)),
            jnp.arange(n // group))
        return o[:, :s], state.reshape(bsz, shape.value_heads, dk, dv)


def decode_step(qkv: jax.Array, beta: jax.Array, g: jax.Array,
                state: jax.Array, live: jax.Array,
                shape: GatedDeltaShape) -> tuple:
    """One position: qkv [B, C] after the convolution, beta and g [B, Hv]
    float32, state [B, Hv, K, V] float32. A row that is not `live` [B]
    keeps its state (its feed is padding). Returns (o [B, Hv, V], state)."""
    with jax.named_scope("gdn_decode"):
        q, k, v = _split(qkv, shape)
        rep = shape.value_heads // shape.key_heads
        q = jnp.repeat(q, rep, axis=1)                         # [B,Hv,K]
        k = jnp.repeat(k, rep, axis=1)
        v = v.reshape(v.shape[0], shape.value_heads, shape.value_dim)
        decayed = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
        read = jnp.sum(decayed * k[..., None], axis=-2)        # [B,Hv,V]
        u = beta.astype(jnp.float32)[..., None] * (v - read)
        new = decayed + k[..., None] * u[..., None, :]
        o = jnp.sum(new * q[..., None], axis=-2)
        state = jnp.where(live[:, None, None, None], new, state)
        return o.astype(qkv.dtype), state


def norm_then_gate(o: jax.Array, z: jax.Array, gain: jax.Array,
                   eps: float) -> jax.Array:
    """gain * (o / rms(o)) * silu(z) over the last axis (one head's
    values), in float32: the norm BEFORE the gate and the gain as it is
    stored (where `ssm.gated_rms_norm` gates first and the block's norms
    store 1 + gain)."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return o * gain.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
