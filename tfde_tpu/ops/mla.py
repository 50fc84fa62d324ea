"""Latent (multi-head latent, MLA) attention: the arithmetic of its two paths.

A layer caches, per token, one latent `c` of `latent` values (normalised)
and ONE rotary key `k_r` of `rope` values that every head shares: a cell of
`latent + rope` values and no head axis (576 values where 64 heads of K and
V would be 16,384). Per head h the keys and values are linear in the
latent, `k_nope_h = c W_uk[h]`, `v_h = c W_uv[h]`, and a score is

    (q_nope_h . k_nope_h + q_rope_h . k_r) * scale

**Per head** (`prefill_attention`): a call that holds all its own keys (a
wave from position 0, the plain forward) up-projects K and V per head and
attends causally over its own tokens: `2 (nope + rope) + 2 value` FLOP an
attended pair and head, the cheap form while K and V need not be kept.

**Absorbed** (`absorbed_attention`): a call over the cache never forms a K
or V per head. The up-projections move to the query's side:
`q_abs_h = q_nope_h W_uk[h]^T` (`latent` values a head), scores
`(q_abs_h . c + q_rope_h . k_r) * scale`, a weighted sum of LATENTS
`o_lat_h = P_h c`, then `o_h = o_lat_h W_uv[h]`: `2 (latent + rope) +
2 latent` FLOP an attended cell and head against one read of the cell. Up-
projecting every cached cell every tick instead would be `heads x (nope +
value)` values a cell of temporaries.

Both are plain `jax.numpy` here (the per-head path reaches the Mosaic flash
forward through `ops/attention.attention` once its scores pass
`_SCORES_BYTES`, each of its two widths padded to ITS next multiple of the
128 lanes: the kernel takes the score width of q and k and the value width
of v apart, 256 and 128 at the published 192 and 128). The flax layer that
owns the parameters, the cache and the choice of path is
`models/transformer.py::LatentAttention`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from tfde_tpu.ops import attention as attn_lib

#: float32 scores past this many bytes are never laid out whole: the
#: per-head path goes through the dispatcher (the flash forward on the
#: chip), the absorbed path a block of queries at a time
_SCORES_BYTES = 2 ** 30
#: queries a step of the absorbed path over a long call, and the bytes of
#: float32 scores such a step may lay out (`query_blocks`)
_QUERY_BLOCKS = (128, 64, 32, 16, 8)
_BLOCK_SCORES_BYTES = 2 ** 28
#: heads whose queries, keys and values exist at a time in the per-head
#: path of a long call: 64 heads of a 30,720-token wave are 1 GB each of
#: q, k and v before any padding, and at 16 heads the wave's program still
#: held 1.5 GiB of them and their padded copies (rehearsal, PR 40)
HEAD_CHUNK = 8

_NEG = -1e30


class MLAShape(NamedTuple):
    """The widths of a latent attention layer, by the names of its
    published keys: `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
    `v_head_dim`."""

    latent: int
    nope: int
    rope: int
    value: int

    @property
    def cell(self) -> int:
        """Values cached per token and layer: the latent and the one
        rotary key (the config's own `head_dim`)."""
        return self.latent + self.rope

    @property
    def query(self) -> int:
        """A head's score width (`q_head_dim`)."""
        return self.nope + self.rope


def prefill_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                      impl: str = "auto"):
    """Causal attention of a call over its own tokens, per head. q_nope /
    k_nope [B, S, H, nope], q_rope [B, S, H, rope], k_rope [B, S, rope]
    (rotated; one key for all heads), v [B, S, H, value] -> [B, S, H,
    value] in q's dtype.

    Two score products while the float32 scores fit (`_SCORES_BYTES`);
    past that through the dispatcher: [q_nope, q_rope] against
    [k_nope, k_rope], padded with zeros to the next multiple of 128 lanes
    of the score width, and v at its own width, padded only where that is
    no multiple of 128 (zeros add nothing to a score, and padded output
    columns are dropped)."""
    b, s, h, nope = q_nope.shape
    rope, value = q_rope.shape[-1], v.shape[-1]
    if 4 * b * h * s * s <= _SCORES_BYTES or s % 128:
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                               preferred_element_type=jnp.float32)) * scale
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, _NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(q_nope.dtype)
    def fill(t):
        short = -t.shape[-1] % 128
        return jnp.pad(t, ((0, 0),) * 3 + ((0, short),)) if short else t

    q = fill(jnp.concatenate([q_nope, q_rope], -1))
    k = fill(jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, rope))], -1))
    out = attn_lib.attention(q, k, fill(v), causal=True, impl=impl,
                             scale=scale)
    return out[..., :value].astype(q_nope.dtype)


def absorbed_attention(q_abs, q_rope, latents, rope_keys, valid, *,
                       scale: float):
    """Attention over cached cells without a K or V per head. q_abs
    [B, S, H, latent] (the no-position queries through `W_uk^T`), q_rope
    [B, S, H, rope] (rotated), latents [B, T, latent] and rope_keys
    [B, T, rope] (`c` and `k_r` per position, as the cache keeps them),
    valid [B, S, T] (which cells each query sees) -> the weighted sums of
    latents [B, S, H, latent] in q's dtype.

    Two score products and one for the sums, each over a cache leaf as it
    lies. The float32 scores [B, H, S, T] are laid out whole: a caller
    with many queries hands them over a block at a time
    (`query_blocks`)."""
    scores = (jnp.einsum("bqhc,btc->bhqt", q_abs.astype(latents.dtype),
                         latents, preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,btr->bhqt", q_rope.astype(rope_keys.dtype),
                           rope_keys, preferred_element_type=jnp.float32)
              ) * scale
    p = jax.nn.softmax(jnp.where(valid[:, None], scores, _NEG), axis=-1)
    return jnp.einsum("bhqt,btc->bqhc", p.astype(latents.dtype), latents,
                      preferred_element_type=jnp.float32
                      ).astype(q_abs.dtype)


def query_blocks(rows: int, heads: int, queries: int, cells: int) -> int:
    """Queries the absorbed path takes at a time over `cells` cached
    cells: all of them while the float32 scores fit (`_SCORES_BYTES`; a
    decode tick always), past that the largest of `_QUERY_BLOCKS` that
    divides them and keeps a block's scores under `_BLOCK_SCORES_BYTES`
    (a prefill wave's program holds this branch beside its own
    temporaries, taken or not)."""
    if 4 * rows * heads * queries * cells <= _SCORES_BYTES:
        return queries
    for block in _QUERY_BLOCKS:
        if queries % block == 0 and (
                4 * rows * heads * block * cells <= _BLOCK_SCORES_BYTES):
            return block
    return queries if queries % _QUERY_BLOCKS[-1] else _QUERY_BLOCKS[-1]


def head_chunks(rows: int, heads: int, queries: int) -> int:
    """Heads the per-head path holds q, k and v of at a time: all of them
    while the float32 scores of the call would fit, `HEAD_CHUNK` past
    that."""
    if 4 * rows * heads * queries * queries <= _SCORES_BYTES or (
            heads % HEAD_CHUNK):
        return heads
    return HEAD_CHUNK
