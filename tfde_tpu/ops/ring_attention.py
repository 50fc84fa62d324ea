"""Ring attention — sequence/context parallelism over the 'seq' mesh axis.

Long-context capability (absent from the reference, SURVEY.md §5; mandated
by the framework goals): the sequence dimension is sharded across chips, so
max context scales linearly with the ring size. Each device keeps its Q
shard resident and computes blockwise attention against the KV shard it
currently holds, while `jax.lax.ppermute` rotates the KV shards one hop
around the ring per step — compute overlaps the ICI transfer (XLA schedules
the collective-permute concurrently with the matmuls; on TPU the permute
rides neighbor ICI links, the topology ring attention was designed for).

Math: the standard online-softmax accumulation (same recurrence the flash
kernel uses) in fp32 —

    m' = max(m, rowmax(S));  o' = o*e^(m-m') + e^(S-m') V;  l' = l*e^(m-m') + rowsum(e^(S-m'))

which yields exactly softmax(QK^T)V after the last ring step, so numerics
match ops/attention.reference_attention to float tolerance regardless of
ring size (tests/test_ring_attention.py asserts this).

Masks: `causal` and key-padding masks ([B,1,1,S], ops/attention.padding_mask)
are supported — the padding row rotates with its KV shard; arbitrary dense
[B,H,Sq,Sk] masks are not (they would have to be sharded along two axes at
once).

GQA: k/v may carry fewer heads than q (H = Kv * groups) — the grouped ring
body rotates kv_heads-sized KV shards, shrinking the per-hop ICI transfer
by the group factor; numerics match ops/attention.grouped_attention
(tests/test_ring_attention.py). Long-context Mistral/LLaMA-class training
composes with the 'seq' axis out of the box.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -1e30  # finite -inf stand-in: keeps exp() NaN-free on fully-masked blocks


def _chunk_attention(carry, q, k, v, kv_valid, q_pos, k_pos, causal,
                     window=None, scale=None, logit_cap=None):
    """One online-softmax accumulation step against one KV chunk.

    GQA: k/v may carry fewer heads [B, Sk, Kv, D] than q (H = Kv * groups)
    — the score/value einsums index the KV head directly, mirroring
    ops/attention.grouped_attention, so the KV shards that rotate around
    the ring stay kv_heads-sized (the ICI transfer shrinks by the group
    factor, on top of the HBM saving). Accumulators stay per-QUERY-head,
    so the carries and every ring/block caller are unchanged.

    scale (None = 1/sqrt(d)) and logit_cap (Gemma-2 tanh softcapping,
    cap * tanh(s / cap) BEFORE masking — same ordering as
    grouped_attention) apply inside the chunk step, so capped models keep
    exact numerics across shard boundaries; the backward is plain AD
    through the recurrence."""
    o, m, l = carry
    b, sq, h, d = q.shape
    kv_heads = k.shape[2]
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if kv_heads != h:
        g = h // kv_heads  # head index = c * g + group member (h-major)
        qg = q.reshape(b, sq, kv_heads, g, d)
        s = jnp.einsum(
            "bqcgd,bkcd->bcgqk", qg, k, preferred_element_type=jnp.float32
        ).reshape(b, h, sq, sk)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
    s = s * scale
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)
    if kv_valid is not None:
        s = jnp.where(kv_valid[:, None, None, :], s, _NEG)
    if causal:
        allowed = q_pos[:, None] >= k_pos[None, :]  # [sq, sk] global positions
        if window is not None:
            # sliding band on GLOBAL positions: row i sees (i - window, i] —
            # exact across shard boundaries because q_pos/k_pos are global
            allowed = jnp.logical_and(
                allowed, q_pos[:, None] - k_pos[None, :] < window
            )
        s = jnp.where(allowed[None, None], s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))            # [b,h,sq]
    p = jnp.exp(s - m_new[..., None])                      # [b,h,sq,sk]
    corr = jnp.exp(m - m_new)                              # [b,h,sq]
    l_new = l * corr + jnp.sum(p, axis=-1)
    if kv_heads != h:
        pv = jnp.einsum(
            "bcgqk,bkcd->bqcgd",
            p.reshape(b, kv_heads, h // kv_heads, sq, sk).astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ).reshape(b, sq, h, d)
    else:
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv    # [b,sq,h,d]
    return o_new, m_new, l_new


def _block_attention(carry, q, k, v, kv_valid, q_pos, k_pos, causal,
                     block_k: int = 1024, window=None, scale=None,
                     logit_cap=None):
    """Online-softmax accumulation against the current KV shard, blockwise:
    the shard is scanned in `block_k` chunks so per-device score memory is
    O(sq * block_k), never O(sq * sk_shard) — the 'blockwise' half of ring
    attention's memory story (the ring shards the sequence across chips;
    this keeps each chip's local block from re-materializing a quadratic
    score tensor at large per-chip shards). Shards at or below `block_k`
    take the single-chunk path unchanged."""
    sk = k.shape[1]
    if sk <= block_k or sk % block_k:
        return _chunk_attention(carry, q, k, v, kv_valid, q_pos, k_pos,
                                causal, window=window, scale=scale,
                                logit_cap=logit_cap)

    def chunk(carry, i):
        start = i * block_k
        kc = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=1)
        kvc = (
            None if kv_valid is None
            else jax.lax.dynamic_slice_in_dim(kv_valid, start, block_k, axis=1)
        )
        kpc = jax.lax.dynamic_slice_in_dim(k_pos, start, block_k, axis=0)
        return (
            _chunk_attention(carry, q, kc, vc, kvc, q_pos, kpc, causal,
                             window=window, scale=scale,
                             logit_cap=logit_cap),
            None,
        )

    carry, _ = jax.lax.scan(chunk, carry, jnp.arange(sk // block_k))
    return carry


def ring_attention_manual(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_valid: Optional[jax.Array] = None,
    causal: bool = False,
    axis: str = "seq",
    ring_size: int = 1,
    block_k: int = 1024,
    vary_axes: tuple = (),
    window=None,
    scale=None,
    logit_cap=None,
) -> jax.Array:
    """The per-shard ring body, for callers ALREADY inside a manual region
    where `axis` is a manual mesh axis — e.g. a stage of the fully-manual
    pipeline (models/pipelined.py), which is how pp x sp composes: one
    flat manual region, pipe hops and seq rotations side by side, AD
    straight through (the round-3 refusal was about NESTED manual
    regions; a flat one lowers fine — tests/test_pipelined_lm.py pp x sp
    suite).

    q/k/v are this shard's [B, S_local, H, D]; `ring_size` the number of
    seq shards; `vary_axes` the manual axes accumulators must be typed
    varying over (normally every manual axis in play). Returns the
    local shard of softmax(QK^T)V over the GLOBAL sequence.
    """
    if window is not None and (not causal or window < 1):
        # the funnel both the public ring and the pp x sp manual dispatch
        # flow through — siblings (grouped/flash) validate identically, and
        # a silently ignored band would be wrong math, not an error
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    idx = jax.lax.axis_index(axis)
    sq = q.shape[1]
    out_dtype = q.dtype
    q_pos = idx * sq + jnp.arange(sq)
    b, _, h, d = q.shape
    from tfde_tpu.parallel.axes import vary_over

    o, m, l = (
        vary_over(jnp.zeros((b, sq, h, d), jnp.float32), vary_axes),
        vary_over(jnp.full((b, h, sq), _NEG, jnp.float32), vary_axes),
        vary_over(jnp.zeros((b, h, sq), jnp.float32), vary_axes),
    )
    n = ring_size
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        o_m_l, k, v, kv_valid = carry
        src = (idx - t) % n  # whose KV shard we hold at step t
        k_pos = src * sq + jnp.arange(sq)

        def accumulate(c):
            return _block_attention(
                c, q, k, v, kv_valid, q_pos, k_pos, causal,
                block_k=block_k, window=window, scale=scale,
                logit_cap=logit_cap,
            )

        if causal:
            # hop skip: a source shard entirely in this shard's future
            # (or, with a window, entirely older than the band) is fully
            # masked — its accumulation is an exact no-op, so skip the
            # compute and let only the rotation run. The long-context
            # windowed case is the payoff: with S >> window each query
            # shard overlaps O(window / S_local + 1) of the n hops, the
            # ring analog of the flash forward's O(S * window) tile skip.
            in_band = idx * sq + sq - 1 >= src * sq  # q_hi >= k_lo
            if window is not None:
                in_band = jnp.logical_and(
                    in_band,
                    idx * sq - (src * sq + sq - 1) < window,  # q_lo-k_hi
                )
            o_m_l = jax.lax.cond(in_band, accumulate, lambda c: c, o_m_l)
        else:
            o_m_l = accumulate(o_m_l)

        # rotate KV one hop; skipped after the last accumulation
        def rotate(args):
            k, v, kv_valid = args
            k = jax.lax.ppermute(k, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            if kv_valid is not None:
                kv_valid = jax.lax.ppermute(kv_valid, axis, perm)
            return k, v, kv_valid

        k, v, kv_valid = jax.lax.cond(
            t < n - 1, rotate, lambda args: args, (k, v, kv_valid)
        )
        return o_m_l, k, v, kv_valid

    (o, m, l), _, _, _ = jax.lax.fori_loop(
        0, n, body, ((o, m, l), k, v, kv_valid)
    )
    l = jnp.maximum(l, 1e-20)  # fully-masked rows (padding) stay finite
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(out_dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    mesh: Optional[Mesh] = None,
    axis: str = "seq",
    block_k: int = 1024,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """[B, S, H, D] attention with S sharded over `axis` of `mesh`.

    Global arrays in, global arrays out — call it like any attention; the
    shard_map inside binds the mesh axes. Degrades to a single local block
    (i.e. plain blockwise attention) when the mesh has no 'seq' axis.
    `block_k` caps the per-chip score-tensor chunk (see _block_attention).
    """
    if mesh is None or axis not in mesh.axis_names:
        raise ValueError(
            f"ring_attention needs a mesh with a {axis!r} axis; use "
            "ops.attention.attention(impl='reference') otherwise"
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1"
        )
    kv_valid = None
    if mask is not None:
        if mask.ndim != 4 or mask.shape[1] != 1 or mask.shape[2] != 1:
            raise NotImplementedError(
                "ring attention supports key-padding masks [B,1,1,S] only"
            )
        kv_valid = mask[:, 0, 0, :].astype(jnp.bool_)

    # Note on pp x sp: NESTING this shard_map inside a partial-manual pipe
    # region does not lower (Shardy, jax 0.9, backward residuals) — the
    # composition instead runs the extracted `ring_attention_manual` body
    # directly inside the pipe's FULLY-manual region (models/pipelined.py
    # via parallel/axes.manual_seq), one flat region, AD straight through.
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"query heads {q.shape[2]} must be a multiple of kv heads "
            f"{k.shape[2]} (GQA)"
        )
    batch = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    batch = batch if batch else None
    heads = "tensor" if "tensor" in mesh.axis_names else None
    if heads is not None and (q.shape[2] % mesh.shape["tensor"]
                              or k.shape[2] % mesh.shape["tensor"]):
        # GQA under TP: both head counts must divide so each shard keeps
        # whole query groups beside their serving KV heads
        raise NotImplementedError(
            f"ring attention with a 'tensor' axis of {mesh.shape['tensor']} "
            f"needs both q heads ({q.shape[2]}) and kv heads "
            f"({k.shape[2]}) divisible by it"
        )
    qkv_spec = P(batch, axis, heads, None)
    valid_spec = P(batch, axis)

    n = mesh.shape[axis]

    def local(q, k, v, kv_valid):
        # accumulators typed varying over every mesh axis: the incoming
        # q/k/v end up varying over all of them, and the fori_loop carry
        # type check requires input/output variance to match
        return ring_attention_manual(
            q, k, v, kv_valid, causal=causal, axis=axis, ring_size=n,
            block_k=block_k, vary_axes=tuple(mesh.axis_names),
            window=window, scale=scale, logit_cap=logit_cap,
        )

    if kv_valid is None:
        # thread a dummy validity plane so the shard_map signature is static
        def local2(q, k, v):
            return local(q, k, v, None)

        fn = jax.shard_map(
            local2, mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
        )
        return fn(q, k, v)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, valid_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, kv_valid)
