"""Transformer encoder core shared by ViT (models/vit.py) and BERT
(models/bert.py) — the driver's scale-up configs (BASELINE.json configs[3-4]).

TPU-first choices:
- bf16 activations / fp32 params + LayerNorm (`dtype` vs `param_dtype`): MXU
  native precision on the matmuls, fp32 where numerics are touchy.
- Megatron-compatible weight shapes: qkv projections produce [embed, heads,
  head_dim] kernels (heads contiguous in one trailing block) and the output /
  fc2 projections consume their sharded dim first — so a tensor-parallel
  strategy can column/row-shard them over the 'tensor' axis with exactly two
  psums per block, both of which XLA overlaps with the following matmul.
- Activation constraints via parallel/axes.constrain: batch over data-like
  axes, sequence over 'seq', heads/hidden over 'tensor'. No-ops when the
  active mesh lacks those axes, so one definition serves every strategy.
- `remat` wraps each block in jax.checkpoint — HBM for FLOPs, the standard
  long-sequence trade.
"""

from __future__ import annotations

import functools
from typing import Callable, Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tfde_tpu.models.cache_state import CacheState
from tfde_tpu.ops import attention as attn_lib
from tfde_tpu.ops import eva_attention as eva_lib
from tfde_tpu.ops import gated_delta as gdn_lib
from tfde_tpu.ops import mla as mla_lib
from tfde_tpu.ops import ssm as ssm_lib
from tfde_tpu.ops.quant import QuantDenseGeneral, kv_dequantize, kv_quantize
from tfde_tpu.ops.rotary import apply_rotary, attention_temperature
from tfde_tpu.parallel.axes import batch_axes, constrain


def _check_quant(quant, train: bool = False) -> bool:
    """Shared `quant` field validation: None (fp) or 'int8' (serving-only
    W8A8 twins, ops/quant.py). train=True with quant on is refused here —
    round() has zero gradient, so a quantized projection would silently
    block all gradient flow (GPT raises the same error at the model level;
    this guard covers direct Encoder/Block/Mlp/MHA users)."""
    if quant not in (None, "int8"):
        raise ValueError(f"quant must be None or 'int8', got {quant!r}")
    if quant is not None and train:
        raise ValueError(
            "quant='int8' is a serving-only mode (round() has zero "
            "gradient) — train the fp model, then quantize_model it"
        )
    return quant == "int8"


#: a prefill into the dense K/V slab attends all its queries at once while
#: their float32 scores stay under this many bytes, and otherwise this many
#: queries at a time (`MultiHeadAttention._decode_attention`)
_PREFILL_SCORES_BYTES = 2 ** 30
_PREFILL_QUERY_BLOCK = 256


class MultiHeadAttention(nn.Module):
    """Self-attention with dispatchable kernel (ops/attention.attention).

    `decode=True` turns on autoregressive KV caching (the serving path,
    inference/decode.py): `cached_key`/`cached_value`/`cache_index`
    variables live in the "cache" collection (flax convention — created at
    `init` with the full `[B, max_len]` input, so the cache length is the
    generation budget). A call with S>1 is a *prefill* (writes the whole
    prompt's K/V at [index, index+S)); S=1 is one decode step. Both use
    `dynamic_update_slice` with a traced start, so the compiled step serves
    every position — no per-position recompiles, static shapes throughout
    (XLA/TPU requirement)."""

    num_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    attn_impl: str = "auto"
    causal: bool = False
    decode: bool = False
    rope: bool = False  # rotary q/k rotation (ops/rotary.py) inside the layer
    rope_theta: float = 10_000.0
    # RoPE frequency rescaling tuple (ops/rotary.scale_frequencies):
    # ('linear', factor) or ('llama3', factor, low, high, orig_max) — the
    # Llama-3.1 long-context convention. Tuple (not dict) so the module
    # config stays hashable.
    rope_scaling: Optional[tuple] = None
    # partial rotary (Phi convention): only the first rope_dim features of
    # each head rotate; None = full head_dim
    rope_dim: Optional[int] = None
    # grouped-query attention: K/V carry this many heads (must divide
    # num_heads); each KV head serves num_heads/num_kv_heads query heads.
    # None = classic MHA. The KV cache and its decode bandwidth shrink by
    # the same factor — the reason every modern serving stack uses GQA.
    num_kv_heads: Optional[int] = None
    use_bias: bool = True  # False: the LLaMA bias-free projections
    # True: bias on q/k/v (and fused qkv) even when use_bias=False — the
    # Qwen2 arrangement (qkv biased, out projection and MLP bias-free)
    qkv_bias: bool = False
    # per-head RMSNorm on q and k after projection, BEFORE rotary — the
    # Qwen3 arrangement (one [head_dim] scale each, shared across heads)
    qk_norm: bool = False
    ln_eps: float = 1e-6  # qk_norm epsilon (the block's rms_norm_eps)
    # the q/k norms store their gain as 1 + scale, as the block's norms do
    # under `norm_unit_offset` (make_norm)
    norm_unit_offset: bool = False
    # the Qwen3-Next arrangement: `query` is twice as wide, per head
    # [q | gate], and the attention's output is multiplied by
    # sigmoid(gate) before `out`
    output_gate: bool = False
    # one [embed, 3, heads, head_dim] projection instead of three
    # [embed, heads, head_dim] GEMMs: a 3x-wider matmul keeps the MXU
    # busier at small per-chip batch (the training MFU knob). Parameter
    # layout changes ('qkv' vs 'query'/'key'/'value'), so checkpoint
    # conversion (models/convert.py) and HF interop stay on the unfused
    # default; MHA only (GQA's k/v are shaped differently).
    fused_qkv: bool = False
    # None (fp) | 'int8': W8A8 dynamic-quantized projections (ops/quant.py)
    # — the serving-only decode-bandwidth lever; params via quantize_model
    quant: Optional[str] = None
    # sliding-window attention (Mistral convention): position i attends the
    # last `window` positions inclusive. Requires causal; composes with the
    # decode cache (the validity mask carries the band), the flash kernel
    # (windowed tile skip), and the 'seq' ring (band on global positions)
    window: Optional[int] = None
    # Gemma-2 attention deltas: attn_scale overrides the 1/sqrt(head_dim)
    # score scale (query_pre_attn_scalar^-0.5); attn_logit_cap softcaps
    # scores (cap * tanh(s/cap)). Both route through the attention()
    # dispatcher like every other knob — the flash kernel applies them
    # inside the fused forward AND backward and the seq ring inside its
    # chunk step, so capped models train fused and sequence-parallel.
    attn_scale: Optional[float] = None
    attn_logit_cap: Optional[float] = None
    # rolling KV cache (decode + window only): the cache is a ring of
    # min(budget, window) cells, each token writing slot (position mod
    # len) — decode memory bounded by the window, not the generation
    # budget (the Mistral rolling-buffer serving lever). OPT-IN because
    # cache REWIND (speculative decoding) breaks it: a rejected draft's
    # write can alias the slot of a committed token one window back; paths
    # that never rewind (inference/decode.generate/generate_ragged/
    # beam_search, and inference/server.ContinuousBatcher with its per-row
    # indices) turn it on via _decode_clone(rolling=True).
    rolling_cache: bool = False
    # paged KV cache (decode only, TFDE_PAGED_KV): K/V live in ONE shared
    # physical pool of `paged_blocks` blocks x `kv_block` tokens
    # ("pool_key"/"pool_value" cache vars) and each row carries a
    # "block_table" [B, nmax] mapping its logical block to a pool block.
    # Writes scatter by (table[pos // kv_block], pos % kv_block); attention
    # gathers the row's table back into position order, so the SAME static
    # program serves every (prompt length, rows) shape — the pad-ladder
    # compile collapse (inference/paged.py owns allocation/refcounts).
    # Block 0 is the null block: unallocated table slots point there and
    # out-of-range writes are routed there, so junk never lands in a live
    # block. Mutually exclusive with rolling_cache.
    paged_blocks: Optional[int] = None
    kv_block: int = 16
    # None (fp) | 'int8': quantized KV cache (TFDE_KV_QUANT). K/V are
    # stored int8 with one fp32 scale per (position, kv-head) — sidecar
    # cache vars "cached_key_scale"/"cached_value_scale" (dense) or
    # "pool_key_scale"/"pool_value_scale" (paged, organized per kv_block
    # like the payload so trie sharing/refcounts carry quantized blocks
    # for free). Quantize-on-write, dequantize fused into the attention
    # read (ops/quant.kv_quantize/kv_dequantize) — the wire format never
    # leaves the device program, and the cache footprint drops ~4x at
    # fp32 / ~2x at bf16 (minus the 4/head_dim scale overhead). Same
    # static program count as fp. Mutually exclusive with rolling_cache
    # (a rolling slot rewrites scales out of order with its payload).
    kv_quant: Optional[str] = None
    # 'full' (softmax over every earlier key, optionally windowed) | 'eva'
    # (ops/eva_attention.py: an exact softmax over the query's own aligned
    # window of `eva_window` positions beside one learned summary per
    # `eva_chunk` positions of everything before it). 'eva' adds two
    # [heads, head_dim] parameters, `eva_phi` and `eva_mu`, and under
    # decode=True a cache layout of its own (`_eva_decode_attention`).
    attention: str = "full"
    eva_window: int = 2048
    eva_chunk: int = 16

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def cache_state(self, max_len: Optional[int]) -> CacheState:
        """What this layer keeps of one row under decode=True in a cache
        of `max_len` positions (None: of a length not yet known), as the
        `self.variable("cache", ...)` calls of `_eva_attention` and
        `_decode_attention` make it: `cache_index` and `feed_pad` are
        bookkeeping and no bytes of the state."""
        cell = 2 * self.kv_heads * self.head_dim * jnp.dtype(
            self.dtype).itemsize
        if self.attention == "eva":
            cells = (min(self.eva_window, max_len)
                     + max(1, max_len // self.eva_chunk)) if max_len else 0
            return CacheState(
                "eva", cells, cell, window=self.eva_window,
                chunk=self.eva_chunk, not_by_position=(
                    "attention='eva' caches one window in progress and one "
                    "summary per chunk (models/transformer.py "
                    "MultiHeadAttention._eva_attention)"))
        if (self.rolling_cache and self.window is not None
                and (max_len is None or self.window < max_len)):
            # a window of `max_len` or more is never left behind: such a
            # ring has the slab's cells and its arithmetic
            return CacheState(
                "ring", self.window, cell, not_by_position=(
                    "its window layers keep a ring of `window` cells, slot "
                    "= position mod window, and a ring cannot give back an "
                    "overwritten cell (models/transformer.py "
                    "MultiHeadAttention._rolling_attention)"))
        if self.kv_quant == "int8":
            # an int8 payload and one float32 scale per K/V head beside it
            cell = 2 * self.kv_heads * (self.head_dim + 4)
        return CacheState("kv", max_len or 0, cell)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        mask: Optional[jax.Array] = None,
        train: bool = False,
    ) -> jax.Array:
        if self.kv_heads <= 0 or self.num_heads % self.kv_heads:
            # (12 % -4 == 0 in Python — the sign check is load-bearing)
            raise ValueError(
                f"num_kv_heads={self.kv_heads} must be positive and divide "
                f"num_heads={self.num_heads}"
            )
        if self.window is not None and not self.causal:
            raise ValueError(
                f"window={self.window} requires causal attention (the "
                f"sliding window is a band below the causal diagonal)"
            )
        b = batch_axes()
        if _check_quant(self.quant, train):
            proj = functools.partial(
                QuantDenseGeneral, dtype=self.dtype, use_bias=self.use_bias,
            )
        else:
            proj = functools.partial(
                nn.DenseGeneral,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                use_bias=self.use_bias,
            )
        in_bias = self.use_bias or self.qkv_bias
        if self.fused_qkv:
            if self.kv_heads != self.num_heads or self.output_gate:
                raise NotImplementedError(
                    "fused_qkv requires classic MHA (num_kv_heads=None) "
                    "without an output gate: GQA's k/v projections and a "
                    "gated query have different shapes and cannot stack "
                    "into one kernel"
                )
            qkv = proj(
                features=(3, self.num_heads, self.head_dim), name="qkv",
                use_bias=in_bias,
            )(x)  # [B, S, 3, H, D] from ONE GEMM
            q, k, v = (qkv[..., i, :, :] for i in range(3))
        else:
            q = proj(features=(self.num_heads,
                               (1 + self.output_gate) * self.head_dim),
                     name="query", use_bias=in_bias)(x)
            k = proj(features=(self.kv_heads, self.head_dim), name="key",
                     use_bias=in_bias)(x)
            v = proj(features=(self.kv_heads, self.head_dim),
                     name="value", use_bias=in_bias)(x)
        gate = None
        if self.output_gate:
            q, gate = q[..., :self.head_dim], q[..., self.head_dim:]
        if self.qk_norm:
            qk_rms = make_norm("rms", self.ln_eps, self.norm_unit_offset)
            q = qk_rms(name="q_norm")(q).astype(self.dtype)
            k = qk_rms(name="k_norm")(k).astype(self.dtype)
        if self.rope and not self.decode:
            q, k = self._rotate(q, k, jnp.zeros((), jnp.int32))
        # [B, S, H, D]: heads carry the tensor-parallel shard.
        q, k, v = (constrain(t, b, "seq", "tensor") for t in (q, k, v))
        if self.attention not in ("full", "eva"):
            raise ValueError(
                f"attention must be 'full' or 'eva', got {self.attention!r}"
            )
        if self.attention == "eva":
            y = self._eva_attention(q, k, v, mask, b)
        elif self.decode:
            if mask is not None:
                raise NotImplementedError(
                    "decode mode builds its own cache-position mask; "
                    "explicit masks are not supported"
                )
            if not self.causal:
                raise ValueError(
                    "decode=True requires causal attention (autoregressive "
                    "generation is a causal-LM capability)"
                )
            y = self._decode_attention(q, k, v, b)
        else:
            # GQA included: K/V stay kv_heads-shaped end to end — the
            # dispatcher routes to the flash kernel (GQA head-folding
            # index maps), the seq ring (kv_heads-sized shards rotate),
            # or the grouped einsum; never a repeat-then-attend
            # expansion. attn_scale/attn_logit_cap (the Gemma-2
            # attention deltas) go through the dispatcher too — every
            # impl applies them natively (flash inside the fused
            # forward+backward, ring inside its chunk step), so capped/
            # windowed models train fused and sequence-parallel; an impl
            # without cap support warn-falls-back to the grouped einsum
            # in the dispatcher rather than refusing here
            y = attn_lib.attention(
                q, k, v, mask=mask, causal=self.causal,
                impl=self.attn_impl, window=self.window,
                scale=self.attn_scale, logit_cap=self.attn_logit_cap,
            )
        if gate is not None:
            y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(y.dtype)
        y = constrain(y, b, "seq", "tensor")
        y = proj(features=x.shape[-1], axis=(-2, -1), name="out")(y)
        y = constrain(y, b, "seq")
        if self.dropout_rate > 0.0:
            y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return y

    def _rotate(self, q, k, start):
        """Rotary q/k rotation at absolute positions start + [0, S) — the
        ONE rotation site for the train forward and both decode paths. A
        cached key's rotation is fixed at write time, so each call rotates
        only its own tokens. `start` is a scalar (shared cache index) or
        [B] (per-row indices, the batched-speculation path): both broadcast
        to per-token positions [S] / [B, S], which apply_rotary accepts."""
        if not self.rope:
            return q, k
        pos = jnp.asarray(start, jnp.int32)[..., None] + jnp.arange(
            q.shape[1], dtype=jnp.int32
        )  # scalar -> [S] (shape-(1,) start broadcasts away), [B] -> [B, S]
        return (apply_rotary(q, pos, self.rope_theta,
                             rotary_dim=self.rope_dim,
                             scaling=self.rope_scaling),
                apply_rotary(k, pos, self.rope_theta,
                             rotary_dim=self.rope_dim,
                             scaling=self.rope_scaling))

    def _eva_attention(self, q, k, v, mask, batch) -> jax.Array:
        """attention='eva' (ops/eva_attention.py), forward and serving.

        Under decode=True the "cache" collection holds, per row, what
        the layer derives from the tokens and not the tokens' own K/V:
        `eva_window_key/value` [B, min(W, max_len), H, D], the window in
        progress (slot = position mod W, reused every W positions);
        `eva_summary_key/value` [B, max_len // C, H, D], one summary per
        chunk, written when the chunk's last key arrives; `cache_index`
        as every layout has it; and `feed_pad` [B], how many trailing
        tokens of THIS call are padding (0 unless the caller sets it;
        read once and reset). A call with S > 1 is a prefill from
        position 0 of right-padded rows (true length S - feed_pad); S = 1
        is one decode step at `cache_index`, and a padded one (a finished
        row held at a frozen index) completes no chunk. Which window is
        live and which summaries a query sees follow from the index
        alone, so a rewind of the index to the true prompt length after
        a padded prefill is enough, as for the dense slab. What position-
        indexed layouts offer (a rewind below a written summary, sharing
        by position, int8 cells, a block pool) this layout does not."""
        if (not self.causal or not self.rope or mask is not None
                or self.kv_heads != self.num_heads
                or self.window is not None
                or self.attn_logit_cap is not None
                or self.paged_blocks is not None or self.rolling_cache
                or self.kv_quant is not None):
            raise NotImplementedError(
                "attention='eva' is causal, rotary, one K/V head per query "
                "head, and keeps its own cache layout: no mask, sliding "
                "window, logit cap, paged pool, rolling cache or int8 KV"
            )
        window, chunk = self.eva_window, self.eva_chunk
        scale = (self.attn_scale if self.attn_scale is not None
                 else self.head_dim ** -0.5)
        shape = (self.num_heads, self.head_dim)
        phi = self.param("eva_phi", nn.initializers.normal(0.02), shape,
                         jnp.float32)
        mu = self.param("eva_mu", nn.initializers.zeros, shape, jnp.float32)
        bsz, sq = q.shape[:2]
        kind = dict(window=window, chunk=chunk, scale=scale)

        def plain_forward(q, k):
            everything = jnp.full((bsz,), sq, jnp.int32)
            return eva_lib.prefill(q, k, v, phi, mu, everything, **kind)[0]

        if not self.decode:
            return plain_forward(q, k)
        is_filled = self.has_variable("cache", "eva_window_key")
        wshape = (bsz, min(window, sq)) + shape
        sshape = (bsz, max(1, sq // chunk)) + shape
        win_k = self.variable("cache", "eva_window_key", jnp.zeros, wshape,
                              k.dtype)
        win_v = self.variable("cache", "eva_window_value", jnp.zeros, wshape,
                              v.dtype)
        sum_k = self.variable("cache", "eva_summary_key", jnp.zeros, sshape,
                              k.dtype)
        sum_v = self.variable("cache", "eva_summary_value", jnp.zeros,
                              sshape, v.dtype)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
        feed_pad = self.variable("cache", "feed_pad", jnp.zeros, (bsz,),
                                 jnp.int32)
        if not is_filled:
            # init pass: the variables were just created from this call's
            # [B, max_len] budget input; the plain forward
            return plain_forward(*self._rotate(q, k, jnp.zeros((), jnp.int32)))
        idx = cache_index.value
        q, k = self._rotate(q, k, idx)
        pad = feed_pad.value
        if sq > 1:
            lengths = sq - pad
            y, kbar, vbar = eva_lib.prefill(q, k, v, phi, mu, lengths,
                                            **kind)
            size = win_k.value.shape[1]
            new_wk = eva_lib.live_window(k, lengths, window, size)
            new_wv = eva_lib.live_window(v, lengths, window, size)
            n = min(kbar.shape[1], sum_k.value.shape[1])
            new_sk = sum_k.value.at[:, :n].set(kbar[:, :n])
            new_sv = sum_v.value.at[:, :n].set(vbar[:, :n])
        else:
            pos = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (bsz,))
            y, new_wk, new_wv, new_sk, new_sv = eva_lib.decode_step(
                q, k, v, win_k.value, win_v.value, sum_k.value, sum_v.value,
                phi, mu, pos, pad == 0, **kind)
        win_k.value = constrain(new_wk, batch, None, "tensor")
        win_v.value = constrain(new_wv, batch, None, "tensor")
        sum_k.value = constrain(new_sk, batch, None, "tensor")
        sum_v.value = constrain(new_sv, batch, None, "tensor")
        cache_index.value = idx + sq
        feed_pad.value = jnp.zeros_like(pad)
        return y

    def _decode_attention(self, q, k, v, batch) -> jax.Array:
        """Write this call's K/V into the cache, attend q over the filled
        prefix. The validity mask `j <= index + i` covers prefill (full
        causal triangle over the prompt) and single-step decode (attend
        everything written so far) in one expression.

        Contract: the caller must not advance `cache_index` past the cache
        budget — `index` is traced, so an overflow cannot raise here, and a
        predicated write would put a full-cache copy on the bandwidth-bound
        decode hot path (dynamic_update_slice would clamp the start and
        overwrite the last entries instead). inference/decode.generate sizes
        the cache to prompt + max_new_tokens exactly and can never overflow;
        direct drivers of this layer own the same invariant."""
        if self.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {self.kv_quant!r}"
            )
        if self.paged_blocks is not None:
            if self.rolling_cache and self.window is not None:
                raise NotImplementedError(
                    "paged_blocks and rolling_cache are mutually exclusive "
                    "cache layouts (a rolling slot can alias any pool "
                    "block); pick one"
                )
            return self._paged_attention(q, k, v, batch)
        is_filled = self.has_variable("cache", "cached_key")
        rolling = self.rolling_cache and self.window is not None
        quant = self.kv_quant == "int8"
        if quant and rolling:
            raise NotImplementedError(
                "kv_quant='int8' and rolling_cache are mutually exclusive: "
                "the rolling slot rewrite (slot = position mod window) "
                "would need a second modular scatter for the scale sidecar "
                "on the decode hot path; pick one"
            )
        cache_shape = list(k.shape)
        if rolling:
            cache_shape[1] = min(cache_shape[1], self.window)
        cached_key = self.variable("cache", "cached_key", jnp.zeros,
                                   tuple(cache_shape),
                                   jnp.int8 if quant else k.dtype)
        cached_value = self.variable("cache", "cached_value", jnp.zeros,
                                     tuple(cache_shape),
                                     jnp.int8 if quant else v.dtype)
        if quant:
            # fp32 scale per (row, position, kv-head) — zeros dequantize
            # to exact 0.0, matching the fp cache's zero fill
            scale_shape = tuple(cache_shape[:2]) + (k.shape[2],)
            key_scale = self.variable("cache", "cached_key_scale",
                                      jnp.zeros, scale_shape, jnp.float32)
            value_scale = self.variable("cache", "cached_value_scale",
                                        jnp.zeros, scale_shape, jnp.float32)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
        if rolling:
            # how many trailing tokens of THIS call are padding, per row
            # (0 unless the caller sets it; read once and reset): a ring
            # keeps a prefill's last TRUE tokens, which an index rewind
            # cannot recover once the padded tail has overwritten them
            feed_pad = self.variable("cache", "feed_pad", jnp.zeros,
                                     (k.shape[0],), jnp.int32)

        if not is_filled:
            # init pass: variables were just created from this call's shapes
            # (the [B, max_len] budget input) — plain causal attention.
            q, k = self._rotate(q, k, jnp.zeros((), jnp.int32))
            return attn_lib.grouped_attention(
                q, k, v, causal=True, window=self.window,
                scale=self.attn_scale, logit_cap=self.attn_logit_cap,
            )
        sq = q.shape[1]
        max_len = cached_key.value.shape[1]
        if sq > max_len and not rolling:
            raise ValueError(
                f"input length {sq} exceeds the cache budget {max_len}; "
                f"re-init the cache with a larger max_len"
            )
        idx = cache_index.value
        q, k = self._rotate(q, k, idx)
        if rolling:
            return self._rolling_attention(
                q, k, v, batch, cached_key, cached_value, cache_index,
                feed_pad
            )
        if quant:
            # quantize-on-write: the int8 payload + fp32 per-(position,
            # head) scale are what the scatter below stores; attention
            # reads dequantize after the scatter so this call's own
            # tokens round-trip through the wire format too (parity with
            # what a later step would read back)
            k_w, k_sc = kv_quantize(k)
            v_w, v_sc = kv_quantize(v)
        else:
            k_w, v_w = (k.astype(cached_key.value.dtype),
                        v.astype(cached_value.value.dtype))
        if idx.ndim == 0:
            # shared index (generate / batch-1 speculation): one cheap
            # dynamic_update_slice covers every row
            k_all = jax.lax.dynamic_update_slice(
                cached_key.value, k_w, (0, idx, 0, 0)
            )
            v_all = jax.lax.dynamic_update_slice(
                cached_value.value, v_w, (0, idx, 0, 0)
            )
            if quant:
                ks_all = jax.lax.dynamic_update_slice(
                    key_scale.value, k_sc, (0, idx, 0))
                vs_all = jax.lax.dynamic_update_slice(
                    value_scale.value, v_sc, (0, idx, 0))
        else:
            # per-row indices [B] (batched speculation, inference/
            # speculative.py: acceptance lengths diverge across rows, so
            # each row writes at its own offset). vmapping the update
            # slice over rows gives per-row starts and lowers to an
            # in-place scatter of just the sq new tokens — no full-cache
            # rewrite on the bandwidth-bound decode path.
            write = jax.vmap(
                lambda cache, new, i: jax.lax.dynamic_update_slice(
                    cache, new, (i, 0, 0)
                )
            )
            k_all = write(cached_key.value, k_w, idx)
            v_all = write(cached_value.value, v_w, idx)
            if quant:
                swrite = jax.vmap(
                    lambda cache, new, i: jax.lax.dynamic_update_slice(
                        cache, new, (i, 0)
                    )
                )
                ks_all = swrite(key_scale.value, k_sc, idx)
                vs_all = swrite(value_scale.value, v_sc, idx)
        cols = jnp.arange(max_len, dtype=jnp.int32)

        def valid_for(queries):
            """Which cells the call's queries number `queries` [n] see:
            the one at position idx + i sees kv j <= idx + i, under a
            window j in (pos - window, pos]. [1, 1, n, max_len] under the
            shared index, [B, 1, n, max_len] under per-row indices (row
            b's query i sits at idx[b] + i)."""
            pos = idx[..., None] + queries                 # [n] | [B, n]
            seen = cols <= pos[..., None]
            if self.window is not None:
                seen = jnp.logical_and(seen,
                                       pos[..., None] - cols < self.window)
            return seen[None, None] if idx.ndim == 0 else seen[:, None]

        cached_key.value = constrain(k_all, batch, None, "tensor")
        cached_value.value = constrain(v_all, batch, None, "tensor")
        if quant:
            key_scale.value = constrain(ks_all, batch, None, "tensor")
            value_scale.value = constrain(vs_all, batch, None, "tensor")
            # dequant fused into the attention read: elementwise
            # int8 * fp32-scale feeding the einsum, so the fp copy lives
            # only inside this program — HBM holds int8 + scales
            k_all = kv_dequantize(k_all, ks_all, k.dtype)
            v_all = kv_dequantize(v_all, vs_all, v.dtype)
        cache_index.value = idx + sq
        # grouped_attention == reference_attention at kv_heads == num_heads;
        # with GQA the kv_heads-shaped cache feeds the einsum directly (no
        # expanded copy on the bandwidth-bound decode path)
        attend = functools.partial(
            attn_lib.grouped_attention, scale=self.attn_scale,
            logit_cap=self.attn_logit_cap)
        block = _PREFILL_QUERY_BLOCK
        if (4 * q.shape[0] * self.num_heads * sq * max_len
                <= _PREFILL_SCORES_BYTES or sq % block):
            return attend(q, k_all, v_all, mask=valid_for(
                jnp.arange(sq, dtype=jnp.int32)))
        # a long prefill over a long slab: the float32 scores of all its
        # queries against the whole slab ([rows, heads, Sq, max_len]: 6 GB
        # a row at 6,144 over 8,192) do not fit. Into an empty cache (a
        # cold prompt: every key is one of this call's own) the dispatcher
        # attends causally over the call's tokens alone, on the chip with
        # the flash kernel; behind a cached prefix, a block of queries at
        # a time over the slab.

        def fresh():
            return attn_lib.attention(
                q, k, v, causal=True, impl=self.attn_impl,
                window=self.window, scale=self.attn_scale,
                logit_cap=self.attn_logit_cap).astype(q.dtype)

        def behind_a_prefix():
            def some(i):
                # the block's mask from its positions: a [Sq, max_len]
                # mask of the whole call sliced here would stand in memory
                # through the loop (5 GB at 30,720 over 32,768)
                return attend(
                    jax.lax.dynamic_slice_in_dim(q, i * block, block, 1),
                    k_all, v_all, mask=valid_for(
                        i * block + jnp.arange(block, dtype=jnp.int32)))

            out = jax.lax.map(some, jnp.arange(sq // block))
            return jnp.moveaxis(out, 0, 1).reshape(q.shape).astype(q.dtype)

        if idx.ndim:
            return behind_a_prefix()
        return jax.lax.cond(idx == 0, fresh, behind_a_prefix)

    def _paged_attention(self, q, k, v, batch) -> jax.Array:
        """Paged decode attention: write this call's K/V into pool blocks
        through the row's block table, gather the table back into position
        order, attend under the same `j <= index + i` validity mask as the
        dense path.

        Bit-exactness with the dense slab: the gathered [B, nmax*block]
        keys are in position order (table slot s holds positions
        [s*block, (s+1)*block)), so column j of the gather IS position j —
        identical to the dense cache column-for-column up to max_len, plus
        trailing columns the mask zeroes exactly (grouped_attention masks
        with finfo.min, so masked weights are exactly 0.0 and garbage
        columns contribute exact-zero terms to both the softmax numerator
        and denominator).

        Junk-write invariant (same as dense, plus the null-block routing):
        any write at a position beyond a row's committed count lands either
        in the row's own allocated-but-uncommitted cells (overwritten
        position-exactly before any mask reaches them), in an unallocated
        table slot (block 0), or past the table entirely (`slot >= nmax`,
        routed to block 0). Shared (refcounted) trie blocks are never
        written: the trie only holds COMPLETE prompt blocks, and a warm
        row's first write position >= pre_len is block-aligned into its
        own private block."""
        is_filled = self.has_variable("cache", "pool_key")
        block = self.kv_block
        bsz = k.shape[0]
        quant = self.kv_quant == "int8"
        pool_shape = (self.paged_blocks, block, k.shape[2], k.shape[3])
        pool_key = self.variable("cache", "pool_key", jnp.zeros,
                                 pool_shape,
                                 jnp.int8 if quant else k.dtype)
        pool_value = self.variable("cache", "pool_value", jnp.zeros,
                                   pool_shape,
                                   jnp.int8 if quant else v.dtype)
        if quant:
            # fp32 scale sidecar per pool block: [nblocks, block, Kv] rides
            # the same block ids as the payload, so trie sharing, refcounts
            # and defrag permutation carry the scales for free
            key_scale = self.variable("cache", "pool_key_scale", jnp.zeros,
                                      pool_shape[:3], jnp.float32)
            value_scale = self.variable("cache", "pool_value_scale",
                                        jnp.zeros, pool_shape[:3],
                                        jnp.float32)
        # nmax from the init call's [B, max_len] budget input; +1 because
        # the decode scan writes one-past-committed for finished rows
        block_table = self.variable(
            "cache", "block_table", jnp.zeros,
            (bsz, -(-(k.shape[1] + 1) // block)), jnp.int32)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
        if not is_filled:
            # init pass: pool/table variables just created — plain causal
            # attention over the budget input, exactly like the dense init
            q, k = self._rotate(q, k, jnp.zeros((), jnp.int32))
            return attn_lib.grouped_attention(
                q, k, v, causal=True, window=self.window,
                scale=self.attn_scale, logit_cap=self.attn_logit_cap,
            )
        sq = q.shape[1]
        nmax = block_table.value.shape[1]
        idx = cache_index.value
        q, k = self._rotate(q, k, idx)
        # scalar (shared) or [B] per-row indices both become [B] — the
        # paged program is per-row by construction
        idxv = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (bsz,))
        pos = idxv[:, None] + jnp.arange(sq, dtype=jnp.int32)  # [B, sq]
        slot = pos // block
        off = pos % block
        table = block_table.value  # [B, nmax]
        rows = jnp.arange(bsz, dtype=jnp.int32)[:, None]
        # out-of-table writes go to the null block, never a live one
        blk = jnp.where(slot < nmax,
                        table[rows, jnp.clip(slot, 0, nmax - 1)], 0)
        # sanitize the write: junk positions (a rider row pad-fed past its
        # committed count during a chunked prefill) can carry non-finite
        # activations — e.g. a learned position embedding looked up past
        # max_position fills NaN — and a masked column's exact-zero weight
        # still poisons the output through 0 * NaN. nan_to_num is identity
        # on every finite (legit) value, so bit-exactness is untouched;
        # it only guarantees the POOL itself never holds a non-finite cell
        if quant:
            # kv_quantize nan_to_nums internally — same sanitize guarantee
            # as the fp write below, plus the scale itself stays finite
            k_w, k_sc = kv_quantize(k)
            v_w, v_sc = kv_quantize(v)
            k_pool = pool_key.value.at[blk, off].set(k_w)
            v_pool = pool_value.value.at[blk, off].set(v_w)
            ks_pool = key_scale.value.at[blk, off].set(k_sc)
            vs_pool = value_scale.value.at[blk, off].set(v_sc)
            # gather payload + scales through the same table, dequant
            # fused into the attention read: [B, nmax*block, Kv, D]
            k_all = kv_dequantize(k_pool[table], ks_pool[table], k.dtype
                                  ).reshape(bsz, nmax * block, *k.shape[2:])
            v_all = kv_dequantize(v_pool[table], vs_pool[table], v.dtype
                                  ).reshape(bsz, nmax * block, *v.shape[2:])
        else:
            k_pool = pool_key.value.at[blk, off].set(
                jnp.nan_to_num(k.astype(pool_key.value.dtype)))
            v_pool = pool_value.value.at[blk, off].set(
                jnp.nan_to_num(v.astype(pool_value.value.dtype)))
            # gather the row's table into position order:
            # [B, nmax*block, Kv, D]
            k_all = k_pool[table].reshape(bsz, nmax * block, *k.shape[2:])
            v_all = v_pool[table].reshape(bsz, nmax * block, *v.shape[2:])
        cols = jnp.arange(nmax * block, dtype=jnp.int32)[None, None, :]
        valid = cols <= pos[:, :, None]  # [B, sq, nmax*block]
        if self.window is not None:
            valid = jnp.logical_and(valid, pos[:, :, None] - cols
                                    < self.window)
        valid = valid[:, None]
        pool_key.value = constrain(k_pool, None, None, "tensor")
        pool_value.value = constrain(v_pool, None, None, "tensor")
        if quant:
            key_scale.value = constrain(ks_pool, None, None, "tensor")
            value_scale.value = constrain(vs_pool, None, None, "tensor")
        cache_index.value = idx + sq
        return attn_lib.grouped_attention(
            q, k_all, v_all, mask=valid, scale=self.attn_scale,
            logit_cap=self.attn_logit_cap,
        )

    def _rolling_attention(self, q, k, v, batch, cached_key, cached_value,
                           cache_index, feed_pad) -> jax.Array:
        """A ring of Wc = min(budget, window) cells beside the other
        layers' slabs: the token at absolute position p lives in slot
        p mod Wc, so a window layer's decode memory is O(window) however
        long the row runs.

        Caller invariant (STRICTER than "no rewind"): ONE prefill from
        position 0 into an empty ring, then single-token steps; a
        multi-token write onto a filled ring would clobber in-window keys
        its own earlier queries still need, and `cache_index` is traced
        so no runtime check can fire. generate / generate_ragged /
        beam_search (one shared index) and the batcher (a fresh row cache
        per wave, then per-row indices) satisfy it; speculative decoding
        violates it twice over (multi-token verify steps AND rewind) and
        never rolls.

        The prefill (S > 1) attends over the call's own tokens, banded
        and causal: every key a query needs is in the call (through the
        dispatcher once the float32 scores pass `_PREFILL_SCORES_BYTES`,
        so on the chip a long wave takes the flash forward and no S x S
        array exists). It keeps each row's last min(true length, Wc)
        tokens, true length S - `feed_pad`: slot j holds the latest true
        position congruent to j. Where S <= Wc nothing wraps, the padded
        tail lands past the true length as in a slab and the index rewind
        hides it.

        A step (S = 1) at position p, a row's own under per-row indices,
        overwrites slot p mod Wc, the cell of position p - Wc, and attends
        over the slots written so far: all of them once p >= Wc, slots
        0 .. p before. Wc <= window, so every cell in the ring is inside
        the band. A frozen row of a scan (its feed is padding) writes its
        slot too; nothing reads that row again before a wave replaces it.
        """
        bsz, sq = q.shape[:2]
        wc = cached_key.value.shape[1]
        idx = cache_index.value
        kd, vd = cached_key.value.dtype, cached_value.value.dtype
        if sq > 1:
            if idx.ndim != 0:
                raise ValueError(
                    "a ring is prefilled once, from position 0 of a fresh "
                    "row cache (one shared index); per-row indices are the "
                    "single-token steps after it")
            lengths = sq - feed_pad.value
            with jax.named_scope("attn_window_prefill"):
                attend = (
                    attn_lib.grouped_attention
                    if (4 * bsz * self.num_heads * sq * sq
                        <= _PREFILL_SCORES_BYTES or sq % _PREFILL_QUERY_BLOCK)
                    else functools.partial(attn_lib.attention,
                                           impl=self.attn_impl))
                y = attend(q, k, v, causal=True, window=self.window,
                           scale=self.attn_scale,
                           logit_cap=self.attn_logit_cap).astype(q.dtype)
            with jax.named_scope("attn_ring_write"):
                k_all = _ring_of(k.astype(kd), lengths, cached_key.value)
                v_all = _ring_of(v.astype(vd), lengths, cached_value.value)
        else:
            pos = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (bsz,))
            with jax.named_scope("attn_ring_write"):
                k_all = _ring_put(cached_key.value, k.astype(kd), pos)
                v_all = _ring_put(cached_value.value, v.astype(vd), pos)
            with jax.named_scope("attn_ring_decode"):
                cols = jnp.arange(wc, dtype=jnp.int32)
                valid = cols[None, :] <= pos[:, None]       # [B, Wc]
                y = attn_lib.grouped_attention(
                    q, k_all, v_all, mask=valid[:, None, None, :],
                    scale=self.attn_scale, logit_cap=self.attn_logit_cap,
                )
        cached_key.value = constrain(k_all, batch, None, "tensor")
        cached_value.value = constrain(v_all, batch, None, "tensor")
        cache_index.value = idx + sq
        feed_pad.value = jnp.zeros_like(feed_pad.value)
        return y


def _ring_put(ring, new, pos):
    """One step's key or value new [B, 1, Kv, D] at position `pos` [B]
    into ring [B, Wc, Kv, D]: slot pos mod Wc, over the cell of position
    pos - Wc; an in-place scatter of the one cell a row."""
    return eva_lib._rows_update(ring, new, pos % ring.shape[1])


def _ring_of(x, lengths, ring):
    """A prefill's keys or values x [B, S, Kv, D] (positions 0 .. S-1,
    true length `lengths` [B]) as the ring [B, Wc, Kv, D] holds them: slot
    j the latest true position congruent to j mod Wc. S <= Wc: the tokens
    as they stand, from slot 0 of `ring` (a fresh one). Otherwise each
    row's last Wc true tokens, a contiguous run from `start`, turned by
    start mod Wc so that position p stands in slot p mod Wc."""
    s, wc = x.shape[1], ring.shape[1]
    if s <= wc:
        return jax.lax.dynamic_update_slice(ring, x, (0, 0, 0, 0))
    start = jnp.clip(lengths - wc, 0, s - wc)
    run = eva_lib._rows_slice(x, start, wc)
    return eva_lib._rows_slice(jnp.concatenate([run, run], axis=1),
                               (wc - start % wc) % wc, wc)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space mixer (ops/ssm.py) in MultiHeadAttention's
    place: `in_proj` to [z, xBC, dt], a causal depthwise `conv1d` over
    xBC, the recurrence with `A_log`, `D` and `dt_bias` per head, a gated
    RMSNorm (`norm_scale`) and `out_proj`.

    Under decode=True the "cache" collection holds per row a running
    state and no axis of positions: `ssm_state` [B, H, P, N] float32,
    `conv_tail` [B, K-1, C] (the last K-1 raw xBC inputs) and `feed_pad`
    [B], how many trailing tokens of THIS call are padding (0 unless the
    caller sets it; read once and reset). A call continues from the
    cached state: S > 1 feeds right-padded rows of true length S -
    feed_pad, past which the state stands and the tail kept ends at the
    true length; S = 1 is one step, and a padded one changes neither. A
    state cannot be rewound, shared or re-encoded by position."""

    ssm: ssm_lib.SSMShape
    dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    ln_eps: float = 1e-6

    def cache_state(self, max_len: Optional[int] = None) -> CacheState:
        """`ssm_state` and `conv_tail` of one row, as `__call__` makes
        them (`feed_pad` is bookkeeping), whatever the cache's length."""
        shape = self.ssm
        return CacheState(
            "state", fixed_bytes=(
                shape.heads * shape.head_dim * shape.state * 4
                + (shape.conv - 1) * shape.conv_channels
                * jnp.dtype(self.dtype).itemsize),
            not_by_position=(
                "its 'mamba' layers cache a running state and a "
                "convolution tail with no axis of positions "
                "(models/transformer.py Mamba2Mixer)"))

    @nn.compact
    def __call__(self, x: jax.Array, mask: Optional[jax.Array] = None,
                 train: bool = False) -> jax.Array:
        if mask is not None:
            raise NotImplementedError(
                "a state-space mixer takes no attention mask")
        b = batch_axes()
        shape = self.ssm
        bsz, sq, width = x.shape
        dense = functools.partial(nn.Dense, dtype=self.dtype,
                                  param_dtype=jnp.float32, use_bias=False)
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (shape.conv, shape.conv_channels), jnp.float32)
        conv_bias = (self.param("conv_bias", nn.initializers.zeros,
                                (shape.conv_channels,), jnp.float32)
                     if shape.conv_bias else None)
        a_log = self.param(
            "A_log", lambda _k, s: jnp.log(jnp.arange(1, s[0] + 1,
                                                      dtype=jnp.float32)),
            (shape.heads,))
        skip = self.param("D", nn.initializers.ones, (shape.heads,),
                          jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros,
                             (shape.heads,), jnp.float32)
        gain = self.param("norm_scale", nn.initializers.ones,
                          (shape.inner,), jnp.float32)

        zxd = dense(shape.in_features, name="in_proj")(x)
        z, xbc, dt = jnp.split(
            zxd, [shape.inner, shape.inner + shape.conv_channels], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))

        tail0 = jnp.zeros((bsz, shape.conv - 1, shape.conv_channels),
                          xbc.dtype)
        state0 = jnp.zeros((bsz, shape.heads, shape.head_dim, shape.state),
                           jnp.float32)
        filled = self.decode and self.has_variable("cache", "ssm_state")
        if self.decode:
            state = self.variable("cache", "ssm_state", lambda: state0)
            tail = self.variable("cache", "conv_tail", lambda: tail0)
            feed_pad = self.variable("cache", "feed_pad", jnp.zeros, (bsz,),
                                     jnp.int32)
        if filled:
            pad = feed_pad.value
            state0, tail0 = state.value, tail.value
        else:
            # the plain forward, and decode's init pass (the variables
            # were just created; flax convention)
            pad = jnp.zeros((bsz,), jnp.int32)
        lengths = sq - pad
        xbc, new_tail = ssm_lib.causal_conv(xbc, tail0, conv_kernel,
                                            conv_bias, lengths)
        if sq > 1 or not filled:
            y, new_state = ssm_lib.prefill(xbc, dt, a_log, skip, state0,
                                           lengths, shape)
        else:
            y, new_state = ssm_lib.decode_step(
                xbc[:, 0], dt[:, 0], a_log, skip, state0, pad == 0, shape)
            y = y[:, None]
        if filled:
            state.value = constrain(new_state, b, "tensor")
            tail.value = constrain(new_tail, b)
            feed_pad.value = jnp.zeros_like(pad)
        y = ssm_lib.gated_rms_norm(y.reshape(bsz, sq, shape.inner), z, gain,
                                   self.ln_eps).astype(self.dtype)
        y = constrain(y, b, "seq", "tensor")
        y = dense(width, name="out_proj")(y)
        return constrain(y, b, "seq")


class GatedDeltaMixer(nn.Module):
    """The gated delta-rule mixer (ops/gated_delta.py) in
    MultiHeadAttention's place: `in_proj_qkvz` to [q, k, v, z] and
    `in_proj_ba` to [b, a], a causal depthwise `conv_kernel` over [q, k, v]
    (no bias, SiLU), the recurrence with `A_log` and `dt_bias` per value
    head, a norm per head and then the gate (`norm_scale`, one [value_dim]
    gain all heads share) and `out_proj`. No bias, no skip term.

    Under decode=True the "cache" collection holds per row a running
    state and no axis of positions: `delta_state` [B, Hv, K, V] float32,
    `conv_tail` [B, conv - 1, C] (the last raw [q, k, v] inputs) and
    `feed_pad` [B], how many trailing tokens of THIS call are padding (0
    unless the caller sets it; read once and reset), as `Mamba2Mixer`
    keeps them. A call continues from the cached state: S > 1 feeds
    right-padded rows of true length S - feed_pad, past which the state
    stands and the tail kept ends at the true length; S = 1 is one step,
    and a padded one changes neither. A state cannot be rewound, shared or
    re-encoded by position."""

    gdn: gdn_lib.GatedDeltaShape
    dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False
    ln_eps: float = 1e-6

    def cache_state(self, max_len: Optional[int] = None) -> CacheState:
        """`delta_state` and `conv_tail` of one row, as `__call__` makes
        them (`feed_pad` is bookkeeping), whatever the cache's length;
        `chunk`, the positions of one triangular system of the chunked
        prefill."""
        shape = self.gdn
        return CacheState(
            "state", chunk=shape.chunk, fixed_bytes=(
                shape.value_heads * shape.key_dim * shape.value_dim * 4
                + (shape.conv - 1) * shape.conv_channels
                * jnp.dtype(self.dtype).itemsize),
            not_by_position=(
                "its 'gated_delta' layers cache one matrix per value head "
                "and a convolution tail with no axis of positions "
                "(models/transformer.py GatedDeltaMixer)"))

    @nn.compact
    def __call__(self, x: jax.Array, mask: Optional[jax.Array] = None,
                 train: bool = False) -> jax.Array:
        if mask is not None:
            raise NotImplementedError(
                "a delta-rule mixer takes no attention mask")
        b = batch_axes()
        shape = self.gdn
        bsz, sq, width = x.shape
        heads = shape.value_heads
        dense = functools.partial(nn.Dense, dtype=self.dtype,
                                  param_dtype=jnp.float32, use_bias=False)
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (shape.conv, shape.conv_channels), jnp.float32)
        a_log = self.param(
            "A_log", lambda key, s: jnp.log(jax.random.uniform(
                key, s, jnp.float32, 1e-3, 16.0)), (heads,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (heads,),
                             jnp.float32)
        gain = self.param("norm_scale", nn.initializers.ones,
                          (shape.value_dim,), jnp.float32)

        qkv, z = jnp.split(dense(shape.in_features, name="in_proj_qkvz")(x),
                           [shape.conv_channels], axis=-1)
        beta, a = jnp.split(
            dense(2 * heads, name="in_proj_ba")(x).astype(jnp.float32),
            2, axis=-1)
        beta = jax.nn.sigmoid(beta)
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            a + dt_bias.astype(jnp.float32))

        tail0 = jnp.zeros((bsz, shape.conv - 1, shape.conv_channels),
                          qkv.dtype)
        state0 = jnp.zeros((bsz, heads, shape.key_dim, shape.value_dim),
                           jnp.float32)
        filled = self.decode and self.has_variable("cache", "delta_state")
        if self.decode:
            state = self.variable("cache", "delta_state", lambda: state0)
            tail = self.variable("cache", "conv_tail", lambda: tail0)
            feed_pad = self.variable("cache", "feed_pad", jnp.zeros, (bsz,),
                                     jnp.int32)
        if filled:
            pad = feed_pad.value
            state0, tail0 = state.value, tail.value
        else:
            # the plain forward, and decode's init pass (the variables
            # were just created; flax convention)
            pad = jnp.zeros((bsz,), jnp.int32)
        lengths = sq - pad
        qkv, new_tail = ssm_lib.causal_conv(qkv, tail0, conv_kernel, None,
                                            lengths)
        if sq > 1 or not filled:
            # the norm and the gate a chunk at a time, inside the scan
            y, new_state = gdn_lib.prefill(
                qkv, beta, g, state0, lengths, shape,
                gate=(z, gain, self.ln_eps))
        else:
            o, new_state = gdn_lib.decode_step(
                qkv[:, 0], beta[:, 0], g[:, 0], state0, pad == 0, shape)
            y = gdn_lib.norm_then_gate(
                o, z.reshape(bsz, heads, shape.value_dim), gain,
                self.ln_eps)[:, None]
        if filled:
            state.value = constrain(new_state, b, "tensor")
            tail.value = constrain(new_tail, b)
            feed_pad.value = jnp.zeros_like(pad)
        y = constrain(y.astype(self.dtype).reshape(bsz, sq,
                                                   shape.value_width),
                      b, "seq", "tensor")
        y = dense(width, name="out_proj")(y)
        return constrain(y, b, "seq")


class Mlp(nn.Module):
    """fc1 -> act -> fc2; hidden dim carries the tensor-parallel shard.

    act='swiglu' (the LLaMA family): a parallel `gate` projection gates the
    up-projection with silu — gate and fc1 are both column-sharded under
    TP, so the elementwise product needs no extra collective."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    # 'gelu' (tanh approx, == GPT-2 gelu_new) | 'relu' | 'swiglu' | 'geglu'
    # | 'reglu' (relu(gate) * up)
    act: str = "gelu"
    use_bias: bool = True
    quant: Optional[str] = None  # see MultiHeadAttention.quant

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        b = batch_axes()
        if _check_quant(self.quant, train):
            dense = functools.partial(
                QuantDenseGeneral, dtype=self.dtype, use_bias=self.use_bias,
            )
        else:
            dense = functools.partial(
                nn.Dense, dtype=self.dtype, param_dtype=jnp.float32,
                use_bias=self.use_bias,
            )
        h = dense(self.mlp_dim, name="fc1")(x)
        if self.act == "gelu":
            h = nn.gelu(h)
        elif self.act == "relu":
            h = nn.relu(h)
        elif self.act == "swiglu":
            gate = dense(self.mlp_dim, name="gate")(x)
            h = nn.silu(gate) * h
        elif self.act == "geglu":
            # gelu-gated (the Gemma family): tanh-approximate gelu on the
            # gate, matching HF's gelu_pytorch_tanh
            gate = dense(self.mlp_dim, name="gate")(x)
            h = nn.gelu(gate, approximate=True) * h
        elif self.act == "reglu":
            gate = dense(self.mlp_dim, name="gate")(x)
            h = nn.relu(gate) * h
        else:
            raise ValueError(
                f"act must be 'gelu', 'relu', 'swiglu', 'geglu' or 'reglu', "
                f"got {self.act!r}"
            )
        h = constrain(h, b, "seq", "tensor")
        h = dense(x.shape[-1], name="fc2")(h)
        h = constrain(h, b, "seq")
        if self.dropout_rate > 0.0:
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return h


class UnitOffsetRMSNorm(nn.Module):
    """RMSNorm whose gain is stored as its distance from one:
    y = x / rms(x) * (1 + scale), `scale` starting at zero (the
    `norm_add_unit_offset` convention of the Gemma and EvaByte releases).
    Computed in float32 whatever the dtypes of `x` and `scale`."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.epsilon)
        return x * rms * (1.0 + scale.astype(jnp.float32))


def make_norm(norm: str, eps: float, unit_offset: bool = False):
    """The block's normalisation as a constructor taking `name=`:
    'layer' | 'rms', float32 inside; `unit_offset` (rms only) stores the
    gain as 1 + scale."""
    if norm not in ("layer", "rms"):
        raise ValueError(f"norm must be 'layer' or 'rms', got {norm!r}")
    if unit_offset:
        if norm != "rms":
            raise ValueError("norm_unit_offset requires norm='rms'")
        return functools.partial(UnitOffsetRMSNorm, epsilon=eps)
    return functools.partial(
        nn.RMSNorm if norm == "rms" else nn.LayerNorm,
        epsilon=eps, dtype=jnp.float32, param_dtype=jnp.float32,
    )


class TransformerBlock(nn.Module):
    """Pre-LN (default): x + MHA(LN(x)); x + MLP(LN(x)) — the stable-training
    variant ViT/GPT use. `norm_style='post'`: LN(x + MHA(x)); LN(x + MLP(x))
    — the original BERT arrangement (models/bert.py needs it for exact
    architecture parity)."""

    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    attn_impl: str = "auto"
    causal: bool = False
    decode: bool = False
    rope: bool = False
    rope_theta: float = 10_000.0
    rope_scaling: Optional[tuple] = None  # RoPE rescale (MultiHeadAttention)
    rope_dim: Optional[int] = None  # partial rotary (MultiHeadAttention)
    num_kv_heads: Optional[int] = None  # GQA (MultiHeadAttention)
    fused_qkv: bool = False  # one-GEMM qkv projection (MultiHeadAttention)
    quant: Optional[str] = None  # int8 serving twins (MultiHeadAttention)
    window: Optional[int] = None  # sliding window (MultiHeadAttention)
    rolling_cache: bool = False  # window-bounded decode cache (MHA)
    paged_blocks: Optional[int] = None  # paged KV pool (MultiHeadAttention)
    kv_block: int = 16  # paged pool block size in tokens (TFDE_KV_BLOCK)
    kv_quant: Optional[str] = None  # int8 KV cache (MHA, TFDE_KV_QUANT)
    attn_scale: Optional[float] = None    # Gemma-2 (MultiHeadAttention)
    attn_logit_cap: Optional[float] = None
    norm_style: str = "pre"
    # 'pre' | 'post' | 'parallel' (Phi: one LN, x + attn(ln(x)) + mlp(ln(x)))
    # | 'parallel2' (NeoX/Pythia: parallel residual, separate attn/MLP LNs)
    norm: str = "layer"  # 'layer' | 'rms' (LLaMA: scale-only, no bias)
    mlp_act: str = "gelu"  # Mlp.act
    use_bias: bool = True
    qkv_bias: bool = False  # Qwen2: biased q/k/v beside bias-free out/MLP
    qk_norm: bool = False  # Qwen3: per-head q/k RMSNorm (MultiHeadAttention)
    ln_eps: float = 1e-6  # checkpoint fidelity: GPT-2 1e-5, BERT 1e-12
    num_experts: int = 0  # > 0 swaps the dense MLP for a routed MoE MLP
    experts_per_token: int = 2
    moe_capacity_factor: Optional[float] = 1.25  # MoEMlp.capacity_factor
    moe_normalize_topk: bool = True        # MoEMlp.normalize_topk
    moe_shared_expert_dim: Optional[int] = None  # MoEMlp.shared_expert_dim
    router_z_loss_weight: float = 0.0  # ST-MoE stabilizer (models/moe.py)
    # MoEMlp.held_experts / shared_expert_gated; moe_capacity_factor=None
    # is the drop-free routing
    moe_held_experts: Optional[tuple] = None
    moe_shared_expert_gated: bool = True
    # True (SmallThinker): the router reads the NORMALISED INPUT OF THE
    # ATTENTION sublayer, the experts the normalised output of the
    # attention residual; False: both read the latter
    moe_router_pre_attention: bool = False
    attention: str = "full"  # 'full' | 'eva' (MultiHeadAttention)
    eva_window: int = 2048
    eva_chunk: int = 16
    norm_unit_offset: bool = False  # norm='rms' only (make_norm)
    # what mixes positions in this block: 'attention' (MultiHeadAttention)
    # | 'mamba' (Mamba2Mixer over `ssm`, an ops/ssm.SSMShape) | 'latent'
    # (LatentAttention over `mla`, an ops/mla.MLAShape)
    # | 'gated_delta' (GatedDeltaMixer over `gdn`, an
    # ops/gated_delta.GatedDeltaShape)
    mixer: str = "attention"
    ssm: Optional[ssm_lib.SSMShape] = None
    mla: Optional[mla_lib.MLAShape] = None
    gdn: Optional[gdn_lib.GatedDeltaShape] = None
    attn_output_gate: bool = False  # MultiHeadAttention.output_gate
    # MoEMlp.score / selection_bias / routed_scale
    moe_score: str = "softmax"
    moe_selection_bias: bool = False
    moe_routed_scale: Optional[float] = None
    # both sublayers' outputs are scaled by this before the residual add
    # (Granite's residual_multiplier); None adds them as they are
    residual_multiplier: Optional[float] = None

    @nn.nowrap
    def mixer_module(self) -> nn.Module:
        """The module that mixes positions in this block, at this block's
        fields: `__call__` runs it, `cache_state` asks it what it keeps.
        Not wrapped, like `Encoder.block` and `GPT.stack`: the module made
        is then the child of whoever is being applied, and of no one
        where a model that is not bound is asked for its descriptions."""
        if self.mixer not in ("attention", "mamba", "latent", "gated_delta"):
            raise ValueError(
                f"mixer must be 'attention', 'mamba', 'latent' or "
                f"'gated_delta', got {self.mixer!r}")
        if self.mixer == "latent":
            if self.mla is None or not self.causal or not self.rope:
                raise ValueError(
                    "mixer='latent' needs its widths (`mla`), a causal "
                    "block and rotary positions")
            if (self.paged_blocks is not None or self.kv_quant is not None
                    or self.window is not None or self.quant is not None):
                raise NotImplementedError(
                    "latent attention caches one [c, k_r] cell per position "
                    "in a leaf of its own: the block pool, int8 cells, a "
                    "sliding window and int8 weights are not built for it")
            return LatentAttention(
                num_heads=self.num_heads, shape=self.mla, dtype=self.dtype,
                attn_impl=self.attn_impl, decode=self.decode,
                rope_theta=self.rope_theta, rope_scaling=self.rope_scaling,
                ln_eps=self.ln_eps, name="attn")
        if self.mixer == "mamba":
            if self.ssm is None or self.norm_style != "pre":
                raise ValueError(
                    "mixer='mamba' needs its widths (`ssm`) and the pre-norm "
                    "block")
            return Mamba2Mixer(ssm=self.ssm, dtype=self.dtype,
                               decode=self.decode, ln_eps=self.ln_eps,
                               name="mamba")
        if self.mixer == "gated_delta":
            if self.gdn is None or self.norm_style != "pre":
                raise ValueError(
                    "mixer='gated_delta' needs its widths (`gdn`) and the "
                    "pre-norm block")
            return GatedDeltaMixer(gdn=self.gdn, dtype=self.dtype,
                                   decode=self.decode, ln_eps=self.ln_eps,
                                   name="delta")
        return MultiHeadAttention(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            dtype=self.dtype,
            dropout_rate=self.dropout_rate,
            attn_impl=self.attn_impl,
            causal=self.causal,
            decode=self.decode,
            rope=self.rope,
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            rope_dim=self.rope_dim,
            num_kv_heads=self.num_kv_heads,
            fused_qkv=self.fused_qkv,
            quant=self.quant,
            window=self.window,
            rolling_cache=self.rolling_cache,
            paged_blocks=self.paged_blocks,
            kv_block=self.kv_block,
            kv_quant=self.kv_quant,
            attn_scale=self.attn_scale,
            attn_logit_cap=self.attn_logit_cap,
            use_bias=self.use_bias,
            qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            ln_eps=self.ln_eps,
            norm_unit_offset=self.norm_unit_offset,
            output_gate=self.attn_output_gate,
            attention=self.attention,
            eva_window=self.eva_window,
            eva_chunk=self.eva_chunk,
            name="attn",
        )

    @nn.nowrap
    def cache_state(self, max_len: Optional[int]) -> CacheState:
        """What this block keeps of one row in a decode cache of `max_len`
        positions: its mixer's own description."""
        return self.mixer_module().cache_state(max_len)

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        mask: Optional[jax.Array] = None,
        train: bool = False,
    ) -> jax.Array:
        ln = make_norm(self.norm, self.ln_eps, self.norm_unit_offset)
        attn = self.mixer_module()
        if self.num_experts > 0:
            if (self.mlp_act, self.use_bias) not in (
                ("gelu", True), ("swiglu", False), ("reglu", False),
            ):
                raise NotImplementedError(
                    "MoE expert MLPs are gelu+bias (Switch/GShard) or "
                    "bias-free swiglu (Mixtral) or reglu (SmallThinker); "
                    "other mlp_act/use_bias combinations would silently "
                    "build a different architecture than requested"
                )
            if self.quant is not None:
                raise NotImplementedError(
                    "quant='int8' does not cover MoE expert MLPs yet — "
                    "quantize a dense model, or set num_experts=0"
                )
            from tfde_tpu.models.moe import MoEMlp

            mlp = MoEMlp(
                num_experts=self.num_experts,
                mlp_dim=self.mlp_dim,
                experts_per_token=self.experts_per_token,
                capacity_factor=self.moe_capacity_factor,
                normalize_topk=self.moe_normalize_topk,
                shared_expert_dim=self.moe_shared_expert_dim,
                shared_expert_gated=self.moe_shared_expert_gated,
                held_experts=self.moe_held_experts,
                score=self.moe_score,
                selection_bias=self.moe_selection_bias,
                routed_scale=self.moe_routed_scale,
                decode=self.decode,
                act=self.mlp_act,
                use_bias=self.use_bias,
                router_z_loss_weight=self.router_z_loss_weight,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                name="moe",
            )
        else:
            mlp = Mlp(
                mlp_dim=self.mlp_dim,
                dtype=self.dtype,
                dropout_rate=self.dropout_rate,
                act=self.mlp_act,
                use_bias=self.use_bias,
                quant=self.quant,
                name="mlp",
            )
        if self.residual_multiplier is not None and self.norm_style != "pre":
            raise NotImplementedError(
                "residual_multiplier is built for the pre-norm block")
        if self.moe_router_pre_attention and (
                self.norm_style != "pre" or self.num_experts <= 0):
            raise NotImplementedError(
                "moe_router_pre_attention is a routed pre-norm block's")
        if self.norm_style == "pre":
            r = self.residual_multiplier
            scaled = (lambda t: t) if r is None else (
                lambda t: t * jnp.asarray(r, t.dtype))
            y = ln(name="ln_attn")(x).astype(self.dtype)
            x = x + scaled(attn(y, mask=mask, train=train))
            routed_by = ({"router_input": y}
                         if self.moe_router_pre_attention else {})
            y = ln(name="ln_mlp")(x).astype(self.dtype)
            return x + scaled(mlp(y, train=train, **routed_by))
        if self.norm_style == "post":
            x = ln(name="ln_attn")(x + attn(x, mask=mask, train=train))
            x = x.astype(self.dtype)
            x = ln(name="ln_mlp")(x + mlp(x, train=train))
            return x.astype(self.dtype)
        if self.norm_style == "parallel":
            # the Phi arrangement: ONE LayerNorm feeds attention and MLP
            # side by side, residual added once — attn and MLP GEMMs have
            # no serial dependency, so XLA overlaps them freely
            y = ln(name="ln_attn")(x).astype(self.dtype)
            return x + attn(y, mask=mask, train=train) + mlp(y, train=train)
        if self.norm_style == "parallel2":
            # the GPT-NeoX/Pythia arrangement: parallel residual like Phi,
            # but attention and MLP each get their OWN LayerNorm
            ya = ln(name="ln_attn")(x).astype(self.dtype)
            ym = ln(name="ln_mlp")(x).astype(self.dtype)
            return (x + attn(ya, mask=mask, train=train)
                    + mlp(ym, train=train))
        if self.norm_style == "sandwich":
            # the Gemma-2 arrangement: each sublayer normed BOTH sides —
            # x + post_ln(sub(pre_ln(x))) — taming residual-stream growth
            y = ln(name="ln_attn")(x).astype(self.dtype)
            a = attn(y, mask=mask, train=train)
            x = x + ln(name="ln_attn_post")(a).astype(self.dtype)
            y = ln(name="ln_mlp")(x).astype(self.dtype)
            h = mlp(y, train=train)
            return x + ln(name="ln_mlp_post")(h).astype(self.dtype)
        raise ValueError(
            f"norm_style must be 'pre', 'post', 'parallel', 'parallel2' "
            f"or 'sandwich', got {self.norm_style!r}"
        )


def remat_policy(remat):
    """Checkpoint-policy selector shared by every model family:
    False — no remat; True / 'full' — nothing_saveable (recompute the whole
    block in backward: max HBM savings, ~1.33x FLOPs); 'dots' — save MXU
    matmul outputs and recompute only the elementwise/fusible ops (the
    usual best HBM/FLOPs tradeoff on TPU: backward recompute is nearly
    free because it never re-runs the matmuls)."""
    if not remat:
        return None
    if remat is True or remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    if remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(
        f"remat must be False, True, 'full', or 'dots'; got {remat!r}"
    )


class Encoder(nn.Module):
    """Stack of TransformerBlocks with optional per-block rematerialization
    (`remat`: False | True/'full' | 'dots', see remat_policy)."""

    depth: int
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    attn_impl: str = "auto"
    causal: bool = False
    decode: bool = False
    rope: bool = False
    rope_theta: float = 10_000.0
    rope_scaling: Optional[tuple] = None
    rope_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    fused_qkv: bool = False
    quant: Optional[str] = None
    # one sliding window per block, None (full causal) or an int, as long
    # as the depth; None: no block is windowed (models/gpt.py writes
    # 'every block' and 'blocks 0, 2, ...' as such tuples)
    windows: Optional[tuple] = None
    # which blocks rotate q/k where `rope` is on, one truth value per
    # block; None: every block. A block that does not rotate has no
    # positions at all
    rope_layers: Optional[tuple] = None
    rolling_cache: bool = False
    paged_blocks: Optional[int] = None
    kv_block: int = 16
    kv_quant: Optional[str] = None
    attn_scale: Optional[float] = None
    attn_logit_cap: Optional[float] = None
    norm_style: str = "pre"
    norm: str = "layer"
    mlp_act: str = "gelu"
    use_bias: bool = True
    qkv_bias: bool = False
    qk_norm: bool = False
    ln_eps: float = 1e-6
    remat: Any = False
    num_experts: int = 0   # > 0: MoE MLP in every `moe_every`-th block
    experts_per_token: int = 2
    moe_capacity_factor: Optional[float] = 1.25
    moe_normalize_topk: bool = True
    moe_shared_expert_dim: Optional[int] = None
    router_z_loss_weight: float = 0.0
    moe_every: int = 2     # GShard convention: alternate dense / MoE
    # the kind of MLP per block where `num_experts` > 0, 'dense' |
    # 'experts', as long as the depth; None: every `moe_every`-th block
    # routes. A routed block's experts are `moe_mlp_dim` wide (None: as
    # wide as the dense MLP, `mlp_dim`)
    mlps: Optional[tuple] = None
    moe_mlp_dim: Optional[int] = None
    moe_score: str = "softmax"             # MoEMlp.score
    moe_selection_bias: bool = False       # MoEMlp.selection_bias
    moe_routed_scale: Optional[float] = None  # MoEMlp.routed_scale
    moe_held_experts: Optional[tuple] = None
    moe_shared_expert_gated: bool = True
    moe_router_pre_attention: bool = False  # TransformerBlock
    attention: str = "full"  # 'full' | 'eva' (MultiHeadAttention)
    eva_window: int = 2048
    eva_chunk: int = 16
    norm_unit_offset: bool = False  # norm='rms' only (make_norm)
    # one mixer kind per block, 'attention' | 'mamba' | 'latent' |
    # 'gated_delta' (TransformerBlock.mixer), as long as the depth; None
    # builds every block with attention
    mixers: Optional[tuple] = None
    ssm: Optional[ssm_lib.SSMShape] = None  # the 'mamba' blocks' widths
    mla: Optional[mla_lib.MLAShape] = None  # the 'latent' blocks' widths
    # the 'gated_delta' blocks' widths
    gdn: Optional[gdn_lib.GatedDeltaShape] = None
    attn_output_gate: bool = False  # MultiHeadAttention.output_gate
    residual_multiplier: Optional[float] = None  # TransformerBlock

    @nn.nowrap
    def block(self, i: int) -> TransformerBlock:
        """Block `i` of the stack at the fields this stack hands it:
        `__call__` runs it, `cache_states` asks it what it keeps."""
        for name in ("mixers", "windows", "rope_layers", "mlps"):
            per_block = getattr(self, name)
            if per_block is not None and len(per_block) != self.depth:
                raise ValueError(
                    f"{name} names {len(per_block)} blocks, depth is "
                    f"{self.depth}")
        if self.mlps is not None and self.mlps[i] not in (
                "dense", "experts"):
            raise ValueError(
                f"mlps[{i}] must be 'dense' or 'experts', got "
                f"{self.mlps[i]!r}")
        is_moe = self.num_experts > 0 and (
            i % self.moe_every == self.moe_every - 1
            if self.mlps is None else self.mlps[i] == "experts")
        return TransformerBlock(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            mlp_dim=(self.moe_mlp_dim if is_moe and self.moe_mlp_dim
                     else self.mlp_dim),
            dtype=self.dtype,
            dropout_rate=self.dropout_rate,
            attn_impl=self.attn_impl,
            causal=self.causal,
            decode=self.decode,
            rope=self.rope and (self.rope_layers is None
                                or bool(self.rope_layers[i])),
            rope_theta=self.rope_theta,
            rope_scaling=self.rope_scaling,
            rope_dim=self.rope_dim,
            num_kv_heads=self.num_kv_heads,
            fused_qkv=self.fused_qkv,
            quant=self.quant,
            window=self.windows[i] if self.windows is not None else None,
            rolling_cache=self.rolling_cache,
            paged_blocks=self.paged_blocks,
            kv_block=self.kv_block,
            kv_quant=self.kv_quant,
            attn_scale=self.attn_scale,
            attn_logit_cap=self.attn_logit_cap,
            norm_style=self.norm_style,
            norm=self.norm,
            mlp_act=self.mlp_act,
            use_bias=self.use_bias,
            qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            ln_eps=self.ln_eps,
            num_experts=self.num_experts if is_moe else 0,
            experts_per_token=self.experts_per_token,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_normalize_topk=self.moe_normalize_topk,
            moe_shared_expert_dim=self.moe_shared_expert_dim,
            router_z_loss_weight=self.router_z_loss_weight,
            moe_held_experts=self.moe_held_experts,
            moe_shared_expert_gated=self.moe_shared_expert_gated,
            moe_router_pre_attention=(self.moe_router_pre_attention
                                      and is_moe),
            attention=self.attention,
            eva_window=self.eva_window,
            eva_chunk=self.eva_chunk,
            norm_unit_offset=self.norm_unit_offset,
            mixer=(self.mixers[i] if self.mixers is not None
                   else "attention"),
            ssm=self.ssm,
            mla=self.mla,
            gdn=self.gdn,
            attn_output_gate=self.attn_output_gate,
            moe_score=self.moe_score,
            moe_selection_bias=self.moe_selection_bias,
            moe_routed_scale=self.moe_routed_scale,
            residual_multiplier=self.residual_multiplier,
            name=f"block_{i}",
        )

    @nn.nowrap
    def cache_states(self, max_len: Optional[int]) -> tuple:
        """What a row keeps in a decode cache of `max_len` positions: one
        description a block, in the tree's order."""
        return tuple(self.block(i).cache_state(max_len)
                     for i in range(self.depth))

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        mask: Optional[jax.Array] = None,
        train: bool = False,
    ) -> jax.Array:
        def body(mdl: TransformerBlock, h: jax.Array) -> jax.Array:
            # mask/train close over: constants to jax.checkpoint (no grads
            # flow to them — mask is boolean, train is a Python bool).
            return mdl(h, mask, train)

        policy = remat_policy(self.remat)
        if policy is not None:
            if self.decode:
                raise ValueError(
                    "decode=True does not compose with remat: the KV-cache "
                    "mutation inside jax.checkpoint is unsupported (and "
                    "pointless — decode is inference, there is no backward)"
                )
            body = nn.remat(body, policy=policy)
        for i in range(self.depth):
            x = body(self.block(i), x)
        if self.norm_style == "post":
            return x  # post-LN blocks already end normalized
        return make_norm(self.norm, self.ln_eps, self.norm_unit_offset)(
            name="ln_final")(x)


class LatentAttention(nn.Module):
    """Latent (multi-head latent, MLA) self-attention in
    MultiHeadAttention's place (`TransformerBlock.mixer='latent'`; the
    arithmetic is ops/mla.py's): `query` [d, H, nope + rope] (the query is
    not compressed), `kv_down` [d, latent + rope] to the latent `c` and the
    ONE rotary key `k_r` all heads share, `kv_norm` (RMSNorm of the latent
    before it is up-projected; no norm per head: that would not be linear
    in `c`), `kv_up` [latent, H, nope + value] and `out` [H, value, d]; no
    bias. Causal. Rotary positions turn `k_r` and the queries' `rope`
    features; a yarn scaling's temperature is NOT folded into the tables
    (it would scale the rotated part of a score alone) but squared onto
    the whole score (ops/rotary.attention_temperature).

    Under decode=True the "cache" collection holds the cell `[c, k_r]` of
    every position as two leaves, `cached_latent` [B, max_len, latent] and
    `cached_rope_key` [B, max_len, rope] (one leaf of latent + rope values
    is no multiple of the 128 lanes: the chip then keeps it with the
    positions in the lanes and every decode scan copies it to rows of
    cells and back, PERF.md section 6, PR 40), with no head axis and no
    value leaf, and `cache_index`. Which path a call takes follows from
    what it is:
    - S > 1 from position 0 under one shared index (a wave into a fresh
      row cache, `generate`'s prompt): every key is the call's own, so it
      attends PER HEAD over its own tokens (K and V up-projected,
      `ops/mla.prefill_attention`: the flash forward on the chip once the
      scores pass 1 GiB, `HEAD_CHUNK` heads at a time) and writes its
      cells;
    - a step (S = 1), and any call behind cached cells or under per-row
      indices: ABSORBED over the cache (`ops/mla.absorbed_attention`), the
      up-projections on the query's side, a block of queries at a time
      where there are many. No K or V per head is ever formed there.
    The cache is a cell per position: an index rewind hides a padded tail
    as it does in a K/V slab. The block pool and int8 cells are not built
    for this leaf (`TransformerBlock` refuses them)."""

    num_heads: int
    shape: mla_lib.MLAShape
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"
    decode: bool = False
    rope_theta: float = 10_000.0
    rope_scaling: Optional[tuple] = None
    ln_eps: float = 1e-6

    def cache_state(self, max_len: Optional[int]) -> CacheState:
        """`cached_latent` and `cached_rope_key` of one row: a cell per
        position like a K/V slab's, `latent + rope` values wide, so
        nothing that works by position is refused on its account."""
        return CacheState("latent", max_len or 0, self.shape.cell
                          * jnp.dtype(self.dtype).itemsize)

    @nn.compact
    def __call__(self, x: jax.Array, mask: Optional[jax.Array] = None,
                 train: bool = False) -> jax.Array:
        if mask is not None:
            raise NotImplementedError(
                "latent attention is causal self-attention and takes no "
                "explicit mask")
        b = batch_axes()
        shape, heads = self.shape, self.num_heads
        bsz, sq, width = x.shape
        init = nn.initializers.lecun_normal
        w_q = self.param("query", init(in_axis=0, out_axis=(1, 2)),
                         (width, heads, shape.query), jnp.float32)
        w_down = self.param("kv_down", init(), (width, shape.cell),
                            jnp.float32)
        w_up = self.param("kv_up", init(in_axis=0, out_axis=(1, 2)),
                          (shape.latent, heads, shape.nope + shape.value),
                          jnp.float32)
        w_out = self.param("out", init(in_axis=(0, 1), out_axis=2),
                           (heads, shape.value, width), jnp.float32)
        w_q, w_down, w_up, w_out = (w.astype(self.dtype)
                                    for w in (w_q, w_down, w_up, w_out))
        x = x.astype(self.dtype)
        scale = shape.query ** -0.5 * attention_temperature(
            self.rope_scaling) ** 2

        filled = self.decode and self.has_variable("cache", "cached_latent")
        if self.decode:
            cached_c = self.variable("cache", "cached_latent", jnp.zeros,
                                     (bsz, sq, shape.latent), self.dtype)
            cached_r = self.variable("cache", "cached_rope_key", jnp.zeros,
                                     (bsz, sq, shape.rope), self.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((), jnp.int32))
        idx = cache_index.value if filled else jnp.zeros((), jnp.int32)
        pos = idx[..., None] + jnp.arange(sq, dtype=jnp.int32)  # [S] | [B,S]
        rotate = functools.partial(
            apply_rotary, positions=pos, theta=self.rope_theta,
            scaling=self.rope_scaling, fold_temperature=False)

        down = jnp.einsum("bsd,dc->bsc", x, w_down)
        c = make_norm("rms", self.ln_eps)(name="kv_norm")(
            down[..., :shape.latent]).astype(self.dtype)
        k_r = rotate(down[..., None, shape.latent:])[:, :, 0]

        def queries(x, w_q, rotate=rotate):
            q = jnp.einsum("bsd,dhq->bshq", x, w_q)
            return q[..., :shape.nope], rotate(q[..., shape.nope:])

        def through_out(o, w):
            return jnp.einsum("bshv,hvd->bsd", o, w,
                              preferred_element_type=jnp.float32)

        def per_head():
            chunk = mla_lib.head_chunks(bsz, heads, sq)

            def some(i):
                heads_of = lambda w, axis=1: jax.lax.dynamic_slice_in_dim(
                    w, i * chunk, chunk, axis)
                q_nope, q_rope = queries(x, heads_of(w_q))
                kv = jnp.einsum("bsc,chk->bshk", c, heads_of(w_up))
                return through_out(mla_lib.prefill_attention(
                    q_nope, q_rope, kv[..., :shape.nope], k_r,
                    kv[..., shape.nope:], scale=scale, impl=self.attn_impl),
                    heads_of(w_out, 0))

            with jax.named_scope("mla_prefill"):
                if chunk == heads:
                    return some(0).astype(self.dtype)
                # a chunk's heads go through their rows of `out` at once:
                # no [B, S, H, value] of all heads is ever laid out
                y, _ = jax.lax.scan(
                    lambda y, i: (y + some(i), None),
                    jnp.zeros((bsz, sq, width), jnp.float32),
                    jnp.arange(heads // chunk))
                return y.astype(self.dtype)

        def absorbed(latents, rope_keys):
            cols = jnp.arange(latents.shape[1], dtype=jnp.int32)
            block = mla_lib.query_blocks(bsz, heads, sq, latents.shape[1])

            def some(i):
                rows = lambda t: jax.lax.dynamic_slice_in_dim(
                    t, i * block, block, 1)
                at = rows(jnp.broadcast_to(pos, (bsz, sq)))
                q_nope, q_rope = queries(
                    rows(x), w_q, functools.partial(rotate, positions=at))
                q_abs = jnp.einsum("bshn,chn->bshc", q_nope,
                                   w_up[..., :shape.nope])
                o_lat = mla_lib.absorbed_attention(
                    q_abs, q_rope, latents, rope_keys,
                    cols <= at[..., None], scale=scale)
                return through_out(
                    jnp.einsum("bshc,chv->bshv", o_lat,
                               w_up[..., shape.nope:]),
                    w_out).astype(self.dtype)

            with jax.named_scope("mla_decode"):
                if block == sq:
                    return some(0)
                out = jax.lax.map(some, jnp.arange(sq // block))
                return jnp.moveaxis(out, 0, 1).reshape(bsz, sq, width)

        if not filled:
            # the plain forward, and decode's init pass (the variables were
            # just created from this call's [B, max_len] input)
            y = per_head()
        else:
            if sq > cached_c.value.shape[1]:
                raise ValueError(
                    f"input length {sq} exceeds the cache budget "
                    f"{cached_c.value.shape[1]}; re-init the cache with a "
                    f"larger max_len")
            with jax.named_scope("mla_cache_write"):
                if idx.ndim == 0:
                    put = lambda cells, new: jax.lax.dynamic_update_slice(
                        cells, new.astype(cells.dtype), (0, idx, 0))
                else:
                    put = lambda cells, new: eva_lib._rows_update(
                        cells, new, idx)
                latents = put(cached_c.value, c)
                rope_keys = put(cached_r.value, k_r)
            cached_c.value = constrain(latents, b, None, None)
            cached_r.value = constrain(rope_keys, b, None, None)
            cache_index.value = idx + sq
            if sq > 1 and idx.ndim == 0:
                y = jax.lax.cond(idx == 0, per_head,
                                 lambda: absorbed(latents, rope_keys))
            else:
                y = absorbed(latents, rope_keys)
        return constrain(y, b, "seq")
