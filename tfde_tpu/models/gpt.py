"""GPT-style causal language model — decoder-only transformer.

Rounds out the model families (reference: CNNs only, SURVEY.md §2a; driver
configs add ViT + BERT): the causal decoder exercises the attention paths
the other configs don't — causal masking in the reference kernel, causal
block-skipping in the Pallas flash kernel (ops/flash_attention.py), and
causal ring attention for long-context (ops/ring_attention.py) — all through
the same Encoder (models/transformer.py, pre-LN, the GPT-2 arrangement).

Weight tying (GPT-2 convention): LM head = embedding transpose via
`nn.Embed.attend`, same as models/bert.py's MLM decoder.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tfde_tpu.models.cache_state import CacheLayout
from tfde_tpu.models.moe import FEED_PAD_UNSHARED
from tfde_tpu.models.transformer import Encoder
from tfde_tpu.parallel.axes import batch_axes, constrain


class GPT(nn.Module):
    """Decoder-only LM over [B, S] int token ids -> [B, S, vocab] logits."""

    vocab_size: int = 50257
    hidden_size: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_position: int = 1024
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"
    remat: Any = False  # False | True/'full' | 'dots' (transformer.remat_policy)
    fused_qkv: bool = False  # one-GEMM qkv projection (transformer.py)
    # > 0 swaps every `moe_every`-th block's MLP for a routed expert MLP
    # (models/moe.py) — train under ExpertParallelStrategy to shard experts
    num_experts: int = 0
    moe_every: int = 2
    experts_per_token: int = 2
    # None: no capacity, no token dropped (serving; models/moe.py MoEMlp)
    moe_capacity_factor: Optional[float] = 1.25
    moe_normalize_topk: bool = True        # models/moe.py MoEMlp
    moe_shared_expert_dim: Optional[int] = None  # Qwen2-MoE shared expert
    # False (Granite): the shared expert is added as it is, no sigmoid gate
    moe_shared_expert_gated: bool = True
    # True (SmallThinker): the router reads the attention sublayer's
    # normalised input, not the expert layer's (transformer.TransformerBlock)
    moe_router_pre_attention: bool = False
    # the kind of MLP per layer where `num_experts` > 0, one entry a layer,
    # 'dense' | 'experts' (a config's `first_k_dense_replace`: dense, then
    # experts everywhere); None: every `moe_every`-th layer routes. A routed
    # layer's experts are `moe_mlp_dim` wide (None: `mlp_dim`, the dense
    # layers' width)
    mlps: Optional[tuple] = None
    moe_mlp_dim: Optional[int] = None
    # how the router scores ('softmax' | 'sigmoid'), a learned bias added
    # to the scores for the CHOICE of experts alone, and a factor on the
    # combined weights (models/moe.py MoEMlp score / selection_bias /
    # routed_scale)
    moe_score: str = "softmax"
    moe_selection_bias: bool = False
    moe_routed_scale: Optional[float] = None
    # (first, end) of the contiguous range of experts this program holds
    # (a chip's share under expert parallelism): the router stays
    # `num_experts` wide, pairs routed elsewhere add nothing (MoEMlp)
    moe_held_experts: Optional[tuple] = None
    router_z_loss_weight: float = 0.0  # ST-MoE stabilizer (models/moe.py)
    # autoregressive serving mode (inference/decode.py): KV caches in the
    # "cache" collection; positions continue from the cached prefix
    decode: bool = False
    # window-bounded rolling decode cache (transformer.MultiHeadAttention
    # rolling_cache) — set by _decode_clone(rolling=True) on paths that
    # never rewind the cache
    rolling_cache: bool = False
    # paged KV pool (transformer.MultiHeadAttention paged_blocks/kv_block)
    # — set by inference/paged._paged_clone under TFDE_PAGED_KV; None keeps
    # the dense per-row slabs
    paged_blocks: Optional[int] = None
    kv_block: int = 16
    # None (fp) | 'int8': quantized KV cache (transformer.MultiHeadAttention
    # kv_quant, TFDE_KV_QUANT) — int8 payload + per-(position, kv-head)
    # fp32 scale sidecars in every cache layout (dense slab / paged pool),
    # dequantized inside the attention program. Orthogonal to `quant`
    # (weights): either, both, or neither. Serving-only like the cache
    # itself; set by _decode_clone(kv_quant=...).
    kv_quant: Optional[str] = None
    ln_eps: float = 1e-6  # GPT-2 checkpoints use 1e-5 (models/convert.py)
    # 'learned' = GPT-2 absolute wpe table; 'rope' = rotary q/k rotation
    # (ops/rotary.py) — no position table, relative-position attention,
    # better length extrapolation; 'none' = no positions at all (Granite
    # 4.0-H: the state-space layers carry the order)
    position: str = "learned"
    rope_theta: float = 10_000.0
    # RoPE frequency rescaling (ops/rotary.scale_frequencies tuple):
    # ('linear', factor) | ('llama3', factor, low, high, orig_max) — the
    # Llama-3.1+ long-context checkpoints carry this
    rope_scaling: Optional[Any] = None
    # partial rotary (the Phi family): only the first rope_dim features of
    # each head rotate; None = full head_dim
    rope_dim: Optional[int] = None
    # grouped-query attention: KV heads per layer (None = num_heads); the
    # KV cache shrinks by num_heads/num_kv_heads — the serving memory knob
    num_kv_heads: Optional[int] = None
    norm: str = "layer"      # 'layer' | 'rms' (LLaMA)
    mlp_act: str = "gelu"    # 'gelu' | 'relu' (OPT) | 'swiglu' (LLaMA) |
    #                          'geglu' (Gemma) | 'reglu' (SmallThinker)
    use_bias: bool = True    # False: LLaMA bias-free projections
    # Qwen2: biased q/k/v projections beside bias-free out/MLP
    qkv_bias: bool = False
    # Qwen3: per-head RMSNorm on q and k before rotary (transformer.py)
    qk_norm: bool = False
    # 'pre' (GPT-2/LLaMA) | 'parallel' (Phi: one LN per block, attention
    # and MLP side by side on it) | 'parallel2' (GPT-NeoX/Pythia: parallel
    # residual with separate attention/MLP LayerNorms)
    norm_style: str = "pre"
    # Phi: the untied lm_head carries a bias
    head_bias: bool = False
    # token embeddings are multiplied by this after lookup (Gemma:
    # sqrt(hidden_size)); None = no scaling (every other family)
    embed_scale: Optional[float] = None
    # per-head width; None = hidden_size // num_heads. Gemma-7b-style
    # checkpoints decouple it (attention width heads*head_dim != hidden;
    # the out projection maps back to hidden either way)
    head_dim: Optional[int] = None
    # True (GPT-2): LM head = wte^T via Embed.attend; False (LLaMA):
    # separate bias-free lm_head Dense
    tie_embeddings: bool = True
    # None (fp) | 'int8': W8A8 serving twin (ops/quant.py) — block
    # projections, the embedding/tied head, and the untied lm_head all go
    # int8; wpe and norms stay fp32. Build params with quantize_model.
    quant: Optional[str] = None
    # sliding-window attention (the Mistral family): each position attends
    # the last `sliding_window` positions. The flash forward AND backward
    # skip out-of-band tiles (compute and DMA drop to O(S * window) for
    # the full fwd+bwd step — the backward scans only the statically
    # in-band tile pairs). The decode cache mask carries the band.
    # None = full causal.
    sliding_window: Optional[int] = None
    # 'all' | 'alternate' (Gemma-2: even blocks windowed, odd blocks full)
    sliding_window_pattern: str = "all"
    # the per-layer form, of which the two fields above are two ways of
    # writing (`layer_windows`): one window per layer, None (full causal)
    # or an int, as long as `depth` (a config's `sliding_window_layout`
    # times its `sliding_window_size`). Under decode a layer with a window
    # may keep a ring of `window` cells beside the other layers' slabs
    # (transformer.MultiHeadAttention.rolling_cache)
    windows: Optional[tuple] = None
    # position='rope' only: which layers rotate q and k, one truth value
    # per layer (a config's `rope_layout`); a layer that does not has no
    # positions. None: every layer rotates
    rope_layers: Optional[tuple] = None
    # Gemma-2 attention deltas (transformer.MultiHeadAttention)
    attn_scale: Optional[float] = None
    attn_logit_cap: Optional[float] = None
    # Gemma-2 final logit softcapping: logits = cap * tanh(logits / cap)
    final_logit_cap: Optional[float] = None
    # 'full' | 'eva' (EvaByte: chunked linear attention, an exact softmax
    # over the query's own aligned `eva_window` beside one learned summary
    # per `eva_chunk` earlier positions; ops/eva_attention.py). Needs
    # position='rope'; under decode=True it keeps windows and summaries,
    # not position-indexed K/V (transformer.MultiHeadAttention)
    attention: str = "full"
    eva_window: int = 2048
    eva_chunk: int = 16
    # norm='rms' with the gain stored as 1 + scale (EvaByte's
    # norm_add_unit_offset; transformer.UnitOffsetRMSNorm)
    norm_unit_offset: bool = False
    # residual stream and its adds in float32, the sublayers in `dtype`
    # (EvaByte's fp32_skip_add)
    fp32_residual: bool = False
    # one mixer kind per layer, 'attention' | 'mamba' | 'latent' |
    # 'gated_delta' (a config's `layer_types`), as long as `depth`; None:
    # every layer is
    # attention. 'mamba' layers are ops/ssm.py's Mamba-2 mixer at the widths
    # of `ssm` (an ops/ssm.SSMShape) and cache a running state, not
    # positions; 'latent' layers are latent attention at the widths of `mla`
    # (an ops/mla.MLAShape; position='rope') and cache one [latent, rotary
    # key] cell per position with no head axis (transformer.LatentAttention);
    # 'gated_delta' layers are ops/gated_delta.py's delta-rule mixer at the
    # widths of `gdn` (an ops/gated_delta.GatedDeltaShape) and cache one
    # matrix per value head (transformer.GatedDeltaMixer)
    mixers: Optional[tuple] = None
    ssm: Optional[Any] = None
    mla: Optional[Any] = None
    gdn: Optional[Any] = None
    # the attention layers' query is twice as wide, per head [q | gate],
    # and sigmoid(gate) multiplies their output before `out`; with
    # `norm_unit_offset` their q/k norms store 1 + gain as well
    # (transformer.MultiHeadAttention.output_gate)
    attn_output_gate: bool = False
    # Granite: each sublayer's output times this before the residual add,
    # and the logits divided by `logits_scaling`
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None

    def layer_windows(self) -> Optional[tuple]:
        """One sliding window per layer (None: full causal), or None where
        no layer has one: `windows` as given, else `sliding_window` under
        `sliding_window_pattern`: 'all' every layer, 'alternate' layers 0,
        2, ... (the Gemma-2 local/global interleave)."""
        if self.windows is not None:
            if self.sliding_window is not None:
                raise ValueError(
                    "give the windows per layer (`windows`) or one for a "
                    "pattern (`sliding_window`), not both")
            windows = tuple(None if not w else int(w) for w in self.windows)
            return windows if any(windows) else None
        if self.sliding_window_pattern not in ("all", "alternate"):
            raise ValueError(
                f"sliding_window_pattern must be 'all' or 'alternate', got "
                f"{self.sliding_window_pattern!r}")
        if self.sliding_window is None:
            return None
        return tuple(
            self.sliding_window
            if self.sliding_window_pattern == "all" or i % 2 == 0 else None
            for i in range(self.depth))

    @nn.nowrap
    def stack(self) -> Encoder:
        """The stack of blocks at the fields this model hands it:
        `__call__` runs it, `cache_layout` asks it what it keeps."""
        return Encoder(
            depth=self.depth,
            num_heads=self.num_heads,
            head_dim=self.head_dim or self.hidden_size // self.num_heads,
            mlp_dim=self.mlp_dim,
            dtype=self.dtype,
            dropout_rate=self.dropout_rate,
            attn_impl=self.attn_impl,
            causal=True,
            decode=self.decode,
            rope=self.position == "rope",
            rope_theta=self.rope_theta,
            rope_scaling=(tuple(self.rope_scaling)
                          if self.rope_scaling is not None else None),
            rope_dim=self.rope_dim,
            num_kv_heads=self.num_kv_heads,
            fused_qkv=self.fused_qkv,
            quant=self.quant,
            windows=self.layer_windows(),
            rope_layers=(tuple(bool(r) for r in self.rope_layers)
                         if self.rope_layers is not None else None),
            rolling_cache=self.rolling_cache,
            paged_blocks=self.paged_blocks,
            kv_block=self.kv_block,
            kv_quant=self.kv_quant,
            attn_scale=self.attn_scale,
            attn_logit_cap=self.attn_logit_cap,
            norm=self.norm,
            norm_style=self.norm_style,
            mlp_act=self.mlp_act,
            use_bias=self.use_bias,
            qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            ln_eps=self.ln_eps,
            remat=self.remat,
            num_experts=self.num_experts,
            moe_every=self.moe_every,
            experts_per_token=self.experts_per_token,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_normalize_topk=self.moe_normalize_topk,
            moe_shared_expert_dim=self.moe_shared_expert_dim,
            router_z_loss_weight=self.router_z_loss_weight,
            attention=self.attention,
            eva_window=self.eva_window,
            eva_chunk=self.eva_chunk,
            norm_unit_offset=self.norm_unit_offset,
            moe_held_experts=(tuple(self.moe_held_experts)
                              if self.moe_held_experts is not None else None),
            moe_shared_expert_gated=self.moe_shared_expert_gated,
            moe_router_pre_attention=self.moe_router_pre_attention,
            mixers=tuple(self.mixers) if self.mixers is not None else None,
            ssm=self.ssm,
            mla=self.mla,
            gdn=self.gdn,
            attn_output_gate=self.attn_output_gate,
            mlps=tuple(self.mlps) if self.mlps is not None else None,
            moe_mlp_dim=self.moe_mlp_dim,
            moe_score=self.moe_score,
            moe_selection_bias=self.moe_selection_bias,
            moe_routed_scale=self.moe_routed_scale,
            residual_multiplier=self.residual_multiplier,
            name="decoder",
        )

    @nn.nowrap
    def cache_layout(self, max_len: Optional[int] = None) -> CacheLayout:
        """What this model keeps in the decode cache under a batcher of
        `max_len` positions a row (None: of some length): each block's
        description of itself, from the module that keeps the state
        (which is where a window shorter than `max_len` is found to keep a
        ring). A decode clone says itself whether its windows roll; a
        model not yet cloned is described as the batcher clones it
        (`_decode_clone(rolling=True)`)."""
        stack = self.stack().clone(
            rolling_cache=self.rolling_cache or not self.decode)
        return CacheLayout(
            stack.cache_states(max_len),
            uncapped_experts=(FEED_PAD_UNSHARED if self.num_experts > 0
                              and self.moe_capacity_factor is None
                              else None))

    @nn.compact
    def __call__(self, input_ids: jax.Array, train: bool = False,
                 segment_ids: Optional[jax.Array] = None,
                 last: Optional[jax.Array] = None) -> jax.Array:
        """last [B]: the head is applied at that one position of each row
        and the logits are [B, 1, vocab] (a prefill that samples one first
        token reads no other position's logits).

        segment_ids [B, S]: sequence-packing support (data/packing.py)
        — tokens attend only within their own segment (block-diagonal
        causal mask; padding is segment 0 and attends only other padding,
        keeping its softmax rows finite). Positions stay GLOBAL within
        the packed row: exact for rope (attention depends only on
        relative position, and cross-segment pairs are masked), offset
        but consistent for learned positions. Training-side only —
        decode mode refuses it."""
        if self.quant is not None and train:
            raise ValueError(
                "quant='int8' is a serving-only mode (round() has zero "
                "gradient) — train the fp model, then quantize_model it"
            )
        seg_mask = None
        if segment_ids is not None:
            if self.decode:
                raise NotImplementedError(
                    "segment_ids (sequence packing) is a training-side "
                    "capability; the decode cache has no segment plane"
                )
            if self.layer_windows() is not None:
                raise NotImplementedError(
                    "segment_ids does not compose with sliding_window "
                    "yet (the band would need per-segment offsets)"
                )
            from tfde_tpu.ops.attention import _seq_parallel_active

            if _seq_parallel_active():
                # auto-dispatch would pick the seq ring, which takes
                # key-padding masks only — fail HERE with the cause named
                # instead of a mask-shape error deep inside the ring
                raise NotImplementedError(
                    "segment_ids (sequence packing) does not compose "
                    "with sequence parallelism — the ring would need a "
                    "sharded segment plane; train packed batches under "
                    "dp/fsdp/tp"
                )
            seg = segment_ids.astype(jnp.int32)
            # [B, 1, S, S]; the causal triangle composes inside attention
            seg_mask = (seg[:, None, :, None] == seg[:, None, None, :])
        b = batch_axes()
        seq = input_ids.shape[1]
        if self.quant is not None:
            from tfde_tpu.ops.quant import QuantEmbed

            wte = QuantEmbed(self.vocab_size, self.hidden_size,
                             dtype=self.dtype, name="wte")
        else:
            wte = nn.Embed(
                self.vocab_size, self.hidden_size, dtype=self.dtype,
                param_dtype=jnp.float32, name="wte",
            )
        if self.position not in ("learned", "rope", "none"):
            raise ValueError(
                f"position must be 'learned', 'rope' or 'none', got "
                f"{self.position!r}"
            )
        if self.rope_layers is not None and self.position != "rope":
            raise ValueError(
                "rope_layers says which layers rotate under position='rope'; "
                f"position is {self.position!r}")
        x = wte(input_ids)
        if self.embed_scale is not None:
            x = x * jnp.asarray(self.embed_scale, self.dtype)
        if self.position == "learned":
            wpe = nn.Embed(
                self.max_position, self.hidden_size, dtype=self.dtype,
                param_dtype=jnp.float32, name="wpe",
            )
            positions = jnp.arange(seq, dtype=jnp.int32)
            if self.decode:
                # position offset rides the cache like the K/V do: a decode
                # step at cache position t embeds wpe[t], matching the full-
                # sequence forward exactly. Check BEFORE self.variable
                # creates it: a call with no pre-existing cache is position 0
                # and must not advance (the attention layers' fresh
                # cache_index stays 0 the same way).
                is_filled = self.has_variable("cache", "position_index")
                pos_index = self.variable("cache", "position_index",
                                          lambda: jnp.zeros((), jnp.int32))
                if is_filled and not self.is_initializing():
                    # scalar index -> positions [S]; per-row [B] index (the
                    # batched-speculation rewind, inference/speculative.py)
                    # broadcasts to [B, S]
                    positions = pos_index.value[..., None] + positions
                    pos_index.value = pos_index.value + seq
            x = x + wpe(positions if positions.ndim == 2
                        else positions[None, :])
        if self.fp32_residual:
            # every block adds its sublayer's output to x: a float32 x
            # keeps each sum in float32 (the norms read it in float32 and
            # hand the sublayers `dtype`)
            x = x.astype(jnp.float32)
        x = constrain(x, b, "seq")
        if self.dropout_rate > 0.0:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = self.stack()(x, mask=seg_mask, train=train)
        if last is not None:
            x = x[jnp.arange(x.shape[0]), last][:, None]
        if self.tie_embeddings:
            if self.head_bias:
                raise ValueError(
                    "head_bias=True requires tie_embeddings=False (the "
                    "tied head is wte^T via Embed.attend, which carries "
                    "no bias) — a silently dropped bias would change the "
                    "architecture"
                )
            logits = wte.attend(x.astype(self.dtype)).astype(jnp.float32)
        elif self.quant is not None:
            from tfde_tpu.ops.quant import QuantDenseGeneral

            logits = QuantDenseGeneral(
                self.vocab_size, use_bias=self.head_bias, dtype=self.dtype,
                name="lm_head",
            )(x.astype(self.dtype)).astype(jnp.float32)
        else:
            logits = nn.Dense(
                self.vocab_size, use_bias=self.head_bias, dtype=self.dtype,
                param_dtype=jnp.float32, name="lm_head",
            )(x.astype(self.dtype)).astype(jnp.float32)
        if self.logits_scaling is not None:
            logits = logits / self.logits_scaling
        if self.final_logit_cap is not None:
            logits = self.final_logit_cap * jnp.tanh(
                logits / self.final_logit_cap
            )
        return constrain(logits, b, "seq", "tensor")


GPT2Small = functools.partial(
    GPT, hidden_size=768, depth=12, num_heads=12, mlp_dim=3072
)
GPT2Medium = functools.partial(
    GPT, hidden_size=1024, depth=24, num_heads=16, mlp_dim=4096,
)


def gpt_tiny_test(**kw) -> GPT:
    """CI config for the 8-device CPU mesh (SURVEY.md §4)."""
    return GPT(
        vocab_size=97, hidden_size=32, depth=2, num_heads=4, mlp_dim=64,
        max_position=64, dtype=jnp.float32, **kw,
    )


def next_token_loss(state, params, batch, rng):
    """(loss, metrics) for make_custom_train_step: shifted CE over all
    positions (predict token t+1 from prefix <= t).

    Applies with mutable=["losses"] so values the model sows there — the
    MoE load-balance aux and router z-loss (models/moe.py) — join the
    objective, matching the default classification path (training/step.py
    `_forward`). Without this an MoE GPT would train with unbalanced
    routing: sow() into an immutable collection is a silent no-op. Each
    sown loss is also surfaced as a metric (summed over layers) so
    telemetry can watch router balance.
    """
    from tfde_tpu.ops.losses import masked_lm_loss

    (tokens,) = batch if isinstance(batch, tuple) else (batch,)
    try:
        logits, mutated = state.apply_fn(
            {"params": params}, tokens, train=True, rngs={"dropout": rng},
            mutable=["losses"],
        )
    except TypeError as e:
        # custom apply_fns without flax's kwarg (PipelinedLM.apply) — no
        # sown-loss collections to collect there. Match the exact
        # unsupported-kwarg signature error: a looser match would silently
        # rerun (and drop sown losses for) models whose own TypeError
        # merely mentions mutable
        if "unexpected keyword argument 'mutable'" not in str(e):
            raise
        logits = state.apply_fn(
            {"params": params}, tokens, train=True, rngs={"dropout": rng}
        )
        mutated = {}
    # align: logits[:, :-1] predict tokens[:, 1:]
    labels = tokens[:, 1:].astype(jnp.int32)
    with jax.named_scope("head_loss"):
        loss, acc = masked_lm_loss(logits[:, :-1], labels)
    metrics = {"next_token_accuracy": acc}
    from tfde_tpu.training.step import sown_losses_by_name

    for name, total in sown_losses_by_name(mutated.get("losses", {})).items():
        loss = loss + total
        metrics[name] = total
    return loss, metrics
