"""Autoregressive generation — the serving-side capability of the causal LMs.

The reference's serving story ends at SavedModel export of a forward pass
(`/root/reference/mnist_keras_distributed.py:287-292` — classifier in, probs
out); for the token-model families this framework adds (GPT, MoE-GPT), the
forward pass alone is not servable — generation is. This module is the
TPU-native decode loop:

- **One compile, every step.** Prefill (the whole prompt in one forward) and
  the per-token decode step are two fixed-shape programs; the sampling loop
  is a `lax.scan`, so the entire generate call is ONE XLA program — no
  per-token dispatch from Python, no dynamic shapes, no recompiles as the
  sequence grows (the cache is allocated at the full budget up front and
  written by `dynamic_update_slice`, models/transformer.py decode path).
- **KV cache in the flax "cache" collection** (cached_key/cached_value/
  cache_index per attention layer + the model's position_index), threaded
  through the scan as ordinary carry state.
- **Sampling on device**: repetition penalty first (CTRL rule over a
  [B, V] presence mask carried through the scan), then greedy
  (temperature=0) or temperature, top-k (`lax.top_k` threshold) and
  nucleus/top-p (sort + exclusive-cumsum mask) — composed in that order,
  then `jax.random.categorical`.
- **EOS with static shapes**: generation always runs the full
  `max_new_tokens` scan; finished rows emit `pad_id` and stop changing. The
  returned `lengths` tells the caller where each row actually ended. (A
  data-dependent early exit would be a `while_loop` barrier on the slowest
  row — on TPU the fixed-length scan is the right trade at batch > 1.)

Sampling params (temperature/top_k/top_p/eos_id) are static arguments: a
generation config is picked once per deployment, and burning it into the
compiled program lets XLA fold the sampling graph; changing it recompiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _make_model_step(decode_model, params):
    """One decode forward: (cache, [B, S] tokens) -> (cache', last-position
    fp32 logits). Shared by generate / generate_ragged; beam_search wraps
    it with a log_softmax for joint-score accumulation."""

    def model_step(cache, tokens):
        logits, mutated = decode_model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            mutable=["cache"],
        )
        return mutated["cache"], logits[:, -1].astype(jnp.float32)

    return model_step


def _decode_clone(model, rolling: bool = False, paged_blocks=None,
                  kv_block=None, kv_quant=None):
    """The serving twin of a training model: decode on, remat off (remat
    only shapes the backward pass, which decode doesn't have — a training
    config with remat must not make the model unservable).

    rolling=True engages the window-bounded rolling KV cache
    (transformer.MultiHeadAttention.rolling_cache) when the model has a
    sliding window — decode memory O(window) instead of O(budget). Only
    paths that NEVER rewind the cache may pass it (generate /
    generate_ragged / beam_search); speculative decoding's rewind would
    alias committed slots.

    paged_blocks engages the paged KV pool (transformer.MultiHeadAttention
    paged_blocks/kv_block, TFDE_PAGED_KV): K/V in one shared block pool
    indexed through per-row block tables (inference/paged.py owns the
    host-side allocation). Mutually exclusive with rolling.

    kv_quant='int8' engages the quantized KV cache (TFDE_KV_QUANT): int8
    payload + per-(position, kv-head) fp32 scale sidecars in either cache
    layout, dequantized inside the attention program. 'fp'/None keep the
    full-precision cache byte-identical. Mutually exclusive with rolling
    (the modular slot rewrite has no scale plane)."""
    if not hasattr(model, "decode"):
        raise ValueError(
            f"{type(model).__name__} has no decode mode — autoregressive "
            f"generation needs a causal LM with KV-cache support (GPT)"
        )
    kw = {"decode": True}
    if getattr(model, "remat", False):
        kw["remat"] = False
    if (rolling and hasattr(model, "rolling_cache")
            and model.layer_windows() is not None):
        kw["rolling_cache"] = True
    if paged_blocks is not None:
        if rolling:
            raise ValueError(
                "paged_blocks and rolling are mutually exclusive cache "
                "layouts"
            )
        if not hasattr(model, "paged_blocks"):
            raise ValueError(
                f"{type(model).__name__} has no paged KV support — "
                f"TFDE_PAGED_KV needs a model threading paged_blocks "
                f"through its attention layers (GPT)"
            )
        kw["paged_blocks"] = int(paged_blocks)
        if kv_block is not None:
            kw["kv_block"] = int(kv_block)
    if kv_quant in ("fp", None):
        kv_quant = None  # 'fp' is the knob spelling of the default
    elif kv_quant == "int8":
        if rolling:
            raise ValueError(
                "kv_quant='int8' and rolling are mutually exclusive cache "
                "layouts (no scale plane for the modular slot rewrite)"
            )
        if not hasattr(model, "kv_quant"):
            raise ValueError(
                f"{type(model).__name__} has no quantized-KV support — "
                f"TFDE_KV_QUANT needs a model threading kv_quant through "
                f"its attention layers (GPT)"
            )
        kw["kv_quant"] = "int8"
    else:
        raise ValueError(
            f"kv_quant must be None, 'fp' or 'int8', got {kv_quant!r}"
        )
    return model.clone(**kw)


def validate_budget(model, prompt_len: int, max_new_tokens: int) -> int:
    """Shared generate/beam_search argument check; returns the total cache
    budget prompt_len + max_new_tokens.

    The max_position cap applies only to learned-position models (their wpe
    table physically ends there); rotary models have no table and may
    extrapolate past their training length — the cache budget is then
    bounded only by memory."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = prompt_len + max_new_tokens
    max_pos = getattr(model, "max_position", None)
    if (max_pos is not None and total > max_pos
            and getattr(model, "position", "learned") != "rope"):
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) = "
            f"{total} exceeds the model's max_position {max_pos}"
        )
    return total


def init_cache(model, batch_size: int, max_len: int,
               rolling: bool = False, paged_blocks=None, kv_block=None,
               kv_quant=None):
    """Zero-filled "cache" collection for `model.clone(decode=True)` sized to
    a [batch_size, max_len] generation budget (window-bounded when
    `rolling`, pool-shaped when `paged_blocks`, int8 + scale sidecars when
    `kv_quant='int8'` — must match the decode clone's flags).

    Uses `jax.eval_shape` on the decode-mode init, so no model compute (and
    no real parameter init) runs — only the cache pytree's shapes/dtypes are
    derived, then materialized as zeros.
    """
    decode_model = _decode_clone(model, rolling=rolling,
                                 paged_blocks=paged_blocks,
                                 kv_block=kv_block, kv_quant=kv_quant)
    tokens = jax.ShapeDtypeStruct((batch_size, max_len), jnp.int32)

    def _init(tokens):
        return decode_model.init(jax.random.key(0), tokens)

    shapes = jax.eval_shape(_init, tokens)
    if "cache" not in shapes:
        raise ValueError(
            f"{type(model).__name__} creates no cache variables in decode "
            f"mode — generation needs a model with decode support (GPT)"
        )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def sample_logits(
    logits: jax.Array,
    rng: jax.Array,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    repetition_penalty: float = 1.0,
    seen: Optional[jax.Array] = None,
) -> jax.Array:
    """[B, V] logits -> [B] sampled token ids. temperature=0 is greedy
    (argmax); the top_k, top_p and min_p filters compose (k, then
    nucleus, then min-p: drop tokens whose probability is below
    min_p * max-probability — a shape-adaptive floor that cuts the long
    tail when the model is confident and keeps diversity when it is
    not).

    repetition_penalty > 1 with `seen` (a [B, V] bool presence mask of
    already-emitted ids) applies the CTRL/HF rule before any other
    processing — positive logits of seen tokens divide by the penalty,
    negative ones multiply — discouraging loops for greedy and sampled
    decoding alike."""
    if repetition_penalty <= 0.0:
        raise ValueError(
            f"repetition_penalty must be > 0 (1.0 = off), got "
            f"{repetition_penalty} — 0 would divide seen logits to inf"
        )
    logits = logits.astype(jnp.float32)
    if repetition_penalty != 1.0 and seen is not None:
        penalized = jnp.where(logits > 0, logits / repetition_penalty,
                              logits * repetition_penalty)
        logits = jnp.where(seen, penalized, logits)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    neg = jnp.finfo(jnp.float32).min
    if top_k is not None and top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # exclusive cumsum: a token stays if the mass strictly above it is
        # still < top_p — the smallest set whose total reaches top_p (the
        # top-1 always stays: its exclusive mass is 0)
        cum = jnp.cumsum(probs, axis=-1) - probs
        keep_sorted = cum < top_p
        # map the per-rank decision back to vocab order via the smallest
        # kept logit (ties at the threshold keep both — harmless)
        threshold = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf),
            axis=-1, keepdims=True,
        )
        logits = jnp.where(logits < threshold, neg, logits)
    if min_p is not None and 0.0 < min_p <= 1.0:
        # min_p=1.0 is MEANINGFUL (keep only tokens tied with the max) —
        # unlike top_p, 1.0 is not a no-op here
        probs = jax.nn.softmax(logits, axis=-1)
        floor = min_p * jnp.max(probs, axis=-1, keepdims=True)
        logits = jnp.where(probs < floor, neg, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "temperature", "top_k",
                     "top_p", "min_p", "eos_id", "pad_id",
                     "repetition_penalty"),
)
def generate(
    model,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    repetition_penalty: float = 1.0,
):
    """Generate `max_new_tokens` continuations of `prompt` [B, P] int32.

    Returns (tokens [B, P + max_new_tokens], lengths [B]): `tokens` is the
    prompt followed by the generated continuation (post-EOS positions hold
    `pad_id`); `lengths[b]` counts prompt + generated-through-EOS.

    The whole call — prefill, scan of decode steps, sampling — is one jitted
    program; recompiles happen per (shape, sampling-config), not per token.
    Prompts are dense [B, P]: batch rows share a prompt length (bucket or
    left-trim ragged prompts; per-row validity masking would put a [B,
    max_len] mask on the attention hot path for a capability batching
    usually handles upstream).
    """
    if rng is None:
        rng = jax.random.key(0)
    b, p = prompt.shape
    total = validate_budget(model, p, max_new_tokens)
    decode_model = _decode_clone(model, rolling=True)
    cache = init_cache(model, b, total, rolling=True)
    prompt = prompt.astype(jnp.int32)
    model_step = _make_model_step(decode_model, params)
    sample = functools.partial(sample_logits, temperature=temperature,
                               top_k=top_k, top_p=top_p, min_p=min_p,
                               repetition_penalty=repetition_penalty)
    penalize = repetition_penalty != 1.0
    # presence mask of everything emitted so far (prompt included, the HF
    # convention); updated per step via a [B, V] scatter — only built when
    # the penalty is on
    vocab = model.vocab_size
    seen = (
        jnp.zeros((b, vocab), jnp.bool_).at[
            jnp.arange(b)[:, None], prompt
        ].set(True)
        if penalize else None
    )

    greedy = temperature == 0.0

    # prefill: the prompt in one fixed-shape forward
    cache, last_logits = model_step(cache, prompt)
    if greedy:
        sub = rng  # argmax path: sample_logits never reads the key
    else:
        rng, sub = jax.random.split(rng)
    tok = sample(last_logits, sub, seen=seen)
    if penalize:
        seen = seen.at[jnp.arange(b), tok].set(True)
    done = jnp.zeros((b,), jnp.bool_)
    if eos_id is not None:
        done = tok == eos_id

    def step(carry, _):
        cache, tok, rng, done, seen = carry
        cache, logits = model_step(cache, tok[:, None])
        if greedy:
            sub = rng  # greedy: skip the per-token key split on device
        else:
            rng, sub = jax.random.split(rng)
        nxt = sample(logits, sub, seen=seen)
        if eos_id is not None:
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
        if penalize:
            seen = seen.at[jnp.arange(b), nxt].set(True)
        return (cache, nxt, rng, done, seen), nxt

    (_, _, _, done, _), rest = jax.lax.scan(
        step, (cache, tok, rng, done, seen), length=max_new_tokens - 1
    )
    new_tokens = jnp.concatenate(
        [tok[:, None], jnp.moveaxis(rest, 0, 1)], axis=1
    )  # [B, max_new_tokens]
    tokens = jnp.concatenate([prompt, new_tokens], axis=1)
    if eos_id is None:
        lengths = jnp.full((b,), total, jnp.int32)
    else:
        # a position counts while no EOS appeared strictly before it — the
        # EOS token itself is counted, post-EOS pad_id fill is not (correct
        # even when pad_id == eos_id, the GPT-2 convention)
        is_eos = (new_tokens == eos_id).astype(jnp.int32)
        seen_before = jnp.cumsum(is_eos, axis=1) - is_eos
        lengths = p + jnp.sum((seen_before == 0).astype(jnp.int32), axis=1)
    return tokens, lengths


def generate_ragged(
    model,
    params,
    prompt: jax.Array,
    prompt_lengths,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    prefill_len: Optional[int] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
):
    """`generate` for a batch of prompts with DIFFERENT lengths.

    `prompt` is [B, Pmax] RIGHT-padded; `prompt_lengths` [B] gives each
    row's real length. Returns (tokens [B, Pmax + max_new_tokens],
    lengths [B]) with row r's continuation starting at slot
    `prompt_lengths[r]`. The batch is decoded by *teacher-forcing through
    the prompt tail*: prefill covers the shortest `prefill_len` slots
    (default: min(prompt_lengths)), then every further slot is one decode
    step whose input is the row's own prompt token while the row is still
    inside its prompt and the sampled continuation after. The cache
    therefore never contains padding — positions and attention per row are
    identical to the solo run, with no per-row masks on the attention hot
    path. Under greedy decoding (temperature=0, the default) each row's
    output is EXACTLY what a solo `generate` on the unpadded row produces;
    with temperature>0 the per-token distributions match but the sampled
    draws differ (rows share one rng split per slot, and a row's k-th
    generated token lands on a different split than the solo run's k-th).

    Trade: the prompt tail beyond `prefill_len` is consumed one token per
    step instead of in one prefill forward. Bucket wildly-varying lengths
    upstream if that tail dominates.
    """
    lengths_np = np.asarray(prompt_lengths, np.int32)
    b, p_max = prompt.shape
    if lengths_np.shape != (b,):
        raise ValueError(
            f"prompt_lengths must be [batch]={b}, got {lengths_np.shape}"
        )
    if lengths_np.min() < 1 or lengths_np.max() > p_max:
        raise ValueError(
            f"prompt_lengths must lie in [1, {p_max}], got "
            f"[{lengths_np.min()}, {lengths_np.max()}]"
        )
    if prefill_len is None:
        prefill_len = int(lengths_np.min())
    if not 1 <= prefill_len <= lengths_np.min():
        raise ValueError(
            f"prefill_len={prefill_len} must lie in [1, min(prompt_lengths)="
            f"{lengths_np.min()}] — prefilling past a row's prompt would "
            f"feed its padding into the cache"
        )
    if rng is None:
        rng = jax.random.key(0)
    return _generate_ragged(
        model, params, prompt.astype(jnp.int32), jnp.asarray(lengths_np),
        max_new_tokens, rng, prefill_len, temperature, top_k, top_p,
        min_p, eos_id, pad_id,
    )


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "prefill_len", "temperature",
                     "top_k", "top_p", "min_p", "eos_id", "pad_id"),
)
def _generate_ragged(model, params, prompt, prompt_lengths, max_new_tokens,
                     rng, prefill_len, temperature, top_k, top_p, min_p,
                     eos_id, pad_id):
    b, p_max = prompt.shape
    total = validate_budget(model, p_max, max_new_tokens)
    decode_model = _decode_clone(model, rolling=True)
    cache = init_cache(model, b, total, rolling=True)
    sample = functools.partial(sample_logits, temperature=temperature,
                               top_k=top_k, top_p=top_p, min_p=min_p)
    model_step = _make_model_step(decode_model, params)

    # seq holds the final assembly; prompt slots are already right, the
    # rest starts as pad and is written slot by slot
    seq = jnp.concatenate(
        [
            jnp.where(
                jnp.arange(p_max)[None, :] < prompt_lengths[:, None],
                prompt, pad_id,
            ),
            jnp.full((b, max_new_tokens), pad_id, jnp.int32),
        ],
        axis=1,
    )
    cache, logits = model_step(cache, prompt[:, :prefill_len])

    greedy = temperature == 0.0

    def fill_slot(t, logits, rng, gen_count, done, seq):
        """Sample slot t's token (prompt token while inside the prompt,
        sampled continuation after) and write it into seq."""
        if greedy:
            sub = rng  # greedy: skip the per-slot key split on device
        else:
            rng, sub = jax.random.split(rng)
        sampled = sample(logits, sub)
        in_prompt = t < prompt_lengths  # [B]
        can_gen = (~in_prompt) & (~done) & (gen_count < max_new_tokens)
        prompt_tok = jax.lax.dynamic_slice_in_dim(seq, t, 1, axis=1)[:, 0]
        tok = jnp.where(in_prompt, prompt_tok,
                        jnp.where(can_gen, sampled, pad_id)).astype(jnp.int32)
        gen_count = gen_count + can_gen.astype(jnp.int32)
        if eos_id is not None:
            done = done | (can_gen & (sampled == eos_id))
        seq = jax.lax.dynamic_update_slice_in_dim(
            seq, tok[:, None], t, axis=1
        )
        return tok, rng, gen_count, done, seq

    def body(carry, t):
        cache, logits, rng, gen_count, done, seq = carry
        tok, rng, gen_count, done, seq = fill_slot(
            t, logits, rng, gen_count, done, seq
        )
        cache, logits = model_step(cache, tok[:, None])
        return (cache, logits, rng, gen_count, done, seq), None

    gen_count = jnp.zeros((b,), jnp.int32)
    done = jnp.zeros((b,), jnp.bool_)
    # scan stops one slot early: the final slot needs no model_step (its
    # logits would feed nothing — one whole decode forward saved per call)
    (_, logits, rng, gen_count, done, seq), _ = jax.lax.scan(
        body, (cache, logits, rng, gen_count, done, seq),
        jnp.arange(prefill_len, total - 1),
    )
    _, _, gen_count, _, seq = fill_slot(
        jnp.asarray(total - 1, jnp.int32), logits, rng, gen_count, done, seq
    )
    return seq, prompt_lengths + gen_count
