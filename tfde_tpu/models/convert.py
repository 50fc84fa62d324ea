"""Pretrained-checkpoint conversion: HuggingFace transformers -> this
framework's param trees.

The migration story for users arriving with trained models: GPT-2 and BERT
checkpoints in the `transformers` torch format load directly into
models/gpt.GPT and models/bert.Bert, verified by logit matching
(tests/test_convert.py builds tiny HF models and asserts our forward
reproduces theirs). Conversion is pure reshaping on host numpy:

- GPT-2 stores fused-projection Conv1D weights as [in, out] — no
  transpose; the [H, 3H] c_attn splits into q/k/v and reshapes to the
  Megatron-friendly [in, heads, head_dim] kernels our DenseGeneral uses.
- BERT uses torch.nn.Linear ([out, in]) — transposed, then reshaped the
  same way.
- LM heads are weight-tied in both (our `Embed.attend` convention), so no
  separate head tensor exists or is needed; BERT's prediction bias maps to
  `mlm_bias`.

Known approximation: our Mlp uses the tanh-approximate gelu (flax
default), which IS GPT-2's `gelu_new` exactly, but differs from BERT's
exact `gelu` by ~1e-3 in activations — far below bf16 noise on TPU, and
the logit-match test bounds it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np


def _np(t) -> np.ndarray:
    return t.detach().cpu().numpy().astype(np.float32)


def _one_layout(to_hf):
    """An exporter of a GPT writes ONE window and one position scheme for
    the whole model (`sliding_window` under its pattern, `position`). A
    model described layer by layer (`windows`, `rope_layers`) has no twin
    among them: exporting it would silently drop its bands."""
    @functools.wraps(to_hf)
    def checked(model, params):
        if (getattr(model, "windows", None) is not None
                or getattr(model, "rope_layers", None) is not None):
            raise NotImplementedError(
                f"{to_hf.__name__} writes one sliding window and one "
                f"position scheme for the model; this one gives them per "
                f"layer (windows={model.windows!r}, "
                f"rope_layers={model.rope_layers!r})")
        return to_hf(model, params)
    return checked


def gpt2_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers GPT2LMHeadModel (or GPT2Model).

    `dtype` overrides the activation dtype (default: the model family's
    bf16; pass jnp.float32 for exact-match verification on CPU)."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    heads = cfg.n_head
    hidden = cfg.n_embd
    hd = hidden // heads
    mlp_dim = cfg.n_inner if cfg.n_inner is not None else 4 * hidden
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.n_layer,
        num_heads=heads,
        mlp_dim=mlp_dim,
        max_position=cfg.n_positions,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        ln_eps=cfg.layer_norm_epsilon,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "transformer." if any(k.startswith("transformer.") for k in sd) else ""

    params = {
        "wte": {"embedding": sd[f"{pre}wte.weight"]},
        "wpe": {"embedding": sd[f"{pre}wpe.weight"]},
        "decoder": {
            "ln_final": {
                "scale": sd[f"{pre}ln_f.weight"],
                "bias": sd[f"{pre}ln_f.bias"],
            },
        },
    }
    for i in range(cfg.n_layer):
        h = f"{pre}h.{i}."
        # Conv1D weight layout is [in, out] already
        c_attn_w = sd[h + "attn.c_attn.weight"]  # [H, 3H]
        c_attn_b = sd[h + "attn.c_attn.bias"]    # [3H]
        qw, kw, vw = np.split(c_attn_w, 3, axis=1)
        qb, kb, vb = np.split(c_attn_b, 3)
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "ln_1.weight"],
                        "bias": sd[h + "ln_1.bias"]},
            "ln_mlp": {"scale": sd[h + "ln_2.weight"],
                       "bias": sd[h + "ln_2.bias"]},
            "attn": {
                "query": {"kernel": qw.reshape(hidden, heads, hd),
                          "bias": qb.reshape(heads, hd)},
                "key": {"kernel": kw.reshape(hidden, heads, hd),
                        "bias": kb.reshape(heads, hd)},
                "value": {"kernel": vw.reshape(hidden, heads, hd),
                          "bias": vb.reshape(heads, hd)},
                "out": {
                    "kernel": sd[h + "attn.c_proj.weight"].reshape(
                        heads, hd, hidden
                    ),
                    "bias": sd[h + "attn.c_proj.bias"],
                },
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "mlp.c_fc.weight"],
                        "bias": sd[h + "mlp.c_fc.bias"]},
                "fc2": {"kernel": sd[h + "mlp.c_proj.weight"],
                        "bias": sd[h + "mlp.c_proj.bias"]},
            },
        }
    return model, params


def _rope_scaling_tuple(rs, max_position=None) -> "Optional[tuple]":
    """HF rope_scaling dict -> the hashable tuple ops/rotary understands:
    ('linear', factor), ('llama3', factor, low, high, orig_max), or
    ('yarn', factor, beta_fast, beta_slow, orig_max, attention_factor,
    truncate). None passes through; dynamic-NTK / longrope are refused
    (their frequency rules are not implemented — converting would produce
    silently wrong logits). `max_position` is the config's
    max_position_embeddings — yarn's original_max falls back to it, the
    HF convention."""
    import math

    if not rs:
        return None
    kind = rs.get("rope_type") or rs.get("type")
    if kind == "linear":
        return ("linear", float(rs["factor"]))
    if kind == "llama3":
        return (
            "llama3", float(rs["factor"]),
            float(rs["low_freq_factor"]), float(rs["high_freq_factor"]),
            float(rs["original_max_position_embeddings"]),
        )
    if kind == "yarn":
        factor = float(rs["factor"])
        att = rs.get("attention_factor")
        if att is None:
            # the paper's mscale rule (HF _compute_yarn_parameters)
            def get_mscale(scale, m=1.0):
                return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

            mscale = rs.get("mscale")
            mscale_all = rs.get("mscale_all_dim")
            if mscale and mscale_all:
                att = get_mscale(factor, mscale) / get_mscale(factor,
                                                              mscale_all)
            else:
                att = get_mscale(factor)
        orig_max = (rs.get("original_max_position_embeddings")
                    or max_position)
        if orig_max is None:
            raise NotImplementedError(
                "yarn rope_scaling without original_max_position_"
                "embeddings needs the config's max_position_embeddings"
            )
        return (
            "yarn", factor,
            float(rs.get("beta_fast") or 32.0),
            float(rs.get("beta_slow") or 1.0),
            float(orig_max), float(att),
            bool(rs.get("truncate", True)),
        )
    if kind == "default":
        return None
    raise NotImplementedError(
        f"rope_scaling type {kind!r} is not supported (only 'linear', "
        f"'llama3' and 'yarn'); converting would produce silently wrong "
        f"logits"
    )


def _rope_scaling_dict(scaling) -> "Optional[dict]":
    """The inverse of _rope_scaling_tuple, for to_hf exports."""
    if scaling is None:
        return None
    scaling = tuple(scaling)
    if scaling[0] == "linear":
        return {"rope_type": "linear", "factor": float(scaling[1])}
    if scaling[0] == "llama3":
        return {
            "rope_type": "llama3", "factor": float(scaling[1]),
            "low_freq_factor": float(scaling[2]),
            "high_freq_factor": float(scaling[3]),
            "original_max_position_embeddings": int(scaling[4]),
        }
    if scaling[0] == "yarn":
        return {
            "rope_type": "yarn", "factor": float(scaling[1]),
            "beta_fast": float(scaling[2]),
            "beta_slow": float(scaling[3]),
            "original_max_position_embeddings": int(scaling[4]),
            # explicit attention_factor: guarantees the exported model
            # computes the identical temperature even if the import
            # derived it from mscale
            "attention_factor": float(scaling[5]),
            "truncate": bool(scaling[6]),
        }
    raise NotImplementedError(f"unknown rope scaling {scaling!r}")


def llama_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers LlamaForCausalLM — the LLaMA
    family maps onto GPT(position='rope', num_kv_heads=..., norm='rms',
    mlp_act='swiglu', use_bias=False): rotary rotate-half, grouped-query
    K/V, RMSNorm (scale only), gated-silu MLP, bias-free projections, and
    an untied lm_head unless the checkpoint ties it."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    rope_scaling = _rope_scaling_tuple(
        getattr(cfg, "rope_scaling", None),
        max_position=cfg.max_position_embeddings,
    )
    if getattr(cfg, "attention_bias", False) or getattr(cfg, "mlp_bias", False):
        raise NotImplementedError(
            "checkpoints with attention_bias/mlp_bias are not supported by "
            "this converter (the bias tensors would be silently dropped)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    # Gemma-7b-class checkpoints decouple the per-head width from
    # hidden/heads; honor the config's head_dim when present
    hd = getattr(cfg, "head_dim", None) or hidden // heads
    kv = cfg.num_key_value_heads
    tied = bool(getattr(cfg, "tie_word_embeddings", False))
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        rope_scaling=rope_scaling,
        num_kv_heads=kv,
        norm="rms",
        mlp_act="swiglu",
        use_bias=False,
        tie_embeddings=tied,
        ln_eps=cfg.rms_norm_eps,
        head_dim=None if hd == hidden // heads else hd,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "model." if any(k.startswith("model.") for k in sd) else ""

    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}norm.weight"]},
        },
    }
    if not tied:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[h + "post_attention_layernorm.weight"]},
            "attn": {
                # torch Linear [out, in] -> in-major kernels
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, kv, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, kv, hd)},
                "out": {"kernel": sd[h + "self_attn.o_proj.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "mlp": {
                "gate": {"kernel": sd[h + "mlp.gate_proj.weight"].T},
                "fc1": {"kernel": sd[h + "mlp.up_proj.weight"].T},
                "fc2": {"kernel": sd[h + "mlp.down_proj.weight"].T},
            },
        }
    return model, params


def mistral_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers MistralForCausalLM.

    Mistral is the LLaMA architecture (rope + GQA + swiglu + RMSNorm +
    bias-free) plus sliding-window attention; the HF state-dict layout is
    identical, so this delegates the weight mapping to `llama_from_hf` and
    sets `sliding_window` from the config (None in the config means full
    attention — some later Mistral checkpoints disable the window)."""
    model, params = llama_from_hf(hf_model, dtype=dtype)
    window = getattr(hf_model.config, "sliding_window", None)
    if window is not None:
        model = model.clone(sliding_window=int(window))
    return model, params


def gemma_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers GemmaForCausalLM.

    Gemma is LLaMA-shaped (rope + GQA + RMSNorm + bias-free + gated MLP
    + decoupled head_dim on 7b), so the weight mapping delegates to
    `llama_from_hf` — like `mistral_from_hf` — and this function handles
    the three Gemma deltas:

    - gelu-gated MLP (`mlp_act='geglu'`, HF gelu_pytorch_tanh);
    - token embeddings scaled by sqrt(hidden) (`GPT(embed_scale=...)`);
    - zero-centered RMSNorm weights (the HF module computes `x * (1 + w)`)
      — folded into the stored scales as `1 + w` at conversion, so the
      model's plain RMSNorm reproduces the math with no runtime branch.
    """
    cfg = hf_model.config
    # hidden_act is what the installed GemmaMLP actually runs
    # (ACT2FN[config.hidden_act]); hidden_activation is a config-era alias
    # that GemmaConfig folds into it — validating the alias could pass a
    # checkpoint whose live field says something else
    act = getattr(cfg, "hidden_act", None)
    if act not in ("gelu_pytorch_tanh", "gelu_tanh", None):
        raise NotImplementedError(
            f"hidden_act {act!r} is not supported (expected the Gemma "
            f"tanh-gelu); converting would silently change the math"
        )
    if not bool(getattr(cfg, "tie_word_embeddings", True)):
        # every Gemma release ties; an untied fine-tune would carry a
        # distinct lm_head.weight this path would silently drop
        raise NotImplementedError(
            "untied Gemma-architecture checkpoints are not supported "
            "(lm_head.weight would be silently dropped)"
        )
    model, params = llama_from_hf(hf_model, dtype=dtype)
    model = model.clone(
        mlp_act="geglu",
        tie_embeddings=True,
        embed_scale=float(cfg.hidden_size) ** 0.5,
    )
    dec = params["decoder"]
    dec["ln_final"]["scale"] = 1.0 + dec["ln_final"]["scale"]
    for i in range(cfg.num_hidden_layers):
        blk = dec[f"block_{i}"]
        blk["ln_attn"]["scale"] = 1.0 + blk["ln_attn"]["scale"]
        blk["ln_mlp"]["scale"] = 1.0 + blk["ln_mlp"]["scale"]
    return model, params


def gemma2_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers Gemma2ForCausalLM.

    Gemma-2 extends the Gemma arrangement with: SANDWICH norms (each
    sublayer normed both sides — `norm_style='sandwich'`, four RMSNorms
    per block), attention logit softcapping and a custom query scale
    (`attn_logit_cap`, `attn_scale = query_pre_attn_scalar^-0.5`), final
    logit softcapping, and ALTERNATING local/global attention (even
    blocks sliding-window, odd full — `sliding_window_pattern=
    'alternate'`). All five norm kinds carry the zero-centered 1+w fold
    like Gemma-1."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    act = getattr(cfg, "hidden_activation", "gelu_pytorch_tanh")
    if act not in ("gelu_pytorch_tanh", "gelu_tanh"):
        raise NotImplementedError(
            f"hidden_activation {act!r} is not supported (expected the "
            f"Gemma tanh-gelu)"
        )
    if not bool(getattr(cfg, "tie_word_embeddings", True)):
        raise NotImplementedError(
            "untied Gemma-2 checkpoints are not supported"
        )
    if bool(getattr(cfg, "attention_bias", False)):
        raise NotImplementedError(
            "attention_bias=True checkpoints are not supported (the bias "
            "tensors would be silently dropped)"
        )
    lt = getattr(cfg, "layer_types", None)
    if lt is not None:
        expect = ["sliding_attention", "full_attention"]
        if any(t != expect[i % 2] for i, t in enumerate(lt)):
            raise NotImplementedError(
                f"layer_types {lt!r} does not match the Gemma-2 "
                f"even-sliding/odd-full interleave this model expresses"
            )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = cfg.head_dim
    kv = cfg.num_key_value_heads
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        head_dim=None if hd == hidden // heads else hd,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        num_kv_heads=kv,
        use_bias=False,
        norm="rms",
        norm_style="sandwich",
        mlp_act="geglu",
        tie_embeddings=True,
        embed_scale=float(hidden) ** 0.5,
        ln_eps=cfg.rms_norm_eps,
        sliding_window=cfg.sliding_window,
        sliding_window_pattern="alternate",
        attn_scale=float(cfg.query_pre_attn_scalar) ** -0.5,
        attn_logit_cap=(float(cfg.attn_logit_softcapping)
                        if cfg.attn_logit_softcapping else None),
        final_logit_cap=(float(cfg.final_logit_softcapping)
                         if cfg.final_logit_softcapping else None),
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "model." if any(k.startswith("model.") for k in sd) else ""

    def fold(w):  # zero-centered RMSNorm weights: stored scale = 1 + w
        return 1.0 + w

    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": fold(sd[f"{pre}norm.weight"])},
        },
    }
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": fold(sd[h + "input_layernorm.weight"])},
            "ln_attn_post": {
                "scale": fold(sd[h + "post_attention_layernorm.weight"])
            },
            "ln_mlp": {
                "scale": fold(sd[h + "pre_feedforward_layernorm.weight"])
            },
            "ln_mlp_post": {
                "scale": fold(sd[h + "post_feedforward_layernorm.weight"])
            },
            "attn": {
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, kv, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, kv, hd)},
                "out": {"kernel": sd[h + "self_attn.o_proj.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "mlp": {
                "gate": {"kernel": sd[h + "mlp.gate_proj.weight"].T},
                "fc1": {"kernel": sd[h + "mlp.up_proj.weight"].T},
                "fc2": {"kernel": sd[h + "mlp.down_proj.weight"].T},
            },
        }
    return model, params


@_one_layout
def gemma2_to_hf(model, params):
    """A transformers Gemma2ForCausalLM carrying `params` — the inverse
    of `gemma2_from_hf` (all five norm kinds un-fold 1+w)."""
    import transformers

    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "geglu" or model.use_bias
            or not model.tie_embeddings or model.qkv_bias
            or getattr(model, "qk_norm", False) or model.head_bias
            or model.norm_style != "sandwich"
            or model.rope_dim is not None
            or model.rope_scaling is not None
            or model.sliding_window is None
            or model.sliding_window_pattern != "alternate"
            or model.attn_scale is None
            or model.embed_scale is None
            or abs(model.embed_scale - hidden ** 0.5) > 1e-6):
        raise NotImplementedError(
            "gemma2_to_hf requires the Gemma-2 arrangement (sandwich "
            "norms, geglu, tied scaled embeddings, alternating sliding "
            "window, custom query scale) — Gemma-1 models export via "
            "gemma_to_hf"
        )
    cfg = transformers.Gemma2Config(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=model.num_kv_heads or heads,
        intermediate_size=model.mlp_dim, head_dim=hd,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta, rms_norm_eps=model.ln_eps,
        sliding_window=int(model.sliding_window),
        query_pre_attn_scalar=float(model.attn_scale) ** -2.0,
        attn_logit_softcapping=(float(model.attn_logit_cap)
                                if model.attn_logit_cap else None),
        final_logit_softcapping=(float(model.final_logit_cap)
                                 if model.final_logit_cap else None),
        tie_word_embeddings=True, attention_dropout=0.0,
        hidden_activation="gelu_pytorch_tanh",
    )
    hf = transformers.Gemma2ForCausalLM(cfg)
    sd = {}
    sd["model.embed_tokens.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]

    def unfold(s):  # stored 1 + w -> the HF zero-centered weight
        return _t(np.asarray(s) - 1.0)

    sd["model.norm.weight"] = unfold(dec["ln_final"]["scale"])
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    kv = model.num_kv_heads or heads
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"model.layers.{i}."
        sd[h + "input_layernorm.weight"] = unfold(blk["ln_attn"]["scale"])
        sd[h + "post_attention_layernorm.weight"] = unfold(
            blk["ln_attn_post"]["scale"]
        )
        sd[h + "pre_feedforward_layernorm.weight"] = unfold(
            blk["ln_mlp"]["scale"]
        )
        sd[h + "post_feedforward_layernorm.weight"] = unfold(
            blk["ln_mlp_post"]["scale"]
        )
        a = blk["attn"]
        sd[h + "self_attn.q_proj.weight"] = _t(
            np.asarray(a["query"]["kernel"]).reshape(hidden, heads * hd).T
        )
        sd[h + "self_attn.k_proj.weight"] = _t(
            np.asarray(a["key"]["kernel"]).reshape(hidden, kv * hd).T
        )
        sd[h + "self_attn.v_proj.weight"] = _t(
            np.asarray(a["value"]["kernel"]).reshape(hidden, kv * hd).T
        )
        sd[h + "self_attn.o_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "mlp.gate_proj.weight"] = _t(
            np.asarray(blk["mlp"]["gate"]["kernel"]).T
        )
        sd[h + "mlp.up_proj.weight"] = _t(
            np.asarray(blk["mlp"]["fc1"]["kernel"]).T
        )
        sd[h + "mlp.down_proj.weight"] = _t(
            np.asarray(blk["mlp"]["fc2"]["kernel"]).T
        )
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def qwen2_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers Qwen2ForCausalLM.

    Qwen2 is the LLaMA architecture with biased q/k/v projections beside
    a bias-free out projection and MLP (`GPT(qkv_bias=True)`); the HF
    modeling code hardcodes those biases, so the weight mapping delegates
    to `llama_from_hf` and this function adds the three bias tensors per
    layer. Sliding-window Qwen2 configs interleave windowed and full
    layers (`layer_types`), which the single-window GPT cannot express —
    refused loudly; every mainline release ships use_sliding_window=False.
    """
    cfg = hf_model.config
    if bool(getattr(cfg, "use_sliding_window", False)):
        raise NotImplementedError(
            "use_sliding_window=True interleaves windowed and full "
            "attention per layer (max_window_layers), which the "
            "single-window model cannot express; mainline Qwen2 releases "
            "ship with it disabled"
        )
    model, params = llama_from_hf(hf_model, dtype=dtype)
    model = model.clone(qkv_bias=True)
    heads = cfg.num_attention_heads
    hd = getattr(cfg, "head_dim", None) or cfg.hidden_size // heads
    kv = cfg.num_key_value_heads
    # pull ONLY the bias tensors — llama_from_hf already materialized the
    # full state dict once; a second full-checkpoint fp32 copy to read
    # O(layers * 3 * width) floats would double peak host memory at 7B
    sd = hf_model.state_dict()
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}.self_attn."
        attn = params["decoder"][f"block_{i}"]["attn"]
        attn["query"]["bias"] = _np(sd[h + "q_proj.bias"]).reshape(heads, hd)
        attn["key"]["bias"] = _np(sd[h + "k_proj.bias"]).reshape(kv, hd)
        attn["value"]["bias"] = _np(sd[h + "v_proj.bias"]).reshape(kv, hd)
    return model, params


def qwen2moe_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers Qwen2MoeForCausalLM.

    Qwen2-MoE = the Qwen2 attention arrangement (biased q/k/v beside
    bias-free o/MLP) with EVERY layer's MLP routed, plus two deltas the
    MoE layer grew for it: RAW top-k combine weights
    (`moe_normalize_topk=False` when norm_topk_prob is off — the released
    A2.7B config) and a dense SHARED expert beside the routed ones, its
    output scaled by a learned sigmoid gate
    (`moe_shared_expert_dim`). Conversion pins the no-drop capacity
    (E/k) like Mixtral, making the converted forward exact."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if list(getattr(cfg, "mlp_only_layers", []) or []):
        raise NotImplementedError(
            f"mlp_only_layers {cfg.mlp_only_layers!r} (dense layers "
            f"interleaved among MoE) is not supported — the released "
            f"Qwen2-MoE configs route every layer"
        )
    if int(getattr(cfg, "decoder_sparse_step", 1)) != 1:
        raise NotImplementedError(
            f"decoder_sparse_step {cfg.decoder_sparse_step} != 1 is not "
            f"supported"
        )
    if bool(getattr(cfg, "use_sliding_window", False)):
        raise NotImplementedError(
            "use_sliding_window=True is not supported (per-layer windows)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    kv = cfg.num_key_value_heads
    e = cfg.num_experts
    k = cfg.num_experts_per_tok
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.moe_intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        rope_scaling=_rope_scaling_tuple(
            getattr(cfg, "rope_scaling", None),
            max_position=cfg.max_position_embeddings,
        ),
        num_kv_heads=kv,
        use_bias=False,
        qkv_bias=True,
        norm="rms",
        mlp_act="swiglu",
        num_experts=e,
        moe_every=1,
        experts_per_token=k,
        moe_capacity_factor=float(e) / k,
        moe_normalize_topk=bool(getattr(cfg, "norm_topk_prob", False)),
        moe_shared_expert_dim=cfg.shared_expert_intermediate_size,
        tie_embeddings=bool(getattr(cfg, "tie_word_embeddings", False)),
        ln_eps=cfg.rms_norm_eps,
    )
    sd = {k_: _np(v) for k_, v in hf_model.state_dict().items()}
    pre = "model." if any(k_.startswith("model.") for k_ in sd) else ""
    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}norm.weight"]},
        },
    }
    if not model.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        moe_pre = h + "mlp."
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[h + "post_attention_layernorm.weight"]},
            "attn": {
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd),
                          "bias": sd[h + "self_attn.q_proj.bias"]
                          .reshape(heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, kv, hd),
                        "bias": sd[h + "self_attn.k_proj.bias"]
                        .reshape(kv, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, kv, hd),
                          "bias": sd[h + "self_attn.v_proj.bias"]
                          .reshape(kv, hd)},
                "out": {"kernel": sd[h + "self_attn.o_proj.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "moe": {
                "router": {"kernel": sd[moe_pre + "gate.weight"].T},
                "experts_gate": np.stack(
                    [sd[moe_pre + f"experts.{j}.gate_proj.weight"].T
                     for j in range(e)]
                ),
                "experts_fc1": np.stack(
                    [sd[moe_pre + f"experts.{j}.up_proj.weight"].T
                     for j in range(e)]
                ),
                "experts_fc2": np.stack(
                    [sd[moe_pre + f"experts.{j}.down_proj.weight"].T
                     for j in range(e)]
                ),
                "shared_gate": {
                    "kernel": sd[moe_pre + "shared_expert.gate_proj.weight"].T
                },
                "shared_fc1": {
                    "kernel": sd[moe_pre + "shared_expert.up_proj.weight"].T
                },
                "shared_fc2": {
                    "kernel": sd[moe_pre + "shared_expert.down_proj.weight"].T
                },
                "shared_expert_gate": {
                    "kernel": sd[moe_pre + "shared_expert_gate.weight"].T
                },
            },
        }
    return model, params


@_one_layout
def qwen2moe_to_hf(model, params):
    """A transformers Qwen2MoeForCausalLM carrying `params` — the inverse
    of `qwen2moe_from_hf`."""
    import transformers

    e = model.num_experts
    k = model.experts_per_token
    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "swiglu" or model.use_bias
            or not model.qkv_bias or e <= 0 or model.moe_every != 1
            or model.moe_shared_expert_dim is None
            or getattr(model, "qk_norm", False) or model.head_bias
            or model.embed_scale is not None or model.head_dim is not None
            or model.norm_style != "pre" or model.rope_dim is not None
            or model.sliding_window is not None):
        raise NotImplementedError(
            "qwen2moe_to_hf requires the Qwen2-MoE arrangement (biased "
            "q/k/v, every layer routed, shared expert) — other families "
            "export via their own inverses"
        )
    if model.moe_capacity_factor < float(e) / k:
        raise NotImplementedError(
            f"moe_capacity_factor {model.moe_capacity_factor} < E/k = "
            f"{float(e) / k}: this model can drop overflow tokens, which "
            f"capacity-free HF Qwen2-MoE cannot express"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads
    kv = model.num_kv_heads or heads
    cfg = transformers.Qwen2MoeConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=kv,
        # intermediate_size (the DENSE MLP width) is inert here: both
        # directions pin mlp_only_layers=[] and decoder_sparse_step=1, so
        # no dense layer is ever instantiated and the original value is
        # not recorded by the import — set to the expert width, not a
        # claim about the source config
        intermediate_size=model.mlp_dim,
        moe_intermediate_size=model.mlp_dim,
        shared_expert_intermediate_size=model.moe_shared_expert_dim,
        num_experts=e, num_experts_per_tok=k,
        norm_topk_prob=model.moe_normalize_topk,
        decoder_sparse_step=1, mlp_only_layers=[],
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        rope_scaling=_rope_scaling_dict(model.rope_scaling),
        rms_norm_eps=model.ln_eps,
        tie_word_embeddings=model.tie_embeddings,
        use_sliding_window=False, attention_dropout=0.0,
        router_aux_loss_coef=0.0, output_router_logits=False,
    )
    hf = transformers.Qwen2MoeForCausalLM(cfg)
    sd = {}
    sd["model.embed_tokens.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]
    sd["model.norm.weight"] = _t(dec["ln_final"]["scale"])
    sd["lm_head.weight"] = (
        sd["model.embed_tokens.weight"] if model.tie_embeddings
        else _t(np.asarray(params["lm_head"]["kernel"]).T)
    )
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"model.layers.{i}."
        sd[h + "input_layernorm.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "post_attention_layernorm.weight"] = _t(
            blk["ln_mlp"]["scale"]
        )
        a = blk["attn"]
        for ours, theirs, nh in (("query", "q_proj", heads),
                                 ("key", "k_proj", kv),
                                 ("value", "v_proj", kv)):
            sd[h + f"self_attn.{theirs}.weight"] = _t(
                np.asarray(a[ours]["kernel"]).reshape(hidden, nh * hd).T
            )
            sd[h + f"self_attn.{theirs}.bias"] = _t(
                np.asarray(a[ours]["bias"]).reshape(nh * hd)
            )
        sd[h + "self_attn.o_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        moe = blk["moe"]
        sd[h + "mlp.gate.weight"] = _t(
            np.asarray(moe["router"]["kernel"]).T
        )
        gate_s = np.asarray(moe["experts_gate"])
        up_s = np.asarray(moe["experts_fc1"])
        down_s = np.asarray(moe["experts_fc2"])
        for j in range(e):
            sd[h + f"mlp.experts.{j}.gate_proj.weight"] = _t(gate_s[j].T)
            sd[h + f"mlp.experts.{j}.up_proj.weight"] = _t(up_s[j].T)
            sd[h + f"mlp.experts.{j}.down_proj.weight"] = _t(down_s[j].T)
        sd[h + "mlp.shared_expert.gate_proj.weight"] = _t(
            np.asarray(moe["shared_gate"]["kernel"]).T
        )
        sd[h + "mlp.shared_expert.up_proj.weight"] = _t(
            np.asarray(moe["shared_fc1"]["kernel"]).T
        )
        sd[h + "mlp.shared_expert.down_proj.weight"] = _t(
            np.asarray(moe["shared_fc2"]["kernel"]).T
        )
        sd[h + "mlp.shared_expert_gate.weight"] = _t(
            np.asarray(moe["shared_expert_gate"]["kernel"]).T
        )
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k_ for k_ in missing if "rotary_emb" not in k_]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def phi3_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers Phi3ForCausalLM (Phi-3/3.5-mini).

    The Phi-3 arrangement is LLaMA-shaped (rope + GQA + RMSNorm +
    gated-silu + bias-free + untied head) with FUSED checkpoint layouts:
    qkv_proj packs [q | k | v] rows flat, gate_up_proj packs
    [gate | up] — split here into the standard kernels. Long-context
    variants carry rope_scaling='longrope', which _rope_scaling_tuple
    refuses loudly (the 4k-context releases ship rope_scaling null).
    partial_rotary_factor < 1 maps to GPT(rope_dim=...)."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if getattr(cfg, "hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"hidden_act {cfg.hidden_act!r} is not supported (Phi-3 "
            f"releases use silu)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    kv = cfg.num_key_value_heads
    prf = float(getattr(cfg, "partial_rotary_factor", 1.0))
    rope_dim = None if prf == 1.0 else int(hd * prf)
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        rope_scaling=_rope_scaling_tuple(
            getattr(cfg, "rope_scaling", None),
            max_position=cfg.max_position_embeddings,
        ),
        rope_dim=rope_dim,
        num_kv_heads=kv,
        use_bias=False,
        norm="rms",
        mlp_act="swiglu",
        sliding_window=getattr(cfg, "sliding_window", None),
        tie_embeddings=bool(getattr(cfg, "tie_word_embeddings", False)),
        ln_eps=cfg.rms_norm_eps,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}norm.weight"]},
        },
    }
    if not model.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    f = cfg.intermediate_size
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        qkv = sd[h + "self_attn.qkv_proj.weight"].T  # [d, H + 2*kv*hd]
        qw, kw, vw = np.split(
            qkv, [heads * hd, heads * hd + kv * hd], axis=1
        )
        gate_up = sd[h + "mlp.gate_up_proj.weight"].T  # [d, 2F]
        gw, uw = np.split(gate_up, [f], axis=1)
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[h + "post_attention_layernorm.weight"]},
            "attn": {
                "query": {"kernel": qw.reshape(hidden, heads, hd)},
                "key": {"kernel": kw.reshape(hidden, kv, hd)},
                "value": {"kernel": vw.reshape(hidden, kv, hd)},
                "out": {"kernel": sd[h + "self_attn.o_proj.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "mlp": {
                "gate": {"kernel": gw},
                "fc1": {"kernel": uw},
                "fc2": {"kernel": sd[h + "mlp.down_proj.weight"].T},
            },
        }
    return model, params


@_one_layout
def phi3_to_hf(model, params):
    """A transformers Phi3ForCausalLM carrying `params` — the inverse of
    `phi3_from_hf`: the shared LLaMA-style state dict with q/k/v fused
    back into qkv_proj and gate/up into gate_up_proj."""
    import torch
    import transformers

    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads
    kv = model.num_kv_heads or heads
    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "swiglu" or model.use_bias
            or model.qkv_bias or model.head_bias
            or getattr(model, "qk_norm", False)
            or model.embed_scale is not None or model.head_dim is not None
            or model.norm_style != "pre"):
        raise NotImplementedError(
            "phi3_to_hf requires the Phi-3 arrangement (LLaMA-style "
            "bias-free gated-silu blocks with fused-checkpoint layouts) "
            "— other families export via their own inverses"
        )
    if model.rope_scaling is not None:
        # Phi3Config validates rope_scaling as longrope-format only
        # ({type, short_factor, long_factor}); the linear/llama3/yarn
        # tuples this framework carries have no Phi-3 representation
        raise NotImplementedError(
            f"rope_scaling {tuple(model.rope_scaling)!r} has no Phi-3 "
            f"config representation (Phi-3 long-context is 'longrope', "
            f"which is not implemented) — export via llama_to_hf instead"
        )
    prf = 1.0 if model.rope_dim is None else model.rope_dim / hd
    cfg = transformers.Phi3Config(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=kv, intermediate_size=model.mlp_dim,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        partial_rotary_factor=prf,
        rms_norm_eps=model.ln_eps,
        sliding_window=model.sliding_window,
        tie_word_embeddings=model.tie_embeddings,
        attention_dropout=0.0, resid_pdrop=0.0, embd_pdrop=0.0,
        pad_token_id=0,
    )
    hf = transformers.Phi3ForCausalLM(cfg)
    # the ONE llama-style builder, then fuse its per-layer keys into the
    # Phi-3 checkpoint layout
    sd = _llama_style_sd(model, params)
    for i in range(model.depth):
        h = f"model.layers.{i}."
        sd[h + "self_attn.qkv_proj.weight"] = torch.cat(
            [sd.pop(h + "self_attn.q_proj.weight"),
             sd.pop(h + "self_attn.k_proj.weight"),
             sd.pop(h + "self_attn.v_proj.weight")], dim=0,
        )
        sd[h + "mlp.gate_up_proj.weight"] = torch.cat(
            [sd.pop(h + "mlp.gate_proj.weight"),
             sd.pop(h + "mlp.up_proj.weight")], dim=0,
        )
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def qwen3_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers Qwen3ForCausalLM.

    Qwen3 is the LLaMA arrangement (bias-free this generation — Qwen2's
    qkv biases are gone) plus per-head RMSNorm on q and k before rotary
    (`GPT(qk_norm=True)`, one [head_dim] scale each shared across heads)
    and a decoupled head_dim. Delegates the weight mapping to
    `llama_from_hf` and adds the two norm scales per layer."""
    cfg = hf_model.config
    model, params = llama_from_hf(hf_model, dtype=dtype)
    model = model.clone(qk_norm=True)
    sd = hf_model.state_dict()
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}.self_attn."
        attn = params["decoder"][f"block_{i}"]["attn"]
        attn["q_norm"] = {"scale": _np(sd[h + "q_norm.weight"])}
        attn["k_norm"] = {"scale": _np(sd[h + "k_norm.weight"])}
    return model, params


@_one_layout
def qwen3_to_hf(model, params):
    """A transformers Qwen3ForCausalLM carrying `params` — the inverse of
    `qwen3_from_hf`: the LLaMA-style state dict plus the per-layer
    q_norm/k_norm scales."""
    import transformers

    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "swiglu" or model.use_bias
            or model.qkv_bias or not model.qk_norm
            or model.embed_scale is not None or model.head_bias
            or model.norm_style != "pre" or model.rope_dim is not None
            or model.sliding_window is not None):
        raise NotImplementedError(
            "qwen3_to_hf requires the Qwen3 arrangement (LLaMA-style "
            "bias-free blocks with per-head q/k RMSNorm) — models without "
            "qk_norm export via llama_to_hf"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    cfg = transformers.Qwen3Config(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=model.num_kv_heads or heads,
        intermediate_size=model.mlp_dim, head_dim=hd,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        rope_scaling=_rope_scaling_dict(model.rope_scaling),
        rms_norm_eps=model.ln_eps,
        tie_word_embeddings=model.tie_embeddings,
        attention_bias=False, attention_dropout=0.0,
        use_sliding_window=False,
    )
    hf = transformers.Qwen3ForCausalLM(cfg)
    sd = _llama_style_sd(model, params)
    dec = params["decoder"]
    for i in range(model.depth):
        a = dec[f"block_{i}"]["attn"]
        h = f"model.layers.{i}.self_attn."
        sd[h + "q_norm.weight"] = _t(a["q_norm"]["scale"])
        sd[h + "k_norm.weight"] = _t(a["k_norm"]["scale"])
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def phi_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers PhiForCausalLM.

    The Phi arrangement: PARALLEL blocks (one LayerNorm feeds attention
    and MLP side by side — `GPT(norm_style='parallel')`), partial rotary
    (`rope_dim = partial_rotary_factor * head_dim`), tanh-gelu MLP,
    biases everywhere including the untied lm_head (`head_bias=True`)."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if getattr(cfg, "rope_scaling", None):
        raise NotImplementedError(
            f"rope_scaling={cfg.rope_scaling!r} is not supported; "
            f"converting would produce silently wrong logits — only plain "
            f"rope_theta Phi checkpoints convert today"
        )
    if bool(getattr(cfg, "qk_layernorm", False)):
        raise NotImplementedError(
            "qk_layernorm=True Phi checkpoints are not supported (the "
            "per-head q/k norms would be silently dropped)"
        )
    if getattr(cfg, "hidden_act", None) not in ("gelu_new", None):
        raise NotImplementedError(
            f"hidden_act {cfg.hidden_act!r} is not supported (expected "
            f"Phi's gelu_new, which our tanh-gelu Mlp matches exactly)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    kv = cfg.num_key_value_heads
    rope_dim = int(getattr(cfg, "partial_rotary_factor", 1.0) * hd)
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        rope_dim=None if rope_dim == hd else rope_dim,
        num_kv_heads=kv,
        norm="layer",
        norm_style="parallel",
        mlp_act="gelu",
        use_bias=True,
        tie_embeddings=False,
        head_bias=True,
        ln_eps=cfg.layer_norm_eps,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}final_layernorm.weight"],
                         "bias": sd[f"{pre}final_layernorm.bias"]},
        },
        "lm_head": {"kernel": sd["lm_head.weight"].T,
                    "bias": sd["lm_head.bias"]},
    }
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        params["decoder"][f"block_{i}"] = {
            # parallel blocks have ONE norm: input_layernorm -> ln_attn
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"],
                        "bias": sd[h + "input_layernorm.bias"]},
            "attn": {
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd),
                          "bias": sd[h + "self_attn.q_proj.bias"]
                          .reshape(heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, kv, hd),
                        "bias": sd[h + "self_attn.k_proj.bias"]
                        .reshape(kv, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, kv, hd),
                          "bias": sd[h + "self_attn.v_proj.bias"]
                          .reshape(kv, hd)},
                "out": {"kernel": sd[h + "self_attn.dense.weight"].T
                        .reshape(heads, hd, hidden),
                        "bias": sd[h + "self_attn.dense.bias"]},
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "mlp.fc1.weight"].T,
                        "bias": sd[h + "mlp.fc1.bias"]},
                "fc2": {"kernel": sd[h + "mlp.fc2.weight"].T,
                        "bias": sd[h + "mlp.fc2.bias"]},
            },
        }
    return model, params


def neox_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers GPTNeoXForCausalLM (the Pythia
    family).

    The NeoX arrangement: parallel residual with SEPARATE attention/MLP
    LayerNorms (`norm_style='parallel2'`; use_parallel_residual=False
    checkpoints map to plain 'pre'), 25%-partial rotary
    (`rope_dim = rotary_pct * head_dim`), biased projections, untied
    bias-free embed_out head. The fused query_key_value weight is
    PER-HEAD interleaved ([heads, 3, head_dim, hidden]) — de-interleaved
    here into the three projection kernels.

    Known approximation: NeoX runs exact erf-gelu; our Mlp uses the
    tanh approximation (~1e-3 activation delta, same as the BERT
    converter — the logit-match test bounds it)."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if getattr(cfg, "rope_scaling", None):
        raise NotImplementedError(
            f"rope_scaling={cfg.rope_scaling!r} is not supported; only "
            f"plain rotary_emb_base checkpoints convert today"
        )
    if getattr(cfg, "hidden_act", "gelu") not in ("gelu", "gelu_new",
                                                  "gelu_pytorch_tanh"):
        raise NotImplementedError(
            f"hidden_act {cfg.hidden_act!r} is not supported (expected a "
            f"gelu variant)"
        )
    if not bool(getattr(cfg, "attention_bias", True)):
        raise NotImplementedError(
            "attention_bias=False NeoX checkpoints are not supported (the "
            "converter maps the biased arrangement every Pythia release "
            "ships)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    rope_dim = int(hd * cfg.rotary_pct)
    tied = bool(getattr(cfg, "tie_word_embeddings", False))
    if tied:
        raise NotImplementedError(
            "tied-embedding NeoX checkpoints are not supported (every "
            "Pythia release unties embed_out); the tied head would drop "
            "embed_out.weight silently"
        )
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(getattr(cfg, "rotary_emb_base", 10_000.0)),
        rope_dim=None if rope_dim == hd else rope_dim,
        norm="layer",
        norm_style=("parallel2" if cfg.use_parallel_residual else "pre"),
        mlp_act="gelu",
        use_bias=True,
        tie_embeddings=False,
        ln_eps=cfg.layer_norm_eps,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "gpt_neox." if any(k.startswith("gpt_neox.") for k in sd) else ""
    if "embed_out.weight" not in sd:
        raise NotImplementedError(
            "pass a GPTNeoXForCausalLM (with its embed_out head); a bare "
            "GPTNeoXModel has no LM head to map"
        )
    params = {
        "wte": {"embedding": sd[f"{pre}embed_in.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}final_layer_norm.weight"],
                         "bias": sd[f"{pre}final_layer_norm.bias"]},
        },
        "lm_head": {"kernel": sd["embed_out.weight"].T},
    }
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        # [3H, H] rows are per-head interleaved: head h's q, then k, then v
        qkv_w = sd[h + "attention.query_key_value.weight"].reshape(
            heads, 3, hd, hidden
        )
        qkv_b = sd[h + "attention.query_key_value.bias"].reshape(
            heads, 3, hd
        )

        def proj(j):
            # [heads, hd, hidden] -> in-major [hidden, heads, hd]
            return {"kernel": qkv_w[:, j].transpose(2, 0, 1),
                    "bias": qkv_b[:, j]}

        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"],
                        "bias": sd[h + "input_layernorm.bias"]},
            "ln_mlp": {"scale": sd[h + "post_attention_layernorm.weight"],
                       "bias": sd[h + "post_attention_layernorm.bias"]},
            "attn": {
                "query": proj(0),
                "key": proj(1),
                "value": proj(2),
                "out": {"kernel": sd[h + "attention.dense.weight"].T
                        .reshape(heads, hd, hidden),
                        "bias": sd[h + "attention.dense.bias"]},
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "mlp.dense_h_to_4h.weight"].T,
                        "bias": sd[h + "mlp.dense_h_to_4h.bias"]},
                "fc2": {"kernel": sd[h + "mlp.dense_4h_to_h.weight"].T,
                        "bias": sd[h + "mlp.dense_4h_to_h.bias"]},
            },
        }
    return model, params


def bigcode_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers GPTBigCodeForCausalLM (the
    StarCoder family): the GPT-2 arrangement (learned positions,
    LayerNorm, tanh-gelu — exact for gelu_pytorch_tanh — tied head,
    biased projections) with MULTI-QUERY attention; the fused c_attn
    packs [q (H) | k (kv*hd) | v (kv*hd)] rows, split here into the
    three projection kernels."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if not bool(getattr(cfg, "scale_attn_weights", True)):
        raise NotImplementedError(
            "scale_attn_weights=False checkpoints are not supported (our "
            "attention always scales by 1/sqrt(head_dim))"
        )
    if getattr(cfg, "activation_function",
               "gelu_pytorch_tanh") not in ("gelu_pytorch_tanh",
                                            "gelu_new"):
        # exact-erf 'gelu' would convert with a silent ~1e-3 drift; the
        # tanh variants match our Mlp exactly
        raise NotImplementedError(
            f"activation_function {cfg.activation_function!r} is not "
            f"supported (expected the tanh-gelu variants "
            f"gelu_pytorch_tanh/gelu_new, which our Mlp matches exactly)"
        )
    heads = cfg.n_head
    hidden = cfg.n_embd
    hd = hidden // heads
    kv = 1 if cfg.multi_query else heads
    mlp_dim = cfg.n_inner if cfg.n_inner is not None else 4 * hidden
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.n_layer,
        num_heads=heads,
        mlp_dim=mlp_dim,
        max_position=cfg.n_positions,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        num_kv_heads=kv,
        ln_eps=cfg.layer_norm_epsilon,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    params = {
        "wte": {"embedding": sd[f"{pre}wte.weight"]},
        "wpe": {"embedding": sd[f"{pre}wpe.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}ln_f.weight"],
                         "bias": sd[f"{pre}ln_f.bias"]},
        },
    }
    for i in range(cfg.n_layer):
        h = f"{pre}h.{i}."
        # torch Linear [out, in] -> in-major, then split. The two fused
        # layouts differ: multi_query packs flat [Q (H) | K (hd) | V (hd)]
        # blocks; classic MHA interleaves PER HEAD ([q_h | k_h | v_h] for
        # each head — the .view(heads, 3*hd) split in the HF forward)
        w = sd[h + "attn.c_attn.weight"].T
        b = sd[h + "attn.c_attn.bias"]
        if cfg.multi_query:
            qw, kw, vw = np.split(w, [hidden, hidden + kv * hd], axis=1)
            qb, kb, vb = np.split(b, [hidden, hidden + kv * hd])
        else:
            w4 = w.reshape(hidden, heads, 3, hd)
            b3 = b.reshape(heads, 3, hd)
            qw, kw, vw = w4[:, :, 0], w4[:, :, 1], w4[:, :, 2]
            qb, kb, vb = b3[:, 0], b3[:, 1], b3[:, 2]
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "ln_1.weight"],
                        "bias": sd[h + "ln_1.bias"]},
            "ln_mlp": {"scale": sd[h + "ln_2.weight"],
                       "bias": sd[h + "ln_2.bias"]},
            "attn": {
                "query": {"kernel": qw.reshape(hidden, heads, hd),
                          "bias": qb.reshape(heads, hd)},
                "key": {"kernel": kw.reshape(hidden, kv, hd),
                        "bias": kb.reshape(kv, hd)},
                "value": {"kernel": vw.reshape(hidden, kv, hd),
                          "bias": vb.reshape(kv, hd)},
                "out": {"kernel": sd[h + "attn.c_proj.weight"].T
                        .reshape(heads, hd, hidden),
                        "bias": sd[h + "attn.c_proj.bias"]},
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "mlp.c_fc.weight"].T,
                        "bias": sd[h + "mlp.c_fc.bias"]},
                "fc2": {"kernel": sd[h + "mlp.c_proj.weight"].T,
                        "bias": sd[h + "mlp.c_proj.bias"]},
            },
        }
    return model, params


def opt_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers OPTForCausalLM.

    The OPT arrangement: pre-LN blocks, relu MLP, learned positions with
    the legacy offset-2 table — handled at conversion by SLICING the
    first two embedding rows off (position i uses HF row i+2; our
    0-based lookup then hits the identical vector, no model knob) —
    biased projections, tied head, final LayerNorm. Projected-embedding
    checkpoints (word_embed_proj_dim != hidden, e.g. opt-350m, which is
    also the only post-LN release) are refused."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if cfg.word_embed_proj_dim != cfg.hidden_size:
        raise NotImplementedError(
            f"word_embed_proj_dim {cfg.word_embed_proj_dim} != hidden "
            f"{cfg.hidden_size}: projected-embedding OPT checkpoints "
            f"(opt-350m) are not supported"
        )
    if not bool(getattr(cfg, "do_layer_norm_before", True)):
        raise NotImplementedError(
            "do_layer_norm_before=False (post-LN OPT) is not supported"
        )
    if bool(getattr(cfg, "_remove_final_layer_norm", False)):
        raise NotImplementedError(
            "_remove_final_layer_norm=True (pre-release metaseq "
            "conversions) is not supported — the checkpoint has no "
            "final LayerNorm to map"
        )
    if not bool(getattr(cfg, "enable_bias", True)) or not bool(
            getattr(cfg, "layer_norm_elementwise_affine", True)):
        raise NotImplementedError(
            "bias-free / non-affine-LN OPT variants are not supported"
        )
    if getattr(cfg, "activation_function", "relu") != "relu":
        raise NotImplementedError(
            f"activation_function {cfg.activation_function!r} is not "
            f"supported (OPT releases use relu)"
        )
    if not bool(getattr(cfg, "tie_word_embeddings", True)):
        raise NotImplementedError(
            "untied OPT checkpoints are not supported (lm_head.weight "
            "would be silently dropped)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.ffn_dim,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        mlp_act="relu",
        tie_embeddings=True,
        ln_eps=1e-5,  # torch nn.LayerNorm default, what OPT runs
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = ("model.decoder."
           if any(k.startswith("model.decoder.") for k in sd)
           else "decoder." if any(k.startswith("decoder.") for k in sd)
           else "")
    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        # drop the legacy offset rows: HF looks up row i+2 for position i
        "wpe": {"embedding": sd[f"{pre}embed_positions.weight"][2:]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}final_layer_norm.weight"],
                         "bias": sd[f"{pre}final_layer_norm.bias"]},
        },
    }
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "self_attn_layer_norm.weight"],
                        "bias": sd[h + "self_attn_layer_norm.bias"]},
            "ln_mlp": {"scale": sd[h + "final_layer_norm.weight"],
                       "bias": sd[h + "final_layer_norm.bias"]},
            "attn": {
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd),
                          "bias": sd[h + "self_attn.q_proj.bias"]
                          .reshape(heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, heads, hd),
                        "bias": sd[h + "self_attn.k_proj.bias"]
                        .reshape(heads, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, heads, hd),
                          "bias": sd[h + "self_attn.v_proj.bias"]
                          .reshape(heads, hd)},
                "out": {"kernel": sd[h + "self_attn.out_proj.weight"].T
                        .reshape(heads, hd, hidden),
                        "bias": sd[h + "self_attn.out_proj.bias"]},
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "fc1.weight"].T,
                        "bias": sd[h + "fc1.bias"]},
                "fc2": {"kernel": sd[h + "fc2.weight"].T,
                        "bias": sd[h + "fc2.bias"]},
            },
        }
    return model, params


def bert_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(Bert, params) from a transformers BertForMaskedLM (or BertModel —
    then the MLM head params initialize to the identity transform)."""
    import jax.numpy as jnp

    from tfde_tpu.models.bert import Bert

    cfg = hf_model.config
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    model = Bert(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        type_vocab_size=cfg.type_vocab_size,
        dropout_rate=0.0,
        pad_vocab=False,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        ln_eps=cfg.layer_norm_eps,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = "bert." if any(k.startswith("bert.") for k in sd) else ""

    def lin_kernel(name, shape):
        # torch.nn.Linear stores [out, in]; our kernels are in-major
        return sd[name].T.reshape(shape)

    params = {
        "embeddings": {
            "word": {"embedding": sd[f"{pre}embeddings.word_embeddings.weight"]},
            "position": {
                "embedding": sd[f"{pre}embeddings.position_embeddings.weight"]
            },
            "token_type": {
                "embedding": sd[f"{pre}embeddings.token_type_embeddings.weight"]
            },
            "ln": {"scale": sd[f"{pre}embeddings.LayerNorm.weight"],
                   "bias": sd[f"{pre}embeddings.LayerNorm.bias"]},
        },
        "encoder": {},
    }
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}encoder.layer.{i}."
        params["encoder"][f"block_{i}"] = {
            "attn": {
                "query": {
                    "kernel": lin_kernel(h + "attention.self.query.weight",
                                         (hidden, heads, hd)),
                    "bias": sd[h + "attention.self.query.bias"].reshape(
                        heads, hd),
                },
                "key": {
                    "kernel": lin_kernel(h + "attention.self.key.weight",
                                         (hidden, heads, hd)),
                    "bias": sd[h + "attention.self.key.bias"].reshape(
                        heads, hd),
                },
                "value": {
                    "kernel": lin_kernel(h + "attention.self.value.weight",
                                         (hidden, heads, hd)),
                    "bias": sd[h + "attention.self.value.bias"].reshape(
                        heads, hd),
                },
                "out": {
                    "kernel": lin_kernel(h + "attention.output.dense.weight",
                                         (heads, hd, hidden)),
                    "bias": sd[h + "attention.output.dense.bias"],
                },
            },
            "ln_attn": {
                "scale": sd[h + "attention.output.LayerNorm.weight"],
                "bias": sd[h + "attention.output.LayerNorm.bias"],
            },
            "mlp": {
                "fc1": {"kernel": lin_kernel(h + "intermediate.dense.weight",
                                             (hidden, cfg.intermediate_size)),
                        "bias": sd[h + "intermediate.dense.bias"]},
                "fc2": {"kernel": lin_kernel(h + "output.dense.weight",
                                             (cfg.intermediate_size, hidden)),
                        "bias": sd[h + "output.dense.bias"]},
            },
            "ln_mlp": {"scale": sd[h + "output.LayerNorm.weight"],
                       "bias": sd[h + "output.LayerNorm.bias"]},
        }
    if "cls.predictions.transform.dense.weight" in sd:
        params["mlm_dense"] = {
            "kernel": sd["cls.predictions.transform.dense.weight"].T,
            "bias": sd["cls.predictions.transform.dense.bias"],
        }
        params["mlm_ln"] = {
            "scale": sd["cls.predictions.transform.LayerNorm.weight"],
            "bias": sd["cls.predictions.transform.LayerNorm.bias"],
        }
        params["mlm_bias"] = sd["cls.predictions.bias"]
    else:
        # bare BertModel: identity transform + zero bias keeps the MLM head
        # well-defined (logits = embeddings . hidden)
        params["mlm_dense"] = {"kernel": np.eye(hidden, dtype=np.float32),
                               "bias": np.zeros(hidden, np.float32)}
        params["mlm_ln"] = {"scale": np.ones(hidden, np.float32),
                            "bias": np.zeros(hidden, np.float32)}
        params["mlm_bias"] = np.zeros(cfg.vocab_size, np.float32)
    return model, params


def mixtral_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers MixtralForCausalLM — the routed
    sparse-MoE LLaMA: every layer's MLP is a top-k gated expert mixture
    (w1=gate, w3=up, w2=down per expert, silu-gated), attention/norms are
    the LLaMA arrangement.

    Maps to GPT(num_experts=E, moe_every=1, mlp_act='swiglu',
    use_bias=False) over models/moe.MoEMlp with experts_gate beside
    experts_fc1/fc2. Routing parity: both sides softmax the full router
    logits, take top-k, and renormalize the kept gates; Mixtral drops NO
    tokens, so conversion pins `moe_capacity_factor = E / k` — per-group
    capacity C = m (every token could route to one expert), making the
    converted forward exact at the cost of an O(m^2 E) dispatch one-hot.
    Fine-tuning configs can lower the factor; serving parity keeps it."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = getattr(cfg, "head_dim", None) or hidden // heads
    kv = cfg.num_key_value_heads
    e = cfg.num_local_experts
    k = cfg.num_experts_per_tok
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        head_dim=None if hd == hidden // heads else hd,
        mlp_dim=cfg.intermediate_size,
        max_position=cfg.max_position_embeddings,
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(cfg.rope_theta),
        rope_scaling=_rope_scaling_tuple(
            getattr(cfg, "rope_scaling", None),
            max_position=cfg.max_position_embeddings,
        ),
        num_kv_heads=kv,
        use_bias=False,
        norm="rms",
        mlp_act="swiglu",
        num_experts=e,
        moe_every=1,
        experts_per_token=k,
        moe_capacity_factor=float(e) / k,
        sliding_window=getattr(cfg, "sliding_window", None),
        tie_embeddings=bool(getattr(cfg, "tie_word_embeddings", False)),
        ln_eps=cfg.rms_norm_eps,
    )
    sd = {k_: _np(v) for k_, v in hf_model.state_dict().items()}
    pre = "model." if any(k_.startswith("model.") for k_ in sd) else ""
    params = {
        "wte": {"embedding": sd[f"{pre}embed_tokens.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}norm.weight"]},
        },
    }
    if not model.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}layers.{i}."
        moe_pre = h + "block_sparse_moe."
        params["decoder"][f"block_{i}"] = {
            "ln_attn": {"scale": sd[h + "input_layernorm.weight"]},
            "ln_mlp": {"scale": sd[h + "post_attention_layernorm.weight"]},
            "attn": {
                "query": {"kernel": sd[h + "self_attn.q_proj.weight"].T
                          .reshape(hidden, heads, hd)},
                "key": {"kernel": sd[h + "self_attn.k_proj.weight"].T
                        .reshape(hidden, kv, hd)},
                "value": {"kernel": sd[h + "self_attn.v_proj.weight"].T
                          .reshape(hidden, kv, hd)},
                "out": {"kernel": sd[h + "self_attn.o_proj.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "moe": {
                "router": {"kernel": sd[moe_pre + "gate.weight"].T},
                # per-expert [f, d] torch Linears stack to [E, d, f]/[E, f, d]
                "experts_gate": np.stack(
                    [sd[moe_pre + f"experts.{j}.w1.weight"].T
                     for j in range(e)]
                ),
                "experts_fc1": np.stack(
                    [sd[moe_pre + f"experts.{j}.w3.weight"].T
                     for j in range(e)]
                ),
                "experts_fc2": np.stack(
                    [sd[moe_pre + f"experts.{j}.w2.weight"].T
                     for j in range(e)]
                ),
            },
        }
    return model, params


@_one_layout
def mixtral_to_hf(model, params):
    """A transformers MixtralForCausalLM carrying `params` — the inverse
    of `mixtral_from_hf`: expert stacks unstack into per-expert w1/w2/w3
    Linears, the router transposes back to gate.weight."""
    import transformers

    e = model.num_experts
    k = model.experts_per_token
    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "swiglu" or model.use_bias
            or e <= 0 or model.moe_every != 1
            or getattr(model, "qk_norm", False)
            or model.qkv_bias or model.head_bias
            or model.embed_scale is not None
            or model.norm_style != "pre" or model.rope_dim is not None):
        raise NotImplementedError(
            "mixtral_to_hf requires the Mixtral arrangement (LLaMA-style "
            "attention/norms with every layer's MLP routed, bias-free "
            "swiglu experts) — dense models export via llama_to_hf"
        )
    if model.moe_capacity_factor < float(e) / k:
        # HF Mixtral has no capacity concept: it computes EVERY token. A
        # model fine-tuned with drops learned around them — exporting it
        # as drop-free would silently change its logits.
        raise NotImplementedError(
            f"moe_capacity_factor {model.moe_capacity_factor} < E/k = "
            f"{float(e) / k}: this model can drop overflow tokens, which "
            f"HF Mixtral (capacity-free) cannot express — raise the "
            f"factor to E/k (exact) before exporting"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    kv = model.num_kv_heads or heads
    cfg = transformers.MixtralConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=kv, intermediate_size=model.mlp_dim,
        num_local_experts=e, num_experts_per_tok=k, head_dim=hd,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        rope_scaling=_rope_scaling_dict(model.rope_scaling),
        rms_norm_eps=model.ln_eps,
        sliding_window=model.sliding_window,
        tie_word_embeddings=model.tie_embeddings,
        attention_dropout=0.0, router_aux_loss_coef=0.0,
    )
    hf = transformers.MixtralForCausalLM(cfg)

    def moe_mlp_fn(sd, h, blk):
        moe = blk["moe"]
        moe_pre = h + "block_sparse_moe."
        sd[moe_pre + "gate.weight"] = _t(
            np.asarray(moe["router"]["kernel"]).T
        )
        gate_s = np.asarray(moe["experts_gate"])
        up_s = np.asarray(moe["experts_fc1"])
        down_s = np.asarray(moe["experts_fc2"])
        for j in range(e):
            sd[moe_pre + f"experts.{j}.w1.weight"] = _t(gate_s[j].T)
            sd[moe_pre + f"experts.{j}.w3.weight"] = _t(up_s[j].T)
            sd[moe_pre + f"experts.{j}.w2.weight"] = _t(down_s[j].T)

    sd = _llama_style_sd(model, params, mlp_fn=moe_mlp_fn)
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def falcon_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(GPT, params) from a transformers FalconForCausalLM.

    Three Falcon arrangements, all expressible with existing GPT knobs:
    the 7B shape (multi_query + parallel_attn: ONE LayerNorm feeds
    attention and MLP — `norm_style='parallel'`, kv=1), the 40B/180B
    shape (new_decoder_architecture: separate ln_attn/ln_mlp parallel
    residual — `norm_style='parallel2'`, grouped kv), and the sequential
    pre-LN shape (parallel_attn=False). All are rope + bias-free Linears
    beside biased LayerNorms (GPT(use_bias=False) keeps LN affine+bias —
    the Phi/NeoX convention this model zoo already relies on).

    The fused query_key_value weight unpacks per arrangement: the 40B
    form groups [g q-heads | k | v] per KV head; multi-query packs flat
    [Q (H) | k | v]; classic MHA interleaves per head. alibi checkpoints
    (falcon-rw) and bias=True Linears are refused — no GPT knob expresses
    them. Falcon's MLP runs erf-gelu; this framework's gelu is the tanh
    approximation — a documented ~1e-3 bounded logit delta, the same as
    bert_from_hf."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    cfg = hf_model.config
    if bool(getattr(cfg, "alibi", False)):
        raise NotImplementedError(
            "alibi Falcon checkpoints (falcon-rw) are not supported — "
            "the position machinery here is rope/learned, not alibi"
        )
    if bool(getattr(cfg, "bias", False)):
        raise NotImplementedError(
            "bias=True Falcon variants are not supported (the mainline "
            "7B/40B/180B releases are bias-free)"
        )
    if getattr(cfg, "rope_scaling", None):
        raise NotImplementedError(
            f"rope_scaling {cfg.rope_scaling!r} is not supported — "
            f"converting would silently apply unscaled rotary embeddings"
        )
    act = getattr(cfg, "activation", "gelu")
    if act not in ("gelu", "gelu_new", "gelu_pytorch_tanh"):
        raise NotImplementedError(
            f"activation {act!r} is not supported (Falcon releases use "
            f"gelu; converting would silently change the math)"
        )
    heads = cfg.num_attention_heads
    hidden = cfg.hidden_size
    hd = hidden // heads
    new_arch = bool(getattr(cfg, "new_decoder_architecture", False))
    # LN arrangement: the 40B/180B new-arch form carries TWO parallel LNs
    # (parallel2) UNLESS num_ln_in_parallel_attn == 1 (the Falcon2-11B
    # form: grouped kv but ONE shared LN — 'parallel'); pre-new-arch
    # models have one LN when parallel_attn, two sequential otherwise
    if new_arch:
        kv = cfg.num_kv_heads
        two_ln = getattr(cfg, "num_ln_in_parallel_attn", None) != 1
        norm_style = "parallel2" if two_ln else "parallel"
    else:
        kv = 1 if bool(getattr(cfg, "multi_query", True)) else heads
        norm_style = ("parallel" if getattr(cfg, "parallel_attn", True)
                      else "pre")
    model = GPT(
        vocab_size=cfg.vocab_size,
        hidden_size=hidden,
        depth=cfg.num_hidden_layers,
        num_heads=heads,
        mlp_dim=getattr(cfg, "ffn_hidden_size", None) or 4 * hidden,
        max_position=getattr(cfg, "max_position_embeddings", 2048),
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        position="rope",
        rope_theta=float(getattr(cfg, "rope_theta", 10_000.0)),
        num_kv_heads=kv,
        use_bias=False,
        norm="layer",
        norm_style=norm_style,
        tie_embeddings=bool(getattr(cfg, "tie_word_embeddings", True)),
        ln_eps=cfg.layer_norm_epsilon,
    )
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    pre = ("transformer."
           if any(k.startswith("transformer.") for k in sd) else "")
    params = {
        "wte": {"embedding": sd[f"{pre}word_embeddings.weight"]},
        "decoder": {
            "ln_final": {"scale": sd[f"{pre}ln_f.weight"],
                         "bias": sd[f"{pre}ln_f.bias"]},
        },
    }
    if not model.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}
    g = heads // kv
    for i in range(cfg.num_hidden_layers):
        h = f"{pre}h.{i}."
        w = sd[h + "self_attention.query_key_value.weight"].T  # [in, out]
        if new_arch:
            # [hidden, kv, g+2, hd]: per-KV-group [g q | k | v]
            w4 = w.reshape(hidden, kv, g + 2, hd)
            qw = w4[:, :, :g].reshape(hidden, heads, hd)
            kw = w4[:, :, g]
            vw = w4[:, :, g + 1]
        elif kv == 1:
            # flat [Q (H) | k (hd) | v (hd)]
            qw, kw, vw = np.split(w, [hidden, hidden + hd], axis=1)
            qw = qw.reshape(hidden, heads, hd)
            kw = kw.reshape(hidden, 1, hd)
            vw = vw.reshape(hidden, 1, hd)
        else:
            # classic MHA: per-head [q_h | k_h | v_h] interleave
            w4 = w.reshape(hidden, heads, 3, hd)
            qw, kw, vw = w4[:, :, 0], w4[:, :, 1], w4[:, :, 2]
        blk = {
            "attn": {
                "query": {"kernel": qw},
                "key": {"kernel": kw},
                "value": {"kernel": vw},
                "out": {"kernel": sd[h + "self_attention.dense.weight"].T
                        .reshape(heads, hd, hidden)},
            },
            "mlp": {
                "fc1": {"kernel": sd[h + "mlp.dense_h_to_4h.weight"].T},
                "fc2": {"kernel": sd[h + "mlp.dense_4h_to_h.weight"].T},
            },
        }
        if norm_style == "parallel2":
            blk["ln_attn"] = {"scale": sd[h + "ln_attn.weight"],
                              "bias": sd[h + "ln_attn.bias"]}
            blk["ln_mlp"] = {"scale": sd[h + "ln_mlp.weight"],
                             "bias": sd[h + "ln_mlp.bias"]}
        else:
            # 'parallel' (one LN — 7B and the new-arch Falcon2-11B form
            # alike) and 'pre' both read input_layernorm
            blk["ln_attn"] = {"scale": sd[h + "input_layernorm.weight"],
                              "bias": sd[h + "input_layernorm.bias"]}
            if norm_style == "pre":
                blk["ln_mlp"] = {
                    "scale": sd[h + "post_attention_layernorm.weight"],
                    "bias": sd[h + "post_attention_layernorm.bias"],
                }
        params["decoder"][f"block_{i}"] = blk
    return model, params


def t5_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(T5, params) from a transformers T5ForConditionalGeneration.

    The T5 arrangement (models/t5.py): shared embedding, relative-position
    -bias attention (UNSCALED scores), T5-RMSNorm (plain w, no 1+ fold),
    bias-free projections with an inner attention dim decoupled from
    d_model, relu (v1.0) or gated tanh-gelu (v1.1) MLPs, tied head with
    the d_model^-0.5 logit rescale (v1.0) or an untied lm_head (v1.1).
    The per-stack shared bias table (HF stores it in block 0's attention;
    this model stores it at the stack level — the same single table) maps
    across directly."""
    import jax.numpy as jnp

    from tfde_tpu.models.t5 import T5

    cfg = hf_model.config
    gated = bool(getattr(cfg, "is_gated_act", False))
    act = getattr(cfg, "dense_act_fn", "relu")
    if gated:
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            raise NotImplementedError(
                f"gated dense_act_fn {act!r} is not supported (expected "
                f"the v1.1 tanh-gelu, which models/t5.py 'geglu' matches "
                f"exactly)"
            )
        mlp_act = "geglu"
    else:
        if act != "relu":
            raise NotImplementedError(
                f"dense_act_fn {act!r} is not supported (T5 v1.0 uses "
                f"relu)"
            )
        mlp_act = "relu"
    heads = cfg.num_heads
    model = T5(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.d_model,
        depth=cfg.num_layers,
        decoder_depth=cfg.num_decoder_layers,
        num_heads=heads,
        head_dim=cfg.d_kv,
        mlp_dim=cfg.d_ff,
        mlp_act=mlp_act,
        num_buckets=cfg.relative_attention_num_buckets,
        max_distance=getattr(cfg, "relative_attention_max_distance", 128),
        tie_embeddings=bool(cfg.tie_word_embeddings),
        dropout_rate=0.0,
        dtype=dtype if dtype is not None else jnp.bfloat16,
        ln_eps=cfg.layer_norm_epsilon,
        pad_id=cfg.pad_token_id,
    )
    hidden, hd = cfg.d_model, cfg.d_kv
    sd = {k: _np(v) for k, v in hf_model.state_dict().items()}
    params: dict = {"shared": {"embedding": sd["shared.weight"]}}
    if not model.tie_embeddings:
        params["lm_head"] = {"kernel": sd["lm_head.weight"].T}

    def attn_tree(pre: str) -> dict:
        return {
            "query": {"kernel": sd[pre + "q.weight"].T
                      .reshape(hidden, heads, hd)},
            "key": {"kernel": sd[pre + "k.weight"].T
                    .reshape(hidden, heads, hd)},
            "value": {"kernel": sd[pre + "v.weight"].T
                      .reshape(hidden, heads, hd)},
            "out": {"kernel": sd[pre + "o.weight"].T
                    .reshape(heads, hd, hidden)},
        }

    def mlp_tree(pre: str) -> dict:
        if gated:
            t = {"gate": {"kernel": sd[pre + "wi_0.weight"].T},
                 "fc1": {"kernel": sd[pre + "wi_1.weight"].T}}
        else:
            t = {"fc1": {"kernel": sd[pre + "wi.weight"].T}}
        t["fc2"] = {"kernel": sd[pre + "wo.weight"].T}
        return t

    for stack, n_layers, cross in (("encoder", cfg.num_layers, False),
                                   ("decoder", cfg.num_decoder_layers,
                                    True)):
        tree: dict = {
            "rel_bias": sd[
                f"{stack}.block.0.layer.0.SelfAttention"
                f".relative_attention_bias.weight"
            ],
            "ln_final": {
                "scale": sd[f"{stack}.final_layer_norm.weight"]
            },
        }
        mlp_layer = 2 if cross else 1
        for i in range(n_layers):
            h = f"{stack}.block.{i}."
            blk = {
                "ln_attn": {"scale": sd[h + "layer.0.layer_norm.weight"]},
                "attn": attn_tree(h + "layer.0.SelfAttention."),
                f"ln_mlp": {
                    "scale": sd[h + f"layer.{mlp_layer}.layer_norm.weight"]
                },
                "mlp": mlp_tree(h + f"layer.{mlp_layer}.DenseReluDense."),
            }
            if cross:
                blk["ln_cross"] = {
                    "scale": sd[h + "layer.1.layer_norm.weight"]
                }
                blk["cross_attn"] = attn_tree(h + "layer.1.EncDecAttention.")
            tree[f"block_{i}"] = blk
        params[stack] = tree
    return model, params


def bert_classifier_from_hf(hf_model, dtype=None) -> Tuple[object, dict]:
    """(BertClassifier, params) from a transformers
    BertForSequenceClassification — the fine-tuned-classifier import path.
    Delegates the encoder mapping to `bert_from_hf` (identical layout under
    the 'bert.' prefix) and adds the pooler + classification head."""
    import dataclasses

    from tfde_tpu.models.bert import BertClassifier

    cfg = hf_model.config
    bert, mlm_params = bert_from_hf(hf_model, dtype=dtype)
    # one cfg->constructor mapping site: rebuild from the Bert that
    # bert_from_hf returned, so the classifier config can never drift
    # from the encoder params grafted below
    shared = {
        f.name: getattr(bert, f.name)
        for f in dataclasses.fields(BertClassifier)
        if f.name not in ("parent", "name", "num_labels")
        and hasattr(bert, f.name)
    }
    model = BertClassifier(num_labels=cfg.num_labels, **shared)
    sd = hf_model.state_dict()
    params = {
        "embeddings": mlm_params["embeddings"],
        "encoder": mlm_params["encoder"],
        "pooler": {"kernel": _np(sd["bert.pooler.dense.weight"]).T,
                   "bias": _np(sd["bert.pooler.dense.bias"])},
        "classifier": {"kernel": _np(sd["classifier.weight"]).T,
                       "bias": _np(sd["classifier.bias"])},
    }
    return model, params


# --------------------------------------------------------------------------
# Reverse conversion: this framework's params -> transformers checkpoints.
# The OTHER half of the migration story: fine-tune here (full, LoRA-merged,
# distilled), deploy anywhere transformers runs. Exact inverses of the
# *_from_hf mappings above, verified by round-trip state-dict equality and
# logit matching (tests/test_convert.py).
# --------------------------------------------------------------------------


def _t(a) -> "object":
    import torch

    return torch.from_numpy(np.ascontiguousarray(np.asarray(a, np.float32)))


@_one_layout
def gpt2_to_hf(model, params):
    """A transformers GPT2LMHeadModel carrying `params` — the inverse of
    `gpt2_from_hf`. Requires the GPT-2 arrangement (learned positions,
    gelu MLP, LayerNorm, tied head, biased projections)."""
    import transformers

    if (model.position != "learned" or model.norm != "layer"
            or model.mlp_act != "gelu" or not model.tie_embeddings
            or not model.use_bias or model.sliding_window is not None
            or model.head_dim is not None or model.embed_scale is not None
            or model.qkv_bias or model.head_bias
            or model.norm_style != "pre" or model.rope_dim is not None
            or (model.num_kv_heads not in (None, model.num_heads))):
        raise NotImplementedError(
            "gpt2_to_hf requires the GPT-2 arrangement (learned positions, "
            "LayerNorm, gelu, tied head, uniformly biased projections, "
            "classic MHA, unscaled embeddings, full causal attention) — "
            "other families export via llama_to_hf or stay native"
        )
    cfg = transformers.GPT2Config(
        vocab_size=model.vocab_size, n_embd=model.hidden_size,
        n_layer=model.depth, n_head=model.num_heads,
        n_inner=model.mlp_dim, n_positions=model.max_position,
        layer_norm_epsilon=model.ln_eps,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    hf = transformers.GPT2LMHeadModel(cfg)
    hidden = model.hidden_size
    sd = {}
    sd["transformer.wte.weight"] = _t(params["wte"]["embedding"])
    sd["transformer.wpe.weight"] = _t(params["wpe"]["embedding"])
    dec = params["decoder"]
    sd["transformer.ln_f.weight"] = _t(dec["ln_final"]["scale"])
    sd["transformer.ln_f.bias"] = _t(dec["ln_final"]["bias"])
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"transformer.h.{i}."
        sd[h + "ln_1.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "ln_1.bias"] = _t(blk["ln_attn"]["bias"])
        sd[h + "ln_2.weight"] = _t(blk["ln_mlp"]["scale"])
        sd[h + "ln_2.bias"] = _t(blk["ln_mlp"]["bias"])
        a = blk["attn"]
        # Conv1D layout is [in, out]: stack q/k/v back into [H, 3H]
        c_attn_w = np.concatenate(
            [np.asarray(a[n]["kernel"]).reshape(hidden, hidden)
             for n in ("query", "key", "value")], axis=1,
        )
        c_attn_b = np.concatenate(
            [np.asarray(a[n]["bias"]).reshape(hidden)
             for n in ("query", "key", "value")]
        )
        sd[h + "attn.c_attn.weight"] = _t(c_attn_w)
        sd[h + "attn.c_attn.bias"] = _t(c_attn_b)
        sd[h + "attn.c_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(hidden, hidden)
        )
        sd[h + "attn.c_proj.bias"] = _t(a["out"]["bias"])
        sd[h + "mlp.c_fc.weight"] = _t(blk["mlp"]["fc1"]["kernel"])
        sd[h + "mlp.c_fc.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "mlp.c_proj.weight"] = _t(blk["mlp"]["fc2"]["kernel"])
        sd[h + "mlp.c_proj.bias"] = _t(blk["mlp"]["fc2"]["bias"])
    sd["lm_head.weight"] = sd["transformer.wte.weight"]
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    # attn.bias buffers (causal masks) are regenerated by HF; everything
    # else must load
    missing = [k for k in missing if not k.endswith("attn.bias")
               and not k.endswith("attn.masked_bias")]
    unexpected = list(unexpected)
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={unexpected}")
    hf.eval()
    return hf


def _llama_style_sd(model, params, mlp_fn=None) -> dict:
    """The transformers state dict for a LLaMA-arranged decoder
    (model.layers.* keys) — shared by `llama_to_hf` (LLaMA/Mistral/Qwen2),
    `gemma_to_hf` (which un-folds the zero-centered norms on top), and
    `mixtral_to_hf` (which swaps the dense-MLP writer for the routed
    expert stacks via `mlp_fn(sd, layer_prefix, block_params)`)."""
    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    kv = model.num_kv_heads or heads
    sd = {}
    sd["model.embed_tokens.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]
    sd["model.norm.weight"] = _t(dec["ln_final"]["scale"])
    if not model.tie_embeddings:
        sd["lm_head.weight"] = _t(np.asarray(params["lm_head"]["kernel"]).T)
    else:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"model.layers.{i}."
        sd[h + "input_layernorm.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "post_attention_layernorm.weight"] = _t(
            blk["ln_mlp"]["scale"]
        )
        a = blk["attn"]
        sd[h + "self_attn.q_proj.weight"] = _t(
            np.asarray(a["query"]["kernel"]).reshape(hidden, heads * hd).T
        )
        sd[h + "self_attn.k_proj.weight"] = _t(
            np.asarray(a["key"]["kernel"]).reshape(hidden, kv * hd).T
        )
        sd[h + "self_attn.v_proj.weight"] = _t(
            np.asarray(a["value"]["kernel"]).reshape(hidden, kv * hd).T
        )
        sd[h + "self_attn.o_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        if model.qkv_bias:
            sd[h + "self_attn.q_proj.bias"] = _t(
                np.asarray(a["query"]["bias"]).reshape(heads * hd)
            )
            sd[h + "self_attn.k_proj.bias"] = _t(
                np.asarray(a["key"]["bias"]).reshape(kv * hd)
            )
            sd[h + "self_attn.v_proj.bias"] = _t(
                np.asarray(a["value"]["bias"]).reshape(kv * hd)
            )
        if mlp_fn is not None:
            mlp_fn(sd, h, blk)
        else:
            sd[h + "mlp.gate_proj.weight"] = _t(
                np.asarray(blk["mlp"]["gate"]["kernel"]).T
            )
            sd[h + "mlp.up_proj.weight"] = _t(
                np.asarray(blk["mlp"]["fc1"]["kernel"]).T
            )
            sd[h + "mlp.down_proj.weight"] = _t(
                np.asarray(blk["mlp"]["fc2"]["kernel"]).T
            )
    return sd


@_one_layout
def llama_to_hf(model, params):
    """A transformers LlamaForCausalLM (or Qwen2 twin when
    model.qkv_bias) carrying `params` — the inverse of `llama_from_hf` /
    `qwen2_from_hf`. Mistral-style `sliding_window` models export as
    MistralForCausalLM with the window in the config."""
    import transformers

    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "swiglu" or model.use_bias
            or model.embed_scale is not None or model.head_bias
            or getattr(model, "qk_norm", False)
            or model.norm_style != "pre" or model.rope_dim is not None):
        raise NotImplementedError(
            "llama_to_hf requires the LLaMA arrangement (rope — full, not "
            "partial — RMSNorm, swiglu, bias-free pre-norm blocks, "
            "unscaled embeddings, bias-free head); Gemma/Phi-style models "
            "stay native"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    kv = model.num_kv_heads or heads
    common = dict(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=kv, intermediate_size=model.mlp_dim,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        rope_scaling=_rope_scaling_dict(model.rope_scaling),
        rms_norm_eps=model.ln_eps,
        tie_word_embeddings=model.tie_embeddings, attention_dropout=0.0,
    )
    if model.qkv_bias:
        if model.sliding_window is not None:
            raise NotImplementedError(
                "qkv_bias + sliding_window has no faithful transformers "
                "twin here (Qwen2 windows are per-layer) — exporting "
                "without the window would silently widen attention"
            )
        cfg = transformers.Qwen2Config(use_sliding_window=False,
                                       head_dim=hd, **common)
        hf = transformers.Qwen2ForCausalLM(cfg)
    elif model.sliding_window is not None:
        cfg = transformers.MistralConfig(
            sliding_window=int(model.sliding_window), head_dim=hd, **common
        )
        hf = transformers.MistralForCausalLM(cfg)
    else:
        cfg = transformers.LlamaConfig(head_dim=hd, **common)
        hf = transformers.LlamaForCausalLM(cfg)
    sd = _llama_style_sd(model, params)
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def gemma_to_hf(model, params):
    """A transformers GemmaForCausalLM carrying `params` — the inverse of
    `gemma_from_hf`: the LLaMA-style state dict with the two Gemma folds
    undone — the stored RMSNorm scales carry the zero-centered `1 + w`
    fold, so the exported weights are `scale - 1` (the HF module computes
    `x * (1 + w)`); the sqrt(hidden) embedding scale and tanh-gelu gate
    are config-level and checked, not transformed."""
    import transformers

    if (model.position != "rope" or model.norm != "rms"
            or model.mlp_act != "geglu" or model.use_bias
            or not model.tie_embeddings or model.qkv_bias
            or getattr(model, "qk_norm", False)
            or model.head_bias or model.sliding_window is not None
            or model.norm_style != "pre" or model.rope_dim is not None
            or model.embed_scale is None
            or abs(model.embed_scale - model.hidden_size ** 0.5) > 1e-6):
        raise NotImplementedError(
            "gemma_to_hf requires the Gemma arrangement (full rope, "
            "RMSNorm, geglu, bias-free pre-norm blocks, tied head, "
            "sqrt(hidden)-scaled embeddings) — LLaMA-style models export "
            "via llama_to_hf"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = model.head_dim or hidden // heads
    cfg = transformers.GemmaConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=model.num_kv_heads or heads,
        intermediate_size=model.mlp_dim, head_dim=hd,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        # re-emit frequency scaling: dropping it would export unscaled
        # rope — silently wrong logits at long context
        rope_scaling=_rope_scaling_dict(model.rope_scaling),
        rms_norm_eps=model.ln_eps,
        tie_word_embeddings=True, attention_dropout=0.0,
        # our geglu gate IS the tanh approximation — the exact match
        hidden_activation="gelu_pytorch_tanh",
    )
    hf = transformers.GemmaForCausalLM(cfg)
    sd = _llama_style_sd(model, params)
    for k in list(sd):
        # un-fold 1+w on every RMSNorm scale (2 per layer + final)
        if k.endswith("layernorm.weight") or k == "model.norm.weight":
            sd[k] = sd[k] - 1.0
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def phi_to_hf(model, params):
    """A transformers PhiForCausalLM carrying `params` — the inverse of
    `phi_from_hf` (parallel blocks, partial rotary, biased everything)."""
    import transformers

    if (model.position != "rope" or model.norm != "layer"
            or model.mlp_act != "gelu" or model.tie_embeddings
            or not model.use_bias or not model.head_bias
            or model.norm_style != "parallel"
            or model.sliding_window is not None
            or model.embed_scale is not None
            or model.head_dim is not None):
        raise NotImplementedError(
            "phi_to_hf requires the Phi arrangement (parallel blocks, "
            "LayerNorm, gelu, biased projections and head, untied) — "
            "other families export via gpt2_to_hf/llama_to_hf or stay "
            "native"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads  # head_dim is None past the guard
    kv = model.num_kv_heads or heads
    cfg = transformers.PhiConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_key_value_heads=kv, intermediate_size=model.mlp_dim,
        max_position_embeddings=model.max_position,
        rope_theta=model.rope_theta,
        partial_rotary_factor=(model.rope_dim or hd) / hd,
        layer_norm_eps=model.ln_eps, tie_word_embeddings=False,
        attention_dropout=0.0, embd_pdrop=0.0, resid_pdrop=0.0,
    )
    hf = transformers.PhiForCausalLM(cfg)
    sd = {}
    sd["model.embed_tokens.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]
    sd["model.final_layernorm.weight"] = _t(dec["ln_final"]["scale"])
    sd["model.final_layernorm.bias"] = _t(dec["ln_final"]["bias"])
    sd["lm_head.weight"] = _t(np.asarray(params["lm_head"]["kernel"]).T)
    sd["lm_head.bias"] = _t(params["lm_head"]["bias"])
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"model.layers.{i}."
        sd[h + "input_layernorm.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "input_layernorm.bias"] = _t(blk["ln_attn"]["bias"])
        a = blk["attn"]
        for ours, theirs, n in (("query", "q_proj", heads),
                                ("key", "k_proj", kv),
                                ("value", "v_proj", kv)):
            sd[h + f"self_attn.{theirs}.weight"] = _t(
                np.asarray(a[ours]["kernel"]).reshape(hidden, n * hd).T
            )
            sd[h + f"self_attn.{theirs}.bias"] = _t(
                np.asarray(a[ours]["bias"]).reshape(n * hd)
            )
        sd[h + "self_attn.dense.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "self_attn.dense.bias"] = _t(a["out"]["bias"])
        sd[h + "mlp.fc1.weight"] = _t(np.asarray(blk["mlp"]["fc1"]["kernel"]).T)
        sd[h + "mlp.fc1.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "mlp.fc2.weight"] = _t(np.asarray(blk["mlp"]["fc2"]["kernel"]).T)
        sd[h + "mlp.fc2.bias"] = _t(blk["mlp"]["fc2"]["bias"])
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def neox_to_hf(model, params):
    """A transformers GPTNeoXForCausalLM carrying `params` — the inverse
    of `neox_from_hf`: the three projection kernels re-interleave into
    the per-head fused query_key_value weight."""
    import transformers

    if (model.position != "rope" or model.norm != "layer"
            or model.mlp_act != "gelu" or model.tie_embeddings
            or not model.use_bias or model.head_bias
            or model.norm_style not in ("parallel2", "pre")
            or model.sliding_window is not None
            or model.embed_scale is not None
            or model.head_dim is not None
            or (model.num_kv_heads not in (None, model.num_heads))):
        raise NotImplementedError(
            "neox_to_hf requires the NeoX arrangement (parallel2/pre "
            "blocks, LayerNorm, gelu, biased projections, untied "
            "bias-free head, MHA) — other families export via their own "
            "inverses or stay native"
        )
    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads  # head_dim is None past the guard
    cfg = transformers.GPTNeoXConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        intermediate_size=model.mlp_dim,
        max_position_embeddings=model.max_position,
        rotary_emb_base=model.rope_theta,
        rotary_pct=(model.rope_dim or hd) / hd,
        use_parallel_residual=model.norm_style == "parallel2",
        layer_norm_eps=model.ln_eps, tie_word_embeddings=False,
        attention_dropout=0.0, hidden_dropout=0.0,
        # our Mlp 'gelu' IS the tanh approximation — export the matching
        # activation so round-trip logits stay exact (plain 'gelu' in HF
        # is the erf form, a silent ~1e-3 drift)
        hidden_act="gelu_pytorch_tanh",
    )
    hf = transformers.GPTNeoXForCausalLM(cfg)
    sd = {}
    sd["gpt_neox.embed_in.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]
    sd["gpt_neox.final_layer_norm.weight"] = _t(dec["ln_final"]["scale"])
    sd["gpt_neox.final_layer_norm.bias"] = _t(dec["ln_final"]["bias"])
    sd["embed_out.weight"] = _t(np.asarray(params["lm_head"]["kernel"]).T)
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"gpt_neox.layers.{i}."
        sd[h + "input_layernorm.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "input_layernorm.bias"] = _t(blk["ln_attn"]["bias"])
        sd[h + "post_attention_layernorm.weight"] = _t(
            blk["ln_mlp"]["scale"]
        )
        sd[h + "post_attention_layernorm.bias"] = _t(blk["ln_mlp"]["bias"])
        a = blk["attn"]
        # [hidden, heads, hd] kernels -> per-head interleaved [3H, hidden]
        qkv_w = np.stack(
            [np.asarray(a[n]["kernel"]).transpose(1, 2, 0)
             for n in ("query", "key", "value")], axis=1,
        )  # [heads, 3, hd, hidden]
        qkv_b = np.stack(
            [np.asarray(a[n]["bias"]) for n in ("query", "key", "value")],
            axis=1,
        )  # [heads, 3, hd]
        sd[h + "attention.query_key_value.weight"] = _t(
            qkv_w.reshape(3 * hidden, hidden)
        )
        sd[h + "attention.query_key_value.bias"] = _t(
            qkv_b.reshape(3 * hidden)
        )
        sd[h + "attention.dense.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "attention.dense.bias"] = _t(a["out"]["bias"])
        sd[h + "mlp.dense_h_to_4h.weight"] = _t(
            np.asarray(blk["mlp"]["fc1"]["kernel"]).T
        )
        sd[h + "mlp.dense_h_to_4h.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "mlp.dense_4h_to_h.weight"] = _t(
            np.asarray(blk["mlp"]["fc2"]["kernel"]).T
        )
        sd[h + "mlp.dense_4h_to_h.bias"] = _t(blk["mlp"]["fc2"]["bias"])
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k
               and "attention.bias" not in k
               and "masked_bias" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def bigcode_to_hf(model, params):
    """A transformers GPTBigCodeForCausalLM carrying `params` — the
    inverse of `bigcode_from_hf`: q/k/v kernels re-fuse into c_attn with
    the layout the HF forward expects (flat [Q|K|V] blocks under
    multi-query; per-head interleave under classic MHA)."""
    import transformers

    heads = model.num_heads
    kv = model.num_kv_heads or heads
    if (model.position != "learned" or model.norm != "layer"
            or model.mlp_act != "gelu" or not model.tie_embeddings
            or not model.use_bias or model.sliding_window is not None
            or model.head_dim is not None or model.embed_scale is not None
            or model.qkv_bias or model.head_bias
            or model.norm_style != "pre" or model.rope_dim is not None
            or kv not in (1, heads)):
        raise NotImplementedError(
            "bigcode_to_hf requires the StarCoder arrangement (learned "
            "positions, LayerNorm, gelu, tied head, biased projections, "
            "multi-query or classic MHA) — other families export via "
            "their own inverses or stay native"
        )
    hidden = model.hidden_size
    hd = hidden // heads
    multi_query = kv == 1 and heads > 1
    cfg = transformers.GPTBigCodeConfig(
        vocab_size=model.vocab_size, n_embd=hidden, n_layer=model.depth,
        n_head=heads, n_inner=model.mlp_dim,
        n_positions=model.max_position, multi_query=multi_query,
        layer_norm_epsilon=model.ln_eps, scale_attn_weights=True,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        # our Mlp gelu IS the tanh approximation — exact for this export
        activation_function="gelu_pytorch_tanh",
    )
    hf = transformers.GPTBigCodeForCausalLM(cfg)
    sd = {}
    sd["transformer.wte.weight"] = _t(params["wte"]["embedding"])
    sd["transformer.wpe.weight"] = _t(params["wpe"]["embedding"])
    dec = params["decoder"]
    sd["transformer.ln_f.weight"] = _t(dec["ln_final"]["scale"])
    sd["transformer.ln_f.bias"] = _t(dec["ln_final"]["bias"])
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"transformer.h.{i}."
        sd[h + "ln_1.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "ln_1.bias"] = _t(blk["ln_attn"]["bias"])
        sd[h + "ln_2.weight"] = _t(blk["ln_mlp"]["scale"])
        sd[h + "ln_2.bias"] = _t(blk["ln_mlp"]["bias"])
        a = blk["attn"]
        qw = np.asarray(a["query"]["kernel"])   # [hidden, heads, hd]
        kw = np.asarray(a["key"]["kernel"])     # [hidden, kv, hd]
        vw = np.asarray(a["value"]["kernel"])
        qb = np.asarray(a["query"]["bias"])     # [heads, hd]
        kb = np.asarray(a["key"]["bias"])       # [kv, hd]
        vb = np.asarray(a["value"]["bias"])
        if multi_query:
            # flat [Q (H) | K (hd) | V (hd)] rows, exactly the split
            # bigcode_from_hf undoes
            w = np.concatenate(
                [qw.reshape(hidden, hidden), kw.reshape(hidden, kv * hd),
                 vw.reshape(hidden, kv * hd)], axis=1,
            )
            b = np.concatenate(
                [qb.reshape(hidden), kb.reshape(kv * hd),
                 vb.reshape(kv * hd)]
            )
        else:
            # classic MHA interleaves per head: [q_h | k_h | v_h] each head
            w = np.stack([qw, kw, vw], axis=2).reshape(hidden, 3 * hidden)
            b = np.stack([qb, kb, vb], axis=1).reshape(3 * hidden)
        sd[h + "attn.c_attn.weight"] = _t(w.T)
        sd[h + "attn.c_attn.bias"] = _t(b)
        sd[h + "attn.c_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "attn.c_proj.bias"] = _t(a["out"]["bias"])
        sd[h + "mlp.c_fc.weight"] = _t(
            np.asarray(blk["mlp"]["fc1"]["kernel"]).T
        )
        sd[h + "mlp.c_fc.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "mlp.c_proj.weight"] = _t(
            np.asarray(blk["mlp"]["fc2"]["kernel"]).T
        )
        sd[h + "mlp.c_proj.bias"] = _t(blk["mlp"]["fc2"]["bias"])
    sd["lm_head.weight"] = sd["transformer.wte.weight"]
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    # '.attn.bias' (with the dot) is the causal-mask buffer ONLY — a bare
    # 'attn.bias' suffix would also swallow the real c_attn.bias weight
    missing = [k for k in missing if not k.endswith(".attn.bias")
               and not k.endswith("masked_bias")]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def opt_to_hf(model, params):
    """A transformers OPTForCausalLM carrying `params` — the inverse of
    `opt_from_hf`. The legacy offset-2 position table is rebuilt by
    PREPENDING two zero rows (opt_from_hf sliced the originals off; HF
    only reaches rows 0-1 for left-padded positions, which attention
    masks exclude — unpadded logits are exact)."""
    import transformers

    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads
    if (model.position != "learned" or model.norm != "layer"
            or model.mlp_act != "relu" or not model.tie_embeddings
            or not model.use_bias or model.sliding_window is not None
            or model.head_dim is not None or model.embed_scale is not None
            or model.qkv_bias or model.head_bias
            or model.norm_style != "pre" or model.rope_dim is not None
            or (model.num_kv_heads not in (None, heads))
            or abs(model.ln_eps - 1e-5) > 1e-12):
        raise NotImplementedError(
            "opt_to_hf requires the OPT arrangement (learned positions, "
            "pre-LN with eps 1e-5, relu MLP, tied head, biased "
            "projections, classic MHA) — other families export via their "
            "own inverses or stay native"
        )
    cfg = transformers.OPTConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        ffn_dim=model.mlp_dim, max_position_embeddings=model.max_position,
        word_embed_proj_dim=hidden, do_layer_norm_before=True,
        activation_function="relu", tie_word_embeddings=True,
        dropout=0.0, attention_dropout=0.0, enable_bias=True,
        layer_norm_elementwise_affine=True,
    )
    hf = transformers.OPTForCausalLM(cfg)
    sd = {}
    pre = "model.decoder."
    sd[pre + "embed_tokens.weight"] = _t(params["wte"]["embedding"])
    wpe = np.asarray(params["wpe"]["embedding"], np.float32)
    sd[pre + "embed_positions.weight"] = _t(
        np.concatenate([np.zeros((2, hidden), np.float32), wpe], axis=0)
    )
    dec = params["decoder"]
    sd[pre + "final_layer_norm.weight"] = _t(dec["ln_final"]["scale"])
    sd[pre + "final_layer_norm.bias"] = _t(dec["ln_final"]["bias"])
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"{pre}layers.{i}."
        sd[h + "self_attn_layer_norm.weight"] = _t(blk["ln_attn"]["scale"])
        sd[h + "self_attn_layer_norm.bias"] = _t(blk["ln_attn"]["bias"])
        sd[h + "final_layer_norm.weight"] = _t(blk["ln_mlp"]["scale"])
        sd[h + "final_layer_norm.bias"] = _t(blk["ln_mlp"]["bias"])
        a = blk["attn"]
        for ours, theirs in (("query", "q_proj"), ("key", "k_proj"),
                             ("value", "v_proj")):
            sd[h + f"self_attn.{theirs}.weight"] = _t(
                np.asarray(a[ours]["kernel"]).reshape(hidden, hidden).T
            )
            sd[h + f"self_attn.{theirs}.bias"] = _t(
                np.asarray(a[ours]["bias"]).reshape(hidden)
            )
        sd[h + "self_attn.out_proj.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "self_attn.out_proj.bias"] = _t(a["out"]["bias"])
        sd[h + "fc1.weight"] = _t(np.asarray(blk["mlp"]["fc1"]["kernel"]).T)
        sd[h + "fc1.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "fc2.weight"] = _t(np.asarray(blk["mlp"]["fc2"]["kernel"]).T)
        sd[h + "fc2.bias"] = _t(blk["mlp"]["fc2"]["bias"])
    sd["lm_head.weight"] = sd[pre + "embed_tokens.weight"]
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def _bert_encoder_sd(model, params, pre: str) -> dict:
    """The transformers embeddings+encoder state dict (under prefix `pre`)
    for a converted Bert/BertClassifier — the shared inverse of the
    encoder mapping in `bert_from_hf`."""
    heads = model.num_heads
    hidden = model.hidden_size
    hd = hidden // heads
    emb = params["embeddings"]
    sd = {
        pre + "embeddings.word_embeddings.weight":
            _t(emb["word"]["embedding"]),
        pre + "embeddings.position_embeddings.weight":
            _t(emb["position"]["embedding"]),
        pre + "embeddings.token_type_embeddings.weight":
            _t(emb["token_type"]["embedding"]),
        pre + "embeddings.LayerNorm.weight": _t(emb["ln"]["scale"]),
        pre + "embeddings.LayerNorm.bias": _t(emb["ln"]["bias"]),
    }
    for i in range(model.depth):
        blk = params["encoder"][f"block_{i}"]
        h = f"{pre}encoder.layer.{i}."
        a = blk["attn"]
        for ours, theirs in (("query", "attention.self.query"),
                             ("key", "attention.self.key"),
                             ("value", "attention.self.value")):
            sd[h + theirs + ".weight"] = _t(
                np.asarray(a[ours]["kernel"]).reshape(hidden, hidden).T
            )
            sd[h + theirs + ".bias"] = _t(
                np.asarray(a[ours]["bias"]).reshape(hidden)
            )
        sd[h + "attention.output.dense.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "attention.output.dense.bias"] = _t(a["out"]["bias"])
        sd[h + "attention.output.LayerNorm.weight"] = _t(
            blk["ln_attn"]["scale"]
        )
        sd[h + "attention.output.LayerNorm.bias"] = _t(
            blk["ln_attn"]["bias"]
        )
        sd[h + "intermediate.dense.weight"] = _t(
            np.asarray(blk["mlp"]["fc1"]["kernel"]).T
        )
        sd[h + "intermediate.dense.bias"] = _t(blk["mlp"]["fc1"]["bias"])
        sd[h + "output.dense.weight"] = _t(
            np.asarray(blk["mlp"]["fc2"]["kernel"]).T
        )
        sd[h + "output.dense.bias"] = _t(blk["mlp"]["fc2"]["bias"])
        sd[h + "output.LayerNorm.weight"] = _t(blk["ln_mlp"]["scale"])
        sd[h + "output.LayerNorm.bias"] = _t(blk["ln_mlp"]["bias"])
    return sd


def _bert_config(model, **extra):
    import transformers

    return transformers.BertConfig(
        vocab_size=model.vocab_size, hidden_size=model.hidden_size,
        num_hidden_layers=model.depth,
        num_attention_heads=model.num_heads,
        intermediate_size=model.mlp_dim,
        max_position_embeddings=model.max_position,
        type_vocab_size=model.type_vocab_size,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        layer_norm_eps=model.ln_eps,
        # our encoder's gelu is the tanh approximation; exporting the
        # matching activation keeps native-vs-exported logits exact (a
        # checkpoint imported from erf-gelu BERT re-exports with ~1e-3
        # drift vs its origin — the same delta bert_from_hf documents)
        hidden_act="gelu_pytorch_tanh",
        **extra,
    )


def _check_bert_exportable(model, fn: str) -> None:
    if getattr(model, "pad_vocab", False) or getattr(model, "fused_qkv",
                                                     False):
        raise NotImplementedError(
            f"{fn} requires the transformers-compatible arrangement "
            f"(pad_vocab=False — a padded vocab widens the logit table — "
            f"and unfused per-projection qkv kernels)"
        )


def bert_to_hf(model, params):
    """A transformers BertForMaskedLM carrying `params` — the inverse of
    `bert_from_hf` (encoder + MLM transform head, tied decoder)."""
    import transformers

    _check_bert_exportable(model, "bert_to_hf")
    sd = _bert_encoder_sd(model, params, "bert.")
    sd["cls.predictions.transform.dense.weight"] = _t(
        np.asarray(params["mlm_dense"]["kernel"]).T
    )
    sd["cls.predictions.transform.dense.bias"] = _t(
        params["mlm_dense"]["bias"]
    )
    sd["cls.predictions.transform.LayerNorm.weight"] = _t(
        params["mlm_ln"]["scale"]
    )
    sd["cls.predictions.transform.LayerNorm.bias"] = _t(
        params["mlm_ln"]["bias"]
    )
    sd["cls.predictions.bias"] = _t(params["mlm_bias"])
    sd["cls.predictions.decoder.weight"] = sd[
        "bert.embeddings.word_embeddings.weight"
    ]
    sd["cls.predictions.decoder.bias"] = sd["cls.predictions.bias"]
    hf = transformers.BertForMaskedLM(_bert_config(model))
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "position_ids" not in k
               and "token_type_ids" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def bert_classifier_to_hf(model, params):
    """A transformers BertForSequenceClassification carrying `params` —
    the inverse of `bert_classifier_from_hf` (encoder + pooler +
    classification head)."""
    import transformers

    _check_bert_exportable(model, "bert_classifier_to_hf")
    sd = _bert_encoder_sd(model, params, "bert.")
    sd["bert.pooler.dense.weight"] = _t(
        np.asarray(params["pooler"]["kernel"]).T
    )
    sd["bert.pooler.dense.bias"] = _t(params["pooler"]["bias"])
    sd["classifier.weight"] = _t(
        np.asarray(params["classifier"]["kernel"]).T
    )
    sd["classifier.bias"] = _t(params["classifier"]["bias"])
    hf = transformers.BertForSequenceClassification(
        _bert_config(model, num_labels=model.num_labels)
    )
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "position_ids" not in k
               and "token_type_ids" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


@_one_layout
def falcon_to_hf(model, params):
    """A transformers FalconForCausalLM carrying `params` — the inverse of
    `falcon_from_hf`: q/k/v kernels re-fuse into query_key_value per
    arrangement (grouped 40B form, flat multi-query, per-head MHA)."""
    import transformers

    heads = model.num_heads
    kv = model.num_kv_heads or heads
    if (model.position != "rope" or model.norm != "layer"
            or model.mlp_act != "gelu" or model.use_bias
            or model.qkv_bias or model.head_bias
            or model.sliding_window is not None
            or model.head_dim is not None or model.embed_scale is not None
            or model.rope_dim is not None
            or model.norm_style not in ("parallel", "parallel2", "pre")):
        raise NotImplementedError(
            "falcon_to_hf requires the Falcon arrangement (full rope, "
            "biased LayerNorms beside bias-free projections, gelu MLP, "
            "parallel/parallel2/pre blocks) — other families export via "
            "their own inverses or stay native"
        )
    hidden = model.hidden_size
    hd = hidden // heads
    # arrangement: parallel2 -> the 40B two-LN new arch; parallel with
    # grouped kv -> the Falcon2-11B new arch with ONE LN
    # (num_ln_in_parallel_attn=1); parallel/pre with kv in (1, heads) ->
    # the pre-new-arch forms
    new_arch = (model.norm_style == "parallel2"
                or (model.norm_style == "parallel"
                    and kv not in (1, heads)))
    if model.norm_style == "pre" and kv not in (1, heads):
        raise NotImplementedError(
            "grouped kv with sequential pre-LN blocks has no Falcon twin"
        )
    cfg = transformers.FalconConfig(
        vocab_size=model.vocab_size, hidden_size=hidden,
        num_hidden_layers=model.depth, num_attention_heads=heads,
        num_kv_heads=kv, new_decoder_architecture=new_arch,
        multi_query=(not new_arch and kv == 1),
        parallel_attn=model.norm_style != "pre",
        num_ln_in_parallel_attn=(
            1 if new_arch and model.norm_style == "parallel" else None
        ),
        alibi=False, bias=False,
        layer_norm_epsilon=model.ln_eps,
        rope_theta=model.rope_theta,
        max_position_embeddings=model.max_position,
        tie_word_embeddings=model.tie_embeddings,
        ffn_hidden_size=model.mlp_dim,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    hf = transformers.FalconForCausalLM(cfg)
    sd = {}
    pre = "transformer."
    sd[pre + "word_embeddings.weight"] = _t(params["wte"]["embedding"])
    dec = params["decoder"]
    sd[pre + "ln_f.weight"] = _t(dec["ln_final"]["scale"])
    sd[pre + "ln_f.bias"] = _t(dec["ln_final"]["bias"])
    sd["lm_head.weight"] = (
        _t(np.asarray(params["lm_head"]["kernel"]).T)
        if not model.tie_embeddings
        else sd[pre + "word_embeddings.weight"]
    )
    g = heads // kv
    for i in range(model.depth):
        blk = dec[f"block_{i}"]
        h = f"{pre}h.{i}."
        a = blk["attn"]
        qw = np.asarray(a["query"]["kernel"])   # [hidden, heads, hd]
        kw = np.asarray(a["key"]["kernel"])     # [hidden, kv, hd]
        vw = np.asarray(a["value"]["kernel"])
        if new_arch:
            w4 = np.concatenate(
                [qw.reshape(hidden, kv, g, hd), kw[:, :, None],
                 vw[:, :, None]], axis=2,
            )  # [hidden, kv, g+2, hd]
            w = w4.reshape(hidden, (kv * (g + 2)) * hd)
        elif kv == 1:
            w = np.concatenate(
                [qw.reshape(hidden, hidden), kw.reshape(hidden, hd),
                 vw.reshape(hidden, hd)], axis=1,
            )
        else:
            w = np.stack([qw, kw, vw], axis=2).reshape(hidden, 3 * hidden)
        sd[h + "self_attention.query_key_value.weight"] = _t(w.T)
        sd[h + "self_attention.dense.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )
        sd[h + "mlp.dense_h_to_4h.weight"] = _t(
            np.asarray(blk["mlp"]["fc1"]["kernel"]).T
        )
        sd[h + "mlp.dense_4h_to_h.weight"] = _t(
            np.asarray(blk["mlp"]["fc2"]["kernel"]).T
        )
        if model.norm_style == "parallel2":
            sd[h + "ln_attn.weight"] = _t(blk["ln_attn"]["scale"])
            sd[h + "ln_attn.bias"] = _t(blk["ln_attn"]["bias"])
            sd[h + "ln_mlp.weight"] = _t(blk["ln_mlp"]["scale"])
            sd[h + "ln_mlp.bias"] = _t(blk["ln_mlp"]["bias"])
        else:
            # one LN: 'parallel' (incl. the new-arch 11B form) and 'pre'
            sd[h + "input_layernorm.weight"] = _t(blk["ln_attn"]["scale"])
            sd[h + "input_layernorm.bias"] = _t(blk["ln_attn"]["bias"])
            if model.norm_style == "pre":
                sd[h + "post_attention_layernorm.weight"] = _t(
                    blk["ln_mlp"]["scale"]
                )
                sd[h + "post_attention_layernorm.bias"] = _t(
                    blk["ln_mlp"]["bias"]
                )
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    missing = [k for k in missing if "rotary_emb" not in k]
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


def t5_to_hf(model, params):
    """A transformers T5ForConditionalGeneration carrying `params` — the
    inverse of `t5_from_hf` (per-stack bias table back into block 0's
    attention, kernels back to [out, in])."""
    import transformers

    if model.mlp_act not in ("relu", "geglu"):
        raise NotImplementedError(
            "t5_to_hf requires the T5 arrangement (relu v1.0 or gated "
            "tanh-gelu v1.1 MLPs) — other activations stay native"
        )
    gated = model.mlp_act == "geglu"
    cfg = transformers.T5Config(
        vocab_size=model.vocab_size, d_model=model.hidden_size,
        d_kv=model.head_dim, d_ff=model.mlp_dim,
        num_layers=model.depth,
        num_decoder_layers=model.decoder_depth or model.depth,
        num_heads=model.num_heads,
        relative_attention_num_buckets=model.num_buckets,
        relative_attention_max_distance=model.max_distance,
        dropout_rate=0.0, layer_norm_epsilon=model.ln_eps,
        feed_forward_proj="gated-gelu" if gated else "relu",
        tie_word_embeddings=model.tie_embeddings,
        pad_token_id=model.pad_id, decoder_start_token_id=model.pad_id,
    )
    hf = transformers.T5ForConditionalGeneration(cfg)
    heads, hd = model.num_heads, model.head_dim
    hidden = model.hidden_size
    sd = {}
    sd["shared.weight"] = _t(params["shared"]["embedding"])
    sd["encoder.embed_tokens.weight"] = sd["shared.weight"]
    sd["decoder.embed_tokens.weight"] = sd["shared.weight"]
    sd["lm_head.weight"] = (
        _t(np.asarray(params["lm_head"]["kernel"]).T)
        if not model.tie_embeddings else sd["shared.weight"]
    )

    def put_attn(pre: str, a: dict) -> None:
        sd[pre + "q.weight"] = _t(
            np.asarray(a["query"]["kernel"]).reshape(hidden, heads * hd).T
        )
        sd[pre + "k.weight"] = _t(
            np.asarray(a["key"]["kernel"]).reshape(hidden, heads * hd).T
        )
        sd[pre + "v.weight"] = _t(
            np.asarray(a["value"]["kernel"]).reshape(hidden, heads * hd).T
        )
        sd[pre + "o.weight"] = _t(
            np.asarray(a["out"]["kernel"]).reshape(heads * hd, hidden).T
        )

    def put_mlp(pre: str, m: dict) -> None:
        if gated:
            sd[pre + "wi_0.weight"] = _t(np.asarray(m["gate"]["kernel"]).T)
            sd[pre + "wi_1.weight"] = _t(np.asarray(m["fc1"]["kernel"]).T)
        else:
            sd[pre + "wi.weight"] = _t(np.asarray(m["fc1"]["kernel"]).T)
        sd[pre + "wo.weight"] = _t(np.asarray(m["fc2"]["kernel"]).T)

    for stack, n_layers, cross in (
        ("encoder", model.depth, False),
        ("decoder", model.decoder_depth or model.depth, True),
    ):
        tree = params[stack]
        sd[f"{stack}.final_layer_norm.weight"] = _t(
            tree["ln_final"]["scale"]
        )
        sd[f"{stack}.block.0.layer.0.SelfAttention"
           f".relative_attention_bias.weight"] = _t(tree["rel_bias"])
        mlp_layer = 2 if cross else 1
        for i in range(n_layers):
            blk = tree[f"block_{i}"]
            h = f"{stack}.block.{i}."
            sd[h + "layer.0.layer_norm.weight"] = _t(
                blk["ln_attn"]["scale"]
            )
            put_attn(h + "layer.0.SelfAttention.", blk["attn"])
            if cross:
                sd[h + "layer.1.layer_norm.weight"] = _t(
                    blk["ln_cross"]["scale"]
                )
                put_attn(h + "layer.1.EncDecAttention.", blk["cross_attn"])
            sd[h + f"layer.{mlp_layer}.layer_norm.weight"] = _t(
                blk["ln_mlp"]["scale"]
            )
            put_mlp(h + f"layer.{mlp_layer}.DenseReluDense.", blk["mlp"])
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    if missing or unexpected:
        raise RuntimeError(f"to_hf mapping drift: missing={missing} "
                           f"unexpected={list(unexpected)}")
    hf.eval()
    return hf


# --------------------------------------------------------------------------
# CLI: python -m tfde_tpu.models.convert <family> <hf_path> <out_dir>
# --------------------------------------------------------------------------

_FAMILIES = {
    "gpt2": ("GPT2LMHeadModel", "gpt2_from_hf"),
    "bert": ("BertForMaskedLM", "bert_from_hf"),
    "llama": ("LlamaForCausalLM", "llama_from_hf"),
    "mistral": ("MistralForCausalLM", "mistral_from_hf"),
    "gemma": ("GemmaForCausalLM", "gemma_from_hf"),
    "qwen2": ("Qwen2ForCausalLM", "qwen2_from_hf"),
    "bert-classifier": ("BertForSequenceClassification",
                        "bert_classifier_from_hf"),
    "phi": ("PhiForCausalLM", "phi_from_hf"),
    "neox": ("GPTNeoXForCausalLM", "neox_from_hf"),
    "bigcode": ("GPTBigCodeForCausalLM", "bigcode_from_hf"),
    "opt": ("OPTForCausalLM", "opt_from_hf"),
    "t5": ("T5ForConditionalGeneration", "t5_from_hf"),
    "falcon": ("FalconForCausalLM", "falcon_from_hf"),
    "mixtral": ("MixtralForCausalLM", "mixtral_from_hf"),
    "qwen3": ("Qwen3ForCausalLM", "qwen3_from_hf"),
    "phi3": ("Phi3ForCausalLM", "phi3_from_hf"),
    "gemma2": ("Gemma2ForCausalLM", "gemma2_from_hf"),
    "qwen2-moe": ("Qwen2MoeForCausalLM", "qwen2moe_from_hf"),
}


def _read_config(artifact_dir: str) -> dict:
    """The artifact's model_config.json as a dict — the one read site."""
    import json

    from tfde_tpu.utils import fs

    with fs.fs_open(fs.join(artifact_dir, "model_config.json"), "r") as f:
        return json.load(f)


def save_converted(model, params, out_dir: str, family: str) -> str:
    """Write (model, params) as a conversion artifact (params.npz +
    model_config.json) — what the forward CLI produces, and what
    `load_converted` / `--reverse` consume. The save half of the artifact
    contract: persist a fine-tuned model (e.g. Estimator.merged_params()
    output on a converted base) so it can be reloaded or exported back to
    transformers later."""
    import dataclasses
    import json

    from tfde_tpu.export.serving import write_params_npz
    from tfde_tpu.utils import fs

    if family not in _FAMILIES:
        raise ValueError(f"unknown family {family!r}; one of "
                         f"{sorted(_FAMILIES)}")
    fs.makedirs(out_dir, exist_ok=True)
    write_params_npz(fs.join(out_dir, "params.npz"), params)
    def _persistable(v) -> bool:
        scalar = (int, float, str, bool, type(None))
        if isinstance(v, scalar):
            return True
        # scalar tuples persist too (rope_scaling); json stores them as
        # lists, which load_converted re-tuples for hashability
        return (isinstance(v, (tuple, list))
                and all(isinstance(x, scalar) for x in v))

    config = {
        f.name: getattr(model, f.name)
        for f in dataclasses.fields(model)
        if f.name not in ("parent", "name")
        and _persistable(getattr(model, f.name))
    }
    config["family"] = family
    config["dtype"] = str(np.dtype(model.dtype))
    with fs.fs_open(fs.join(out_dir, "model_config.json"), "w") as f:
        json.dump(config, f, indent=2)
    return out_dir


def load_converted(artifact_dir: str, dtype=None):
    """(model, params) from a conversion-CLI artifact directory
    (params.npz + model_config.json, written by
    `python -m tfde_tpu.models.convert`). The public loader every
    consumer of converted checkpoints uses — the serving example,
    notebooks, and the CLI round-trip test share this one rebuild path.

    dtype overrides the recorded compute dtype (e.g. jnp.float32 on CPU).
    """
    import io

    import jax.numpy as jnp

    from tfde_tpu.export.serving import _unflatten_params
    from tfde_tpu.utils import fs

    conf = _read_config(artifact_dir)
    family = conf.pop("family")
    recorded = conf.pop("dtype")
    kwargs = {
        # json stores tuples as lists; re-tuple so the rebuilt module's
        # config stays hashable (rope_scaling)
        k: tuple(v) if isinstance(v, list) else v
        for k, v in conf.items()
    }
    kwargs["dtype"] = jnp.dtype(dtype if dtype is not None else recorded)

    from tfde_tpu.models.bert import Bert, BertClassifier
    from tfde_tpu.models.gpt import GPT
    from tfde_tpu.models.t5 import T5

    cls = {"gpt2": GPT, "llama": GPT, "mistral": GPT, "gemma": GPT,
           "qwen2": GPT, "phi": GPT, "neox": GPT, "bigcode": GPT,
           "opt": GPT, "falcon": GPT, "mixtral": GPT, "qwen3": GPT,
           "phi3": GPT, "gemma2": GPT, "qwen2-moe": GPT, "bert": Bert,
           "bert-classifier": BertClassifier, "t5": T5}[family]
    model = cls(**kwargs)
    with fs.fs_open(fs.join(artifact_dir, "params.npz"), "rb") as f:
        z = np.load(io.BytesIO(f.read()))
        params = _unflatten_params({k: z[k] for k in z.files})
    return model, params


def _cli(argv=None) -> str:
    """Convert a local HF checkpoint directory into this framework's
    artifact: <out>/params.npz (flat, the export/serving layout) +
    <out>/model_config.json (the constructor kwargs to rebuild the model).
    Returns the output dir. Offline by construction — `hf_path` is a local
    directory saved with save_pretrained(); nothing is downloaded."""
    import argparse

    parser = argparse.ArgumentParser(
        description="HF checkpoint -> tfde_tpu params (or back, --reverse)",
    )
    parser.add_argument("family", choices=sorted(_FAMILIES))
    parser.add_argument("hf_path", help="local save_pretrained() directory "
                        "(with --reverse: a conversion-artifact dir)")
    parser.add_argument("out_dir")
    parser.add_argument("--reverse", action="store_true",
                        help="artifact dir -> HF save_pretrained() "
                             "checkpoint: deploy a model fine-tuned here "
                             "(full, LoRA-merged, distilled) anywhere "
                             "transformers runs")
    args = parser.parse_args(argv)

    if args.reverse:
        recorded = _read_config(args.hf_path).get("family")
        if recorded != args.family:
            raise SystemExit(
                f"artifact {args.hf_path!r} records family {recorded!r}, "
                f"not {args.family!r} — pass the family the artifact was "
                f"converted as"
            )
        model, params = load_converted(args.hf_path)
        to_hf = {
            "gpt2": gpt2_to_hf, "llama": llama_to_hf,
            "mistral": llama_to_hf, "qwen2": llama_to_hf,
            "gemma": gemma_to_hf, "phi": phi_to_hf, "neox": neox_to_hf,
            "bigcode": bigcode_to_hf, "opt": opt_to_hf,
            "bert": bert_to_hf, "bert-classifier": bert_classifier_to_hf,
            "t5": t5_to_hf, "falcon": falcon_to_hf,
            "mixtral": mixtral_to_hf, "qwen3": qwen3_to_hf,
            "phi3": phi3_to_hf, "gemma2": gemma2_to_hf,
            "qwen2-moe": qwen2moe_to_hf,
        }[args.family]
        hf = to_hf(model, params)
        hf.save_pretrained(args.out_dir)
        print(f"exported {args.family} HF checkpoint -> {args.out_dir}")
        return args.out_dir

    import os

    import transformers

    if not os.path.isdir(args.hf_path):
        raise SystemExit(
            f"{args.hf_path!r} is not a directory — pass a local "
            f"save_pretrained() checkpoint; this CLI never downloads"
        )
    cls_name, fn_name = _FAMILIES[args.family]
    hf = getattr(transformers, cls_name).from_pretrained(
        args.hf_path, local_files_only=True
    )
    hf.eval()
    model, params = globals()[fn_name](hf)
    save_converted(model, params, args.out_dir, args.family)
    print(f"converted {args.family} checkpoint -> {args.out_dir}")
    return args.out_dir


if __name__ == "__main__":
    _cli()
