"""sarvam-105b (sarvamai, `sarvam_mla`), plainly: the forward pass of a
decoder whose every layer is latent (multi-head latent, MLA) attention
followed by a SwiGLU MLP (the first `first_k_dense_replace` layers) or by a
routed layer of SwiGLU experts beside one shared expert (all others), in
straightforward `jax.numpy` and float32: no kernel, no cache, no batching,
no sorting of tokens by expert, and the attention in its NON-ABSORBED form
at every position (keys and values up-projected per head), so that the
program's absorbed decode is checked against other mathematics. It follows
the published `config.json` keys (`hidden_size`, `num_attention_heads`,
`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`rope_theta`, `rope_scaling`, `intermediate_size`, `first_k_dense_replace`,
`moe_intermediate_size`, `num_experts_per_tok`, `num_shared_experts`,
`routed_scaling_factor`, `moe_router_enable_expert_bias`, `rms_norm_eps`,
`vocab_size`, `tie_word_embeddings`).

Per layer l, on the hidden state x [T, hidden], rmsnorm(x) = x / rms(x) * g:

    a = rmsnorm(x; g1)
    q = a Wq [T, heads, nope + rope], split q_nope | q_rope
    [c, k_r] = a Wdkv [T, latent + rope];  c = rmsnorm(c; g_kv)
    q_rope, k_r rotated (theta `rope_theta`, two halves, the yarn
        frequencies of `rope_scaling`: factor, original length, beta fast /
        slow; `mscale / mscale_all_dim` = 1, so the tables are NOT scaled);
        k_r is ONE key for all heads
    [k_nope, v] = c Wukv [T, heads, nope + value]
    scores (q_nope . k_nope + q_rope . k_r) * s,  s = (nope + rope)^-0.5 m^2,
        m = 0.1 mscale_all_dim ln(factor) + 1;  causal softmax in float32
    h = x + concat(P v) Wo
    b = rmsnorm(h; g2)
    l < first_k_dense_replace:  y = h + (silu(b Wg) * (b Wu)) Wd
    otherwise:  s = sigmoid(b Wr) over all published experts in float32;
        the `num_experts_per_tok` largest of s + bias chosen; weights
        `routed_scaling_factor` * s_i / sum over the chosen of s_j (the
        bias never enters a weight);
        y = h + sum over the chosen e HELD HERE of w_e E_e(b) + S(b)
        E_e and the shared S SwiGLU of `moe_intermediate_size` (x
        `num_shared_experts` for S), S added as it is

then a final rmsnorm and an untied head. No bias on any projection.

It imports nothing of the program and takes nothing the program has made.
Weights come from `make_weights(seed, dims)` alone; the driver hands the
same numbers to the program through `to_program_params`.

Departures from the release, each for the comparison's sake:
- **The share.** `dims["held_experts"] = [first, end)` of the published
  experts have weights here; the router and its bias are
  `published_experts` wide and a chosen expert held elsewhere adds nothing,
  here and in the program alike. The vocabulary is the held slice.
- **Readings the config has no key for** (the configuration file's
  `assumed`): `use_qk_norm` is the latent's RMSNorm before the
  up-projection and no norm per head; the router scores by sigmoid and
  normalises over the chosen; no group-limited routing. The rotation is
  in two halves (`rotate_half`); the release's interleaved pairs are a
  permutation of the same arithmetic on seeded weights.
- Weights are drawn from the seed, rounded once to bfloat16 (the
  deployment's dtype) and KEPT in bfloat16 arrays shaped as the program's
  own leaves, so that `to_program_params` only re-nests them and both sides
  hold the same numbers (4.54 G parameters: a float32 copy would not fit
  beside anything); every use upcasts to float32 first, which is exact.
  The spreads (`_SPREAD`) are set so that no part is negligible, the
  routing does not collapse and a near-tie of the router that falls the
  other way on rounding moves the logits less than the arithmetic does.
  The selection bias is drawn NON-zero (`_SPREAD["router_bias"]`): N(0,
  0.015) beside sigmoid scores whose eighth largest of 128 stands at 0.82
  with a slope of 0.15 changes the chosen set of about two tokens in three
  (a zero bias is untested) and leaves the busiest held expert at about
  1.4 of the mean; a release's bias is learnt to LEVEL the load, a seeded
  one tilts it, and at 0.05 the busiest stood at 2.6 (my chip run, PR 40).
- Attention runs `_HEAD_CHUNK` heads and a block of queries at a time and
  the experts one at a time over all positions, so that 32,768 positions
  in float32 fit on a 16 GB chip beside the weights; layers run one jitted
  call each. The arithmetic is the plain one.
- The head is applied at the positions asked for (`rows`): 32,768
  positions of a 65,536-wide head are 8.6 GB of logits.

`precision`: "highest" is the reference (float32, `Precision.HIGHEST`);
"bf16" and "fp8" round both operands of every matrix product first (fp8:
e4m3 under a per-tensor scale). `drop`: None, or a term LEFT OUT of the
arithmetic: "rope_term" (the scores' q_rope . k_r) or "selection_bias"
(the experts are chosen by s alone). Both exist for the controls: the
reference put in the program's place in the nearest precision below the
one the configuration states, or without a term, must come out as not
correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bf16", "fp8")
DROPS = (None, "rope_term", "selection_bias")
DIM_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_hidden_layers", "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "num_shared_experts", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta", "vocab_size")
_QUERY_BLOCK = 256
_HEAD_CHUNK = 8

# standard deviations of the seeded weights by leaf (N(0, 1) times this);
# the leaves not named take 1 / sqrt(fan in) of a normalised input
_SPREAD = {"wte": 1.0, "wo_out": 0.7, "router_logits": 1.0,
           "router_bias": 0.015, "w_down_out": 0.5}
_GAINS = ("ln1_g", "ln2_g", "kv_g", "lnf_g")     # 1 + N(0, 0.1)


def dims_of(cfg: dict) -> dict:
    """The sizes as run, read from the configuration file's top level, the
    yarn scaling as a tuple and the share of the published experts held."""
    if (cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or not cfg["moe_router_enable_expert_bias"]
            or not cfg["use_qk_norm"]
            or cfg["rope_scaling"]["type"] != "deepseek_yarn"
            or cfg["rope_scaling"]["mscale"]
            != cfg["rope_scaling"]["mscale_all_dim"]):
        raise ValueError(
            "this reference computes an untied head, SwiGLU, a selection "
            "bias, the latent's norm and deepseek_yarn with unscaled tables "
            "(mscale = mscale_all_dim)")
    dims = {k: cfg[k] for k in DIM_KEYS}
    scaling = cfg["rope_scaling"]
    dims["yarn"] = (float(scaling["factor"]), float(scaling["beta_fast"]),
                    float(scaling["beta_slow"]),
                    int(scaling["original_max_position_embeddings"]),
                    float(scaling["mscale_all_dim"]))
    dims["published_experts"] = cfg["published"]["num_experts"]
    dims["held_experts"] = tuple(cfg["deployment_share"]["experts"])
    if dims["held_experts"][1] - dims["held_experts"][0] != \
            cfg["num_experts"]:
        raise ValueError("num_experts is not the held range's width")
    return dims


def _frozen(dims: dict) -> tuple:
    return tuple(sorted(dims.items()))


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def routed(dims: dict, layer: int) -> bool:
    return layer >= dims["first_k_dense_replace"]


def layer_shapes(dims: dict, layer: int) -> dict:
    d, h = dims["hidden_size"], dims["num_attention_heads"]
    latent, nope, rope, value = (
        dims["kv_lora_rank"], dims["qk_nope_head_dim"],
        dims["qk_rope_head_dim"], dims["v_head_dim"])
    shapes = {"ln1_g": (d,), "ln2_g": (d,), "wq": (d, h, nope + rope),
              "w_dkv": (d, latent + rope), "kv_g": (latent,),
              "w_ukv": (latent, h, nope + value), "wo": (h, value, d)}
    if not routed(dims, layer):
        f = dims["intermediate_size"]
        return dict(shapes, w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    f, e = dims["moe_intermediate_size"], dims["published_experts"]
    held = dims["held_experts"][1] - dims["held_experts"][0]
    fs = f * dims["num_shared_experts"]
    return dict(shapes, router=(d, e), router_bias=(e,),
                e_gate=(held, d, f), e_up=(held, d, f), e_down=(held, f, d),
                s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))


def num_params(dims: dict) -> int:
    ends = 2 * dims["vocab_size"] * dims["hidden_size"] + dims["hidden_size"]
    return ends + sum(
        math.prod(s) for l in range(dims["num_hidden_layers"])
        for s in layer_shapes(dims, l).values())


def _spread(name: str, shape: tuple, dims: dict) -> float:
    """The standard deviation of leaf `name`: every input is a normalised
    state (rms about 1), so a leaf's fan-in sets what comes out. Scores:
    q, k_nope and k_r entries of std 1 give (nope + rope)^0.5 before the
    scale and m^2 = 1.87 after it, a peaked softmax."""
    if name in ("wte", "router_bias"):
        return _SPREAD[name]
    if name == "wo":
        return _SPREAD["wo_out"] / math.sqrt(shape[0] * shape[1])
    if name == "router":
        return _SPREAD["router_logits"] / math.sqrt(shape[0])
    if name in ("w_down", "e_down", "s_down"):
        return _SPREAD["w_down_out"] / math.sqrt(shape[-2])
    if name in ("e_gate", "e_up"):
        return 1.0 / math.sqrt(shape[1])
    return 1.0 / math.sqrt(shape[0])


def _draw(key, name: str, shape: tuple, dims: dict):
    noise = jax.random.normal(key, shape, jnp.float32)
    x = (1.0 + 0.1 * noise if name in _GAINS
         else _spread(name, shape, dims) * noise)
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dims_key", "layer"))
def _make_layer(key, dims_key, layer):
    dims = dict(dims_key)
    return {name: _draw(jax.random.fold_in(key, i), name, shape, dims)
            for i, (name, shape) in enumerate(
                sorted(layer_shapes(dims, layer).items()))}


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_ends(key, dims_key):
    dims = dict(dims_key)
    d, v = dims["hidden_size"], dims["vocab_size"]
    return {"wte": _draw(jax.random.fold_in(key, 0), "wte", (v, d), dims),
            "lnf_g": _draw(jax.random.fold_in(key, 1), "lnf_g", (d,), dims),
            "lm_head": _draw(jax.random.fold_in(key, 2), "lm_head", (d, v),
                             dims)}


def make_weights(seed: int, dims: dict) -> dict:
    """Every weight from the seed: bfloat16 arrays on the default device,
    `{"wte", "lnf_g", "lm_head", "layers": [one dict a layer]}`."""
    key, frozen = seed_key(seed), _frozen(dims)
    out = _make_ends(jax.random.fold_in(key, 0), frozen)
    out["layers"] = [_make_layer(jax.random.fold_in(key, 1 + l), frozen, l)
                     for l in range(dims["num_hidden_layers"])]
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _round_fp8(x):
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision: str):
    hi = jax.lax.Precision.HIGHEST
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "highest":
        return jnp.matmul(a, b, precision=hi)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        return jnp.matmul(_round_fp8(a), _round_fp8(b), precision=hi)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        g.astype(jnp.float32))


def yarn_frequencies(rope: int, theta: float, yarn: tuple):
    """The `rope / 2` rotary frequencies under deepseek_yarn: a feature
    that turns more than `beta_fast` times over the original length keeps
    theta^(-2i / rope), one that turns fewer than `beta_slow` times has it
    divided by `factor`, a linear ramp between (the bounds rounded
    outwards)."""
    factor, fast, slow, original, _ = yarn
    plain = theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)

    def turns_at(n):
        return rope * math.log(original / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(fast)), 0)
    high = min(math.ceil(turns_at(slow)), rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rope // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(plain / factor * ramp + plain * (1.0 - ramp),
                       jnp.float32)


def temperature(yarn: tuple) -> float:
    """m = 0.1 mscale_all_dim ln(factor) + 1; the scores take m^2."""
    factor, _, _, _, mscale = yarn
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotate(x, freqs):
    """x [S, ..., rope] at positions 0 .. S-1: feature i of the first half
    and feature i of the second turn together by position x freqs[i]."""
    s, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, lw, dims: dict, precision: str, drop):
    """a [S, hidden] -> [S, hidden]: causal latent attention with keys and
    values up-projected per head at every position, `_HEAD_CHUNK` heads
    and a block of queries at a time."""
    s, d = a.shape
    h = dims["num_attention_heads"]
    latent, nope, rope, value = (
        dims["kv_lora_rank"], dims["qk_nope_head_dim"],
        dims["qk_rope_head_dim"], dims["v_head_dim"])
    freqs = yarn_frequencies(rope, dims["rope_theta"], dims["yarn"])
    scale = (nope + rope) ** -0.5 * temperature(dims["yarn"]) ** 2
    down = _mm(a, lw["w_dkv"], precision)                     # [S, L + r]
    c = _rms_norm(down[:, :latent], lw["kv_g"], dims["rms_norm_eps"])
    k_r = _rotate(down[:, latent:], freqs).T                  # [r, S]
    hc = _HEAD_CHUNK if h % _HEAD_CHUNK == 0 else h
    bq = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def heads(acc, i):
        of = lambda w, axis: jax.lax.dynamic_slice_in_dim(w, i * hc, hc, axis)
        q = _mm(a, of(lw["wq"], 1).reshape(d, hc * (nope + rope)),
                precision).reshape(s, hc, nope + rope)
        kv = _mm(c, of(lw["w_ukv"], 1).reshape(latent, hc * (nope + value)),
                 precision).reshape(s, hc, nope + value)
        q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], freqs)
        k_nope = kv[..., :nope].transpose(1, 2, 0)            # [hc, n, S]
        v = kv[..., nope:].transpose(1, 0, 2)                 # [hc, S, v]

        def block(j):
            rows = lambda t: jax.lax.dynamic_slice_in_dim(
                t, j * bq, bq, 0).transpose(1, 0, 2)          # [hc, bq, .]
            scores = _mm(rows(q_nope), k_nope, precision)
            if drop != "rope_term":
                scores = scores + _mm(rows(q_rope), k_r, precision)
            seen = cols[None, :] <= (j * bq + jnp.arange(bq))[:, None]
            scores = jnp.where(seen, scores * scale, -jnp.inf)
            return _mm(jax.nn.softmax(scores, -1), v, precision)

        out = jax.lax.map(block, jnp.arange(s // bq))         # [n,hc,bq,v]
        out = out.transpose(0, 2, 1, 3).reshape(s, hc * value)
        return acc + _mm(out, of(lw["wo"], 0).reshape(hc * value, d),
                         precision), None

    out, _ = jax.lax.scan(heads, jnp.zeros((s, d), jnp.float32),
                          jnp.arange(h // hc))
    return out


def _swiglu(b, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.silu(_mm(b, w_gate, precision))
               * _mm(b, w_up, precision), w_down, precision)


def _moe(b, lw, dims: dict, precision: str, drop):
    """b [S, hidden] -> (the held chosen experts' weighted sum plus the
    shared expert [S, hidden], the chosen experts [S, k] sorted)."""
    first, end = dims["held_experts"]
    scores = jax.nn.sigmoid(_mm(b, lw["router"], precision))    # [S, E]
    select = scores
    if drop != "selection_bias":
        select = scores + lw["router_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(select, dims["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = dims["routed_scaling_factor"] * picked / picked.sum(
        -1, keepdims=True)

    def one(acc, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)  # [S]
        return acc + w_e[:, None] * _swiglu(b, w_gate, w_up, w_down,
                                            precision), None

    out, _ = jax.lax.scan(
        one, _swiglu(b, lw["s_gate"], lw["s_up"], lw["s_down"], precision),
        (jnp.arange(first, end), lw["e_gate"], lw["e_up"], lw["e_down"]))
    return out, jnp.sort(chosen, -1)


@functools.partial(jax.jit,
                   static_argnames=("dims_key", "precision", "drop"))
def _layer(x, lw, dims_key, precision, drop):
    dims = dict(dims_key)
    eps = dims["rms_norm_eps"]
    h = x + _attention(_rms_norm(x, lw["ln1_g"], eps), lw, dims, precision,
                       drop)
    b = _rms_norm(h, lw["ln2_g"], eps)
    if "router" not in lw:
        return h + _swiglu(b, lw["w_gate"], lw["w_up"], lw["w_down"],
                           precision), None
    out, chosen = _moe(b, lw, dims, precision, drop)
    return h + out, chosen


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _head(h, lm_head, lnf_g, dims_key, precision):
    return _mm(_rms_norm(h, lnf_g, dict(dims_key)["rms_norm_eps"]), lm_head,
               precision)


def forward(w: dict, tokens, dims: dict, precision: str = "highest",
            routes: bool = False, rows: slice = slice(None), drop=None):
    """[S] token ids -> [S, vocab] float32 logits (of the positions `rows`
    alone where given); with `routes` also the experts every position
    chose in every routed layer, [routed layers, S, k] sorted."""
    if drop not in DROPS:
        raise ValueError(f"drop {drop!r} is not one of {DROPS}")
    frozen = _frozen(dims)
    h = _embed(w["wte"], jnp.asarray(tokens))
    chosen = []
    for lw in w["layers"]:
        h, c = _layer(h, lw, frozen, precision, drop)
        if c is not None:
            chosen.append(c)
    logits = _head(h[rows], w["lm_head"], w["lnf_g"], frozen, precision)
    return (logits, jnp.stack(chosen)) if routes else logits


# ---------------------------------------------------------------------------
# the program's parameter tree (models/gpt.py::GPT, flax names): the same
# arrays, re-nested
# ---------------------------------------------------------------------------

def to_program_params(w: dict) -> dict:
    decoder = {"ln_final": {"scale": w["lnf_g"]}}
    for l, lw in enumerate(w["layers"]):
        block = {
            "ln_attn": {"scale": lw["ln1_g"]},
            "ln_mlp": {"scale": lw["ln2_g"]},
            "attn": {"query": lw["wq"], "kv_down": lw["w_dkv"],
                     "kv_norm": {"scale": lw["kv_g"]}, "kv_up": lw["w_ukv"],
                     "out": lw["wo"]},
        }
        if "router" in lw:
            block["moe"] = {
                "router": {"kernel": lw["router"]},
                "router_bias": lw["router_bias"],
                "experts_gate": lw["e_gate"], "experts_fc1": lw["e_up"],
                "experts_fc2": lw["e_down"],
                "shared_gate": {"kernel": lw["s_gate"]},
                "shared_fc1": {"kernel": lw["s_up"]},
                "shared_fc2": {"kernel": lw["s_down"]}}
        else:
            block["mlp"] = {"gate": {"kernel": lw["w_gate"]},
                            "fc1": {"kernel": lw["w_up"]},
                            "fc2": {"kernel": lw["w_down"]}}
        decoder[f"block_{l}"] = block
    return {"wte": {"embedding": w["wte"]}, "decoder": decoder,
            "lm_head": {"kernel": w["lm_head"]}}


# ---------------------------------------------------------------------------
# serving: one full forward over a prompt with its served tokens
# ---------------------------------------------------------------------------

@jax.jit
def _picked_gaps(logits, picks):
    chosen = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
    return logits.max(-1) - chosen, logits.argmax(-1), jnp.abs(logits).max()


def _padded(prompt, served, pad_to: int) -> tuple:
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n = prompt.size + served.size
    full = np.zeros(pad_to, np.int32)
    full[:n] = np.concatenate([prompt, served])
    return full, slice(prompt.size - 1, n - 1)   # position P-1+i predicts i


#: the head is applied to a run of this many positions' multiple that
#: holds the served ones (its programs compile once a run length)
_HEAD_ROWS = 256


def _gaps(w, prompt, served, picks, dims, pad_to, precision, drop=None):
    full, where = _padded(prompt, served, pad_to)
    length = min(pad_to, -(-(where.stop - where.start) // _HEAD_ROWS)
                 * _HEAD_ROWS)
    start = min(where.start, pad_to - length)
    mine = slice(where.start - start, where.stop - start)
    at = np.zeros(length, np.int32)
    at[mine] = np.asarray(picks, np.int32)
    logits, chosen = forward(w, full, dims, precision, routes=True,
                             rows=slice(start, start + length), drop=drop)
    gap, first, _ = jax.device_get(_picked_gaps(logits, jnp.asarray(at)))
    span = float(jnp.abs(logits[mine]).max())
    n = where.stop + 1
    return gap[mine], first[mine], span, np.asarray(chosen[:, :n])


def served_token_gaps(w: dict, prompt, served, dims: dict, pad_to: int,
                      precision: str = "highest", drop=None) -> dict:
    """One forward over prompt + served tokens, padded to `pad_to` (every
    layer is causal, so the padding is never seen). Per served token: how
    far its logit lies below the best logit at its position (`gap`) and
    the first choice there (`argmax`); the logits' largest magnitude
    (`range`); and the experts each real position chose in each routed
    layer (`routes` [routed layers, n, k], sorted), as numpy."""
    gap, first, span, chosen = _gaps(w, prompt, served, served, dims,
                                     pad_to, precision, drop)
    return {"gap": gap, "argmax": first, "range": span, "routes": chosen}


def gaps_of_choices(w: dict, prompt, served, choices, dims: dict,
                    pad_to: int) -> np.ndarray:
    """For the controls: at each served position of the same prompt and
    tokens, how far the reference's logit of `choices[i]` (what a lower
    precision, or the arithmetic without a term, put first there) lies
    below the reference's best."""
    return _gaps(w, prompt, served, choices, dims, pad_to, "highest")[0]


def routing_flips(a: np.ndarray, b: np.ndarray) -> int:
    """How many (layer, position) choices of experts differ between two
    `routes` of one request."""
    return int((a != b).any(-1).sum())
