"""SmallThinker-21BA3B-Instruct (PowerInfer, `smallthinker_21b_instruct`),
plainly: the forward pass of a decoder in which every layer is grouped-query
attention followed by a routed layer of ReGLU experts, the attention either
over everything before the query and without positions or over a sliding
window with rotary positions, and the router reading the ATTENTION's
normalised input, in straightforward `jax.numpy` and float32: no kernel, no
cache, no ring, no batching, no sorting of tokens by expert. It follows the
published `config.json` keys (`hidden_size`, `head_dim`,
`num_attention_heads`, `num_key_value_heads`, `sliding_window_layout`,
`sliding_window_size`, `rope_layout`, `rope_theta`, `moe_num_primary_experts`,
`moe_num_active_primary_experts`, `moe_ffn_hidden_size`, `rms_norm_eps`,
`vocab_size`, `tie_word_embeddings`).

Per layer l, on the hidden state x [T, hidden], rmsnorm(x) = x / rms(x) * g:

    a = rmsnorm(x; g1)                       also the router's input r
    q = a Wq [T, heads, head_dim];  k = a Wk, v = a Wv [T, kv heads, head_dim]
    window layer (`sliding_window_layout[l]` = 1, with `rope_layout[l]` = 1):
        q, k rotated over all of head_dim, theta `rope_theta` (two halves,
        the rotate_half convention); query i sees keys j in (i - W, i]
    global layer (both 0): no positions; query i sees every j <= i
    scores x head_dim^-0.5, softmax in float32, heads / kv heads query heads
    share a K/V head
    h = x + concat(heads) Wo
    b = rmsnorm(h; g2)
    s = r Wr [T, experts] in float32;  the `active` largest;  w = softmax
        over those (`moe_primary_router_apply_softmax` and `norm_topk_prob`:
        a softmax over all renormalised over the chosen is the same numbers)
    E_e(b) = (relu(b Wg_e) * (b Wu_e)) Wd_e
    y = h + sum over the chosen e of w_e E_e(b)

then a final rmsnorm and an untied head. No bias anywhere, no q/k norm, no
shared expert and no secondary experts: the config has no key for any of
them (the configuration file lists these under `assumed`).

It imports nothing of the program and takes nothing the program has made.
Weights come from `make_weights(seed, dims)` alone; the driver hands the
same numbers to the program through `to_program_params`.

Departures from the release, each for the comparison's sake:
- Weights are drawn from the seed, rounded once to bfloat16 (the
  deployment's dtype) and KEPT in bfloat16 arrays shaped as the program's
  own leaves, so that `to_program_params` only re-nests them and both sides
  hold the same numbers (3.97 G parameters at eight layers: a float32 copy
  would not fit beside anything); every use upcasts to float32 first,
  which is exact. The spreads (`_SPREAD`) are set so that no part is
  negligible and so that a near-tie of the router that falls the other way
  on rounding moves the logits less than the arithmetic does (the
  configuration file says how they were measured).
- Attention runs a block of queries at a time and the experts one at a time
  over all positions, so that 16,384 positions in float32 fit on a 16 GB
  chip; layers run one jitted call each. The arithmetic is the plain one.

`precision`: "highest" is the reference (float32, `Precision.HIGHEST`);
"bf16" and "fp8" round both operands of every matrix product first (fp8:
e4m3 under a per-tensor scale). They exist for the control: the reference
put in the program's place in the nearest precision below the one the
configuration states must come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bf16", "fp8")
DIM_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "moe_ffn_hidden_size",
            "moe_num_primary_experts", "moe_num_active_primary_experts",
            "rms_norm_eps", "rope_theta", "sliding_window_size", "vocab_size")
_QUERY_BLOCK = 512

# standard deviations of the seeded weights by leaf (N(0, 1) times this);
# the leaves not named take 1 / sqrt(fan in) of a hidden-wide input
_SPREAD = {"wte": 1.0, "wq_scores": 2.0, "wo_out": 0.7, "router_logits": 3.0,
           "w_down_out": 0.5}
_GAINS = ("ln1_g", "ln2_g", "lnf_g")     # 1 + N(0, 0.1)


def dims_of(cfg: dict) -> dict:
    """The sizes as run, read from the configuration file's top level: the
    two layouts cut to the depth."""
    if (not cfg["moe_primary_router_apply_softmax"]
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] is not None
            or cfg["tie_word_embeddings"]):
        raise ValueError(
            "this reference computes softmax weights renormalised over the "
            "chosen experts, unscaled rotary positions and an untied head")
    dims = {k: cfg[k] for k in DIM_KEYS}
    depth = cfg["num_hidden_layers"]
    dims["window_layers"] = tuple(
        bool(w) for w in cfg["sliding_window_layout"][:depth])
    if dims["window_layers"] != tuple(
            bool(r) for r in cfg["rope_layout"][:depth]):
        raise ValueError("a layer rotates exactly where it has a window")
    return dims


def _frozen(dims: dict) -> tuple:
    return tuple(sorted(dims.items()))


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def layer_shapes(dims: dict) -> dict:
    d, f, e = (dims["hidden_size"], dims["moe_ffn_hidden_size"],
               dims["moe_num_primary_experts"])
    nh, nkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    return {"ln1_g": (d,), "ln2_g": (d,), "wq": (d, nh, hd),
            "wk": (d, nkv, hd), "wv": (d, nkv, hd), "wo": (nh, hd, d),
            "router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
            "w_down": (e, f, d)}


def num_params(dims: dict) -> int:
    ends = 2 * dims["vocab_size"] * dims["hidden_size"] + dims["hidden_size"]
    return ends + dims["num_hidden_layers"] * sum(
        math.prod(s) for s in layer_shapes(dims).values())


def _spread(name: str, dims: dict) -> float:
    """The standard deviation of leaf `name`: every input is a normalised
    hidden state (rms about 1), so a leaf's fan-in sets what comes out."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    if name == "wte":
        return _SPREAD["wte"]
    if name in ("wq", "wk"):      # scores of std `wq_scores` after head_dim^-0.5
        return math.sqrt(_SPREAD["wq_scores"]) / math.sqrt(d)
    if name == "wo":
        return _SPREAD["wo_out"] / math.sqrt(dims["num_attention_heads"] * hd)
    if name == "router":
        return _SPREAD["router_logits"] / math.sqrt(d)
    if name == "w_down":
        return _SPREAD["w_down_out"] / math.sqrt(dims["moe_ffn_hidden_size"])
    return 1.0 / math.sqrt(d)


def _draw(key, name: str, shape: tuple, dims: dict):
    noise = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * noise if name in _GAINS else _spread(name, dims) * noise
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_layer(key, dims_key):
    dims = dict(dims_key)
    return {name: _draw(jax.random.fold_in(key, i), name, shape, dims)
            for i, (name, shape) in enumerate(
                sorted(layer_shapes(dims).items()))}


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
    out["layers"] = [_make_layer(jax.random.fold_in(key, 1 + l), frozen)
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


def _rotate(x, theta: float):
    """x [S, heads, head_dim] at positions 0 .. S-1: feature i of the
    first half and feature i of the second turn together by position x
    theta^(-2i / head_dim)."""
    s, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, lw, windowed: bool, dims: dict, precision: str):
    """a [S, hidden] -> [S, hidden]: causal grouped-query attention, a
    block of queries at a time; with a window and rotary positions where
    `windowed`, over everything and without positions otherwise."""
    s, d = a.shape
    nh, nkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    q = _mm(a, lw["wq"].reshape(d, nh * hd), precision).reshape(s, nh, hd)
    k = _mm(a, lw["wk"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    v = _mm(a, lw["wv"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    if windowed:
        q, k = _rotate(q, dims["rope_theta"]), _rotate(k, dims["rope_theta"])
    k = jnp.repeat(k, nh // nkv, 1).transpose(1, 2, 0)     # [H, hd, S]
    v = jnp.repeat(v, nh // nkv, 1).transpose(1, 0, 2)     # [H, S, hd]
    bq = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0).transpose(1, 0, 2)
        scores = _mm(qi, k, precision) * hd ** -0.5
        rows = i * bq + jnp.arange(bq)
        seen = cols[None, :] <= rows[:, None]
        if windowed:
            seen &= cols[None, :] > rows[:, None] - dims["sliding_window_size"]
        scores = jnp.where(seen, scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, -1), v, precision)   # [H, bq, hd]

    out = jax.lax.map(block, jnp.arange(s // bq))              # [n,H,bq,hd]
    out = out.transpose(0, 2, 1, 3).reshape(s, nh * hd)
    return _mm(out, lw["wo"].reshape(nh * hd, d), precision)


def _reglu(b, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.relu(_mm(b, w_gate, precision))
               * _mm(b, w_up, precision), w_down, precision)


def _moe(b, r, lw, dims: dict, precision: str):
    """The experts read b [S, hidden], the router r [S, hidden] -> (the
    chosen experts' weighted sum [S, hidden], the chosen experts [S, k],
    sorted)."""
    logits = _mm(r, lw["router"], precision)                   # [S, E]
    top, chosen = jax.lax.top_k(logits,
                                dims["moe_num_active_primary_experts"])
    weights = jax.nn.softmax(top, -1)

    def one(acc, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)  # [S]
        return acc + w_e[:, None] * _reglu(b, w_gate, w_up, w_down,
                                           precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(b),
        (jnp.arange(dims["moe_num_primary_experts"]), lw["w_gate"],
         lw["w_up"], lw["w_down"]))
    return routed, jnp.sort(chosen, -1)


@functools.partial(jax.jit,
                   static_argnames=("windowed", "dims_key", "precision"))
def _layer(x, lw, windowed, dims_key, precision):
    dims = dict(dims_key)
    eps = dims["rms_norm_eps"]
    a = _rms_norm(x, lw["ln1_g"], eps)
    h = x + _attention(a, lw, windowed, dims, precision)
    out, chosen = _moe(_rms_norm(h, lw["ln2_g"], eps), a, lw, dims, precision)
    return h + out, chosen


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _head(h, lm_head, lnf_g, dims_key, precision):
    return _mm(_rms_norm(h, lnf_g, dict(dims_key)["rms_norm_eps"]), lm_head,
               precision)


def forward(w: dict, tokens, dims: dict, precision: str = "highest",
            routes: bool = False, rows: slice = slice(None)):
    """[S] token ids -> [S, vocab] float32 logits (of the positions `rows`
    alone where given: 16,384 positions of a 151,936-wide head are 10 GB);
    with `routes` also the experts every position chose in every layer,
    [layers, S, k] sorted."""
    frozen = _frozen(dims)
    h = _embed(w["wte"], jnp.asarray(tokens))
    chosen = []
    for windowed, lw in zip(dims["window_layers"], w["layers"]):
        h, c = _layer(h, lw, windowed, frozen, precision)
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
        decoder[f"block_{l}"] = {
            "ln_attn": {"scale": lw["ln1_g"]},
            "ln_mlp": {"scale": lw["ln2_g"]},
            "attn": {"query": {"kernel": lw["wq"]},
                     "key": {"kernel": lw["wk"]},
                     "value": {"kernel": lw["wv"]},
                     "out": {"kernel": lw["wo"]}},
            "moe": {"router": {"kernel": lw["router"]},
                    "experts_gate": lw["w_gate"], "experts_fc1": lw["w_up"],
                    "experts_fc2": lw["w_down"]},
        }
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


def _gaps(w, prompt, served, picks, dims, pad_to, precision):
    full, where = _padded(prompt, served, pad_to)
    length = min(pad_to, -(-(where.stop - where.start) // _HEAD_ROWS)
                 * _HEAD_ROWS)
    start = min(where.start, pad_to - length)
    mine = slice(where.start - start, where.stop - start)
    at = np.zeros(length, np.int32)
    at[mine] = np.asarray(picks, np.int32)
    logits, chosen = forward(w, full, dims, precision, routes=True,
                             rows=slice(start, start + length))
    gap, first, _ = jax.device_get(_picked_gaps(logits, jnp.asarray(at)))
    span = float(jnp.abs(logits[mine]).max())
    n = where.stop + 1
    return gap[mine], first[mine], span, np.asarray(chosen[:, :n])


def served_token_gaps(w: dict, prompt, served, dims: dict, pad_to: int,
                      precision: str = "highest") -> dict:
    """One forward over prompt + served tokens, padded to `pad_to` (every
    layer is causal, so the padding is never seen). Per served token: how
    far its logit lies below the best logit at its position (`gap`) and
    the first choice there (`argmax`); the logits' largest magnitude
    (`range`); and the experts each real position chose in each layer
    (`routes` [layers, n, k], sorted), as numpy."""
    gap, first, span, chosen = _gaps(w, prompt, served, served, dims,
                                     pad_to, precision)
    return {"gap": gap, "argmax": first, "range": span, "routes": chosen}


def gaps_of_choices(w: dict, prompt, served, choices, dims: dict,
                    pad_to: int) -> np.ndarray:
    """For the control: at each served position of the same prompt and
    tokens, how far the reference's logit of `choices[i]` (what a lower
    precision put first there) lies below the reference's best."""
    return _gaps(w, prompt, served, choices, dims, pad_to, "highest")[0]


def routing_flips(a: np.ndarray, b: np.ndarray) -> int:
    """How many (layer, position) choices of experts differ between two
    `routes` of one request."""
    return int((a != b).any(-1).sum())
