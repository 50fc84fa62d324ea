"""Qwen3-Next-80B-A3B-Instruct (`model_type: qwen3_next`), plainly: the
forward pass of a decoder whose layers mix positions with a gated delta
rule (three of every four) or with output-gated grouped-query attention
(the fourth), each followed by a routed layer of SwiGLU experts beside one
sigmoid-gated shared expert, in straightforward `jax.numpy` and float32: no
kernel, no cache, no chunks, no batching, no sorting of tokens by expert,
and the delta rule TOKEN BY TOKEN under a `lax.scan`, so that the
program's chunked solve is checked against other mathematics. It follows
the published `config.json` keys (`hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `partial_rotary_factor`, `rope_theta`,
`full_attention_interval`, `linear_conv_kernel_dim`, `linear_key_head_dim`,
`linear_value_head_dim`, `linear_num_key_heads`, `linear_num_value_heads`,
`moe_intermediate_size`, `shared_expert_intermediate_size`,
`num_experts_per_tok`, `norm_topk_prob`, `rms_norm_eps`, `vocab_size`).

Per layer i on the hidden state x [T, hidden]; every norm is
x / rms(x) * (1 + w) in float32 except the delta rule's own:

    x = x + mixer_i(norm1(x));   x = x + moe(norm2(x))

- layer i is `full_attention` where (i + 1) % `full_attention_interval`
  == 0, else `linear_attention`.
- `linear_attention`, h the normalised input: [q, k, v, z] = h W_qkvz (q, k
  of `linear_num_key_heads` heads x `linear_key_head_dim`, v and z of
  `linear_num_value_heads` x `linear_value_head_dim`); [b, a] = h W_ba, one
  of each a value head. The channels of [q, k, v] pass a causal depthwise
  convolution (`linear_conv_kernel_dim` taps, no bias) and SiLU.
  beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias). q and k are
  l2-normalised per head (eps 1e-6), q times key_dim^-0.5; key head j
  serves value heads j r .. j r + r - 1. Per value head, S [key, value]:
      S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
      S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
  y = w * (o / rms(o)) * silu(z) per head over its values (gain w, NOT
  1 + w; the norm before the gate); out y W_out. No skip term.
- `full_attention`: [q, gate] = h W_q, per head `head_dim` + `head_dim`; k,
  v of `num_key_value_heads` heads; q and k RMS-normed per head (1 + w);
  the first `partial_rotary_factor` x `head_dim` features rotated in two
  halves (theta `rope_theta`, no scaling); causal softmax of
  q k^T / sqrt(head_dim) in float32; (attn * sigmoid(gate)) W_o.
- `moe`: p = softmax(h W_r) over all published experts in float32; the
  `num_experts_per_tok` largest, renormalised to sum 1 (`norm_topk_prob`);
  sum over the chosen e HELD HERE of p_e E_e(h), one expert at a time over
  all positions, plus sigmoid(h w_g) S(h); E_e and S SwiGLU.

then the final norm and an untied head. No bias anywhere.

It imports nothing of the program and takes nothing the program has made.
Weights come from `make_weights(seed, dims)` alone; the driver hands the
same numbers to the program through `to_program_params`.

Departures from the release, each for the comparison's sake or because one
chip holds a share of the model (the configuration file says which):
- **The share.** `dims["held_experts"] = [first, end)` of the published
  experts have weights here; the router is `published_experts` wide and a
  chosen expert held elsewhere adds nothing, here and in the program alike.
  The vocabulary is the held slice.
- **Readings the config has no key for** (the file's `assumed`): the
  columns of W_qkvz stand as [q | k | v | z] and of W_ba as [b | a] (the
  release interleaves them by key head: a permutation of seeded columns);
  the rotation is in two halves (`rotate_half`); `intermediate_size` is
  unused (`mlp_only_layers` empty, `decoder_sparse_step` 1); no
  multi-token-prediction module.
- Weights are drawn from the seed, rounded once to bfloat16 (the
  deployment's dtype) and KEPT in bfloat16 arrays shaped as the program's
  own leaves, so that `to_program_params` only re-nests them and both sides
  hold the same numbers (3.68 G parameters: a float32 copy would not fit
  beside anything); every use upcasts to float32 first, which is exact.
  The spreads (`_SPREAD`, `_draw`) are set so that no part is negligible
  and the routing does not collapse (output projections at a gain under 1):
  `A_log` = log(1 + 15 U), `dt_bias` the inverse softplus of log-uniform
  [0.01, 0.5], the taps N(0, 0.5), every norm gain random, the q/k norms'
  gain about 2 (scores of spread 4: an attention that looks somewhere), so
  that an implementation that drops one shows in the logits.
- Attention runs a block of queries at a time and the experts one at a
  time over all positions, so that 32,768 positions in float32 fit on a 16
  GB chip beside the weights; layers run one jitted call each. The head is
  applied at the positions asked for (`rows`). The arithmetic is the plain
  one.

`precision`: "highest" is the reference (float32, `Precision.HIGHEST`);
"bf16" and "fp8" round both operands of every matrix product first (fp8:
e4m3 under a per-tensor scale), and in the delta rule the rows q, k, v and
u that meet the state. `drop`: None, or a term LEFT OUT of the arithmetic:
"delta_term" (u_t = beta_t v_t: nothing read back, a gated linear
attention) or "output_gate" (the attention's output goes to W_o ungated).
Both kinds exist for the controls: the reference put in the program's
place in the nearest precision below the one the configuration states, or
without a term, must come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "bf16", "fp8")
DROPS = (None, "delta_term", "output_gate")
DIM_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "partial_rotary_factor", "rope_theta",
            "full_attention_interval", "linear_conv_kernel_dim",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_num_key_heads", "linear_num_value_heads",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts_per_tok", "num_hidden_layers", "rms_norm_eps",
            "vocab_size")
_QUERY_BLOCK = 256
_HEAD_ROWS = 256

# standard deviations of the seeded weights by leaf (N(0, 1) times this);
# the leaves not named take 1 / sqrt(fan in) of a normalised input
_SPREAD = {"wte": 1.0, "wo_out": 0.7, "out_proj_out": 0.7,
           "w_down_out": 0.5, "conv_w": 0.5}
_OFFSETS = ("ln1_g", "ln2_g", "lnf_g")      # stored w of 1 + w: N(0, 0.1)
_QK_OFFSETS = ("qn_g", "kn_g")              # stored w of 1 + w: 1 + N(0, 0.1)


def dims_of(cfg: dict) -> dict:
    """The sizes as run, read from the configuration file's top level, and
    the share of the published experts held."""
    if (cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu"
            or not cfg["norm_topk_prob"] or cfg["mlp_only_layers"]
            or cfg["decoder_sparse_step"] != 1
            or cfg["rope_scaling"] is not None
            or cfg["use_sliding_window"]):
        raise ValueError(
            "this reference computes an untied head, SwiGLU, chosen gates "
            "renormalised, experts in every layer, unscaled rotary "
            "frequencies and no sliding window")
    dims = {k: cfg[k] for k in DIM_KEYS}
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


def layer_kind(dims: dict, layer: int) -> str:
    return ("attention"
            if (layer + 1) % dims["full_attention_interval"] == 0
            else "delta")


def _delta_widths(dims: dict) -> tuple:
    """(key width, value width) of the delta rule's q/k and v/z."""
    return (dims["linear_num_key_heads"] * dims["linear_key_head_dim"],
            dims["linear_num_value_heads"] * dims["linear_value_head_dim"])


def layer_shapes(dims: dict, kind: str) -> dict:
    d, f, fs = (dims["hidden_size"], dims["moe_intermediate_size"],
                dims["shared_expert_intermediate_size"])
    held = dims["held_experts"][1] - dims["held_experts"][0]
    out = {"ln1_g": (d,), "ln2_g": (d,),
           "router": (d, dims["published_experts"]),
           "e_gate": (held, d, f), "e_up": (held, d, f),
           "e_down": (held, f, d),
           "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d),
           "s_mix": (d, 1)}
    if kind == "delta":
        kw, vw = _delta_widths(dims)
        hv = dims["linear_num_value_heads"]
        out.update({"w_qkvz": (d, 2 * kw + 2 * vw), "w_ba": (d, 2 * hv),
                    "conv_w": (dims["linear_conv_kernel_dim"], 2 * kw + vw),
                    "A_log": (hv,), "dt_bias": (hv,),
                    "norm_g": (dims["linear_value_head_dim"],),
                    "out_proj": (vw, d)})
    elif kind == "attention":
        nh, nkv, hd = (dims["num_attention_heads"],
                       dims["num_key_value_heads"], dims["head_dim"])
        out.update({"wq": (d, nh, 2 * hd), "wk": (d, nkv, hd),
                    "wv": (d, nkv, hd), "wo": (nh, hd, d),
                    "qn_g": (hd,), "kn_g": (hd,)})
    else:
        raise ValueError(f"layer kind {kind!r} is not 'delta' or 'attention'")
    return out


def num_params(dims: dict) -> int:
    n = 2 * dims["vocab_size"] * dims["hidden_size"] + dims["hidden_size"]
    for layer in range(dims["num_hidden_layers"]):
        n += sum(math.prod(s) for s in
                 layer_shapes(dims, layer_kind(dims, layer)).values())
    return n


def _draw(key, name: str, shape: tuple):
    noise = jax.random.normal(key, shape, jnp.float32)
    if name in _OFFSETS:
        x = 0.1 * noise
    elif name in _QK_OFFSETS or name == "norm_g":
        x = 1.0 + 0.1 * noise
    elif name == "A_log":
        x = jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, minval=math.log(0.01), maxval=math.log(0.5)))
        x = dt + jnp.log(-jnp.expm1(-dt))          # inverse softplus
    elif name == "wte":
        x = _SPREAD["wte"] * noise
    elif name == "conv_w":
        x = _SPREAD["conv_w"] * noise
    elif name == "wo":
        x = _SPREAD["wo_out"] / math.sqrt(shape[0] * shape[1]) * noise
    elif name == "out_proj":
        x = _SPREAD["out_proj_out"] / math.sqrt(shape[0]) * noise
    elif name in ("e_down", "s_down"):
        x = _SPREAD["w_down_out"] / math.sqrt(shape[-2]) * noise
    elif name in ("e_gate", "e_up"):
        x = noise / math.sqrt(shape[1])
    else:
        x = noise / math.sqrt(shape[0])
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("kind", "dims_key"))
def _make_layer(key, kind, dims_key):
    dims = dict(dims_key)
    return {name: _draw(jax.random.fold_in(key, i), name, shape)
            for i, (name, shape) in enumerate(
                sorted(layer_shapes(dims, kind).items()))}


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_ends(key, dims_key):
    dims = dict(dims_key)
    d, v = dims["hidden_size"], dims["vocab_size"]
    return {"wte": _draw(jax.random.fold_in(key, 0), "wte", (v, d)),
            "lnf_g": _draw(jax.random.fold_in(key, 1), "lnf_g", (d,)),
            "lm_head": _draw(jax.random.fold_in(key, 2), "lm_head", (d, v))}


def make_weights(seed: int, dims: dict) -> dict:
    """Every weight from the seed: bfloat16 arrays on the default device,
    `{"wte", "lnf_g", "lm_head", "layers": [one dict a layer]}`."""
    key, frozen = seed_key(seed), _frozen(dims)
    out = _make_ends(jax.random.fold_in(key, 0), frozen)
    out["layers"] = [
        _make_layer(jax.random.fold_in(key, 1 + l), layer_kind(dims, l),
                    frozen)
        for l in range(dims["num_hidden_layers"])]
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _round_fp8(x):
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rounded(x, precision: str):
    """x as a matrix product of `precision` reads its operands."""
    x = x.astype(jnp.float32)
    if precision == "highest":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return _round_fp8(x)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _mm(a, b, precision: str):
    return jnp.matmul(_rounded(a, precision), _rounded(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _offset_norm(x, w, eps):
    """x / rms(x) * (1 + w)."""
    return _rms(x, eps) * (1.0 + w.astype(jnp.float32))


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _delta(h, lw, dims: dict, precision: str, drop):
    """h [S, hidden] -> [S, hidden]: the gated delta rule position by
    position."""
    s = h.shape[0]
    kw, vw = _delta_widths(dims)
    hk, hv = dims["linear_num_key_heads"], dims["linear_num_value_heads"]
    dk, dv = dims["linear_key_head_dim"], dims["linear_value_head_dim"]
    taps = dims["linear_conv_kernel_dim"]
    qkvz = _mm(h, lw["w_qkvz"], precision)
    ba = _mm(h, lw["w_ba"], precision)
    qkv, z = qkvz[:, :2 * kw + vw], qkvz[:, 2 * kw + vw:]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    qkv = jax.nn.silu(sum(padded[k:k + s] * w[k] for k in range(taps)))
    q = _l2(qkv[:, :kw].reshape(s, hk, dk)) * dk ** -0.5
    k = _l2(qkv[:, kw:2 * kw].reshape(s, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))     # [S,Hv,K]
    v = qkv[:, 2 * kw:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + lw["dt_bias"].astype(jnp.float32))
    low = functools.partial(_rounded, precision=precision)

    def step(state, t):
        q_t, k_t, v_t, beta_t, g_t = t
        state = state * jnp.exp(g_t)[:, None, None]
        read = jnp.sum(state * low(k_t)[:, :, None], 1)          # S'^T k
        u = beta_t[:, None] * (v_t if drop == "delta_term"
                               else v_t - read)
        state = state + low(k_t)[:, :, None] * low(u)[:, None, :]
        return state, jnp.sum(state * low(q_t)[:, :, None], 1)   # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, low(v), beta, g))
    y = (_rms(o, dims["rms_norm_eps"]) * lw["norm_g"].astype(jnp.float32)
         * jax.nn.silu(z.reshape(s, hv, dv)))
    return _mm(y.reshape(s, vw), lw["out_proj"], precision)


def _rotate(x, rot: int, theta: float):
    """x [S, heads, D] at positions 0 .. S-1: of the first `rot` features,
    feature i of the first half and feature i of the second turn together
    by position x theta^(-2 i / rot); the rest pass."""
    s, half = x.shape[0], rot // 2
    freqs = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.asarray(freqs, jnp.float32)[None, :])[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _attention(h, lw, dims: dict, precision: str, drop):
    """h [S, hidden] -> [S, hidden]: causal grouped-query attention with
    normed, partly rotated q and k and a gate on its output, a block of
    queries at a time."""
    s, d = h.shape
    nh, nkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    eps = dims["rms_norm_eps"]
    rot = int(hd * dims["partial_rotary_factor"])
    qg = _mm(h, lw["wq"].reshape(d, nh * 2 * hd), precision
             ).reshape(s, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _mm(h, lw["wk"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    v = _mm(h, lw["wv"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    q = _rotate(_offset_norm(q, lw["qn_g"], eps), rot, dims["rope_theta"])
    k = _rotate(_offset_norm(k, lw["kn_g"], eps), rot, dims["rope_theta"])
    k = jnp.repeat(k, nh // nkv, 1).transpose(1, 2, 0)         # [H, hd, S]
    v = jnp.repeat(v, nh // nkv, 1).transpose(1, 0, 2)         # [H, S, hd]
    bq = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0).transpose(1, 0, 2)
        scores = _mm(qi, k, precision) * hd ** -0.5
        rows = i * bq + jnp.arange(bq)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, -1), v, precision)   # [H, bq, hd]

    out = jax.lax.map(block, jnp.arange(s // bq))              # [n,H,bq,hd]
    out = out.transpose(0, 2, 1, 3).reshape(s, nh, hd)
    if drop != "output_gate":
        out = out * jax.nn.sigmoid(gate)
    return _mm(out.reshape(s, nh * hd), lw["wo"].reshape(nh * hd, d),
               precision)


def _swiglu(h, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision))
               * _mm(h, w_up, precision), w_down, precision)


def routed_part(h, lw, dims: dict, precision: str = "highest",
                held=None) -> tuple:
    """h [S, hidden] -> (the weighted sum of the chosen experts held in
    `held` = [first, end) (the dims' own where None; `lw`'s expert leaves
    are those experts'), the chosen experts [S, k] sorted)."""
    first, end = held or dims["held_experts"]
    probs = jax.nn.softmax(_mm(h, lw["router"], precision), -1)  # [S, E]
    top, chosen = jax.lax.top_k(probs, dims["num_experts_per_tok"])
    gates = top / top.sum(-1, keepdims=True)

    def one(acc, expert):
        e, w_gate, w_up, w_down = expert
        g_e = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)    # [S]
        return acc + g_e[:, None] * _swiglu(h, w_gate, w_up, w_down,
                                            precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (jnp.arange(first, end), lw["e_gate"], lw["e_up"], lw["e_down"]))
    return out, jnp.sort(chosen, -1)


def shared_part(h, lw, precision: str = "highest"):
    """sigmoid(h w_g) times the shared SwiGLU expert: what every chip of a
    layer computes alike."""
    return jax.nn.sigmoid(_mm(h, lw["s_mix"], precision)) * _swiglu(
        h, lw["s_gate"], lw["s_up"], lw["s_down"], precision)


@functools.partial(jax.jit,
                   static_argnames=("kind", "dims_key", "precision", "drop"))
def _layer(x, lw, kind, dims_key, precision, drop):
    dims = dict(dims_key)
    eps = dims["rms_norm_eps"]
    mixer = _delta if kind == "delta" else _attention
    x = x + mixer(_offset_norm(x, lw["ln1_g"], eps), lw, dims, precision,
                  drop)
    h = _offset_norm(x, lw["ln2_g"], eps)
    routed, chosen = routed_part(h, lw, dims, precision)
    return x + routed + shared_part(h, lw, precision), chosen


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _head(h, lm_head, lnf_g, dims_key, precision):
    return _mm(_offset_norm(h, lnf_g, dict(dims_key)["rms_norm_eps"]),
               lm_head, precision)


def forward(w: dict, tokens, dims: dict, precision: str = "highest",
            routes: bool = False, rows: slice = slice(None), drop=None):
    """[S] token ids -> [S, vocab] float32 logits (of the positions `rows`
    alone where given); with `routes` also the experts every position
    chose in every layer, [layers, S, k] sorted."""
    if drop not in DROPS:
        raise ValueError(f"drop {drop!r} is not one of {DROPS}")
    frozen = _frozen(dims)
    h = _embed(w["wte"], jnp.asarray(tokens))
    chosen = []
    for l, lw in enumerate(w["layers"]):
        h, c = _layer(h, lw, layer_kind(dims, l), frozen, precision, drop)
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
            "moe": {"router": {"kernel": lw["router"]},
                    "experts_gate": lw["e_gate"], "experts_fc1": lw["e_up"],
                    "experts_fc2": lw["e_down"],
                    "shared_gate": {"kernel": lw["s_gate"]},
                    "shared_fc1": {"kernel": lw["s_up"]},
                    "shared_fc2": {"kernel": lw["s_down"]},
                    "shared_expert_gate": {"kernel": lw["s_mix"]}},
        }
        if "w_qkvz" in lw:
            block["delta"] = {
                "in_proj_qkvz": {"kernel": lw["w_qkvz"]},
                "in_proj_ba": {"kernel": lw["w_ba"]},
                "conv_kernel": lw["conv_w"], "A_log": lw["A_log"],
                "dt_bias": lw["dt_bias"], "norm_scale": lw["norm_g"],
                "out_proj": {"kernel": lw["out_proj"]}}
        else:
            block["attn"] = {
                "query": {"kernel": lw["wq"]}, "key": {"kernel": lw["wk"]},
                "value": {"kernel": lw["wv"]}, "out": {"kernel": lw["wo"]},
                "q_norm": {"scale": lw["qn_g"]},
                "k_norm": {"scale": lw["kn_g"]}}
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
    logits = logits[mine]
    n = where.stop + 1
    return (gap[mine], first[mine], float(jnp.abs(logits).max()),
            np.asarray(chosen[:, :n]), logits)


def served_token_gaps(w: dict, prompt, served, dims: dict, pad_to: int,
                      precision: str = "highest", drop=None,
                      keep_logits: bool = False) -> dict:
    """One forward over prompt + served tokens, padded to `pad_to` (every
    layer is causal, so the padding is never seen). Per served token: how
    far its logit lies below the best logit at its position (`gap`) and
    the first choice there (`argmax`); the logits' largest magnitude
    (`range`); and the experts each real position chose in each layer
    (`routes` [layers, n, k], sorted), as numpy; with `keep_logits` the
    served positions' logits themselves (`logits` [n, vocab], on the
    host), for `gaps_of_choices`."""
    gap, first, span, chosen, logits = _gaps(w, prompt, served, served, dims,
                                             pad_to, precision, drop)
    out = {"gap": gap, "argmax": first, "range": span, "routes": chosen}
    if keep_logits:
        out["logits"] = np.asarray(logits)
    return out


def gaps_of_choices(w: dict, prompt, served, choices, dims: dict,
                    pad_to: int, logits=None) -> np.ndarray:
    """For the controls: at each served position of the same prompt and
    tokens, how far the reference's logit of `choices[i]` (what a lower
    precision, or the arithmetic without a term, put first there) lies
    below the reference's best. `logits`: the reference's own at those
    positions where the caller kept them (`served_token_gaps(...,
    keep_logits=True)`), which saves the forward."""
    if logits is None:
        return _gaps(w, prompt, served, choices, dims, pad_to, "highest")[0]
    picked = np.take_along_axis(
        logits, np.asarray(choices, np.int64)[:, None], -1)[:, 0]
    return logits.max(-1) - picked


def routing_flips(a: np.ndarray, b: np.ndarray) -> int:
    """How many (layer, position) choices of experts differ between two
    `routes` of one request."""
    return int((a != b).any(-1).sum())
