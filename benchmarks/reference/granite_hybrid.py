"""Granite 4.0-H (`model_type: granitemoehybrid`), plainly: the forward pass
of a decoder whose layers mix positions either with a Mamba-2 state-space
recurrence or with grouped-query attention without positions, each followed
by a routed layer of SwiGLU experts beside one shared SwiGLU, in
straightforward `jax.numpy` and float32: no kernel, no cache, no chunks, no
batching, no sorting of tokens by expert. It follows the published
`config.json` keys (`hidden_size`, `layer_types`, `mamba_*`,
`num_attention_heads`, `num_key_value_heads`, `attention_multiplier`,
`num_local_experts`, `num_experts_per_tok`, `intermediate_size`,
`shared_intermediate_size`, `embedding_multiplier`, `residual_multiplier`,
`logits_scaling`, `rms_norm_eps`, `vocab_size`, `tie_word_embeddings`).

Per layer l, with r = residual_multiplier and rmsnorm(x) = x / rms(x) * g:

    u = rmsnorm(h);  h = h + r * mixer_l(u)
    v = rmsnorm(h);  h = h + r * (moe(v) + shared(v))

- `mamba`: [z, xBC, dt] = u W_in (widths inner, inner + 2 groups x state,
  heads); xBC_t = silu(sum_{k<K} w_k xBC_{t-K+1+k} + b) per channel;
  [x, B, C] = xBC (x as `heads` heads of `d_head`; B, C of `d_state` a
  group); dt = softplus(dt + dt_bias); a_t = exp(-exp(A_log) dt_t) per
  head; S_t = a_t S_{t-1} + dt_t x_t B_t^T ([d_head, d_state] a head), a
  plain `lax.scan` over positions; y_t = S_t C_t + D x_t;
  y = rmsnorm(y * silu(z)) over all inner channels; out y W_out.
- `attention`: q of `num_attention_heads` heads, k and v of
  `num_key_value_heads`, no positions, causal, scores times
  `attention_multiplier`, softmax in float32, W_o.
- `moe`: p = v W_r over all `num_local_experts` published experts; the
  `num_experts_per_tok` largest; g = softmax over those; sum over the HELD
  experts e of g_e W2_e (silu(W1g_e v) * W1u_e v), one expert at a time
  over all positions; `shared`: the same SwiGLU at
  `shared_intermediate_size`, ungated.
- embedding times `embedding_multiplier`; final rmsnorm; logits =
  h E^T / `logits_scaling` over the held rows of E (tied head).

It imports nothing of the program (not `tfde_tpu/ops/ssm.py` nor
`tfde_tpu/models/moe.py`) and takes nothing the program has made. Weights
come from `make_weights(seed, dims)` alone; the driver hands the same
numbers to the program through `to_program_params`.

Departures from the release, each for the comparison's sake or because one
chip holds a share of the model (the configuration file says which share):
- **The share.** `dims["held_experts"] = [first, end)` of the published
  experts have weights here; the router is `published_experts` wide and a
  pair routed to an absent expert adds nothing: the partial result goes on,
  here and in the program alike. The vocabulary is the held slice.
- Weights are drawn from the seed, rounded once to bfloat16 (the
  deployment's dtype) and KEPT in bfloat16 arrays shaped as the program's
  own leaves, so that `to_program_params` only re-nests them and both sides
  hold the same numbers (4.76 G parameters: a float32 copy would not fit
  beside anything); every use upcasts to float32 first, which is exact.
  The spreads (`_SPREAD`) are set so that no part is negligible: output
  projections wide enough that the twenty sublayers together outweigh the
  embedding thirty-fold (a tied head otherwise makes every token predict
  itself), `A_log` = log U[1, 16], `dt_bias` the inverse softplus of
  log-uniform [0.01, 0.5], `D`, the conv and every norm gain random, so
  that an implementation that drops one shows in the logits.
- HF keeps an expert's gate and up projections in one `input_linear`
  ([experts, 2 x width, hidden], the first `width` rows the gate, which
  goes through silu, the last `width` the up); they are drawn here as the
  two halves, `w_gate` and `w_up`, transposed to [hidden, width].
- Attention runs a block of queries at a time and the experts one at a
  time, so that 7,168 positions in float32 fit on a 16 GB chip; layers run
  one jitted call each. The arithmetic is the plain one.

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
DIM_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
            "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "num_experts_per_tok", "vocab_size",
            "rms_norm_eps", "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_expand")
_QUERY_BLOCK = 512

# standard deviations of the seeded weights by leaf (N(0, 1) times this);
# the leaves not named take 1 / sqrt(fan in) of a hidden-wide input
_SPREAD = {"wte": 0.25, "wq": 0.074, "wk": 0.074, "wo": 4.0,
           "out_proj": 1.1, "router": 2.0 / 64, "w_down": 3.0,
           "s_down": 3.0, "conv_w": 0.5, "conv_b": 0.1}
_GAINS = ("ln1_g", "ln2_g", "lnf_g", "norm_g")     # 1 + N(0, 0.1)


def dims_of(cfg: dict) -> dict:
    """The sizes as run, read from the configuration file's top level:
    `layer_types` cut to the depth, the experts held of those published."""
    dims = {k: cfg[k] for k in DIM_KEYS}
    dims["layer_types"] = tuple(
        cfg["layer_types"][:cfg["num_hidden_layers"]])
    dims["published_experts"] = cfg["published"]["num_local_experts"]
    dims["held_experts"] = tuple(cfg["deployment_share"]["experts"])
    if dims["held_experts"][1] - dims["held_experts"][0] != \
            cfg["num_local_experts"]:
        raise ValueError("num_local_experts is not the held range's width")
    return dims


def _frozen(dims: dict) -> tuple:
    return tuple(sorted(dims.items()))


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _mamba_widths(dims: dict) -> tuple:
    inner = dims["mamba_n_heads"] * dims["mamba_d_head"]
    if inner != dims["mamba_expand"] * dims["hidden_size"]:
        raise ValueError("mamba heads x head width is not expand x hidden")
    gn = dims["mamba_n_groups"] * dims["mamba_d_state"]
    return inner, gn


def layer_shapes(dims: dict, kind: str) -> dict:
    d, f, fs = (dims["hidden_size"], dims["intermediate_size"],
                dims["shared_intermediate_size"])
    held = dims["held_experts"][1] - dims["held_experts"][0]
    out = {"ln1_g": (d,), "ln2_g": (d,),
           "router": (d, dims["published_experts"]),
           "w_gate": (held, d, f), "w_up": (held, d, f),
           "w_down": (held, f, d),
           "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)}
    if kind == "mamba":
        inner, gn = _mamba_widths(dims)
        h, conv = dims["mamba_n_heads"], inner + 2 * gn
        out.update({"in_proj": (d, inner + conv + h),
                    "conv_w": (dims["mamba_d_conv"], conv),
                    "conv_b": (conv,), "A_log": (h,), "D": (h,),
                    "dt_bias": (h,), "norm_g": (inner,),
                    "out_proj": (inner, d)})
    elif kind == "attention":
        nh, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
        hd = d // nh
        out.update({"wq": (d, nh, hd), "wk": (d, nkv, hd),
                    "wv": (d, nkv, hd), "wo": (nh, hd, d)})
    else:
        raise ValueError(f"layer type {kind!r} is not 'mamba' or 'attention'")
    return out


def num_params(dims: dict) -> int:
    n = dims["vocab_size"] * dims["hidden_size"] + dims["hidden_size"]
    for kind in dims["layer_types"]:
        n += sum(math.prod(s) for s in layer_shapes(dims, kind).values())
    return n


def _draw(key, name: str, shape: tuple, hidden: int):
    noise = jax.random.normal(key, shape, jnp.float32)
    if name in _GAINS:
        x = 1.0 + 0.1 * noise
    elif name == "A_log":
        x = jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, minval=math.log(0.01), maxval=math.log(0.5)))
        x = dt + jnp.log(-jnp.expm1(-dt))          # inverse softplus
    elif name == "D":
        x = 1.0 + 0.5 * noise
    else:
        x = _SPREAD.get(name, 1.0 / math.sqrt(hidden)) * noise
    return x.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("kind", "dims_key"))
def _make_layer(key, kind, dims_key):
    dims = dict(dims_key)
    return {name: _draw(jax.random.fold_in(key, i), name, shape,
                        dims["hidden_size"])
            for i, (name, shape) in enumerate(
                sorted(layer_shapes(dims, kind).items()))}


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_ends(key, dims_key):
    dims = dict(dims_key)
    d = dims["hidden_size"]
    return {"wte": _draw(jax.random.fold_in(key, 0), "wte",
                         (dims["vocab_size"], d), d),
            "lnf_g": _draw(jax.random.fold_in(key, 1), "lnf_g", (d,), d)}


def make_weights(seed: int, dims: dict) -> dict:
    """Every weight from the seed: bfloat16 arrays on the default device,
    `{"wte", "lnf_g", "layers": [one dict a layer]}`."""
    key, frozen = seed_key(seed), _frozen(dims)
    out = _make_ends(jax.random.fold_in(key, 0), frozen)
    out["layers"] = [
        _make_layer(jax.random.fold_in(key, 1 + l), kind, frozen)
        for l, kind in enumerate(dims["layer_types"])]
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


def _mamba(u, lw, dims: dict, precision: str):
    """u [S, hidden] -> [S, hidden]: the recurrence position by position."""
    s = u.shape[0]
    inner, gn = _mamba_widths(dims)
    heads, hd, n = (dims["mamba_n_heads"], dims["mamba_d_head"],
                    dims["mamba_d_state"])
    groups, taps = dims["mamba_n_groups"], dims["mamba_d_conv"]
    zxd = _mm(u, lw["in_proj"], precision)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn],
                  zxd[:, 2 * inner + 2 * gn:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    w = lw["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(
        sum(padded[k:k + s] * w[k] for k in range(taps))
        + lw["conv_b"].astype(jnp.float32))
    x = xbc[:, :inner].reshape(s, heads, hd)
    per = heads // groups
    b = xbc[:, inner:inner + gn].reshape(s, groups, n)
    c = xbc[:, inner + gn:].reshape(s, groups, n)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))  # [S, H]
    a = jnp.exp(-jnp.exp(lw["A_log"].astype(jnp.float32)) * dt)

    def step(state, t):
        x_t, b_t, c_t, dt_t, a_t = t       # B and C are a group's: [G, N]
        b_t, c_t = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)
        state = (a_t[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, b, c, dt, a))
    y = y + lw["D"].astype(jnp.float32)[:, None] * x
    y = _rms_norm(y.reshape(s, inner) * jax.nn.silu(z), lw["norm_g"],
                  dims["rms_norm_eps"])
    return _mm(y, lw["out_proj"], precision)


def _attention(u, lw, dims: dict, precision: str):
    """u [S, hidden] -> [S, hidden]: causal grouped-query attention with
    no positions, a block of queries at a time."""
    s, d = u.shape
    nh, nkv = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd = d // nh
    q = _mm(u, lw["wq"].reshape(d, nh * hd), precision).reshape(s, nh, hd)
    k = _mm(u, lw["wk"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    v = _mm(u, lw["wv"].reshape(d, nkv * hd), precision).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, 1).transpose(1, 2, 0)     # [H, hd, S]
    v = jnp.repeat(v, nh // nkv, 1).transpose(1, 0, 2)     # [H, S, hd]
    bq = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, 0).transpose(1, 0, 2)
        scores = _mm(qi, k, precision) * dims["attention_multiplier"]
        rows = i * bq + jnp.arange(bq)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, -1), v, precision)   # [H, bq, hd]

    out = jax.lax.map(block, jnp.arange(s // bq))              # [n,H,bq,hd]
    out = out.transpose(0, 2, 1, 3).reshape(s, nh * hd)
    return _mm(out, lw["wo"].reshape(nh * hd, d), precision)


def _swiglu(v, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.silu(_mm(v, w_gate, precision))
               * _mm(v, w_up, precision), w_down, precision)


def _moe(v, lw, dims: dict, precision: str):
    """v [S, hidden] -> (the held experts' part plus the shared expert
    [S, hidden], the chosen experts [S, k], sorted)."""
    k = dims["num_experts_per_tok"]
    first, end = dims["held_experts"]
    logits = _mm(v, lw["router"], precision)                   # [S, E]
    top, chosen = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, -1)

    def one(acc, expert):
        e, w_gate, w_up, w_down = expert
        g_e = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)  # [S]
        return acc + g_e[:, None] * _swiglu(v, w_gate, w_up, w_down,
                                            precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(v),
        (jnp.arange(first, end), lw["w_gate"], lw["w_up"], lw["w_down"]))
    shared = _swiglu(v, lw["s_gate"], lw["s_up"], lw["s_down"], precision)
    return routed + shared, jnp.sort(chosen, -1)


@functools.partial(jax.jit, static_argnames=("kind", "dims_key", "precision"))
def _layer(h, lw, kind, dims_key, precision):
    dims = dict(dims_key)
    eps, r = dims["rms_norm_eps"], dims["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    h = h + r * mixer(_rms_norm(h, lw["ln1_g"], eps), lw, dims, precision)
    out, chosen = _moe(_rms_norm(h, lw["ln2_g"], eps), lw, dims, precision)
    return h + r * out, chosen


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _embed(wte, tokens, dims_key):
    return wte[tokens].astype(jnp.float32) * dict(dims_key)[
        "embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _head(h, wte, lnf_g, dims_key, precision):
    dims = dict(dims_key)
    h = _rms_norm(h, lnf_g, dims["rms_norm_eps"])
    return _mm(h, wte.T, precision) / dims["logits_scaling"]


def forward(w: dict, tokens, dims: dict, precision: str = "highest",
            routes: bool = False):
    """[S] token ids -> [S, vocab] float32 logits; with `routes` also the
    experts every position chose in every layer, [layers, S, k] sorted."""
    frozen = _frozen(dims)
    h = _embed(w["wte"], jnp.asarray(tokens), frozen)
    chosen = []
    for kind, lw in zip(dims["layer_types"], w["layers"]):
        h, c = _layer(h, lw, kind, frozen, precision)
        chosen.append(c)
    logits = _head(h, w["wte"], w["lnf_g"], frozen, precision)
    return (logits, jnp.stack(chosen)) if routes else logits


# ---------------------------------------------------------------------------
# the program's parameter tree (models/gpt.py::GPT with `mixers`, flax
# names): the same arrays, re-nested
# ---------------------------------------------------------------------------

def to_program_params(w: dict, dims: dict) -> dict:
    decoder = {"ln_final": {"scale": w["lnf_g"]}}
    for l, (kind, lw) in enumerate(zip(dims["layer_types"], w["layers"])):
        block = {
            "ln_attn": {"scale": lw["ln1_g"]},
            "ln_mlp": {"scale": lw["ln2_g"]},
            "moe": {"router": {"kernel": lw["router"]},
                    "experts_gate": lw["w_gate"], "experts_fc1": lw["w_up"],
                    "experts_fc2": lw["w_down"],
                    "shared_gate": {"kernel": lw["s_gate"]},
                    "shared_fc1": {"kernel": lw["s_up"]},
                    "shared_fc2": {"kernel": lw["s_down"]}},
        }
        if kind == "mamba":
            block["mamba"] = {
                "in_proj": {"kernel": lw["in_proj"]},
                "conv_kernel": lw["conv_w"], "conv_bias": lw["conv_b"],
                "A_log": lw["A_log"], "D": lw["D"],
                "dt_bias": lw["dt_bias"], "norm_scale": lw["norm_g"],
                "out_proj": {"kernel": lw["out_proj"]}}
        else:
            block["attn"] = {"query": {"kernel": lw["wq"]},
                             "key": {"kernel": lw["wk"]},
                             "value": {"kernel": lw["wv"]},
                             "out": {"kernel": lw["wo"]}}
        decoder[f"block_{l}"] = block
    return {"wte": {"embedding": w["wte"]}, "decoder": decoder}


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


def _gaps(w, prompt, served, picks, dims, pad_to, precision):
    full, where = _padded(prompt, served, pad_to)
    at = np.zeros(pad_to, np.int32)
    at[where] = np.asarray(picks, np.int32)
    logits, chosen = forward(w, full, dims, precision, routes=True)
    gap, first, span = jax.device_get(_picked_gaps(logits, jnp.asarray(at)))
    n = where.stop + 1
    return gap[where], first[where], float(span), np.asarray(chosen[:, :n])


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
