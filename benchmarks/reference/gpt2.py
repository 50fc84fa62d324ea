"""GPT-2, plainly: the forward pass, the next-token loss, its gradients and
AdamW in straightforward `jax.numpy` and float32, with no kernel, no cache
and no batching tricks. It follows Radford et al. 2019 and the published
`config.json` keys (`n_layer`, `n_embd`, `n_head`, `n_positions`,
`vocab_size`, `layer_norm_epsilon`, `activation_function` = gelu_new):
pre-LayerNorm blocks, learned absolute positions, causal attention scaled
by 1/sqrt(head size), a 4x MLP with the tanh GELU, and a head tied to the
token embedding.

It imports nothing of the program and takes nothing the program has made.
Weights come from `make_weights(seed, dims)` alone; the driver hands the
same arrays to the program through `to_program_params`.

Departures from the published model, each for the comparison's sake:
- Biases and LayerNorm gains are drawn from the seed too (N(0, 0.02) and
  1 + N(0, 0.02)), not 0 and 1, so that a bias or a gain handled wrongly
  shows in the logits. Matrices are N(0, 0.02), the two residual
  projections scaled by 1/sqrt(2 n_layer), as the paper says.
- Layers are stacked along a leading axis and run under `lax.scan` with a
  per-layer `jax.checkpoint`, and attention runs over blocks of queries,
  so that S=4096 in float32 fits beside nothing else on a 16 GB chip. The
  arithmetic is the plain one.

`precision` selects how every matrix product is computed: "highest" is the
reference (float32, `Precision.HIGHEST`: six bf16 passes on a TPU); "bf16"
and "fp8" round both operands first (fp8: e4m3 under a per-tensor scale,
and e5m2 for the incoming gradient in the backward pass). They exist
for the control: the reference put in the program's place in the nearest
precision below the one the configuration states must come out as not
correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("ln1_g", "ln1_b", "attn_w", "attn_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "fc_w", "fc_b", "mlp_proj_w", "mlp_proj_b")
DECAYED = ("wte", "wpe", "attn_w", "proj_w", "fc_w", "mlp_proj_w")
# leaves outside the blocks: their gradients pass through the head alone
HEAD_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b")
PRECISIONS = ("highest", "bf16", "fp8")
DIM_KEYS = ("n_layer", "n_embd", "n_head", "n_inner", "n_positions",
            "vocab_size", "layer_norm_epsilon")
_QUERY_BLOCK = 512


def dims_of(cfg: dict) -> dict:
    """The published sizes, read from the configuration file's top level."""
    return {k: cfg[k] for k in DIM_KEYS if cfg.get(k) is not None}


def _frozen(dims: dict) -> tuple:
    """The sizes as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in dims.items()
                        if isinstance(v, (int, float))))


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def shapes(dims: dict) -> dict:
    n, d, v, p = (dims["n_layer"], dims["n_embd"], dims["vocab_size"],
                  dims["n_positions"])
    m = dims.get("n_inner") or 4 * d
    return {
        "wte": (v, d), "wpe": (p, d),
        "ln1_g": (n, d), "ln1_b": (n, d),
        "attn_w": (n, d, 3 * d), "attn_b": (n, 3 * d),
        "proj_w": (n, d, d), "proj_b": (n, d),
        "ln2_g": (n, d), "ln2_b": (n, d),
        "fc_w": (n, d, m), "fc_b": (n, m),
        "mlp_proj_w": (n, m, d), "mlp_proj_b": (n, d),
        "lnf_g": (d,), "lnf_b": (d,),
    }


def num_params(dims: dict) -> int:
    return sum(math.prod(s) for s in shapes(dims).values())


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_weights(key, dims_key):
    dims = dict(dims_key)
    out = {}
    resid = 0.02 / math.sqrt(2 * dims["n_layer"])
    for i, (name, shape) in enumerate(sorted(shapes(dims).items())):
        k = jax.random.fold_in(key, i)
        std = resid if name in ("proj_w", "mlp_proj_w") else 0.02
        x = std * jax.random.normal(k, shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith("_g") else x
    return out


def make_weights(seed: int, dims: dict) -> dict:
    """Every weight from the seed, float32, on the default device, in one
    jitted call."""
    return _make_weights(seed_key(seed), _frozen(dims))


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _round_fp8(x, dtype):
    """Round to an 8-bit float under a per-tensor scale to its largest
    finite value, as fp8 recipes do."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_fp8(a, b):
    """a @ b with both operands in e4m3; in the backward pass the incoming
    gradient is in e5m2 (the usual fp8 training recipe)."""
    hi = jax.lax.Precision.HIGHEST
    return jnp.matmul(_round_fp8(a, jnp.float8_e4m3fn),
                      _round_fp8(b, jnp.float8_e4m3fn), precision=hi)


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _unbroadcast(x, shape):
    extra = x.ndim - len(shape)
    return x.sum(tuple(range(extra))) if extra else x


def _mm_fp8_bwd(saved, g):
    a, b = saved
    hi = jax.lax.Precision.HIGHEST
    g8 = _round_fp8(g, jnp.float8_e5m2)
    a8 = _round_fp8(a, jnp.float8_e4m3fn)
    b8 = _round_fp8(b, jnp.float8_e4m3fn)
    da = jnp.matmul(g8, jnp.swapaxes(b8, -1, -2), precision=hi)
    db = jnp.matmul(jnp.swapaxes(a8, -1, -2), g8, precision=hi)
    return _unbroadcast(da, a.shape), _unbroadcast(db, b.shape)


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        return _mm_fp8(a, b)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, precision: str):
    """Causal softmax(q k^T / sqrt(d)) v over [B, H, S, d], a block of
    queries at a time."""
    b, h, s, d = q.shape
    bq = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    kt = jnp.swapaxes(k, -1, -2)
    cols = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)
        scores = _mm(qi, kt, precision) / math.sqrt(d)
        rows = i * bq + jnp.arange(bq)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, -1), v, precision)

    out = jax.lax.map(block, jnp.arange(s // bq))      # [nb, B, H, bq, d]
    return jnp.moveaxis(out, 0, 2).reshape(b, h, s, d)


def _block(x, lw, n_head: int, eps: float, precision: str):
    b, s, d = x.shape
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(h, lw["attn_w"], precision) + lw["attn_b"]
    q, k, v = (t.reshape(b, s, n_head, d // n_head).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, -1))
    a = _attention(q, k, v, precision).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _mm(a, lw["proj_w"], precision) + lw["proj_b"]
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
    h = _gelu_new(_mm(h, lw["fc_w"], precision) + lw["fc_b"])
    return x + _mm(h, lw["mlp_proj_w"], precision) + lw["mlp_proj_b"]


def forward(w: dict, tokens, dims: dict, precision: str = "highest"):
    """[B, S] token ids -> [B, S, vocab] float32 logits."""
    s = tokens.shape[1]
    eps = dims["layer_norm_epsilon"]
    x = w["wte"][tokens] + w["wpe"][:s][None]
    layer = jax.checkpoint(
        lambda x, lw: (_block(x, lw, dims["n_head"], eps, precision), None))
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in STACKED})
    x = _layer_norm(x, w["lnf_g"], w["lnf_b"], eps)
    return _mm(x, w["wte"].T, precision)


def next_token_loss(w: dict, tokens, dims: dict, precision: str = "highest"):
    """Mean cross-entropy of token t+1 given the tokens up to t."""
    logits = forward(w, tokens, dims, precision)[:, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


# ---------------------------------------------------------------------------
# training: gradients row by row, then AdamW with decay masked off biases
# and gains (optax.adamw's order: Adam's direction, plus decay, times rate)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _row_grad(w, row, dims_key, precision):
    return jax.value_and_grad(next_token_loss)(
        w, row[None], dict(dims_key), precision)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, part, weight):
    return jax.tree.map(lambda t, p: t + weight * p, total, part)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                   static_argnames=("lr", "b1", "b2", "eps", "wd"))
def _adamw(w, g, m, v, t, lr, b1, b2, eps, wd):
    new_w, new_m, new_v = {}, {}, {}
    for name in w:
        new_m[name] = b1 * m[name] + (1 - b1) * g[name]
        new_v[name] = b2 * v[name] + (1 - b2) * jnp.square(g[name])
        step = (new_m[name] / (1 - b1 ** t)) / (
            jnp.sqrt(new_v[name] / (1 - b2 ** t)) + eps)
        if name in DECAYED:
            step = step + wd * w[name]
        new_w[name] = w[name] - lr * step
    return new_w, new_m, new_v


@jax.jit
def leaf_norms(w: dict) -> dict:
    """The norm of every leaf, layer by layer: a stacked leaf gives one
    norm per layer, and the fused q/k/v projection one per layer and part,
    as the program keeps them apart."""
    out = {}
    for name, x in w.items():
        x = x.astype(jnp.float32)
        if name == "attn_w":
            n, d, _ = x.shape
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(n, d, 3, d)), (1, 3)))
        elif name == "attn_b":
            n = x.shape[0]
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(n, 3, -1)), 2))
        elif name in STACKED:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(x.shape[0], -1)), 1))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


@jax.jit
def _delta(a: dict, b: dict) -> dict:
    return {k: a[k] - b[k] for k in a}


def delta_norms(after: dict, before: dict) -> dict:
    return leaf_norms(_delta(after, before))


def train_steps(w: dict, batches: list, dims: dict, hyper: dict,
                precision: str = "highest", devices=None) -> dict:
    """Follow the first len(batches) steps from weights `w` (consumed).
    Each batch is [B, S] int32 on the host; its rows' gradients are worked
    out one row at a time, dealt round to `devices` (a cell's chips, so
    that four chips follow eight rows in the time one follows two) and
    summed on the first. Returns each step's loss, the leaf norms of the
    first step's gradient, and the leaf norms of the weights' change after
    the last step, all as numpy."""
    dk = _frozen(dims)
    devices = list(devices) if devices else [None]
    home = devices[0]
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    start = jax.tree.map(jnp.copy, w)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        rows = np.asarray(batch, np.int32)
        copies = [w] + [jax.device_put(w, d) for d in devices[1:]]
        sums, row_losses = [None] * len(devices), []
        for i, row in enumerate(rows):
            k = i % len(devices)
            loss, g = _row_grad(copies[k], jax.device_put(row, devices[k]),
                                dk, precision)
            row_losses.append(loss)
            sums[k] = (jax.tree.map(lambda x: x / len(rows), g)
                       if sums[k] is None
                       else _accumulate(sums[k], g, 1.0 / len(rows)))
        total = sums[0]
        for part in sums[1:]:
            if part is not None:
                total = _accumulate(total, jax.device_put(part, home), 1.0)
        losses.append(sum(float(x) for x in row_losses) / len(rows))
        if t == 1:
            grad_norms = jax.device_get(leaf_norms(total))
        del copies, sums
        w, m, v = _adamw(w, total, m, v, float(t), hyper["learning_rate"],
                         hyper["b1"], hyper["b2"], hyper["eps"],
                         hyper["weight_decay"])
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta_norms(w, start))}


def norm_gaps(got: dict, want: dict) -> dict:
    """Leaf by leaf, the gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    flat = np.concatenate([np.ravel(want[k]) for k in sorted(want)])
    floor = float(np.median(flat))
    out = {}
    for k in want:
        ref = np.asarray(want[k], np.float64)
        gap = np.abs(np.asarray(got[k], np.float64) - ref)
        out[k] = gap / np.maximum(ref, floor)
    return out


def worst_norm_gap(got: dict, want: dict, leaves=None,
                   updates: bool = False) -> tuple:
    """(the worst leaf's gap, its name), over `leaves` or all of them.
    With `updates`, the key bias is left out: its gradient is identically
    zero (softmax does not see a shift common to all keys), so Adam turns
    its rounding noise into a full-sized update that no two computations
    share."""
    gaps = norm_gaps(got, want)
    if updates:
        gaps["attn_b"] = np.array(gaps["attn_b"])
        gaps["attn_b"][..., 1] = 0.0
    if leaves is not None:
        gaps = {k: v for k, v in gaps.items() if k in leaves}
    name = max(gaps, key=lambda k: float(np.max(gaps[k])))
    where = np.unravel_index(int(np.argmax(gaps[name])), gaps[name].shape)
    return float(np.max(gaps[name])), f"{name}{list(map(int, where))}"


# ---------------------------------------------------------------------------
# the program's parameter tree (models/gpt.py::GPT, flax names): a
# rearrangement of the same numbers, and back
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_head",))
def to_program_params(w: dict, n_head: int) -> dict:
    n, d, _ = w["attn_w"].shape
    hd = d // n_head
    decoder = {"ln_final": {"scale": w["lnf_g"], "bias": w["lnf_b"]}}
    for l in range(n):
        q, k, v = jnp.split(w["attn_w"][l], 3, -1)
        qb, kb, vb = jnp.split(w["attn_b"][l], 3, -1)
        decoder[f"block_{l}"] = {
            "ln_attn": {"scale": w["ln1_g"][l], "bias": w["ln1_b"][l]},
            "attn": {
                "query": {"kernel": q.reshape(d, n_head, hd),
                          "bias": qb.reshape(n_head, hd)},
                "key": {"kernel": k.reshape(d, n_head, hd),
                        "bias": kb.reshape(n_head, hd)},
                "value": {"kernel": v.reshape(d, n_head, hd),
                          "bias": vb.reshape(n_head, hd)},
                "out": {"kernel": w["proj_w"][l].reshape(n_head, hd, d),
                        "bias": w["proj_b"][l]},
            },
            "ln_mlp": {"scale": w["ln2_g"][l], "bias": w["ln2_b"][l]},
            "mlp": {"fc1": {"kernel": w["fc_w"][l], "bias": w["fc_b"][l]},
                    "fc2": {"kernel": w["mlp_proj_w"][l],
                            "bias": w["mlp_proj_b"][l]}},
        }
    return {"wte": {"embedding": w["wte"]}, "wpe": {"embedding": w["wpe"]},
            "decoder": decoder}


@jax.jit
def from_program_params(p: dict) -> dict:
    dec = p["decoder"]
    n = sum(1 for k in dec if k.startswith("block_"))
    d = p["wte"]["embedding"].shape[1]
    blocks = [dec[f"block_{l}"] for l in range(n)]

    def stack(get):
        return jnp.stack([get(b) for b in blocks])

    return {
        "wte": p["wte"]["embedding"], "wpe": p["wpe"]["embedding"],
        "lnf_g": dec["ln_final"]["scale"], "lnf_b": dec["ln_final"]["bias"],
        "ln1_g": stack(lambda b: b["ln_attn"]["scale"]),
        "ln1_b": stack(lambda b: b["ln_attn"]["bias"]),
        "attn_w": stack(lambda b: jnp.concatenate(
            [b["attn"][k]["kernel"].reshape(d, d)
             for k in ("query", "key", "value")], -1)),
        "attn_b": stack(lambda b: jnp.concatenate(
            [b["attn"][k]["bias"].reshape(d)
             for k in ("query", "key", "value")], -1)),
        "proj_w": stack(lambda b: b["attn"]["out"]["kernel"].reshape(d, d)),
        "proj_b": stack(lambda b: b["attn"]["out"]["bias"]),
        "ln2_g": stack(lambda b: b["ln_mlp"]["scale"]),
        "ln2_b": stack(lambda b: b["ln_mlp"]["bias"]),
        "fc_w": stack(lambda b: b["mlp"]["fc1"]["kernel"]),
        "fc_b": stack(lambda b: b["mlp"]["fc1"]["bias"]),
        "mlp_proj_w": stack(lambda b: b["mlp"]["fc2"]["kernel"]),
        "mlp_proj_b": stack(lambda b: b["mlp"]["fc2"]["bias"]),
    }


# ---------------------------------------------------------------------------
# serving: one full forward over a prompt with its served tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _sequence_gaps(w, tokens, nxt, dims_key, precision):
    logits = forward(w, tokens[None], dict(dims_key), precision)[0]
    best = logits.max(-1)
    chosen = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    return best - chosen, logits.argmax(-1), jnp.abs(logits).max()


@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _sequence_gaps_of(w, tokens, picks, dims_key, precision):
    logits = forward(w, tokens[None], dict(dims_key), precision)[0]
    chosen = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
    return logits.max(-1) - chosen


def _padded(prompt, served, pad_to: int) -> tuple:
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    full = np.zeros(pad_to, np.int32)
    n = prompt.size + served.size
    full[:n] = np.concatenate([prompt, served])
    nxt = np.zeros(pad_to, np.int32)
    nxt[prompt.size - 1:n - 1] = served     # position P-1+i predicts token i
    return full, nxt, slice(prompt.size - 1, n - 1)


def served_token_gaps(w: dict, prompt, served, dims: dict, pad_to: int,
                      precision: str = "highest") -> dict:
    """One forward over prompt + served tokens (padded to `pad_to`; causal,
    so the padding is never seen; one shape, so one program). Returns, per
    served token, how far its logit lies below the best logit at its
    position (`gap`) and the first choice there (`argmax`), and the
    logits' largest magnitude (`range`), as numpy."""
    full, nxt, where = _padded(prompt, served, pad_to)
    gap, first, span = jax.device_get(_sequence_gaps(
        w, jnp.asarray(full), jnp.asarray(nxt), _frozen(dims), precision))
    return {"gap": gap[where], "argmax": first[where], "range": float(span)}


def gaps_of_choices(w: dict, prompt, served, choices, dims: dict,
                    pad_to: int) -> np.ndarray:
    """For the control: at each served position of the same prompt and
    tokens, how far the reference's logit of `choices[i]` (what a lower
    precision put first there) lies below the reference's best."""
    full, _, where = _padded(prompt, served, pad_to)
    picks = np.zeros(pad_to, np.int32)
    picks[where] = np.asarray(choices, np.int32)
    gap = jax.device_get(_sequence_gaps_of(
        w, jnp.asarray(full), jnp.asarray(picks), _frozen(dims), "highest"))
    return gap[where]
