"""EvaByte, plainly: the forward pass of a byte-level decoder with EVA
chunked linear attention in straightforward `jax.numpy` and float32, with
no kernel, no cache and no batching. It follows the published
`config.json` keys (`hidden_size`, `intermediate_size`,
`num_attention_heads`, `num_hidden_layers`, `vocab_size`, `rms_norm_eps`,
`rope_theta`, `window_size`, `chunk_size`, `norm_add_unit_offset`,
`fp32_skip_add`, `fp32_logits`) and, for what the config does not say, EVA
(Zheng et al., arXiv:2302.04542) as the release applies it.

The block: x + Attn(norm(x)), then x + MLP(norm(x)); norm(x) =
x / rms(x) * (1 + g); MLP = down(silu(gate(x)) * up(x)); no bias anywhere;
an untied head on the final norm. The attention of one head of width d,
s = d^-1/2, window W, chunk C, q and k rotated (RoPE, halves paired, at
absolute positions); w(i) = i // W:

    a_j    = softmax_{j in chunk c}(s k_j . phi)
    kbar_c = sum_j a_j k_j + mu          vbar_c = sum_j a_j v_j
    L_i = {j : w(j) = w(i), j <= i}      R_i = {c : cC + C - 1 < w(i) W}
    o_i = (sum_L e^{s q_i.k_j} v_j + sum_R e^{s q_i.kbar_c} vbar_c)
          / (sum_L e^{s q_i.k_j} + sum_R e^{s q_i.kbar_c})

It imports nothing of the program (not `tfde_tpu/ops/eva_attention.py`
either) and takes nothing the program has made. Weights come from
`make_weights(seed, dims)` alone; the driver hands the same numbers to the
program through `to_program_params`.

Departures from the release, each for the comparison's sake or for want
of the release's code (no network here; the configuration file repeats
the second kind under `assumed`):
- Weights are drawn from the seed at the config's `init_std` (the two
  residual projections scaled by 1/sqrt(2 layers)) and rounded once to
  bfloat16, the deployment's dtype, so that both sides hold the same
  numbers and the gap measures the computation. `phi` is N(0, 1), `mu`
  N(0, 0.5) and the norm gains g N(0, 0.1), not 0: an implementation that
  drops one of them shows in the logits.
- Only prediction head 0 (the next byte) exists; the release's heads 1-7
  serve multi-byte decoding, which the program does not build.
- The pooling (a_j, mu added to kbar and not to vbar, one softmax over
  both sets, summaries visible from the next window on) is written from
  the paper and a reading of the release, not from its code.
- RoPE pairs feature i with i + d/2; a release that pairs neighbours
  differs by a fixed permutation of W_q and W_k's columns, which seeded
  weights cannot tell apart.
- Layers are stacked and run under `lax.scan`, and attention runs a block
  of queries at a time, so that 16,384 positions in float32 fit on a 16 GB
  chip beside nothing else. The arithmetic is the plain one.

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

STACKED = ("ln1_g", "wq", "wk", "wv", "wo", "phi", "mu", "ln2_g", "w_gate",
           "w_up", "w_down")
PRECISIONS = ("highest", "bf16", "fp8")
DIM_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
            "window_size", "chunk_size", "init_std")
_QUERY_BLOCK = 512


def dims_of(cfg: dict) -> dict:
    """The published sizes, read from the configuration file's top level."""
    return {k: cfg[k] for k in DIM_KEYS if cfg.get(k) is not None}


def _frozen(dims: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in dims.items()
                        if isinstance(v, (int, float))))


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def shapes(dims: dict) -> dict:
    n, d, m = (dims["num_hidden_layers"], dims["hidden_size"],
               dims["intermediate_size"])
    h, v = dims["num_attention_heads"], dims["vocab_size"]
    return {
        "wte": (v, d), "lm_head": (d, v), "lnf_g": (d,),
        "ln1_g": (n, d), "ln2_g": (n, d),
        "wq": (n, d, d), "wk": (n, d, d), "wv": (n, d, d), "wo": (n, d, d),
        "phi": (n, h, d // h), "mu": (n, h, d // h),
        "w_gate": (n, d, m), "w_up": (n, d, m), "w_down": (n, m, d),
    }


def num_params(dims: dict) -> int:
    return sum(math.prod(s) for s in shapes(dims).values())


@functools.partial(jax.jit, static_argnames=("dims_key",))
def _make_weights(key, dims_key):
    dims = dict(dims_key)
    std = dims.get("init_std", 0.02)
    spread = {"phi": 1.0, "mu": 0.5, "ln1_g": 0.1, "ln2_g": 0.1,
              "lnf_g": 0.1,
              "wo": std / math.sqrt(2 * dims["num_hidden_layers"]),
              "w_down": std / math.sqrt(2 * dims["num_hidden_layers"])}
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(dims).items())):
        x = spread.get(name, std) * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = x.astype(jnp.bfloat16).astype(jnp.float32)
    return out


def make_weights(seed: int, dims: dict) -> dict:
    """Every weight from the seed: float32 arrays holding bfloat16 values,
    on the default device, in one jitted call."""
    return _make_weights(seed_key(seed), _frozen(dims))


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _round_fp8(x):
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision: str):
    hi = jax.lax.Precision.HIGHEST
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
        1.0 + g)


def _rope(x, theta: float):
    """x [H, S, d] at positions 0 .. S-1; feature i turns with i + d/2."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _summaries(k, v, phi, mu, chunk: int):
    """k, v [H, S, d] -> kbar, vbar [H, S/C, d]."""
    h, s, d = k.shape
    kc = k.reshape(h, s // chunk, chunk, d)
    vc = v.reshape(h, s // chunk, chunk, d)
    a = jax.nn.softmax(
        jnp.sum(kc * phi[:, None, None, :], -1) / math.sqrt(d), axis=-1)
    kbar = jnp.sum(a[..., None] * kc, 2) + mu[:, None, :]
    return kbar, jnp.sum(a[..., None] * vc, 2)


def _attention(q, k, v, phi, mu, window: int, chunk: int, precision: str):
    """q, k, v [H, S, d], rotated, S a multiple of the window."""
    h, s, d = q.shape
    kbar, vbar = _summaries(k, v, phi, mu, chunk)
    bq = _QUERY_BLOCK if window % _QUERY_BLOCK == 0 else window
    nc = s // chunk
    cols = jnp.arange(window)
    chunks = jnp.arange(nc)

    def block(i):
        w = (i * bq) // window
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, w * window, window, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, w * window, window, axis=1)
        keys = jnp.concatenate([kw, kbar], 1)          # [H, W + S/C, d]
        vals = jnp.concatenate([vw, vbar], 1)
        scores = _mm(qi, jnp.swapaxes(keys, -1, -2), precision) / math.sqrt(d)
        rows = i * bq - w * window + jnp.arange(bq)
        seen = jnp.concatenate([
            cols[None, :] <= rows[:, None],
            jnp.broadcast_to(chunks * chunk + chunk - 1 < w * window,
                             (bq, nc))], 1)
        scores = jnp.where(seen, scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, -1), vals, precision)

    out = jax.lax.map(block, jnp.arange(s // bq))       # [n, H, bq, d]
    return jnp.moveaxis(out, 0, 1).reshape(h, s, d)


def _block(x, lw, dims: dict, precision: str):
    s, d = x.shape
    h = dims["num_attention_heads"]
    eps, theta = dims["rms_norm_eps"], float(dims["rope_theta"])
    y = _rms_norm(x, lw["ln1_g"], eps)
    q, k, v = (_mm(y, lw[n], precision).reshape(s, h, d // h).transpose(
        1, 0, 2) for n in ("wq", "wk", "wv"))
    a = _attention(_rope(q, theta), _rope(k, theta), v, lw["phi"], lw["mu"],
                   dims["window_size"], dims["chunk_size"], precision)
    x = x + _mm(a.transpose(1, 0, 2).reshape(s, d), lw["wo"], precision)
    y = _rms_norm(x, lw["ln2_g"], eps)
    y = jax.nn.silu(_mm(y, lw["w_gate"], precision)) * _mm(
        y, lw["w_up"], precision)
    return x + _mm(y, lw["w_down"], precision)


def forward(w: dict, tokens, dims: dict, precision: str = "highest"):
    """[S] byte ids -> [S, vocab] float32 logits of head 0."""
    s = tokens.shape[0]
    window = dims["window_size"]
    grown = -(-s // window) * window
    x = w["wte"][jnp.pad(tokens, (0, grown - s))]
    layer = jax.checkpoint(
        lambda x, lw: (_block(x, lw, dims, precision), None))
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in STACKED})
    x = _rms_norm(x, w["lnf_g"], dims["rms_norm_eps"])
    return _mm(x, w["lm_head"], precision)[:s]


# ---------------------------------------------------------------------------
# the program's parameter tree (models/gpt.py::GPT with attention="eva",
# flax names), in bfloat16 as the deployment holds it
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_head",))
def to_program_params(w: dict, n_head: int) -> dict:
    n, d, _ = w["wq"].shape
    hd = d // n_head
    b16 = lambda x: x.astype(jnp.bfloat16)   # exact: the values are bf16
    decoder = {"ln_final": {"scale": b16(w["lnf_g"])}}
    for l in range(n):
        decoder[f"block_{l}"] = {
            "ln_attn": {"scale": b16(w["ln1_g"][l])},
            "attn": {
                "query": {"kernel": b16(w["wq"][l]).reshape(d, n_head, hd)},
                "key": {"kernel": b16(w["wk"][l]).reshape(d, n_head, hd)},
                "value": {"kernel": b16(w["wv"][l]).reshape(d, n_head, hd)},
                "out": {"kernel": b16(w["wo"][l]).reshape(n_head, hd, d)},
                "eva_phi": b16(w["phi"][l]), "eva_mu": b16(w["mu"][l]),
            },
            "ln_mlp": {"scale": b16(w["ln2_g"][l])},
            "mlp": {"fc1": {"kernel": b16(w["w_up"][l])},
                    "gate": {"kernel": b16(w["w_gate"][l])},
                    "fc2": {"kernel": b16(w["w_down"][l])}},
        }
    return {"wte": {"embedding": b16(w["wte"])},
            "lm_head": {"kernel": b16(w["lm_head"])}, "decoder": decoder}


# ---------------------------------------------------------------------------
# serving: one full forward over a prompt with its served bytes
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims_key", "precision"))
def _sequence_gaps(w, tokens, picks, dims_key, precision):
    logits = forward(w, tokens, dict(dims_key), precision)
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
    gap, first, span = jax.device_get(_sequence_gaps(
        w, jnp.asarray(full), jnp.asarray(at), _frozen(dims), precision))
    return gap[where], first[where], float(span)


def served_token_gaps(w: dict, prompt, served, dims: dict, pad_to: int,
                      precision: str = "highest") -> dict:
    """One forward over prompt + served bytes, padded to `pad_to` (the
    layer is causal, chunk summaries included, so the padding is never
    seen). Per served byte: how far its logit lies below the best logit at
    its position (`gap`) and the first choice there (`argmax`); and the
    logits' largest magnitude (`range`), as numpy."""
    gap, first, span = _gaps(w, prompt, served, served, dims, pad_to,
                             precision)
    return {"gap": gap, "argmax": first, "range": span}


def gaps_of_choices(w: dict, prompt, served, choices, dims: dict,
                    pad_to: int) -> np.ndarray:
    """For the control: at each served position of the same prompt and
    bytes, how far the reference's logit of `choices[i]` (what a lower
    precision put first there) lies below the reference's best."""
    return _gaps(w, prompt, served, choices, dims, pad_to, "highest")[0]
