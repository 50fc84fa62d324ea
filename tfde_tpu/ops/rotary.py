"""Rotary position embeddings (RoFormer/RoPE) — the positional scheme of
the modern decoder families (LLaMA/GPT-NeoX lineage).

Instead of adding learned absolute positions to the embedding stream
(models/gpt.py `wpe`), RoPE rotates each (even, odd) feature pair of the
query/key heads by an angle proportional to the token's absolute position;
the q.k dot product then depends only on RELATIVE position — better length
extrapolation, no learned position table, and a natural fit for the KV
cache (a cached key's rotation never changes, so decode steps rotate only
the new token; models/transformer.py passes the cache offset as
`positions`).

TPU shape notes: operates on [B, S, H, D] with D even, as two half-feature
blocks (the GPT-NeoX/LLaMA "rotate_half" convention — contiguous halves
vectorize on the VPU; the interleaved original is a permutation of the
same math). Everything is elementwise over S, so XLA partitions it
transparently under any mesh, including the 'seq' ring."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def scale_frequencies(freqs: jax.Array, scaling,
                      theta: float = 10_000.0) -> jax.Array:
    """RoPE frequency rescaling for long-context fine-tunes.

    `scaling` is a tuple (hashable — it lives on flax module configs):
      ('linear', factor) — position-interpolation (Llama-2-long style):
          every frequency divided by factor.
      ('llama3', factor, low_freq_factor, high_freq_factor,
       original_max_position) — the Llama-3.1 rule (HF
       `_compute_llama3_parameters` math): wavelengths shorter than
       original_max/high_freq_factor keep their frequency, longer than
       original_max/low_freq_factor divide by factor, and the band
       between interpolates smoothly.
      ('yarn', factor, beta_fast, beta_slow, original_max,
       attention_factor, truncate) — NTK-by-parts (HF
       `_compute_yarn_parameters` math): dimensions rotating faster than
       beta_fast turns over the original context keep their frequency
       (extrapolation), slower than beta_slow divide by factor
       (interpolation), with a linear ramp between; attention_factor
       additionally scales cos/sin (applied in rotary_angles).
       `theta` must be the same base the frequencies were built with —
       the correction range is computed in its log space.
    """
    import math

    kind = scaling[0]
    if kind == "linear":
        return freqs / float(scaling[1])
    if kind == "yarn":
        _, factor, beta_fast, beta_slow, orig_max, _att, truncate = scaling
        factor = float(factor)
        dim = freqs.shape[0] * 2

        def corr_dim(num_rot: float) -> float:
            return (dim * math.log(float(orig_max)
                                   / (num_rot * 2 * math.pi))
                    ) / (2 * math.log(theta))

        low = corr_dim(float(beta_fast))
        high = corr_dim(float(beta_slow))
        if truncate:
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, dim - 1)
        if low == high:
            high += 0.001  # prevent singularity (the HF guard)
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        extrapolation_factor = 1.0 - ramp
        return (freqs / factor) * (1.0 - extrapolation_factor) \
            + freqs * extrapolation_factor
    if kind == "llama3":
        _, factor, low_f, high_f, orig_max = scaling
        factor, low_f, high_f = float(factor), float(low_f), float(high_f)
        orig_max = float(orig_max)
        wavelen = 2.0 * math.pi / freqs
        low_wl = orig_max / low_f
        high_wl = orig_max / high_f
        smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
        interpolated = (1.0 - smooth) * freqs / factor + smooth * freqs
        return jnp.where(
            wavelen < high_wl, freqs,
            jnp.where(wavelen > low_wl, freqs / factor, interpolated),
        )
    raise ValueError(
        f"rope scaling kind must be 'linear', 'llama3' or 'yarn', "
        f"got {kind!r}"
    )


def attention_temperature(scaling) -> float:
    """yarn's attention temperature, the `attention_factor` of its tuple
    (1.0 for every other scaling and for none). `rotary_angles` folds it
    into cos and sin, which scales the rotated features alone; a caller
    whose scores add a part that is not rotated (latent attention:
    models/transformer.py `LatentAttention`) asks for the tables as they
    are (`fold_temperature=False`) and puts this on the whole score."""
    if scaling is not None and scaling[0] == "yarn":
        return float(scaling[5])
    return 1.0


def yarn_temperature(factor: float, mscale: float = 1.0) -> float:
    """The temperature the yarn family derives from its factor:
    0.1 x mscale x ln(factor) + 1 (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_angles(positions: jax.Array, dim: int,
                  theta: float = 10_000.0, scaling=None,
                  fold_temperature: bool = True) -> tuple:
    """(cos, sin) [..., dim/2] for integer `positions` [...]."""
    if dim % 2:
        raise ValueError(f"rotary head_dim must be even, got {dim}")
    freqs = theta ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )  # [dim/2]
    if scaling is not None:
        freqs = scale_frequencies(freqs, scaling, theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., dim/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if fold_temperature and attention_temperature(scaling) != 1.0:
        # yarn's attention temperature: cos/sin scale by the attention
        # factor (HF multiplies the cached cos/sin the same way)
        att = attention_temperature(scaling)
        cos, sin = cos * att, sin * att
    return cos, sin


def apply_rotary(x: jax.Array, positions: jax.Array,
                 theta: float = 10_000.0,
                 rotary_dim=None, scaling=None,
                 fold_temperature: bool = True) -> jax.Array:
    """Rotate [B, S, H, D] by per-token angles; `positions` is [S] or
    [B, S] absolute token positions. fp32 trig, result in x.dtype.

    rotary_dim: PARTIAL rotary (the Phi/GPT-NeoX partial_rotary_factor
    convention) — only the first `rotary_dim` features rotate, the rest
    pass through untouched. None/D = full rotation.

    scaling: RoPE frequency rescaling tuple (see scale_frequencies) —
    the Llama-3.1 long-context convention. `fold_temperature=False`
    leaves yarn's temperature to the caller (`attention_temperature`)."""
    d = x.shape[-1]
    if rotary_dim is not None and rotary_dim != d:
        if not 0 < rotary_dim < d:
            raise ValueError(
                f"rotary_dim {rotary_dim} must be in (0, head_dim={d}]"
            )
        rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate(
            [apply_rotary(rot, positions, theta, scaling=scaling,
                          fold_temperature=fold_temperature), rest],
            axis=-1,
        )
    cos, sin = rotary_angles(positions, d, theta, scaling,
                             fold_temperature)  # [..., S, d/2]
    # broadcast to [B, S, 1, d/2] over heads
    if cos.ndim == 2:  # [S, d/2] -> [1, S, 1, d/2]
        cos, sin = cos[None, :, None], sin[None, :, None]
    else:  # [B, S, d/2] -> [B, S, 1, d/2]
        cos, sin = cos[:, :, None], sin[:, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)
