"""Attention ops — the hot kernel of the transformer scale-up configs.

The reference has no attention anywhere (its models are MNIST CNNs, SURVEY.md
§5 "long-context: entirely absent"); this exists for the driver's scale
configs (BASELINE.json: ViT-B/16 FSDP, BERT-base MLM) and the long-context
story (ring attention over a 'seq' mesh axis).

Three implementations behind one dispatcher:

- ``reference``: einsum + fp32 softmax. The numerics oracle; also what XLA
  fuses perfectly well at short sequence lengths.
- ``flash``: Pallas TPU forward + fused Pallas backward (ops/flash_attention.py)
  — online softmax, O(S) memory, MXU-shaped tiles. Auto-dispatch uses it
  on TPU from S>=2048 causal / S>=4096 non-causal (where its O(S) memory,
  not speed, is the win); the thresholds rest on a host-timed A/B older
  than the ledger, and S=4096 causal is the only length a benchmark cell
  runs (ROADMAP Speed 2). ``TFDE_FLASH=0`` disables; ``TFDE_FLASH=1``
  lowers both thresholds to S>=1024. Takes GQA shapes (k/v with fewer
  heads) directly — the kernel folds each q head onto its serving KV head.
- ``ring``: sequence-parallel blockwise attention over the mesh's 'seq' axis
  (ops/ring_attention.py) — KV blocks rotate around the ring via ppermute
  while compute overlaps, so sequence length scales with the number of chips.
  Takes GQA shapes too: the rotating KV shards stay kv_heads-sized, so the
  per-hop ICI transfer shrinks by the group factor.

Shapes follow the Flax convention: q/k/v are [batch, length, heads, head_dim].
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from tfde_tpu.parallel import axes as axes_lib


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    window: Optional[int] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Plain softmax(QK^T/sqrt(d))V with fp32 accumulation.

    mask: broadcastable to [B, H, Sq, Sk]; True/1 = attend. Additive -inf
    masking in fp32 keeps bf16 inputs numerically safe. window: sliding-
    window (Mistral-style) band — position i attends [i-window+1, i];
    requires causal=True. bias: additive pre-softmax score bias (see
    grouped_attention). scale/logit_cap: see grouped_attention.

    The numerics oracle every other kernel is tested against. Internally
    the degenerate (groups == 1) case of `grouped_attention` — ONE
    scale/mask/fp32-softmax implementation, so the oracle and the GQA
    decode path cannot drift.
    """
    return grouped_attention(q, k, v, mask=mask, causal=causal,
                             window=window, bias=bias, scale=scale,
                             logit_cap=logit_cap)


def grouped_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    window: Optional[int] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention: q [B,Sq,H,D] against k/v [B,Sk,Kv,D] with
    H = Kv * groups — each KV head serves a contiguous group of query heads.

    The einsums index the KV head directly (`bqkgd,bskd->bkgqs`), so the
    [B,Sk,H,D] expansion a repeat-then-attend formulation would write/read
    through HBM never exists — the point of GQA is exactly that bandwidth
    saving, largest on the decode path where K/V is the whole cache.

    mask: broadcastable to [B, H, Sq, Sk] (or with a size-1 head dim);
    True = attend, matching reference_attention.

    bias: additive pre-softmax score bias broadcastable to [B, H, Sq, Sk]
    (T5's relative position bias, models/t5.py) — added in fp32 AFTER the
    score scaling and BEFORE masking, matching the transformers ordering.

    scale: score multiplier; None = the standard 1/sqrt(d). T5 runs
    UNSCALED attention (the scale is folded into its init) — its module
    passes scale=1.0; Gemma-2 passes query_pre_attn_scalar^-0.5 — one
    einsum path for every convention.

    logit_cap: attention logit softcapping (Gemma-2):
    cap * tanh(score / cap) applied after scaling and bias, before the
    mask — bounds score magnitudes without the hard clip's dead
    gradient.
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads {kv}")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1 — the "
            f"sliding window is a band below the causal diagonal"
        )
    if logit_cap is not None and logit_cap <= 0:
        raise ValueError(
            f"logit_cap={logit_cap} must be > 0 (cap * tanh(score / cap) "
            f"divides by the cap) — same check as the flash kernel"
        )
    g = h // kv
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = q.reshape(b, sq, kv, g, d)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        if bias.ndim == 3:  # [H, Sq, Sk]
            bias = bias[None]
        if bias.ndim != 4:
            raise ValueError(
                f"bias must be broadcastable to [B,H,Sq,Sk] (ndim 3/4), "
                f"got ndim={bias.ndim}"
            )
        if bias.shape[1] == h:
            bias = bias.reshape(bias.shape[0], kv, g, *bias.shape[2:])
        else:  # size-1 head dim broadcasts over [kv, g]
            bias = bias[:, :, None]
        logits = logits + bias.astype(jnp.float32)
    if logit_cap is not None:
        logits = logit_cap * jnp.tanh(logits / logit_cap)
    if causal:
        cm = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        if window is not None:
            # rows are the LAST sq absolute positions (offset sk - sq, the
            # same alignment the causal tril uses): row i sees cols in
            # (i - window, i]
            rows = (sk - sq) + jnp.arange(sq)[:, None]
            cols = jnp.arange(sk)[None, :]
            cm = jnp.logical_and(cm, rows - cols < window)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    if mask is not None:
        if mask.ndim == 2:  # [Sq, Sk]
            mask = mask[None, None, None]
        elif mask.ndim == 3:  # [B|1, Sq, Sk]
            mask = mask[:, None, None]
        elif mask.ndim == 4:  # [B|1, H|1, Sq, Sk]
            if mask.shape[1] == h:
                mask = mask.reshape(mask.shape[0], kv, g, *mask.shape[2:])
            else:
                mask = mask[:, :, None]  # size-1 head dim broadcasts
        else:
            raise ValueError(
                f"mask must be broadcastable to [B,H,Sq,Sk] "
                f"(ndim 2/3/4), got ndim={mask.ndim}"
            )
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _seq_parallel_active() -> bool:
    if axes_lib.manual_seq_info() is not None:
        return True  # pp x sp: seq is a manual axis, no mesh to consult
    mesh = axes_lib.current_mesh()
    return mesh is not None and "seq" in mesh.axis_names and mesh.shape["seq"] > 1


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# impls whose kernels take scale/logit_cap natively. All three current
# impls do (flash applies the cap inside the fused forward AND backward);
# the capability check below is the warn-fallback safety net for any impl
# that loses (or ships without) cap support — the model keeps training on
# the grouped einsum instead of hard-refusing.
_KNOWN_IMPLS = ("reference", "flash", "ring")
_CAP_IMPLS = frozenset(_KNOWN_IMPLS)


def _flash_min_seq(causal: bool) -> Optional[int]:
    """Parse ``TFDE_FLASH`` into a minimum auto-dispatch sequence length.

    '0'/'false' disable the flash auto-pick (None); '1'/'true' lower both
    thresholds to 1024; ''/'auto' keep the r04-measured defaults (2048
    causal / 4096 non-causal). Any OTHER value used to fall through a
    ``.get(env, 1024)`` — a typo like ``TFDE_FLASH=ture`` silently
    LOWERED the threshold to 1024 instead of doing nothing; now it warns
    once per call site and falls back to auto."""
    import os

    env = os.environ.get("TFDE_FLASH", "auto")
    default_min = 2048 if causal else 4096
    table = {
        "0": None, "false": None, "False": None,
        "": default_min, "auto": default_min,
        "1": 1024, "true": 1024, "True": 1024,
    }
    if env in table:
        return table[env]
    import warnings

    warnings.warn(
        f"TFDE_FLASH={env!r} is not a recognized value (expected 0/false, "
        f"1/true, or auto); ignoring it — flash auto-dispatch keeps the "
        f"measured default (S >= {default_min})",
        stacklevel=3,
    )
    return default_min


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    impl: str = "auto",
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Dispatching attention: [B,S,H,D] -> [B,S,H,D].

    window: sliding-window band (Mistral convention — position i attends
    the last `window` positions inclusive, requires causal). Composes with
    every impl: 'reference' masks, 'flash' skips out-of-band tiles in the
    forward AND the backward (compute and DMA O(S * window) fwd+bwd — the
    backward scans only the statically in-band tile pairs), and 'ring'
    masks on global positions — the band is exact across shard boundaries,
    so sliding-window models train under sequence parallelism and pp x sp.

    scale: logit multiplier (None = 1/sqrt(d)); logit_cap: Gemma-2 tanh
    softcapping, cap * tanh(score / cap) before masking. Both compose with
    every impl — flash applies them inside the fused kernels, ring inside
    its online-softmax chunk step. If a selected impl ever lacks cap
    support (`_CAP_IMPLS`), dispatch warns and falls back to the grouped
    reference einsum instead of refusing.

    impl: 'auto' | 'reference' | 'flash' | 'ring'. 'auto' picks ring when the
    active mesh shards 'seq'; on TPU it picks flash for CAUSAL
    self-attention at S >= 2048 (no mask; the causal whole-tile skip is
    where the kernel wins) and for non-causal at S >= 4096, where the O(S)
    memory replaces the reference's O(S^2) score tensor, the binding
    constraint at long S. Below those, the reference einsum.
    ``TFDE_FLASH=0`` disables the flash auto-pick; ``TFDE_FLASH=1`` lowers
    both thresholds to S >= 1024.

    Inside a fully-manual region whose 'seq' axis is manual (the pp x sp
    pipeline, parallel/axes.manual_seq), dispatch goes straight to the
    per-shard ring body — there is no mesh to consult in there, and local
    attention over a seq shard would silently be the wrong math.
    """
    manual = axes_lib.manual_seq_info()
    if manual is not None:
        if impl not in ("auto", "ring"):
            # q/k/v here are per-shard sequence slices: any non-ring impl
            # would silently attend within the shard only — wrong math
            raise NotImplementedError(
                f"attn_impl={impl!r} inside a seq-manual region would "
                f"compute shard-local attention; use 'auto' or 'ring' "
                f"(the per-shard ring body) under pp x sp"
            )
        ring_size, vary_axes = manual
        if mask is not None:
            raise NotImplementedError(
                "ring attention inside a manual region supports causal "
                "masking only (key-padding masks would need a sharded "
                "validity plane threaded through the pipe)"
            )
        from tfde_tpu.ops import ring_attention as ra

        return ra.ring_attention_manual(
            q, k, v, causal=causal, ring_size=ring_size,
            vary_axes=vary_axes, window=window, scale=scale,
            logit_cap=logit_cap,
        )
    if impl == "auto":
        flash_min_seq = _flash_min_seq(causal)
        if _seq_parallel_active():
            impl = "ring"
        elif (
            _on_tpu()
            and flash_min_seq is not None
            and q.shape[1] >= flash_min_seq
            # self-attention, MHA or GQA (k/v may carry fewer heads)
            and q.shape[:2] == k.shape[:2]
            and q.shape[3] == k.shape[3]
            and q.shape[2] % k.shape[2] == 0
            and q.shape[1] % 128 == 0
            and mask is None
            # inside a partial-manual pipeline region (AbstractMesh) the
            # kernel's custom-VJP variance doesn't compose with a nested
            # shard_map; the reference einsum partitions fine there
            and not isinstance(
                axes_lib.current_mesh(), jax.sharding.AbstractMesh
            )
        ):
            impl = "flash"
        else:
            impl = "reference"
    if ((scale is not None or logit_cap is not None)
            and impl in _KNOWN_IMPLS and impl not in _CAP_IMPLS):
        import warnings

        warnings.warn(
            f"attention impl {impl!r} does not support scale/logit_cap; "
            f"falling back to the grouped reference einsum",
            stacklevel=2,
        )
        impl = "reference"
    if impl == "reference":
        return reference_attention(q, k, v, mask=mask, causal=causal,
                                   window=window, scale=scale,
                                   logit_cap=logit_cap)
    if impl == "flash":
        if mask is not None:
            raise NotImplementedError(
                "flash attention does not take an explicit mask; use "
                "impl='reference' (or 'auto', which refuses flash when a "
                "mask is present)"
            )
        return _flash_sharded(q, k, v, causal, window, scale, logit_cap)
    if impl == "ring":
        from tfde_tpu.ops import ring_attention

        return ring_attention.ring_attention(
            q, k, v, mask=mask, causal=causal, mesh=axes_lib.current_mesh(),
            window=window, scale=scale, logit_cap=logit_cap,
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def _flash_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool, window=None, scale=None,
                   logit_cap=None) -> jax.Array:
    """Call the Pallas flash kernel batch-parallel over the active mesh.

    A pallas_call under plain jit with sharded operands is NOT partitioned
    automatically — XLA gathers the inputs and replicates the whole kernel
    (measured: sharded-in, replicated-out), silently destroying data
    parallelism. Attention is embarrassingly parallel over batch (and over
    heads under TP), so when a concrete mesh is active we shard_map the
    kernel over those axes; each device runs flash on its own shard with
    zero communication. Falls back to the direct (replicating) call when no
    mesh is active, inside a fully-manual region (current_mesh is None
    there), or when the shapes don't divide. Inside a *partial-manual*
    region (AbstractMesh — the 3D pipe) flash is refused outright: the
    kernel's custom-VJP loses the pipe-variance annotations through a
    nested shard_map, so auto-dispatch picks the reference einsum there
    and an explicit impl='flash' errors with guidance."""
    from tfde_tpu.ops import flash_attention as fa

    # interpret on CPU only, for the fake-device test methodology; any
    # other non-TPU backend should fail loudly at Mosaic lowering rather
    # than silently run the orders-of-magnitude-slower interpreter
    interpret = jax.default_backend() == "cpu"
    mesh = axes_lib.current_mesh()
    if isinstance(mesh, jax.sharding.AbstractMesh):
        raise NotImplementedError(
            "flash attention inside a partial-manual pipeline region is not "
            "supported (the kernel's custom-VJP variance does not compose "
            "with a nested shard_map); use attn_impl='reference' (or 'auto', "
            "which picks it automatically) for pipelined models"
        )
    if not isinstance(mesh, jax.sharding.Mesh):
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=interpret, scale=scale,
                                  logit_cap=logit_cap)
    from jax.sharding import PartitionSpec as P

    from tfde_tpu.parallel.sharding import data_axes as _data_axes

    batch_axes = _data_axes(mesh)
    d = 1
    for a in batch_axes:
        d *= mesh.shape[a]
    heads = None
    if "tensor" in mesh.axis_names and mesh.shape["tensor"] > 1 \
            and q.shape[2] % mesh.shape["tensor"] == 0 \
            and k.shape[2] % mesh.shape["tensor"] == 0:
        # GQA: k/v heads must also divide (each shard keeps whole groups)
        heads = "tensor"
    if q.shape[0] % max(d, 1):
        batch_axes, d = (), 1
    if d <= 1 and heads is None:
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=interpret, scale=scale,
                                  logit_cap=logit_cap)
    spec = P(batch_axes if batch_axes else None, None, heads, None)
    fn = jax.shard_map(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, window=window, interpret=interpret,
            scale=scale, logit_cap=logit_cap
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call's out_shape carries no vma annotations; the kernel is
        # pure per-shard compute (no collectives), so the check adds nothing
        check_vma=False,
    )
    return fn(q, k, v)


def padding_mask(valid: jax.Array) -> jax.Array:
    """[B, S] 1/True-for-real-token -> [B, 1, 1, S] attention mask."""
    return valid.astype(jnp.bool_)[:, None, None, :]
