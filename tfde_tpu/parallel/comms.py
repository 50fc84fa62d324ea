"""Communication-efficient gradient exchange: quantized all-reduce with
error feedback (EQuARX-style, arxiv 2506.17615).

Every DP strategy in parallel/strategies.py exchanges gradients at full
fp32 width — the implicit `psum` the SPMD partitioner inserts moves
~8 bytes/param per step over a ring, which dominates step time on DCN-heavy
meshes (MultiWorkerMirroredStrategy spanning hosts). This module provides
the int8 transport behind the `grad_transport='fp32'|'int8'` knob:

1. the local per-device gradient contribution (plus the error-feedback
   residual carried in `TrainState.comm_residual`) is flattened, packed
   into ONE buffer, and blockwise absmax-quantized to int8 against a
   *shared* per-block scale (`pmax` of the local absmaxes — tiny fp32
   collective, 4/block bytes per element);
2. the int8 payload reduce-scatters over the data axis (`psum_scatter`;
   the int32 accumulator is exact: 127 x nshards fits easily);
3. each device dequantizes the partial sums of its owned chunk with the
   shared scales — exact, because every device quantized against the same
   scale — and re-quantizes them blockwise to int8;
4. the re-quantized chunks and their scales all-gather back, so every
   device reconstructs the *identical* averaged gradient (bit-equal across
   the ring — replicas cannot drift).

Total wire traffic: ~2 bytes/param (reduce-scatter + all-gather, both
int8) + ~8/block bytes of scales, vs ~8 bytes/param for the fp32 ring —
a >=70% cut, reported by `comm_bytes` and the `comm/*` gauges.

Error feedback: quantization error does not vanish, it is *carried*. Each
device keeps the part of its own contribution the quantizer dropped
(input-side error, plus the re-quantization error of the chunk it owns)
in `TrainState.comm_residual` and re-injects it into the next step's
transmission — the compressed SGD trajectory then tracks the fp32 oracle
(tests/test_comms.py asserts loss-trajectory parity on MNIST). The
residual is per-device state: it rides through jit as a nominally
replicated pytree whose per-device contents differ, which is safe because
it only ever re-enters this exchange (the exchange output is what touches
params, and that is bit-identical across devices). Quantization bias is
killed separately by stochastic rounding (ops/quant.py
`stochastic_round`), on by default.

Small leaves (< `min_elems`) skip quantization: their scale metadata would
cost more than the payload saves. They ride a single packed fp32 `psum`
together with the step's scalars (loss/metrics/weights), so the whole
exchange is a fixed five collectives regardless of model structure —
tests/test_comms.py pins the count from the lowered HLO.

The fp32 default is a true no-op: training/step.py does not even import
this module's exchange into the traced program, and the jaxpr is
bit-identical to the pre-compression step.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tfde_tpu import knobs
from tfde_tpu.ops import quant as quant_lib
from tfde_tpu.parallel import sharding as shd

log = logging.getLogger(__name__)

#: env default for the transport knob, so the whole tier-1 suite can
#: re-run under int8 transport in one command:
#:   TFDE_GRAD_TRANSPORT=int8 tools/tier1.sh
ENV_TRANSPORT = "TFDE_GRAD_TRANSPORT"

TRANSPORTS = ("fp32", "int8")


@dataclasses.dataclass(frozen=True)
class CommsConfig:
    """Gradient-transport knobs (strategy `grad_transport=` /
    RunConfig.grad_transport sugar resolves to this)."""

    #: 'fp32' = the implicit SPMD psum (today's path, byte-identical);
    #: 'int8' = the quantized exchange above
    transport: str = "fp32"
    #: per-leaf size threshold: leaves with fewer elements stay fp32
    #: (biases/norms — scale metadata would outweigh the payload saving)
    min_elems: int = 2048
    #: quantization block: one shared fp32 scale per `block` elements
    block: int = 256
    #: stochastic rounding (unbiased in expectation; deterministic under
    #: the step rng) — nearest rounding would bias the EWMA the error
    #: feedback has to clean up
    stochastic: bool = True

    def __post_init__(self):
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"grad_transport must be one of {TRANSPORTS}, "
                f"got {self.transport!r}"
            )
        if self.block < 1:
            raise ValueError("block must be >= 1")
        if self.min_elems < 0:
            raise ValueError("min_elems must be >= 0")


def resolve(value: Any = None) -> CommsConfig:
    """Sugar -> CommsConfig: a CommsConfig passes through, a transport
    string selects defaults, None defers to $TFDE_GRAD_TRANSPORT (unset =
    'fp32', so existing configs are byte-identical)."""
    if isinstance(value, CommsConfig):
        return value
    if value is None:
        # env-derived: a typo'd transport warns once and runs fp32
        # (tfde_tpu/knobs.py); explicit call-site values still raise in
        # CommsConfig.__post_init__.
        value = knobs.env_choice(ENV_TRANSPORT) or "fp32"
    if isinstance(value, str):
        return CommsConfig(transport=value)
    raise TypeError(
        f"grad_transport must be None/str/CommsConfig, "
        f"got {type(value).__name__}"
    )


# -- mesh eligibility ---------------------------------------------------------
def data_axis(mesh) -> Optional[str]:
    """The single data-like axis the int8 exchange runs over, or None when
    the mesh is not eligible (no data axis, or model axes > 1 — the
    exchange assumes replicated params, i.e. pure-DP meshes)."""
    daxes = shd.data_axes(mesh)
    if len(daxes) != 1:
        return None
    for a in mesh.axis_names:
        if a != daxes[0] and mesh.shape[a] > 1:
            return None
    return daxes[0]


def effective(cfg: CommsConfig, mesh) -> CommsConfig:
    """Downgrade int8 -> fp32 (with a warning) on meshes the exchange does
    not support: model-parallel axes > 1 (params not replicated over the
    exchange axis) or a single data shard (nothing to exchange). Keeps
    `TFDE_GRAD_TRANSPORT=int8 tools/tier1.sh` green across every strategy
    instead of exploding mid-suite."""
    if cfg.transport != "int8":
        return cfg
    axis = data_axis(mesh)
    if axis is None:
        log.warning(
            "grad_transport='int8' needs a pure-DP mesh (one data axis, "
            "replicated params); mesh %s is not — falling back to fp32",
            dict(mesh.shape),
        )
        return dataclasses.replace(cfg, transport="fp32")
    if mesh.shape[axis] < 2:
        log.warning(
            "grad_transport='int8' with a single data shard has nothing "
            "to exchange — falling back to fp32"
        )
        return dataclasses.replace(cfg, transport="fp32")
    return cfg


# -- leaf partitioning + residual ---------------------------------------------
def _size(leaf) -> int:
    shape = getattr(leaf, "shape", ())
    n = 1
    for d in shape:
        n *= int(d)
    return n


def compress_mask(tree: Any, cfg: CommsConfig) -> Any:
    """Per-leaf bool tree: True = quantized exchange, False = fp32 psum.
    Static (shape-only), so the split compiles into the step."""
    return jax.tree_util.tree_map(
        lambda leaf: _size(leaf) >= cfg.min_elems and _size(leaf) > 0, tree
    )


def init_residual(params: Any, cfg: CommsConfig) -> Any:
    """Fresh error-feedback residual: zeros_like for compressed leaves, a
    4-byte scalar placeholder for fp32 leaves (keeps the pytree structure
    congruent with params so tree_maps stay trivial)."""
    mask = compress_mask(params, cfg)
    return jax.tree_util.tree_map(
        lambda leaf, m: (
            jnp.zeros(leaf.shape, jnp.float32) if m
            else jnp.zeros((), jnp.float32)
        ),
        params, mask,
    )


# -- flat packing -------------------------------------------------------------
def pack(leaves: Sequence[jax.Array]) -> Tuple[jax.Array, List[Tuple]]:
    """Flatten + concat a leaf list into one fp32 vector; returns
    (vec, shapes) with shapes feeding `unpack`. One buffer per collective
    is the whole point: the collective count stays fixed no matter how
    many tensors the model has."""
    shapes = [tuple(l.shape) for l in leaves]
    if not leaves:
        return jnp.zeros((0,), jnp.float32), shapes
    flat = [l.astype(jnp.float32).reshape(-1) for l in leaves]
    return jnp.concatenate(flat) if len(flat) > 1 else flat[0], shapes


def unpack(vec: jax.Array, shapes: Sequence[Tuple]) -> List[jax.Array]:
    out, off = [], 0
    for shape in shapes:
        n = 1
        for d in shape:
            n *= int(d)
        out.append(jax.lax.dynamic_slice_in_dim(vec, off, n).reshape(shape))
        off += n
    return out


def psum_packed(leaves: Sequence[jax.Array], axis: str) -> List[jax.Array]:
    """Sum a list of small arrays across the data axis in ONE fp32 psum
    (inside shard_map). The fp32 sidecar of the int8 exchange: small grad
    leaves, loss/metric/weight scalars, BatchNorm stats."""
    vec, shapes = pack(leaves)
    if vec.size == 0:
        return list(leaves)
    return unpack(jax.lax.psum(vec, axis), shapes)


# -- the quantized exchange ---------------------------------------------------
def _round(x: jax.Array, rng: Optional[jax.Array]) -> jax.Array:
    if rng is None:
        return jnp.round(x)
    return quant_lib.stochastic_round(x, rng)


def _int8_scatter_phase(
    vec: jax.Array,
    residual: jax.Array,
    cfg: CommsConfig,
    axis: str,
    nshards: int,
    rng: Optional[jax.Array] = None,
):
    """Stages 1-2 of the exchange (shared scales + int8 reduce-scatter),
    shared between `int8_reduce` (which re-quantizes and all-gathers the
    gradient back) and `int8_scatter` (ZeRO weight-update sharding,
    parallel/zero.py: the owner chunk feeds the optimizer directly and the
    all-gather carries updated params instead). Returns
    (t, q, scale, partial, overflow, padded, chunk, idx)."""
    if nshards < 2:
        raise ValueError("int8_reduce needs >= 2 shards")
    length = vec.shape[0]
    t = vec.astype(jnp.float32) + residual.astype(jnp.float32)
    quantum = nshards * cfg.block
    padded = -(-max(length, 1) // quantum) * quantum
    if padded != length:
        t = jnp.pad(t, (0, padded - length))
    blocks = t.reshape(-1, cfg.block)                       # [P/B, B]

    # 1. shared per-block scale: pmax of the local absmaxes. Shared scales
    # make the int8 payload summable on the wire — psum_scatter of q is
    # EXACTLY the dequantized sum, no per-hop dequant/requant needed.
    amax = jnp.max(jnp.abs(blocks), axis=1)
    # a non-finite input must trip the overflow flag ON EVERY DEVICE, and
    # NaN through a max-reduce is implementation-defined — so poison the
    # local absmaxes with +inf (which max propagates deterministically);
    # the flag is then derived only from post-collective values that are
    # bit-identical across the ring (gmax here, full_s below).
    amax = jnp.where(jnp.all(jnp.isfinite(t)), amax, jnp.inf)
    gmax = jax.lax.pmax(amax, axis)                         # [P/B]
    overflow = jnp.any(~jnp.isfinite(gmax)).astype(jnp.float32)
    scale = jnp.maximum(gmax, 1e-12) / 127.0
    r1 = None if (rng is None or not cfg.stochastic) else jax.random.fold_in(rng, 1)
    q = jnp.clip(_round(blocks / scale[:, None], r1), -127, 127)
    q = q.astype(jnp.int8)

    # 2. reduce-scatter the int8 payload; int32 accumulation is exact
    sums = jax.lax.psum_scatter(
        q.reshape(padded).astype(jnp.int32), axis,
        scatter_dimension=0, tiled=True,
    )                                                       # [C] int32
    chunk = padded // nshards
    cblocks = chunk // cfg.block
    idx = jax.lax.axis_index(axis)
    my_scale = jax.lax.dynamic_slice_in_dim(scale, idx * cblocks, cblocks)
    partial = sums.astype(jnp.float32).reshape(-1, cfg.block) * my_scale[:, None]
    return t, q, scale, partial, overflow, padded, chunk, idx


def int8_scatter(
    vec: jax.Array,
    residual: jax.Array,
    cfg: CommsConfig,
    axis: str,
    nshards: int,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The scatter-only half of the exchange, for the sharded weight
    update (parallel/zero.py): returns (owner_chunk [padded/N] — the EXACT
    dequantized partial sum of the chunk this device owns, new_residual
    [L] — the input-side quantization error only (there is no
    re-quantization leg; the chunk feeds the optimizer at full fp32),
    overflow flag). `vec` must already be padded to the layout quantum
    contract or shorter — padding appends zeros, which quantize to zero.

    Collectives: pmax + psum_scatter (2; the trailing all-gather of the
    gradient is replaced by the caller's all-gather of updated params).
    The EF identity still holds: sum_dev(new_residual) + concat_of_chunks
    == sum_dev(vec + residual).
    """
    length = vec.shape[0]
    t, q, scale, partial, overflow, padded, chunk, idx = _int8_scatter_phase(
        vec, residual, cfg, axis, nshards, rng
    )
    deq_in = (q.astype(jnp.float32) * scale[:, None]).reshape(padded)
    new_res = (t - deq_in)[:length]
    return partial.reshape(chunk), new_res, overflow


def int8_reduce(
    vec: jax.Array,
    residual: jax.Array,
    cfg: CommsConfig,
    axis: str,
    nshards: int,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The EQuARX-style exchange, called INSIDE a shard_map body.

    `vec` is this device's local contribution (already in final units:
    sum over devices == the desired global gradient) and `residual` the
    error-feedback carry from the previous step, both [L] fp32. Returns
    (global_sum [L] — bit-identical on every device, new_residual [L] —
    per-device, overflow flag — 1.0 when a quantizer scale went
    non-finite, i.e. the incoming gradients held NaN/Inf; the numerics
    sentry trips on it rather than letting saturation pass silently).

    Collectives: pmax (shared block scales) + psum_scatter (int8 payload,
    int32 accumulator) + all_gather x2 (re-quantized chunks + scales).
    """
    length = vec.shape[0]
    t, q, scale, partial, overflow, padded, chunk, idx = _int8_scatter_phase(
        vec, residual, cfg, axis, nshards, rng
    )

    # 3. re-quantize the owned chunk's partial sums (fresh blockwise scale
    # — the sum's dynamic range grew by up to nshards)
    am2 = jnp.max(jnp.abs(partial), axis=1)
    am2 = jnp.where(jnp.all(jnp.isfinite(partial)), am2, jnp.inf)
    s2 = jnp.maximum(am2, 1e-12) / 127.0
    r2 = None if (rng is None or not cfg.stochastic) else jax.random.fold_in(rng, 2)
    q2 = jnp.clip(_round(partial / s2[:, None], r2), -127, 127)
    q2 = q2.astype(jnp.int8)

    # 4. all-gather the int8 chunks + scales; every device reconstructs
    # the same bytes -> the same averaged gradient (replicas cannot drift)
    full_q = jax.lax.all_gather(q2.reshape(chunk), axis, tiled=True)
    full_s = jax.lax.all_gather(s2, axis, tiled=True)
    overflow = jnp.maximum(
        overflow, jnp.any(~jnp.isfinite(full_s)).astype(jnp.float32)
    )
    out = (full_q.astype(jnp.float32).reshape(-1, cfg.block)
           * full_s[:, None]).reshape(padded)

    # error feedback: what MY quantizer dropped (input side), plus the
    # re-quantization error of the chunk I own — summed over devices the
    # residuals equal the total compression error, so next step's
    # transmission re-injects all of it
    deq_in = (q.astype(jnp.float32) * scale[:, None]).reshape(padded)
    new_res = t - deq_in
    out_err = (partial - q2.astype(jnp.float32) * s2[:, None]).reshape(chunk)
    own = jax.lax.dynamic_slice_in_dim(new_res, idx * chunk, chunk)
    new_res = jax.lax.dynamic_update_slice_in_dim(
        new_res, own + out_err, idx * chunk, 0
    )
    return out[:length], new_res[:length], overflow


# -- analytic wire-byte accounting --------------------------------------------
def comm_bytes(tree: Any, cfg: CommsConfig, nshards: int,
               opt_sharding: str = "replicated") -> dict:
    """Per-step gradient-exchange bytes on the wire, per device, for the
    fp32 ring vs the int8 transport — the numbers behind the
    `comm/bytes_per_step_{fp32,int8}` gauges.

    Ring cost model: an all-reduce moves 2(N-1)/N bytes-per-payload-byte,
    a reduce-scatter or all-gather (N-1)/N. The int8 path pays
    reduce-scatter + all-gather on the 1-byte payload plus the fp32 scale
    sidecars (pmax of block absmaxes, all-gather of re-quant scales).

    Under `opt_sharding='shard'` (parallel/zero.py) the dataflow changes:
    the big segment is only reduce-SCATTERED (fp32 or int8; no gradient
    all-gather, no re-quant leg), and a trailing fp32 all-gather moves the
    updated params (both padded segments + one norm scalar per shard)
    instead — the `param_gather` key, folded into both transports' totals
    so the `comm/bytes_per_step_*` gauges stay truthful."""
    nshards = max(int(nshards), 1)
    ring = 2.0 * (nshards - 1) / nshards
    half = (nshards - 1) / nshards
    mask = compress_mask(tree, cfg)
    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda l, m: (_size(l), bool(m)), tree, mask),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    big = sum(n for n, m in leaves if m)
    small = sum(n for n, m in leaves if not m)
    quantum = nshards * cfg.block
    big_pad = -(-big // quantum) * quantum if big else 0
    blocks = big_pad // cfg.block
    if opt_sharding == "shard":
        small_pad = -(-small // nshards) * nshards if small else 0
        # fp32 all-gather of updated params: both segments + N norm scalars
        gather = 4.0 * half * (big_pad + small_pad + nshards)
        fp32_bytes = (
            4.0 * ring * small        # packed fp32 sidecar psum
            + 4.0 * half * big_pad    # fp32 reduce-scatter of the big seg
            + gather
        )
        int8_bytes = (
            4.0 * ring * small        # packed fp32 sidecar psum
            + 1.0 * half * big_pad    # int8 reduce-scatter
            + 4.0 * ring * blocks     # pmax of block absmaxes
            + gather
        )
    else:
        gather = 0.0
        fp32_bytes = 4.0 * ring * (big + small)
        int8_bytes = (
            4.0 * ring * small            # packed fp32 sidecar psum
            + 1.0 * half * big_pad        # int8 reduce-scatter
            + 1.0 * half * big_pad        # int8 all-gather
            + 4.0 * ring * blocks         # pmax of block absmaxes
            + 4.0 * half * blocks         # all-gather of re-quant scales
        )
    return {
        "fp32": fp32_bytes,
        "int8": int8_bytes if cfg.transport == "int8" else fp32_bytes,
        "ratio": (int8_bytes / fp32_bytes) if fp32_bytes else 1.0,
        "param_gather": gather,
        "compressed_elems": big,
        "fp32_elems": small,
    }
