"""ZeRO-style weight-update sharding (arXiv 2004.13336).

Data-parallel training replicates the optimizer state: every replica holds
a full copy of Adam's mu/nu (2x params in fp32) and every replica redoes
the identical weight update. "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" shards both across the data replicas
instead: reduce-scatter the gradients so each replica owns 1/N of them,
run the optimizer on that 1/N slice only (optimizer state allocated for
the slice alone), then all-gather the *updated parameters* — the same
wire volume as the all-reduce the update replaced, but 1/N the optimizer
memory and 1/N the update flops.

This module owns the chunked layout behind the `opt_sharding=
'replicated'|'shard'` knob (strategies / RunConfig / $TFDE_OPT_SHARDING):

- `build_layout` flattens the params like `comms.pack` into two segments:
  "big" leaves (>= the comms config's min_elems — the same split the int8
  transport uses, so the int8 reduce-scatter's owner chunks ARE the update
  chunks) and "small" leaves (biases/norms riding the fp32 sidecar). Both
  segments pad to an nshards multiple; the big segment pads to the int8
  quantum (nshards x block) even under fp32 transport, so chunk boundaries
  are transport-independent and a sharded checkpoint written under fp32
  restores bit-identically under int8 and vice versa.
- `pack_params` / `unpack_params` move between the params tree and the
  {packed_big: [N, Cb], packed_small: [N, Cs]} chunk tree; the optimizer
  state is simply `tx.init` of the packed tree, so its params-shaped slots
  (mu/nu/trace/ema) are born [N, C] and shard row-wise over the data axis
  (`opt_state_spec`) — genuinely distributed arrays that Orbax
  checkpoints shard-by-shard.
- `pack_opt_state` / `unpack_opt_state` convert a replicated optimizer
  state to the packed form and back (checkpoint cross-compat both ways).

Correctness contract: the packed chunk update is bit-identical to the
replicated per-leaf update for ELEMENTWISE transforms (sgd, momentum,
adam, adamw without a mask, param-EMA) — the update of element i depends
only on (g_i, state_i, p_i), so slicing commutes with updating. Structure-
sensitive transforms (optax.masked / `training.optimizers.decay_mask`,
anything keyed on leaf paths or shapes) would silently see the packed
{packed_big, packed_small} tree instead of the params tree; `packable`
detects the masked case from the abstract state and init_state
warn-falls-back to replicated, the rest is a documented limitation
(README "Weight-update sharding").
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tfde_tpu import knobs
from tfde_tpu.parallel import comms as comms_lib

log = logging.getLogger(__name__)

#: env default for the knob, so the whole tier-1 suite can re-run with
#: sharded weight updates in one command:
#:   TFDE_OPT_SHARDING=shard tools/tier1.sh
ENV_OPT_SHARDING = "TFDE_OPT_SHARDING"

MODES = ("replicated", "shard")

#: keys of the packed chunk tree. Deliberately distinctive (not "big"/
#: "small") so checkpoint metadata sniffing cannot false-match a user dict.
BIG = "packed_big"
SMALL = "packed_small"


def resolve(value: Any = None) -> str:
    """Sugar -> mode string: a mode passes through, None defers to
    $TFDE_OPT_SHARDING (unset = 'replicated', so existing configs are
    byte-identical)."""
    if value is None:
        # env-derived: a typo'd mode warns once and runs 'replicated'
        # (tfde_tpu/knobs.py); explicit call-site values still raise below.
        value = knobs.env_choice(ENV_OPT_SHARDING) or "replicated"
    if isinstance(value, str):
        if value not in MODES:
            raise ValueError(
                f"opt_sharding must be one of {MODES}, got {value!r}"
            )
        return value
    raise TypeError(
        f"opt_sharding must be None or str, got {type(value).__name__}"
    )


# -- the chunked layout -------------------------------------------------------
def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static description of the packed two-segment layout. Hashable (all
    tuple/int fields + a treedef) so it can ride `TrainState.opt_layout`
    as a non-pytree (static) field through jit."""

    nshards: int
    block: int
    treedef: Any            # params treedef (jax treedefs hash/compare)
    shapes: Tuple[tuple, ...]   # per-leaf shapes, tree_flatten order
    dtypes: Tuple[str, ...]     # per-leaf dtype names
    mask: Tuple[bool, ...]      # True = big segment (comms.compress_mask)
    padded_big: int             # big segment length, quantum-padded
    padded_small: int           # small segment length, nshards-padded

    @property
    def chunk_big(self) -> int:
        return self.padded_big // self.nshards

    @property
    def chunk_small(self) -> int:
        return self.padded_small // self.nshards

    @property
    def total_big(self) -> int:
        return sum(_size(s) for s, m in zip(self.shapes, self.mask) if m)

    @property
    def total_small(self) -> int:
        return sum(_size(s) for s, m in zip(self.shapes, self.mask) if not m)


def build_layout(params: Any, ccfg: "comms_lib.CommsConfig",
                 nshards: int) -> Layout:
    """Layout for `params` (concrete or abstract) on an `nshards`-way data
    axis. The big/small split reuses the comms config's min_elems so the
    int8 transport's reduce-scatter chunks are exactly the update chunks;
    the big segment pads to the int8 quantum (nshards x block) under BOTH
    transports, making the layout — and therefore sharded checkpoints —
    transport-independent."""
    if nshards < 2:
        raise ValueError(f"opt_sharding='shard' needs >= 2 shards, got {nshards}")
    leaves, treedef = jax.tree_util.tree_flatten(params)
    mask = tuple(
        bool(m) for m in jax.tree_util.tree_leaves(
            comms_lib.compress_mask(params, ccfg)
        )
    )
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype).name for l in leaves)
    total_big = sum(_size(s) for s, m in zip(shapes, mask) if m)
    total_small = sum(_size(s) for s, m in zip(shapes, mask) if not m)
    quantum = nshards * ccfg.block
    padded_big = -(-total_big // quantum) * quantum if total_big else 0
    padded_small = -(-total_small // nshards) * nshards if total_small else 0
    return Layout(
        nshards=nshards, block=ccfg.block, treedef=treedef,
        shapes=shapes, dtypes=dtypes, mask=mask,
        padded_big=padded_big, padded_small=padded_small,
    )


def _pack_pad(leaves: Sequence[jax.Array], padded: int) -> jax.Array:
    """comms.pack + zero-pad to the segment length."""
    vec, _ = comms_lib.pack(list(leaves))
    if vec.shape[0] != padded:
        vec = jnp.pad(vec, (0, padded - vec.shape[0]))
    return vec


def segment_vectors(params: Any, layout: Layout) -> Tuple[jax.Array, jax.Array]:
    """(big [padded_big], small [padded_small]) fp32 segment vectors."""
    leaves = jax.tree_util.tree_leaves(params)
    big = [l for l, m in zip(leaves, layout.mask) if m]
    small = [l for l, m in zip(leaves, layout.mask) if not m]
    return (_pack_pad(big, layout.padded_big),
            _pack_pad(small, layout.padded_small))


def pack_params(params: Any, layout: Layout) -> dict:
    """Params tree -> {packed_big: [N, Cb], packed_small: [N, Cs]} fp32.
    Row i is replica i's owned chunk."""
    bigv, smallv = segment_vectors(params, layout)
    return {
        BIG: bigv.reshape(layout.nshards, layout.chunk_big),
        SMALL: smallv.reshape(layout.nshards, layout.chunk_small),
    }


def unpack_params(big_vec: jax.Array, small_vec: jax.Array,
                  layout: Layout) -> Any:
    """Segment vectors -> params tree (original shapes/dtypes; padding
    dropped)."""
    big_shapes = [s for s, m in zip(layout.shapes, layout.mask) if m]
    small_shapes = [s for s, m in zip(layout.shapes, layout.mask) if not m]
    big = comms_lib.unpack(big_vec, big_shapes)
    small = comms_lib.unpack(small_vec, small_shapes)
    out, bi, si = [], 0, 0
    for m, dt in zip(layout.mask, layout.dtypes):
        if m:
            out.append(big[bi].astype(dt))
            bi += 1
        else:
            out.append(small[si].astype(dt))
            si += 1
    return jax.tree_util.tree_unflatten(layout.treedef, out)


def unpack_packed(packed: dict, layout: Layout) -> Any:
    return unpack_params(
        jnp.asarray(packed[BIG]).reshape(-1),
        jnp.asarray(packed[SMALL]).reshape(-1),
        layout,
    )


def with_nshards(layout: Layout, nshards: int) -> Layout:
    """The same params packed over a *different* shard count: identical
    treedef/shapes/mask/block (the big/small split and the quantum unit are
    properties of the params + comms config, not of the world size), with
    the segment paddings recomputed for `nshards`. This is how a reader
    reconstructs the layout an M-way writer used from its own N-way layout
    — the elastic cross-world checkpoint bridge (checkpoint/manager.py).
    Accepts nshards=1 (a one-row packed form) so M->1 relayouts stay
    expressible even though training itself falls back to replicated
    below 2 shards."""
    if nshards < 1:
        raise ValueError(f"nshards must be >= 1, got {nshards}")
    quantum = nshards * layout.block
    total_big = layout.total_big
    total_small = layout.total_small
    return dataclasses.replace(
        layout,
        nshards=nshards,
        padded_big=-(-total_big // quantum) * quantum if total_big else 0,
        padded_small=(-(-total_small // nshards) * nshards
                      if total_small else 0),
    )


# -- optimizer-state conversion (checkpoint cross-compat) ---------------------
def _walk(node, match, rebuild):
    if match(node):
        return rebuild(node)
    if isinstance(node, tuple) and hasattr(node, "_fields"):
        return type(node)(*[_walk(c, match, rebuild) for c in node])
    if isinstance(node, tuple):
        return tuple(_walk(c, match, rebuild) for c in node)
    if isinstance(node, list):
        return [_walk(c, match, rebuild) for c in node]
    if isinstance(node, dict):
        return {k: _walk(v, match, rebuild) for k, v in node.items()}
    return node


def _is_packed_node(node) -> bool:
    return isinstance(node, dict) and set(node.keys()) == {BIG, SMALL}


def pack_opt_state(opt_state: Any, layout: Layout) -> Any:
    """Replicated optimizer state -> packed form: every params-congruent
    subtree (optax mu/nu/trace/ema slots) becomes its packed chunk tree;
    scalars (counts) pass through. Exact inverse of `unpack_opt_state`."""

    def match(node):
        try:
            return jax.tree_util.tree_structure(node) == layout.treedef
        except Exception:
            return False

    return _walk(opt_state, match, lambda n: pack_params(n, layout))


def unpack_opt_state(opt_state: Any, layout: Layout) -> Any:
    """Packed optimizer state -> replicated per-leaf form."""
    return _walk(opt_state, _is_packed_node,
                 lambda n: unpack_packed(n, layout))


def relayout_opt_state(opt_state: Any, from_layout: Layout,
                       to_layout: Layout) -> Any:
    """Re-chunk a packed optimizer state from one shard count to another
    (M-way checkpoint -> N-way mesh, both directions). Pure reshapes —
    unpack to the per-leaf form under the writer's layout, re-pack under
    the reader's — so the payload values are bit-exact; only the zero
    padding at the segment tails differs."""
    if (from_layout.treedef != to_layout.treedef
            or from_layout.shapes != to_layout.shapes
            or from_layout.mask != to_layout.mask):
        raise ValueError(
            "relayout_opt_state needs layouts over the same params "
            "(treedef/shapes/segment mask must match; only nshards may "
            "differ)"
        )
    return pack_opt_state(unpack_opt_state(opt_state, from_layout), to_layout)


def packable(abstract_opt_state: Any) -> bool:
    """False when the optimizer state contains an optax MaskedState — the
    mask function was evaluated against the params TREE, so re-initializing
    on the packed {packed_big, packed_small} tree would silently change
    which elements the inner transform sees. (Other structure-sensitive
    transforms cannot be detected from the state; see the module
    docstring.)"""
    bad: List[str] = []

    def scan(node):
        if type(node).__name__ == "MaskedState":
            bad.append(type(node).__name__)
        if isinstance(node, (tuple, list)):
            for c in node:
                scan(c)
        elif isinstance(node, dict):
            for c in node.values():
                scan(c)

    scan(abstract_opt_state)
    return not bad


# -- sharding + eligibility ---------------------------------------------------
def opt_state_spec(opt_state: Any, axis: str, nshards: int) -> Any:
    """PartitionSpec tree for a packed optimizer state: [N, C] chunk leaves
    shard row-wise over the data axis, scalars (counts) replicate."""
    return jax.tree_util.tree_map(
        lambda l: (
            P(axis)
            if getattr(l, "ndim", 0) >= 1 and l.shape[0] == nshards
            else P()
        ),
        opt_state,
    )


def eligible_axis(strategy, abstract_params: Any) -> Optional[str]:
    """The data axis the sharded update runs over, or None (with a warning)
    when the mesh/strategy is ineligible — the comms-style warn-fallback:
    needs a pure-DP mesh (exactly one data axis, no model axes > 1, same
    rule as the int8 exchange) AND fully replicated params (the packed
    chunks slice a replica-identical param vector; FSDP/TP layouts are
    already sharded and keep their own optimizer layout)."""
    mesh = strategy.mesh
    axis = comms_lib.data_axis(mesh)
    if axis is None or mesh.shape[axis] < 2:
        log.warning(
            "opt_sharding='shard' needs a pure-DP mesh with >= 2 data "
            "shards; mesh %s is not — falling back to replicated",
            dict(mesh.shape),
        )
        return None
    specs = jax.tree_util.tree_leaves(
        strategy.params_spec(abstract_params),
        is_leaf=lambda x: isinstance(x, P),
    )
    if any(any(e is not None for e in tuple(s)) for s in specs):
        log.warning(
            "opt_sharding='shard' needs replicated params; strategy %s "
            "shards them — falling back to replicated",
            type(strategy).__name__,
        )
        return None
    return axis


# -- accounting (opt/* gauges) ------------------------------------------------
def state_bytes(opt_state: Any, layout: Optional[Layout] = None) -> float:
    """Per-device optimizer-state bytes. With a layout, [N, C] chunk leaves
    count 1/N (each device holds one row); without, everything is
    replicated and counts in full."""
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        shape = tuple(getattr(leaf, "shape", ()))
        n = _size(shape) * jnp.dtype(leaf.dtype).itemsize
        if (layout is not None and shape
                and shape[0] == layout.nshards):
            n /= layout.nshards
        total += n
    return total


def measured_state_bytes(opt_state: Any) -> float:
    """Per-device optimizer-state bytes MEASURED from the committed arrays
    (max over devices of the shard bytes each actually holds, via
    memwatch.device_bytes) rather than derived from shapes. Returns 0.0
    for abstract/uncommitted leaves (callers fall back to the analytic
    state_bytes). The two should agree within padding; a larger gap is a
    sharding bug worth an alarm."""
    from tfde_tpu.observability import memwatch

    try:
        return float(memwatch.device_bytes(opt_state))
    except Exception:  # noqa: BLE001 — accounting must not break the step
        return 0.0


def param_gather_bytes(layout: Optional[Layout]) -> float:
    """Per-device wire bytes of the trailing param all-gather (ring cost:
    (N-1)/N per payload byte; the payload is both fp32 segments plus one
    grad-norm scalar per shard)."""
    if layout is None:
        return 0.0
    n = layout.nshards
    payload = 4.0 * (layout.padded_big + layout.padded_small + n)
    return (n - 1) / n * payload
