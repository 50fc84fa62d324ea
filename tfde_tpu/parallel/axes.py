"""Activation-sharding annotations — the model side of the partitioning story.

Strategies (parallel/strategies.py) declare where *weights* live; models
declare where *activations* live by calling `constrain(x, ...axes)` at layer
boundaries. Both speak mesh-axis names (runtime/mesh.AXIS_ORDER), and the XLA
SPMD partitioner meets in the middle, inserting the collectives the reference
delegated to NCCL/gRPC (SURVEY.md §2b).

The helper is deliberately forgiving: axis names absent from the active mesh
degrade to `None` (replicated), and with no active mesh it is the identity —
so the same model code runs single-chip, DP, FSDP, TP, and SP unchanged. The
active mesh is set by `use_axes(mesh)` (strategies' step factories do this) or
inherited from an enclosing `jax.sharding.use_mesh`.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Sequence[str], None]

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_axes(mesh: Optional[Mesh]):
    """Make `mesh` the target of `constrain` calls in this thread."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _filter_spec(mesh: Mesh, axes: Sequence[Axis]) -> P:
    """Drop axis names the mesh doesn't have; collapse empty tuples to None."""
    out = []
    for a in axes:
        if a is None:
            out.append(None)
            continue
        names = (a,) if isinstance(a, str) else tuple(a)
        names = tuple(n for n in names if n in mesh.axis_names)
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(names)
    return P(*out)


def constrain(x: jax.Array, *axes: Axis) -> jax.Array:
    """`with_sharding_constraint(x, P(*axes))` against the active mesh.

    Identity when no mesh is active or every named axis is absent — model
    code stays mesh-agnostic. `axes` may be shorter than `x.ndim`; trailing
    dims replicate.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _filter_spec(mesh, tuple(axes) + (None,) * (x.ndim - len(axes)))
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def vary_over(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Promote `x` to device-varying over exactly the axes it lacks from
    `axes` (jax vma typing inside shard_map regions): carries entering a
    fori_loop/scan must match the loop body's variance, and psums demand
    their operands vary over the reduced axes. Shared by the pipeline's
    reductions and ring attention's accumulators."""
    have = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in have)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def batch_axes() -> tuple:
    """The axis-name tuple activations' batch dim is split over: ('data',
    'fsdp') — mirrors sharding.batch_spec so activation constraints agree
    with the input sharding."""
    return ("data", "fsdp")


@contextlib.contextmanager
def manual_seq(ring_size: int, vary_axes: Sequence[str] = ()):
    """Mark this thread as INSIDE a fully-manual region whose 'seq' axis is
    manual with `ring_size` shards — the pp x sp composition signal
    (models/pipelined.py sets it around stage bodies; ops/attention.py
    dispatches to ring_attention_manual when it is set, since the usual
    mesh-based dispatch sees no mesh inside a fully-manual shard_map).
    `vary_axes`: every manual axis in play, for accumulator variance."""
    prev = getattr(_state, "manual_seq", None)
    _state.manual_seq = (int(ring_size), tuple(vary_axes))
    try:
        yield
    finally:
        _state.manual_seq = prev


def manual_seq_info() -> Optional[tuple]:
    """(ring_size, vary_axes) when inside a manual_seq region, else None."""
    return getattr(_state, "manual_seq", None)
