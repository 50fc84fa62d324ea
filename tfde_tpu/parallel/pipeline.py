"""Pipeline parallelism over the 'pipe' mesh axis — the collective-pipeline
formulation (GPipe and 1F1B schedules, SPMD-style).

Scale-up scope beyond the reference (SURVEY.md §2c: "Pipeline parallel:
absent") completing the parallelism families named by runtime/mesh.AXIS_ORDER.

Formulation: instead of placing different *programs* on different devices
(the GPU-framework approach the reference's ecosystem would use), the stage
loop is data: stage weights are stacked along a leading [num_stages, ...]
axis sharded over 'pipe', every device runs the *same* traced stage function
on its resident stage, and activations hop one rank forward per tick via
`jax.lax.ppermute` — neighbor ICI traffic, the link pattern TPU tori are
built for. A GPipe schedule over M microbatches and S stages takes M + S - 1
ticks; utilization is M/(M+S-1), the standard bubble.

Two backward stories:

- GPipe (`pipeline_apply` + jax.grad): ppermute and the stage fn are
  differentiable, so the backward pipeline (reverse hops) is generated by
  AD. Simple and composes with everything (TP/auto mode, dropout), but AD
  saves the forward loop's carries — activation memory grows O(M + S) per
  rank with the microbatch count.
- 1F1B (`pipeline_train_1f1b`): the classic one-forward-one-backward
  interleave, hand-scheduled with explicit per-tick VJPs. Activation
  memory is O(S) per rank — a [2S-1, micro]-slot stash of stage INPUTS
  (backward recomputes the stage forward from the stashed input, the
  remat='full' trade) — so M can grow to shrink the relative bubble
  without activations growing with it.

  Accounting (T_f/T_b = stage fwd/bwd time; each tick runs one fwd slot
  AND one bwd slot, masked during bubbles): rank r forwards microbatch i
  at tick r+i and backwards it at tick 2(S-1)-r+i, so the run is
  M + 2(S-1) ticks against GPipe's M+S-1 fwd + M+S-1 bwd half-ticks —
  equal steady-state throughput, one extra (S-1)-tick bubble, in-flight
  activations bounded by 2(S-1-r)+1 <= 2S-1 instead of M. Choose 1F1B
  when M*micro activations don't fit HBM (the regime pipelining exists
  for); GPipe otherwise.

  Why no interleaved (virtual-chunk) schedule: Megatron's interleaving
  shrinks the bubble by a factor v because an idle rank is genuinely
  idle there — its fill/drain time is real wall-clock recovered by
  working on other chunks. In this synchronous SPMD formulation every
  rank executes every tick's full compute with invalid lanes MASKED,
  so splitting stages into v chunks leaves per-tick cost unchanged
  while multiplying the masked fill/drain ticks by v — the bubble
  cost it saves elsewhere is exactly the cost it adds here. A
  profitable interleave would need per-rank asynchronous dispatch,
  which is the GPU-framework execution model this module deliberately
  avoids (module docstring).

Usage (see tests/test_pipeline.py):

    stacked = stack_stage_params([p0, p1, p2, p4])   # [S, ...] pytree
    y = pipeline_apply(stage_fn, stacked, x_microbatched, mesh)

where `stage_fn(stage_params, x) -> x` is one stage's computation and
`x_microbatched` is [M, micro_batch, ...]. Output is [M, micro_batch, ...]
after all S stages, microbatch order preserved.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(stage_params: list) -> Any:
    """[per-stage pytree, ...] -> one pytree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params
    )


def pipeline_apply(
    stage_fn: Callable[..., jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "pipe",
    reduce_fn: Optional[Callable[..., Any]] = None,
    reduce_aux: Any = None,
    extra_params: Any = None,
    mode: str = "manual",
) -> Any:
    """Run M microbatches through S pipeline stages over mesh[axis].

    mode='manual' (default): shard_map is manual over every mesh axis — the
    proven ring, with microbatches explicitly sharded over the data axes.
    mode='auto': shard_map is manual over `axis` ONLY (partial-manual);
    every other mesh axis stays under the automatic SPMD partitioner inside
    the ring, so stage weights may carry additional shardings (e.g. Megatron
    'tensor' splits — dp x pp x tp composition) and the stage body's
    with_sharding_constraint annotations bind normally.

    stacked_params: pytree of [S, ...] arrays (sharded over `axis` outside,
    or replicated — shard_map slices either way). x: [M, micro, ...] with M
    microbatches.

    stage_fn(stage_params, x) or stage_fn(stage_params, x, mb_idx) — the
    3-arg form also receives the microbatch index the activation belongs to
    (clipped to [0, M-1] during bubble ticks, whose outputs are discarded),
    so stages can derive per-microbatch dropout keys.

    Without reduce_fn: returns the full [M, micro, ...] outputs, psum-
    broadcast from the last stage so every rank holds them (replicated over
    `axis`). With reduce_fn: the full-output broadcast is skipped — only
    `reduce_fn(extra_params, outputs, reduce_aux)`'s (small) result crosses
    the ring. reduce_fn must return per-shard SUMS; they are summed over the
    pipe ring and the data axes, so the caller gets fully-reduced replicated
    values (pass a count to divide by). reduce_aux: pytree of [M, micro,...]
    arrays laid out like x (e.g. labels); extra_params: replicated pytree
    (e.g. the LM head weights).
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    if mode not in ("manual", "auto"):
        raise ValueError(f"mode must be 'manual' or 'auto', got {mode!r}")
    s = mesh.shape[axis]
    m = x.shape[0]
    if m < 1:
        raise ValueError("need at least one microbatch")
    for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_params):
        if leaf.shape[:1] != (s,):
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} has leading dim "
                f"{leaf.shape[0]} but mesh {axis!r} has {s} stages — a "
                f"mismatch would silently skip stages"
            )

    # Microbatches shard their *batch* dim over the data-like mesh axes (a
    # [M, micro, ...] input keeps per-device memory at micro/D, not M*micro —
    # the round-1 all-replicated layout defeated pipelining's memory point at
    # scale). The M (schedule) dim stays unsharded: it is the time axis of
    # the pipeline. Replicated over 'pipe': every pipe rank sees the full
    # local microbatch and stage 0 injects it.
    from tfde_tpu.parallel.sharding import data_axes as _data_axes

    data_axes = _data_axes(mesh)
    d = 1
    for a in data_axes:
        d *= mesh.shape[a]
    if x.ndim >= 2 and x.shape[1] % d:
        raise ValueError(
            f"microbatch size {x.shape[1]} must divide by the data-axis "
            f"size {d} (mesh {dict(mesh.shape)}) — use fewer microbatches "
            f"or a larger global batch"
        )
    micro_spec = data_axes if data_axes else None

    # pp x sp: a >1 'seq' axis shards the activations' sequence dim (dim 2
    # of [M, micro, seq, ...]); the stage body must then run the ring
    # (models/pipelined.py wraps stages in axes.manual_seq so attention
    # dispatches to ring_attention_manual). Fully-manual mode only.
    seq_ax = ("seq" if "seq" in mesh.axis_names and mesh.shape["seq"] > 1
              else None)
    if seq_ax:
        if mode == "auto":
            raise NotImplementedError(
                "pp x sp runs in the fully-manual ring only; the "
                "partial-manual 'tensor' mode does not compose with a "
                "'seq' axis (nested-manual lowering) — drop tensor or seq"
            )
        if reduce_fn is not None:
            raise NotImplementedError(
                "the last-stage reduction does not compose with a 'seq' "
                "axis (a shifted loss would misalign at shard "
                "boundaries); use the full-output path and compute the "
                "loss on the gathered sequence outside the pipe"
            )
        if x.ndim < 3 or x.shape[2] % mesh.shape["seq"]:
            raise ValueError(
                f"sequence length {x.shape[2] if x.ndim >= 3 else '?'} "
                f"must divide by the 'seq' axis size "
                f"{mesh.shape['seq']} (mesh {dict(mesh.shape)})"
            )

    def batched_spec(a):
        if a.ndim >= 3:
            return P(None, micro_spec, seq_ax, *(None,) * (a.ndim - 3))
        if a.ndim == 2:
            return P(None, micro_spec)
        return P(*(None,) * a.ndim)

    p_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    if mode == "auto":
        # specs describe the MANUAL axes only ('pipe'); data/tensor/...
        # shardings ride the arrays into the automatic partitioner
        x_spec = P()
        aux_spec = jax.tree_util.tree_map(lambda _: P(), reduce_aux)
        vary_axes = (axis,)
    else:
        x_spec = batched_spec(x)
        aux_spec = jax.tree_util.tree_map(batched_spec, reduce_aux)
        vary_axes = (axis,) + data_axes + ((seq_ax,) if seq_ax else ())
    extra_spec = jax.tree_util.tree_map(lambda _: P(), extra_params)

    takes_mb = _takes_mb(stage_fn)

    fwd = [(i, (i + 1) % s) for i in range(s)]

    def local(params, xs, aux, extra):
        # params: [1, ...] (this rank's stage); xs: [M, micro, ...]
        rank = jax.lax.axis_index(axis)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        micro_shape = xs.shape[1:]
        ticks = m + s - 1

        # activation in flight + collected outputs; pcast marks them
        # device-varying over the manual axes up front so the fori_loop
        # carry type stays stable once rank-dependent values mix in ('pipe'
        # always; plus the data axes in fully-manual mode)
        state, outputs = jax.lax.pcast(
            (
                jnp.zeros(micro_shape, xs.dtype),
                jnp.zeros((m,) + micro_shape, xs.dtype),
            ),
            vary_axes,
            to="varying",  # the final psum over `axis` keeps data variance
        )

        def tick(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t (if any remain); other ranks keep
            # the activation that just hopped in
            inject = jax.lax.select(
                t < m, xs[jnp.minimum(t, m - 1)], jnp.zeros_like(state)
            )
            state = jnp.where(rank == 0, inject, state)
            # the microbatch this rank's activation belongs to at tick t;
            # clipped during bubble ticks (their outputs are never emitted)
            mb_idx = jnp.clip(t - rank, 0, m - 1)
            if takes_mb:
                state = stage_fn(my_params, state, mb_idx)
            else:
                state = stage_fn(my_params, state)
            # last rank emits microbatch t-(s-1) (valid once t >= s-1)
            out_idx = t - (s - 1)
            emit = jnp.logical_and(rank == s - 1, out_idx >= 0)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, state, jnp.maximum(out_idx, 0), 0
                ),
                lambda o: o,
                outputs,
            )
            state = jax.lax.ppermute(state, axis, fwd)
            return state, outputs

        _, outputs = jax.lax.fori_loop(
            0, ticks, tick, (state, outputs)
        )
        if reduce_fn is None:
            # only the last rank holds real outputs; one collective broadcast
            # hands them to every rank so the result is replicated over 'pipe'
            return jax.lax.psum(
                jnp.where(rank == s - 1, outputs, jnp.zeros_like(outputs)),
                axis,
            )
        # last-stage reduction: every rank runs reduce_fn on its local
        # buffer (zeros off the last rank), the mask keeps only the real
        # one, and only the small reduction crosses the ring — the full
        # [M, micro, ...] psum broadcast never happens.
        red = reduce_fn(extra, outputs, aux)
        last = (rank == s - 1)
        # manual mode: sums must cross pipe AND the (manual) data axes;
        # auto mode: the automatic partitioner already made them global
        # over data, so only the pipe masking+sum remains
        want = vary_axes

        def mask_and_sum(r):
            r = jnp.where(last, r, jnp.zeros_like(r))
            # leaves differ in device-variance (a count is data-invariant,
            # a loss sum is data-varying); promote each to varying over
            # exactly the axes it lacks so one psum fully reduces it
            from tfde_tpu.parallel.axes import vary_over

            return jax.lax.psum(vary_over(r, want), want)

        return jax.tree_util.tree_map(mask_and_sum, red)

    if reduce_fn is None:
        out_specs = x_spec
    else:
        red_shape = jax.eval_shape(
            lambda e, o, a: reduce_fn(e, o, a),
            extra_params,
            jax.ShapeDtypeStruct((m,) + x.shape[1:], x.dtype),
            reduce_aux,
        )
        out_specs = jax.tree_util.tree_map(
            lambda r: P(*(None,) * r.ndim), red_shape
        )

    kwargs = {"axis_names": {axis}} if mode == "auto" else {}
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_spec, x_spec, aux_spec, extra_spec),
        out_specs=out_specs,
        **kwargs,
    )
    return fn(stacked_params, x, reduce_aux, extra_params)


def _takes_mb(stage_fn) -> bool:
    import inspect

    try:
        return len(inspect.signature(stage_fn).parameters) >= 3
    except (TypeError, ValueError):
        return False


def pipeline_train_1f1b(
    stage_fn: Callable[..., jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    loss_fn: Callable[[Any, jax.Array, Any], dict],
    loss_aux: Any,
    extra_params: Any,
    axis: str = "pipe",
    grad_key: str = "loss_sum",
):
    """One-forward-one-backward pipeline training pass (see module
    docstring for the schedule/memory accounting vs GPipe).

    Runs M microbatches forward AND backward through S stages in one
    M + 2(S-1)-tick loop, computing parameter gradients with explicit
    per-tick `jax.vjp` calls instead of AD-through-the-loop — in-flight
    activation memory is O(S) per rank (a [2S-1]-slot stash of stage
    inputs; the backward recomputes the stage forward from the stash, the
    remat trade), never O(M).

    stage_fn(stage_params, x[, mb_idx]) -> x; the 3-arg form receives the
    microbatch index (for per-microbatch dropout keys) and is re-invoked
    with the SAME index during the backward recompute, so dropout masks
    reproduce exactly.

    loss_fn(extra_params, y_mb, aux_mb) -> dict of per-shard scalar SUMS
    (the last-stage reduction, same contract as pipeline_apply's
    reduce_fn); the backward seeds d/d(sums[grad_key]) = 1 and ignores
    the other keys (counts and argmax-style metrics — zero derivative
    a.e.). loss_aux: pytree of [M, micro, ...] arrays laid out like x.

    Fully-manual mode only: composes with data axes (activations sharded
    over them, gradients psum'd), not with the partial-manual 'tensor'
    mode — use the GPipe path for dp x pp x tp.

    Returns (sums, grads) where sums is loss_fn's dict fully psum-reduced
    and grads = {"stages": like stacked_params, "x": like x (the
    d loss_sum/d input activations — feed your embedding's vjp),
    "extra": like extra_params}.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    s = mesh.shape[axis]
    m = x.shape[0]
    if m < 1:
        raise ValueError("need at least one microbatch")
    for path, leaf in jax.tree_util.tree_leaves_with_path(stacked_params):
        if leaf.shape[:1] != (s,):
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} has leading dim "
                f"{leaf.shape[0]} but mesh {axis!r} has {s} stages — a "
                f"mismatch would silently skip stages"
            )
    from tfde_tpu.parallel.sharding import data_axes as _data_axes

    data_axes = _data_axes(mesh)
    d = 1
    for a in data_axes:
        d *= mesh.shape[a]
    if x.ndim >= 2 and x.shape[1] % d:
        raise ValueError(
            f"microbatch size {x.shape[1]} must divide by the data-axis "
            f"size {d} (mesh {dict(mesh.shape)})"
        )
    if "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        raise NotImplementedError(
            "schedule='1f1b' does not compose with a 'seq' axis yet: its "
            "per-microbatch loss runs inside the pipe, where a shifted "
            "next-token loss would misalign at seq-shard boundaries — "
            "use the GPipe schedule for pp x sp"
        )
    micro_spec = data_axes if data_axes else None

    def batched_spec(a):
        return P(None, micro_spec, *(None,) * (a.ndim - 2)) if a.ndim >= 2 \
            else P(*(None,) * a.ndim)

    p_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    x_spec = batched_spec(x)
    aux_spec = jax.tree_util.tree_map(batched_spec, loss_aux)
    extra_spec = jax.tree_util.tree_map(lambda _: P(), extra_params)
    vary_axes = (axis,) + data_axes
    takes_mb = _takes_mb(stage_fn)

    fwd_perm = [(i, (i + 1) % s) for i in range(s)]
    bwd_perm = [(i, (i - 1) % s) for i in range(s)]
    stash_n = 2 * s - 1
    ticks = m + 2 * (s - 1)

    # seed cotangent: 1.0 on grad_key, 0 on every other reported sum
    sums_abstract = jax.eval_shape(
        loss_fn, extra_params,
        jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), loss_aux
        ),
    )
    if grad_key not in sums_abstract:
        raise ValueError(
            f"loss_fn must report {grad_key!r} among its sums "
            f"(got {list(sums_abstract)})"
        )

    def local(params, xs, aux, extra):
        rank = jax.lax.axis_index(axis)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        micro_shape = xs.shape[1:]

        from tfde_tpu.parallel.axes import vary_over

        def varying(t):
            # promote each leaf over exactly the axes it lacks — param-grad
            # accumulators start pipe-varying already (sliced from the
            # P(axis)-sharded input), data-invarying until grads mix in
            return jax.tree_util.tree_map(
                lambda v: vary_over(v, vary_axes), t
            )

        # The vjps below MUST see fully device-varying inputs: jax's
        # pullback wrt an invarying input transposes the implicit broadcast
        # into a psum over the varying axes, silently mixing every rank's
        # (mostly bubble/garbage) contributions into each rank's cotangent
        # BEFORE the schedule masks can drop them. Promoting the params to
        # varying keeps every pullback purely local; the explicit masked
        # accumulation + end-of-loop psums below own all cross-rank math.
        extra_v = varying(extra)
        my_params_v = varying(my_params)

        carry = varying((
            jnp.zeros(micro_shape, xs.dtype),                 # fwd activation
            jnp.zeros(micro_shape, xs.dtype),                 # bwd cotangent
            jnp.zeros((stash_n,) + micro_shape, xs.dtype),    # input stash
            jax.tree_util.tree_map(jnp.zeros_like, my_params),  # stage grads
            jax.tree_util.tree_map(jnp.zeros_like, extra),    # head grads
            {k: jnp.zeros(v.shape, v.dtype)
             for k, v in sums_abstract.items()},              # loss sums
            jnp.zeros_like(xs),                               # d/d input
        ))

        def tick(t, carry):
            (fwd_state, bwd_state, stash, gparams, gextra, sums_acc,
             dxs) = carry

            # ---- forward slot: rank r forwards microbatch t - r ----
            fwd_i = t - rank
            fwd_valid = jnp.logical_and(fwd_i >= 0, fwd_i < m)
            fwd_mb = jnp.clip(fwd_i, 0, m - 1)
            x_in = jnp.where(rank == 0, xs[fwd_mb], fwd_state)
            stash = jax.lax.cond(
                fwd_valid,
                lambda st: jax.lax.dynamic_update_index_in_dim(
                    st, x_in, fwd_mb % stash_n, 0
                ),
                lambda st: st,
                stash,
            )
            if takes_mb:
                y = stage_fn(my_params, x_in, fwd_mb)
            else:
                y = stage_fn(my_params, x_in)

            # last rank: loss sums + output cotangent for the SAME tick's
            # backward slot (B_{S-1}(i) == F_{S-1}(i))
            aux_mb = jax.tree_util.tree_map(lambda a: a[fwd_mb], aux)
            sums_i, pull = jax.vjp(
                lambda e, yy: loss_fn(e, yy, aux_mb), extra_v, y
            )
            # cotangent seed: 1 on grad_key, 0 elsewhere — with each leaf's
            # device-variance matched to the primal output it seeds
            seed = {
                k: jax.lax.pcast(
                    (jnp.ones if k == grad_key else jnp.zeros)(
                        v.shape, v.dtype
                    ),
                    tuple(jax.typeof(v).vma),
                    to="varying",
                )
                for k, v in sums_i.items()
            }
            d_extra_i, d_y = pull(seed)
            lmask = jnp.logical_and(fwd_valid, rank == s - 1)
            sums_acc = jax.tree_util.tree_map(
                lambda acc, v: acc + jnp.where(lmask, v, 0), sums_acc, sums_i
            )
            gextra = jax.tree_util.tree_map(
                lambda acc, v: acc + jnp.where(lmask, v, 0), gextra,
                d_extra_i,
            )

            # ---- backward slot: rank r backwards microbatch
            # t - (2(S-1) - r), recomputing the stage forward from the
            # stashed input (remat) ----
            bwd_i = t - (2 * (s - 1) - rank)
            bwd_valid = jnp.logical_and(bwd_i >= 0, bwd_i < m)
            bwd_mb = jnp.clip(bwd_i, 0, m - 1)
            g_in = jnp.where(rank == s - 1, d_y, bwd_state)
            x_res = jax.lax.dynamic_index_in_dim(
                stash, bwd_mb % stash_n, 0, keepdims=False
            )
            if takes_mb:
                stg = lambda p, xx: stage_fn(p, xx, bwd_mb)
            else:
                stg = lambda p, xx: stage_fn(p, xx)
            _, pull2 = jax.vjp(stg, my_params_v, x_res)
            dp, dx = pull2(g_in)
            gparams = jax.tree_util.tree_map(
                lambda acc, v: acc + jnp.where(bwd_valid, v, 0), gparams, dp
            )
            dxs = jax.lax.cond(
                jnp.logical_and(bwd_valid, rank == 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, dx, bwd_mb, 0
                ),
                lambda o: o,
                dxs,
            )

            fwd_state = jax.lax.ppermute(y, axis, fwd_perm)
            bwd_state = jax.lax.ppermute(dx, axis, bwd_perm)
            return (fwd_state, bwd_state, stash, gparams, gextra, sums_acc,
                    dxs)

        (_, _, _, gparams, gextra, sums_acc, dxs) = jax.lax.fori_loop(
            0, ticks, tick, carry
        )
        # stage grads: this rank's slice, summed over the (manual) data
        # axes; the pipe axis stays sharded (out_spec P(axis))
        if data_axes:
            gparams = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(vary_over(g, data_axes), data_axes),
                gparams,
            )
        gparams = jax.tree_util.tree_map(lambda g: g[None], gparams)

        def full_sum(r):
            return jax.lax.psum(vary_over(r, vary_axes), vary_axes)

        gextra = jax.tree_util.tree_map(full_sum, gextra)
        sums_acc = jax.tree_util.tree_map(full_sum, sums_acc)
        # input grads live on rank 0 only; replicate over the pipe
        dxs = jax.lax.psum(
            jnp.where(jax.lax.axis_index(axis) == 0, dxs,
                      jnp.zeros_like(dxs)),
            axis,
        )
        return sums_acc, {"stages": gparams, "x": dxs, "extra": gextra}

    sums_spec = jax.tree_util.tree_map(
        lambda v: P(*(None,) * v.ndim), sums_abstract
    )
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_spec, x_spec, aux_spec, extra_spec),
        out_specs=(sums_spec,
                   {"stages": p_spec, "x": x_spec, "extra": extra_spec}),
    )
    return fn(stacked_params, x, loss_aux, extra_params)
