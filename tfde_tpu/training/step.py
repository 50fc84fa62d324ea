"""Compiled train/eval steps — the hot loop (SURVEY.md §3, "HOT LOOP").

One traced computation serves every strategy: the batch arrives sharded over
the mesh's data axes, params/opt-state carry the strategy's shardings, and the
XLA SPMD partitioner inserts the gradient `psum` (replacing the reference's
CollectiveAllReduce, distributed_with_keras.py:16) or reduce-scatter/all-gather
pairs (ZeRO/FSDP, the ParameterServerStrategy capability). No hand-written
collectives, per the design rule in SURVEY.md §2b.

Loss convention: mean over the *global* batch == sum x 1/global_batch
(tf2_mnist_distributed.py:81-83); see ops/losses.py.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from tfde_tpu.ops import losses, metrics as metrics_lib
from tfde_tpu.parallel import axes as axes_lib
from tfde_tpu.parallel import comms as comms_lib
from tfde_tpu.parallel import zero as zero_lib
from tfde_tpu.parallel.strategies import Strategy
from tfde_tpu.training.train_state import TrainState

log = logging.getLogger(__name__)


def sown_losses_by_name(mutated_losses) -> dict:
    """Group everything sown into the 'losses' collection by its final sown
    name (e.g. 'moe_aux', 'moe_z'), summed across layers. The ONE
    definition of "every sown loss joins the objective" — used by the
    default classification path (`_forward`) and the custom-LM path
    (models/gpt.py `next_token_loss`); sow() into an immutable collection
    is a silent no-op, so any apply that skips this drops the MoE
    load-balance term."""
    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(mutated_losses):
        keys = [getattr(p, "key", None) for p in path]
        name = next((k for k in reversed(keys) if isinstance(k, str)), "aux")
        by_name[name] = by_name.get(name, 0.0) + jnp.sum(leaf)
    return by_name


def _forward(state: TrainState, params, images, train: bool, dropout_rng=None):
    """Returns (logits, new_batch_stats, aux_loss). aux_loss collects every
    value the model sows into the 'losses' collection (e.g. the MoE
    load-balance loss, models/moe.py) so routed models train correctly under
    the default classification step too."""
    variables = {"params": params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    kwargs = {}
    if dropout_rng is not None:
        kwargs["rngs"] = {"dropout": dropout_rng}
    if train:
        logits, mutated = state.apply_fn(
            variables, images, train=True,
            mutable=["batch_stats", "losses"], **kwargs
        )
        aux = sum(
            sown_losses_by_name(mutated.get("losses", {})).values()
        )
        return logits, mutated.get("batch_stats", state.batch_stats), aux
    logits = state.apply_fn(variables, images, train=train, **kwargs)
    return logits, state.batch_stats, jnp.zeros((), jnp.float32)


def _classification_loss(state: TrainState, params, batch, rng):
    """The default objective (tf2_mnist_distributed.py:81-83 semantics) in
    loss_fn form — the single definition behind both `train_step` and the
    grad-accum path, so they cannot drift."""
    images, labels = batch
    logits, new_stats, aux = _forward(
        state, params, images, train=True, dropout_rng=rng
    )
    loss = losses.sparse_categorical_crossentropy(logits, labels) + aux
    return loss, {
        "accuracy": metrics_lib.accuracy(logits, labels),
        "batch_stats": new_stats,
    }


def train_step(
    state: TrainState, batch: Tuple[jax.Array, jax.Array], rng: jax.Array
) -> Tuple[TrainState, dict]:
    """One SGD step. batch = (images, int labels); returns (state, metrics)."""
    step_rng = jax.random.fold_in(rng, state.step)

    def loss_fn(params):
        return _classification_loss(state, params, batch, step_rng)

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params
    )
    metrics = dict(metrics)
    new_stats = metrics.pop("batch_stats", state.batch_stats)
    new_state = state.apply_gradients(grads, new_batch_stats=new_stats)
    # global grad norm: the divergence/clipping telemetry every training
    # dashboard wants — computed from grads already in registers, one
    # scalar, summarized at the usual cadence by the lifecycle
    metrics["grad_norm"] = optax.global_norm(grads)
    return new_state, {"loss": loss, **metrics}


def eval_step(
    state: TrainState, batch: Tuple[jax.Array, jax.Array, jax.Array]
) -> dict:
    """Masked eval: batch = (images, labels, mask). The mask (1 for real
    examples, 0 for padding) lets ragged final eval batches — the reference
    batches the eval set without dropping the remainder (mnist_keras:147) —
    be padded up to the mesh's batch divisor while keeping exact metrics."""
    images, labels, mask = batch
    logits, _, _ = _forward(state, state.params, images, train=False)
    labels1d = labels.reshape(labels.shape[:1])
    per_ex = losses.softmax_cross_entropy_with_integer_labels(logits, labels)
    correct = (jnp.argmax(logits, axis=-1) == labels1d).astype(jnp.float32)
    # Sums, not means: the caller accumulates *on device* and fetches once at
    # the end of the pass — per-step host syncs would serialize eval on
    # high-latency links (each device_get is a full round trip).
    return {
        "loss_sum": jnp.sum(per_ex * mask),
        "correct_sum": jnp.sum(correct * mask),
        "weight": jnp.sum(mask),
    }


def _state_shardings(strategy: Strategy, state: TrainState):
    mesh = strategy.mesh

    def ns(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    if state.opt_layout is not None:
        # ZeRO-sharded optimizer state (parallel/zero.py): [N, C] chunk
        # leaves shard row-wise over the data axis — genuinely distributed
        # arrays, 1/N bytes per device, checkpointed shard-by-shard. On a
        # mesh whose data axis does not match the layout (e.g. an eval
        # strategy) the chunks replicate; only the train step needs them
        # distributed.
        daxis = comms_lib.data_axis(mesh)
        if daxis is not None and int(mesh.shape[daxis]) == state.opt_layout.nshards:
            opt_spec = zero_lib.opt_state_spec(
                state.opt_state, daxis, state.opt_layout.nshards
            )
        else:
            opt_spec = jax.tree_util.tree_map(lambda _: P(), state.opt_state)
    else:
        opt_spec = strategy.opt_state_spec(state.opt_state, state.params)
    return TrainState(
        step=NamedSharding(mesh, P()),
        params=ns(strategy.params_spec(state.params)),
        batch_stats=ns(
            jax.tree_util.tree_map(lambda _: P(), state.batch_stats)
        ),
        opt_state=ns(opt_spec),
        apply_fn=state.apply_fn,
        tx=state.tx,
        # error-feedback residual (parallel/comms.py): nominally replicated
        # — each device's copy differs, but only the exchange reads it, so
        # the claim is safe and XLA never moves the bytes
        comm_residual=ns(
            jax.tree_util.tree_map(lambda _: P(), state.comm_residual)
        ),
        opt_layout=state.opt_layout,  # static field: treedefs must match
    )


def init_state(
    model,
    tx,
    strategy: Strategy,
    sample_input: jax.Array,
    seed: int = 0,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState *directly sharded* per the strategy.

    Init runs under `jit` with `out_shardings` so large FSDP params
    materialize already-sharded (never a full replica per host). Returns
    (state, state_shardings).
    """
    mesh = strategy.mesh
    ccfg = comms_lib.effective(strategy.comms, mesh)

    def base_init(rng):
        # a tuple sample feeds multi-input models positionally (the T5
        # encoder-decoder takes (input_ids, decoder_input_ids)); a bare
        # array keeps the single-input contract every other family uses
        sample = jax.tree_util.tree_map(jnp.zeros_like, sample_input)
        args = sample if isinstance(sample, tuple) else (sample,)
        variables = model.init(rng, *args, train=False)
        return variables["params"], variables.get("batch_stats", {})

    # ZeRO weight-update sharding (parallel/zero.py): decide eligibility
    # from shapes alone, then init the optimizer on the PACKED params (tx
    # init depends on param values for e.g. param-EMA slots, so pack the
    # real values, not zeros) with the chunk arrays born sharded.
    layout = None
    if zero_lib.resolve(strategy.opt_sharding) == "shard":
        ab_params, _ = jax.eval_shape(base_init, jax.random.key(seed))
        zaxis = zero_lib.eligible_axis(strategy, ab_params)
        if zaxis is not None:
            if zero_lib.packable(jax.eval_shape(tx.init, ab_params)):
                layout = zero_lib.build_layout(
                    ab_params, ccfg, int(mesh.shape[zaxis])
                )
            else:
                log.warning(
                    "opt_sharding='shard' with a masked optimizer "
                    "(optax.masked / a decay mask) would re-evaluate the "
                    "mask on the packed tree — falling back to replicated"
                )

    def init_fn(rng):
        params, batch_stats = base_init(rng)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=(
                tx.init(zero_lib.pack_params(params, layout))
                if layout is not None else tx.init(params)
            ),
            apply_fn=model.apply,
            tx=tx,
            # int8 transport: allocate the error-feedback residual up
            # front so the step's carry structure is fixed. fp32 keeps
            # None — state structure (and checkpoints) byte-identical.
            comm_residual=(
                comms_lib.init_residual(params, ccfg)
                if ccfg.transport == "int8" else None
            ),
            opt_layout=layout,
        )
    _claim("train/init", init_fn)
    abstract = jax.eval_shape(init_fn, jax.random.key(seed))
    shardings = _state_shardings(strategy, abstract)
    state = jax.jit(init_fn, out_shardings=shardings)(jax.random.key(seed))
    return state, shardings


def _with_mesh(fn, mesh):
    """Trace `fn` under axes.use_axes(mesh) so the models' activation
    `constrain` annotations (parallel/axes.py) bind to the strategy's mesh.
    with_sharding_constraint is a trace-time op, so entering the context
    inside the traced body is exactly what pins it."""

    @functools.wraps(fn)
    def wrapped(*args):
        with axes_lib.use_axes(mesh):
            return fn(*args)

    return wrapped


def _sentried(step_fn, sentry_cfg):
    """Fuse the numerics sentry (observability/sentry.py) onto a step fn:
    the returned fn takes an extra device-side sentry carry and returns the
    updated carry. Pure jnp on metrics already in registers — the check
    compiles INTO the step (no second dispatch, no host callback); the host
    polls the carry's sticky flag only every poll_every steps."""
    from tfde_tpu.observability import sentry as sentry_lib

    def fused(state, batch, rng, sstate):
        new_state, m = step_fn(state, batch, rng)
        new_sstate = sentry_lib.update(
            sentry_cfg, sstate, new_state.step, m["loss"], m.get("grad_norm"),
            # int8 gradient transport (parallel/comms.py): the residual
            # norm feeds its EWMA; a quantizer overflow trips the sentry
            # instead of saturating silently
            residual_norm=m.get("comm_residual_norm"),
            comm_overflow=m.get("comm_overflow"),
        )
        return new_state, m, new_sstate

    return fused


def _resolve_comms(strategy: Strategy, state: TrainState, comms):
    """The one resolution point for the grad_transport knob: explicit arg >
    strategy knob ($TFDE_GRAD_TRANSPORT-aware), downgraded to fp32 on
    ineligible meshes (comms.effective) or when the state carries no
    error-feedback residual (e.g. built before the knob was set, or the
    LoRA path — the adapters are tiny; compressing them saves nothing)."""
    cfg = comms_lib.resolve(comms if comms is not None else strategy.comms)
    cfg = comms_lib.effective(cfg, strategy.mesh)
    if cfg.transport == "int8" and state.comm_residual is None:
        log.warning(
            "grad_transport='int8' but the TrainState has no comm_residual "
            "(built with fp32 transport?) — falling back to fp32. "
            "Re-init the state with the strategy's grad_transport set."
        )
        cfg = dataclasses.replace(cfg, transport="fp32")
    return cfg


def _resolve_opt_sharding(strategy: Strategy, state: TrainState,
                          opt_sharding=None) -> bool:
    """The one resolution point for the weight-update sharding knob
    (parallel/zero.py): the STATE's physical layout is authoritative — the
    optimizer state either is packed/sharded or it is not — and the knob
    (explicit arg > strategy > $TFDE_OPT_SHARDING) only gets to warn when
    it disagrees (state built before the knob was set, or an ineligible
    mesh already fell back at init)."""
    mode = zero_lib.resolve(
        opt_sharding if opt_sharding is not None else strategy.opt_sharding
    )
    if state.opt_layout is not None:
        if mode != "shard":
            log.warning(
                "opt_sharding='replicated' requested but the TrainState "
                "carries a sharded (packed) optimizer state — using the "
                "sharded update. Re-init the state to change layouts."
            )
        return True
    if mode == "shard":
        log.warning(
            "opt_sharding='shard' but the TrainState's optimizer state is "
            "replicated (built before the knob was set, or the mesh/"
            "optimizer was ineligible at init) — falling back to the "
            "replicated update. Re-init the state with the strategy's "
            "opt_sharding set."
        )
    return False


def _make_comms_step(strategy: Strategy, state: TrainState, loss_fn,
                     cfg: comms_lib.CommsConfig, grad_accum: int):
    """Build the explicit-exchange step fn: gradients computed per device
    on the LOCAL batch shard inside a `shard_map` over the data axis, then
    exchanged through the quantized all-reduce (parallel/comms.py) and/or
    updated through the ZeRO owner-chunk path (parallel/zero.py) instead
    of the partitioner's implicit fp32 psum + replicated update. Serves
    three of the four mode combinations (int8 x replicated — the original
    `_make_int8_step` — plus fp32/int8 x sharded); fp32 x replicated never
    reaches here, keeping that jaxpr byte-identical.

    The microbatch semantics match the fp32 path exactly: the device-major
    split there means global microbatch `a` is the concatenation of every
    device's a-th local sub-chunk — which is precisely the local
    [A, b_local/A] reshape here. Weighted accumulation decomposes too:
    sum_i sum_a w_ia * g_ia / sum w_ia over LOCAL masked means equals the
    global weighted update, because w*grad(masked mean) == grad(masked
    sum). Compression happens ONCE per update, after the accumulation —
    never per microbatch.

    Known (documented) deviations from the fp32 oracle: dropout keys fold
    in the shard index (per-shard masks instead of one global mask — same
    statistics, different bits), and BatchNorm batch statistics are the
    mean of per-shard statistics.

    Sharded-update collective budget (within PR 5's five-collective pin):
    fp32 x shard = sidecar psum + fp32 psum_scatter + param all_gather
    (3); int8 x shard = sidecar psum + scale pmax + int8 psum_scatter +
    param all_gather (4) — the gradient all-gather x2 of the replicated
    int8 path is REPLACED by one fp32 all-gather of updated params, which
    also carries each chunk's squared grad-norm so `grad_norm` costs no
    extra collective.
    """
    mesh = strategy.mesh
    axis = comms_lib.data_axis(mesh)
    nshards = int(mesh.shape[axis])
    apply_fn, tx = state.apply_fn, state.tx
    zlay = state.opt_layout
    mask_leaves = jax.tree_util.tree_leaves(
        comms_lib.compress_mask(state.params, cfg)
    )
    if zlay is not None:
        assert tuple(mask_leaves) == zlay.mask, (
            "opt_layout disagrees with the comms compress mask — state "
            "built under a different CommsConfig than the step's"
        )

    def micro_grads_local(pstate, mb, r):
        def wrapped(params):
            # no active mesh inside the manual region: the models'
            # activation `constrain` calls degrade to identity (they only
            # speak batch/model axes, all trivial on a per-device shard)
            with axes_lib.use_axes(None):
                return loss_fn(pstate, params, mb, r)

        (loss, metrics), grads = jax.value_and_grad(wrapped, has_aux=True)(
            pstate.params
        )
        metrics = dict(metrics)
        new_stats = metrics.pop("batch_stats", pstate.batch_stats)
        weight = metrics.pop("grad_weight", None)
        return grads, loss, metrics, new_stats, weight

    def as_weight(w):
        return (jnp.ones((), jnp.float32) if w is None
                else jnp.asarray(w, jnp.float32))

    def body(step_c, params, batch_stats, opt_local, residual, batch, key):
        shard = jax.lax.axis_index(axis)
        key = jax.random.fold_in(key, shard)
        pstate = TrainState(
            step=step_c, params=params, batch_stats=batch_stats,
            opt_state=opt_local, apply_fn=apply_fn, tx=tx,
        )
        # -- local microbatch accumulation (mirrors the fp32 path) --------
        if grad_accum == 1:
            g, l, m, stats, w = micro_grads_local(
                pstate, batch, jax.random.fold_in(key, 0)
            )
            w0 = as_weight(w)
            grads = jax.tree_util.tree_map(lambda x: x * w0, g)
            loss, wsum = l * w0, w0
            metrics = jax.tree_util.tree_map(lambda x: x * w0, m)
        else:
            def split(x):
                a = x.shape[0] // grad_accum
                return x.reshape(grad_accum, a, *x.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)
            first = jax.tree_util.tree_map(lambda x: x[0], micro)
            rest = jax.tree_util.tree_map(lambda x: x[1:], micro)
            g, l, m, stats, w = micro_grads_local(
                pstate, first, jax.random.fold_in(key, 0)
            )
            w0 = as_weight(w)
            grads = jax.tree_util.tree_map(lambda x: x * w0, g)
            loss = l * w0
            metrics = jax.tree_util.tree_map(lambda x: x * w0, m)

            def scan_body(carry, inp):
                grads_sum, loss_sum, metrics_sum, wsum, stats = carry
                i, mb = inp
                st = pstate.replace(batch_stats=stats)
                gi, li, mi, stats, wi = micro_grads_local(
                    st, mb, jax.random.fold_in(key, i)
                )
                wi = as_weight(wi)
                return (
                    jax.tree_util.tree_map(
                        lambda a, b: a + b * wi, grads_sum, gi),
                    loss_sum + li * wi,
                    jax.tree_util.tree_map(
                        lambda a, b: a + b * wi, metrics_sum, mi),
                    wsum + wi,
                    stats,
                ), None

            idx = jnp.arange(1, grad_accum)
            (grads, loss, metrics, wsum, stats), _ = jax.lax.scan(
                scan_body, (grads, loss, metrics, w0, stats), (idx, rest)
            )

        # -- the exchange: one packed fp32 psum (small leaves + scalars), --
        # -- one quantized all-reduce (everything else)                   --
        grads_l, gdef = jax.tree_util.tree_flatten(grads)
        res_l = jax.tree_util.tree_flatten(residual)[0]
        big_g = [g for g, c in zip(grads_l, mask_leaves) if c]
        big_r = [r for r, c in zip(res_l, mask_leaves) if c]
        small_g = [g for g, c in zip(grads_l, mask_leaves) if not c]
        res_sq = sum(
            (jnp.sum(jnp.square(r)) for r in big_r),
            jnp.zeros((), jnp.float32),
        )
        mkeys = sorted(metrics)
        stats_l, stats_def = jax.tree_util.tree_flatten(stats)
        aux = (list(small_g) + [loss, wsum, res_sq]
               + [metrics[k] for k in mkeys] + list(stats_l))
        aux = comms_lib.psum_packed(aux, axis)
        ns_small = len(small_g)
        small_sum = aux[:ns_small]
        loss_g, wsum_g, res_sq_g = aux[ns_small:ns_small + 3]
        moff = ns_small + 3
        metrics_g = aux[moff:moff + len(mkeys)]
        stats_g = [s / nshards for s in aux[moff + len(mkeys):]]

        # wsum == 0 (every microbatch weightless on every shard) must give
        # the clean zero-gradient update, same as the fp32 path
        inv = 1.0 / jnp.where(wsum_g > 0, wsum_g, 1.0)
        metrics_out = {k: v * inv for k, v in zip(mkeys, metrics_g)}
        new_stats = jax.tree_util.tree_unflatten(stats_def, stats_g)

        if zlay is not None:
            # -- ZeRO owner-chunk update (parallel/zero.py): reduce-
            # SCATTER the mean gradient, update only this replica's 1/N
            # packed slice (optimizer state is the matching local slice),
            # then all-gather updated params — the gradient all-gather of
            # the replicated path becomes a param all-gather, whose
            # payload also carries each chunk's squared grad-norm.
            idx = jax.lax.axis_index(axis)
            cb, cs = zlay.chunk_big, zlay.chunk_small
            if big_g:
                gvec, _ = comms_lib.pack([g * inv for g in big_g])
                if cfg.transport == "int8":
                    rvec, rshapes = comms_lib.pack(big_r)
                    g_chunk, new_rvec, overflow = comms_lib.int8_scatter(
                        gvec, rvec, cfg, axis, nshards,
                        rng=(jax.random.fold_in(key, grad_accum)
                             if cfg.stochastic else None),
                    )
                    new_big_r = comms_lib.unpack(new_rvec, rshapes)
                else:
                    gvec = jnp.pad(
                        gvec, (0, zlay.padded_big - gvec.shape[0])
                    )
                    g_chunk = jax.lax.psum_scatter(
                        gvec, axis, scatter_dimension=0, tiled=True
                    )
                    overflow = jnp.zeros((), jnp.float32)
                    new_big_r = list(big_r)
            else:
                g_chunk = jnp.zeros((cb,), jnp.float32)
                overflow = jnp.zeros((), jnp.float32)
                new_big_r = []
            svec, _ = comms_lib.pack([s * inv for s in small_sum])
            svec = jnp.pad(svec, (0, zlay.padded_small - svec.shape[0]))
            s_chunk = jax.lax.dynamic_slice_in_dim(svec, idx * cs, cs)
            pb_vec, ps_vec = zero_lib.segment_vectors(params, zlay)
            g_chunks = {
                zero_lib.BIG: g_chunk[None],
                zero_lib.SMALL: s_chunk[None],
            }
            p_chunks = {
                zero_lib.BIG: jax.lax.dynamic_slice_in_dim(
                    pb_vec, idx * cb, cb)[None],
                zero_lib.SMALL: jax.lax.dynamic_slice_in_dim(
                    ps_vec, idx * cs, cs)[None],
            }
            new_p_chunks, new_opt = pstate.apply_chunk_gradients(
                g_chunks, p_chunks
            )
            gnorm_sq = (jnp.sum(jnp.square(g_chunk))
                        + jnp.sum(jnp.square(s_chunk)))
            payload = jnp.concatenate([
                new_p_chunks[zero_lib.BIG].reshape(-1),
                new_p_chunks[zero_lib.SMALL].reshape(-1),
                gnorm_sq[None],
            ])
            full = jax.lax.all_gather(payload, axis, tiled=True)
            full = full.reshape(nshards, cb + cs + 1)
            new_params = zero_lib.unpack_params(
                full[:, :cb].reshape(-1),
                full[:, cb:cb + cs].reshape(-1),
                zlay,
            )
            grad_norm = jnp.sqrt(jnp.sum(full[:, -1]))
            if residual is None:
                new_residual = None
            else:
                new_res_l, bi = [], 0
                for r, c in zip(res_l, mask_leaves):
                    if c:
                        new_res_l.append(new_big_r[bi])
                        bi += 1
                    else:
                        new_res_l.append(r)
                new_residual = jax.tree_util.tree_unflatten(gdef, new_res_l)
            return (new_params, new_opt, loss_g * inv, metrics_out,
                    new_stats, new_residual, overflow,
                    jnp.sqrt(res_sq_g), grad_norm)

        if big_g:
            gvec, gshapes = comms_lib.pack(
                [g * inv for g in big_g]
            )
            rvec, _ = comms_lib.pack(big_r)
            out_vec, new_rvec, overflow = comms_lib.int8_reduce(
                gvec, rvec, cfg, axis, nshards,
                rng=(jax.random.fold_in(key, grad_accum)
                     if cfg.stochastic else None),
            )
            big_out = comms_lib.unpack(out_vec, gshapes)
            new_big_r = comms_lib.unpack(new_rvec, gshapes)
        else:
            overflow = jnp.zeros((), jnp.float32)
            big_out, new_big_r = [], []

        out_l, new_res_l, bi, si = [], [], 0, 0
        for r, c in zip(res_l, mask_leaves):
            if c:
                out_l.append(big_out[bi])
                new_res_l.append(new_big_r[bi])
                bi += 1
            else:
                out_l.append(small_sum[si] * inv)
                new_res_l.append(r)
                si += 1
        grads_mean = jax.tree_util.tree_unflatten(gdef, out_l)
        new_residual = jax.tree_util.tree_unflatten(gdef, new_res_l)
        return (grads_mean, loss_g * inv, metrics_out, new_stats,
                new_residual, overflow, jnp.sqrt(res_sq_g))

    def step(state: TrainState, batch, rng):
        step_rng = jax.random.fold_in(rng, state.step)
        for leaf in jax.tree_util.tree_leaves(batch):
            n = leaf.shape[0]
            if n % (grad_accum * nshards):
                raise ValueError(
                    f"global batch {n} not divisible by grad_accum="
                    f"{grad_accum} x {nshards} data shards"
                )
        batch_specs = jax.tree_util.tree_map(
            lambda l: P(axis, *(None,) * (l.ndim - 1)), batch
        )
        if zlay is None:
            exchanged = jax.shard_map(
                lambda s, p, bs, r, b, k: body(s, p, bs, (), r, b, k),
                mesh=mesh,
                in_specs=(P(), P(), P(), P(), batch_specs, P()),
                out_specs=P(),
                check_vma=False,  # the residual is deliberately device-varying
            )(state.step, state.params, state.batch_stats,
              state.comm_residual, batch, step_rng)
            grads, loss, metrics, new_stats, new_residual, overflow, res_norm = (
                exchanged
            )
            new_state = state.apply_gradients(
                grads, new_batch_stats=new_stats,
                new_comm_residual=new_residual
            )
            metrics = dict(metrics)
            metrics.setdefault("grad_norm", optax.global_norm(grads))
            metrics["comm_residual_norm"] = res_norm
            metrics["comm_overflow"] = overflow
            return new_state, {"loss": loss, **metrics}

        # sharded update: params/opt emerge from the shard_map already
        # final — no apply_gradients outside (the update ran on-chunk)
        opt_specs = zero_lib.opt_state_spec(state.opt_state, axis, nshards)
        outs = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), opt_specs, P(), batch_specs, P()),
            out_specs=(P(), opt_specs, P(), P(), P(), P(), P(), P(), P()),
            check_vma=False,  # the residual is deliberately device-varying
        )(state.step, state.params, state.batch_stats, state.opt_state,
          state.comm_residual, batch, step_rng)
        (new_params, new_opt, loss, metrics, new_stats, new_residual,
         overflow, res_norm, grad_norm) = outs
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt,
            comm_residual=new_residual,
        )
        metrics = dict(metrics)
        metrics.setdefault("grad_norm", grad_norm)
        if cfg.transport == "int8":
            metrics["comm_residual_norm"] = res_norm
            metrics["comm_overflow"] = overflow
        return new_state, {"loss": loss, **metrics}

    return step


def _export_comm_gauges(state: TrainState, cfg, nshards: int) -> None:
    """Publish the analytic wire-byte accounting as comm/* gauges — set
    once at step-build time (the numbers are static per model x config)."""
    from tfde_tpu.observability import metrics as obs_metrics

    opt_sharding = "shard" if state.opt_layout is not None else "replicated"
    b = comms_lib.comm_bytes(state.params, cfg, nshards,
                             opt_sharding=opt_sharding)
    reg = obs_metrics.default_registry()
    reg.gauge("comm/bytes_per_step_fp32").set(b["fp32"])
    reg.gauge("comm/bytes_per_step_int8").set(b["int8"])
    reg.gauge("comm/compression_ratio").set(b["ratio"])
    reg.gauge("comm/compressed_elems").set(b["compressed_elems"])
    reg.gauge("comm/fp32_elems").set(b["fp32_elems"])


def _export_opt_gauges(state: TrainState) -> None:
    """Publish the weight-update-sharding memory/wire accounting as opt/*
    gauges: per-device optimizer-state bytes (the ~N x saving the ZeRO
    layout buys) and the trailing param all-gather's wire bytes (0 when
    replicated — there is no gather). Static per model x config, set once
    at step-build time.

    ``opt/state_bytes`` is MEASURED from the arrays XLA actually
    allocated (per-device shard bytes, parallel/zero.py
    measured_state_bytes); the shape-derived number stays published as
    ``opt/state_bytes_analytic`` for cross-check — a drift between the
    two is a padding or layout bug."""
    from tfde_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.default_registry()
    analytic = zero_lib.state_bytes(state.opt_state, state.opt_layout)
    measured = zero_lib.measured_state_bytes(state.opt_state)
    reg.gauge("opt/state_bytes").set(measured if measured else analytic)
    reg.gauge("opt/state_bytes_analytic").set(analytic)
    reg.gauge("opt/param_gather_bytes").set(
        zero_lib.param_gather_bytes(state.opt_layout)
    )


def make_train_step(strategy: Strategy, state: TrainState, donate: bool = True,
                    grad_accum: int = 1, sentry=None, comms=None,
                    opt_sharding=None):
    """Compile train_step with the strategy's shardings pinned. `grad_accum`
    splits the batch into that many sequential microbatches per update (see
    make_custom_train_step). `sentry` (a SentryConfig) fuses the numerics
    check into the compiled step; the returned callable then takes and
    returns an extra sentry-state pytree: (state, batch, rng, sstate) ->
    (state, metrics, sstate). `comms` overrides the strategy's
    grad_transport knob (parallel/comms.py); int8 routes through the
    custom-step machinery, fp32 is byte-identical to always.
    `opt_sharding` overrides the strategy's weight-update-sharding knob
    (parallel/zero.py); a sharded (packed-opt) state routes through the
    custom-step machinery too."""
    cfg = _resolve_comms(strategy, state, comms)
    if (grad_accum != 1 or cfg.transport == "int8"
            or _resolve_opt_sharding(strategy, state, opt_sharding)):
        return make_custom_train_step(
            strategy, state, _classification_loss, donate=donate,
            grad_accum=grad_accum, sentry=sentry, comms=cfg,
            opt_sharding=opt_sharding,
        )
    _export_opt_gauges(state)
    shardings = _state_shardings(strategy, state)
    batch_sh = strategy.batch_sharding()
    if sentry is None:
        return jax.jit(
            _with_mesh(train_step, strategy.mesh),
            in_shardings=(shardings, (batch_sh, batch_sh), None),
            out_shardings=(shardings, None),
            donate_argnums=(0,) if donate else (),
        )
    rep = NamedSharding(strategy.mesh, P())  # sentry carry: tiny, replicated
    return jax.jit(
        _with_mesh(_sentried(train_step, sentry), strategy.mesh),
        in_shardings=(shardings, (batch_sh, batch_sh), None, rep),
        out_shardings=(shardings, None, rep),
        donate_argnums=(0,) if donate else (),
    )


def make_custom_train_step(
    strategy: Strategy,
    state: TrainState,
    loss_fn: Callable[[TrainState, Any, Any, jax.Array], Tuple[jax.Array, dict]],
    donate: bool = True,
    grad_accum: int = 1,
    sentry=None,
    comms=None,
    opt_sharding=None,
):
    """Compile a train step with a user loss over an arbitrary batch pytree.

    The generalization of `make_train_step` for objectives beyond
    (images, labels) classification — MLM, seq2seq, contrastive — the analog
    of the reference's hand-written `model_fn` path
    (tf2_mnist_distributed.py:65-91), where the user owns the loss and the
    framework owns differentiation, sharding, and the optimizer update.

    `loss_fn(state, params, batch, rng) -> (scalar_loss, metrics_dict)`.
    Models with BatchNorm return updated stats under the reserved metrics key
    ``"batch_stats"``. Every batch leaf must be [global_batch, ...]; each is
    sharded over the mesh's data axes.

    `grad_accum=A` splits the global batch into A sequential microbatches
    inside the SAME compiled step (`lax.scan`), averaging gradients before
    the single optimizer update — activation memory drops ~A-fold while the
    update matches the full-batch step exactly (BatchNorm stats chain
    through the microbatches in order). For losses normalized by a
    data-dependent denominator (e.g. masked-LM CE over the masked-position
    count), a uniform average of microbatch gradients would be a
    mean-of-means; return that denominator under the reserved metrics key
    ``"grad_weight"`` and the accumulation weights each microbatch by it
    (gradients, loss, and metrics), restoring the exact full-batch update.
    The reserved key ``"grad_norm"`` is emitted automatically (global norm
    of the final averaged gradients); a loss_fn returning its own
    ``grad_norm`` metric takes precedence.
    The standard route to reference-scale global batches on few chips.

    `comms` selects the gradient transport (parallel/comms.py): None reads
    the strategy's grad_transport knob; 'fp32' (the default everywhere) is
    byte-identical to the historical path; 'int8' swaps the step body for
    the quantized exchange with error feedback — compression happens once
    per update, after grad accumulation.

    `opt_sharding` selects the weight-update layout (parallel/zero.py):
    None reads the strategy's knob; 'replicated' (the default) keeps every
    replica updating the full params; a state whose optimizer state was
    built sharded ('shard' at init_state) routes through the same
    explicit-exchange body as int8, with the update run on each replica's
    owned 1/N chunk and updated params all-gathered — composing with both
    transports inside the five-collective budget.
    """
    shardings = _state_shardings(strategy, state)
    batch_sh = strategy.batch_sharding()
    if grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
    ccfg = _resolve_comms(strategy, state, comms)
    zshard = _resolve_opt_sharding(strategy, state, opt_sharding)

    def micro_grads(state: TrainState, batch, rng):
        def wrapped(params):
            return loss_fn(state, params, batch, rng)

        (loss, metrics), grads = jax.value_and_grad(wrapped, has_aux=True)(
            state.params
        )
        metrics = dict(metrics)
        new_stats = metrics.pop("batch_stats", state.batch_stats)
        weight = metrics.pop("grad_weight", None)
        return grads, loss, metrics, new_stats, weight

    def step(state: TrainState, batch, rng):
        step_rng = jax.random.fold_in(rng, state.step)
        if grad_accum == 1:
            grads, loss, metrics, new_stats, _ = micro_grads(
                state, batch, step_rng
            )
            new_state = state.apply_gradients(grads, new_batch_stats=new_stats)
            metrics.setdefault("grad_norm", optax.global_norm(grads))
            return new_state, {"loss": loss, **metrics}

        b = axes_lib.batch_axes()
        from tfde_tpu.parallel.sharding import data_axes as _data_axes

        d_shards = 1
        for a in _data_axes(strategy.mesh):
            d_shards *= strategy.mesh.shape[a]

        def split(x):
            n = x.shape[0]
            if n % (grad_accum * d_shards):
                raise ValueError(
                    f"global batch {n} not divisible by grad_accum="
                    f"{grad_accum} x {d_shards} data shards"
                )
            m = n // (grad_accum * d_shards)
            # device-major split: microbatch i takes the i-th sub-chunk of
            # every device's local shard, so the [B] -> [A, B/A] reshape is
            # local to each device (a microbatch-major reshape would cut
            # across shard boundaries and force SPMD to replicate the batch
            # — "involuntary full rematerialization"). Microbatch membership
            # is exchangeable; the accumulated gradient is identical.
            x = x.reshape(d_shards, grad_accum, m, *x.shape[1:])
            x = jnp.swapaxes(x, 0, 1)
            x = x.reshape(grad_accum, d_shards * m, *x.shape[3:])
            # microbatches keep the data sharding on their own batch dim
            return axes_lib.constrain(x, None, b)

        micro = jax.tree_util.tree_map(split, batch)
        first = jax.tree_util.tree_map(lambda x: x[0], micro)
        rest = jax.tree_util.tree_map(lambda x: x[1:], micro)

        def as_weight(w):
            return (jnp.ones((), jnp.float32) if w is None
                    else jnp.asarray(w, jnp.float32))

        # microbatch 0 eagerly — its (grads, loss, metrics) fix the carry
        # structure for the scan over microbatches 1..A-1
        grads, loss, metrics, stats, w = micro_grads(
            state, first, jax.random.fold_in(step_rng, 0)
        )
        w0 = as_weight(w)
        grads = jax.tree_util.tree_map(lambda g: g * w0, grads)
        loss = loss * w0
        metrics = jax.tree_util.tree_map(lambda m: m * w0, metrics)

        def body(carry, inp):
            grads_sum, loss_sum, metrics_sum, wsum, stats = carry
            i, mb = inp
            st = state.replace(batch_stats=stats)
            g, l, m, stats, w = micro_grads(
                st, mb, jax.random.fold_in(step_rng, i)
            )
            wi = as_weight(w)
            return (
                jax.tree_util.tree_map(lambda a, b: a + b * wi, grads_sum, g),
                loss_sum + l * wi,
                jax.tree_util.tree_map(lambda a, b: a + b * wi, metrics_sum, m),
                wsum + wi,
                stats,
            ), None

        idx = jnp.arange(1, grad_accum)
        (grads, loss, metrics, wsum, stats), _ = jax.lax.scan(
            body, (grads, loss, metrics, w0, stats), (idx, rest)
        )
        # wsum == 0 (every microbatch weightless, e.g. an all-IGNORE MLM
        # batch) must yield the accum=1 behavior — a clean zero-gradient
        # update — not 0 * inf = NaN params; any positive wsum divides exactly
        inv = 1.0 / jnp.where(wsum > 0, wsum, 1.0)
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        loss = loss * inv
        metrics = jax.tree_util.tree_map(lambda m: m * inv, metrics)
        new_state = state.apply_gradients(grads, new_batch_stats=stats)
        metrics["grad_norm"] = metrics.get(
            "grad_norm", optax.global_norm(grads)
        )
        return new_state, {"loss": loss, **metrics}

    if ccfg.transport == "int8" or zshard:
        # swap the whole step body: local grads + explicit exchange
        # (quantized and/or owner-chunk-updated) instead of the
        # partitioner's implicit fp32 psum + replicated update. The fp32
        # `step` above is never traced, so the default path's jaxpr stays
        # byte-identical.
        step = _make_comms_step(strategy, state, loss_fn, ccfg, grad_accum)
        _export_comm_gauges(
            state, ccfg,
            int(strategy.mesh.shape[comms_lib.data_axis(strategy.mesh)]),
        )
    _export_opt_gauges(state)

    def batch_shardings(batch):
        return jax.tree_util.tree_map(lambda _: batch_sh, batch)

    if sentry is None:
        jitted = jax.jit(
            _with_mesh(step, strategy.mesh),
            in_shardings=(shardings, None, None),  # batch via device_put
            out_shardings=(shardings, None),
            donate_argnums=(0,) if donate else (),
        )

        def run(state: TrainState, batch, rng):
            batch = jax.device_put(batch, batch_shardings(batch))
            return jitted(state, batch, rng)
    else:
        rep = NamedSharding(strategy.mesh, P())  # sentry carry: replicated
        jitted = jax.jit(
            _with_mesh(_sentried(step, sentry), strategy.mesh),
            in_shardings=(shardings, None, None, rep),
            out_shardings=(shardings, None, rep),
            donate_argnums=(0,) if donate else (),
        )

        def run(state: TrainState, batch, rng, sstate):
            batch = jax.device_put(batch, batch_shardings(batch))
            return jitted(state, batch, rng, sstate)
    _claim("train/step", jitted)
    run.jitted = jitted  # the lower()/jaxpr inspection hook (tests)
    run.lower = jitted.lower  # quacks like the jitted fast path for guards
    return run


def make_custom_eval_step(
    strategy: Strategy,
    state: TrainState,
    eval_fn: Callable[[TrainState, Any, Any], dict],
):
    """Compile a weighted-metrics eval step for a user metric fn — the eval
    twin of make_custom_train_step (the Estimator's custom-objective path).

    `eval_fn(state, params, batch) -> {metric: per-batch mean}`; an optional
    reserved key ``"weight"`` carries the batch's aggregation weight (e.g.
    the masked-position count for MLM metrics; defaults to the batch size).
    The returned step emits weighted SUMS plus the weight, so the caller
    accumulates on device and divides once after the pass — the same
    one-fetch protocol as the classification eval_step."""
    shardings = _state_shardings(strategy, state)
    batch_sh = strategy.batch_sharding()

    def step(state: TrainState, batch):
        metrics = dict(eval_fn(state, state.params, batch))
        weight = metrics.pop("weight", None)
        if weight is None:
            leaf = jax.tree_util.tree_leaves(batch)[0]
            weight = jnp.asarray(float(leaf.shape[0]), jnp.float32)
        weight = jnp.asarray(weight, jnp.float32)
        out = {k: jnp.asarray(v, jnp.float32) * weight
               for k, v in metrics.items()}
        out["weight"] = weight
        return out

    jitted = jax.jit(
        _with_mesh(step, strategy.mesh),
        in_shardings=(shardings, None),
    )

    def run(state: TrainState, batch):
        batch = jax.device_put(
            batch, jax.tree_util.tree_map(lambda _: batch_sh, batch)
        )
        return jitted(state, batch)

    return run


def make_eval_step(strategy: Strategy, state: TrainState):
    shardings = _state_shardings(strategy, state)
    batch_sh = strategy.batch_sharding()
    return jax.jit(
        _with_mesh(eval_step, strategy.mesh),
        in_shardings=(shardings, (batch_sh, batch_sh, batch_sh)),
    )


def pad_batch_for_mesh(
    batch: Tuple, divisor: int
) -> Tuple[Any, Any, Any]:
    """Pad (images, labels) up to a multiple of the mesh batch divisor and
    append the validity mask consumed by eval_step."""
    import numpy as np

    images, labels = batch[0], batch[1]
    n = images.shape[0]
    padded = -(-n // divisor) * divisor
    mask = np.zeros((padded,), np.float32)
    mask[:n] = 1.0
    if padded != n:
        pad = [(0, padded - n)] + [(0, 0)] * (images.ndim - 1)
        images = np.pad(np.asarray(images), pad)
        labels = np.pad(np.asarray(labels), [(0, padded - n)] + [(0, 0)] * (labels.ndim - 1))
    return images, labels, mask


def _claim(site: str, fn) -> None:
    """The programs traced under `fn`'s name are `site`'s in the set-up ledger
    (observability/recompile.py): by name, because a wrapper around the jitted
    step would cost every step and hide `.lower()`. Defined at the END of the
    file and called from lines that were blank, so that no line above moved:
    the step holds Mosaic kernels, whose serialised modules carry the file and
    line of the frames they were traced under into jax's persistent-cache key,
    and a shifted `step` or `micro_grads` is a cold start for everyone."""
    from tfde_tpu.observability import recompile

    recompile.site(site).claim(fn.__name__)
