"""Training cells: the program's own entry points (`bootstrap`,
`MultiWorkerMirroredStrategy`, `init_state`, `make_custom_train_step` with
`next_token_loss`, `masked adamw`), driven as `chip_smoke.py` drives them.

Set-up builds one object, the compiled step with its state, drives it from
the seed through its first three steps by the window's own call and feed,
and hands that same object to the window. After the window the state is
freed, the peak memory is read, and the plain reference follows the same
three steps on the same weights and rows. Compared, each with its limit
from the configuration file: every step's loss, the first gradient's norm
by the worst leaf (worked out from Adam's first moment after one step),
the norm of the weights' change after three steps by the worst leaf, that
every loss of the window is finite and that the loss fell.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.lib import clock, flops, traffic
from benchmarks.lib.hostwatch import HostWatch
from benchmarks.lib.manifest import reference_module
from benchmarks.lib.result import memory_peak_bytes
from benchmarks.lib.stats import median

WARM_STEPS = 3


def build_model(cfg: dict):
    """`models.gpt.GPT` from the configuration file's published sizes:
    the existing constructor, no preset."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    extra = dict(cfg.get("constructor", {}))
    extra["dtype"] = getattr(jnp, extra.get("dtype", "bfloat16"))
    return GPT(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        depth=cfg["n_layer"], num_heads=cfg["n_head"],
        mlp_dim=cfg.get("n_inner") or 4 * cfg["n_embd"],
        max_position=cfg["n_positions"], ln_eps=cfg["layer_norm_epsilon"],
        dropout_rate=cfg["resid_pdrop"], **extra)


def _first_moment(opt_state):
    """Adam's first moment inside optax's chained state."""
    import jax

    found = [node.mu for node in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu")) if hasattr(node, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


class Program:
    """The compiled step with its state, and the feed. `step(i)` places
    rows [i*B, (i+1)*B) of the pool, runs one step and fetches its loss."""

    def __init__(self, cfg: dict, ref, seed: int, pool, global_batch: int,
                 devices):
        import jax

        from tfde_tpu import bootstrap
        from tfde_tpu.models.gpt import next_token_loss
        from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
        from tfde_tpu.runtime.mesh import data_parallel_mesh
        from tfde_tpu.training.optimizers import adamw as masked_adamw
        from tfde_tpu.training.step import init_state, make_custom_train_step

        bootstrap()
        hyper = cfg["optimizer"]
        self.model = build_model(cfg)
        self.strategy = MultiWorkerMirroredStrategy(
            mesh=data_parallel_mesh(list(devices)))
        tx = masked_adamw(hyper["learning_rate"], b1=hyper["b1"],
                          b2=hyper["b2"], eps=hyper["eps"],
                          weight_decay=hyper["weight_decay"])
        seq = cfg["seq_len"]
        state, shardings = init_state(
            self.model, tx, self.strategy,
            np.zeros((global_batch, seq), np.int32))
        # the benchmark's weights, not the program's own initialisation:
        # the reference is given the same numbers and nothing else
        weights = ref.to_program_params(
            ref.make_weights(seed, ref.dims_of(cfg)), ref.dims_of(cfg)["n_head"])
        self.state = state.replace(
            params=jax.device_put(weights, shardings.params))
        del state, weights
        self.step_fn = make_custom_train_step(
            self.strategy, self.state, next_token_loss)
        self.rng = jax.random.key(1)
        self.pool, self.batch = pool, global_batch
        self.batch_sharding = self.strategy.batch_sharding()
        self.last_batch = self.last_metrics = self.last_split = None

    def rows(self, i: int) -> np.ndarray:
        n = len(self.pool) // self.batch
        return self.pool[(i % n) * self.batch:(i % n + 1) * self.batch]

    def step(self, i: int) -> float:
        """Place step i's rows, enqueue the step, fetch its loss; the three
        parts' seconds are kept in `last_split`."""
        import jax

        t0 = clock.now()
        self.last_batch = jax.device_put(
            (self.rows(i),), self.batch_sharding)
        t1 = clock.now()
        self.state, self.last_metrics = self.step_fn(
            self.state, self.last_batch, self.rng)
        t2 = clock.now()
        loss = float(clock.fetch(self.last_metrics["loss"]))
        self.last_split = (t1 - t0, t2 - t1, clock.now() - t2)
        return loss


def warm_up(program: Program, ref, cfg: dict, seed: int) -> dict:
    """The first steps through the window's own call; what the reference
    will be asked about them."""
    import jax

    b1 = cfg["optimizer"]["b1"]
    losses, grad_norms = [], None
    for i in range(WARM_STEPS):
        losses.append(program.step(i))
        if i == 0:
            mu = ref.from_program_params(_first_moment(
                program.state.opt_state))
            grad_norms = {k: np.asarray(v) / (1.0 - b1) for k, v in
                          jax.device_get(ref.leaf_norms(mu)).items()}
    dev0 = jax.devices()[0]
    after = jax.device_put(ref.from_program_params(program.state.params),
                           dev0)
    start = jax.device_put(ref.make_weights(seed, ref.dims_of(cfg)), dev0)
    delta = jax.device_get(ref.delta_norms(after, start))
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def compare(got: dict, want: dict, ref, limits: dict) -> tuple:
    """([(name, value, limit)], the worst leaves) for the first steps
    against the reference. The gradient's norms are judged in two groups:
    the blocks' leaves, where bf16 compute itself stands 1-2 % off the
    float32 reference, and the leaves outside the blocks, where it stands
    within 0.1 % and a lower precision shows."""
    loss_gap = max(abs(g - w) / abs(w)
                   for g, w in zip(got["losses"], want["losses"]))
    grads = (got["grad_norms"], want["grad_norms"])
    blocks = [k for k in want["grad_norms"] if k not in ref.HEAD_LEAVES]
    block, block_leaf = ref.worst_norm_gap(*grads, leaves=blocks)
    head, head_leaf = ref.worst_norm_gap(*grads, leaves=ref.HEAD_LEAVES)
    upd, upd_leaf = ref.worst_norm_gap(got["delta_norms"],
                                       want["delta_norms"], updates=True)
    by_leaf = {k: float(v.max())
               for k, v in ref.norm_gaps(*grads).items()}
    return [
        ("loss_rel_gap_max", loss_gap, limits["loss_rel_gap_max"]),
        ("grad_norm_gap_block_leaves", block,
         limits["grad_norm_gap_block_leaves"]),
        ("grad_norm_gap_head_leaves", head,
         limits["grad_norm_gap_head_leaves"]),
        ("update_norm_gap_worst_leaf", upd,
         limits["update_norm_gap_worst_leaf"]),
    ], {"block": block_leaf, "head": head_leaf, "update": upd_leaf,
        "grad_gap_by_leaf": by_leaf}


def run(ctx) -> dict:
    import jax

    cfg, cell = ctx.config, ctx.cell
    ref = reference_module(cfg["reference"])
    dims, chips = ref.dims_of(cfg), cell["chips"]
    devices = ctx.devices[:chips]
    data = traffic.generate(ctx.traffic, ctx.seed, ctx.seconds,
                            vocab=dims["vocab_size"], seq_len=cfg["seq_len"],
                            chips=chips)
    pool, batch = data["pool"], data["global_batch"]
    t_data = clock.now()
    program = Program(cfg, ref, ctx.seed, pool, batch, devices)
    t_built = clock.now()
    got = warm_up(program, ref, cfg, ctx.seed)
    t_warm = clock.now()

    # -- the window ---------------------------------------------------------
    compiles_before = ctx.meter.snapshot()["backend_compiles"]
    # the traced run profiles the window's last seconds and stops the
    # profiler after the window, so that its stop is not in the window
    trace_from = ctx.seconds - min(float(ctx.traffic["trace_seconds"]),
                                   ctx.seconds / 2.0)
    losses, step_s, splits = [], [], []
    watch = HostWatch()
    setup_meter = ctx.meter.snapshot()
    t_open = clock.now()
    setup_s = t_open - ctx.t_start
    watch.start()
    i = WARM_STEPS
    while True:
        t0 = clock.now()
        elapsed = t0 - t_open
        if elapsed >= ctx.seconds:
            break
        if (ctx.tracer is not None and not ctx.tracer.started
                and elapsed >= trace_from):
            ctx.tracer.start()
        losses.append(program.step(i))
        step_s.append(clock.now() - t0)
        splits.append(program.last_split)
        i += 1
    window_s = clock.now() - t_open
    host = watch.stop()
    if ctx.tracer is not None:
        ctx.tracer.stop()
    window_compiles = ctx.meter.snapshot()["backend_compiles"] - compiles_before
    steps = len(losses)
    wrapped = steps + WARM_STEPS > len(pool) // batch

    # -- placement, then free the program and read the peak ------------------
    outputs = jax.tree_util.tree_leaves(
        (program.state.params, program.last_metrics))
    placed = {
        "batch_devices": len(program.last_batch[0].sharding.device_set),
        "output_devices_min": min(len(x.sharding.device_set)
                                  for x in outputs),
    }
    del outputs
    program.state = program.last_batch = program.last_metrics = None
    peak = memory_peak_bytes(devices)

    # -- the reference, on the same weights and rows -------------------------
    t_ref = clock.now()
    batches = [program.rows(k) for k in range(WARM_STEPS)]
    want = ref.train_steps(ref.make_weights(ctx.seed, dims), batches, dims,
                           cfg["optimizer"], devices=devices)
    reference_s = clock.now() - t_ref
    limits = cfg["correct"]
    compared, worst_leaves = compare(got, want, ref, limits)
    finite = all(math.isfinite(v) for v in losses + got["losses"])
    tail = median(losses[-max(1, steps // 10):]) if losses else float("nan")
    compared += [
        ("nonfinite_losses", 0.0 if finite else 1.0, 0.0),
        ("loss_last_tenth_over_first", tail / got["losses"][0],
         limits["loss_last_tenth_over_first"]),
        ("devices_missing_batch_or_outputs",
         float(2 * chips - placed["batch_devices"]
               - placed["output_devices_min"]), 0.0),
    ]
    control = None
    if ctx.control:
        lower = ref.train_steps(ref.make_weights(ctx.seed, dims), batches,
                                dims, cfg["optimizer"],
                                precision=cfg["control_precision"],
                                devices=devices)
        control, control_leaves = compare(lower, want, ref, limits)
        worst_leaves["control"] = control_leaves

    tokens = steps * batch * cfg["seq_len"]
    per_token = flops.gpt_train_flops_per_token(
        dims["n_embd"], dims.get("n_inner") or 4 * dims["n_embd"],
        dims["n_layer"], cfg["seq_len"], dims["vocab_size"])
    longest = None
    if steps:
        slow = max(range(steps), key=step_s.__getitem__)
        longest = dict(zip(("place_s", "enqueue_s", "fetch_s"), splits[slow]),
                       index=slow, s=step_s[slow], at_s=sum(step_s[:slow]),
                       median_s=median(step_s))
    notes = {
        "steps": steps, "global_batch": batch, "window_s": window_s,
        "window_compiles": window_compiles, "pool_wrapped": wrapped,
        "first_losses": got["losses"], "reference_losses": want["losses"],
        "loss_first": got["losses"][0], "loss_last_tenth_median": tail,
        "reference_s": reference_s,
        "setup_parts_s": {"start_to_data": t_data - ctx.t_start,
                          "build_program": t_built - t_data,
                          "first_steps": t_warm - t_built,
                          "compile": setup_meter["compile_s"]},
        "longest_step": longest, "host": host,
        "worst_leaves": worst_leaves, **placed,
    }
    return {
        "attempted": steps,
        "failed": sum(1 for v in losses if not math.isfinite(v)),
        "end_to_end": {
            "train_tokens_per_s": tokens / window_s,
            "setup_s": setup_s,
        },
        "compared": compared,
        "control": control,
        "memory_peak_bytes": peak,
        "notes": notes,
        "observed": {
            "counters": dict(setup_meter, window_compiles=window_compiles,
                             steps=steps),
            "step_s": step_s,
            "tokens_per_step": batch * cfg["seq_len"],
            "train_flops_per_token": per_token,
            "chips": chips,
            "flash": {
                "batch_per_chip": batch // chips,
                "heads": dims["n_head"],
                "seq": cfg["seq_len"],
                "head_dim": dims["n_embd"] // dims["n_head"],
            },
        },
    }
