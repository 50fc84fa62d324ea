"""The three rehearsals that cost no chip time, for every cell.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py tiny      # 1 and 2
    JAX_PLATFORMS=cpu python benchmarks/rehearse.py compile   # 3

1. `tiny`: each cell's toy twin (tests/benchmarks/fixtures/tiny) end to end
   through the real drivers and `run.py::run_cell` on the CPU, with the look
   for a chip left out (this script hands the CPU devices in).
2. The four-chip cell's twin runs on four virtual CPU devices in that pass.
3. `compile`: the real sizes compiled for a described v5e (`v5e:2x2`, no
   chip attached): the training step on one and four chips at per-chip
   batch 1 and 2, the decode scan, and the widest and longest prefill
   waves, each with `memory_analysis()` and its compile time. The program
   asks `jax.default_backend()` to pick its kernels; this script answers
   "tpu" for it, here and nowhere else.

Nothing here is a measurement: a compile that passes is not a chip run.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(ROOT, "tests", "benchmarks", "fixtures", "tiny")
GIB = 2.0 ** 30


def tiny(seconds: float = 2.0) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmarks import run as runner
    from benchmarks.lib.manifest import Manifest, check

    manifest = Manifest(TINY)
    problems = check(manifest)
    if problems:
        print("\n".join(problems))
        return 1
    failed = 0
    for cell in manifest.cells:
        print(f"== {cell}", flush=True)
        line = runner.run_cell(
            manifest, cell, seed=2_147_483_659, seconds=seconds, tracer=None,
            devices=jax.devices(), t_start=time.perf_counter())
        failed += not line["correct"]
    print(f"tiny rehearsal: {len(manifest.cells) - failed} of "
          f"{len(manifest.cells)} cells correct")
    return int(failed > 0)


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "arguments_gib": m.argument_size_in_bytes / GIB,
        "outputs_gib": m.output_size_in_bytes / GIB,
        "aliased_gib": m.alias_size_in_bytes / GIB,
        "temporaries_gib": m.temp_size_in_bytes / GIB,
        "live_gib": (m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes) / GIB,
    }


def _timed_compile(lowered) -> tuple:
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def compile_train(cfg: dict, topo, chips: int, per_chip: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers.train import build_model
    from tfde_tpu.models.gpt import next_token_loss
    from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
    from tfde_tpu.runtime.mesh import data_parallel_mesh
    from tfde_tpu.training.optimizers import adamw as masked_adamw
    from tfde_tpu.training.step import make_custom_train_step
    from tfde_tpu.training.train_state import TrainState

    hyper = cfg["optimizer"]
    model = build_model(cfg)
    strategy = MultiWorkerMirroredStrategy(
        mesh=data_parallel_mesh(list(topo.devices[:chips])))
    tx = masked_adamw(hyper["learning_rate"], b1=hyper["b1"], b2=hyper["b2"],
                      eps=hyper["eps"], weight_decay=hyper["weight_decay"])
    batch = per_chip * chips
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((batch, cfg["seq_len"]), np.int32),
        train=False)["params"])
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        batch_stats={}, opt_state=jax.eval_shape(tx.init, params),
        apply_fn=model.apply, tx=tx)
    step_fn = make_custom_train_step(strategy, state, next_token_loss)
    rows = jax.ShapeDtypeStruct((batch, cfg["seq_len"]), jnp.int32,
                                sharding=strategy.batch_sharding())
    rng = jax.eval_shape(lambda: jax.random.key(1))
    compiled, seconds = _timed_compile(step_fn.lower(state, (rows,), rng))
    text = compiled.as_text()
    return {"program": f"train step, {chips} chip(s), per-chip batch "
                       f"{per_chip}",
            "compile_s": seconds, **_memory(compiled),
            "mosaic_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count(" all-reduce(")
            + text.count(" all-reduce-start(")}


def compile_serve(cfg: dict, topo, waves: list) -> list:
    """The decode scan at full depth and the prefill at each (bucket,
    width) of `waves`, for one described chip. Uses the batcher's own
    jitted programs (`_decode_scan`, `_prefill_rows`), as a scratch script
    has to: the batcher builds its state on real devices."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from benchmarks.drivers.train import build_model
    from tfde_tpu.inference import server
    from tfde_tpu.inference.decode import _decode_clone, init_cache
    from tfde_tpu.inference.speculative import _set_index_counters

    one = SingleDeviceSharding(topo.devices[0])
    b = cfg["batcher"]
    model = build_model(cfg)
    decode_model = _decode_clone(model)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]))
    sampling = dict(temperature=0.0, top_k=None, top_p=None, min_p=None,
                    repetition_penalty=1.0)
    out = []

    cache = on_chip(jax.eval_shape(lambda: _set_index_counters(
        init_cache(model, b["batch_size"], b["max_len"]),
        np.zeros(b["batch_size"], np.int32))))
    vec = on_chip(jax.ShapeDtypeStruct((b["batch_size"],), jnp.int32))
    done = on_chip(jax.ShapeDtypeStruct((b["batch_size"],), jnp.bool_))
    lowered = server._decode_scan.lower(
        decode_model, cache, params, vec, vec, vec, done, None, None,
        depth=b["scan_depth"], eos_id=None, pad_id=0, **sampling)
    compiled, seconds = _timed_compile(lowered)
    out.append({"program": f"decode scan, {b['batch_size']} rows x "
                           f"{b['max_len']}, depth {b['scan_depth']}",
                "compile_s": seconds, **_memory(compiled)})

    for bucket, width in waves:
        rows = on_chip(jax.eval_shape(functools.partial(
            init_cache, model, width, b["max_len"])))
        prompts = on_chip(jax.ShapeDtypeStruct((width, bucket), jnp.int32))
        last = on_chip(jax.ShapeDtypeStruct((width,), jnp.int32))
        lowered = server._prefill_rows.lower(
            decode_model, rows, params, prompts, last, None, None, **sampling)
        compiled, seconds = _timed_compile(lowered)
        out.append({"program": f"prefill, bucket {bucket} x width {width}",
                    "compile_s": seconds, **_memory(compiled)})
    return out


def compile_real(which: str = "all") -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from unittest import mock

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    from benchmarks.lib.manifest import Manifest

    manifest = Manifest(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    rows = []
    with mock.patch("jax.default_backend", return_value="tpu"):
        if which in ("all", "train"):
            cfg = manifest.config("gpt2-medium-s4096")
            for chips, per_chip in ((1, 1), (1, 2), (4, 2)):
                try:
                    rows.append(compile_train(cfg, topo, chips, per_chip))
                except Exception as e:    # the compiler's refusal is the
                    rows.append({          # finding; keep going
                        "program": f"train step, {chips} chip(s), per-chip "
                                   f"batch {per_chip}",
                        "refused": f"{type(e).__name__}: {str(e)[:300]}"})
                print(json.dumps(rows[-1]), flush=True)
        if which in ("all", "serve"):
            cfg = manifest.config("gpt2-large-serve-1k")
            widest = cfg["feed"]["max_unadmitted"]
            longest = max(cfg["batcher"]["prompt_buckets"])
            for row in compile_serve(cfg, topo, [
                    (64, 1), (longest, 1), (longest, widest),
                    (longest, 2 * widest)]):
                rows.append(row)
                print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "tiny"
    if mode == "tiny":
        sys.exit(tiny())
    sys.exit(compile_real(sys.argv[2] if len(sys.argv) > 2 else "all"))
