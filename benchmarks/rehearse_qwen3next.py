"""`rehearse.py compile serve` for a configuration whose driver is
`serve_qwen3next`: the decode scan at full depth and prefill waves at the
real sizes, compiled for a described v5e (`v5e:2x2`, no chip attached),
each with `memory_analysis()` and its compile time.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_qwen3next.py \
        [rows=64,48,32] [bucket:width ...]

`rehearse.py` builds GPT-2 by GPT-2's key names and float32 parameters and
may not be edited; this hands the batcher's own jitted programs the shapes
of THIS configuration: the model of `serve_qwen3next.build_model`, the
reference's bfloat16 parameter tree and the cache as the batcher lays it
out (a `delta_state` [rows, 32, 128, 128] float32 and a `conv_tail` a
delta-rule layer, `cached_key` / `cached_value` [rows, max_len, 2, 256] in
the attention layer). `rows=` compiles the decode scan and the waves once a
batch size (the configuration's own where not given): the builder keeps
the largest whose longest wave and scan leave 1 GB of the chip free. Each
line adds its arguments, outputs and temporaries to `peak_gb` (what the
program needs beside nothing else; a wave's row cache is donated, the
batch's cache and the parameters stand beside it: `beside_gb`), the Mosaic
kernels it holds, and whether a triangular solve survived as a custom call
or a while loop. Nothing here is a measurement: a compile that passes is
not a chip run.
"""

from __future__ import annotations

import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CONFIG = "qwen3-next-80b-serve-32k"


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from unittest import mock

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import manifest as manifest_lib
    from benchmarks.rehearse import _memory, _timed_compile
    from tfde_tpu.inference import server
    from tfde_tpu.inference.decode import _decode_clone, init_cache
    from tfde_tpu.inference.speculative import _set_index_counters

    cfg = manifest_lib.Manifest(ROOT).config(CONFIG)
    ref = manifest_lib.reference_module(cfg["reference"])
    model = manifest_lib.driver_module(cfg["driver"]).build_model(cfg)
    dims, b = ref.dims_of(cfg), cfg["batcher"]
    longest = max(x for x in b["prompt_buckets"] if x < b["max_len"])
    args = list(argv or [])
    batches = [b["batch_size"]]
    for a in [a for a in args if a.startswith("rows=")]:
        batches = [int(n) for n in a[len("rows="):].split(",")]
        args.remove(a)
    waves = [tuple(int(n) for n in a.split(":")) for a in args] or [
        (min(b["prompt_buckets"]), 1), (longest, 1),
        (longest, cfg["feed"]["max_unadmitted"])]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def nbytes(tree):
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(tree))

    params = on_chip(jax.eval_shape(lambda: ref.to_program_params(
        ref.make_weights(1, dims))))
    decode_model = _decode_clone(model)
    sampling = dict(temperature=0.0, top_k=None, top_p=None, min_p=None,
                    repetition_penalty=1.0)

    def say(program, beside, compiled, seconds):
        text = compiled.as_text()
        memory = _memory(compiled)
        print(json.dumps({"program": program, "compile_s": seconds,
                          "mosaic_calls": text.count(
                              'custom_call_target="tpu_custom_call"'),
                          "kernels": [name for name in ("flash_fwd",
                                                        "moe_gmm")
                                      if name in text],
                          "triangular_solve_ops": text.count(
                              "triangular-solve"),
                          "code_mib": compiled.memory_analysis()
                          .generated_code_size_in_bytes / 2.0 ** 20,
                          "beside_gb": beside / 1e9, **memory}), flush=True)

    with mock.patch("jax.default_backend", return_value="tpu"):
        for rows in batches:
            cache = on_chip(jax.eval_shape(lambda: _set_index_counters(
                init_cache(model, rows, b["max_len"]),
                np.zeros(rows, np.int32))))
            vec = on_chip(jax.ShapeDtypeStruct((rows,), jnp.int32))
            done = on_chip(jax.ShapeDtypeStruct((rows,), jnp.bool_))
            # the scan's arguments hold the cache and the parameters
            say(f"decode scan, {rows} rows x {b['max_len']}, depth "
                f"{b['scan_depth']}", 0,
                *_timed_compile(server._decode_scan.lower(
                    decode_model, cache, params, vec, vec, vec, done, None,
                    None, depth=b["scan_depth"], eos_id=None, pad_id=0,
                    **sampling)))
            for bucket, width in waves:
                row_cache = on_chip(jax.eval_shape(functools.partial(
                    init_cache, model, width, b["max_len"])))
                prompts = on_chip(jax.ShapeDtypeStruct((width, bucket),
                                                       jnp.int32))
                last = on_chip(jax.ShapeDtypeStruct((width,), jnp.int32))
                # the batch's cache stands beside a wave's program
                say(f"prefill, bucket {bucket} x width {width}, beside "
                    f"{rows} rows", nbytes(cache),
                    *_timed_compile(server._prefill_rows.lower(
                        decode_model, row_cache, params, prompts, last, None,
                        None, **sampling)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
