"""`rehearse.py compile serve` for a configuration whose driver is
`serve_sarvam`: the decode scan at full depth and the shortest and longest
prefill waves at the real sizes, compiled for a described v5e (`v5e:2x2`, no
chip attached), each with `memory_analysis()` and its compile time.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_sarvam.py [bucket:width ...]

`rehearse.py` builds GPT-2 by GPT-2's key names and float32 parameters and
may not be edited; this hands the batcher's own jitted programs the shapes
of THIS configuration: the model of `serve_sarvam.build_model`, the
reference's bfloat16 parameter tree and the cache as the batcher lays it out
(a `cached_latent` [rows, max_len, latent] and a `cached_rope_key` [rows,
max_len, rope] leaf a layer). Beside `memory_analysis()` each line says in
which layouts those leaves appear (one leaf of 576 values, no multiple of the
128 lanes, came to every program with the positions in the lanes, and the
decode scan copied all five to rows of cells and back, 3.2 GiB of
temporaries: PERF.md section 6, PR 40), whether any array with a head axis over
the cache's length exists in the program (`per_head_over_cache`: a K or V
per head formed from the cache, which the absorbed decode never does), and
which Mosaic kernels the program holds. Nothing here is a measurement: a
compile that passes is not a chip run.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CONFIG = "sarvam-105b-serve-32k"


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
    waves = [tuple(int(n) for n in a.split(":")) for a in (argv or [])] or [
        (min(b["prompt_buckets"]), 1), (longest, 1),
        (longest, cfg["feed"]["max_unadmitted"])]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: ref.to_program_params(
        ref.make_weights(1, dims))))
    decode_model = _decode_clone(model)
    heads, cells = cfg["num_attention_heads"], b["max_len"]
    sampling = dict(temperature=0.0, top_k=None, top_p=None, min_p=None,
                    repetition_penalty=1.0)

    def say(program, compiled, seconds):
        text = compiled.as_text()
        print(json.dumps({"program": program, "compile_s": seconds,
                          "mosaic_calls": text.count(
                              'custom_call_target="tpu_custom_call"'),
                          "kernels": [name for name in ("flash_fwd",
                                                        "moe_gmm")
                                      if name in text],
                          "latent_layouts": sorted(set(re.findall(
                              rf"bf16\[\d+,{cells},(?:{cfg['kv_lora_rank']}|"
                              rf"{cfg['qk_rope_head_dim']})\]"
                              r"\{[^}]*\}", text))),
                          "per_head_over_cache": sorted(set(re.findall(
                              rf"(?:bf16|f32)\[(?:\d+,)?(?:{heads},{cells}"
                              rf"|{cells},{heads}),(?:128|192|256)\]",
                              text))),
                          "code_mib": compiled.memory_analysis()
                          .generated_code_size_in_bytes / 2.0 ** 20,
                          **_memory(compiled)}), flush=True)

    with mock.patch("jax.default_backend", return_value="tpu"):
        cache = on_chip(jax.eval_shape(lambda: _set_index_counters(
            init_cache(model, b["batch_size"], b["max_len"]),
            np.zeros(b["batch_size"], np.int32))))
        vec = on_chip(jax.ShapeDtypeStruct((b["batch_size"],), jnp.int32))
        done = on_chip(jax.ShapeDtypeStruct((b["batch_size"],), jnp.bool_))
        say(f"decode scan, {b['batch_size']} rows x {b['max_len']}, depth "
            f"{b['scan_depth']}", *_timed_compile(server._decode_scan.lower(
                decode_model, cache, params, vec, vec, vec, done, None, None,
                depth=b["scan_depth"], eos_id=None, pad_id=0, **sampling)))
        for bucket, width in waves:
            rows = on_chip(jax.eval_shape(functools.partial(
                init_cache, model, width, b["max_len"])))
            prompts = on_chip(jax.ShapeDtypeStruct((width, bucket),
                                                   jnp.int32))
            last = on_chip(jax.ShapeDtypeStruct((width,), jnp.int32))
            say(f"prefill, bucket {bucket} x width {width}",
                *_timed_compile(server._prefill_rows.lower(
                    decode_model, rows, params, prompts, last, None, None,
                    **sampling)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
