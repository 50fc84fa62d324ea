"""Hardware A/B of the flash-attention backward implementations.

Times causal fwd+bwd on the host's clock for three implementations: XLA
reference einsum (autodiff), Pallas forward + the fused Pallas backward
(TFDE_FLASH_BWD=pallas, the default), Pallas forward + blockwise-JAX
backward (TFDE_FLASH_BWD=jax). 16 heads of 64: the S=4096 row is the
training cells' shape. Prints one JSON line. What the cells themselves
measure is in PERF.md; this is the quick look at a kernel change.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import _Clock
from tfde_tpu.ops.attention import reference_attention
from tfde_tpu.ops.flash_attention import flash_attention


def make_qkv(b, s, h, d):
    rng = np.random.default_rng(0)
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
        for _ in range(3)
    )


def main():
    causal = "--non-causal" not in sys.argv
    clock = _Clock()
    out = {"platform": jax.devices()[0].platform, "causal": causal}

    def ref_loss(q, k, v):
        return reference_attention(q, k, v, causal=causal).astype(jnp.float32).sum()

    ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))

    def make_flash_grad(bwd):
        # separate closures per bwd mode: the env var is read at trace time
        def loss(q, k, v):
            os.environ["TFDE_FLASH_BWD"] = bwd
            return flash_attention(q, k, v, causal=causal).astype(jnp.float32).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    impls = {"ref": ref_g, "pallas": make_flash_grad("pallas"),
             "jax": make_flash_grad("jax")}

    def time_impl(g, q, k, v):
        def run(reps):
            dq = None
            for _ in range(reps):
                dq, _, _ = g(q, k, v)
            return dq

        reps, window, _, _ = clock.timed(
            run, lambda dq: dq[0, 0, 0, 0].astype(jnp.float32), 1.0,
            start_reps=5, max_reps=5_000,
        )
        return window / reps

    for b, s in ((4, 2048), (2, 4096), (1, 8192)):
        q, k, v = make_qkv(b, s, 16, 64)
        times = {}
        for name, g in impls.items():
            os.environ["TFDE_FLASH_BWD"] = (
                "jax" if name == "jax" else "pallas"
            )
            clock.fetch_scalar(g(q, k, v)[0][0, 0, 0, 0].astype(jnp.float32))
            times[name] = time_impl(g, q, k, v)
        for name, t in times.items():
            out[f"{name}_ms_s{s}"] = round(t * 1e3, 3)
        out[f"pallas_speedup_s{s}"] = round(times["ref"] / times["pallas"], 3)
        out[f"jax_speedup_s{s}"] = round(times["ref"] / times["jax"], 3)
        print(json.dumps(out), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
