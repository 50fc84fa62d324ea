"""Per-op roofline suite for the attention hot path.

Extends the tools/flash_ab.py lowered-HLO/microbench pattern from a single
A/B into a roofline report: each op variant is timed on the live chip and
its ACHIEVED flops — credited by the analytic model in ops/roofline.py,
which counts causal/windowed attention at its true in-band work — are
reported against chip peak (bench.py's PEAK_FLOPS table). A full-causal
MFU figure computed against full-S^2 flops looks artificially healthy;
this is the per-op view that shows where the gpt_long gap actually lives.

Two modes:

  python tools/roofline.py               # hardware microbench (run on TPU;
                                         # runs on CPU via interpret mode
                                         # for plumbing checks, slowly)
  python tools/roofline.py --smoke       # tiny shapes, any backend
  python tools/roofline.py --check-tiles # tile-visit gate only: pins the
                                         # flash kernels' executed tile
                                         # schedule against the analytic
                                         # band (CPU-fast, no hardware) and
                                         # exits 1 on regression — wired
                                         # into tools/tier1.sh

Per-op JSON fields (one line per op, cumulative like bench.py):
  <op>_ms            timed fwd+bwd step
  <op>_credited_tflops   achieved, counting in-band work only
  <op>_frac_of_peak  credited achieved / chip peak (the roofline height)
  <op>_band_frac     credited / executed-tile flops — how much of what the
                     kernel computes is useful work (tile-quantization
                     overhead of the band; 1.0 for bidirectional)
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import (
    _Clock, device_peak_flops, exit_if_errors, peak_tflops_field,
)
from tfde_tpu.ops import roofline as rl
from tfde_tpu.ops.flash_attention import flash_attention, bwd_tile_plan

# (name, seq, causal, window, logit_cap): the bench attention variants —
# plain causal (gpt_long), windowed and windowed+softcap (gpt_long_win /
# the Gemma-2 family), bidirectional (bert)
OPS = [
    ("attn_causal", 4096, True, None, None),
    ("attn_win1024", 4096, True, 1024, None),
    ("attn_win1024_cap50", 4096, True, 1024, 50.0),
    ("attn_bidir", 4096, False, None, None),
]
TRAIN_MULT = 3.0  # fwd+bwd credited at 3x forward (backward ~2x)


def measure(clock, name, b, s, h, d, causal, window, logit_cap, peak,
            interpret, smoke):
    rng = np.random.default_rng(0)
    dtype = jnp.float32 if interpret else jnp.bfloat16
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
        for _ in range(3)
    )

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal, None, None, interpret, window, None, logit_cap
        ).astype(jnp.float32).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    clock.fetch_scalar(g(q, k, v)[0][0, 0, 0, 0].astype(jnp.float32))

    def run(reps):
        dq = None
        for _ in range(reps):
            dq, _, _ = g(q, k, v)
        return dq

    reps, window_s, _, _ = clock.timed(
        run, lambda dq: dq[0, 0, 0, 0].astype(jnp.float32),
        0.05 if smoke else 1.0, start_reps=1 if smoke else 5,
        max_reps=5_000,
    )
    step_s = window_s / reps

    credited = TRAIN_MULT * b * s * rl.attention_flops_per_token(
        h * d, s, causal, window
    )
    plan = rl.tile_visits(s, None, None, causal, window)
    # executed-tile flops: every visited tile runs a full bq x bk block
    executed = credited * (
        plan["fwd"] * plan["block_q"] * plan["block_k"]
        / (s * rl.mean_attended_keys(s, causal, window))
    )
    achieved = credited / step_s
    return {
        f"{name}_ms": round(step_s * 1e3, 3),
        f"{name}_credited_tflops": round(achieved / 1e12, 2),
        f"{name}_frac_of_peak": round(achieved / peak, 4),
        f"{name}_band_frac": round(credited / executed, 4),
        f"{name}_tile_visits": plan["fwd"],
        f"{name}_tile_grid": plan["grid"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check-tiles", action="store_true",
                    help="tile-visit gate only (tier-1; exits 1 on drift)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for plumbing checks on any backend")
    args = ap.parse_args()

    if args.check_tiles:
        failures = rl.check_tile_visits(verbose=True)
        for f in failures:
            print(f"TILE REGRESSION: {f}", file=sys.stderr)
        print(json.dumps({"roofline_tile_gate": "fail" if failures
                          else "pass", "failures": failures}))
        sys.exit(1 if failures else 0)

    dev = jax.devices()[0]
    interpret = dev.platform == "cpu"
    peak = device_peak_flops(dev)
    clock = _Clock()
    out = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "chip_peak_tflops": peak_tflops_field(peak),
    }
    for name, seq, causal, window, cap in OPS:
        b, s, h, d = (1, 512, 2, 64) if args.smoke else (1, seq, 12, 64)
        if args.smoke and window is not None:
            window = 128
        try:
            out.update(measure(clock, name, b, s, h, d, causal, window,
                               cap, peak, interpret, args.smoke))
        except Exception as e:
            out[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
        print(json.dumps(out), flush=True)
    print(json.dumps(out))
    exit_if_errors(out, "roofline")


if __name__ == "__main__":
    main()
