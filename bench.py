"""Headline benchmark: one process, one cumulative JSON line per config.

`python bench.py` measures in the calling process (one process per chip: a
second process that needs the TPU would fail or hang while this one holds
it). It refuses a CPU backend unless TFDE_BENCH_ALLOW_CPU=1, raises for a
device kind missing from PEAK_FLOPS, and exits non-zero — after printing
what it has — when any config fails.

Trust layer (VERDICT r2 "What's weak" #1: the round-2 bench printed 2531
achieved TFLOPs on a 197-TFLOP chip — 1285% MFU — without noticing):

- **Host-fetch timing.** On the platform of rounds 2-4,
  `jax.block_until_ready` returned ~immediately with device work still
  pending (10 chained 4096^3 matmuls "completed" in 0.3 ms), so every
  round-2 number was enqueue time, not compute. Every timed window now ends
  with a device->host fetch of a scalar that is data-dependent on the final
  step (the jitted step's own loss output / the calibration chain's out[0,0]),
  which no backend can fake, minus a separately-measured fetch latency. The
  residual block->fetch gap is reported as `sync_block_gap_ms` — direct
  evidence of whether block_until_ready returns early.
- **Calibration matmul.** A bf16 matmul chain of analytically-known FLOPs
  (lax.fori_loop inside one jit, so dispatch overhead is out of the picture)
  runs first; its achieved TFLOPs vs chip peak (`calib_frac_of_peak`) gates
  everything: >1.05x peak means timing is broken and the bench says so in an
  `"error"` field instead of printing numbers.
- **Peak gate per config.** Any config whose achieved FLOPs exceed 1.05x chip
  peak withholds its number and reports `<cfg>_error` instead.
- **Loss-motion check.** The loss scalar is fetched before and after each
  timed window and must change (`<cfg>_loss_moved`) — a window that executes
  nothing cannot pass.
- **No invented baseline.** The reference publishes no numbers, so
  `vs_baseline` is null with a note — round 2's `/ 10_000.0` estimate was
  fiction and is gone.
- **End-to-end config.** `mnist_e2e_*` times training *through the host input
  pipeline* (data.Dataset shuffle/repeat/batch/prefetch + device_prefetch),
  not just a resident device batch — the overlap the >=90% scaling story
  depends on (SURVEY.md §7).
- **Flash qualification.** `flash_*` runs the Pallas flash-attention kernel
  vs the reference einsum at S=2048 on the real chip: max |err| + fwd+bwd
  speedup (`flash_speedup`). This is the hardware qualification that flips
  ops/attention.py auto-dispatch.

Configs measured (each in try/except so the rest still print; any failure
makes the exit code non-zero):
  calib   — bf16 4096^3 matmul chain, known FLOPs (the trust anchor)
  mnist   — BN-CNN of mnist_keras_distributed.py:67-120 @ batch 128, SGD,
            resident device batch: images/sec/chip (compute path)
  mnist_e2e — same model fed by the real host pipeline: images/sec/chip
  bert    — BERT-base MLM fwd+bwd bf16 @ seq 512: MFU vs chip peak
  flash   — Pallas flash kernel vs reference attention @ S=2048
  gpt_long_win — gpt_long with Gemma-2 deltas (alternating window 1024 +
            softcap 50) on the fused path, MFU vs the windowed-flop model
            (ops/roofline.py; tools/roofline.py has the per-op view)

Env knobs: TFDE_BENCH_ALLOW_CPU=1 (let the measurement run on cpu and say
so), TFDE_BENCH_SMOKE=1 (tiny shapes, path validation only).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

GLOBAL_BATCH = 128  # tf2_mnist_distributed.py:33

# Peak bf16 matmul FLOP/s per chip, keyed by substrings of
# jax.Device.device_kind (public figures; first match wins).
PEAK_FLOPS = [
    ("v6", 918e12),  # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]
PEAK_TOLERANCE = 1.05  # achieved/peak above this = broken timing, not speed


def chip_peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device_kind!r}; add it "
        f"to PEAK_FLOPS with its source rather than guess"
    )


def device_peak_flops(device) -> float:
    """Peak for a `jax.Device`. A CPU (reached only by explicit request:
    TFDE_BENCH_ALLOW_CPU, the interpret-mode plumbing checks) has no chip
    peak, so every share of peak reads 0.0 there; any other device missing
    from the table is an error."""
    if device.platform == "cpu":
        return float("inf")
    return chip_peak_flops(str(device.device_kind))


def peak_tflops_field(peak: float):
    return None if peak == float("inf") else round(peak / 1e12, 1)


def exit_if_errors(result: dict, who: str) -> None:
    """After everything measurable has been printed: a `<name>_error`
    field anywhere in the result makes the exit code non-zero."""
    errors = sorted(k for k in result if k.endswith("_error"))
    if errors:
        print(f"{who}: failed: {', '.join(errors)}", file=sys.stderr)
        sys.exit(1)


def bert_train_flops_per_token(hidden: int, mlp: int, depth: int,
                               seq: int, vocab: int) -> float:
    """Analytic matmul FLOPs per token for one fwd+bwd MLM step.

    fwd per layer per token: qkvo 2*4H^2, mlp 2*2HF, attention matmuls
    (scores + values) 2*2SH. Plus the MLM transform dense 2H^2 and the tied
    decoder 2HV. Training = 3x forward (backward is 2x).
    """
    per_layer = 8 * hidden * hidden + 4 * hidden * mlp + 4 * seq * hidden
    fwd = depth * per_layer + 2 * hidden * hidden + 2 * hidden * vocab
    return 3.0 * fwd


# --------------------------------------------------------------------------
# Trusted timing: the clock stops at a host fetch, never at block_until_ready.
# --------------------------------------------------------------------------

class _Clock:
    """Timing helper whose windows end at a host fetch.

    fetch(x): device_get a scalar jit *output* (cheap: no new compile) —
    a synchronization no backend can answer before the work is done.
    """

    def __init__(self):
        import jax
        import numpy as np

        from tfde_tpu.observability import recompile

        self._jax = jax
        self._np = np
        self._recompile = recompile
        # every timed window asserts zero jit-cache misses (the 0.7-TFLOP
        # round-2 hazard was a recompile inside the window)
        recompile.install()
        # Warm the transfer channel, then measure steady-state fetch latency
        # on an already-ready scalar.
        z = jax.jit(lambda: jax.numpy.zeros(()))()
        self.fetch_scalar(z)
        lats = []
        for _ in range(5):
            t0 = time.perf_counter()
            self.fetch_scalar(z)
            lats.append(time.perf_counter() - t0)
        self.fetch_latency_s = float(np.median(lats))

    def fetch_scalar(self, x) -> float:
        return float(self._np.asarray(self._jax.device_get(x)))

    def timed(self, run_reps, scalar_of, min_window_s: float,
              start_reps: int, max_reps: int):
        """Run `run_reps(n)` (returns an object whose scalar_of(obj) is a
        jit-output scalar data-dependent on the final rep), growing n until
        the fetched window is long enough to swamp fetch latency.

        Returns (reps, window_s, block_gap_s, fetched_value).

        Windows are compile-free by construction: the recompile sentinel's
        process-wide compile counter is diffed around every window, and a
        window that caught an XLA compile (insufficient warm-up, a shape
        the warm pass missed) is discarded and re-measured ONCE with a
        stderr warning — the second recurrence is reported as-is so a
        genuinely thrashing program cannot hide.
        """
        jax = self._jax
        reps = start_reps
        remeasured = False
        while True:
            c0 = self._recompile.process_compiles()
            t0 = time.perf_counter()
            out = run_reps(reps)
            jax.block_until_ready(out)
            t_block = time.perf_counter()
            val = self.fetch_scalar(scalar_of(out))
            t_fetch = time.perf_counter()
            window = t_fetch - t0 - self.fetch_latency_s
            in_window = self._recompile.process_compiles() - c0
            if in_window and not remeasured:
                remeasured = True
                print(
                    f"bench: {in_window} XLA compile(s) landed inside a "
                    f"timed window ({reps} reps) — discarding and "
                    f"re-measuring once",
                    file=sys.stderr,
                )
                continue
            if window >= min_window_s or reps >= max_reps:
                return reps, max(window, 1e-9), t_fetch - t_block, val
            scale = max(2.0, 1.3 * min_window_s / max(window, 1e-3))
            reps = min(max_reps, int(reps * scale) + 1)


def _gate(result: dict, prefix: str, achieved: float, peak: float) -> bool:
    """False (and records an error) if achieved FLOPs are physically
    impossible — the round-2 failure mode, now a refusal instead of a
    headline."""
    if achieved > PEAK_TOLERANCE * peak:
        result[f"{prefix}_error"] = (
            f"achieved {achieved / 1e12:.1f} TFLOPs/chip exceeds "
            f"{PEAK_TOLERANCE:.2f}x chip peak {peak / 1e12:.1f} — timing or "
            f"synchronization is broken; number withheld"
        )
        return False
    return True


# --------------------------------------------------------------------------
# The measurement: every config in the calling process.
# --------------------------------------------------------------------------

def _bench_calibration(clock: _Clock, peak: float, smoke: bool) -> dict:
    """bf16 matmul chain of known FLOPs inside ONE jit (fori_loop), so
    per-call dispatch overhead (the entire round-2 'BERT step') cannot
    contaminate it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = 256 if smoke else 4096
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n)) , jnp.bfloat16)
    # scale so the chained product stays O(1) (bf16 overflow -> inf/nan
    # could let the backend shortcut; keep the numerics honest)
    b = jnp.asarray(rng.standard_normal((n, n)) / np.sqrt(n), jnp.bfloat16)

    @jax.jit
    def chain(x, reps):
        # reps is TRACED (fori_loop -> while_loop): one compile serves every
        # rep count the adaptive window picks. With a static rep count the
        # recompile landed inside the timed window and read as 0.7 TFLOPs.
        def body(_, acc):
            return jax.lax.dot(
                acc, b, preferred_element_type=jnp.float32
            ).astype(jnp.bfloat16)
        out = jax.lax.fori_loop(0, reps, body, x)
        return out[0, 0].astype(jnp.float32)

    clock.fetch_scalar(chain(a, jnp.int32(2)))  # compile + warm
    flops_per = 2.0 * n ** 3
    min_window = 0.02 if smoke else 1.0
    reps, window, gap, val = clock.timed(
        lambda r: chain(a, jnp.int32(r)), lambda s: s, min_window,
        start_reps=4 if smoke else 64, max_reps=1 << 14,
    )
    achieved = reps * flops_per / window
    out = {
        "calib_matmul_n": n,
        "calib_reps": reps,
        "calib_tflops": round(achieved / 1e12, 1),
        "calib_frac_of_peak": round(achieved / peak, 4),
        "calib_value_finite": bool(np.isfinite(val)),
        "sync_fetch_latency_ms": round(clock.fetch_latency_s * 1e3, 3),
        "sync_block_gap_ms": round(gap * 1e3, 2),
    }
    if achieved > PEAK_TOLERANCE * peak and not smoke:
        out["calib_error"] = (
            f"calibration matmul 'achieved' {achieved / 1e12:.0f} TFLOPs on a "
            f"{peak / 1e12:.0f}-TFLOP chip: the timing itself is broken on "
            f"this backend; all numbers below are untrustworthy"
        )
    return out


def _mnist_setup(strategy):
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.cnn import BatchNormCNN
    from tfde_tpu.training.step import init_state, make_train_step

    model = BatchNormCNN()
    tx = optax.sgd(0.01)
    sample = np.zeros((GLOBAL_BATCH, 784), np.float32)
    state, _ = init_state(model, tx, strategy, sample, seed=0)
    step_fn = make_train_step(strategy, state, donate=True)
    return state, step_fn


def _bench_mnist(clock: _Clock, strategy, n_chips: int, smoke: bool) -> dict:
    """Compute-path MNIST: resident device batch (no host feed)."""
    import jax
    import numpy as np

    state, step_fn = _mnist_setup(strategy)
    rng = np.random.default_rng(0)
    images = rng.random((GLOBAL_BATCH, 784), np.float32)
    labels = rng.integers(0, 10, (GLOBAL_BATCH, 1)).astype(np.int32)
    batch_sh = strategy.batch_sharding()
    images = jax.device_put(images, batch_sh)
    labels = jax.device_put(labels, batch_sh)
    key = jax.random.key(0)

    holder = {"state": state}
    metrics = None
    for _ in range(2 if smoke else 20):  # warmup
        holder["state"], metrics = step_fn(holder["state"], (images, labels), key)
    loss_start = clock.fetch_scalar(metrics["loss"])

    def run(reps):
        m = None
        for _ in range(reps):
            holder["state"], m = step_fn(holder["state"], (images, labels), key)
        return m

    reps, window, gap, loss_end = clock.timed(
        run, lambda m: m["loss"], 0.05 if smoke else 1.5,
        start_reps=5 if smoke else 200, max_reps=20_000,
    )
    step_s = window / reps
    return {
        "mnist_images_per_sec_per_chip": round(GLOBAL_BATCH / step_s / n_chips, 1),
        "mnist_step_ms": round(step_s * 1e3, 3),
        "mnist_timed_steps": reps,
        "mnist_block_gap_ms": round(gap * 1e3, 2),
        "mnist_loss_start": round(loss_start, 5),
        "mnist_loss_end": round(loss_end, 5),
        "mnist_loss_moved": bool(abs(loss_end - loss_start) > 1e-9),
    }


def _bench_mnist_e2e(clock: _Clock, strategy, n_chips: int, smoke: bool) -> dict:
    """End-to-end MNIST: host pipeline (Dataset shuffle/repeat/batch/prefetch)
    + device_prefetch feeding the same train step — measures what the
    reference's input_fn path (mnist_keras:123-148) actually delivers,
    including host->device transfer overlap."""
    import numpy as np

    from tfde_tpu.data.device import device_prefetch
    from tfde_tpu.data.pipeline import Dataset

    state, step_fn = _mnist_setup(strategy)
    n = 1024 if smoke else 16384
    rng = np.random.default_rng(0)
    images = rng.random((n, 784), np.float32)
    labels = rng.integers(0, 10, (n, 1)).astype(np.int32)
    ds = (
        Dataset.from_tensor_slices((images, labels))
        .shuffle(n, seed=0)
        .repeat()
        .batch(GLOBAL_BATCH, drop_remainder=True)
        .prefetch(4)
    )
    # background=True: host pull + device_put in a worker thread, so a
    # host link whose device_put is effectively synchronous still
    # overlaps transfer with the device step
    feed = device_prefetch(iter(ds), strategy.mesh, buffer_size=2,
                           background=True)
    import jax

    key = jax.random.key(0)
    holder = {"state": state}
    metrics = None
    for _ in range(2 if smoke else 20):  # warmup
        holder["state"], metrics = step_fn(holder["state"], next(feed), key)
    loss_start = clock.fetch_scalar(metrics["loss"])

    def run(reps):
        m = None
        for _ in range(reps):
            holder["state"], m = step_fn(holder["state"], next(feed), key)
        return m

    reps, window, gap, loss_end = clock.timed(
        run, lambda m: m["loss"], 0.05 if smoke else 1.5,
        start_reps=5 if smoke else 200, max_reps=20_000,
    )
    step_s = window / reps
    return {
        "mnist_e2e_images_per_sec_per_chip": round(
            GLOBAL_BATCH / step_s / n_chips, 1
        ),
        "mnist_e2e_step_ms": round(step_s * 1e3, 3),
        "mnist_e2e_timed_steps": reps,
        "mnist_e2e_loss_moved": bool(abs(loss_end - loss_start) > 1e-9),
    }


def _bench_mnist_dev(clock: _Clock, strategy, n_chips: int,
                     smoke: bool) -> dict:
    """Device-resident input (data.device.device_resident_feed): the whole
    dataset staged in HBM, per-batch shuffle/gather ON DEVICE — zero
    per-step host transfer. On a co-located host this should track the
    compute-path number; over a slow host link it PROVES the e2e gap is
    the link (same step, same data-shape, transfer removed)."""
    import jax
    import numpy as np

    from tfde_tpu.data.device import device_resident_feed

    state, step_fn = _mnist_setup(strategy)
    n = 1024 if smoke else 16384
    rng = np.random.default_rng(0)
    images = rng.random((n, 784), np.float32)
    labels = rng.integers(0, 10, (n, 1)).astype(np.int32)
    feed = device_resident_feed((images, labels), strategy.mesh,
                                GLOBAL_BATCH, seed=0)
    key = jax.random.key(0)
    holder = {"state": state, "step": 0}
    metrics = None
    for _ in range(2 if smoke else 20):
        holder["state"], metrics = step_fn(
            holder["state"], feed(holder["step"]), key
        )
        holder["step"] += 1
    loss_start = clock.fetch_scalar(metrics["loss"])

    def run(reps):
        m = None
        for _ in range(reps):
            holder["state"], m = step_fn(
                holder["state"], feed(holder["step"]), key
            )
            holder["step"] += 1
        return m

    reps, window, gap, loss_end = clock.timed(
        run, lambda m: m["loss"], 0.05 if smoke else 1.5,
        start_reps=5 if smoke else 200, max_reps=20_000,
    )
    step_s = window / reps
    return {
        "mnist_dev_images_per_sec_per_chip": round(
            GLOBAL_BATCH / step_s / n_chips, 1
        ),
        "mnist_dev_step_ms": round(step_s * 1e3, 3),
        "mnist_dev_loss_moved": bool(abs(loss_end - loss_start) > 1e-9),
    }


def _bench_obs(strategy, smoke: bool) -> dict:
    """Observability self-measurement: a short Estimator-driven run with
    the goodput ledger attached — reports where the wall-clock of a real
    instrumented train loop goes (compile, data-wait, goodput) and how much
    the span accounting leaves unexplained (obs_other_fraction; the
    acceptance bar is <= 0.05 on a summary-synced run)."""
    import tempfile
    import time

    import numpy as np
    import optax

    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.observability.goodput import GoodputLedger
    from tfde_tpu.training.lifecycle import Estimator, RunConfig

    steps = 10 if smoke else 40
    n = GLOBAL_BATCH * 4
    rng = np.random.default_rng(0)
    images = rng.random((n, 784), np.float32)
    labels = rng.integers(0, 10, (n, 1)).astype(np.int32)

    def input_fn():
        def gen():
            i = 0
            while True:
                s = (i * GLOBAL_BATCH) % n
                yield (images[s:s + GLOBAL_BATCH],
                       labels[s:s + GLOBAL_BATCH])
                i += 1

        return gen()

    est = Estimator(
        model=PlainCNN(),
        optimizer=optax.sgd(0.1),
        strategy=strategy,
        config=RunConfig(
            model_dir=tempfile.mkdtemp(prefix="tfde-bench-obs-"),
            save_summary_steps=5,
            log_step_count_steps=steps,
            save_checkpoints_steps=None,  # no checkpoint I/O in the number
        ),
    )
    ledger = GoodputLedger()
    t0 = time.perf_counter()
    est.train(input_fn, steps)
    wall = time.perf_counter() - t0
    est.close()
    rep = ledger.report(wall)
    # memory + compile columns from the memwatch ledger / recompile
    # sentinel the lifecycle wires around the train step
    from tfde_tpu.observability import memwatch, recompile

    pm = memwatch.programs().get("train_step")
    sites = recompile.sites().get("train_step", {})
    return {
        "obs_steps": rep["steps"],
        "obs_compile_seconds": round(rep["seconds"]["compile"], 3),
        "obs_compile_count": int(sites.get("misses", 0)),
        "obs_peak_hbm_bytes": int(pm.peak_bytes) if pm else 0,
        "obs_data_wait_fraction": round(rep["fractions"]["data_wait"], 4),
        "obs_goodput": round(rep["goodput"], 4),
        "obs_other_fraction": round(rep["fractions"]["other"], 4),
        "obs_mean_step_ms": round(rep["mean_step_seconds"] * 1e3, 3),
        "obs_sentry_overhead_pct": _sentry_overhead_pct(
            strategy, images, labels, smoke
        ),
    }


def _sentry_overhead_pct(strategy, images, labels, smoke: bool) -> float:
    """Per-step cost of the fused numerics sentry (observability/sentry.py)
    relative to the identical step without it — same model, same strategy,
    min-of-repeats on both sides so scheduler noise cancels. The sentry is
    a handful of scalar ops fused into an already-compiled step (no extra
    dispatch, no host sync), so the acceptance bar is < 2%."""
    import time

    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.observability import sentry as sentry_lib
    from tfde_tpu.training.step import init_state, make_train_step

    batch = (images[:GLOBAL_BATCH], labels[:GLOBAL_BATCH])
    key = jax.random.key(0)
    reps = 3 if smoke else 5
    k = 10 if smoke else 40

    def per_step_s(sentry_cfg) -> float:
        st, _ = init_state(PlainCNN(), optax.sgd(0.1), strategy,
                           np.zeros_like(batch[0]))
        step_fn = make_train_step(strategy, st, sentry=sentry_cfg)
        sst = sentry_lib.init_state() if sentry_cfg is not None else None
        best = float("inf")
        m = None
        for r in range(reps + 1):  # rep 0 = compile warmup, untimed
            t0 = time.perf_counter()
            for _ in range(k):
                if sst is not None:
                    st, m, sst = step_fn(st, batch, key, sst)
                else:
                    st, m = step_fn(st, batch, key)
            jax.block_until_ready(m)
            if r > 0:
                best = min(best, time.perf_counter() - t0)
        return best / k

    plain = per_step_s(None)
    fused = per_step_s(sentry_lib.SentryConfig())
    return round(max(0.0, (fused - plain) / plain * 100.0), 3)


def _bench_link(clock: _Clock, smoke: bool) -> dict:
    """Host->device transfer microbenchmark — the attribution control for
    the e2e gap (VERDICT r3 #3). Measures the per-transfer latency floor
    (4-byte put), the MNIST batch payload's per-batch cost, and streaming
    bandwidth (16 MiB put). On a co-located host, link_batch_ms is tens of
    microseconds and e2e==compute; over a slow host link it is the gap. The
    derived fields land in the cumulative result via run_mode."""
    import jax
    import numpy as np

    rng = np.random.default_rng(0)

    def put_time_s(arr, budget):
        def run(reps):
            out = None
            for _ in range(reps):
                out = jax.device_put(arr)
            return out

        reps, window, _gap, _ = clock.timed(
            # a device-side scalar slice: the fetch must move 4 bytes, not
            # the whole buffer (a full device_get inside the window would
            # inflate link_batch_ms on exactly the links this measures)
            run, lambda o: o.ravel()[0],
            budget, start_reps=3 if smoke else 20, max_reps=5000,
        )
        return window / reps

    budget = 0.05 if smoke else 1.0
    lat_s = put_time_s(np.ones((1,), np.float32), budget)
    batch = rng.random((GLOBAL_BATCH, 784), np.float32)
    batch_s = put_time_s(batch, budget)
    big = rng.random((1 << 22,), np.float32)  # 16 MiB
    big_s = put_time_s(big, budget)
    return {
        "link_latency_ms": round(lat_s * 1e3, 3),
        "link_batch_ms": round(batch_s * 1e3, 3),
        "link_batch_bytes": int(batch.nbytes),
        "link_bandwidth_mb_s": round(
            big.nbytes / max(big_s - lat_s, 1e-9) / 1e6, 1
        ),
    }


def _bench_bert_mfu(clock: _Clock, strategy, n_chips: int, peak: float,
                    smoke: bool, per_chip_batch: int = 16,
                    prefix: str = "bert", fused_qkv: bool = False) -> dict:
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.bert import Bert, BertBase
    from tfde_tpu.ops import losses
    from tfde_tpu.training.step import init_state, make_custom_train_step

    if smoke:  # CPU-sized config: validates the path, not a real number
        seq, per_chip_batch = 128, 2
        model = Bert(vocab_size=1024, hidden_size=128, depth=2, num_heads=4,
                     mlp_dim=256, dropout_rate=0.0, pad_vocab=True,
                     fused_qkv=fused_qkv)
        warmup = 1
    else:
        seq = 512
        model = BertBase(dropout_rate=0.0, pad_vocab=True,
                         fused_qkv=fused_qkv)
        warmup = 3
    global_batch = per_chip_batch * n_chips
    vocab = model.padded_vocab

    def loss_fn(state, params, batch, rng):
        input_ids, labels = batch
        logits = state.apply_fn({"params": params}, input_ids, train=True,
                                rngs={"dropout": rng})
        loss, acc = losses.masked_lm_loss(logits, labels)
        return loss, {"mlm_accuracy": acc}

    tx = optax.adamw(1e-4)
    sample = np.zeros((global_batch, seq), np.int32)
    state, _ = init_state(model, tx, strategy, sample, seed=0)
    step_fn = make_custom_train_step(strategy, state, loss_fn)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -100, np.int32)
    labels[:, ::7] = ids[:, ::7]  # ~15% positions predicted
    key = jax.random.key(0)

    holder = {"state": state}
    metrics = None
    for _ in range(warmup):
        holder["state"], metrics = step_fn(holder["state"], (ids, labels), key)
    loss_start = clock.fetch_scalar(metrics["loss"])

    def run(reps):
        m = None
        for _ in range(reps):
            holder["state"], m = step_fn(holder["state"], (ids, labels), key)
        return m

    reps, window, gap, loss_end = clock.timed(
        run, lambda m: m["loss"], 0.05 if smoke else 2.0,
        start_reps=2 if smoke else 10, max_reps=2_000,
    )
    step_s = window / reps

    out = {
        f"{prefix}_step_ms": round(step_s * 1e3, 2),
        f"{prefix}_timed_steps": reps,
        f"{prefix}_block_gap_ms": round(gap * 1e3, 2),
        f"{prefix}_loss_moved": bool(abs(loss_end - loss_start) > 1e-9),
        f"{prefix}_per_chip_batch": per_chip_batch,
    }
    if prefix == "bert":
        # Diagnostic (VERDICT r2 next-steps 1b): a short per-step-synced
        # window — each step's loss fetched to host before the next starts.
        # Dispatch overhead + fetch latency make this an upper bound on step
        # time; the primary (amortized-fetch) number must lie between
        # compute truth and this bound.
        t0 = time.perf_counter()
        synced_reps = 2 if smoke else 5
        for _ in range(synced_reps):
            holder["state"], m = step_fn(holder["state"], (ids, labels), key)
            clock.fetch_scalar(m["loss"])
        out["bert_step_ms_synced"] = round(
            (time.perf_counter() - t0) / synced_reps * 1e3, 2
        )

    tokens_per_step = global_batch * seq
    flops_per_token = bert_train_flops_per_token(
        model.hidden_size, model.mlp_dim, model.depth, seq, vocab
    )
    achieved = tokens_per_step * flops_per_token / step_s / n_chips
    if _gate(out, prefix, achieved, peak):
        out.update({
            f"{prefix}_mfu": round(achieved / peak, 4),
            f"{prefix}_tokens_per_sec_per_chip": round(
                tokens_per_step / step_s / n_chips, 1
            ),
            f"{prefix}_achieved_tflops_per_chip": round(achieved / 1e12, 2),
        })
    return out


def _bench_comms(n_chips: int, smoke: bool) -> dict:
    """Quantized gradient exchange (parallel/comms.py): analytic wire bytes
    for the bert config plus a measured fp32-vs-int8 A/B on a CPU mesh.

    Two layers because they answer different questions:

    - **Analytic bytes** come from the real BertBase parameter shapes
      (`comms.comm_bytes`, the same accounting behind the `comm/*` gauges)
      — the per-step gradient traffic the int8 transport removes. This is
      a cost model, not a measurement, so it works on any backend; the
      acceptance bar is `comm_bytes_per_step_int8 <= 0.3 x fp32`.
    - **The A/B run** (step time + loss-trajectory parity vs the
      uncompressed oracle) happens in a `--comms-child` subprocess forced
      to an 8-way CPU mesh, so the exchange, the error feedback, and the
      shard_map path execute for real even when the parent process sees a
      single device (plain `bench.py` on a laptop) or a TPU. Smoke-sized
      bert shapes keep the child ~seconds; on CPU the int8 path is
      *slower* (quantize/dequantize compute with zero network to save) —
      the number validates the path, the byte ratio is the perf claim.
    """
    import jax
    import numpy as np

    from tfde_tpu.models.bert import BertBase
    from tfde_tpu.parallel import comms as comms_lib

    # -- analytic: real BertBase shapes, no device work -----------------------
    model = BertBase(dropout_rate=0.0, pad_vocab=True)
    sample = np.zeros((2, 8), np.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample, train=False)
    )["params"]
    cfg = comms_lib.CommsConfig(transport="int8")
    nshards = n_chips if n_chips >= 2 else 8
    b = comms_lib.comm_bytes(abstract, cfg, nshards)
    out = {
        "comm_bytes_per_step_fp32": int(b["fp32"]),
        "comm_bytes_per_step_int8": int(b["int8"]),
        "comms_ratio": round(b["ratio"], 4),
        "comms_analytic_nshards": nshards,
        "comms_compressed_elems": int(b["compressed_elems"]),
        "comms_fp32_elems": int(b["fp32_elems"]),
    }

    # -- measured A/B: fresh interpreter pinned to an 8-way CPU mesh ----------
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--comms-child"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        child = _last_json(proc.stdout)
        if child is None:
            out["comms_child_error"] = (proc.stderr or "no output")[-400:]
        else:
            out.update(child)
    except subprocess.TimeoutExpired:
        out["comms_child_error"] = "comms child timed out"
    return out


def comms_child_mode() -> None:
    """`bench.py --comms-child`: the fp32-vs-int8 A/B on the 8-way CPU mesh
    the parent pinned via env. Prints one JSON line."""
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.bert import Bert
    from tfde_tpu.ops import losses
    from tfde_tpu.parallel.strategies import MirroredStrategy
    from tfde_tpu.training.step import init_state, make_custom_train_step

    seq, per_chip_batch, steps = 128, 2, 10
    model = Bert(vocab_size=1024, hidden_size=128, depth=2, num_heads=4,
                 mlp_dim=256, dropout_rate=0.0, pad_vocab=True)
    n_chips = len(jax.local_devices())
    global_batch = per_chip_batch * n_chips

    def loss_fn(state, params, batch, rng):
        input_ids, labels = batch
        logits = state.apply_fn({"params": params}, input_ids, train=True,
                                rngs={"dropout": rng})
        loss, acc = losses.masked_lm_loss(logits, labels)
        return loss, {"mlm_accuracy": acc}

    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size,
                       (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -100, np.int32)
    labels[:, ::7] = ids[:, ::7]
    key = jax.random.key(0)

    def trajectory(transport):
        strategy = MirroredStrategy(grad_transport=transport)
        state, _ = init_state(model, optax.adamw(1e-4), strategy, ids)
        step_fn = make_custom_train_step(strategy, state, loss_fn,
                                         comms=transport)
        state, m = step_fn(state, (ids, labels), key)  # compile + step 0
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        traj = [float(m["loss"])]
        for _ in range(steps - 1):
            state, m = step_fn(state, (ids, labels), key)
            traj.append(float(m["loss"]))
        dt = (time.perf_counter() - t0) / (steps - 1)
        return traj, dt

    fp32_traj, fp32_dt = trajectory("fp32")
    int8_traj, int8_dt = trajectory("int8")
    max_diff = max(abs(a - b) for a, b in zip(fp32_traj, int8_traj))
    # tolerance: the loss is O(ln 1024)~7 at init; a transport that tracks
    # the oracle stays within a few percent over 10 steps, a broken one
    # (no error feedback / wrong scales) diverges by whole units
    scale = max(1.0, abs(fp32_traj[0]))
    print(json.dumps({
        "comms_step_ms_fp32": round(fp32_dt * 1e3, 2),
        "comms_step_ms_int8": round(int8_dt * 1e3, 2),
        "comms_step_delta_pct": round(
            (int8_dt - fp32_dt) / fp32_dt * 100.0, 1),
        "comms_loss_moved": bool(
            abs(int8_traj[-1] - int8_traj[0]) > 1e-9),
        "comms_loss_max_diff": round(max_diff, 5),
        "comms_parity_ok": bool(max_diff < 0.05 * scale),
        "comms_child_n_chips": n_chips,
    }))


def _bench_zero(n_chips: int, smoke: bool) -> dict:
    """ZeRO weight-update sharding (parallel/zero.py): analytic optimizer
    memory for the real BERT-base shapes plus a measured replicated-vs-
    sharded A/B on a CPU mesh.

    Same two-layer shape as `_bench_comms`:

    - **Analytic bytes** price Adam's mu/nu for BertBase under both
      layouts (`zero.state_bytes`, the accounting behind the
      `opt/state_bytes` gauge): replicated ~= 2 x params x 4B per device,
      sharded ~= 1/N of that (quantum padding keeps it off the exact 1/N).
      The acceptance bar is sharded <= 1/4 x replicated on the 8-way mesh.
    - **The A/B run** happens in a `--zero-child` subprocess forced to an
      8-way CPU mesh: step time + measured per-device opt-state bytes +
      loss parity for all four transport x sharding combos. fp32 x shard
      must match the replicated fp32 oracle BITWISE; int8 x shard within
      the int8 tolerance. On CPU the gather/scatter is compute, not
      network, so step-time deltas validate the path rather than the perf
      claim — the byte ratio is the claim.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tfde_tpu.models.bert import BertBase
    from tfde_tpu.parallel import comms as comms_lib
    from tfde_tpu.parallel import zero as zero_lib

    model = BertBase(dropout_rate=0.0, pad_vocab=True)
    sample = np.zeros((2, 8), np.int32)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample, train=False)
    )["params"]
    nshards = n_chips if n_chips >= 2 else 8
    tx = optax.adam(1e-3)
    layout = zero_lib.build_layout(abstract, comms_lib.CommsConfig(), nshards)
    rep_bytes = zero_lib.state_bytes(jax.eval_shape(tx.init, abstract))
    sh_bytes = zero_lib.state_bytes(
        jax.eval_shape(lambda p: tx.init(zero_lib.pack_params(p, layout)),
                       abstract),
        layout,
    )
    out = {
        "zero_opt_bytes_per_device_replicated": int(rep_bytes),
        "zero_opt_bytes_per_device_sharded": int(sh_bytes),
        "zero_opt_bytes_ratio": round(sh_bytes / rep_bytes, 4),
        "zero_analytic_nshards": nshards,
        "zero_param_gather_bytes": int(zero_lib.param_gather_bytes(layout)),
    }

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env.pop(zero_lib.ENV_OPT_SHARDING, None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--zero-child"],
            capture_output=True, text=True, timeout=420, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        child = _last_json(proc.stdout)
        if child is None:
            out["zero_child_error"] = (proc.stderr or "no output")[-400:]
        else:
            out.update(child)
    except subprocess.TimeoutExpired:
        out["zero_child_error"] = "zero child timed out"
    return out


def zero_child_mode() -> None:
    """`bench.py --zero-child`: the replicated-vs-sharded x fp32-vs-int8
    A/B on the 8-way CPU mesh the parent pinned via env. Prints one JSON
    line."""
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.bert import Bert
    from tfde_tpu.ops import losses
    from tfde_tpu.parallel.strategies import MirroredStrategy
    from tfde_tpu.parallel import zero as zero_lib
    from tfde_tpu.training.step import init_state, make_custom_train_step

    seq, per_chip_batch, steps = 128, 2, 8
    model = Bert(vocab_size=1024, hidden_size=128, depth=2, num_heads=4,
                 mlp_dim=256, dropout_rate=0.0, pad_vocab=True)
    n_chips = len(jax.local_devices())
    global_batch = per_chip_batch * n_chips

    def loss_fn(state, params, batch, rng):
        input_ids, labels = batch
        logits = state.apply_fn({"params": params}, input_ids, train=True,
                                rngs={"dropout": rng})
        loss, acc = losses.masked_lm_loss(logits, labels)
        return loss, {"mlm_accuracy": acc}

    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size,
                       (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -100, np.int32)
    labels[:, ::7] = ids[:, ::7]
    key = jax.random.key(0)

    from tfde_tpu.observability import memwatch, recompile

    recompile.install()

    def trajectory(mode, transport):
        strategy = MirroredStrategy(grad_transport=transport,
                                    opt_sharding=mode)
        state, _ = init_state(model, optax.adamw(1e-4), strategy, ids)
        step_fn = make_custom_train_step(strategy, state, loss_fn)
        opt_analytic = zero_lib.state_bytes(state.opt_state,
                                            state.opt_layout)
        c0 = recompile.process_compiles()
        s0 = recompile.seconds_total()
        state, m = step_fn(state, (ids, labels), key)  # compile + step 0
        jax.block_until_ready(m["loss"])
        compiles = recompile.process_compiles() - c0
        csecs = recompile.seconds_total() - s0
        # MEASURED per-device bytes of the arrays XLA committed for the
        # post-step opt state — the number the analytic accounting claims
        opt_measured = zero_lib.measured_state_bytes(state.opt_state)
        pm = memwatch.register(f"zero/step_{mode}_{transport}", step_fn,
                               args=(state, (ids, labels), key),
                               donated=None)
        peak = int(pm.peak_bytes) if pm is not None else 0
        t0 = time.perf_counter()
        traj = [float(m["loss"])]
        for _ in range(steps - 1):
            state, m = step_fn(state, (ids, labels), key)
            traj.append(float(m["loss"]))
        dt = (time.perf_counter() - t0) / (steps - 1)
        return traj, dt, opt_analytic, opt_measured, compiles, csecs, peak

    runs = {
        (mode, transport): trajectory(mode, transport)
        for mode in ("replicated", "shard")
        for transport in ("fp32", "int8")
    }
    oracle = runs[("replicated", "fp32")][0]

    def max_diff(mode, transport):
        return max(abs(a - b)
                   for a, b in zip(oracle, runs[(mode, transport)][0]))

    scale = max(1.0, abs(oracle[0]))
    fp32_rep_dt = runs[("replicated", "fp32")][1]
    fp32_sh_dt = runs[("shard", "fp32")][1]
    rep_run = runs[("replicated", "fp32")]
    sh_run = runs[("shard", "fp32")]
    measured_rep, measured_sh = rep_run[3], sh_run[3]
    print(json.dumps({
        "zero_step_ms_fp32_replicated": round(fp32_rep_dt * 1e3, 2),
        "zero_step_ms_fp32_sharded": round(fp32_sh_dt * 1e3, 2),
        "zero_step_ms_int8_replicated": round(
            runs[("replicated", "int8")][1] * 1e3, 2),
        "zero_step_ms_int8_sharded": round(
            runs[("shard", "int8")][1] * 1e3, 2),
        "zero_step_delta_pct": round(
            (fp32_sh_dt - fp32_rep_dt) / fp32_rep_dt * 100.0, 1),
        # measured = per-device bytes of the committed arrays (memwatch
        # shard walk); analytic = the shape-derived accounting. The ratio
        # confirms the ~Nx replicated->sharded saving with XLA's own
        # allocations, and measured-vs-analytic agreement (within padding)
        # is the cross-check tests/test_memwatch.py pins
        "zero_measured_opt_bytes_replicated": int(measured_rep),
        "zero_measured_opt_bytes_sharded": int(measured_sh),
        "zero_analytic_opt_bytes_replicated": int(rep_run[2]),
        "zero_analytic_opt_bytes_sharded": int(sh_run[2]),
        "zero_measured_bytes_ratio": round(
            measured_sh / max(measured_rep, 1.0), 4),
        "zero_peak_hbm_bytes": int(max(r[6] for r in runs.values())),
        "zero_compile_count": int(sum(r[4] for r in runs.values())),
        "zero_compile_seconds": round(
            sum(r[5] for r in runs.values()), 3),
        # fp32 x shard is bitwise vs the oracle for plain-mean losses
        # (tests/test_zero.py pins that); the masked-LM loss here
        # normalizes by non-power-of-two token counts, so the local-sum
        # decomposition rounds differently — tight, not bitwise
        "zero_loss_max_diff_fp32": round(max_diff("shard", "fp32"), 7),
        "zero_parity_ok_fp32": bool(max_diff("shard", "fp32") < 0.01 * scale),
        "zero_loss_max_diff_int8": round(max_diff("shard", "int8"), 5),
        "zero_parity_ok_int8": bool(
            max_diff("shard", "int8") < 0.05 * scale),
        "zero_child_n_chips": n_chips,
    }))


def _bench_flash(clock: _Clock, smoke: bool) -> dict:
    """Hardware qualification of the Pallas flash-attention kernel
    (VERDICT r2 next-steps 4): numerics vs the reference einsum, then
    fwd+bwd timing at S=2048. On CPU/smoke, interpret-mode numerics only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.ops.attention import reference_attention
    from tfde_tpu.ops.flash_attention import flash_attention

    interpret = jax.default_backend() != "tpu"

    def ref_loss(q, k, v):
        return reference_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def flash_loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret).astype(
            jnp.float32).sum()

    def make_qkv(b, s, h, d):
        rng = np.random.default_rng(0)
        return tuple(
            jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
            for _ in range(3)
        )

    # numerics first (small enough for either backend)
    b, s, h, d = (1, 256, 2, 64) if (smoke or interpret) else (2, 2048, 4, 64)
    q, k, v = make_qkv(b, s, h, d)
    ref_fwd = jax.jit(lambda q, k, v: reference_attention(q, k, v, causal=True))
    fl_fwd = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=interpret)
    )
    o_ref = ref_fwd(q, k, v)
    o_fl = fl_fwd(q, k, v)
    err = float(
        jnp.max(jnp.abs(o_ref.astype(jnp.float32) - o_fl.astype(jnp.float32)))
    )
    scale_ref = float(jnp.max(jnp.abs(o_ref.astype(jnp.float32))))
    ok = err <= 2e-2 * max(scale_ref, 1.0)  # bf16 tolerance
    out = {
        "flash_max_abs_err": round(err, 5),
        "flash_numerics_ok": bool(ok),
        "flash_interpret": interpret,
    }
    if interpret or smoke:
        return out  # interpret-mode timing is meaningless

    # fwd+bwd timing across the length sweep (token count held constant):
    # XLA's fused attention is strong at moderate S; the flash win is the
    # long-S regime where the O(S^2) score tensor stops fitting.
    ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))
    fl_g = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))

    # backward numerics on hardware: the default flash backward (blockwise,
    # TFDE_FLASH_BWD) vs autodiff through the reference einsum
    gr = ref_g(q, k, v)
    gf = fl_g(q, k, v)
    gerr = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(gr, gf)
    )
    gscale = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)))) for a in gr
    )
    out["flash_grad_max_abs_err"] = round(gerr, 5)
    out["flash_grad_ok"] = bool(gerr <= 5e-2 * max(gscale, 1.0))

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

    def ab_pair(g_ref, g_fl, q, k, v):
        """Warm both compiled grads, then time each — the ONE A/B
        protocol for causal and non-causal sweeps."""
        clock.fetch_scalar(g_ref(q, k, v)[0][0, 0, 0, 0].astype(jnp.float32))
        clock.fetch_scalar(g_fl(q, k, v)[0][0, 0, 0, 0].astype(jnp.float32))
        return time_impl(g_ref, q, k, v), time_impl(g_fl, q, k, v)

    # S=1024 joins the sweep for the causal dispatch threshold decision
    # (ops/attention.py dispatches causal at S>=2048 from the 128-tile
    # A/Bs; the 512-tile auto default needs the 1024 point re-measured)
    for b, s in ((8, 1024), (4, 2048), (2, 4096), (1, 8192)):
        try:
            t_ref, t_fl = ab_pair(ref_g, fl_g, *make_qkv(b, s, 12, 64))
            out[f"flash_speedup_s{s}"] = round(t_ref / t_fl, 3)
            out[f"flash_ref_ms_s{s}"] = round(t_ref * 1e3, 3)
            out[f"flash_ms_s{s}"] = round(t_fl * 1e3, 3)
        except Exception as e:
            out[f"flash_error_s{s}"] = f"{type(e).__name__}: {e}"[:200]
    speedups = [v for k_, v in out.items() if k_.startswith("flash_speedup_s")]
    if speedups:
        out["flash_speedup"] = max(speedups)

    # non-causal A/B at the auto tile size: at 128 tiles this measured
    # 0.87-0.97x (dispatch threshold stayed memory-motivated at S>=4096);
    # the 512-tile default may flip it — this measurement decides whether
    # the non-causal threshold drops (round-5 queue). ONE
    # warm+time protocol (ab_pair) serves the causal sweep above and this,
    # so the two stay comparable.
    def nc_ref_loss(q, k, v):
        return reference_attention(q, k, v).astype(jnp.float32).sum()

    def nc_flash_loss(q, k, v):
        return flash_attention(q, k, v, interpret=interpret).astype(
            jnp.float32).sum()

    b, s = 2, 4096
    try:
        t_ref, t_fl = ab_pair(
            jax.jit(jax.grad(nc_ref_loss, argnums=(0, 1, 2))),
            jax.jit(jax.grad(nc_flash_loss, argnums=(0, 1, 2))),
            *make_qkv(b, s, 12, 64),
        )
        out[f"flash_nc_speedup_s{s}"] = round(t_ref / t_fl, 3)
    except Exception as e:
        out[f"flash_nc_error_s{s}"] = f"{type(e).__name__}: {e}"[:200]
    return out


def gpt_train_flops_per_token(hidden: int, mlp: int, depth: int,
                              seq: int, vocab: int, window=None,
                              window_pattern: str = "all") -> float:
    """Analytic matmul FLOPs per token for one causal-LM fwd+bwd step: qkvo
    + mlp per-layer terms as in BERT; attention matmuls credited by the
    EXACT in-band count from ops/roofline.py — (S+1)/2 mean attended keys
    for plain causal (the flash kernels skip future tiles in forward AND
    backward, so counting full bidirectional attention would inflate MFU
    by ~20% at S=4096; the old half-count 2*S*H was ~1/(2n) conservative
    on the diagonal, now exact), the triangle-plus-band mean for a
    sliding `window`, and the per-layer average when `window_pattern=
    'alternate'` windows only even layers (gpt_long_win / Gemma-2). Plus
    the tied LM head 2HV; training = 3x forward."""
    from tfde_tpu.ops.roofline import stacked_attention_flops_per_token

    per_layer = 8 * hidden * hidden + 4 * hidden * mlp
    attn = stacked_attention_flops_per_token(
        hidden, seq, depth, causal=True, window=window,
        window_pattern=window_pattern,
    )
    return 3.0 * (depth * per_layer + attn + 2 * hidden * vocab)


def _bench_gpt_long(clock: _Clock, strategy, n_chips: int, peak: float,
                    smoke: bool, prefix: str = "gpt_long") -> dict:
    """GPT training MFU configs on the flash-attention path:

    - ``gpt_long``: GPT-2-small at S=4096, per-chip batch 1 — the
      long-context regime where attention auto-dispatches to the Pallas
      flash kernel (ops/attention.py). Capability measured, not just
      qualified.
    - ``gpt_medium``: GPT-2-medium (h=1024, 24 layers) at S=1024, batch 8,
      attn_impl='flash' explicitly (below the auto threshold) — the
      model-width axis of the MFU story: the BERT roofline
      attributes the 42%-vs-73% gap to h=768 GEMM efficiency, and this
      config measures what wider GEMMs recover (36.6% at first light vs
      20% for gpt_long: width + shorter S both lift it).
    - ``gpt_long_win``: the Gemma-2-shaped variant of gpt_long — sliding
      window 1024 with window_pattern='alternate' plus attention logit
      softcap 50.0, all running through the fused flash kernels (forward
      AND backward skip out-of-band tiles). MFU is reported against the
      corrected windowed-flop model (gpt_train_flops_per_token with
      window/pattern — ops/roofline.py credits banded layers their true
      in-band work), so the number is comparable to gpt_long instead of
      flattered by phantom full-causal flops.
    """
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.gpt import GPT, next_token_loss
    from tfde_tpu.training.step import init_state, make_custom_train_step

    medium = prefix == "gpt_medium"
    windowed = prefix == "gpt_long_win"
    if smoke:
        import jax.numpy as jnp

        seq, per_chip_batch = 128, 1
        model = GPT(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                    mlp_dim=128, max_position=seq, dtype=jnp.float32,
                    attn_impl="flash" if medium else "auto",
                    # smoke must cover the knob composition the full
                    # configs ship with: gpt_long4's remat, gpt_long_win's
                    # alternating window + softcap
                    sliding_window=64 if windowed else None,
                    sliding_window_pattern="alternate" if windowed
                    else "all",
                    attn_logit_cap=50.0 if windowed else None,
                    remat="dots" if prefix == "gpt_long4" else False)
        warmup = 1
    elif windowed:
        # gpt_long with the Gemma-2 attention deltas: even layers banded at
        # 1024, odd layers full causal, logits softcapped at 50 — the
        # whole stack stays on the fused flash path (auto-dispatch at
        # S=4096), and MFU below uses the windowed-flop model
        seq, per_chip_batch = 4096, 1
        model = GPT(max_position=seq, dropout_rate=0.0,  # GPT-2 small dims
                    sliding_window=1024,
                    sliding_window_pattern="alternate",
                    attn_logit_cap=50.0)
        warmup = 2
    elif medium:
        seq, per_chip_batch = 1024, 8
        model = GPT(hidden_size=1024, depth=24, num_heads=16, mlp_dim=4096,
                    max_position=seq, dropout_rate=0.0, attn_impl="flash")
        warmup = 2
    else:
        # gpt_long2 (b=2) / gpt_long4 (b=4 + remat='dots'): the round-5
        # batch-lever ladder — b=1 measured ~20% MFU after the 512-tile
        # flip; more tokens/step lifts the h=768 GEMM efficiency term, and
        # at b=4 the dots-only remat trades recompute FLOPs for the
        # activation memory that would otherwise bound the batch
        seq = 4096
        per_chip_batch = {"gpt_long2": 2, "gpt_long4": 4}.get(prefix, 1)
        model = GPT(max_position=seq, dropout_rate=0.0,  # GPT-2 small dims
                    remat="dots" if prefix == "gpt_long4" else False)
        warmup = 2
    global_batch = per_chip_batch * n_chips

    tx = optax.adamw(1e-4)
    sample = np.zeros((global_batch, seq), np.int32)
    state, _ = init_state(model, tx, strategy, sample, seed=0)
    step_fn = make_custom_train_step(strategy, state, next_token_loss)

    rng = np.random.default_rng(0)
    toks = rng.integers(0, model.vocab_size, (global_batch, seq)).astype(np.int32)
    key = jax.random.key(0)
    holder = {"state": state}
    metrics = None
    for _ in range(warmup):
        holder["state"], metrics = step_fn(holder["state"], (toks,), key)
    loss_start = clock.fetch_scalar(metrics["loss"])

    def run(reps):
        m = None
        for _ in range(reps):
            holder["state"], m = step_fn(holder["state"], (toks,), key)
        return m

    reps, window, gap, loss_end = clock.timed(
        run, lambda m: m["loss"], 0.05 if smoke else 2.0,
        start_reps=2 if smoke else 5, max_reps=500,
    )
    step_s = window / reps
    tokens_per_step = global_batch * seq
    flops_per_token = gpt_train_flops_per_token(
        model.hidden_size, model.mlp_dim, model.depth, seq,
        model.vocab_size, window=model.sliding_window,
        window_pattern=model.sliding_window_pattern,
    )
    achieved = tokens_per_step * flops_per_token / step_s / n_chips
    out = {
        f"{prefix}_seq": seq,
        f"{prefix}_step_ms": round(step_s * 1e3, 2),
        f"{prefix}_loss_moved": bool(abs(loss_end - loss_start) > 1e-9),
    }
    if model.sliding_window is not None:
        out[f"{prefix}_window"] = model.sliding_window
        out[f"{prefix}_window_pattern"] = model.sliding_window_pattern
    if _gate(out, prefix, achieved, peak):
        out.update({
            f"{prefix}_mfu": round(achieved / peak, 4),
            f"{prefix}_tokens_per_sec_per_chip": round(
                tokens_per_step / step_s / n_chips, 1
            ),
            f"{prefix}_achieved_tflops_per_chip": round(achieved / 1e12, 2),
        })
    return out


def moe_gpt_train_flops_per_token(hidden: int, mlp: int, depth: int,
                                  seq: int, vocab: int, num_experts: int,
                                  experts_per_token: int,
                                  moe_every: int) -> float:
    """Analytic *useful* matmul FLOPs per token for a routed causal-LM
    fwd+bwd step: the gpt formula with the MLP term split — dense layers
    keep 4HF, MoE layers cost k*4HF (each token through k experts) plus
    the router GEMM 2HE. The dispatch/combine one-hot einsums are real
    MXU work but move no information per FLOP, so they are NOT counted:
    `moe_mfu` is useful-FLOP MFU and understates hardware utilization —
    the honest direction (attention credited at the exact in-band count
    from ops/roofline.py, same as gpt_train_flops_per_token)."""
    from tfde_tpu.ops.roofline import attention_flops_per_token

    n_moe = depth // moe_every
    n_dense = depth - n_moe
    attn_qkvo = (8 * hidden * hidden
                 + attention_flops_per_token(hidden, seq, causal=True))
    dense_layer = attn_qkvo + 4 * hidden * mlp
    moe_layer = (attn_qkvo + experts_per_token * 4 * hidden * mlp
                 + 2 * hidden * num_experts)
    return 3.0 * (n_dense * dense_layer + n_moe * moe_layer
                  + 2 * hidden * vocab)


def _bench_moe(clock: _Clock, strategy, n_chips: int, peak: float,
               smoke: bool) -> dict:
    """Routed-MoE training on hardware (VERDICT r4 weak #5: the only model
    family with no chip number). GPT-2-small dims with every 2nd MLP
    routed (8 experts, top-2, ST-MoE z-loss) at S=1024, per-chip batch 8,
    vs its dense-FLOP-matched twin: the twin's mlp_dim is scaled so total
    MLP GEMM FLOPs match (12 dense units vs 6 + 6*k units), isolating the
    routing machinery's overhead at equal useful work. Reports moe_mfu
    (useful-FLOP), the step-time ratio, and router-balance evidence: the
    load-balance aux summed over layers (n_moe * weight — the emitted
    moe_aux_balanced_value — = perfectly balanced top-1 routing) and
    z-loss at the start and end of the timed window."""
    import jax
    import numpy as np
    import optax

    from tfde_tpu.models.gpt import GPT, next_token_loss
    from tfde_tpu.training.step import init_state, make_custom_train_step

    e, k, every = 8, 2, 2
    if smoke:
        import jax.numpy as jnp

        seq, per_chip_batch = 64, 8
        dims = dict(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                    max_position=seq, dtype=jnp.float32)
        mlp, warmup = 128, 1
    else:
        seq, per_chip_batch = 1024, 8
        dims = dict(hidden_size=768, depth=12, num_heads=12,
                    max_position=seq, dropout_rate=0.0)
        mlp, warmup = 3072, 2
    depth = dims["depth"]
    n_moe = depth // every
    # FLOP-matched dense twin: depth*F_twin = (depth-n_moe)*F + n_moe*k*F
    twin_mlp = mlp * ((depth - n_moe) + n_moe * k) // depth
    global_batch = per_chip_batch * n_chips

    def build(model):
        tx = optax.adamw(1e-4)
        sample = np.zeros((global_batch, seq), np.int32)
        state, _ = init_state(model, tx, strategy, sample, seed=0)
        return state, make_custom_train_step(strategy, state, next_token_loss)

    def timed_steps(state, step_fn, toks, key):
        holder = {"state": state}
        metrics = None
        for _ in range(warmup):
            holder["state"], metrics = step_fn(holder["state"], (toks,), key)
        first = {kk: clock.fetch_scalar(v) for kk, v in metrics.items()
                 if kk in ("loss", "moe_aux", "moe_z")}

        def run(reps):
            m = None
            for _ in range(reps):
                holder["state"], m = step_fn(holder["state"], (toks,), key)
            holder["last"] = m
            return m

        reps, window, _gap, loss_end = clock.timed(
            run, lambda m: m["loss"], 0.05 if smoke else 2.0,
            start_reps=2 if smoke else 5, max_reps=500,
        )
        last = {kk: clock.fetch_scalar(v)
                for kk, v in holder["last"].items()
                if kk in ("moe_aux", "moe_z")}
        return window / reps, first, loss_end, last

    rng = np.random.default_rng(0)
    moe_model = GPT(mlp_dim=mlp, num_experts=e, moe_every=every,
                    router_z_loss_weight=1e-3, **dims)
    toks = rng.integers(0, moe_model.vocab_size,
                        (global_batch, seq)).astype(np.int32)
    key = jax.random.key(0)
    state, step_fn = build(moe_model)
    step_s, first, loss_end, last = timed_steps(state, step_fn, toks, key)

    tokens_per_step = global_batch * seq
    flops_per_token = moe_gpt_train_flops_per_token(
        moe_model.hidden_size, mlp, depth, seq, moe_model.vocab_size,
        e, k, every,
    )
    achieved = tokens_per_step * flops_per_token / step_s / n_chips
    out = {
        "moe_experts": e,
        "moe_top_k": k,
        "moe_seq": seq,
        "moe_step_ms": round(step_s * 1e3, 2),
        "moe_loss_moved": bool(abs(loss_end - first["loss"]) > 1e-9),
    }
    # router balance: the metric sums E*sum(f*p)*weight over all n_moe
    # layers, so perfectly balanced routing reads n_moe * aux_loss_weight
    # (= 6 * 0.01 here), larger = more collapsed; z-loss shrinking means
    # logit magnitudes are controlled
    from tfde_tpu.models.moe import MoEMlp

    out["moe_aux_balanced_value"] = round(
        (depth // every) * MoEMlp.aux_loss_weight, 6
    )
    for kk in ("moe_aux", "moe_z"):
        if kk in first:
            out[f"{kk}_start"] = round(first[kk], 6)
        if kk in last:
            out[f"{kk}_end"] = round(last[kk], 6)
    if _gate(out, "moe", achieved, peak):
        out.update({
            "moe_mfu": round(achieved / peak, 4),
            "moe_tokens_per_sec_per_chip": round(
                tokens_per_step / step_s / n_chips, 1
            ),
        })

    # dense-FLOP-matched twin (own try: its failure keeps the moe numbers)
    try:
        dense_model = GPT(mlp_dim=twin_mlp, **dims)
        dstate, dstep = build(dense_model)
        d_step_s, _f, d_loss_end, _l = timed_steps(dstate, dstep, toks, key)
        d_flops = gpt_train_flops_per_token(
            dims["hidden_size"], twin_mlp, depth, seq,
            dense_model.vocab_size,
        )
        d_achieved = tokens_per_step * d_flops / d_step_s / n_chips
        out["moe_dense_twin_mlp_dim"] = twin_mlp
        out["moe_dense_twin_step_ms"] = round(d_step_s * 1e3, 2)
        # routing overhead at equal useful FLOPs: >1 = MoE step is slower
        out["moe_over_dense_step_ratio"] = round(step_s / d_step_s, 3)
        if _gate(out, "moe_dense_twin", d_achieved, peak):
            out["moe_dense_twin_mfu"] = round(d_achieved / peak, 4)
    except Exception as ex:
        out["moe_dense_twin_error"] = f"{type(ex).__name__}: {ex}"[:300]
    return out


def _bench_serve(clock: _Clock, smoke: bool) -> dict:
    """Continuous-batching serving throughput (inference/server.py): a
    stream of mixed-length requests through a fixed decode batch, rows
    re-used mid-flight. Complements `decode_*` (steady one-shot batch):
    this measures the throughput of the loop a server actually runs —
    admission prefills, the fused K-tick decode scan, and the per-step
    host sync included. Alongside the raw rate it reports the HOST
    OVERHEAD the device-resident loop exists to eliminate: an in-config
    greedy `generate` run (same model, same batch, one XLA program, zero
    scheduling) is the device ceiling, and `serve_host_overhead` = 1 −
    serve/decode throughput is the fraction of that ceiling the serving
    loop still spends on the host (the 97× gap of BENCH_r05 was this
    number at ~0.99). Latency rides the serving histograms: TTFT
    (submit → first token at admission) and per-output-token latency."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.inference.decode import generate
    from tfde_tpu.inference.server import ContinuousBatcher
    from tfde_tpu.observability import metrics as _metrics
    from tfde_tpu.models.gpt import GPT, GPT2Small

    if smoke:
        batch, new, n_req, max_len, depth = 2, 6, 4, 48, 4
        model = GPT(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                    mlp_dim=128, max_position=64, dtype=jnp.float32)
    else:
        batch, new, n_req, max_len, depth = 8, 96, 24, 256, 8
        model = GPT2Small(max_position=256, dropout_rate=0.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(0)
    # warm the scan/prefill compiles outside the timed window (two prompt
    # lengths cover the bucket set below; the warm run drains through the
    # same adaptive-depth ladder the timed run will use)
    warm = ContinuousBatcher(model, params, batch_size=batch,
                             max_len=max_len, scan_depth=depth)
    lens = (16, 32) if not smoke else (4, 8)
    for i in range(2 * batch):
        warm.submit(rng.integers(0, model.vocab_size, lens[i % len(lens)]),
                    new)
    warm.run()

    srv = ContinuousBatcher(model, params, batch_size=batch,
                            max_len=max_len, scan_depth=depth)
    reg = _metrics.default_registry()
    reg.reset("serving/")  # drop the warm run's TTFT/latency samples
    for i in range(n_req):
        srv.submit(
            rng.integers(0, model.vocab_size, lens[i % len(lens)]), new
        )
    t0 = _time.perf_counter()
    # step (rather than run) so slab occupancy can be sampled per decode
    # round — kv_stats is the same host-side read _publish_stats already
    # does every step, so the timed path is unchanged
    done = []
    occ_samples = []
    min_headroom = batch
    while not srv.idle:
        done.extend(srv.step())
        kv = srv.kv_stats()
        occ_samples.append(1.0 - kv["waste_frac"])
        min_headroom = min(min_headroom, kv["headroom_rows"])
    total = sum(len(t) for _, t in done)
    # the loop's own host round-trips are part of what's measured; the
    # final host sync is implicit in the per-step bundled fetch
    dt = _time.perf_counter() - t0
    stats = srv.stats()
    serve_tps = total / max(dt, 1e-9)
    pad = srv._ledger.pad_stats()
    out = {
        "serve_tokens_per_sec": round(serve_tps, 1),
        "serve_requests": len(done),
        "serve_batch": batch,
        "serve_total_tokens": int(total),
        "serve_scan_depth": depth,
        "serve_ms_per_token": round(dt * 1e3 / max(total, 1), 3),
        # host cost per generated token — the O(1/K) bound the fused scan
        # buys (the old loop paid >= 3); admission waves included
        "serve_dispatches_per_token": round(
            stats["dispatches_per_token"], 3
        ),
        "serve_syncs_per_token": round(stats["syncs_per_token"], 3),
        # capacity ledger columns (observability/capacity.py): the
        # paged-KV PR's before/after baseline. waste_frac is the
        # pad-ladder fraction (prefill cells computed beyond the true
        # prompt); occupancy is mean committed/allocated slab fraction
        # across decode rounds; headroom_rows is the tightest admission
        # headroom the run saw
        "serve_kv_waste_frac": round(
            pad["pad_waste_tokens"] / max(pad["pad_alloc_tokens"], 1), 4),
        "serve_kv_occupancy": round(
            sum(occ_samples) / max(len(occ_samples), 1), 4),
        "serve_headroom_rows": int(min_headroom),
    }
    # memory + compile columns: peak bytes over every serve/* program the
    # ledger registered (prefill buckets + decode depths) and the serve
    # sites' sentinel counters — misses here are the pad-ladder compiles
    # the warm run is supposed to have prepaid
    from tfde_tpu.observability import memwatch as _memwatch
    from tfde_tpu.observability import recompile as _recompile

    serve_pms = [p for n, p in _memwatch.programs().items()
                 if n.startswith("serve/")]
    serve_sites = [s for n, s in _recompile.sites().items()
                   if n.startswith("serve/")]
    out["serve_peak_hbm_bytes"] = int(max(
        (p.peak_bytes for p in serve_pms), default=0))
    out["serve_compile_count"] = int(sum(
        s["misses"] for s in serve_sites))
    out["serve_compile_seconds"] = round(sum(
        s["seconds"] for s in serve_sites), 3)
    ttft = reg.get("serving/ttft_ms")
    if ttft is not None and ttft.count:
        out["serve_ttft_ms"] = round(ttft.percentile(50), 2)
        out["serve_ttft_p95_ms"] = round(ttft.percentile(95), 2)
        out["serve_ttft_p99_ms"] = round(ttft.percentile(99), 2)
    # TTFT decomposition: queue wait (submit -> wave start, which includes
    # sitting behind in-flight decode scans) + prefill (the serving/prefill
    # span) account for the first token; the residual is per-wave host
    # bookkeeping (planning, scatter, the admission fetch)
    qw = reg.get("serving/queue_wait_ms")
    if qw is not None and qw.count:
        out["serve_ttft_queue_wait_ms"] = round(qw.percentile(50), 2)
    pf = reg.get("serving/prefill")   # span histogram, seconds
    if pf is not None and pf.count:
        out["serve_ttft_prefill_ms"] = round(pf.percentile(50) * 1e3, 2)
    if {"serve_ttft_ms", "serve_ttft_queue_wait_ms",
            "serve_ttft_prefill_ms"} <= out.keys():
        out["serve_ttft_other_ms"] = round(max(
            0.0, out["serve_ttft_ms"] - out["serve_ttft_queue_wait_ms"]
            - out["serve_ttft_prefill_ms"]), 2)

    # device ceiling: the same model generating the same per-request
    # budget as ONE program (prompt = the stream's shorter bucket) — what
    # the chip does with the host fully out of the loop
    prompt = jnp.asarray(
        rng.integers(0, model.vocab_size, (batch, lens[0])), jnp.int32
    )

    def run(reps):
        toks = None
        for _ in range(reps):
            toks, _ = generate(model, params, prompt, max_new_tokens=new)
        return toks

    clock.fetch_scalar(run(1)[0, -1].astype(jnp.float32))  # compile+warm
    reps, window, _, _ = clock.timed(
        run, lambda t: t[0, -1].astype(jnp.float32),
        0.05 if smoke else 1.0, start_reps=1, max_reps=100,
    )
    decode_tps = batch * new / (window / reps)
    out["serve_decode_ceiling_tokens_per_sec"] = round(decode_tps, 1)
    # fraction of the device ceiling still lost to the serving loop's
    # host work (0 = fully device-resident; admission makes a small
    # irreducible floor). Negative means serving BEAT the one-shot
    # program (possible: continuous batching refills rows the one-shot
    # batch leaves padding) — report 0, not a nonsense negative.
    out["serve_host_overhead"] = round(
        max(0.0, 1.0 - serve_tps / max(decode_tps, 1e-9)), 4
    )

    # ---- prefix-KV cache A/B: shared system prompt, cold vs warm TTFT ----
    # The serving win the cache exists for: every request opens with the
    # same system prompt; after the first (cold) request seeds the trie,
    # admission scatters the cached K/V and prefills only the per-request
    # tail. Cold = full-prompt prefill TTFT; warm = suffix-only TTFT for a
    # wave of requests sharing the prefix. Compiles are warmed with a
    # same-shape throwaway system prompt so neither phase times XLA.
    from tfde_tpu.inference.prefix_cache import PrefixCache

    if smoke:
        sys_len, tail, pnew, pblock, pmax_len = 40, 4, 6, 32, 64
        pmodel, pparams = model, params
    else:
        sys_len, tail, pnew, pblock, pmax_len = 512, 16, 32, 16, 640
        pmodel = GPT2Small(max_position=640, dropout_rate=0.0)
        pparams = pmodel.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    rng2 = np.random.default_rng(7)

    def mk_reqs(sys_tokens, n):
        return [
            np.concatenate([
                sys_tokens,
                rng2.integers(0, pmodel.vocab_size, tail),
            ])
            for _ in range(n)
        ]

    def phase(b, reqs):
        """Submit `reqs`, run to drain, return (ttft_p50_ms, outputs)."""
        reg.reset("serving/ttft_ms")
        for p in reqs:
            b.submit(p, pnew)
        finished = b.run()
        h = reg.get("serving/ttft_ms")
        toks = [list(map(int, t)) for _, t in sorted(finished)]
        return (h.percentile(50) if h is not None and h.count
                else float("nan")), toks

    pc = PrefixCache(block=pblock)
    pb = ContinuousBatcher(pmodel, pparams, batch_size=batch,
                           max_len=pmax_len, scan_depth=depth,
                           prefix_cache=pc)
    wsys = rng2.integers(0, pmodel.vocab_size, sys_len)
    msys = rng2.integers(0, pmodel.vocab_size, sys_len)
    phase(pb, mk_reqs(wsys, 1))       # compile the cold single-row wave
    phase(pb, mk_reqs(wsys, batch))   # compile the warm wave (wsys cached)
    cold, _ = phase(pb, mk_reqs(msys, 1))
    reqs_warm = mk_reqs(msys, batch)
    warmed, warm_toks = phase(pb, reqs_warm)
    # correctness rider: the warm wave must be bit-identical to a
    # cache-off batcher fed the same requests (greedy decode)
    ref = ContinuousBatcher(pmodel, pparams, batch_size=batch,
                            max_len=pmax_len, scan_depth=depth)
    for p in reqs_warm:
        ref.submit(p, pnew)
    ref_toks = [list(map(int, t)) for _, t in sorted(ref.run())]
    st = pc.stats()
    out["serve_prefix_cold_ttft_ms"] = round(cold, 2)
    out["serve_prefix_warm_ttft_ms"] = round(warmed, 2)
    out["serve_prefix_warm_over_cold"] = round(
        warmed / max(cold, 1e-9), 3
    )
    out["serve_prefix_hit_rate"] = round(st["hit_rate"], 3)
    out["serve_prefix_reused_tokens"] = int(st["reused_tokens"])
    out["serve_prefix_bytes_saved_mb"] = round(
        st["bytes_saved"] / 2**20, 2
    )
    out["serve_prefix_parity_ok"] = warm_toks == ref_toks

    # ---- paged-KV A/B (inference/paged.py): same byte budget, short ----
    # requests. The block pool's capacity claim needs a number: a dense
    # batcher allocates max_len cells per row up front, so a fixed KV
    # byte budget affords batch = budget / row_bytes rows; the paged
    # batcher allocates blocks_for(prompt + new + 1) blocks per row, so
    # short requests (1 block here vs max_len/block = 5 dense) pack ~5x
    # more concurrent rows into the SAME bytes. Both sides run under the
    # same TFDE_CAPACITY_BUDGET_BYTES; the paged pool is sized to exactly
    # the dense slab's bytes, and max in-flight rows is measured from the
    # actual step loop, not computed. Greedy parity across the two runs
    # rides along (same stream, same rids).
    ab_batch = 2 if smoke else 4
    ab_max_len, ab_block_rows = 80, 16 if smoke else 32
    ab_new, ab_nreq = 6, (2 * ab_block_rows)
    ab_model = GPT(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                   mlp_dim=128, max_position=128, dtype=jnp.float32)
    ab_params = ab_model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng3 = np.random.default_rng(11)
    ab_reqs = [rng3.integers(0, ab_model.vocab_size, int(rng3.integers(4, 9)))
               for _ in range(ab_nreq)]

    def ab_run(paged: bool, budget: int):
        from tfde_tpu.inference.prefix_cache import DEFAULT_BLOCK as _blk
        kwargs = dict(batch_size=ab_batch, max_len=ab_max_len,
                      scan_depth=depth, paged=False)
        if paged:
            usable = ab_batch * ab_max_len // _blk
            kwargs = dict(batch_size=ab_block_rows, max_len=ab_max_len,
                          scan_depth=depth, paged=True,
                          pool_blocks=usable + 1)
        prev_budget = os.environ.get("TFDE_CAPACITY_BUDGET_BYTES")
        os.environ["TFDE_CAPACITY_BUDGET_BYTES"] = str(budget)
        try:
            b = ContinuousBatcher(ab_model, ab_params, **kwargs)
        finally:
            if prev_budget is None:
                os.environ.pop("TFDE_CAPACITY_BUDGET_BYTES", None)
            else:
                os.environ["TFDE_CAPACITY_BUDGET_BYTES"] = prev_budget
        for p in ab_reqs:
            b.submit(p, ab_new)
        fin, inflight, blk_active, blk_free = [], 0, 0, None
        while not b.idle:
            fin.extend(b.step())
            inflight = max(inflight,
                           sum(r is not None for r in b._req))
            kv = b.kv_stats()
            if "pool_blocks_active" in kv:
                blk_active = max(blk_active, int(kv["pool_blocks_active"]))
                free = int(kv["pool_blocks_free"])
                blk_free = free if blk_free is None else min(blk_free, free)
        toks = [list(map(int, t)) for _, t in sorted(fin)]
        return toks, inflight, blk_active, blk_free, b.kv_stats()

    # the budget is the DENSE slab's bytes — measured, not assumed
    from tfde_tpu.observability.capacity import kv_slab_bytes as _ksb
    probe = ContinuousBatcher(ab_model, ab_params, batch_size=ab_batch,
                              max_len=ab_max_len, scan_depth=depth)
    ab_budget = int(_ksb(probe._cache))
    del probe
    dense_toks, dense_rows, _a, _f, _kv = ab_run(False, ab_budget)
    paged_toks, paged_rows, blk_active, blk_free, pkv = ab_run(
        True, ab_budget)
    out["serve_paged_budget_bytes"] = ab_budget
    out["serve_max_inflight_rows"] = int(paged_rows)
    out["serve_max_inflight_rows_dense"] = int(dense_rows)
    out["serve_paged_inflight_gain"] = round(
        paged_rows / max(dense_rows, 1), 2)
    out["serve_kv_blocks_active"] = int(blk_active)
    out["serve_kv_blocks_free"] = int(0 if blk_free is None else blk_free)
    out["serve_paged_kv_waste_frac"] = round(
        float(pkv.get("waste_frac", 0.0)), 4)
    out["serve_paged_parity_ok"] = paged_toks == dense_toks

    # ---- int8 KV-cache A/B (TFDE_KV_QUANT, ops/quant.kv_quantize) ----
    # The quantization claim needs numbers at a FIXED byte budget (the
    # config's own fp dense slab, measured): each ledger prices rows by
    # its dtype-true cost — int8 payload is a quarter of fp32 plus a
    # per-(position, head) fp32 scale sidecar — so the same budget
    # admits ~2.7x the rows at this head_dim (the >= 1.8x bar; the
    # sidecar's share shrinks as head_dim grows). Headroom is read from
    # the kv/headroom_rows surface of idle batchers whose row count
    # does NOT clamp the budget. Greedy parity runs on a small-head
    # config where argmax gaps dwarf the amax/254 round-trip error —
    # the mechanism bar (>= 0.98), not a model-quality claim: a
    # random-init wide-vocab model near-ties its logits, where ANY
    # eps-perturbation (a dtype cast included) flips coin-flip argmaxes
    # the 0.98 bar was never about.
    kvq_model = GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4,
                    mlp_dim=64, max_position=128, dtype=jnp.float32)
    kvq_params = kvq_model.init(
        jax.random.key(2), jnp.zeros((1, 8), jnp.int32))["params"]
    kvq_batch, kvq_rows, kvq_new = (2 if smoke else 4), ab_block_rows, 6
    rng4 = np.random.default_rng(13)
    kvq_reqs = [rng4.integers(0, 97, int(rng4.integers(4, 9)))
                for _ in range(ab_nreq)]

    def kvq_build(kv_quant, *, use_paged, rows, pool_mult=1, budget=None):
        from tfde_tpu.inference.prefix_cache import DEFAULT_BLOCK as _blk
        kwargs = dict(batch_size=rows, max_len=ab_max_len,
                      scan_depth=depth, paged=use_paged,
                      kv_quant=kv_quant)
        if use_paged:
            usable = kvq_batch * ab_max_len // _blk
            kwargs["pool_blocks"] = usable * pool_mult + 1
        prev = os.environ.get("TFDE_CAPACITY_BUDGET_BYTES")
        if budget is not None:
            os.environ["TFDE_CAPACITY_BUDGET_BYTES"] = str(budget)
        try:
            return ContinuousBatcher(kvq_model, kvq_params, **kwargs)
        finally:
            if budget is not None:
                if prev is None:
                    os.environ.pop("TFDE_CAPACITY_BUDGET_BYTES", None)
                else:
                    os.environ["TFDE_CAPACITY_BUDGET_BYTES"] = prev

    def kvq_drain(b):
        for p in kvq_reqs:
            b.submit(p, kvq_new)
        ts = _time.perf_counter()
        fin = b.run()
        wall = max(_time.perf_counter() - ts, 1e-9)
        toks = [list(map(int, t)) for _, t in sorted(fin)]
        return toks, sum(len(t) for t in toks) / wall

    def kvq_match(got, ref):
        hit = tot = 0
        for g, r in zip(got, ref):
            tot += max(len(g), len(r))
            hit += sum(1 for a, b in zip(g, r) if a == b)
        return hit / max(tot, 1)

    # the fixed envelope: this config's own fp dense slab, measured
    kvq_probe = kvq_build("fp", use_paged=False, rows=kvq_batch)
    kvq_budget = int(_ksb(kvq_probe._cache))
    # headroom probes: idle batchers under that envelope; the int8
    # sides carry 4x the rows/blocks so the BUDGET binds, not the batch
    hd_fp = kvq_build("fp", use_paged=False, rows=kvq_batch,
                      budget=kvq_budget).kv_stats()["headroom_rows"]
    hd_q8 = kvq_build("int8", use_paged=False, rows=4 * kvq_batch,
                      budget=kvq_budget).kv_stats()["headroom_rows"]
    hdp_fp = kvq_build("fp", use_paged=True, rows=kvq_rows,
                       budget=kvq_budget).kv_stats()["headroom_rows"]
    hdp_q8 = kvq_build("int8", use_paged=True, rows=kvq_rows,
                       pool_mult=4,
                       budget=kvq_budget).kv_stats()["headroom_rows"]
    out["serve_kv_quant_budget_bytes"] = kvq_budget
    out["serve_kv_quant_headroom_rows"] = int(hd_q8)
    out["serve_kv_quant_headroom_gain"] = round(hd_q8 / max(hd_fp, 1), 2)
    out["serve_kv_quant_headroom_gain_paged"] = round(
        hdp_q8 / max(hdp_fp, 1), 2)
    # parity + throughput on the live stream (budget off: this leg
    # measures tokens, not admission). Each batcher drains the stream
    # twice and the second pass is the number — pass one swallows the
    # XLA compiles, so the int8 wall never includes its own program
    # builds while fp rides the cache-warm twins from the A/Bs above.
    b_fp = kvq_probe
    b_q8 = kvq_build("int8", use_paged=False, rows=kvq_batch)
    b_q8p = kvq_build("int8", use_paged=True, rows=kvq_rows, pool_mult=4)
    fp_toks, _ = kvq_drain(b_fp)
    q8_toks, _ = kvq_drain(b_q8)
    q8p_toks, _ = kvq_drain(b_q8p)
    _, fp_tps = kvq_drain(b_fp)
    _, q8_tps = kvq_drain(b_q8)
    out["serve_kv_quant_greedy_match"] = round(
        min(kvq_match(q8_toks, fp_toks), kvq_match(q8p_toks, fp_toks)), 4)
    out["serve_kv_quant_decode_tps"] = round(q8_tps, 1)
    out["serve_kv_quant_decode_tps_ratio"] = round(
        q8_tps / max(fp_tps, 1e-9), 3)

    # ---- tracing A/B (observability/trace.py): same stream, ring on ----
    # The zero-cost-when-off claim needs a number: re-run the serving
    # stream with every request carrying a trace id and the process ring
    # recording queue/prefill/decode-round/done events, and report the
    # throughput give-up. Ring appends are nanoseconds but the wall clock
    # is not: interleaved best-of-N per side (drift hits both alike; the
    # per-round spread on a tiny CPU smoke run is ~15%, far above the
    # effect being measured — 8 rounds converge it, 3 suffice on the
    # longer full-config walls), clamped at 0. Compiles are already
    # warm — the A/B times scheduling, not XLA.
    from tfde_tpu.observability import trace as reqtrace

    def stream_tps(traced: bool) -> float:
        b = ContinuousBatcher(model, params, batch_size=batch,
                              max_len=max_len, scan_depth=depth)
        srng = np.random.default_rng(0)
        for i in range(n_req):
            b.submit(
                srng.integers(0, model.vocab_size, lens[i % len(lens)]),
                new, trace=reqtrace.new_id() if traced else None,
            )
        ts = _time.perf_counter()
        fin = b.run()
        return (sum(len(t) for _, t in fin)
                / max(_time.perf_counter() - ts, 1e-9))

    trace_was_on = reqtrace.active()
    if not trace_was_on:
        reqtrace.enable()
    try:
        plain_tps, traced_tps = 0.0, 0.0
        for _ in range(8 if smoke else 3):
            plain_tps = max(plain_tps, stream_tps(False))
            traced_tps = max(traced_tps, stream_tps(True))
        out["serve_trace_overhead_pct"] = round(
            max(0.0, 1.0 - traced_tps / max(plain_tps, 1e-9)) * 100, 2
        )
        # exemplar linking: the trace ids a p99 hunt would start from
        ex = reqtrace.exemplars("serving/ttft_ms")
        if ex:
            out["serve_ttft_p99_exemplar_traces"] = [
                r["trace"] for r in ex[:3]
            ]
    finally:
        if not trace_was_on:
            reqtrace.disable()
    return out


def serve_replica_child_mode() -> None:
    """Child of the serve_cluster config: one tiny-GPT ContinuousBatcher
    behind a ReplicaServer on an ephemeral port, announced through an
    atomically renamed port file. argv:
    ``--serve-replica-child <replica_id> <port_file> <push_url|->``.
    Compiles are warmed before the port is announced, so the parent's
    Poisson load never times a child's XLA. Request tracing follows the
    inherited ``TFDE_TRACE`` env (the parent spawns recording and
    non-recording twins for the overhead A/B). Runs until the parent
    kills it — SIGTERM at teardown, SIGKILL in the drill."""
    i = sys.argv.index("--serve-replica-child")
    rid = int(sys.argv[i + 1])
    port_file = sys.argv[i + 2]
    push_url = None if sys.argv[i + 3] == "-" else sys.argv[i + 3]

    import pickle

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.inference.router import ReplicaServer
    from tfde_tpu.inference.server import ContinuousBatcher
    from tfde_tpu.models.gpt import GPT
    from tfde_tpu.observability import boot as boot_lib

    # the boot ledger narrates this child's cold start: init (backdated
    # to process birth) -> restore (a real file round-trip, so the
    # bandwidth gauge is a disk number) -> compile (the warm loop's XLA)
    # -> warmup -> ready. The parent reads the phases off the push
    # gauges for the serve_cluster_* cold-boot columns.
    led = boot_lib.current()
    led.begin("init")
    model = GPT(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                mlp_dim=128, max_position=64, dtype=jnp.float32)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    ckpt = port_file + ".ckpt"
    with open(ckpt, "wb") as f:
        pickle.dump(jax.device_get(params), f)
    led.begin("restore")
    t_r = time.perf_counter()
    with open(ckpt, "rb") as f:
        params = pickle.load(f)
    led.note_restore_leaf(
        "params",
        sum(x.nbytes for x in jax.tree_util.tree_leaves(params)),
        max(time.perf_counter() - t_r, 1e-9),
    )
    os.remove(ckpt)
    led.begin("compile")
    # batch 2 on purpose: the cluster bench wants per-replica saturation
    # (queueing behind a small decode batch) so adding the second replica
    # shows up as throughput, not idle rows
    b = ContinuousBatcher(model, params, batch_size=2, max_len=48,
                          scan_depth=4)
    rng = np.random.default_rng(rid)
    for ln in (4, 8, 4, 8):
        b.submit(rng.integers(0, model.vocab_size, ln), 16)
    b.run()
    led.begin("warmup")
    b.submit(rng.integers(0, model.vocab_size, 4), 4)
    b.run()
    srv = ReplicaServer(b, replica_id=rid, push_url=push_url,
                        push_interval=0.5, boot_ledger=led).start()
    led.ready()
    with open(port_file + ".tmp", "w") as f:
        f.write(str(srv.port))
    os.replace(port_file + ".tmp", port_file)
    while True:
        time.sleep(3600)


def _bench_serve_cluster(smoke: bool) -> dict:
    """Serving front door at cluster scale (inference/router.py): two
    batcher replicas in SUBPROCESSES (each its own CPU jax runtime — the
    real multi-host shape, not threads sharing one dispatch lock) behind
    the Router under open-loop Poisson load. Three phases: the same load
    against one replica (baseline tok/s), against both (the scaling
    claim: ~2x when each replica saturates), then the kill drill —
    SIGKILL one replica mid-run and verify queued sessions re-route, the
    survivor absorbs the load, the router's flight ring dumps the
    `replica_down` story, and the chief aggregator's host-up gauge
    flips. Replicas run a tiny GPT on CPU regardless of the bench
    platform: the claim here is routing/scaling behaviour, not model
    speed. NOTE the speedup is only meaningful with at least one core
    per replica (plus one for the router/load) — on a 1-core container
    both replicas time-share the same CPU and the honest answer is ~1x;
    `serve_cluster_host_cores` is reported so the reader can tell which
    regime produced the number."""
    import shutil
    import signal as _signal
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from tfde_tpu.inference.router import Router, request_generate
    from tfde_tpu.observability import metrics as _metrics
    from tfde_tpu.observability import trace as reqtrace
    from tfde_tpu.observability.aggregate import ClusterAggregator
    from tfde_tpu.observability.exposition import serve_metrics

    n_req = 8 if smoke else 24
    new = 16
    rate = 50.0   # arrivals/sec: the queue builds well past one replica
    reg = _metrics.default_registry()
    tmp = tempfile.mkdtemp(prefix="tfde_serve_cluster_")
    procs, routers, ms = [], [], None
    # the parent holds the routers, so its ring carries the router half of
    # every stitched waterfall below
    trace_was_on = reqtrace.active()
    if not trace_was_on:
        reqtrace.enable()
    try:
        agg = ClusterAggregator(stale_after=2.0)
        ms = serve_metrics(host="127.0.0.1", aggregator=agg)
        push = f"http://127.0.0.1:{ms.port}/push"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"   # replicas never contend for the TPU
        env.pop("XLA_FLAGS", None)
        # children 0/1 are the cluster (rings recording — the drill below
        # wants the survivor's half of a stitched waterfall); child 2 is a
        # tracing-OFF twin of child 0 for the overhead A/B, kept out of
        # the routers' tables and the aggregator
        port_files = [os.path.join(tmp, f"port{i}") for i in range(3)]
        for i in range(3):
            cenv = dict(env)
            cenv["TFDE_TRACE"] = "on" if i < 2 else "off"
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--serve-replica-child", str(i), port_files[i],
                 push if i < 2 else "-"],
                env=cenv, cwd=os.path.dirname(os.path.abspath(__file__)),
                stdout=open(os.path.join(tmp, f"child{i}.out"), "w"),
                stderr=subprocess.STDOUT,
            ))
        deadline = time.time() + 240
        while not all(os.path.exists(p) for p in port_files):
            if time.time() > deadline:
                raise RuntimeError(
                    "replica children never announced their ports"
                )
            if any(p.poll() is not None for p in procs):
                raise RuntimeError("a replica child died during startup")
            time.sleep(0.2)
        urls = []
        for p in port_files:
            with open(p) as f:
                urls.append(f"http://127.0.0.1:{int(f.read())}")

        def run_load(router_url, seed, kill_at=None, kill_fn=None):
            """Open-loop Poisson arrivals: fire-and-thread at exponential
            gaps regardless of completions; returns (results, wall_s)."""
            lrng = np.random.default_rng(seed)
            gaps = lrng.exponential(1.0 / rate, size=n_req)
            prompts = [
                lrng.integers(0, 512, int(lrng.integers(3, 9))).tolist()
                for _ in range(n_req)
            ]
            results: list = [None] * n_req
            threads = []
            t0 = time.perf_counter()
            for k in range(n_req):
                time.sleep(gaps[k])
                if kill_at is not None and k == kill_at:
                    kill_fn()

                def call(idx=k, p=prompts[k]):
                    try:
                        results[idx] = request_generate(
                            router_url, p, new, timeout=60.0
                        )
                    except Exception as e:  # retriable mid-stream death
                        results[idx] = {
                            "error": f"{type(e).__name__}: {e}"
                        }
                th = threading.Thread(target=call)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=120.0)
            return results, time.perf_counter() - t0

        def tps(results, wall):
            toks = sum(len(r["tokens"]) for r in results
                       if r and "tokens" in r)
            return toks / max(wall, 1e-9)

        out = {"serve_cluster_replicas": 2,
               "serve_cluster_requests": n_req,
               "serve_cluster_new_tokens": new,
               "serve_cluster_poisson_rate": rate,
               "serve_cluster_host_cores": os.cpu_count() or 1}

        r1 = Router([urls[0]]).start()
        routers.append(r1)
        single, wall = run_load(r1.url, seed=1)
        single_tps = tps(single, wall)
        out["serve_cluster_single_tokens_per_sec"] = round(single_tps, 1)

        # tracing overhead at cluster scale: the identical load against
        # the tracing-OFF twin replica (child 2). The router side records
        # in both runs (same parent process), so the delta isolates the
        # replica-side ring cost on the serving path.
        r0 = Router([urls[2]]).start()
        routers.append(r0)
        untraced, wall = run_load(r0.url, seed=1)
        out["serve_cluster_trace_overhead_pct"] = round(
            max(0.0, 1.0 - single_tps / max(tps(untraced, wall), 1e-9))
            * 100, 2
        )

        r2 = Router(urls[:2]).start()
        routers.append(r2)
        pair, wall = run_load(r2.url, seed=1)
        pair_tps = tps(pair, wall)
        out["serve_cluster_pair_tokens_per_sec"] = round(pair_tps, 1)
        out["serve_cluster_speedup"] = round(
            pair_tps
            / max(out["serve_cluster_single_tokens_per_sec"], 1e-9), 2
        )
        ttfts = sorted(r["ttft_s"] * 1e3 for r in pair
                       if r and r.get("ttft_s") is not None)
        if ttfts:
            out["serve_cluster_ttft_p95_ms"] = round(
                ttfts[min(len(ttfts) - 1, int(0.95 * len(ttfts)))], 2
            )
            out["serve_cluster_ttft_p99_ms"] = round(
                ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))], 2
            )
        # overload accounting on the same pair run (no extra phase, so
        # the trendgate series stay comparable): with TFDE_ADMIT_* caps
        # unset these stay 0 and the columns just pin the orderly-exit
        # classes — completed / 429-rejected / deadline-shed
        adm = [r for r in pair if r and "tokens" in r]
        rej = [r for r in pair if r and "429" in r.get("error", "")]
        sheds = [r for r in pair
                 if r and "deadline_shed" in r.get("error", "")]
        out["serve_cluster_rejected_429"] = len(rej)
        out["serve_cluster_shed"] = len(sheds)
        out["serve_cluster_reject_rate"] = round(
            (len(rej) + len(sheds)) / max(len(pair), 1), 3)
        adm_ttfts = sorted(r["ttft_s"] * 1e3 for r in adm
                           if r.get("ttft_s") is not None)
        if adm_ttfts:
            out["serve_cluster_admitted_ttft_p99_ms"] = round(
                adm_ttfts[min(len(adm_ttfts) - 1,
                              int(0.99 * len(adm_ttfts)))], 2
            )
        # fleet KV capacity after the pair run: the replicas pushed their
        # kv/* gauges with every metrics push, so the chief's rollup has
        # the allocation-weighted waste and summed headroom (the cluster
        # face of the paged-KV baseline)
        roll = agg.rollup()
        if "kv_waste_frac" in roll:
            out["serve_cluster_kv_waste_frac"] = round(
                roll["kv_waste_frac"], 4)
            out["serve_cluster_kv_headroom_rows"] = int(
                roll["kv_headroom_rows"])
        flat_hosts = agg.host_metrics(("kv/",))
        occ = [1.0 - h["kv/waste_frac"] for h in flat_hosts.values()
               if "kv/waste_frac" in h]
        if occ:
            out["serve_cluster_kv_occupancy"] = round(
                sum(occ) / len(occ), 4)
        # block-pool columns (paged replicas only — the kv/pool_blocks_*
        # gauges exist exactly when TFDE_PAGED_KV reached the children):
        # summed across the fleet like headroom, the capacity story in
        # blocks instead of rows
        blk_act = [h["kv/pool_blocks_active"] for h in flat_hosts.values()
                   if "kv/pool_blocks_active" in h]
        if blk_act:
            out["serve_cluster_kv_blocks_active"] = int(sum(blk_act))
            out["serve_cluster_kv_blocks_free"] = int(sum(
                h.get("kv/pool_blocks_free", 0)
                for h in flat_hosts.values()))
        # cold-boot columns (informational, gate:false): the children
        # pushed their boot/* ledger gauges; report the slowest replica's
        # time-to-ready, its boot-attributed compile wall, and the mean
        # restore bandwidth — the serving face of WORKFLOWS.md §21
        boot_hosts = agg.host_metrics(("boot/",))
        ttrs = [h["boot/time_to_ready_seconds"]
                for h in boot_hosts.values()
                if "boot/time_to_ready_seconds" in h]
        if ttrs:
            out["serve_cluster_time_to_ready_s"] = round(max(ttrs), 3)
        compiles = [h["boot/compile_wall_seconds"]
                    for h in boot_hosts.values()
                    if "boot/compile_wall_seconds" in h]
        if compiles:
            out["serve_cluster_boot_compile_s"] = round(max(compiles), 3)
        bws = [h["boot/restore_bandwidth_bps"]
               for h in boot_hosts.values()
               if "boot/restore_bandwidth_bps" in h]
        if bws:
            out["serve_cluster_restore_bw_mbps"] = round(
                sum(bws) / len(bws) / 1e6, 2)

        # kill drill: router with the aggregator attached (staleness is a
        # second down signal) and a flight ring to dump the post-mortem
        reg.reset("router/")
        router_dir = os.path.join(tmp, "router")
        os.makedirs(router_dir, exist_ok=True)
        rk = Router(urls[:2], aggregator=agg, model_dir=router_dir).start()
        routers.append(rk)
        killed, wall = run_load(
            rk.url, seed=2, kill_at=max(1, n_req // 3),
            kill_fn=lambda: os.kill(procs[0].pid, _signal.SIGKILL),
        )
        done = [r for r in killed if r and "tokens" in r]
        errs = [r for r in killed if r and "error" in r]
        out["serve_cluster_kill_completed"] = len(done)
        out["serve_cluster_kill_retriable_errors"] = len(errs)
        c = reg.get("router/reroutes")
        out["serve_cluster_kill_reroutes"] = int(c.value) if c else 0
        try:
            survivor = request_generate(rk.url, [5, 6, 7, 8], new,
                                        timeout=60.0)
            out["serve_cluster_kill_survivor_ok"] = (
                len(survivor["tokens"]) == new
            )
        except Exception as e:
            out["serve_cluster_kill_survivor_ok"] = False
            out["serve_cluster_kill_survivor_error"] = str(e)[:200]
        out["serve_cluster_kill_flight_dump"] = bool(
            _find_flight_dumps(router_dir)
        )
        # the acceptance waterfall: find a completed request the drill
        # re-routed and fetch its stitched trace from the router — the
        # router's attempts (replica 0, then the reroute to 1) and the
        # survivor's serve/* events must land in ONE trace. The dead
        # replica's ring died with it (SIGKILL), which is exactly the
        # post-mortem shape: attempts tell the routing story, the
        # survivor tells the serving story.
        stitched_ok = False
        for r in done:
            tid = r.get("trace")
            if not tid:
                continue
            try:
                with urllib.request.urlopen(
                    rk.url + f"/trace/{tid}", timeout=5.0
                ) as resp:
                    tr = json.loads(resp.read())
            except Exception:
                continue
            evs = tr.get("events", [])
            attempts = {e.get("replica") for e in evs
                        if e.get("name") == "router/attempt"}
            if {0, 1} <= attempts:
                out["serve_cluster_trace_stitched_procs"] = tr.get(
                    "procs", []
                )
                out["serve_cluster_trace_events"] = len(evs)
                stitched_ok = any(
                    str(e.get("name", "")).startswith("serve/")
                    for e in evs
                )
                break
        out["serve_cluster_trace_rerouted_ok"] = stitched_ok
        ex = reqtrace.exemplars("router/ttft_ms")
        if ex:
            out["serve_cluster_ttft_exemplar_traces"] = [
                r["trace"] for r in ex[:3]
            ]
        # the dead replica stops pushing; after stale_after the chief
        # scrape must report it down
        time.sleep(agg.stale_after + 0.5)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics", timeout=5.0
        ) as resp:
            text = resp.read().decode()
        out["serve_cluster_kill_host_up_flipped"] = (
            'tfde_cluster_host_up{host="0"} 0' in text
        )
        return out
    finally:
        if not trace_was_on:
            reqtrace.disable()
        for r in routers:
            try:
                r.close()
            except Exception:
                pass
        if ms is not None:
            ms.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def _find_flight_dumps(root: str) -> list:
    """Flight-recorder dump files under `root` (any depth)."""
    hits = []
    for dirpath, _dirs, files in os.walk(root):
        hits.extend(os.path.join(dirpath, f) for f in files
                    if "flight" in f)
    return hits


def _bench_decode(clock: _Clock, smoke: bool) -> dict:
    """Serving-side decode throughput: GPT-2-small KV-cache generation
    (inference/decode.py) — tokens/sec at batch 8, prompt 128. The decode
    regime is HBM-bandwidth-bound (every step streams the full weights +
    cache for one token per row), so this measures a different ceiling than
    the training MFU configs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.inference.decode import generate
    from tfde_tpu.models.gpt import GPT, GPT2Small

    if smoke:
        batch, prompt_len, new = 2, 16, 8
        model = GPT(vocab_size=512, hidden_size=64, depth=2, num_heads=2,
                    mlp_dim=128, max_position=64, dtype=jnp.float32)
    else:
        batch, prompt_len, new = 8, 128, 128
        model = GPT2Small(max_position=prompt_len + new, dropout_rate=0.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((batch, prompt_len + new), jnp.int32)
    )["params"]
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, model.vocab_size, (batch, prompt_len)), jnp.int32
    )

    def make_run(mdl, prms, n_new):
        def run(reps):
            toks = None
            for i in range(reps):
                toks, _ = generate(mdl, prms, prompt, max_new_tokens=n_new,
                                   rng=jax.random.key(i), temperature=1.0,
                                   top_k=40)
            return toks
        return run

    def time_call(mdl, prms, n_new):
        run = make_run(mdl, prms, n_new)
        clock.fetch_scalar(run(1)[0, -1].astype(jnp.float32))  # compile+warm
        reps, window, _, _ = clock.timed(
            run, lambda t: t[0, -1].astype(jnp.float32),
            0.05 if smoke else 2.0, start_reps=1, max_reps=200,
        )
        return window / reps, reps

    # The full call includes the prompt prefill; an N=1 baseline isolates
    # it (prefill + a single sample), so the difference over new-1 tokens
    # is the pure per-token decode cost — the HBM-bandwidth figure.
    per_call, reps = time_call(model, params, new)
    prefill_call, _ = time_call(model, params, 1)
    delta = per_call - prefill_call
    out = {
        "decode_batch": batch,
        "decode_prompt_len": prompt_len,
        "decode_new_tokens": new,
        # whole-call generation throughput (prefill amortized over the call)
        "decode_gen_tokens_per_sec": round(batch * new / per_call, 1),
        "decode_call_ms": round(per_call * 1e3, 2),
        "decode_prefill_ms": round(prefill_call * 1e3, 2),
        "decode_calls_timed": reps,
    }
    # decode-only rate: prefill subtracted via the N=1 baseline. A delta
    # within noise of zero is an invalid measurement — report it as such,
    # never a clamped absurdity (the trust rule every config follows).
    if new > 1 and delta > 0.05 * per_call:
        out["decode_ms_per_token"] = round(delta / (new - 1) * 1e3, 3)
        out["decode_tokens_per_sec"] = round(batch * (new - 1) / delta, 1)
    else:
        out["decode_error"] = (
            "prefill baseline >= full call within noise; decode-only rate "
            "unmeasurable at this config"
        )

    def twin(prefix: str, mdl, prms) -> None:
        """One serving-lever twin, measured exactly like the base model:
        full call, N=1 prefill baseline, decode-only delta — with the SAME
        5% noise gate on the twin's own delta (a noise-level delta must
        report as unmeasurable, never as an absurd tokens/sec; the trust
        rule every config follows). Speedup is decode-only vs decode-only:
        the full call is prefill-diluted, which would understate the
        bandwidth effect the twins measure. Own try/except — a twin
        failure must not discard the numbers already measured."""
        try:
            t_call, _ = time_call(mdl, prms, new)
            t_prefill, _ = time_call(mdl, prms, 1)
            t_delta = t_call - t_prefill
            out[f"{prefix}_gen_tokens_per_sec"] = round(
                batch * new / t_call, 1
            )
            if (new > 1 and delta > 0.05 * per_call
                    and t_delta > 0.05 * t_call):
                out[f"{prefix}_tokens_per_sec"] = round(
                    batch * (new - 1) / t_delta, 1
                )
                out[f"{prefix}_speedup"] = round(delta / t_delta, 3)
            else:
                out[f"{prefix}_error"] = (
                    f"decode-only delta unmeasurable for the {prefix} twin"
                )
        except Exception as e:
            out[f"{prefix}_error"] = f"{type(e).__name__}: {e}"[:300]

    if not smoke:
        # GQA twin (4 KV heads instead of 12): the serving memory/bandwidth
        # knob — same dims, random init (throughput only, quality N/A)
        gqa = GPT2Small(max_position=prompt_len + new, dropout_rate=0.0,
                        num_kv_heads=4)
        gparams = gqa.init(
            jax.random.key(0),
            jnp.zeros((batch, prompt_len + new), jnp.int32),
        )["params"]
        out["decode_gqa_kv_heads"] = 4
        twin("decode_gqa", gqa, gparams)

    # int8 W8A8 twin (ops/quant.py): weight HBM traffic halves and the
    # matmuls ride the v5e's double-rate int8 MXU — the quantization
    # serving lever. Runs in smoke mode too (unlike GQA) so CI exercises
    # the quantized decode path end to end.
    try:
        from tfde_tpu.ops.quant import quantize_model

        qmodel, qparams = quantize_model(model, params)
        twin("decode_int8", qmodel, qparams["params"])
    except Exception as e:
        out["decode_int8_error"] = f"{type(e).__name__}: {e}"[:300]
    return out


#: bench_meta schema: 1 = implicit (pre-provenance lines, no meta block);
#: 2 = bench_meta {schema, git_sha, backend, knobs} on every emitted line
BENCH_SCHEMA_VERSION = 2


def _git_sha() -> str | None:
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode == 0:
            return proc.stdout.strip() or None
    except Exception:
        pass
    return None  # tarball checkouts bench too


def _knob_snapshot() -> dict:
    """Every TFDE_* knob actually set in this environment — the capture's
    configuration fingerprint. Unregistered names are included on purpose:
    a knob the registry doesn't know yet is exactly the drift a cross-round
    diff needs to surface (registry: tfde_tpu/knobs.py)."""
    return {k: v for k, v in sorted(os.environ.items())
            if k.startswith("TFDE_")}


def _bench_meta(platform: str | None = None, device_kind: str | None = None,
                n_chips: int | None = None) -> dict:
    """Provenance block stamped onto every emitted JSON line so captures
    are alignable across machines and rounds (trendgate's raw material)."""
    meta: dict = {
        "schema": BENCH_SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "knobs": _knob_snapshot(),
    }
    if platform is not None:
        meta["backend"] = {"platform": platform, "device_kind": device_kind,
                           "n_chips": n_chips}
    return meta


def run_mode() -> None:
    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    device_kind = str(devices[0].device_kind)
    if platform == "cpu" and os.environ.get("TFDE_BENCH_ALLOW_CPU") != "1":
        print(json.dumps({"error": "backend came up as cpu; refusing a "
                          "silent-fallback number (set TFDE_BENCH_ALLOW_CPU=1 "
                          "to override)", "platform": platform}))
        sys.exit(3)

    from tfde_tpu.parallel.strategies import MirroredStrategy

    strategy = MirroredStrategy()
    n_chips = strategy.num_replicas
    peak = device_peak_flops(devices[0])
    print(f"platform={platform} kind={device_kind} chips={n_chips}",
          file=sys.stderr)

    smoke = os.environ.get("TFDE_BENCH_SMOKE") == "1"
    result = {"platform": platform, "device_kind": device_kind,
              "n_chips": n_chips,
              "chip_peak_tflops": peak_tflops_field(peak)}
    if smoke:
        result["smoke"] = True

    clock = _Clock()
    configs = [
        ("calib", lambda: _bench_calibration(clock, peak, smoke)),
        ("mnist", lambda: _bench_mnist(clock, strategy, n_chips, smoke)),
        ("mnist_e2e", lambda: _bench_mnist_e2e(clock, strategy, n_chips, smoke)),
        ("link", lambda: _bench_link(clock, smoke)),
        ("mnist_dev", lambda: _bench_mnist_dev(clock, strategy, n_chips,
                                               smoke)),
        ("obs", lambda: _bench_obs(strategy, smoke)),
        ("bert", lambda: _bench_bert_mfu(clock, strategy, n_chips, peak, smoke)),
        ("comms", lambda: _bench_comms(n_chips, smoke)),
        ("zero", lambda: _bench_zero(n_chips, smoke)),
        ("flash", lambda: _bench_flash(clock, smoke)),
        # stretch configs: ordered last so an attempt-timeout salvages the
        # core numbers above (run mode emits a cumulative line per config)
        ("bert32", lambda: _bench_bert_mfu(clock, strategy, n_chips, peak,
                                           smoke, per_chip_batch=32,
                                           prefix="bert32")),
        # fusion A/B at equal batch: bert_fused_mfu - bert_mfu isolates the
        # one-GEMM qkv projection (transformer.fused_qkv)
        ("bert_fused", lambda: _bench_bert_mfu(clock, strategy, n_chips,
                                               peak, smoke,
                                               prefix="bert_fused",
                                               fused_qkv=True)),
        ("gpt_long", lambda: _bench_gpt_long(clock, strategy, n_chips, peak,
                                             smoke)),
        ("gpt_medium", lambda: _bench_gpt_long(clock, strategy, n_chips,
                                               peak, smoke,
                                               prefix="gpt_medium")),
        ("gpt_long2", lambda: _bench_gpt_long(clock, strategy, n_chips,
                                              peak, smoke,
                                              prefix="gpt_long2")),
        ("gpt_long4", lambda: _bench_gpt_long(clock, strategy, n_chips,
                                              peak, smoke,
                                              prefix="gpt_long4")),
        ("gpt_long_win", lambda: _bench_gpt_long(clock, strategy, n_chips,
                                                 peak, smoke,
                                                 prefix="gpt_long_win")),
        ("moe", lambda: _bench_moe(clock, strategy, n_chips, peak, smoke)),
        ("decode", lambda: _bench_decode(clock, smoke)),
        ("serve", lambda: _bench_serve(clock, smoke)),
        ("serve_cluster", lambda: _bench_serve_cluster(smoke)),
    ]

    def emit(partial: bool) -> None:
        # One cumulative JSON line after every config: a run killed at its
        # time limit still leaves every number measured so far on stdout.
        value = result.get("mnist_images_per_sec_per_chip", 0.0)
        line = {
            "metric": "mnist_bncnn_train_images_per_sec_per_chip",
            "value": value,
            "unit": "images/sec/chip",
            # The reference publishes no numbers (its README is a
            # bare title) — a ratio against an invented constant is not a
            # baseline.
            "vs_baseline": None,
            "vs_baseline_note": "reference publishes no benchmark numbers",
            **result,
            "bench_meta": _bench_meta(platform, device_kind, n_chips),
        }
        if partial:
            line["partial"] = True
        if "calib_error" in result:
            line["error"] = result["calib_error"]
            line["value"] = 0.0
        print(json.dumps(line), flush=True)

    def attribute_e2e() -> None:
        """e2e-gap attribution (VERDICT r3 #3): how much of
        e2e_step - compute_step the measured per-batch link cost explains.
        A fraction near 1.0 proves the residual is pure transfer (host
        link latency); well below 1.0 points at pipeline overhead instead."""
        need = ("mnist_step_ms", "mnist_e2e_step_ms", "link_batch_ms")
        if not all(k in result for k in need):
            return
        gap = result["mnist_e2e_step_ms"] - result["mnist_step_ms"]
        result["e2e_gap_ms"] = round(gap, 3)
        if gap > 1e-3:
            result["e2e_gap_link_fraction"] = round(
                result["link_batch_ms"] / gap, 3
            )

    for i, (name, fn) in enumerate(configs):
        try:
            result.update(fn())
        except Exception as e:  # keep the rest; the exit code says it failed
            result[f"{name}_error"] = f"{type(e).__name__}: {e}"[:400]
        print(f"{name} done", file=sys.stderr)
        if name == "calib" and "calib_error" in result:
            break  # timing itself is broken; more numbers would be noise
        if name == "link":
            attribute_e2e()
        if i < len(configs) - 1:
            emit(partial=True)
    emit(partial=False)
    exit_if_errors(result, "bench")


def _last_json(stdout: str) -> dict | None:
    """Last stdout line that parses as a JSON object, or None."""
    for ln in reversed((stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            return parsed
    return None


if __name__ == "__main__":
    if "--comms-child" in sys.argv:
        comms_child_mode()
    elif "--serve-replica-child" in sys.argv:
        serve_replica_child_mode()
    elif "--zero-child" in sys.argv:
        zero_child_mode()
    else:
        run_mode()
