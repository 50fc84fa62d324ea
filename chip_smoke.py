"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one pass over the two main paths through the entry points a
user calls, at GPT-2-small's published width with seeded random weights:

- **train** (the examples/gpt_lm.py path): `bootstrap()`,
  `MultiWorkerMirroredStrategy()` over every local device, `init_state`,
  `make_custom_train_step(strategy, state, next_token_loss)`; S=4096, one
  sequence per chip, a few AdamW steps on a small fixed pool of
  `datasets.synthetic_tokens` rows. Checked: finite, falling loss, the
  Mosaic flash kernel in the compiled step, per-chip kernel operands, and
  the batch and outputs on every device.
- **serve** (the examples/serve_gpt.py path): `ContinuousBatcher` over
  `GPT2Small`, 16 requests of 16-512 prompt tokens and 64 new tokens each.
  Checked: every request returns its full budget of in-vocabulary tokens,
  fewer than one host sync per token, KV-cache decode logits equal to the
  full forward, and every served token near the full forward's argmax.

It refuses to run unless `jax.devices()[0].platform == "tpu"`, exits
non-zero if any phase fails, and prints as its last line of stdout one JSON
object with exactly these keys, the device as jax reports it:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
The line before it, `[summary] {...,"claim": null}`, carries everything
else. The times it prints are bring-up sanity (interpret mode or a CPU
would be >10x off), not benchmark results.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from importlib import metadata

SEED = 0
TRAIN_SEQ = 4096          # the r04 `gpt_long` shape: S=4096, batch 1 per chip
TRAIN_STEPS = 20
TRAIN_POOL_PER_CHIP = 2   # a pool this small is memorised within 20 steps
# constant, at the example's peak rate: a 20-step run never leaves its
# warm-up, and 1e-3 without one spikes (11.3 -> 14.7 at step 19 on four chips)
TRAIN_LR = 3e-4
SERVE_MAX_LEN = 1024
SERVE_BATCH = 8
SERVE_SCAN_DEPTH = 8
SERVE_REQUESTS = 16
SERVE_PROMPT_RANGE = (16, 512)
SERVE_NEW_TOKENS = 64
# bf16 keeps 8 significand bits, so every rounding is worth 2^-8 relative.
# Two programs that compute the same logits along different routes (one
# S-long causal forward; a prefill plus one-token steps against a masked
# max_len cache; the batcher's padded bucket prefill plus a fused scan)
# round the residual stream about twice per layer in different orders, so
# their logits agree to ~2 * depth * 2^-8 of the logit range — ~9% of it
# for 12 layers. A wrong program (a shifted position, a stale cache row)
# is off by the whole range.
BF16_EPS = 2.0 ** -8


class SmokeFailure(Exception):
    """A phase ran but what came out is wrong."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileMeter:
    """Seconds jax spent lowering, compiling and reading the persistent
    cache, plus the cache's request/hit/write counts, from jax.monitoring
    — so each phase's wall splits into compile and run. Tracing stays on
    the run side: jax reports it once per nested jit, so its events
    overlap and cannot be summed."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )
    _COUNTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.backend_compiles = 0
        self.counts = {name: 0 for name in self._COUNTS.values()}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event in self._DURATIONS:
            self.compile_s += seconds
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        name = self._COUNTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s,
                "backend_compiles": self.backend_compiles, **self.counts}


def _timed_phase(name: str, meter: CompileMeter, fn) -> dict:
    """Run one phase; its facts plus the cold wall split into compile and
    run. A phase that raises takes the process down — nothing here
    catches."""
    before, t0 = meter.snapshot(), time.perf_counter()
    facts = fn()
    wall = time.perf_counter() - t0
    delta = {k: v - before[k] for k, v in meter.snapshot().items()}
    compile_s = delta.pop("compile_s")
    facts.update(wall_s=round(wall, 2), compile_s=round(compile_s, 2),
                 run_s=round(wall - compile_s, 2), **delta)
    print(f"[{name}] wall {facts['wall_s']}s = compile {facts['compile_s']}s "
          f"+ run {facts['run_s']}s; backend compiles "
          f"{facts['backend_compiles']}, cache requests "
          f"{facts['cache_requests']} hits {facts['cache_hits']} writes "
          f"{facts['cache_writes']}", flush=True)
    return facts


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def train_phase(model, seq: int, steps: int) -> dict:
    """The examples/gpt_lm.py default path at `seq`, one sequence per chip.
    Returns what it observed; `check_train` judges it."""
    import jax
    import numpy as np

    from tfde_tpu import bootstrap
    from tfde_tpu.data import datasets
    from tfde_tpu.models.gpt import next_token_loss
    from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy
    from tfde_tpu.training.optimizers import adamw as masked_adamw
    from tfde_tpu.training.step import init_state, make_custom_train_step

    bootstrap()
    n = jax.device_count()  # the global batch too: one sequence per chip
    strategy = MultiWorkerMirroredStrategy()
    tx = masked_adamw(TRAIN_LR, weight_decay=0.1)
    state, _ = init_state(
        model, tx, strategy, np.zeros((n, seq), np.int32)
    )
    step_fn = make_custom_train_step(strategy, state, next_token_loss)
    pool = datasets.synthetic_tokens(
        TRAIN_POOL_PER_CHIP * n, seq, vocab=model.vocab_size
    )
    rng = jax.random.key(1)
    nrng = np.random.default_rng(SEED)

    losses, step_s = [], []
    batch = None
    for _ in range(steps):
        idx = nrng.integers(0, len(pool), n)
        t0 = time.perf_counter()
        # placed here (step_fn's own device_put is then a no-op) so the
        # placement can be read back below
        batch = jax.device_put((pool[idx],), strategy.batch_sharding())
        state, metrics = step_fn(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
        step_s.append(time.perf_counter() - t0)

    # in-memory or persistent-cache hit: the step above already compiled it
    hlo = step_fn.lower(state, batch, rng).compile().as_text()
    kernel_lines = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    head_dim = model.head_dim or model.hidden_size // model.num_heads
    # the forward's operands are [b, heads, seq, d], the fused backward's
    # the model's own layout flattened, [b, seq, heads * d]
    operand = re.compile(
        r"\[(\d+),(?:%d,%d,%d|%d,%d)\]" % (
            model.num_heads, seq, head_dim, seq, model.num_heads * head_dim))
    kernel_batch = sorted({int(b) for ln in kernel_lines
                           for b in operand.findall(ln)})
    outputs = jax.tree_util.tree_leaves((state.params, metrics))
    return {
        "devices": n,
        "seq": seq,
        "losses": [round(v, 4) for v in losses],
        "first_step_s": round(step_s[0], 2),
        "steady_step_ms": round(float(np.median(step_s[1:])) * 1e3, 1),
        "mosaic_calls": len(kernel_lines),
        "kernel_batch_dims": kernel_batch,
        "batch_devices": len(batch[0].sharding.device_set),
        "batch_shard_shapes": sorted(
            {tuple(s.data.shape) for s in batch[0].addressable_shards}),
        "output_devices_min": min(
            len(x.sharding.device_set) for x in outputs),
    }


def check_train(f: dict) -> None:
    import math

    n = f["devices"]
    _require(all(math.isfinite(v) for v in f["losses"]),
             f"non-finite loss: {f['losses']}")
    # batches are random draws from the pool, so single steps are noisy:
    # the median of the last five is what must sit below the first
    tail = sorted(f["losses"][-5:])[2]
    _require(tail < f["losses"][0],
             f"loss did not fall: first {f['losses'][0]}, median of the "
             f"last five {tail}")
    _require(f["mosaic_calls"] > 0,
             "no Mosaic custom call in the compiled step: attention went to "
             "the einsum or the interpreter, not the flash kernel")
    _require(f["kernel_batch_dims"] == [1],
             f"flash kernel operands carry batch dims "
             f"{f['kernel_batch_dims']}, expected the per-chip shard [1] "
             f"(the hazard: sharded in, replicated out)")
    _require(f["batch_devices"] == n and f["output_devices_min"] == n,
             f"batch on {f['batch_devices']} and outputs on "
             f"{f['output_devices_min']} of {n} devices")
    _require(f["batch_shard_shapes"] == [(1, f["seq"])],
             f"batch shards {f['batch_shard_shapes']}, expected "
             f"[(1, {f['seq']})]")


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def serve_phase(model, max_len: int, batch_size: int, scan_depth: int,
                n_requests: int, prompt_range: tuple, new_tokens: int) -> dict:
    """The examples/serve_gpt.py default path, then the served tokens and
    the KV-cache logits against the full forward. Returns what it
    observed; `check_serve` judges it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tfde_tpu.inference.decode import (
        _decode_clone, _make_model_step, init_cache,
    )
    from tfde_tpu.inference.server import ContinuousBatcher

    params = model.init(
        jax.random.key(SEED), np.zeros((1, 8), np.int32)
    )["params"]
    srv = ContinuousBatcher(
        model, params, batch_size=batch_size, max_len=max_len,
        scan_depth=scan_depth,
    )
    nrng = np.random.default_rng(SEED)
    lo, hi = prompt_range
    # log-uniform: as many short prompts as long ones
    plens = np.exp(nrng.uniform(np.log(lo), np.log(hi), n_requests))
    prompts = {}
    for plen in np.clip(plens.round().astype(int), lo, hi):
        prompt = nrng.integers(0, model.vocab_size, int(plen)).astype(np.int32)
        prompts[srv.submit(prompt, new_tokens)] = prompt
    t0 = time.perf_counter()
    done = dict(srv.run())
    serve_s = time.perf_counter() - t0
    stats = srv.stats()

    # -- every served token against the full forward (teacher-forced) -----
    width = -(-(hi + new_tokens) // 128) * 128
    rows = 4  # [4, width, vocab] fp32 logits at a time

    @jax.jit
    def chosen_vs_best(params, tokens, nxt):
        logits = model.apply({"params": params}, tokens).astype(jnp.float32)
        chosen = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
        return chosen, logits.max(-1), jnp.abs(logits).max()

    rids = sorted(done)
    worst_gap, logit_range = 0.0, 0.0
    for i in range(0, len(rids), rows):
        chunk = rids[i:i + rows]
        tokens = np.zeros((rows, width), np.int32)
        nxt = np.zeros((rows, width), np.int32)
        spans = []
        for r, rid in enumerate(chunk):
            p, g = prompts[rid], np.asarray(done[rid], np.int32)
            full = np.concatenate([p, g])
            tokens[r, :full.size] = full
            # position P-1+i predicts generated token i
            nxt[r, p.size - 1:p.size - 1 + g.size] = g
            spans.append((r, p.size - 1, p.size - 1 + g.size))
        chosen, best, amax = jax.device_get(
            chosen_vs_best(params, tokens, nxt))
        logit_range = max(logit_range, float(amax))
        for r, a, b in spans:
            worst_gap = max(worst_gap, float((best - chosen)[r, a:b].max()))

    # -- KV-cache decode logits against the full forward, one request -----
    rid = min(rids, key=lambda k: prompts[k].size)
    prompt, gen = prompts[rid], np.asarray(done[rid], np.int32)
    decode_model = _decode_clone(model)

    @jax.jit
    def kv_logits(params, cache, prompt, gen):
        step = _make_model_step(decode_model, params)
        cache, first = step(cache, prompt[None])

        def body(cache, tok):
            cache, logits = step(cache, tok[None, None])
            return cache, logits[0]

        _, rest = jax.lax.scan(body, cache, gen[:-1])
        return jnp.concatenate([first, rest], 0)  # predicts gen[0..]

    @jax.jit
    def full_logits(params, tokens):
        return model.apply({"params": params}, tokens[None])[0].astype(
            jnp.float32)

    kv = jax.device_get(kv_logits(
        params, init_cache(model, 1, max_len), prompt, gen))
    full = jax.device_get(full_logits(
        params, np.concatenate([prompt, gen])))[prompt.size - 1:-1]

    lengths = [len(done.get(rid, ())) for rid in prompts]
    flat = np.concatenate([np.asarray(t).ravel() for t in done.values()])
    return {
        "requests": n_requests,
        "returned": len(done),
        "tokens_min": min(lengths),
        "tokens_max": max(lengths),
        "new_tokens": new_tokens,
        "in_vocab": bool(flat.min() >= 0 and flat.max() < model.vocab_size),
        "paged": bool(srv.paged),
        "serve_s": round(serve_s, 2),
        "generated": int(stats["generated"]),
        "syncs_per_token": round(float(stats["syncs_per_token"]), 4),
        "dispatches_per_token": round(
            float(stats["dispatches_per_token"]), 4),
        "serve_devices": len({d for leaf in jax.tree_util.tree_leaves(params)
                              for d in leaf.devices()}),
        "logit_range": round(logit_range, 3),
        "served_token_gap_max": round(worst_gap, 4),
        "kv_vs_full_max_abs": round(float(np.abs(kv - full).max()), 4),
        "kv_vs_full_finite": bool(np.isfinite(kv).all()
                                  and np.isfinite(full).all()),
        "kv_checked_request": {"prompt": int(prompt.size),
                               "generated": int(gen.size)},
        "tolerance": round(2 * model.depth * BF16_EPS * logit_range, 4),
    }


def check_serve(f: dict) -> None:
    _require(f["returned"] == f["requests"],
             f"{f['returned']} of {f['requests']} requests returned")
    _require(f["tokens_min"] == f["tokens_max"] == f["new_tokens"],
             f"requests returned {f['tokens_min']}..{f['tokens_max']} tokens, "
             f"budget {f['new_tokens']}")
    _require(f["in_vocab"], "a served token id lies outside the vocabulary")
    _require(f["syncs_per_token"] < 1.0,
             f"{f['syncs_per_token']} host syncs per token: the fused decode "
             f"scan is not in effect")
    _require(f["kv_vs_full_finite"], "non-finite logits")
    _require(f["kv_vs_full_max_abs"] <= f["tolerance"],
             f"KV-cache decode logits differ from the full forward by "
             f"{f['kv_vs_full_max_abs']} > {f['tolerance']}")
    _require(f["served_token_gap_max"] <= f["tolerance"],
             f"a served token sits {f['served_token_gap_max']} below the "
             f"full forward's best logit (> {f['tolerance']}): the batcher "
             f"did not decode what the model computes")


# --------------------------------------------------------------------------

def _cache_entries(cache_dir: str) -> int:
    """Programs in jax's persistent cache (one `<key>-cache` file each)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(cache_dir))


def result_line(ok: bool, devices) -> str:
    """The last line of stdout: exactly these keys, the device as jax
    reports it. Whoever runs this check parses that line and nothing else,
    so everything else the run learned goes on the `[summary]` line."""
    return json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: this check needs a TPU; jax found "
              f"platform={dev.platform!r} kind={dev.device_kind!r} "
              f"count={len(devices)} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was run.",
              file=sys.stderr)
        return 2

    import jaxlib

    import tfde_tpu  # noqa: F401  (places the compile cache)

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    cache_dir = jax.config.jax_compilation_cache_dir
    entries0 = _cache_entries(cache_dir)
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={jax.device_count()} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    print(f"compile cache: {cache_dir} ({entries0} entries at start)",
          flush=True)

    try:
        summary = _run_phases(cache_dir, entries0)
    except Exception:
        # not a catch-and-continue: the failure still ends the process
        # non-zero; the last line only says so in the agreed form
        print(result_line(False, devices), flush=True)
        raise
    summary["versions"] = {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__, "libtpu": libtpu}
    summary["claim"] = None
    print(f"[summary] {json.dumps(summary)}")
    print(result_line(True, devices), flush=True)
    return 0


def _run_phases(cache_dir: str, entries0: int) -> dict:
    """Both phases, each judged as soon as it ends; the run's summary."""
    import jax

    from tfde_tpu.models.gpt import GPT, GPT2Small

    meter = CompileMeter()
    t0 = time.perf_counter()

    train = _timed_phase("train", meter, lambda: train_phase(
        GPT(max_position=TRAIN_SEQ, dropout_rate=0.0), TRAIN_SEQ,
        TRAIN_STEPS))
    print(f"[train] {json.dumps(train)}", flush=True)
    check_train(train)

    serve = _timed_phase("serve", meter, lambda: serve_phase(
        GPT2Small(max_position=SERVE_MAX_LEN, dropout_rate=0.0),
        SERVE_MAX_LEN, SERVE_BATCH, SERVE_SCAN_DEPTH, SERVE_REQUESTS,
        SERVE_PROMPT_RANGE, SERVE_NEW_TOKENS))
    print(f"[serve] {json.dumps(serve)}", flush=True)
    print(f"[serve] served on {serve['serve_devices']} device of "
          f"{jax.device_count()}: the batcher names no device, so it lives "
          f"on device 0 (replicas per chip are not part of this check)")
    check_serve(serve)

    entries1 = _cache_entries(cache_dir)
    total = meter.snapshot()
    return {
        "compile_cache": {"dir": cache_dir, "entries_before": entries0,
                          "entries_after": entries1,
                          "hits": total["cache_hits"],
                          "writes": total["cache_writes"]},
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_s": round(total["compile_s"], 1),
        "train": {k: train[k] for k in (
            "devices", "wall_s", "compile_s", "mosaic_calls",
            "kernel_batch_dims", "batch_devices")}
        | {"loss_first": train["losses"][0],
           "loss_last5_median": sorted(train["losses"][-5:])[2]},
        "serve": {k: serve[k] for k in (
            "serve_devices", "wall_s", "compile_s", "returned",
            "syncs_per_token", "kv_vs_full_max_abs", "served_token_gap_max",
            "tolerance")},
    }


if __name__ == "__main__":
    sys.exit(main())
