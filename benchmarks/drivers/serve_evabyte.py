"""Serving cells of a byte model with EVA chunked linear attention:
`inference.server.ContinuousBatcher` over `models.gpt.GPT(attention="eva")`
built from the configuration's published keys, bfloat16 weights, greedy,
driven through the batcher's public surface by `drivers/serve.py`'s
open-loop sender (`serve_window`, with the sender's cap of
`feed.max_unadmitted`) after `serve.py`'s warm-up of every (prompt bucket,
wave width) the mix can reach.

What differs from `serve.py`, which builds GPT-2 by GPT-2's key names and
may not be edited (PERF.md, open questions: the parts of its `run` copied
here):
- the model and its weights (`build_model`, `build_server`);
- every seed offers the same requests at the same times (`offered`): a
  window of this cell holds some forty admissions, too few for the order
  of arrival to average out, and a run's bytes followed the order, not
  the program;
- tokens are bytes: `serve_tokens_per_s` is bytes delivered inside the
  window per second;
- the check sample is `check_sample`'s plus, where one finished, a request
  whose served bytes straddle a multiple of the window (a decode step
  crossed position 2,048 k: the window was handed over and the summaries
  of the window just closed became visible); the `[notes]` line says
  whether a run compared across such an edge;
- each checked request is padded to the next multiple of the window, not
  to `max_len`: the float32 reference over 16,384 positions costs 2.5
  times the mean request's.
"""

from __future__ import annotations

import numpy as np

from benchmarks.drivers.serve import (check_sample, reachable_buckets,
                                      serve_window, warm_up)
from benchmarks.lib import clock, traffic
from benchmarks.lib.manifest import reference_module
from benchmarks.lib.result import memory_peak_bytes
from benchmarks.lib.stats import percentile


# The seed of the generator's one order of arrival (its due times and the
# lengths that come with them). 0, and not chosen by its reading.
ARRIVALS_SEED = 0


def offered(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The mix's requests as every seed offers them: one realisation of
    its arrivals (`traffic.generate` at `ARRIVALS_SEED`: when each request
    is due, how long its prompt is and how many bytes it asks for),
    carrying the bytes the generator draws for `seed` (its stream 2).

    `open_loop` gives every seed the same multiset in another order, which
    is the same work where a window serves all of it or hundreds of
    requests. This window closes on time over some forty admissions of
    2-14 kB each, the first sixteen into an empty batcher: which requests
    came early decided 258-296 bytes/s over eleven seeds, 12-13 % between
    the quartiles in the driver's check, while two runs of one seed read
    alike (PERF.md, section 4). So the order is the cell's, and the seed's
    are the bytes and the weights."""
    requests = traffic.generate(mix, ARRIVALS_SEED, seconds, vocab=vocab)
    ids = np.random.default_rng([int(seed), 2])
    for request in requests:
        request.prompt = ids.integers(0, vocab, request.prompt.size,
                                      dtype=np.int32)
    return requests


def build_model(cfg: dict):
    """`models.gpt.GPT` from the configuration file's published keys: the
    existing constructor, no preset."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT

    if cfg["hidden_act"] != "silu" or cfg.get("rope_scaling") is not None:
        raise ValueError("this driver builds a SwiGLU block with plain RoPE")
    extra = dict(cfg.get("constructor", {}))
    extra["dtype"] = getattr(jnp, extra.get("dtype", "bfloat16"))
    return GPT(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"], mlp_act="swiglu",
        max_position=cfg["max_position_embeddings"], position="rope",
        rope_theta=float(cfg["rope_theta"]), norm="rms",
        norm_unit_offset=cfg["norm_add_unit_offset"],
        ln_eps=cfg["rms_norm_eps"], use_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        fp32_residual=cfg["fp32_skip_add"],
        attention=cfg["attention_class"], eva_window=cfg["window_size"],
        eva_chunk=cfg["chunk_size"], **extra)


def build_server(cfg: dict, mix: dict, ref, seed: int) -> tuple:
    """(the batcher over the seed's weights in bfloat16 with every program
    the mix can reach run once, the number of warm-up waves, when the
    batcher stood)."""
    from tfde_tpu.inference.server import ContinuousBatcher

    dims, feed = ref.dims_of(cfg), cfg["feed"]
    batcher = dict(cfg["batcher"],
                   prompt_buckets=tuple(cfg["batcher"]["prompt_buckets"]))
    model = build_model(cfg)   # first: a program without the layer stops here
    params = ref.to_program_params(ref.make_weights(seed, dims),
                                   dims["num_attention_heads"])
    srv = ContinuousBatcher(model, params, **batcher)
    t_built = clock.now()
    waves = warm_up(
        srv,
        reachable_buckets(batcher["prompt_buckets"], mix["prompt"]["min"],
                          mix["prompt"]["max"]),
        mix["prompt"]["min"], mix["prompt"]["max"], feed["max_unadmitted"],
        batcher["scan_depth"], dims["vocab_size"])
    srv.enable_progress()
    return srv, waves, t_built


def crosses_window(record, window: int) -> bool:
    """Did a decode step of this finished request cross a multiple of the
    window? Served byte i was fed at position P + i; the step at the first
    position of a window reads the summaries of the one just closed."""
    first = record.request.prompt.size
    last = first + record.tokens.size - 2     # the last byte is never fed
    return last // window > (first - 1) // window


def edge_sample(done: list, seed: int, size: int, window: int) -> list:
    """`check_sample`, and one more request that crossed a window edge
    where the sample holds none and one finished."""
    sample = check_sample(done, seed, size)
    if not any(crosses_window(r, window) for r in sample):
        sample += [r for r in done if crosses_window(r, window)][:1]
    return sample


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    ref = reference_module(cfg["reference"])
    dims, batcher, feed = ref.dims_of(cfg), cfg["batcher"], cfg["feed"]
    device = ctx.devices[0]
    vocab, window = dims["vocab_size"], dims["window_size"]
    requests = offered(mix, ctx.seed, ctx.seconds, vocab)

    t_data = clock.now()
    srv, waves, t_built = build_server(cfg, mix, ref, ctx.seed)

    before = dict(srv.stats())
    compiles_before = ctx.meter.snapshot()["backend_compiles"]
    stop_at_close = mix["after_window"] == "stop"
    trace_from = ctx.seconds - min(float(mix["trace_seconds"]),
                                   ctx.seconds / 2.0)
    setup_s = clock.now() - ctx.t_start
    setup_meter = ctx.meter.snapshot()
    setup_parts = {"start_to_data": t_data - ctx.t_start,
                   "weights_and_batcher": t_built - t_data,
                   "warm_waves": clock.now() - t_built, **setup_meter}
    out = serve_window(
        srv, requests, ctx.seconds, feed["max_unadmitted"], stop_at_close,
        float(mix.get("drain_limit_s", 60.0)), ctx.tracer, trace_from)
    window_compiles = (ctx.meter.snapshot()["backend_compiles"]
                       - compiles_before)
    after = dict(srv.stats())
    seconds = ctx.seconds

    # -- what the users saw ---------------------------------------------------
    recs = list(out["records"].values())
    done = [r for r in recs if r.tokens is not None]
    in_window = [r for r in done if r.done_at <= seconds]
    short = [r for r in done if r.tokens.size != r.request.max_new_tokens]
    ttft = [(r.first - r.request.due_s) * 1e3 for r in recs
            if r.first is not None]
    tpot = [(r.last - r.first) * 1e3 / (r.count - 1) for r in done
            if r.count > 1]
    late = [(r.submitted - r.request.due_s) * 1e3 for r in recs]
    bytes_in_window = out["tokens_in_window"]
    if stop_at_close:
        attempted = len(in_window)
        failed = sum(1 for r in in_window
                     if r.tokens.size != r.request.max_new_tokens)
    else:
        attempted = len(requests)
        failed = attempted - sum(
            1 for r in done if r.tokens.size == r.request.max_new_tokens)
    counted = {k: after[k] - before[k] for k in after
               if type(after[k]) is int}

    # -- free the program, read the peak, then the reference ------------------
    del srv
    peak = memory_peak_bytes([device])
    t_ref = clock.now()
    limits = cfg["correct"]
    sample = edge_sample(done, ctx.seed, limits["sample_requests"], window)
    weights = ref.make_weights(ctx.seed, dims)
    worst, worst_control, checked, logit_range = 0.0, None, 0, 0.0
    for r in sample:
        pad_to = -(-(r.request.prompt.size + r.tokens.size) // window) * window
        gaps = ref.served_token_gaps(weights, r.request.prompt, r.tokens,
                                     dims, pad_to)
        worst = max(worst, float(gaps["gap"].max()))
        logit_range = max(logit_range, gaps["range"])
        checked += int(r.tokens.size)
        if ctx.control:
            lower = ref.served_token_gaps(
                weights, r.request.prompt, r.tokens, dims, pad_to,
                precision=cfg["control_precision"])
            below = ref.gaps_of_choices(
                weights, r.request.prompt, r.tokens, lower["argmax"], dims,
                pad_to)
            worst_control = max(worst_control or 0.0, float(below.max()))
    del weights
    reference_s = clock.now() - t_ref
    flat = (np.concatenate([r.tokens for r in done]) if done
            else np.zeros(0, np.int64))
    compared = [
        ("served_token_gap_max", worst, limits["served_token_gap_max"]),
        ("requests_returned_short", float(len(short)), 0.0),
        ("tokens_out_of_vocabulary",
         float(((flat < 0) | (flat >= vocab)).sum()), 0.0),
        ("sample_is_empty", 0.0 if checked else 1.0, 0.0),
    ]
    control = ([("served_token_gap_max", worst_control,
                 limits["served_token_gap_max"])] if ctx.control else None)

    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": bytes_in_window / seconds}
    if ttft:
        end_to_end["ttft_p50_ms"] = percentile(ttft, 50)
    if tpot:
        end_to_end["tpot_p50_ms"] = percentile(tpot, 50)
    notes = {
        "requests_generated": len(requests), "arrivals_seed": ARRIVALS_SEED,
        "sent": len(recs),
        "not_sent": out["not_sent"], "finished": len(done),
        "finished_in_window": len(in_window), "closed_s": out["closed_s"],
        "bytes_in_window": bytes_in_window, "warm_waves": waves,
        "window_compiles": window_compiles, "reference_s": reference_s,
        "setup_parts_s": setup_parts,
        "longest_step": out["longest_step"], "host": out["host"],
        "checked_requests": len(sample), "checked_tokens": checked,
        "checked_across_window_edge": sum(
            crosses_window(r, window) for r in sample),
        "finished_across_window_edge": sum(
            crosses_window(r, window) for r in done),
        "logit_range": logit_range,
        "sender_late_ms": {"p50": percentile(late, 50),
                           "p95": percentile(late, 95),
                           "max": max(late)} if late else None,
        "ttft_ms": {"p50": percentile(ttft, 50), "p95": percentile(ttft, 95),
                    "n": len(ttft)} if ttft else None,
        "tpot_ms": {"p50": percentile(tpot, 50), "p95": percentile(tpot, 95),
                    "n": len(tpot)} if tpot else None,
        "serve_tokens_per_s": bytes_in_window / seconds,
        "eva": {k: v for k, v in counted.items() if k.startswith("eva_")},
        # the step ledger over the window: a stalled run says which leaf
        "step_ns": {k: v for k, v in counted.items() if k.endswith("_ns")},
    }
    return {
        "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end, "compared": compared, "control": control,
        "memory_peak_bytes": peak, "notes": notes,
        "observed": {
            "counters": dict(setup_meter, **counted,
                             window_compiles=window_compiles),
            "chips": 1,
        },
    }
