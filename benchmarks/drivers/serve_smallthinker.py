"""Serving cells of a decoder whose layers attend either over everything
without positions or over a sliding window with rotary positions, each
followed by a routed layer of ReGLU experts whose router reads the
attention's input (SmallThinker): `inference.server.ContinuousBatcher` over
`models.gpt.GPT(windows=..., rope_layers=...)` built from the configuration's
published keys, every expert and the whole vocabulary held, bfloat16
weights, greedy, driven through the batcher's public surface by
`drivers/serve.py`'s open-loop sender (`serve_window`, with the sender's cap
of `feed.max_unadmitted`) after `serve.py`'s warm-up of every (prompt
bucket, wave width) the mix can reach. Under the batcher a window layer
keeps a ring of `sliding_window_size` cells a row beside the global layers'
slab.

What differs from `serve.py`, which builds GPT-2 by GPT-2's key names and
may not be edited (PERF.md, open questions: the parts of its `run` copied
here, as `serve_evabyte.py` and `serve_granite.py` do):
- the model and its weights (`build_model`, `build_server`): the program's
  parameter tree is the reference's own bfloat16 arrays re-nested, so the
  two copies of 7.9 GB never exist side by side;
- each checked request is padded to the next multiple of 2,048 positions,
  not to `max_len`;
- the check sample is `check_sample`'s plus, where the sample holds none
  and one finished, a request whose prompt is longer than the window (the
  wave kept its last `window` true tokens) and one whose served tokens
  straddle a multiple of the window (a decode step overwrote the cell one
  window back where the prompt was shorter, and the ring turned once more
  where it was longer); the `[notes]` line counts both;
- what is compared is a high quantile of the checked served tokens' gaps
  and a count of gaps no near-tie explains, not the widest gap
  (`gap_summary`, `GAP_QUANTILE`): over contexts of 4-15k the widest of
  some three thousand gaps reads 0.10-0.24 on sound runs and 0.49 under the
  fp8 control, too close for a limit between them (PERF.md, section 4);
  the `[notes]` line carries the whole summary, the widest gap in it;
- the `[notes]` line carries `routing_flips`, as `serve_granite.py`'s: in
  how many (layer, position) pairs of the longest checked request the
  reference computed in bfloat16 chooses other experts than the float32
  reference does;
- every seed offers the same requests at the same times (`offered`), as
  `serve_evabyte.py` and `serve_granite.py` do and for their reason;
- the counters of the window (`moe_*`, `kv_*`).
"""

from __future__ import annotations

import numpy as np

from benchmarks.drivers.serve import (check_sample, reachable_buckets,
                                      serve_window, warm_up)
from benchmarks.lib import clock, traffic
from benchmarks.lib.manifest import reference_module
from benchmarks.lib.result import memory_peak_bytes
from benchmarks.lib.stats import percentile

#: checked requests are padded to a multiple of this (the reference's
#: programs compile once a length)
PAD_TO = 2048
#: the seed of the generator's one order of arrival (its due times and the
#: lengths that come with them). 0, and not chosen by its reading
ARRIVALS_SEED = 0
#: the quantile of the checked served tokens' gaps that is compared: how far
#: the hundredth-worst token lies below the reference's best
GAP_QUANTILE = 0.99


def offered(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The mix's requests as every seed offers them: one realisation of
    its arrivals (`traffic.generate` at `ARRIVALS_SEED`: when each request
    is due, how long its prompt is and how many tokens it asks for),
    carrying the ids the generator's stream 2 draws for `seed`.

    `open_loop` gives every seed the same multiset in another order. This
    window closes on time over some hundred admissions of 0.3-14k tokens,
    the first 32 into an empty batcher, and each wave holds all rows for
    tenths of a second: with the seed's own order the two cells before
    this one spread 7.7-18.7 % between the quartiles, where a new cell is
    admitted under 5 % (PERF.md, section 4). So the order is the cell's,
    as in `serve_evabyte.py` and `serve_granite.py`, and the seed's are
    the ids and the weights."""
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

    if (not cfg["moe_primary_router_apply_softmax"]
            or not cfg["norm_topk_prob"] or cfg["rope_scaling"] is not None):
        raise ValueError("this driver builds a router whose weights are a "
                         "softmax renormalised over the chosen experts, and "
                         "unscaled rotary positions")
    extra = dict(cfg.get("constructor", {}))
    extra["dtype"] = getattr(jnp, extra.get("dtype", "bfloat16"))
    depth = cfg["num_hidden_layers"]
    return GPT(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        depth=depth, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["moe_ffn_hidden_size"], mlp_act="reglu",
        max_position=cfg["max_position_embeddings"], position="rope",
        rope_theta=float(cfg["rope_theta"]),
        rope_layers=tuple(cfg["rope_layout"][:depth]),
        windows=tuple(cfg["sliding_window_size"] if windowed else None
                      for windowed in cfg["sliding_window_layout"][:depth]),
        norm="rms", ln_eps=cfg["rms_norm_eps"], use_bias=False,
        tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["moe_num_primary_experts"], moe_every=1,
        experts_per_token=cfg["moe_num_active_primary_experts"],
        moe_capacity_factor=None, moe_normalize_topk=cfg["norm_topk_prob"],
        moe_router_pre_attention=True, **extra)


def build_server(cfg: dict, mix: dict, ref, seed: int) -> tuple:
    """(the batcher over the seed's weights in bfloat16 with every program
    the mix can reach run once, the number of warm-up waves, when the
    batcher stood)."""
    from tfde_tpu.inference.server import ContinuousBatcher

    dims, feed = ref.dims_of(cfg), cfg["feed"]
    batcher = dict(cfg["batcher"],
                   prompt_buckets=tuple(cfg["batcher"]["prompt_buckets"]))
    model = build_model(cfg)   # first: a program without the layers stops here
    params = ref.to_program_params(ref.make_weights(seed, dims))
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


def _flash_traces() -> dict:
    """Which forward `ops/flash_attention._flash_forward` took, per trace
    of a call site (the program's own counters)."""
    from tfde_tpu.observability import counters

    return {name: counters.value(name) for name in
            ("flash/fwd_lane_traces", "flash/fwd_grid_traces")}


def _prompt_over_window(record, window: int) -> bool:
    return record.request.prompt.size > window


def _decode_crossed_window(record, window: int) -> bool:
    """A served token was fed at a position that is a multiple of the
    window: token i is fed at position prompt + i, the last one never."""
    first = record.request.prompt.size
    last = first + record.tokens.size - 2
    return last // window > (first - 1) // window


def edge_sample(done: list, seed: int, size: int, window: int) -> list:
    """`check_sample`, and for each way of meeting the window's edge one
    more request that met it, where the sample holds none and one
    finished."""
    sample = check_sample(done, seed, size)
    for met in (_prompt_over_window, _decode_crossed_window):
        if not any(met(r, window) for r in sample):
            sample += [r for r in done if met(r, window)][:1]
    return sample


def gap_summary(gaps: list, far_off: float) -> dict:
    """Of every checked served token's gap (how far its logit lies below
    the reference's best at its position), pooled over the sample: the
    quantile that is compared, the count of gaps over `far_off` (a served
    token that is no near-tie of the reference's first choice: altered,
    or computed from a wrong state), and what else the `[notes]` line
    prints."""
    pooled = (np.concatenate([np.asarray(g, np.float64).ravel()
                              for g in gaps])
              if gaps else np.zeros(0))
    if pooled.size == 0:
        return {"n": 0, "quantile": 0.0, "far_off": 0, "max": 0.0}
    return {
        "n": int(pooled.size),
        "quantile": float(np.quantile(pooled, GAP_QUANTILE)),
        "far_off": int((pooled > far_off).sum()),
        "max": float(pooled.max()), "mean": float(pooled.mean()),
        "not_first_share": float((pooled > 0).mean()),
        **{f"p{name}": float(np.quantile(pooled, q)) for name, q in
           (("50", 0.5), ("90", 0.9), ("95", 0.95), ("98", 0.98),
            ("995", 0.995), ("999", 0.999))},
    }


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    ref = reference_module(cfg["reference"])
    dims, feed = ref.dims_of(cfg), cfg["feed"]
    device = ctx.devices[0]
    vocab = dims["vocab_size"]
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
    tokens_in_window = out["tokens_in_window"]
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
    window = dims["sliding_window_size"]
    sample = edge_sample(done, ctx.seed, limits["sample_requests"], window)
    weights = ref.make_weights(ctx.seed, dims)
    sound, lowered, checked, logit_range = [], [], 0, 0.0
    flips, flips_control, routed = 0, None, 0
    for r in sample:
        pad_to = -(-(r.request.prompt.size + r.tokens.size) // PAD_TO) * PAD_TO
        args = (weights, r.request.prompt, r.tokens, dims, pad_to)
        gaps = ref.served_token_gaps(*args)
        sound.append(gaps["gap"])
        logit_range = max(logit_range, gaps["range"])
        checked += int(r.tokens.size)
        first = r is sample[0]      # the longest: the flips are counted there
        if first:
            routed = gaps["routes"].shape[0] * gaps["routes"].shape[1]
            flips = ref.routing_flips(
                gaps["routes"],
                ref.served_token_gaps(*args, precision="bf16")["routes"])
        if ctx.control:
            lower = ref.served_token_gaps(
                *args, precision=cfg["control_precision"])
            below = ref.gaps_of_choices(
                weights, r.request.prompt, r.tokens, lower["argmax"], dims,
                pad_to)
            lowered.append(below)
            if first:
                flips_control = ref.routing_flips(gaps["routes"],
                                                  lower["routes"])
    del weights
    reference_s = clock.now() - t_ref
    flat = (np.concatenate([r.tokens for r in done]) if done
            else np.zeros(0, np.int64))
    served = gap_summary(sound, limits["far_off_gap"])
    control_served = (gap_summary(lowered, limits["far_off_gap"])
                      if ctx.control else None)
    compared = [
        ("served_token_gap_p99", served["quantile"],
         limits["served_token_gap_p99"]),
        ("served_tokens_far_off", float(served["far_off"]), 0.0),
        ("requests_returned_short", float(len(short)), 0.0),
        ("tokens_out_of_vocabulary",
         float(((flat < 0) | (flat >= vocab)).sum()), 0.0),
        ("sample_is_empty", 0.0 if checked else 1.0, 0.0),
    ]
    control = ([("served_token_gap_p99", control_served["quantile"],
                 limits["served_token_gap_p99"])] if ctx.control else None)

    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_in_window / seconds}
    if ttft:
        end_to_end["ttft_p50_ms"] = percentile(ttft, 50)
    if tpot:
        end_to_end["tpot_p50_ms"] = percentile(tpot, 50)
    notes = {
        "requests_generated": len(requests), "arrivals_seed": ARRIVALS_SEED,
        "sent": len(recs),
        "not_sent": out["not_sent"], "finished": len(done),
        "finished_in_window": len(in_window), "closed_s": out["closed_s"],
        "tokens_in_window": tokens_in_window, "warm_waves": waves,
        "window_compiles": window_compiles, "reference_s": reference_s,
        "setup_parts_s": setup_parts,
        "longest_step": out["longest_step"], "host": out["host"],
        "checked_requests": len(sample), "checked_tokens": checked,
        "checked_across_window_edge": sum(
            _decode_crossed_window(r, window) for r in sample),
        "checked_prompts_over_window": sum(
            _prompt_over_window(r, window) for r in sample),
        "finished_across_window_edge": sum(
            _decode_crossed_window(r, window) for r in done),
        "logit_range": logit_range,
        "served_token_gaps": served,
        "control_token_gaps": control_served,
        "routing_flips": {"bf16_for_float32": flips,
                          "control_for_float32": flips_control,
                          "of_routings": routed},
        "sender_late_ms": {"p50": percentile(late, 50),
                           "p95": percentile(late, 95),
                           "max": max(late)} if late else None,
        "ttft_ms": {"p50": percentile(ttft, 50), "p95": percentile(ttft, 95),
                    "n": len(ttft)} if ttft else None,
        "tpot_ms": {"p50": percentile(tpot, 50), "p95": percentile(tpot, 95),
                    "n": len(tpot)} if tpot else None,
        "serve_tokens_per_s": tokens_in_window / seconds,
        "ring": {k: v for k, v in counted.items()
                 if k.startswith(("moe_", "kv_"))},
        "flash": _flash_traces(),
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
