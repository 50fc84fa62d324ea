"""Serving cell of a decoder that mixes positions with a gated delta rule in
three layers of four and with output-gated grouped-query attention in the
fourth, every layer followed by routed SwiGLU experts beside a
sigmoid-gated shared expert (Qwen3-Next-80B-A3B): `inference.server.
ContinuousBatcher` over `models.gpt.GPT(mixers=('gated_delta', ...,
'attention'), gdn=..., attn_output_gate=True)` built from the
configuration's published keys, the held share of the experts and of the
vocabulary, bfloat16 weights, greedy, driven through the batcher's public
surface by `drivers/serve.py`'s open-loop sender (`serve_window`, with the
sender's cap of `feed.max_unadmitted`) after `serve.py`'s warm-up of every
(prompt bucket, wave width) the mix can reach. Under the batcher a
delta-rule layer keeps one float32 matrix per value head and a convolution
tail a row, whatever the row's length, and the attention layer a K/V cell
a position: a wave solves the rule a chunk of 64 positions at a time, a
tick takes its one step.

What differs from `serve.py`, which builds GPT-2 by GPT-2's key names and
may not be edited (PERF.md, open questions; ROADMAP Speed 12g: the
accounting of its `run` that the imports leave is copied here, as the four
drivers before this one do):
- the model and its weights (`build_model`, `build_server`): the program's
  parameter tree is the reference's own bfloat16 arrays re-nested, so the
  two copies of 7.4 GB never exist side by side;
- each checked request is padded to the next multiple of 2,048 positions
  (`PAD_TO`), not to `max_len`;
- what is compared is the MEAN of the checked served tokens' gaps and a
  count of gaps no near-tie explains (`gap_summary` of
  `serve_smallthinker.py`, as `serve_sarvam.py` compares them); the
  `[notes]` line carries the whole summary, the 99th percentile in it;
- under `--control 1` three controls, each the reference's own first
  choices under a fault, measured against the sound reference: every
  product in fp8 (`control_precision`), the delta rule without what it
  read back (u_t = beta_t v_t) and the attention without its output gate
  (`CONTROL_DROPS`);
- the `[notes]` line carries `routing_flips`, as `serve_granite.py`'s;
- every seed offers the same requests at the same times (`offered`);
- the counters of the window (`moe_*`, `gdn_*`, `kv_*`).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.drivers.serve import (check_sample, reachable_buckets,
                                      serve_window, warm_up)
# the one order of arrival (`offered`, `ARRIVALS_SEED`), the padding of a
# checked request (`PAD_TO`), the summary of the gaps (`gap_summary`) and
# the flash forward's counters are that driver's own, unchanged: names of
# this module too
from benchmarks.drivers.serve_smallthinker import (ARRIVALS_SEED,  # noqa: F401
                                                   PAD_TO, _flash_traces,
                                                   gap_summary, offered)
from benchmarks.lib import clock
from benchmarks.lib.manifest import reference_module
from benchmarks.lib.result import memory_peak_bytes
from benchmarks.lib.stats import percentile

#: the terms the dropped-term controls leave out of the reference's
#: arithmetic (`reference/qwen3_next.py::DROPS`)
CONTROL_DROPS = ("delta_term", "output_gate")


def build_model(cfg: dict):
    """`models.gpt.GPT` from the configuration file's published keys: the
    existing constructor, no preset. The router stays as wide as the
    published experts; the layer is told which of them it holds."""
    import jax.numpy as jnp

    from tfde_tpu.models.gpt import GPT
    from tfde_tpu.ops.gated_delta import GatedDeltaShape

    if (cfg["rope_scaling"] is not None or cfg["hidden_act"] != "silu"
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1
            or cfg["use_sliding_window"] or not cfg["norm_topk_prob"]):
        raise ValueError(
            "this driver builds unscaled rotary frequencies, SwiGLU, "
            "experts in every layer with the chosen gates renormalised, "
            "and no sliding window")
    extra = dict(cfg.get("constructor", {}))
    extra["dtype"] = getattr(jnp, extra.get("dtype", "bfloat16"))
    depth, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return GPT(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        depth=depth, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mixers=tuple("attention" if (layer + 1) % every == 0
                     else "gated_delta" for layer in range(depth)),
        gdn=GatedDeltaShape(
            key_heads=cfg["linear_num_key_heads"],
            value_heads=cfg["linear_num_value_heads"],
            key_dim=cfg["linear_key_head_dim"],
            value_dim=cfg["linear_value_head_dim"],
            conv=cfg["linear_conv_kernel_dim"]),
        attn_output_gate=True, qk_norm=True,
        max_position=cfg["max_position_embeddings"], position="rope",
        rope_theta=float(cfg["rope_theta"]),
        rope_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        norm="rms", norm_unit_offset=True, ln_eps=cfg["rms_norm_eps"],
        use_bias=False, tie_embeddings=cfg["tie_word_embeddings"],
        mlp_act="swiglu", mlp_dim=cfg["moe_intermediate_size"],
        mlps=("experts",) * depth,
        num_experts=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_capacity_factor=None, moe_normalize_topk=True,
        moe_shared_expert_dim=cfg["shared_expert_intermediate_size"],
        moe_shared_expert_gated=True,
        moe_held_experts=tuple(cfg["deployment_share"]["experts"]), **extra)


def build_server(cfg: dict, mix: dict, ref, seed: int) -> tuple:
    """(the batcher over the seed's weights in bfloat16 with every program
    the mix can reach run once, the number of warm-up waves, when the
    batcher stood)."""
    from tfde_tpu.inference.server import ContinuousBatcher

    dims, feed = ref.dims_of(cfg), cfg["feed"]
    batcher = dict(cfg["batcher"],
                   prompt_buckets=tuple(cfg["batcher"]["prompt_buckets"]))
    model = build_model(cfg)   # first: a program without the mixer stops here
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
    sample = check_sample(done, ctx.seed, limits["sample_requests"])
    weights = ref.make_weights(ctx.seed, dims)
    sound, lowered, checked, logit_range = [], [], 0, 0.0
    dropped = {drop: [] for drop in CONTROL_DROPS}
    flips, flips_control, routed = 0, None, 0
    for r in sample:
        pad_to = -(-(r.request.prompt.size + r.tokens.size) // PAD_TO) * PAD_TO
        args = (weights, r.request.prompt, r.tokens, dims, pad_to)
        # under the controls the sound logits are kept: each control's
        # choices are then read off them without another forward
        gaps = ref.served_token_gaps(*args, keep_logits=bool(ctx.control))
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
            choices = functools.partial(
                ref.gaps_of_choices, weights, r.request.prompt, r.tokens,
                dims=dims, pad_to=pad_to, logits=gaps["logits"])
            lower = ref.served_token_gaps(
                *args, precision=cfg["control_precision"])
            lowered.append(choices(choices=lower["argmax"]))
            if first:
                flips_control = ref.routing_flips(gaps["routes"],
                                                  lower["routes"])
            for drop in CONTROL_DROPS:
                without = ref.served_token_gaps(*args, drop=drop)
                dropped[drop].append(choices(choices=without["argmax"]))
    del weights
    reference_s = clock.now() - t_ref
    flat = (np.concatenate([r.tokens for r in done]) if done
            else np.zeros(0, np.int64))
    served = gap_summary(sound, limits["far_off_gap"])
    control_served = (gap_summary(lowered, limits["far_off_gap"])
                      if ctx.control else None)
    control_dropped = ({drop: gap_summary(gaps, limits["far_off_gap"])
                        for drop, gaps in dropped.items()}
                       if ctx.control else None)
    compared = [
        ("served_token_gap_mean", served["mean"] if checked else 0.0,
         limits["served_token_gap_mean"]),
        ("served_tokens_far_off", float(served["far_off"]), 0.0),
        ("requests_returned_short", float(len(short)), 0.0),
        ("tokens_out_of_vocabulary",
         float(((flat < 0) | (flat >= vocab)).sum()), 0.0),
        ("sample_is_empty", 0.0 if checked else 1.0, 0.0),
    ]
    control = ([("served_token_gap_mean", control_served["mean"],
                 limits["served_token_gap_mean"])]
               + [(f"served_token_gap_mean.without_{drop}",
                   summary["mean"], limits["served_token_gap_mean"])
                  for drop, summary in control_dropped.items()]
               if ctx.control else None)

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
        "checked_longest_prompt": max(
            (r.request.prompt.size for r in sample), default=0),
        "logit_range": logit_range,
        "served_token_gaps": served,
        "control_token_gaps": control_served,
        "dropped_term_token_gaps": control_dropped,
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
        "delta": {k: v for k, v in counted.items()
                  if k.startswith(("moe_", "gdn_", "kv_"))},
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
