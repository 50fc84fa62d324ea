"""Serving cells: `inference.server.ContinuousBatcher` over the configured
model, greedy, driven through its public surface (`submit`, `step`,
`enable_progress`/`take_progress`, `stats`) by an open-loop sender in the
same thread: between two steps everything that has come due is handed
over, each request is timed from when it was due, and how late the sender
ran is printed.

The sender forwards at most `feed.max_unadmitted` requests that the
batcher has not yet admitted; the rest wait in the sender's own FIFO, on
the same clock. That is a wave-width limit the batcher lacks (it admits
every waiting request that finds a row in one wave): it bounds the width
of a prefill wave, and with it the set of programs to warm and the memory
one wave needs. It belongs in the program; once the batcher bounds its own
waves the sender hands everything over (PERF.md, open questions).

After the window the batcher and the program's weights are freed, the peak
memory is read, and the plain reference runs once over each of a seeded
sample of finished requests (the longest among them): compared is the
widest gap by which a served token's logit lies below the reference's
best, that every request returned its full budget, and that every token is
in the vocabulary.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmarks.drivers.train import build_model
from benchmarks.lib import clock, traffic
from benchmarks.lib.hostwatch import HostWatch
from benchmarks.lib.manifest import reference_module
from benchmarks.lib.result import memory_peak_bytes
from benchmarks.lib.stats import percentile


def wave_widths(limit: int) -> list:
    """The batcher pads a wave to a power of two: the widths up to
    `limit`."""
    out, w = [], 1
    while w < limit:
        out.append(w)
        w *= 2
    return out + [limit]


def reachable_buckets(buckets, lo: int, hi: int) -> list:
    """The prompt buckets that lengths in [lo, hi] pad up to."""
    buckets = sorted(buckets)
    first = next(i for i, b in enumerate(buckets) if b >= lo)
    last = next(i for i, b in enumerate(buckets) if b >= hi)
    return buckets[first:last + 1]


def warm_up(srv, buckets, prompt_lo: int, prompt_hi: int, max_wave: int,
            scan_depth: int, vocab: int) -> int:
    """Run every (prompt bucket, wave width) the mix can reach once, and
    with the first wave every scan depth: a request with a budget of twice
    the scan depth walks the depth ladder down as it drains. Returns the
    number of waves."""
    rng = np.random.default_rng(0)
    waves, lower = 0, 0
    for bucket in buckets:
        length = max(prompt_lo, min(bucket, prompt_hi), lower + 1)
        for width in wave_widths(max_wave):
            budget = 2 * scan_depth if waves == 0 else 2
            for _ in range(width):
                srv.submit(rng.integers(0, vocab, length, dtype=np.int32),
                           budget)
            srv.run()
            waves += 1
        lower = bucket
    return waves


class _Record:
    __slots__ = ("request", "submitted", "first", "last", "count", "tokens",
                 "done_at")

    def __init__(self, request, submitted):
        self.request, self.submitted = request, submitted
        self.first = self.last = self.done_at = None
        self.count, self.tokens = 0, None


def serve_window(srv, requests: list, seconds: float, max_unadmitted: int,
                 stop_at_close: bool, drain_limit_s: float, tracer=None,
                 trace_from: float = 0.0) -> dict:
    """The open loop. Times are seconds after the window opened."""
    pending = collections.deque(sorted(requests, key=lambda r: r.due_s))
    fifo = collections.deque()
    records, live, unadmitted, tokens_in_window = {}, set(), 0, 0
    longest = {"s": 0.0, "at_s": None}
    watch = HostWatch()
    watch.start()
    t_open = clock.now()
    while True:
        t = clock.now() - t_open
        if tracer is not None:
            if not tracer.started and t >= trace_from:
                tracer.start()
            elif t >= seconds:
                tracer.stop()   # outside the window; it blocks for a while
        if t >= seconds and (stop_at_close or t >= seconds + drain_limit_s):
            break
        while pending and pending[0].due_s <= t:
            fifo.append(pending.popleft())
        while fifo and unadmitted < max_unadmitted:
            request = fifo.popleft()
            rid = srv.submit(request.prompt, request.max_new_tokens)
            records[rid] = _Record(request, t)
            live.add(rid)
            unadmitted += 1
        if srv.idle:
            if not pending:
                break
            time.sleep(max(0.0, min(pending[0].due_s, seconds) - t))
            continue
        t_step = clock.now() - t_open
        finished = srv.step()
        t = clock.now() - t_open
        if t - t_step > longest["s"]:
            longest = {"s": t - t_step, "at_s": t_step}
        for rid in list(live):
            tokens, done = srv.take_progress(rid)
            if tokens:
                rec = records[rid]
                if rec.first is None:
                    rec.first = t
                    unadmitted -= 1
                rec.last = t
                rec.count += len(tokens)
                if t <= seconds:
                    tokens_in_window += len(tokens)
            if done:
                live.discard(rid)
        for rid, tokens in finished:
            records[rid].tokens = np.asarray(tokens)
            records[rid].done_at = t
    if tracer is not None:
        tracer.stop()
    return {"records": records, "not_sent": len(pending) + len(fifo),
            "tokens_in_window": tokens_in_window,
            "closed_s": clock.now() - t_open,
            "longest_step": longest, "host": watch.stop()}


def check_sample(records: list, seed: int, size: int) -> list:
    """A sample of finished requests drawn from the seed, the longest in
    it."""
    if not records:
        return []
    longest = max(range(len(records)), key=lambda i: (
        records[i].request.prompt.size + records[i].tokens.size))
    others = [i for i in range(len(records)) if i != longest]
    rng = np.random.default_rng([int(seed), 5])
    picked = rng.permutation(others)[:max(0, size - 1)].tolist()
    return [records[i] for i in [longest] + picked]


def build_server(cfg: dict, mix: dict, ref, seed: int) -> tuple:
    """(the batcher over the seed's weights with every program the mix can
    reach run once, the number of warm-up waves, when the batcher stood)."""
    from tfde_tpu.inference.server import ContinuousBatcher

    dims, feed = ref.dims_of(cfg), cfg["feed"]
    batcher = dict(cfg["batcher"],
                   prompt_buckets=tuple(cfg["batcher"]["prompt_buckets"]))
    params = ref.to_program_params(ref.make_weights(seed, dims),
                                   dims["n_head"])
    srv = ContinuousBatcher(build_model(cfg), params, **batcher)
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
    dims, batcher, feed = ref.dims_of(cfg), cfg["batcher"], cfg["feed"]
    device = ctx.devices[0]
    vocab = dims["vocab_size"]
    requests = traffic.generate(mix, ctx.seed, ctx.seconds, vocab=vocab)

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
    records = out["records"]
    seconds = ctx.seconds

    # -- what the users saw ---------------------------------------------------
    recs = list(records.values())
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
    # every count the batcher keeps, over the window (and the drain) alone;
    # its ratios are for the readers to form
    counted = {k: after[k] - before[k] for k in after
               if type(after[k]) is int}

    # -- free the program, read the peak, then the reference ------------------
    del srv
    peak = memory_peak_bytes([device])
    t_ref = clock.now()
    limits = cfg["correct"]
    sample = check_sample(done, ctx.seed, limits["sample_requests"])
    weights = ref.make_weights(ctx.seed, dims)
    worst, worst_control, checked, logit_range = 0.0, None, 0, 0.0
    for r in sample:
        gaps = ref.served_token_gaps(weights, r.request.prompt, r.tokens,
                                     dims, batcher["max_len"])
        worst = max(worst, float(gaps["gap"].max()))
        logit_range = max(logit_range, gaps["range"])
        checked += int(r.tokens.size)
        if ctx.control:
            lower = ref.served_token_gaps(
                weights, r.request.prompt, r.tokens, dims,
                batcher["max_len"], precision=cfg["control_precision"])
            below = ref.gaps_of_choices(
                weights, r.request.prompt, r.tokens, lower["argmax"], dims,
                batcher["max_len"])
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
                  "serve_tokens_per_s": tokens_in_window / seconds}
    # medians: at the 112 requests a window holds, a 95th percentile rests
    # on six of them and swings 6-8 % from run to run (PERF.md, section 2)
    if ttft:
        end_to_end["ttft_p50_ms"] = percentile(ttft, 50)
    if tpot:
        end_to_end["tpot_p50_ms"] = percentile(tpot, 50)
    notes = {
        "requests_generated": len(requests), "sent": len(recs),
        "not_sent": out["not_sent"], "finished": len(done),
        "finished_in_window": len(in_window), "closed_s": out["closed_s"],
        "tokens_in_window": tokens_in_window, "warm_waves": waves,
        "window_compiles": window_compiles, "reference_s": reference_s,
        "setup_parts_s": setup_parts,
        "longest_step": out["longest_step"], "host": out["host"],
        "checked_requests": len(sample), "checked_tokens": checked,
        "logit_range": logit_range,
        "sender_late_ms": {"p50": percentile(late, 50),
                           "p95": percentile(late, 95),
                           "max": max(late)} if late else None,
        "ttft_ms": {"p50": percentile(ttft, 50), "p95": percentile(ttft, 95),
                    "n": len(ttft)} if ttft else None,
        "tpot_ms": {"p50": percentile(tpot, 50), "p95": percentile(tpot, 95),
                    "n": len(tpot)} if tpot else None,
        "serve_tokens_per_s": tokens_in_window / seconds,
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
