"""The knee of a serving configuration, found once by a sweep on the chip.

    python benchmarks/sweep.py --config gpt2-large-serve-1k \
        --mix chat-poisson-r80 --rates 2,3,4,5,6,8 --seconds 20 --seed 5

One process: the batcher is built and warmed once, then each rate is
offered for `--seconds` with the mix's lengths and followed to completion,
the k-th window with the seed `--seed` + k (the same rate several times
over gives the spread from window to window at that length). A rate is
sustained when the backlog does not grow: what was still waiting or running
when the window closed is no more than a window at a lower rate left. The
cells' rates are then written into their traffic files by hand. Every
request's due time, TTFT and TPOT go to `chiprun_out/sweep.jsonl`, one line
a window. It is a tool for the PR that defines or re-bases a serving cell,
not a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--mix", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=5)
    args = parser.parse_args(argv)

    from benchmarks import run as runner
    from benchmarks.lib import manifest as manifest_lib
    from benchmarks.lib import traffic
    from benchmarks.lib.stats import percentile

    from benchmarks.lib.compile_meter import CompileMeter

    manifest = manifest_lib.Manifest(ROOT)
    runner.place_compile_cache(ROOT)
    meter = CompileMeter()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("benchmarks/sweep.py needs a TPU; nothing was run")
    serve = manifest_lib.driver_module("serve")
    cfg = manifest.config(args.config)
    mix = manifest.traffic(args.mix)
    ref = manifest_lib.reference_module(cfg["reference"])
    dims, feed = ref.dims_of(cfg), cfg["feed"]
    t0 = time.perf_counter()
    srv, _, _ = serve.build_server(cfg, mix, ref, args.seed)
    print(f"[sweep] set-up {time.perf_counter() - t0:.1f}s "
          f"{json.dumps(meter.snapshot())}", flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    per_request = open(os.path.join(ROOT, "chiprun_out", "sweep.jsonl"), "w")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        requests = traffic.generate(dict(mix, rate_per_s=rate), args.seed + k,
                                    args.seconds,
                                    vocab=dims["vocab_size"])
        before = dict(srv.stats())
        out = serve.serve_window(srv, requests, args.seconds,
                                 feed["max_unadmitted"], False, 120.0)
        after = dict(srv.stats())
        recs = list(out["records"].values())
        ttft = [(r.first - r.request.due_s) * 1e3 for r in recs
                if r.first is not None]
        tpot = [(r.last - r.first) * 1e3 / (r.count - 1) for r in recs
                if r.count > 1]
        per_request.write(json.dumps({
            "rate_per_s": rate, "seed": args.seed + k,
            "longest_step": out["longest_step"], "host": out["host"],
            "requests": [[r.request.due_s, r.first, r.last, r.count]
                         for r in recs]}) + "\n")
        per_request.flush()
        open_at_close = sum(1 for r in recs if r.done_at is None
                            or r.done_at > args.seconds)
        waiting_at_close = sum(1 for r in recs if r.first is None
                               or r.first > args.seconds)
        print("[sweep] " + json.dumps({
            "rate_per_s": rate, "seed": args.seed + k,
            "requests": len(requests),
            "longest_step": out["longest_step"], "host": out["host"],
            "open_at_close": open_at_close,
            "not_admitted_at_close": waiting_at_close,
            "drain_s": out["closed_s"] - args.seconds,
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "tpot_p50_ms": percentile(tpot, 50),
            "tpot_p95_ms": percentile(tpot, 95),
            "tokens_per_s_in_window": out["tokens_in_window"] / args.seconds,
            "rows_per_tick": (after["generated"] - before["generated"])
            / max(1, after["rounds"] - before["rounds"]),
        }), flush=True)
    per_request.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
