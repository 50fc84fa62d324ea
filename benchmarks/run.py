"""One run of one cell: a new process that loads, warms up, measures for
`--seconds`, checks what the timed path produced against the plain
reference, prints the result as the last line of standard output, and
exits.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With `--trace 0` the last line carries the cell's end-to-end metrics and no
profile is taken; with `--trace 1` a few seconds of the window are profiled
and it carries the per-layer metrics and a breakdown. Anything but a TPU
with the chips the cell asks for ends the process non-zero with no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import manifest as manifest_lib   # noqa: E402
from benchmarks.lib import result                     # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    meter: object
    t_start: float
    tracer: object = None       # a WindowTracer in the traced run
    control: bool = False       # also compute the control's numbers


def place_compile_cache(root: str) -> str:
    """jax's persistent cache at a fixed place inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), and every program kept: the
    sub-second programs of a serving start are most of its compile time."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_chips(cell: dict) -> list:
    """The devices jax reports, if they are TPU chips and enough of them;
    otherwise nothing runs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit(
            f"benchmarks/run.py: cell {cell['name']!r} needs {cell['chips']} "
            f"TPU chip(s); jax found platform={devices[0].platform!r} "
            f"kind={devices[0].device_kind!r} count={len(devices)} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
            f"Nothing was run.")
    return devices


def run_cell(manifest, workload: str, seed: int, seconds: float, tracer,
             devices: list, t_start: float, control=False,
             out=sys.stdout) -> dict:
    """Everything after the look for a chip: drive the cell, judge it,
    print the lines. `tracer` is a WindowTracer in the traced run and None
    otherwise. Returns the parsed last line."""
    from benchmarks.lib.compile_meter import CompileMeter

    cell = manifest.cell(workload)
    config = manifest.config(cell["config"])
    driver = manifest_lib.driver_module(config["driver"])
    ctx = Context(cell=cell, config=config,
                  traffic=manifest.traffic(cell["traffic"]), seed=seed,
                  seconds=seconds, devices=devices, meter=CompileMeter(),
                  t_start=t_start, tracer=tracer, control=control)
    outcome = driver.run(ctx)

    def say(tag, payload):
        print(f"[{tag}] {json.dumps(payload, default=float)}", file=out,
              flush=True)

    say("notes", outcome["notes"])
    say("end_to_end", outcome["end_to_end"])
    correct = True
    for name, value, limit in outcome["compared"]:
        ok = value <= limit
        correct = correct and ok
        say("compared", {"name": name, "value": value, "limit": limit,
                         "ok": ok})
    if outcome.get("control"):
        for name, value, limit in outcome["control"]:
            say("control", {"name": name, "value": value, "limit": limit,
                            "fails_as_it_must": not value <= limit})

    used = devices[:cell["chips"]]
    breakdown = None
    if tracer is not None:
        summary = tracer.summary(cell["chips"])
        say("trace", summary)
        observed = dict(outcome["observed"], trace=summary,
                        device_kind=used[0].device_kind, config=config)
        metrics = {}
        for m in manifest.cell_metrics(workload, "per_layer"):
            value = manifest.metric_reader(m["name"])(observed)
            if value is None:
                # the manifest lists the metric for this cell, and the
                # driver's check refuses a traced line that lacks it
                raise RuntimeError(
                    f"cell {workload!r}: the reader of {m['name']!r} found "
                    f"nothing to read; counters were "
                    f"{sorted(observed.get('counters', {}))}")
            metrics[m["name"]] = (value, m["unit"])
        device = result.device_block(
            used, outcome["memory_peak_bytes"], summary["busy_s"],
            summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {}
        for m in manifest.cell_metrics(workload, "end_to_end"):
            if m["name"] not in outcome["end_to_end"]:
                raise RuntimeError(
                    f"cell {workload!r} did not measure {m['name']!r}")
            metrics[m["name"]] = (outcome["end_to_end"][m["name"]], m["unit"])
        device = result.device_block(used, outcome["memory_peak_bytes"])
    line = result.result_line(correct, outcome["attempted"],
                              outcome["failed"], metrics, device, breakdown)
    print(line, file=out, flush=True)
    return json.loads(line)


def _span_switch(config: dict):
    """The program's own switch for host spans in a profile, named by the
    configuration as `module:function`."""
    name = config.get("span_switch")
    if not name:
        return None
    import importlib

    module, function = name.split(":")
    return getattr(importlib.import_module(module), function)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also print the control's numbers (not a "
                             "benchmark run)")
    args = parser.parse_args(argv)

    manifest = manifest_lib.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    cache = place_compile_cache(ROOT)
    devices = find_chips(cell)
    print(f"[start] cell={args.workload} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} compile_cache={cache}",
          flush=True)
    tracer = None
    if args.trace:
        from benchmarks.lib.tracing import WindowTracer

        tracer = WindowTracer(
            os.path.join(ROOT, ".bench_scratch", "trace"),
            _span_switch(manifest.config(cell["config"])))
    run_cell(manifest, args.workload, args.seed, args.seconds, tracer,
             devices, T_START, control=bool(args.control))
    return 0


if __name__ == "__main__":
    sys.exit(main())
