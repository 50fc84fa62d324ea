"""What several per-layer readers share. A reader is a file of its own
under `benchmarks/layer_metrics/` with one function, `read(obs)`; `obs`
holds what the driver counted (`counters`, `step_s`, `tokens`, ...), the
reduced trace (`trace`), the device kind and the configuration. A reader
that finds nothing to read returns None, and the metric is left out."""

from __future__ import annotations

from benchmarks.lib import flops
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.stats import median


def counter(obs: dict, name: str):
    return obs.get("counters", {}).get(name)


def ratio(obs: dict, above: str, below: str):
    a, b = counter(obs, above), counter(obs, below)
    if a is None or not b:
        return None
    return a / b


def device_idle_pct(obs: dict):
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def step_ms(obs: dict):
    if not obs.get("step_s"):
        return None
    return 1e3 * median(obs["step_s"])


def mfu_pct(obs: dict):
    """The operations a step's tokens need (forward and backward, nothing
    recomputed) over the median step's time, chips and the published peak.
    From the step time and not the window, which in a traced run also
    holds the profiler's start and stop."""
    if not obs.get("step_s") or not obs.get("tokens_per_step"):
        return None
    peak = peaks_for(obs["device_kind"])["bf16_flops"]
    rate = obs["tokens_per_step"] / median(obs["step_s"])
    return 100.0 * rate * obs["train_flops_per_token"] / (obs["chips"] * peak)


def collective_exposed_pct(obs: dict):
    trace = obs.get("trace")
    if not trace or not trace["collective_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]


def flash_fwd_roofline(obs: dict):
    """The least time the chip could take for one causal flash forward
    (the larger of FLOPs over peak and bytes over bandwidth; it is the
    FLOPs) over the mean time of the Mosaic custom calls in the trace."""
    trace, shape = obs.get("trace"), obs.get("flash")
    if not trace or not shape or not trace["custom_calls"]:
        return None
    peaks = peaks_for(obs["device_kind"])
    args = (shape["batch_per_chip"], shape["heads"], shape["seq"],
            shape["head_dim"])
    least = max(flops.flash_forward_flops(*args) / peaks["bf16_flops"],
                flops.flash_forward_bytes(*args) / peaks["hbm_bytes_per_s"])
    per_call = trace["custom_call_s"] / trace["custom_calls"]
    return 100.0 * least / per_call
