"""The last line of standard output: exactly the keys the contract names."""

from __future__ import annotations

import json


def device_block(devices, memory_peak_bytes: int, busy_s=None,
                 window_s=None) -> dict:
    """The device as jax reports it, the peak on the fullest chip, and with
    a trace the busy seconds and the traced window."""
    block = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(memory_peak_bytes),
    }
    if busy_s is not None:
        block["busy_s"] = float(busy_s)
        block["window_s"] = float(window_s)
    return block


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None) -> str:
    """`metrics` maps a name to (value, unit); values go out unrounded."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": float(value), "unit": unit}
                    for name, (value, unit) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = {
            "device_ops": [[str(n), float(s)]
                           for n, s in breakdown["device_ops"][:10]],
            "idle_gaps": [[str(n), float(s)]
                          for n, s in breakdown["idle_gaps"][:10]],
        }
    return json.dumps(line)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of `devices`, as the backend
    reports it (0 where it reports nothing, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
