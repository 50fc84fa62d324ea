"""What the `setup_*` readers share: the program's own set-up ledger
(`tfde_tpu.observability.recompile.setup()`: tracing, lowering, backend
compiles and cache reads in integer nanoseconds, by site and program), read
in the process that ran the cell. A program without that ledger (the parent
of PR 37 and everything before it) reads nothing: None, and nothing is
raised, as `ring_readers.py` does for a program that counts no ring.

The readers sum the SITED programs: those that compiled inside a watch of
the recompile sentinel (every prefill wave and decode scan of a serve cell)
or under a name a site claims (the jitted train step and the init program).
The plain reference's compiles after the window and the eager calls around
the cell are unsited, so no cut by time is needed.

The first read in a process prints one line, `[setup] {...}`, before the
result line: every sited program in the order it began, with its stages
in seconds, whether the persistent cache held it and the wall time of the
watched call it compiled in. That line is the per-program table a builder
reads on the chip; the driver reads only the last line.
"""

from __future__ import annotations

import json

_STAGE_SECONDS = (("trace_s", "trace_ns"), ("lower_s", "lower_ns"),
                  ("backend_s", "backend_ns"), ("cache_read_s",
                                                "cache_read_ns"))
_said = False


def ledger():
    """The program's set-up ledger, or None where it keeps none."""
    try:
        from tfde_tpu.observability import recompile
    except ImportError:
        return None
    setup = getattr(recompile, "setup", None)
    if setup is None:
        return None
    out = setup()
    _say_once(out)
    return out


def sited(obs: dict, key: str):
    """One integer of the sited totals; `obs` is what every reader is
    handed and holds nothing of this (the ledger is the process's)."""
    del obs
    setup = ledger()
    return None if setup is None else setup["sited"][key]


def sited_seconds(obs: dict, key: str):
    ns = sited(obs, key)
    return None if ns is None else ns * 1e-9


def table(setup: dict) -> dict:
    """The `[setup]` line's payload: sited and unsited totals in seconds
    and counts, and one row a sited program in the order they began."""
    def seconds(totals):
        out = {s: totals[ns] * 1e-9 for s, ns in _STAGE_SECONDS}
        out["first_calls_s"] = totals["first_call_ns"] * 1e-9
        for k in ("programs", "cache_misses", "nested_traces"):
            out[k] = totals[k]
        return out

    rows = []
    for name, snap in setup["sites"].items():
        for ep in snap["episodes"]:
            row = {"site": name, "program": ep["fun_name"],
                   "fingerprint": ep["fingerprint"], "t0_ns": ep["t0_ns"]}
            row.update((s, ep[ns] * 1e-9) for s, ns in _STAGE_SECONDS)
            row["cache_hit"] = ep["cache_hit"]
            row["nested_traces"] = ep["nested_traces"]
            row["compiles"] = ep["compiles"]
            row["wall_s"] = (None if ep["wall_ns"] is None
                             else ep["wall_ns"] * 1e-9)
            rows.append(row)
    rows.sort(key=lambda r: r.pop("t0_ns"))
    return {"sited": seconds(setup["sited"]),
            "unsited": seconds(setup["unsited"]), "programs": rows}


def _say_once(setup: dict) -> None:
    global _said
    if _said:
        return
    _said = True
    print("[setup] " + json.dumps(table(setup), default=str), flush=True)
