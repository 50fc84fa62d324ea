"""From a profiler trace (`.xplane.pb`) to numbers, with nothing but jax.

What it computes, and no more: device busy seconds (the union of the
intervals in which an operation ran) and the window; the device operations
that took most time; the longest idle gaps, each named by the host
annotation that covers it; collective operations' time and the part of it
with no compute on that device; Mosaic custom calls' summed time.

What a v5e trace looks like (read by hand, PR 23): a device plane
`/device:TPU:<n>` carries the lines `Steps`, `XLA Modules` (one event per
program run), `XLA Ops` (one event per executed HLO instruction, those of a
`while` body nested inside the `while`'s own event) and `Async XLA Ops`
(copy-start/-done and the like, overlapping the former). An event on
`XLA Ops` is named by the instruction's whole text,
`%attn.24 = (bf16[2,16,4096,64]{...}, f32[...]) custom-call(...)`: the name
before ` = `, the opcode after the result's type. A Pallas (Mosaic) kernel
is a `custom-call` whose name is the flax scope it was called in (`attn`).
Host threads are lines of the plane `/host:CPU`; a
`jax.profiler.TraceAnnotation` is an event on the line `python`, under its
own name, beside jax's own (`PjitFunction(step)`, `np.asarray(jax.Array)`).
Host and device events share one clock.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
# a Pallas (Mosaic) kernel: XLA's own custom calls (buffer allocation and
# the like) are many and tiny, and carry other targets
MOSAIC = re.compile(r"\bcustom-call\(.*custom_call_target=\"tpu_custom_call\"")
WINDOW_ANNOTATION = "bench/window"
# host events that wrap the whole window or the interpreter itself say
# nothing about what the host was doing in a gap
_NOT_AN_OWNER = re.compile(r"^(\$|bench/window$|<module>|Thread\b)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith((".textproto", ".txt")):
        with open(path, encoding="utf-8") as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def parse_op(text: str) -> tuple:
    """(name, opcode) of an `XLA Ops` event. `%n = type opcode(...)` gives
    (`n`, `opcode`); a bare name such as `all-reduce.3` gives itself and
    its stem."""
    if " = " not in text:
        name = text.lstrip("%")
        return name, re.sub(r"[.\d]+$", "", name)
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.index(" "):] if " " in rest else rest
    m = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest)
    return name.lstrip("%"), (m.group(1) if m else "?")


def _events(line) -> list:
    """[(name, start_ns, end_ns)] of one line, by start (longest first
    among equal starts, so that an enclosing event precedes its inner
    ones)."""
    out = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
           for e in line.events]
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def _outermost(events: list) -> list:
    """Drop events nested inside an earlier one (a `while` body's
    instructions), so that durations can be summed by name."""
    out, end = [], float("-inf")
    for name, a, b in events:
        if a >= end:
            out.append((name, a, b))
            end = b
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [(start, end)]."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _host_events(profile) -> list:
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend(_events(line))
    return out


_BUCKET_NS = 1e6
_SLIVER_NS = 5e3


class _HostIndex:
    """Host events by the milliseconds they cover, so that a gap finds the
    events around it without a pass over all of them."""

    def __init__(self, host: list):
        self.buckets = {}
        for event in host:
            name, a, b = event
            if _NOT_AN_OWNER.search(name):
                continue
            for k in range(int(a // _BUCKET_NS), int(b // _BUCKET_NS) + 1):
                self.buckets.setdefault(k, []).append(event)

    def owner(self, lo: float, hi: float) -> str:
        """The shortest host event covering the gap's middle: what the
        host was doing while the device waited."""
        if hi - lo < _SLIVER_NS:
            return "(under 5 us, between operations)"
        mid, best, best_len = (lo + hi) / 2.0, None, None
        for name, a, b in self.buckets.get(int(mid // _BUCKET_NS), ()):
            if a <= mid <= b and (best_len is None or b - a < best_len):
                best, best_len = name, b - a
        return best or "(no host annotation)"


def reduce(profile, chips: int = None) -> dict:
    """All the numbers, in seconds, averaged over the device planes that
    ran an operation (the first `chips` of them by device number)."""
    host = _host_events(profile)
    owners = _HostIndex(host)
    window = [(a, b) for name, a, b in host if name == WINDOW_ANNOTATION]
    planes = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops, overlapped = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend(_events(line))
            elif line.name == ASYNC_LINE:
                overlapped.extend(_events(line))
        if ops:
            ops.sort(key=lambda e: (e[1], -e[2]))
            planes.append((int(m.group(1)), ops, overlapped))
    planes.sort(key=lambda p: p[0])
    if chips is not None:
        planes = planes[:chips]
    if not planes:
        raise ValueError("the trace holds no device operation")
    if window:
        lo, hi = window[0]
        if not any(_clip([(a, b) for _, a, b in ops], lo, hi)
                   for _, ops, _ in planes):
            window = []     # host and device clocks do not line up here
    if not window:
        lo = min(a for _, ops, _ in planes for _, a, _b in ops)
        hi = max(b for _, ops, _ in planes for _, _a, b in ops)

    def inside(events):
        return [(name, max(a, lo), min(b, hi)) for name, a, b in events
                if min(b, hi) > max(a, lo)]

    n = len(planes)
    busy = coll = exposed = custom = 0.0
    custom_calls = 0
    by_name, gaps = {}, {}
    for index, (_, ops, overlapped) in enumerate(planes):
        kept = _outermost(inside(ops))
        outer = [(parse_op(text), a, b) for text, a, b in kept]
        for text, a, b in kept:
            if MOSAIC.search(text):
                custom += (b - a) / n
                custom_calls += index == 0
        merged = _union([(a, b) for _, a, b in outer])
        busy += _length(merged) / n
        comp_iv = _union([(a, b) for (_, op), a, b in outer
                          if not COLLECTIVE.match(op)])
        coll_iv = _union(
            [(a, b) for (_, op), a, b in outer if COLLECTIVE.match(op)]
            + [(a, b) for text, a, b in inside(overlapped)
               if COLLECTIVE.match(parse_op(text)[1])])
        coll += _length(coll_iv) / n
        exposed += (_length(coll_iv) - _overlap(coll_iv, comp_iv)) / n
        for (name, op), a, b in outer:
            label = name if name.startswith(op) else f"{name} {op}"
            by_name[label] = by_name.get(label, 0.0) + (b - a) / n
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                owner = owners.owner(a, b)
                gaps[owner] = gaps.get(owner, 0.0) + (b - a) / n
    ns = 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "collective_s": coll * ns,
        "collective_exposed_s": exposed * ns,
        "custom_call_s": custom * ns,
        "custom_calls": custom_calls,
        "device_ops": [[name[:80], t * ns] for name, t in top[:10]],
        "idle_gaps": [[name[:80], t * ns] for name, t in top_gaps[:10]],
    }
