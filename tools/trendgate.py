"""Perf trendline gate: pin the committed BENCH history against a
direction/threshold policy so a hardware capture that regresses a
headline metric fails tier-1 the same way a compile-count or peak-bytes
regression does (the hardware sibling of tools/memgate.py — ROADMAP
item 6).

The committed ``BENCH_*.json`` files are heterogeneous: driver wrapper
records (``{"n", "cmd", "rc", "tail", "parsed"}`` — ``parsed`` may be
null and ``tail`` may hold a truncated payload), flat builder artifacts,
failed rounds carrying an ``"error"``, and one capture with plausible-
looking numbers but no calibration anchor. This gate reads them ALL, in
round order, and sorts each into:

- **comparable**: parses, has no error, ``platform == "tpu"`` and a
  calibration anchor at >= 0.8 of chip peak (the trust rule
  — a capture that cannot vouch for its own clock cannot vouch for a
  trend either);
- **skipped-with-reason**: everything else, listed in TREND.md so a
  burned round is visible instead of silently absent.

``--check`` compares the latest comparable capture against the previous
one, metric by metric, under tools/trendgate_policy.json: higher-is-
better for mfu/throughput, lower-is-better for step/latency/compile
metrics, per-metric slack, and ``gate: false`` for informational rows
(e.g. ``flash_speedup``, whose reference implementation legitimately got
faster between rounds). A gated metric moving past its slack in the
wrong direction — or disappearing from the latest capture — fails
loudly.

Modes:

  python tools/trendgate.py --check    # gate the committed history;
                                       # exit 1 on regression (tier1.sh)
  python tools/trendgate.py --update   # rewrite TREND.md (commit it)
  python tools/trendgate.py --print    # dump the trend table as JSON

Injection self-test: with TFDE_TRENDGATE_INJECT=1 a synthetic latest
round is appended with every gated metric pushed past twice its slack in
the regressing direction — --check must fail (tools/tier1.sh runs this
to prove the gate bites, like the memgate/lintgate drills).

A deliberate perf change re-baselines by committing the new BENCH
capture and regenerating the report::

  python tools/trendgate.py --update

(adjust the metric's slack in tools/trendgate_policy.json when the new
level is intended).
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLICY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "trendgate_policy.json")
REPORT = os.path.join(REPO, "TREND.md")
ENV_INJECT = "TFDE_TRENDGATE_INJECT"

_ROUND = re.compile(r"BENCH_(builder_)?r(\d+)\.json$")
#: trend columns rendered in TREND.md (older comparable rounds elide)
MAX_COLUMNS = 6


# -- capture parsing ----------------------------------------------------------
def _salvage_tail(tail: str):
    """Last line of a wrapper's captured tail that parses as a JSON
    object — the driver emits one cumulative line per config, so a
    timed-out attempt's tail may still hold a full payload. A HEAD-
    truncated tail (BENCH_r05) fails here and the round skips."""
    for ln in reversed((tail or "").strip().splitlines()):
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            return obj
    return None


def parse_capture(path: str, trust: dict) -> dict:
    """One BENCH file -> {"file", "round", "source", "metrics"|None,
    "skip"|None, "meta", "raw"}. Never raises: a malformed committed
    capture is a skip reason, not a gate crash."""
    name = os.path.basename(path)
    m = _ROUND.search(name)
    cap = {
        "file": name,
        "round": int(m.group(2)) if m else 0,
        "source": "builder" if (m and m.group(1)) or "builder" in name
        else "driver",
        "metrics": None,
        "skip": None,
        "meta": None,
    }

    def skip(reason):
        cap["skip"] = reason
        return cap

    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return skip(f"unparseable file: {e}")
    if not isinstance(doc, dict):
        return skip("not a JSON object")

    if "parsed" in doc and "cmd" in doc:  # driver wrapper record
        payload = doc.get("parsed")
        if payload is None:
            payload = _salvage_tail(doc.get("tail", ""))
        if payload is None:
            return skip(f"no parseable payload (driver rc={doc.get('rc')}; "
                        f"tail holds no complete JSON line)")
    else:
        payload = doc
    if not isinstance(payload, dict):
        return skip("payload is not a JSON object")
    cap["meta"] = payload.get("bench_meta")

    if payload.get("error"):
        return skip(f"failed capture: {payload['error']}")
    want_platform = trust.get("platform", "tpu")
    if payload.get("platform") != want_platform:
        return skip(f"platform {payload.get('platform')!r} != "
                    f"{want_platform!r}")
    calib = payload.get("calib_frac_of_peak")
    if calib is None:
        return skip("no calibration anchor (calib_frac_of_peak absent) — "
                    "untrusted clock")
    floor = float(trust.get("min_calib_frac_of_peak", 0.8))
    try:
        calib = float(calib)
    except (TypeError, ValueError):
        return skip(f"calibration anchor not a number: {calib!r}")
    if calib < floor:
        return skip(f"calib_frac_of_peak {calib} below trust floor {floor}")
    if not float(payload.get("value", 0.0) or 0.0) > 0.0:
        return skip("headline value is zero/absent")

    cap["metrics"] = {
        k: float(v) for k, v in payload.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }
    return cap


def load_history(repo: str, trust: dict) -> list:
    """Every BENCH_*.json parsed, in round order (builder captures sort
    before the driver record of the same round — the driver line may be
    a replay OF the builder artifact)."""
    caps = [parse_capture(p, trust)
            for p in glob.glob(os.path.join(repo, "BENCH_*.json"))]
    caps.sort(key=lambda c: (c["round"],
                             0 if c["source"] == "builder" else 1,
                             c["file"]))
    return caps


def inject_capture(last: dict, policy: dict) -> dict:
    """Synthetic regressed round for the TFDE_TRENDGATE_INJECT drill:
    every gated metric present in the latest comparable capture is
    pushed past TWICE its slack in the regressing direction."""
    metrics = dict(last["metrics"])
    default_slack = float(policy.get("default_slack", 0.10))
    for name, mp in policy.get("metrics", {}).items():
        if not mp.get("gate", True) or name not in metrics:
            continue
        slack = float(mp.get("slack", default_slack))
        if mp.get("direction", "higher") == "higher":
            metrics[name] *= (1.0 - 2.0 * slack)
        else:
            metrics[name] *= (1.0 + 2.0 * slack)
    return {
        "file": "INJECTED(seeded regression)",
        "round": last["round"] + 1,
        "source": "inject",
        "metrics": metrics,
        "skip": None,
        "meta": {"note": "synthetic TFDE_TRENDGATE_INJECT round"},
    }


# -- trend + gate -------------------------------------------------------------
def comparable(caps: list) -> list:
    return [c for c in caps if c["skip"] is None]


def build_trend(caps: list, policy: dict) -> dict:
    """{"rows": [per-policy-metric], "skipped": [...], "pair": (prev,
    last) filenames or None} — the --print payload and the TREND.md
    source."""
    comp = comparable(caps)
    default_slack = float(policy.get("default_slack", 0.10))
    rows = []
    prev = comp[-2] if len(comp) >= 2 else None
    last = comp[-1] if comp else None
    for name in sorted(policy.get("metrics", {})):
        mp = policy["metrics"][name]
        direction = mp.get("direction", "higher")
        slack = float(mp.get("slack", default_slack))
        gate = bool(mp.get("gate", True))
        row = {
            "metric": name, "direction": direction, "slack": slack,
            "gate": gate,
            "values": [(c["file"], c["metrics"].get(name)) for c in comp],
            "delta_pct": None, "status": "no data",
        }
        a = prev["metrics"].get(name) if prev else None
        b = last["metrics"].get(name) if last else None
        if b is not None and a is None:
            row["status"] = "new"
        elif b is None and a is not None:
            row["status"] = "missing from latest"
        elif a is not None and b is not None:
            row["delta_pct"] = 100.0 * (b - a) / a if a else None
            worse = (b < a * (1.0 - slack) if direction == "higher"
                     else b > a * (1.0 + slack))
            better = b > a if direction == "higher" else b < a
            row["status"] = ("REGRESSED" if worse
                             else "improved" if better else "ok")
            if worse and not gate:
                row["status"] = "regressed (informational)"
        rows.append(row)
    return {
        "rows": rows,
        "skipped": [{"file": c["file"], "reason": c["skip"]}
                    for c in caps if c["skip"] is not None],
        "pair": (prev["file"], last["file"]) if prev else None,
        "comparable": [c["file"] for c in comp],
    }


def check(caps: list, policy: dict) -> list:
    """Gate the latest comparable capture against the previous one;
    returns failure strings (empty = pass)."""
    comp = comparable(caps)
    if len(comp) < 2:
        # a single trusted capture is a baseline, not a trend
        return []
    prev, last = comp[-2], comp[-1]
    default_slack = float(policy.get("default_slack", 0.10))
    fails = []
    for name in sorted(policy.get("metrics", {})):
        mp = policy["metrics"][name]
        if not mp.get("gate", True):
            continue
        direction = mp.get("direction", "higher")
        slack = float(mp.get("slack", default_slack))
        a, b = prev["metrics"].get(name), last["metrics"].get(name)
        if a is None:
            continue  # metric is new (or older than the window) — no trend
        if b is None:
            fails.append(
                f"{name}: present in {prev['file']} but ABSENT from "
                f"{last['file']} — a gated metric disappeared; fix the "
                f"capture or mark it gate:false in tools/"
                f"trendgate_policy.json"
            )
            continue
        worse = (b < a * (1.0 - slack) if direction == "higher"
                 else b > a * (1.0 + slack))
        if worse:
            arrow = "dropped" if direction == "higher" else "rose"
            fails.append(
                f"{name} ({direction}-is-better): {arrow} "
                f"{a:g} -> {b:g} ({100.0 * (b - a) / a:+.1f}%, slack "
                f"{slack:.0%}) between {prev['file']} and {last['file']} "
                f"— a deliberate change commits the new capture and "
                f"re-renders with: python tools/trendgate.py --update"
            )
    return fails


# -- report -------------------------------------------------------------------
def _fmt(v) -> str:
    if v is None:
        return "—"
    if abs(v) >= 1000:
        return f"{v:,.1f}"
    return f"{v:g}"


def render_report(caps: list, policy: dict, fails: list) -> str:
    trend = build_trend(caps, policy)
    comp = comparable(caps)
    cols = comp[-MAX_COLUMNS:]
    lines = [
        "# BENCH trendline",
        "",
        "Generated by `python tools/trendgate.py --update` — do not edit "
        "by hand. Gate policy: `tools/trendgate_policy.json`; gate "
        "command: `python tools/trendgate.py --check` (wired into "
        "`tools/tier1.sh` as `TRENDGATE`).",
        "",
        "## Captures",
        "",
        "| capture | round | status |",
        "| --- | --- | --- |",
    ]
    for c in caps:
        status = "comparable" if c["skip"] is None else f"skipped: {c['skip']}"
        sha = (c["meta"] or {}).get("git_sha")
        if sha and c["skip"] is None:
            status += f" (sha {sha})"
        lines.append(f"| `{c['file']}` | r{c['round']:02d} | {status} |")
    lines += ["", "## Trend", ""]
    if trend["pair"]:
        lines.append(f"Gate compares `{trend['pair'][1]}` (latest "
                     f"comparable) against `{trend['pair'][0]}`.")
    else:
        lines.append("Fewer than two comparable captures — no trend to "
                     "gate yet.")
    header = "| metric | dir | gated | slack | " + " | ".join(
        f"`{c['file'].replace('BENCH_', '').replace('.json', '')}`"
        for c in cols) + " | Δ% | status |"
    sep = "| --- | --- | --- | --- |" + " --- |" * (len(cols) + 2)
    lines += ["", header, sep]
    for row in trend["rows"]:
        vals = dict(row["values"])
        cells = " | ".join(_fmt(vals.get(c["file"])) for c in cols)
        delta = ("—" if row["delta_pct"] is None
                 else f"{row['delta_pct']:+.1f}%")
        lines.append(
            f"| `{row['metric']}` | {row['direction']} "
            f"| {'yes' if row['gate'] else 'no'} | {row['slack']:.0%} "
            f"| {cells} | {delta} | {row['status']} |"
        )
    lines += ["", "## Gate result", ""]
    if fails:
        lines.append("**FAIL**")
        lines += [f"- {f}" for f in fails]
    else:
        lines.append(f"pass ({len(comp)} comparable capture(s), "
                     f"{len(trend['skipped'])} skipped)")
    lines += ["", ""]
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", action="store_true",
                      help="gate the committed history; exit 1 on "
                           "regression")
    mode.add_argument("--update", action="store_true",
                      help="rewrite TREND.md from the committed history")
    mode.add_argument("--print", dest="show", action="store_true",
                      help="dump the trend table as JSON")
    ap.add_argument("--repo", default=REPO,
                    help=f"repo root holding BENCH_*.json (default {REPO})")
    ap.add_argument("--policy", default=POLICY,
                    help=f"policy path (default {POLICY})")
    args = ap.parse_args()

    try:
        with open(args.policy) as f:
            policy = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"trendgate: FAIL — unreadable policy ({e})")
        return 1
    caps = load_history(args.repo, policy.get("trust", {}))
    if os.environ.get(ENV_INJECT, "") not in ("", "0"):
        comp = comparable(caps)
        if comp:
            caps.append(inject_capture(comp[-1], policy))
    fails = check(caps, policy)

    if args.show:
        print(json.dumps(build_trend(caps, policy), indent=2))
        return 0
    if args.update:
        report = render_report(caps, policy, fails)
        with open(os.path.join(args.repo, "TREND.md"), "w") as f:
            f.write(report)
        print(f"trendgate: report written to "
              f"{os.path.join(args.repo, 'TREND.md')}")
        return 0
    if fails:
        print("trendgate: FAIL")
        for f in fails:
            print(f"  - {f}")
        return 1
    comp = comparable(caps)
    skipped = [c for c in caps if c["skip"] is not None]
    print(f"trendgate: pass ({len(comp)} comparable capture(s), "
          f"{len(skipped)} skipped with reasons; latest "
          f"{comp[-1]['file'] if comp else 'n/a'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
