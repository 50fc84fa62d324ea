"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found here by the name the manifest
gives it:

    <root>/BENCHMARK.json
    <root>/<configs[].file>                          one configuration
    <root>/benchmarks/traffic/<mix>.json             one traffic mix
    <root>/benchmarks/layer_metrics/<metric>.py      one per-layer reader
    benchmarks/drivers/<driver>.py                   named by the configuration
    benchmarks/reference/<reference>.py              named by the configuration

A later PR adds files and entries and edits none of these.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = "benchmarks"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise ManifestError(f"missing file: {path}") from e
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path} is not JSON: {e}") from e


def load_module(path: str, name: str):
    """Import one file by path (metric names carry dots, so no package
    import can reach them)."""
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    """The manifest at `root` and look-ups by name."""

    def __init__(self, root: str = REPO_ROOT):
        self.root = os.path.abspath(root)
        self.data = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        self.cells = {c["name"]: c for c in self.data.get("workloads", [])}
        self.configs = {c["name"]: c for c in self.data.get("configs", [])}
        self.end_to_end = {m["name"]: m
                           for m in self.data.get("end_to_end", [])}
        self.per_layer = {m["name"]: m for m in self.data.get("per_layer", [])}

    # -- look-ups -----------------------------------------------------------
    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        """The configuration file's contents (sizes as they are run)."""
        if name not in self.configs:
            raise ManifestError(f"no configuration {name!r}")
        return _read_json(os.path.join(self.root, self.configs[name]["file"]))

    def _data_file(self, kind: str, filename: str) -> str:
        """Under this manifest's root; a manifest elsewhere (a test's
        fixture) falls back on the repository's own files."""
        path = os.path.join(self.root, BENCH_DIR, kind, filename)
        if not os.path.isfile(path) and self.root != REPO_ROOT:
            fallback = os.path.join(REPO_ROOT, BENCH_DIR, kind, filename)
            if os.path.isfile(fallback):
                return fallback
        return path

    def traffic_path(self, mix: str) -> str:
        return self._data_file("traffic", f"{mix}.json")

    def traffic(self, mix: str) -> dict:
        return _read_json(self.traffic_path(mix))

    def metric_path(self, name: str) -> str:
        return self._data_file("layer_metrics", f"{name}.py")

    def metric_reader(self, name: str):
        """The `read(obs)` function of one per-layer metric's own file."""
        module = load_module(self.metric_path(name), f"_layer_metric_{name}")
        if not callable(getattr(module, "read", None)):
            raise ManifestError(
                f"{self.metric_path(name)} defines no read(obs)")
        return module.read

    def cell_metrics(self, cell: str, section: str) -> list:
        """Metric entries of `section` that this cell reports: those whose
        `workloads` lists it, and those with no `workloads` key (end to
        end: every cell; per layer: every cell reporting what it moves)."""
        out = []
        e2e_here = {m["name"] for m in self.data["end_to_end"]
                    if "workloads" not in m or cell in m["workloads"]}
        for m in self.data[section]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in e2e_here:
                out.append(m)
        return out


def _code_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py: code, so it lives beside this file and
    not under a manifest's root."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(here, kind, f"{name}.py"),
                       f"_{kind}_{name}")


def driver_module(name: str):
    """The driver a configuration names: `run(ctx) -> dict`."""
    return _code_module("drivers", name)


def reference_module(name: str):
    """A configuration's plain reference."""
    return _code_module("reference", name)


# ---------------------------------------------------------------------------
# consistency: what the driver refuses before a single run, checked here so
# that a test finds it first
# ---------------------------------------------------------------------------

def _one_line(s, what: str, errors: list) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def _check_metric(m: dict, extra: set, where: str, cells: set,
                  errors: list) -> None:
    allowed = {"name", "unit", "better", "source"} | extra | {"workloads"}
    if set(m) - allowed or not ({"name", "unit", "better", "source"}
                                | extra) <= set(m):
        errors.append(f"{where}: keys {sorted(m)} are not {sorted(allowed)}")
        return
    if not NAME_RE.match(m["name"]):
        errors.append(f"{where}: bad name {m['name']!r}")
    if not UNIT_RE.match(m["unit"]):
        errors.append(f"{where}: bad unit {m['unit']!r}")
    if m["better"] not in ("lower", "higher"):
        errors.append(f"{where}: better is {m['better']!r}")
    if m["source"] not in SOURCES:
        errors.append(f"{where}: source is {m['source']!r}")
    for w in m.get("workloads", []):
        if w not in cells:
            errors.append(f"{where}: unknown workload {w!r}")


def check(manifest: Manifest) -> list:
    """Every inconsistency found, as text; an empty list means sound."""
    d, errors = manifest.data, []
    if set(d) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(d)} are not {sorted(TOP_KEYS)}")
        return errors
    paths = d["paths"]
    if not (1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/") \
                or ".." in p.split("/"):
            errors.append(f"paths: bad entry {p!r}")
        if not os.path.isdir(os.path.join(manifest.root, p)):
            errors.append(f"paths: {p!r} is not a directory")
    if not (isinstance(d["command"], list) and 1 <= len(d["command"]) <= 32):
        errors.append("command: a list of 1 to 32 strings")
    for word in d["command"]:
        _one_line(word, f"command word {word!r}", errors)
    if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/")
                   for p in paths)

    # configurations
    files = set()
    for c in d["configs"]:
        where = f"config {c.get('name')!r}"
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errors.append(f"{where}: keys {sorted(c)}")
            continue
        if not NAME_RE.match(c["name"]):
            errors.append(f"{where}: bad name")
        _one_line(c["source"], f"{where} source", errors)
        _one_line(c["why"], f"{where} why", errors)
        if not under_paths(c["file"]) or c["file"] in files:
            errors.append(f"{where}: file {c['file']!r} outside paths or "
                          f"shared")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(
                NAME_RE.match(k) for k in c["reduced"]):
            errors.append(f"{where}: bad reduced {c['reduced']}")
        try:
            cfg = manifest.config(c["name"])
        except ManifestError as e:
            errors.append(str(e))
            continue
        for key in ("driver", "reference", "source", "reduced", "assumed"):
            if key not in cfg:
                errors.append(f"{c['file']}: no {key!r}")
        if cfg.get("reduced") != c["reduced"]:
            errors.append(f"{c['file']}: reduced {cfg.get('reduced')} differs "
                          f"from the manifest's {c['reduced']}")
    if not (1 <= len(d["configs"]) <= 24) or \
            len(manifest.configs) != len(d["configs"]):
        errors.append("configs: 1 to 24, names distinct")

    # cells
    cells = set(manifest.cells)
    pairs = set()
    for w in d["workloads"]:
        where = f"workload {w.get('name')!r}"
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errors.append(f"{where}: keys {sorted(w)}")
            continue
        for key in ("name", "config", "traffic"):
            if not NAME_RE.match(w[key]):
                errors.append(f"{where}: bad {key} {w[key]!r}")
        _one_line(w["why"], f"{where} why", errors)
        if w["chips"] not in (1, 4):
            errors.append(f"{where}: chips is {w['chips']}")
        if w["config"] not in manifest.configs:
            errors.append(f"{where}: unknown configuration {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            errors.append(f"{where}: pair of configuration and traffic "
                          f"appears twice")
        pairs.add((w["config"], w["traffic"]))
        if not os.path.isfile(manifest.traffic_path(w["traffic"])):
            errors.append(f"{where}: no traffic file "
                          f"{manifest.traffic_path(w['traffic'])}")
        elif "generator" not in manifest.traffic(w["traffic"]):
            errors.append(f"{where}: traffic file names no generator")
    if not (1 <= len(d["workloads"]) <= 24) or len(cells) != len(
            d["workloads"]):
        errors.append("workloads: 1 to 24, names distinct")
    used = {w.get("config") for w in d["workloads"]}
    for name in manifest.configs:
        if name not in used:
            errors.append(f"config {name!r} is used by no cell")
    four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
    if four > max(1, len(d["workloads"]) // 4):
        errors.append(f"{four} four-chip cells of {len(d['workloads'])}")

    # metrics
    names = [m.get("name") for m in d["end_to_end"] + d["per_layer"]]
    if len(set(names)) != len(names):
        errors.append("two metrics share a name")
    if not (1 <= len(d["end_to_end"]) <= 16) or \
            not (1 <= len(d["per_layer"]) <= 128):
        errors.append("end_to_end: 1 to 16; per_layer: 1 to 128")
    for m in d["end_to_end"]:
        where = f"end_to_end {m.get('name')!r}"
        _check_metric(m, {"bound"}, where, cells, errors)
        if m.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"{where}: source must be host_clock or "
                          f"device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0 < b <= 0.1):
            errors.append(f"{where}: bound {b!r} outside (0, 0.1]")
    if "setup_s" not in manifest.end_to_end:
        errors.append("end_to_end has no setup_s")
    elif "workloads" in manifest.end_to_end["setup_s"]:
        errors.append("setup_s must be reported by every cell")
    for m in d["per_layer"]:
        where = f"per_layer {m.get('name')!r}"
        _check_metric(m, {"layer", "moves"}, where, cells, errors)
        if "layer" in m:
            _one_line(m["layer"], f"{where} layer", errors)
        if m.get("moves") not in manifest.end_to_end:
            errors.append(f"{where}: moves {m.get('moves')!r} is no "
                          f"end-to-end metric")
            continue
        if not os.path.isfile(manifest.metric_path(m["name"])):
            errors.append(f"{where}: no reader "
                          f"{manifest.metric_path(m['name'])}")
        moved = manifest.end_to_end[m["moves"]]
        for w in m.get("workloads", []):
            if "workloads" in moved and w not in moved["workloads"]:
                errors.append(f"{where}: cell {w!r} does not report "
                              f"{m['moves']!r}")
    for cell in cells:
        e2e = [m["name"] for m in manifest.cell_metrics(cell, "end_to_end")]
        if "setup_s" not in e2e or len(e2e) < 2:
            errors.append(f"cell {cell!r} reports {e2e}: needs setup_s and "
                          f"one more")
        if not manifest.cell_metrics(cell, "per_layer"):
            errors.append(f"cell {cell!r} reports no per-layer metric")
    size = os.path.getsize(os.path.join(manifest.root, "BENCHMARK.json"))
    if size > 64 * 1024:
        errors.append(f"BENCHMARK.json is {size} bytes, over 64 KiB")
    return errors
