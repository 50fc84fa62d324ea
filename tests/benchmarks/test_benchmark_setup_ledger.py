"""The six readers of the program's set-up ledger
(`tfde_tpu.observability.recompile.setup()`), their helper and their
entries (`fixtures/setup_ledger/entries.json`, which names the real cells):
each file against a hand-made ledger, nothing read and nothing raised from
a program that keeps none, the manifest consistent with the entries
appended, and a traced toy run of every kind of cell on the CPU that
reports them and prints the `[setup]` line once. Whether `BENCHMARK.json`
lists the entries is not asserted: while `run_cell` ends a run whose listed
reader finds nothing, they cannot be listed by the PR that brings the
ledger (its parent keeps none), only by one after it."""

import io
import json
import os
import shutil
import time

import jax
import pytest

from benchmarks import run as runner
from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import setup_readers
from benchmarks.lib.manifest import Manifest, check
from tfde_tpu.observability import recompile

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRIES = os.path.join(HERE, "fixtures", "setup_ledger", "entries.json")
ALL = ["gpt2m-train-s4096-1chip", "gpt2l-serve-chat-r80",
       "gpt2l-serve-chat-over", "gpt2m-train-s4096-4chip",
       "gpt2l-serve-long-over", "evabyte-serve-longdoc-over",
       "granite4h-serve-rag-over", "smallthinker-serve-mixed-over"]
SERVE = [c for c in ALL if "-serve-" in c]
CELLS = {"setup_trace_s": ALL, "setup_lower_s": ALL, "setup_backend_s": ALL,
         "setup_cache_misses": ALL, "setup_programs": ALL,
         "setup_first_calls_s": SERVE}
UNITS = {"setup_cache_misses": "count", "setup_programs": "count"}
# the toy twin of each real cell, by fixture directory
TWINS = {
    "tiny": {"gpt2m-train-s4096-1chip": "tiny-train-1chip",
             "gpt2m-train-s4096-4chip": "tiny-train-4chip",
             "gpt2l-serve-chat-r80": "tiny-serve-r80",
             "gpt2l-serve-chat-over": "tiny-serve-over"},
    "tiny_eva": {"evabyte-serve-longdoc-over": "tiny-evabyte-over"},
    "tiny_granite": {"granite4h-serve-rag-over": "tiny-granite-over"},
    "tiny_smallthinker": {
        "smallthinker-serve-mixed-over": "tiny-smallthinker-over"},
}

HAND_MADE = {
    "sited": {"trace_ns": 41_500_000_000, "lower_ns": 9_250_000_000,
              "backend_ns": 21_000_000_000, "cache_read_ns": 6_000_000_000,
              "first_call_ns": 73_000_000_000, "programs": 31,
              "cache_misses": 2, "nested_traces": 48_213},
    "unsited": {"trace_ns": 1_000_000_000, "lower_ns": 2_000_000_000,
                "backend_ns": 3_000_000_000, "cache_read_ns": 0,
                "first_call_ns": 0, "programs": 7, "cache_misses": 7,
                "nested_traces": 0},
    "sites": {
        "serve/decode": {"episodes": [
            {"fun_name": "jit(_decode_scan)", "fingerprint": ("b0", 8),
             "trace_ns": 1_500_000_000, "lower_ns": 250_000_000,
             "backend_ns": 1_000_000_000, "cache_read_ns": 500_000_000,
             "cache_hit": True, "nested_traces": 213, "compiles": 1,
             "wall_ns": 4_000_000_000, "t0_ns": 2_000}]},
        "train/step": {"episodes": [
            {"fun_name": "jit(step)", "fingerprint": (),
             "trace_ns": 40_000_000_000, "lower_ns": 9_000_000_000,
             "backend_ns": 20_000_000_000, "cache_read_ns": 0,
             "cache_hit": False, "nested_traces": 48_000, "compiles": 1,
             "wall_ns": None, "t0_ns": 1_000}]},
    },
}
EXPECTED = {"setup_trace_s": 41.5, "setup_lower_s": 9.25,
            "setup_backend_s": 21.0, "setup_cache_misses": 2,
            "setup_programs": 31, "setup_first_calls_s": 73.0}


@pytest.fixture(scope="module")
def entries():
    with open(ENTRIES) as f:
        return json.load(f)["per_layer"]


@pytest.fixture
def quiet(monkeypatch):
    """The `[setup]` line already said in this process."""
    monkeypatch.setattr(setup_readers, "_said", True)


def _reader(name):
    return Manifest(manifest_lib.REPO_ROOT).metric_reader(name)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_against_a_hand_made_ledger(name, monkeypatch, quiet):
    monkeypatch.setattr(recompile, "setup", lambda: HAND_MADE)
    value = _reader(name)({"counters": {}})
    assert value == pytest.approx(EXPECTED[name], rel=1e-12)
    assert isinstance(value, int) == (name in UNITS)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_finds_nothing_in_a_program_without_the_ledger(
        name, monkeypatch, capsys):
    monkeypatch.setattr(setup_readers, "_said", False)
    monkeypatch.delattr(recompile, "setup")
    assert _reader(name)({"counters": {"compile_s": 1.0}}) is None
    assert "[setup]" not in capsys.readouterr().out


def test_no_watched_call_compiled_means_no_first_calls(monkeypatch, quiet):
    """A training cell: its programs are claimed by name, no call is
    watched, and the metric is not listed there."""
    none = dict(HAND_MADE, sited=dict(HAND_MADE["sited"], first_call_ns=0))
    monkeypatch.setattr(recompile, "setup", lambda: none)
    assert _reader("setup_first_calls_s")({}) is None
    assert _reader("setup_programs")({}) == 31


def test_the_setup_line_is_said_once_and_parses(monkeypatch, capsys):
    monkeypatch.setattr(setup_readers, "_said", False)
    monkeypatch.setattr(recompile, "setup", lambda: HAND_MADE)
    for name in sorted(CELLS):
        _reader(name)({})
    lines = [text for text in capsys.readouterr().out.splitlines()
             if text.startswith("[setup] ")]
    assert len(lines) == 1
    said = json.loads(lines[0][len("[setup] "):])
    assert set(said) == {"sited", "unsited", "programs"}
    assert said["sited"]["trace_s"] == pytest.approx(41.5)
    assert said["sited"]["first_calls_s"] == pytest.approx(73.0)
    assert said["sited"]["programs"] == 31
    assert said["unsited"]["programs"] == 7
    # in the order they began, stages in seconds
    assert [r["program"] for r in said["programs"]] == [
        "jit(step)", "jit(_decode_scan)"]
    scan = said["programs"][1]
    assert scan["site"] == "serve/decode" and scan["fingerprint"] == ["b0", 8]
    assert (scan["trace_s"], scan["lower_s"], scan["backend_s"],
            scan["cache_read_s"], scan["wall_s"]) == pytest.approx(
        (1.5, 0.25, 1.0, 0.5, 4.0))
    assert scan["cache_hit"] is True and scan["nested_traces"] == 213
    assert said["programs"][0]["wall_s"] is None


def test_entries_name_the_cells_the_issue_names(entries):
    assert [m["name"] for m in entries] == list(CELLS)
    for m in entries:
        assert m["workloads"] == CELLS[m["name"]]
        assert m["unit"] == UNITS.get(m["name"], "s")
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "runtime set-up", "setup_s", "program_counter", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_real_manifest_is_consistent_with_the_entries(entries, tmp_path):
    """BENCHMARK.json with the six appended where it lacks them: nothing
    the driver would refuse, and a reader's file for each."""
    real = Manifest(manifest_lib.REPO_ROOT)
    data = json.loads(json.dumps(real.data))
    listed = {m["name"]: m for m in data["per_layer"]}
    for m in entries:
        if m["name"] in listed:
            assert listed[m["name"]] == m   # listed as the fixture has it
        else:
            data["per_layer"].append(m)
    for part in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(
            os.path.join(real.root, manifest_lib.BENCH_DIR, part),
            tmp_path / manifest_lib.BENCH_DIR / part)
    shutil.copytree(os.path.join(real.root, "tests", "benchmarks"),
                    tmp_path / "tests" / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data, indent=1))
    appended = Manifest(str(tmp_path))
    assert check(appended) == []
    for cell in ALL:
        names = {m["name"] for m in appended.cell_metrics(cell, "per_layer")}
        assert names >= {n for n, cells in CELLS.items() if cell in cells}
        assert ("setup_first_calls_s" in names) == (cell in SERVE)
    assert len(data["per_layer"]) <= 128


def _with_entries(tmp_path_factory, fixture, entries):
    """The toy manifest of `fixture` with the six entries appended, their
    cells mapped to its twins."""
    root = str(tmp_path_factory.mktemp(fixture))
    shutil.copytree(os.path.join(HERE, "fixtures", fixture), root,
                    dirs_exist_ok=True)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    twin = TWINS[fixture]
    for m in entries:
        cells = [twin[w] for w in m["workloads"] if w in twin]
        if cells:
            data["per_layer"].append(dict(m, workloads=cells))
    with open(path, "w") as f:
        json.dump(data, f)
    manifest = Manifest(root)
    assert check(manifest) == []
    return manifest


@pytest.mark.parametrize("fixture,cell", [
    (fixture, cell) for fixture, twin in TWINS.items()
    for cell in twin.values()])
def test_traced_toy_run_reports_the_setup_metrics(
        fixture, cell, entries, tmp_path_factory, recorded_trace,
        monkeypatch, capsys):
    manifest = _with_entries(tmp_path_factory, fixture, entries)
    monkeypatch.setattr(setup_readers, "_said", False)
    recompile.reset()
    out = io.StringIO()
    line = runner.run_cell(manifest, cell, 7, 1.5, recorded_trace,
                           jax.devices(), time.perf_counter(), out=out)
    assert line["correct"] is True
    serve = "train" not in cell
    want = {n for n in CELLS if serve or n != "setup_first_calls_s"}
    assert want <= set(line["metrics"])
    value = {n: line["metrics"][n]["value"] for n in want}
    units = {n: line["metrics"][n]["unit"] for n in want}
    assert units == {n: UNITS.get(n, "s") for n in want}
    # at least one: jax keeps in the process what an earlier toy cell of
    # the same shapes compiled
    assert value["setup_programs"] >= 1
    assert value["setup_trace_s"] > 0 and value["setup_lower_s"] > 0
    assert value["setup_backend_s"] > 0
    # the suite keeps no persistent cache: nothing asked, nothing missed
    assert value["setup_cache_misses"] == 0
    said = [text for text in capsys.readouterr().out.splitlines()
            if text.startswith("[setup] ")]
    assert len(said) == 1   # once, however many readers asked
    table = json.loads(said[0][len("[setup] "):])
    if serve:
        # a wave is traced, lowered and compiled inside its watched call; a
        # decode scan is traced where the memory ledger interrogates it,
        # just before its watch opens
        scans_traced = sum(r["trace_s"] for r in table["programs"]
                           if r["site"] == "serve/decode")
        assert (value["setup_trace_s"] - scans_traced
                + value["setup_lower_s"] + value["setup_backend_s"]
                ) <= value["setup_first_calls_s"] + 1e-6
        # a program's line holds its tracing, not the microseconds of the
        # trace event that found it traced
        assert all(r["trace_s"] > 1e-4 for r in table["programs"]
                   if r["program"] in ("jit(_prefill_rows)",
                                       "jit(_decode_scan)"))
    assert table["sited"]["programs"] == value["setup_programs"] == len(
        table["programs"])
    assert sum(r["trace_s"] for r in table["programs"]) <= (
        table["sited"]["trace_s"] + 1e-9)
    sites = {r["site"] for r in table["programs"]}
    assert sites and sites <= ({"serve/prefill_cold", "serve/decode"}
                               if serve else {"train/init", "train/step"})
    # the harness's own count holds every program, sited or not
    counters = json.loads(json.dumps(recompile.setup()))
    assert counters["sited"]["programs"] + counters["unsited"][
        "programs"] == recompile.process_compiles()
