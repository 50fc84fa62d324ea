"""BENCHMARK.json is consistent, and a later PR can add a configuration, a
traffic mix, a cell and a per-layer metric by adding files and entries."""

import copy
import io
import json
import os
import shutil
import time

import pytest

from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "fixtures", "tiny")
ADDITION = os.path.join(HERE, "fixtures", "addition")


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent(real):
    assert check(real) == []


def test_tiny_fixture_manifest_is_consistent():
    assert check(Manifest(TINY)) == []


def test_one_four_chip_cell_of_four(real):
    chips = [w["chips"] for w in real.data["workloads"]]
    assert sorted(chips) == [1, 1, 1, 4]


def test_paths_name_both_directories(real):
    assert real.data["paths"] == ["benchmarks", "tests/benchmarks"]
    assert real.data["command"] == ["python", "benchmarks/run.py"]


@pytest.mark.parametrize("cell", ["gpt2m-train-s4096-1chip",
                                  "gpt2l-serve-chat-r80",
                                  "gpt2l-serve-chat-over",
                                  "gpt2m-train-s4096-4chip"])
def test_every_cell_finds_its_files(real, cell):
    w = real.cell(cell)
    cfg = real.config(w["config"])
    assert {"source", "reduced", "assumed", "driver", "reference",
            "deployment", "correct"} <= set(cfg)
    assert real.traffic(w["traffic"])["generator"]
    manifest_lib.driver_module(cfg["driver"]).run
    names = [m["name"] for m in real.cell_metrics(cell, "end_to_end")]
    assert "setup_s" in names and len(names) >= 2
    for m in real.cell_metrics(cell, "per_layer"):
        assert callable(real.metric_reader(m["name"]))
        assert m["moves"] in names


def test_published_widths_are_kept(real):
    medium = real.config("gpt2-medium-s4096")
    large = real.config("gpt2-large-serve-1k")
    assert (medium["n_layer"], medium["n_embd"], medium["n_head"],
            medium["vocab_size"]) == (24, 1024, 16, 50257)
    assert (large["n_layer"], large["n_embd"], large["n_head"],
            large["vocab_size"], large["n_positions"]) == (
        36, 1280, 20, 50257, 1024)
    assert large["reduced"] == []
    assert "n_positions" in medium["assumed"]


def _copy_of_benchmark(tmp_path) -> str:
    root = str(tmp_path)
    shutil.copy(os.path.join(manifest_lib.REPO_ROOT, "BENCHMARK.json"), root)
    for kind in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(
            os.path.join(manifest_lib.REPO_ROOT, "benchmarks", kind),
            os.path.join(root, "benchmarks", kind))
    os.makedirs(os.path.join(root, "tests", "benchmarks"))
    return root


def test_adding_one_of_each_needs_only_files_and_entries(
        tmp_path, recorded_trace):
    root = _copy_of_benchmark(tmp_path)
    shutil.copytree(os.path.join(ADDITION, "benchmarks"),
                    os.path.join(root, "benchmarks"), dirs_exist_ok=True)
    with open(os.path.join(ADDITION, "entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["configs"] += entries["configs"]
    data["workloads"] += entries["workloads"]
    data["per_layer"] += entries["per_layer"]
    for m in data["end_to_end"]:
        m.get("workloads", []).extend(
            entries["end_to_end_workloads"].get(m["name"], []))
    with open(path, "w") as f:
        json.dump(data, f)
    added = Manifest(root)
    assert check(added) == []
    names = [m["name"] for m in added.cell_metrics("extra-cell", "per_layer")]
    assert "extra_dispatches.lat" in names and "compile_s" in names
    # the added cell runs (a toy twin, on the CPU) and its traced line
    # carries the added metric: the reader finds the counter it reads
    # among those the driver hands over, with no driver edited
    import jax

    from benchmarks import run as runner

    out = io.StringIO()
    line = runner.run_cell(added, "extra-cell", 7, 1.5, recorded_trace,
                           jax.devices(), time.perf_counter(), out=out)
    assert line["correct"] is True
    assert set(line["metrics"]) == set(names)
    assert line["metrics"]["extra_dispatches.lat"]["value"] > 0


def _broken(data: dict, how: str) -> dict:
    data = copy.deepcopy(data)
    if how == "pair twice":
        data["workloads"][3]["traffic"] = data["workloads"][0]["traffic"]
    elif how == "two four-chip cells":
        data["workloads"][0]["chips"] = 4
    elif how == "no reader":
        data["per_layer"][0]["name"] = "no_such_reader"
    elif how == "moves nothing":
        data["per_layer"][2]["moves"] = "ttft_p50_ms"
    elif how == "bad unit":
        data["end_to_end"][0]["unit"] = "tokens per second"
    elif how == "extra key":
        data["per_layer"][0]["why"] = "not allowed"
    elif how == "no setup_s":
        data["end_to_end"] = [m for m in data["end_to_end"]
                              if m["name"] != "setup_s"]
    elif how == "bound too wide":
        data["end_to_end"][0]["bound"] = 0.2
    elif how == "unused config":
        data["workloads"] = [w for w in data["workloads"]
                             if w["config"] != "gpt2-large-serve-1k"]
    return data


@pytest.mark.parametrize("how", [
    "pair twice", "two four-chip cells", "no reader", "moves nothing",
    "bad unit", "extra key", "no setup_s", "bound too wide", "unused config"])
def test_check_finds_what_the_driver_would_refuse(tmp_path, real, how):
    root = _copy_of_benchmark(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(_broken(real.data, how), f)
    assert check(Manifest(root)) != []
