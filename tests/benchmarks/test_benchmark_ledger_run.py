"""Every reader of the step ledger driven through the real serve driver
and a real batcher on the CPU: a traced toy run over a manifest built from
the tiny fixture's BENCHMARK.json plus the ledger's per-layer entries
(`fixtures/ledger/entries.json`, which names the real cells), their cells
mapped to the toy twins."""

import io
import json
import os
import shutil
import time

import jax
import pytest

from benchmarks import run as runner
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "fixtures", "tiny")
ENTRIES = os.path.join(HERE, "fixtures", "ledger", "entries.json")
TWIN = {"gpt2l-serve-chat-r80": "tiny-serve-r80",
        "gpt2l-serve-chat-over": "tiny-serve-over"}


@pytest.fixture(scope="module")
def with_ledger(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ledger"))
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    with open(ENTRIES) as f:
        entries = json.load(f)["per_layer"]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    for m in entries:
        data["per_layer"].append(
            dict(m, workloads=[TWIN[w] for w in m["workloads"]]))
    with open(path, "w") as f:
        json.dump(data, f)
    return Manifest(root), entries


def test_manifest_with_the_ledger_entries_is_consistent(with_ledger):
    manifest, entries = with_ledger
    assert check(manifest) == []
    assert len({m["name"] for m in entries}) == len(entries) == 11


@pytest.mark.parametrize("cell", ["tiny-serve-r80", "tiny-serve-over"])
def test_traced_toy_run_reports_every_ledger_metric(
        with_ledger, recorded_trace, cell):
    manifest, entries = with_ledger
    out = io.StringIO()
    line = runner.run_cell(manifest, cell, 7, 1.5, recorded_trace,
                           jax.devices(), time.perf_counter(), out=out)
    assert line["correct"] is True
    want = {m["name"] for m in entries if TWIN[m["workloads"][0]] == cell}
    assert want and want <= set(line["metrics"])
    assert set(line["metrics"]) == {
        m["name"] for m in manifest.cell_metrics(cell, "per_layer")}
    value = {name: line["metrics"][name]["value"] for name in want}
    assert all(v > 0 for v in value.values()), value
    suffix = ".lat" if cell == "tiny-serve-r80" else ".tput"
    assert 0 < value["host_serial_pct" + suffix] <= 100
    assert 0 < value["prefill_useful_pct" + suffix] <= 100
    # a share of the v5e's bandwidth, on a CPU: only that it is a number
    assert value["decode_hbm_roofline" + suffix] < 100
