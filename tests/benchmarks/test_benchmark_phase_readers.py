"""The readers of the batcher's step ledger (`benchmarks/lib/phase_readers`
and the metric files over it), on hand-made counters: the value, and None
where a counter is missing (a program older than the ledger) or the
divisor is 0."""

import json
import os

import pytest

from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib.manifest import Manifest
from benchmarks.lib.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRIES = os.path.join(HERE, "fixtures", "ledger", "entries.json")
KIND = "TPU v5 lite"

COUNTERS = {
    "step_ns": 1_000_000_000, "device_wait_ns": 700_000_000,
    "decode_ns": 600_000_000, "rounds": 40, "prefill_ns": 150_000_000,
    "prefill_waves": 2, "admitted": 5, "queue_wait_ns": 400_000_000,
    "first_token_hold_ns": 650_000_000, "prefill_tokens": 900,
    "prefill_cells": 2048, "decode_least_bytes": 140_000_000_000,
}

# metric (without its cell suffix): (value on COUNTERS, the divisor)
WANT = {
    "queue_wait_ms": (80.0, "admitted"),
    "prefill_wave_ms": (75.0, "prefill_waves"),
    "first_token_hold_ms": (130.0, "admitted"),
    "decode_tick_ms": (15.0, "rounds"),
    "host_serial_pct": (30.0, "step_ns"),
    "prefill_useful_pct": (100.0 * 900 / 2048, "prefill_cells"),
    "decode_hbm_roofline": (
        100.0 * (140e9 / peaks_for(KIND)["hbm_bytes_per_s"]) / 0.6,
        "decode_ns"),
}


def _entries():
    with open(ENTRIES) as f:
        return json.load(f)["per_layer"]


@pytest.mark.parametrize("name", [m["name"] for m in _entries()])
def test_reader_on_hand_made_counters(name):
    read = Manifest(manifest_lib.REPO_ROOT).metric_reader(name)
    value, divisor = WANT[name.rsplit(".", 1)[0]]
    obs = {"counters": dict(COUNTERS), "device_kind": KIND}
    assert read(obs) == pytest.approx(value, rel=1e-12)
    assert read({"counters": dict(COUNTERS, **{divisor: 0}),
                 "device_kind": KIND}) is None
    # a program that keeps no ledger: the counts stats() always had, only
    old = {"counters": {"rounds": 40, "generated": 300, "syncs": 9},
           "device_kind": KIND}
    assert read(old) is None
    assert read({"device_kind": KIND}) is None


def test_every_ledger_metric_has_a_want():
    names = {m["name"].rsplit(".", 1)[0] for m in _entries()}
    assert names == set(WANT)
    for m in _entries():
        assert os.path.isfile(os.path.join(
            manifest_lib.REPO_ROOT, "benchmarks", "layer_metrics",
            m["name"] + ".py"))
