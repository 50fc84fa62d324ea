"""The configuration `evabyte-serve-16k`, its driver and its two cells'
files: the real manifest stays consistent with both cells added, the
published widths are kept, the four-chip rule holds, and a toy twin of
the configuration (fixtures/tiny_eva) runs through `run_cell` on the CPU,
traced and untraced."""

import io
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import traffic as traffic_lib
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_EVA = Manifest(os.path.join(HERE, "fixtures", "tiny_eva"))
EVA_CELL, LONG_CELL = "evabyte-serve-longdoc-over", "gpt2l-serve-long-over"
EVA_METRICS = {"device_idle_pct.eva", "rows_per_tick.eva",
               "decode_tick_ms.eva", "prefill_ms_per_kbyte.eva",
               "eva_remote_share_pct.eva", "eva_decode_least_bytes_pct.eva",
               "syncs_per_token.eva", "compile_s"}
# https://huggingface.co/EvaByte/EvaByte/blob/main/config.json, as the
# catalog of public architectures holds it
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent_with_both_cells(real):
    assert check(real) == []
    assert {EVA_CELL, LONG_CELL} <= set(real.cells)
    assert check(TINY_EVA) == []


def test_one_four_chip_cell_and_at_most_a_quarter(real):
    chips = [w["chips"] for w in real.data["workloads"]]
    assert chips.count(4) == 1
    assert chips.count(4) <= max(1, len(chips) // 4)
    assert set(chips) == {1, 4}


@pytest.mark.parametrize("cell", [EVA_CELL, LONG_CELL])
def test_new_cells_find_their_files(real, cell):
    w = real.cell(cell)
    cfg = real.config(w["config"])
    assert {"source", "reduced", "assumed", "driver", "reference",
            "deployment", "correct"} <= set(cfg)
    mix = real.traffic(w["traffic"])
    assert mix["generator"] == "open_loop" and mix["after_window"] == "stop"
    manifest_lib.driver_module(cfg["driver"]).run
    manifest_lib.reference_module(cfg["reference"]).served_token_gaps
    names = [m["name"] for m in real.cell_metrics(cell, "end_to_end")]
    assert names == ["serve_tokens_per_s", "setup_s"]
    for m in real.cell_metrics(cell, "per_layer"):
        assert callable(real.metric_reader(m["name"]))
        assert m["moves"] in names
    # the longest request fits a row, every prompt finds a bucket
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        cfg["batcher"]["max_len"]
    assert mix["prompt"]["max"] <= max(cfg["batcher"]["prompt_buckets"])


def test_new_cells_report_the_metrics_the_issue_names(real):
    assert {m["name"] for m in real.cell_metrics(EVA_CELL, "per_layer")} \
        == EVA_METRICS
    assert {m["name"] for m in real.cell_metrics(LONG_CELL, "per_layer")} \
        == {"device_idle_pct.long", "rows_per_tick.long",
            "syncs_per_token.long", "compile_s"}
    for name in EVA_METRICS - {"compile_s"}:
        assert real.per_layer[name]["workloads"] == [EVA_CELL]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_keys_are_kept(real, key):
    cfg = real.config("evabyte-serve-16k")
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_depth_and_prediction_heads_only(real):
    cfg = real.config("evabyte-serve-16k")
    assert cfg["reduced"] == ["num_hidden_layers", "num_pred_heads"]
    assert cfg["num_hidden_layers"] == 8 and cfg["num_pred_heads"] == 1
    assert {"weights", "pooling", "batcher.max_len",
            "batcher.prompt_buckets", "feed.max_unadmitted"} <= set(
        cfg["assumed"])
    assert all(b % cfg["window_size"] == 0
               for b in cfg["batcher"]["prompt_buckets"])


def test_driver_builds_the_published_block(real):
    cfg = real.config("evabyte-serve-16k")
    model = manifest_lib.driver_module("serve_evabyte").build_model(cfg)
    assert (model.hidden_size, model.num_heads, model.mlp_dim,
            model.vocab_size, model.depth) == (4096, 32, 11008, 320, 8)
    assert (model.attention, model.eva_window, model.eva_chunk) == (
        "eva", 2048, 16)
    assert model.norm_unit_offset and model.fp32_residual
    assert not model.tie_embeddings and not model.use_bias
    ref = manifest_lib.reference_module("evabyte")
    dims = ref.dims_of(cfg)
    # 8 x (4 x 4096^2 + 3 x 4096 x 11008) and the small leaves
    assert ref.num_params(dims) == 8 * (
        4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 4096
    ) + 2 * 320 * 4096 + 4096


@pytest.mark.parametrize("mix,lo,hi", [
    ("longdoc-poisson-over", 2304, 14336),
    ("long-poisson-over", 512, 960),
])
def test_traffic_of_the_new_cells(real, mix, lo, hi):
    m = real.traffic(mix)
    # the issue's traffic and nothing else: Poisson arrivals into a window
    # that opens on an empty batcher and closes on time
    assert set(m) == {"generator", "prompt", "output", "rate_per_s",
                      "after_window", "trace_seconds", "why"}
    assert m["generator"] == "open_loop" and m["after_window"] == "stop"
    a = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=320)
    b = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=320)
    assert len(a) == round(m["rate_per_s"] * 35) and len(a) >= 30
    # no request is waiting when the window opens beyond the first arrival
    assert sum(r.due_s == 0.0 for r in a) <= 1
    assert max(r.due_s for r in a) < 35.0
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    sizes = [r.prompt.size for r in a]
    assert min(sizes) >= lo and max(sizes) <= hi
    assert 0.8 * m["prompt"]["median"] <= np.median(sizes) <= \
        1.2 * m["prompt"]["median"]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 2 ** 31 + 977])
def test_every_seed_offers_the_same_work_at_the_same_times(real, seed):
    """The byte model's cell serves some forty admissions a window: the
    order of arrival is the cell's (the generator's at `ARRIVALS_SEED`),
    the bytes are the seed's."""
    driver = manifest_lib.driver_module("serve_evabyte")
    m = real.traffic("longdoc-poisson-over")
    one = traffic_lib.generate(m, driver.ARRIVALS_SEED, 35.0, vocab=320)
    a = driver.offered(m, seed, 35.0, 320)
    b = driver.offered(m, seed, 35.0, 320)
    other = driver.offered(m, seed + 1, 35.0, 320)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in one] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in other]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, other))
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 320 for r in a)


def _record(prompt: int, served: int):
    return types.SimpleNamespace(
        request=types.SimpleNamespace(prompt=np.zeros(prompt, np.int32)),
        tokens=np.zeros(served, np.int32))


@pytest.mark.parametrize("prompt,served,crosses", [
    (2040, 8, False),     # fed at 2040 .. 2046: the last byte is never fed
    (2040, 9, False),     # .. 2047
    (2040, 10, True),     # a step at position 2048
    (2048, 2, True),      # the first fed position opens a window
    (2049, 500, False),
    (6000, 300, True),    # crosses 6144
])
def test_crosses_window(prompt, served, crosses):
    driver = manifest_lib.driver_module("serve_evabyte")
    assert driver.crosses_window(_record(prompt, served), 2048) is crosses


def test_edge_sample_adds_a_request_across_an_edge():
    driver = manifest_lib.driver_module("serve_evabyte")
    done = [_record(100, 20) for _ in range(8)] + [_record(2040, 30)]
    sample = driver.edge_sample(done, seed=3, size=2, window=2048)
    assert sum(driver.crosses_window(r, 2048) for r in sample) == 1
    assert 2 <= len(sample) <= 3
    assert len(driver.edge_sample(done[:8], 3, 2, 2048)) == 2


def test_sweep_wrapper_names_the_driver_and_puts_it_back(monkeypatch):
    from benchmarks import sweep, sweep_evabyte

    seen = {}

    def fake_main(argv):
        seen["driver"] = manifest_lib.driver_module("serve")
        return 0

    before = manifest_lib.driver_module
    monkeypatch.setattr(sweep, "main", fake_main)
    assert sweep_evabyte.main([]) == 0
    assert seen["driver"].build_server.__module__.endswith("serve_evabyte")
    assert callable(seen["driver"].serve_window)
    assert manifest_lib.driver_module is before


# ---------------------------------------------------------------------------
# the toy twin through run_cell
# ---------------------------------------------------------------------------

def _run(seed=2 ** 31 + 11, seconds=2.0, control=False, tracer=None):
    out = io.StringIO()
    line = runner.run_cell(
        TINY_EVA, "tiny-evabyte-over", seed, seconds, tracer, jax.devices(),
        time.perf_counter(), control=control, out=out)
    tagged = {}
    for text in out.getvalue().splitlines():
        if text.startswith("["):
            tag, payload = text.split("] ", 1)
            tagged.setdefault(tag[1:], []).append(json.loads(payload))
    return line, tagged


def test_toy_twin_runs_and_is_correct():
    line, tagged = _run(control=True)
    assert line["correct"] is True, tagged["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    notes = tagged["notes"][0]
    assert notes["window_compiles"] == 0
    assert notes["checked_across_window_edge"] >= 1
    # one summary per 4 committed bytes per row, within rounding: between
    # (bytes - 3 a request) / 4 and bytes / 4
    eva = notes["eva"]
    assert eva["eva_summaries_written"] > 0 and eva["eva_window_turns"] > 0
    # the control: the reference one precision down is not correct
    assert [c["fails_as_it_must"] for c in tagged["control"]] == [True]


def test_toy_twin_traced_carries_every_new_metric(recorded_trace):
    line, _ = _run(tracer=recorded_trace)
    assert line["correct"] is True
    assert set(line["metrics"]) == EVA_METRICS
    share = line["metrics"]["eva_remote_share_pct.eva"]["value"]
    assert 0.0 < share < 100.0
    assert line["metrics"]["decode_tick_ms.eva"]["value"] > 0
    assert line["metrics"]["prefill_ms_per_kbyte.eva"]["value"] > 0
    assert 0.0 < line["metrics"]["eva_decode_least_bytes_pct.eva"]["value"]


def test_readers_find_nothing_in_a_program_without_the_counters(real):
    obs = {"counters": {"generated": 10, "rounds": 5, "decode_ns": 10 ** 9,
                        "decode_least_bytes": 10 ** 9},
           "device_kind": "TPU v5 lite"}
    for name in ("eva_remote_share_pct.eva",
                 "eva_decode_least_bytes_pct.eva"):
        assert real.metric_reader(name)(obs) is None
    assert real.metric_reader("prefill_ms_per_kbyte.eva")(obs) is None
    assert real.metric_reader("rows_per_tick.eva")(obs) == 2.0


def test_a_served_byte_altered_is_not_correct(monkeypatch):
    from tfde_tpu.inference import server

    real_fetch = server._fetch

    def altered(tree):
        out = real_fetch(tree)
        if isinstance(out, tuple) and len(out) == 2:   # the scan's tokens
            toks, emitted = out
            toks = np.array(toks)
            toks[:, 0] = (toks[:, 0] + 1) % 320
            return toks, emitted
        return out

    monkeypatch.setattr(server, "_fetch", altered)
    line, tagged = _run()
    assert line["correct"] is False
    failed = {c["name"] for c in tagged["compared"] if not c["ok"]}
    assert failed == {"served_token_gap_max"}
