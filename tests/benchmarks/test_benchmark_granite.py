"""The configuration `granite-4.0-h-small-serve-8k`, its driver and its
cell's files: the real manifest stays consistent with the cell added, the
published widths are kept and the cut is written down, and a toy twin of the
configuration (fixtures/tiny_granite) runs through `run_cell` on the CPU,
traced and untraced, and ends not correct when the timed path is broken."""

import io
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import traffic as traffic_lib
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = Manifest(os.path.join(HERE, "fixtures", "tiny_granite"))
CONFIG, CELL, MIX = ("granite-4.0-h-small-serve-8k",
                     "granite4h-serve-rag-over", "rag-poisson-over")
GRAN_METRICS = {"device_idle_pct.gran", "rows_per_tick.gran",
                "syncs_per_token.gran", "decode_tick_ms.gran",
                "prefill_ms_per_ktoken.gran", "moe_held_share_pct.gran",
                "moe_busiest_over_mean.gran", "decode_least_bytes_pct.gran"}
# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
# as the catalog of public architectures holds it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent_with_the_cell(real):
    assert check(real) == []
    assert CELL in real.cells
    assert check(TINY) == []
    chips = [w["chips"] for w in real.data["workloads"]]
    assert chips.count(4) == 1 and real.cell(CELL)["chips"] == 1


def test_the_cell_finds_its_files(real):
    w = real.cell(CELL)
    assert (w["config"], w["traffic"]) == (CONFIG, MIX)
    cfg = real.config(CONFIG)
    assert {"source", "reduced", "published", "assumed", "deployment",
            "driver", "reference", "correct"} <= set(cfg)
    assert (cfg["driver"], cfg["reference"]) == ("serve_granite",
                                                 "granite_hybrid")
    manifest_lib.driver_module(cfg["driver"]).run
    manifest_lib.reference_module(cfg["reference"]).served_token_gaps
    names = [m["name"] for m in real.cell_metrics(CELL, "end_to_end")]
    assert names == ["serve_tokens_per_s", "setup_s"]
    mix = real.traffic(MIX)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        cfg["batcher"]["max_len"]
    assert mix["prompt"]["max"] <= max(cfg["batcher"]["prompt_buckets"])
    assert all(b % 256 == 0 for b in cfg["batcher"]["prompt_buckets"])


def test_the_cell_reports_the_metrics_the_issue_names(real):
    assert {m["name"] for m in real.cell_metrics(CELL, "per_layer")} == \
        GRAN_METRICS | {"compile_s"}
    for name in GRAN_METRICS:
        entry = real.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert callable(real.metric_reader(name))
    assert real.end_to_end["serve_tokens_per_s"]["workloads"][-1] == CELL


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_keys_are_kept(real, key):
    cfg = real.config(CONFIG)
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_depth_experts_held_and_vocabulary(real):
    cfg = real.config(CONFIG)
    entry = real.configs[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    share = cfg["deployment_share"]
    assert share == {"chips_per_layer": 2, "experts": [0, 36],
                     "vocabulary_rows": [0, 50176]}
    # the floors: a whole period, at least 8 experts, an eighth of the rows
    period = cfg["layer_types"][:10]
    assert period.count("attention") == 1 and period.count("mamba") == 9
    assert cfg["layer_types"][:10] == cfg["layer_types"][10:20]
    assert {"weights", "batcher.max_len", "batcher.batch_size",
            "batcher.prompt_buckets", "batcher.scan_depth",
            "feed.max_unadmitted", "state"} <= set(cfg["assumed"])


def test_driver_builds_the_published_blocks(real):
    cfg = real.config(CONFIG)
    model = manifest_lib.driver_module("serve_granite").build_model(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.mlp_dim, model.vocab_size, model.depth) == (
        4096, 32, 8, 768, 50176, 10)
    assert model.mixers == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (model.ssm.heads, model.ssm.head_dim, model.ssm.state,
            model.ssm.groups, model.ssm.conv, model.ssm.chunk) == (
        128, 64, 128, 1, 4, 256)
    assert model.ssm.in_features == 8192 + 8448 + 128
    assert (model.num_experts, model.experts_per_token, model.moe_every,
            model.moe_held_experts, model.moe_capacity_factor) == (
        72, 10, 1, (0, 36), None)
    assert (model.moe_shared_expert_dim, model.moe_shared_expert_gated) == (
        1536, False)
    assert (model.position, model.embed_scale, model.attn_scale,
            model.residual_multiplier, model.logits_scaling) == (
        "none", 12.0, 1 / 128, 0.22, 16.0)
    assert model.tie_embeddings and not model.use_bias
    ref = manifest_lib.reference_module("granite_hybrid")
    dims = ref.dims_of(cfg)
    moe = 4096 * 72 + 36 * 3 * 4096 * 768 + 3 * 4096 * 1536 + 2 * 4096
    mamba = (4096 * 16768 + 8192 * 4096 + 4 * 8448 + 8448 + 3 * 128 + 8192)
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert ref.num_params(dims) == (9 * (mamba + moe) + attention + moe
                                    + 50176 * 4096 + 4096)
    # the issue's arithmetic: 4,757 M parameters
    assert round(ref.num_params(dims) / 1e6) == 4757
    tree = jax.eval_shape(lambda: ref.to_program_params(
        jax.eval_shape(lambda: ref.make_weights(1, dims)), dims))
    mine = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda s: s.shape, tree) == \
        jax.tree.map(lambda s: s.shape, mine)
    assert {str(s.dtype) for s in jax.tree.leaves(tree)} == {"bfloat16"}


def test_traffic_of_the_cell(real):
    m = real.traffic(MIX)
    # the issue's traffic and nothing else: Poisson arrivals into a window
    # that opens on an empty batcher and closes on time
    assert set(m) == {"generator", "prompt", "output", "rate_per_s",
                      "after_window", "trace_seconds", "why"}
    assert m["generator"] == "open_loop" and m["after_window"] == "stop"
    assert m["prompt"] == {"median": 1536, "sigma": 0.7, "min": 256,
                           "max": 6144}
    assert m["output"] == {"median": 192, "sigma": 0.7, "min": 32,
                           "max": 768}
    assert m["trace_seconds"] == 2.0
    a = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=50176)
    b = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=50176)
    assert len(a) == round(m["rate_per_s"] * 35) and len(a) >= 60
    assert sum(r.due_s == 0.0 for r in a) <= 1
    assert max(r.due_s for r in a) < 35.0
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    sizes = [r.prompt.size for r in a]
    assert min(sizes) >= 256 and max(sizes) <= 6144
    assert 0.8 * 1536 <= np.median(sizes) <= 1.2 * 1536
    # ids from the held half of the vocabulary
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50176 for r in a)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_every_seed_offers_the_same_work_at_the_same_times(real, seed):
    """Seeded orders spread the cell by 7.7 % between the quartiles: the
    order of arrival is the cell's (the generator's at `ARRIVALS_SEED`),
    the ids are the seed's."""
    driver = manifest_lib.driver_module("serve_granite")
    m = real.traffic(MIX)
    one = traffic_lib.generate(m, driver.ARRIVALS_SEED, 35.0, vocab=50176)
    a = driver.offered(m, seed, 35.0, 50176)
    b = driver.offered(m, seed, 35.0, 50176)
    other = driver.offered(m, seed + 1, 35.0, 50176)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in one] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in other]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, other))
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 50176 for r in a)


def test_sweep_wrapper_names_the_driver_and_puts_it_back(monkeypatch):
    from benchmarks import sweep, sweep_granite

    seen = {}

    def fake_main(argv):
        seen["driver"] = manifest_lib.driver_module("serve")
        return 0

    before = manifest_lib.driver_module
    monkeypatch.setattr(sweep, "main", fake_main)
    assert sweep_granite.main([]) == 0
    assert seen["driver"].build_server.__module__.endswith("serve_granite")
    assert callable(seen["driver"].serve_window)
    assert manifest_lib.driver_module is before


# ---------------------------------------------------------------------------
# the toy twin through run_cell
# ---------------------------------------------------------------------------

def _run(seed=2 ** 31 + 11, seconds=2.0, control=False, tracer=None):
    out = io.StringIO()
    line = runner.run_cell(
        TINY, "tiny-granite-over", seed, seconds, tracer, jax.devices(),
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
    hybrid = notes["hybrid"]
    assert hybrid["moe_pairs"] > hybrid["moe_pairs_held"] > 0
    assert hybrid["ssm_state_bytes_touched"] > 0
    assert hybrid["kv_cells_read"] > 0
    flips = notes["routing_flips"]
    assert 0 <= flips["bf16_for_float32"] < flips["of_routings"]
    assert flips["control_for_float32"] > flips["bf16_for_float32"]
    # the control: the reference one precision down is not correct
    assert [c["fails_as_it_must"] for c in tagged["control"]] == [True]


def test_toy_twin_traced_carries_every_new_metric(recorded_trace):
    line, _ = _run(tracer=recorded_trace)
    assert line["correct"] is True
    assert set(line["metrics"]) == GRAN_METRICS | {"compile_s"}
    value = lambda name: line["metrics"][name]["value"]
    assert 25.0 < value("moe_held_share_pct.gran") < 75.0
    assert 1.0 <= value("moe_busiest_over_mean.gran") <= 4.0
    assert value("decode_tick_ms.gran") > 0
    assert value("prefill_ms_per_ktoken.gran") > 0
    assert 0.0 < value("decode_least_bytes_pct.gran")
    assert 0.0 < value("rows_per_tick.gran") <= 4.0
    assert 0.0 < value("syncs_per_token.gran") < 1.0


def test_readers_find_nothing_in_a_program_without_the_counters(real):
    obs = {"counters": {"generated": 10, "rounds": 5, "decode_ns": 10 ** 9,
                        "decode_least_bytes": 10 ** 9},
           "device_kind": "TPU v5 lite",
           "config": {"num_local_experts": 36}}
    for name in ("moe_held_share_pct.gran", "moe_busiest_over_mean.gran",
                 "decode_least_bytes_pct.gran",
                 "prefill_ms_per_ktoken.gran"):
        assert real.metric_reader(name)(obs) is None
    assert real.metric_reader("rows_per_tick.gran")(obs) == 2.0
    obs["counters"].update(moe_pairs=400, moe_pairs_held=180,
                           moe_pairs_busiest=10,
                           ssm_state_bytes_touched=1)
    assert real.metric_reader("moe_held_share_pct.gran")(obs) == 45.0
    assert real.metric_reader("moe_busiest_over_mean.gran")(obs) == 2.0
    assert real.metric_reader("decode_least_bytes_pct.gran")(obs) > 0


# the timed path broken three ways: each ends `correct: false`
def _a_served_token_altered(monkeypatch):
    from tfde_tpu.inference import server

    real_fetch = server._fetch

    def altered(tree):
        out = real_fetch(tree)
        if isinstance(out, tuple) and len(out) == 3:   # the scan's tokens
            toks, emitted, routed = out
            toks = np.array(toks)
            toks[:, 0] = (toks[:, 0] + 1) % 96
            return toks, emitted, routed
        return out

    monkeypatch.setattr(server, "_fetch", altered)


def _the_state_not_advanced_in_decode(monkeypatch):
    from tfde_tpu.ops import ssm

    real_step = ssm.decode_step

    def stale(xbc, dt, a_log, d, state, live, shape):
        return real_step(xbc, dt, a_log, d, state, jnp.zeros_like(live),
                         shape)

    monkeypatch.setattr(ssm, "decode_step", stale)


def _pairs_of_absent_experts_given_to_a_held_one(monkeypatch):
    from tfde_tpu.models import moe

    real_uncapped = moe.MoEMlp._uncapped

    def wrapped(self, x, gate_vals, gate_idx, lo, hi):
        return real_uncapped(self, x, gate_vals, gate_idx % (hi - lo), lo,
                             hi)

    monkeypatch.setattr(moe.MoEMlp, "_uncapped", wrapped)


@pytest.mark.parametrize("break_it", [
    _a_served_token_altered, _the_state_not_advanced_in_decode,
    _pairs_of_absent_experts_given_to_a_held_one])
def test_a_broken_timed_path_is_not_correct(monkeypatch, break_it):
    break_it(monkeypatch)
    # the batcher's programs are jitted by the model: one traced before
    # the break (or with it) must not serve another test
    jax.clear_caches()
    try:
        line, tagged = _run()
    finally:
        jax.clear_caches()
    assert line["correct"] is False
    failed = {c["name"] for c in tagged["compared"] if not c["ok"]}
    assert failed == {"served_token_gap_max"}
