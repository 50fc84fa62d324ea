"""The configuration `smallthinker-21b-serve-16k`, its driver and its cell's
files: the real manifest stays consistent with the cell added, the published
widths are kept and the cut is written down, and a toy twin of the
configuration (fixtures/tiny_smallthinker) runs through `run_cell` on the
CPU, traced and untraced, and ends not correct when the timed path is
broken."""

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
TINY = Manifest(os.path.join(HERE, "fixtures", "tiny_smallthinker"))
CONFIG, CELL, MIX = ("smallthinker-21b-serve-16k",
                     "smallthinker-serve-mixed-over", "mixed-poisson-over")
SMT_METRICS = {"device_idle_pct.smt", "rows_per_tick.smt",
               "syncs_per_token.smt", "decode_tick_ms.smt",
               "prefill_ms_per_ktoken.smt", "decode_least_bytes_pct.smt",
               "kv_window_share_pct.smt", "moe_busiest_over_mean.smt",
               "moe_touched_pct.smt"}
# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/
# config.json as the catalog of public architectures holds it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent_with_the_cell(real):
    assert check(real) == []
    assert CELL in real.cells and len(real.cells) == 8
    assert check(TINY) == []
    # one four-chip cell, and at most a quarter of the eight
    chips = [w["chips"] for w in real.data["workloads"]]
    assert chips.count(4) == 1 and real.cell(CELL)["chips"] == 1
    assert chips.count(4) <= max(1, len(chips) // 4)
    assert set(chips) == {1, 4}


def test_the_cell_finds_its_files(real):
    w = real.cell(CELL)
    assert (w["config"], w["traffic"]) == (CONFIG, MIX)
    cfg = real.config(CONFIG)
    assert {"source", "reduced", "published", "assumed", "deployment",
            "deployment_share", "driver", "reference", "correct"} <= set(cfg)
    assert (cfg["driver"], cfg["reference"]) == ("serve_smallthinker",
                                                 "smallthinker")
    manifest_lib.driver_module(cfg["driver"]).run
    manifest_lib.reference_module(cfg["reference"]).served_token_gaps
    names = [m["name"] for m in real.cell_metrics(CELL, "end_to_end")]
    assert names == ["serve_tokens_per_s", "setup_s"]
    mix = real.traffic(MIX)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        cfg["batcher"]["max_len"] == cfg["max_position_embeddings"]
    assert mix["prompt"]["max"] <= max(
        b for b in cfg["batcher"]["prompt_buckets"]
        if b < cfg["batcher"]["max_len"])
    # every bucket tiles the flash forward and the prefill's query blocks
    assert all(b % 512 == 0 for b in cfg["batcher"]["prompt_buckets"])


def test_the_cell_reports_the_metrics_the_issue_names(real):
    assert {m["name"] for m in real.cell_metrics(CELL, "per_layer")} == \
        SMT_METRICS | {"compile_s"}
    for name in SMT_METRICS:
        entry = real.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert callable(real.metric_reader(name))
    assert CELL in real.end_to_end["serve_tokens_per_s"]["workloads"]
    # appended: what the benchmark had before stands first, in its order
    # (no place is asserted for THIS cell: the next PR appends after it)
    before = ["gpt2m-train-s4096-1chip", "gpt2l-serve-chat-r80",
              "gpt2l-serve-chat-over", "gpt2m-train-s4096-4chip",
              "gpt2l-serve-long-over", "evabyte-serve-longdoc-over",
              "granite4h-serve-rag-over"]
    assert [w["name"] for w in real.data["workloads"]][:7] == before
    names = [m["name"] for m in real.data["per_layer"]]
    assert min(names.index(n) for n in SMT_METRICS) > names.index(
        "decode_least_bytes_pct.gran")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_keys_are_kept(real, key):
    cfg = real.config(CONFIG)
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_depth_alone(real):
    cfg = real.config(CONFIG)
    entry = real.configs[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert cfg["source"] == entry["source"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["deployment_share"] == {
        "chips_per_layer": 1, "experts": [0, 64],
        "vocabulary_rows": [0, 151936]}
    # two whole periods, every kind of layer in its published ratio
    assert cfg["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert cfg["rope_layout"] == cfg["sliding_window_layout"]
    assert {"router_input", "no_bias_no_qk_norm", "no_secondary_experts",
            "weights", "cache", "batcher.max_len", "batcher.batch_size",
            "batcher.prompt_buckets", "batcher.scan_depth",
            "feed.max_unadmitted", "feed.arrivals",
            "correct.served_token_gap_p99"} <= set(cfg["assumed"])


def test_driver_builds_the_published_blocks(real):
    cfg = real.config(CONFIG)
    model = manifest_lib.driver_module("serve_smallthinker").build_model(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.mlp_dim, model.vocab_size, model.depth) == (
        2560, 28, 4, 128, 768, 151936, 8)
    assert model.layer_windows() == (None, 4096, 4096, 4096) * 2
    assert model.rope_layers == (0, 1, 1, 1) * 2
    assert (model.position, model.rope_theta, model.rope_scaling) == (
        "rope", 1.5e6, None)
    assert (model.num_experts, model.experts_per_token, model.moe_every,
            model.moe_held_experts, model.moe_capacity_factor) == (
        64, 6, 1, None, None)
    assert (model.mlp_act, model.moe_router_pre_attention,
            model.moe_normalize_topk, model.moe_shared_expert_dim) == (
        "reglu", True, True, None)
    assert not model.tie_embeddings and not model.use_bias
    assert not model.qk_norm and model.norm == "rms"
    ref = manifest_lib.reference_module("smallthinker")
    dims = ref.dims_of(cfg)
    tree = jax.eval_shape(lambda: ref.to_program_params(
        jax.eval_shape(lambda: ref.make_weights(1, dims))))
    mine = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda s: s.shape, tree) == \
        jax.tree.map(lambda s: s.shape, mine)
    assert {str(s.dtype) for s in jax.tree.leaves(tree)} == {"bfloat16"}
    # the cache as the batcher lays it out: two slabs and six rings
    from tfde_tpu.inference.decode import init_cache

    cache = jax.eval_shape(lambda: init_cache(model, 32, 16384, rolling=True))
    cells = [cache["decoder"][f"block_{l}"]["attn"]["cached_key"].shape
             for l in range(8)]
    assert cells == [(32, 16384, 4, 128), (32, 4096, 4, 128),
                     (32, 4096, 4, 128), (32, 4096, 4, 128)] * 2
    assert sum(2 * int(np.prod(s)) * 2 for s in cells) == 3_758_096_384


def test_traffic_of_the_cell(real):
    m = real.traffic(MIX)
    # the issue's traffic and nothing else: Poisson arrivals into a window
    # that opens on an empty batcher and closes on time
    assert set(m) == {"generator", "prompt", "output", "rate_per_s",
                      "after_window", "trace_seconds", "why"}
    assert m["generator"] == "open_loop" and m["after_window"] == "stop"
    assert m["prompt"] == {"median": 3072, "sigma": 1.0, "min": 256,
                           "max": 14336}
    assert m["output"] == {"median": 256, "sigma": 0.7, "min": 64,
                           "max": 1024}
    assert m["trace_seconds"] == 2.0
    a = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=151936)
    b = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=151936)
    assert len(a) == round(m["rate_per_s"] * 35) and len(a) >= 60
    assert max(r.due_s for r in a) < 35.0
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    sizes = np.array([r.prompt.size for r in a])
    assert sizes.min() >= 256 and sizes.max() <= 14336
    assert 0.8 * 3072 <= np.median(sizes) <= 1.2 * 3072
    # short and long in one queue: about two fifths pass the window, about
    # a seventh stay under 1,024
    assert 0.3 < (sizes > 4096).mean() < 0.5
    assert 0.08 < (sizes < 1024).mean() < 0.2
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151936 for r in a)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_every_seed_offers_the_same_work_at_the_same_times(real, seed):
    """The order of arrival is the cell's (the generator's at
    `ARRIVALS_SEED`), the ids are the seed's."""
    driver = manifest_lib.driver_module("serve_smallthinker")
    m = real.traffic(MIX)
    one = traffic_lib.generate(m, driver.ARRIVALS_SEED, 35.0, vocab=151936)
    a = driver.offered(m, seed, 35.0, 151936)
    b = driver.offered(m, seed, 35.0, 151936)
    other = driver.offered(m, seed + 1, 35.0, 151936)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in one] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in other]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, other))
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 151936 for r in a)


def test_the_sample_meets_the_windows_edge_both_ways():
    driver = manifest_lib.driver_module("serve_smallthinker")

    class Rec:
        def __init__(self, prompt, served):
            self.request = type("R", (), {"prompt": np.zeros(prompt)})()
            self.tokens = np.zeros(served)

    # token i is fed at position prompt + i, the last one never: 30 + 3
    # feeds positions 30, 31; 30 + 4 feeds 32 as well, a multiple of 8
    assert not driver._decode_crossed_window(Rec(30, 3), 8)
    assert driver._decode_crossed_window(Rec(30, 4), 8)
    assert driver._decode_crossed_window(Rec(8, 2), 8)
    assert not driver._decode_crossed_window(Rec(9, 7), 8)
    assert driver._prompt_over_window(Rec(9, 1), 8)
    assert not driver._prompt_over_window(Rec(8, 1), 8)
    done = [Rec(41, 2), Rec(3, 2), Rec(4, 2), Rec(5, 20), Rec(2, 2)]
    sample = driver.edge_sample(done, 1, 1, 8)
    assert sample[0] is done[0]            # the longest
    assert done[3] in sample and len(sample) == 2


def test_sweep_wrapper_names_the_driver_and_puts_it_back(monkeypatch):
    from benchmarks import sweep, sweep_smallthinker

    seen = {}

    def fake_main(argv):
        seen["driver"] = manifest_lib.driver_module("serve")
        return 0

    before = manifest_lib.driver_module
    monkeypatch.setattr(sweep, "main", fake_main)
    assert sweep_smallthinker.main([]) == 0
    assert seen["driver"].build_server.__module__.endswith(
        "serve_smallthinker")
    assert callable(seen["driver"].serve_window)
    assert manifest_lib.driver_module is before


# ---------------------------------------------------------------------------
# the toy twin through run_cell
# ---------------------------------------------------------------------------

def _run(seed=2 ** 31 + 11, seconds=2.0, control=False, tracer=None):
    out = io.StringIO()
    line = runner.run_cell(
        TINY, "tiny-smallthinker-over", seed, seconds, tracer, jax.devices(),
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
    assert notes["checked_prompts_over_window"] >= 1
    ring = notes["ring"]
    assert ring["moe_pairs"] == ring["moe_pairs_held"] > 0
    assert ring["kv_window_cells_read"] > 0 < ring["kv_full_cells_read"]
    assert ring["kv_window_wraps"] > 0
    flips = notes["routing_flips"]
    assert 0 <= flips["bf16_for_float32"] < flips["of_routings"]
    assert flips["control_for_float32"] > flips["bf16_for_float32"]
    # the control: the reference one precision down is not correct
    assert [c["fails_as_it_must"] for c in tagged["control"]] == [True]
    # what is compared is the summary's quantile, of every checked token
    gaps, lowered = notes["served_token_gaps"], notes["control_token_gaps"]
    assert gaps["n"] == lowered["n"] == notes["checked_tokens"]
    by_name = {c["name"]: c for c in tagged["compared"]}
    assert by_name["served_token_gap_p99"]["value"] == gaps["quantile"]
    assert by_name["served_tokens_far_off"]["value"] == gaps["far_off"] == 0
    assert tagged["control"][0]["value"] == lowered["quantile"] > gaps["max"]


def test_the_gap_summary_pools_the_sample():
    """A hundredth of the tokens may lie anywhere under `far_off`: the
    quantile reads the body of the gaps, the count the ones no near-tie
    explains."""
    summary = manifest_lib.driver_module("serve_smallthinker").gap_summary
    body = np.linspace(0.0, 0.099, 100)
    got = summary([body[:40], body[40:]], far_off=1.0)
    assert got["n"] == 100 and got["far_off"] == 0
    assert got["quantile"] == pytest.approx(np.quantile(body, 0.99))
    assert got["max"] == pytest.approx(0.099)
    assert got["not_first_share"] == pytest.approx(0.99)
    one_altered = summary([body, np.array([4.2])], far_off=1.0)
    assert one_altered["far_off"] == 1
    assert one_altered["quantile"] < 0.1
    assert summary([], far_off=1.0) == {"n": 0, "quantile": 0.0,
                                        "far_off": 0, "max": 0.0}


def test_toy_twin_traced_carries_every_new_metric(recorded_trace):
    line, _ = _run(tracer=recorded_trace)
    assert line["correct"] is True
    assert set(line["metrics"]) == SMT_METRICS | {"compile_s"}
    value = lambda name: line["metrics"][name]["value"]
    # six rings of 8 cells beside two slabs of contexts of 10-90
    assert 0.0 < value("kv_window_share_pct.smt") < 75.0
    assert 1.0 <= value("moe_busiest_over_mean.smt") <= 8.0
    assert 0.0 < value("moe_touched_pct.smt") <= 100.0
    assert value("decode_tick_ms.smt") > 0
    assert value("prefill_ms_per_ktoken.smt") > 0
    assert 0.0 < value("decode_least_bytes_pct.smt")
    # a wave's first tokens count as generated, so a little over the rows
    assert 0.0 < value("rows_per_tick.smt") <= 5.0
    assert 0.0 < value("syncs_per_token.smt") < 1.0


@pytest.mark.parametrize("name", sorted(SMT_METRICS))
def test_a_reader_finds_nothing_in_a_program_without_the_counters(real,
                                                                  name):
    """What another cell's program (or the parent's) hands over: no reader
    of this cell reads a number from it, and none raises."""
    obs = {"counters": {"generated": 10, "rounds": 5, "syncs": 2,
                        "decode_ns": 10 ** 9, "prefill_ns": 10 ** 9,
                        "prefill_tokens": 1000, "prefill_waves": 3,
                        "decode_least_bytes": 10 ** 9, "moe_pairs": 400,
                        "moe_pairs_held": 180, "moe_pairs_busiest": 10,
                        "moe_experts_touched": 100},
           "trace": {"busy_s": 1.0, "window_s": 2.0},
           "device_kind": "TPU v5 lite",
           "config": {"moe_num_primary_experts": 64, "num_hidden_layers": 8}}
    read = real.metric_reader(name)
    assert read(obs) is None
    assert read({"counters": {}, "config": {}}) is None
    obs["counters"].update(kv_window_cells_read=300, kv_full_cells_read=100,
                           kv_window_wraps=1)
    assert read(obs) == {
        "device_idle_pct.smt": 50.0, "rows_per_tick.smt": 2.0,
        "syncs_per_token.smt": 0.2, "decode_tick_ms.smt": 200.0,
        "prefill_ms_per_ktoken.smt": 1024.0,
        "decode_least_bytes_pct.smt": pytest.approx(100 / 819.0),
        "kv_window_share_pct.smt": 75.0,
        "moe_busiest_over_mean.smt": pytest.approx(10 * 64 / 180),
        "moe_touched_pct.smt": pytest.approx(100 * 100 / (64 * 8 * 8)),
    }[name]


# the timed path broken: each ends `correct: false`
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


def _the_ring_returned_unchanged(monkeypatch):
    from tfde_tpu.models import transformer

    monkeypatch.setattr(transformer, "_ring_put",
                        lambda ring, new, pos: ring)


@pytest.mark.parametrize("break_it", [
    _a_served_token_altered, _the_ring_returned_unchanged])
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
    assert "served_token_gap_p99" in failed
    assert failed <= {"served_token_gap_p99", "served_tokens_far_off"}
