"""The configuration `qwen3-next-80b-serve-32k`, its driver and its cell's
files: the real manifest stays consistent with the cell added, the published
widths are kept and the cut is written down, what the parameter, byte and
operation counts come to, and a toy twin of the configuration
(fixtures/tiny_qwen3next) runs through `run_cell` on the CPU, traced and
untraced, and ends not correct when the timed path is broken."""

import io
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import gdn_flops
from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import traffic as traffic_lib
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = Manifest(os.path.join(HERE, "fixtures", "tiny_qwen3next"))
CONFIG, CELL, MIX = ("qwen3-next-80b-serve-32k",
                     "qwen3next-serve-longchat-over", "longchat-poisson-over")
GDN_METRICS = {"device_idle_pct.gdn", "rows_per_tick.gdn",
               "syncs_per_token.gdn", "decode_tick_ms.gdn",
               "prefill_ms_per_ktoken.gdn", "decode_least_bytes_pct.gdn",
               "prefill_mfu_pct.gdn", "cache_state_share_pct.gdn",
               "moe_busiest_over_mean.gdn", "moe_touched_pct.gdn"}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/
# config.json as the catalog of public architectures holds it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent_with_the_cell(real):
    assert check(real) == []
    assert CELL in real.cells and len(real.cells) >= 10
    assert CONFIG in real.configs and len(real.configs) >= 7
    assert check(TINY) == []
    chips = [w["chips"] for w in real.data["workloads"]]
    assert real.cell(CELL)["chips"] == 1
    assert chips.count(4) <= max(1, len(chips) // 4)


def test_the_cell_finds_its_files(real):
    w = real.cell(CELL)
    assert (w["config"], w["traffic"]) == (CONFIG, MIX)
    cfg = real.config(CONFIG)
    assert {"source", "reduced", "published", "assumed", "deployment",
            "deployment_share", "driver", "reference", "correct"} <= set(cfg)
    assert (cfg["driver"], cfg["reference"]) == ("serve_qwen3next",
                                                 "qwen3_next")
    manifest_lib.driver_module(cfg["driver"]).run
    manifest_lib.reference_module(cfg["reference"]).served_token_gaps
    names = {m["name"] for m in real.cell_metrics(CELL, "end_to_end")}
    assert names >= {"serve_tokens_per_s", "setup_s"}
    mix = real.traffic(MIX)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= \
        cfg["batcher"]["max_len"] == cfg["max_position_embeddings"]
    assert mix["prompt"]["max"] <= max(
        b for b in cfg["batcher"]["prompt_buckets"]
        if b < cfg["batcher"]["max_len"])
    # every bucket is whole chunks of the delta rule and tiles the flash
    # forward
    assert all(b % 2048 == 0 for b in cfg["batcher"]["prompt_buckets"])


def test_the_cell_reports_the_metrics_the_issue_names(real):
    assert {m["name"] for m in real.cell_metrics(CELL, "per_layer")} >= \
        GDN_METRICS | {"compile_s"}
    for name in GDN_METRICS:
        entry = real.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
        assert callable(real.metric_reader(name))
    # no place in a list is asserted: the next PR appends after this cell
    assert CELL in real.end_to_end["serve_tokens_per_s"]["workloads"]
    assert {w["name"] for w in real.data["workloads"]} >= {
        "gpt2m-train-s4096-1chip", "gpt2l-serve-chat-r80",
        "gpt2l-serve-chat-over", "gpt2m-train-s4096-4chip",
        "gpt2l-serve-long-over", "evabyte-serve-longdoc-over",
        "granite4h-serve-rag-over", "smallthinker-serve-mixed-over",
        "sarvam105b-serve-longctx-over"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_keys_are_kept(real, key):
    cfg = real.config(CONFIG)
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_the_share_of_one_of_two_chips(real):
    cfg = real.config(CONFIG)
    entry = real.configs[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    # the floors: a whole period and four layers, at least eight experts a
    # layer, at least an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (4, 256, 75968, 32768)
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["deployment_share"] == {
        "chips_per_layer": 2, "experts": [0, 256],
        "vocabulary_rows": [0, 75968]}
    assert "twelve pipeline stages" in cfg["deployment"]
    # the readings the config has no key for, each with its reason
    for reading in ("chunk", "intermediate_size", "no_mtp", "rotary",
                    "projection_columns", "weights", "state"):
        assert len(cfg["assumed"][reading]) > 60
    assert "float32" in cfg["precision"] and "bfloat16" in cfg["precision"]


def test_the_parameters_are_reckoned_from_the_file(real):
    """3,677,613,120: four layers of 805,306,368 held, 4,196,352 of router
    and shared expert and 4,096 of norms; three delta-rule mixers of
    33,718,464 and one attention of 27,263,488; 311,164,928 of embedding
    and head; 2,048 of final norm."""
    cfg = real.config(CONFIG)
    assert gdn_flops.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    assert cfg["num_experts"] * gdn_flops.expert_params(cfg) == 805_306_368
    assert gdn_flops.router_and_shared_params(cfg) == 4_196_352
    assert (gdn_flops.delta_projection_params(cfg)
            + gdn_flops.delta_small_params(cfg)) == 33_718_464
    assert gdn_flops.attention_projection_params(cfg) + 2 * 256 == 27_263_488
    assert gdn_flops.layers(cfg) == (3, 1)
    total = (4 * (805_306_368 + 4_196_352 + 4_096) + 3 * 33_718_464
             + 27_263_488 + 311_164_928 + 2_048)
    assert gdn_flops.num_params(cfg) == total == 3_677_613_120
    ref = manifest_lib.reference_module("qwen3_next")
    assert ref.num_params(ref.dims_of(cfg)) == total
    assert round(2 * total / 1e9, 2) == 7.36
    # a row's cache: the one attention layer's K and V at 32,768 positions,
    # three states and three tails
    cells = 2 * 2 * 256 * 2 * 32768
    state = 3 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert (cells, state) == (67_108_864, 6_438_912)
    assert round(48 * (cells + state) / 1e9, 2) == 3.53


def test_driver_builds_the_published_blocks(real):
    cfg = real.config(CONFIG)
    model = manifest_lib.driver_module("serve_qwen3next").build_model(cfg)
    assert (model.hidden_size, model.num_heads, model.num_kv_heads,
            model.head_dim, model.mlp_dim, model.vocab_size, model.depth) == (
        2048, 16, 2, 256, 512, 75968, 4)
    assert model.mixers == ("gated_delta",) * 3 + ("attention",)
    assert (model.gdn.key_heads, model.gdn.value_heads, model.gdn.key_dim,
            model.gdn.value_dim, model.gdn.conv, model.gdn.chunk) == (
        16, 32, 128, 128, 4, 64)
    assert (model.gdn.conv_channels, model.gdn.in_features) == (8192, 12288)
    assert (model.num_experts, model.experts_per_token,
            model.moe_held_experts, model.moe_capacity_factor) == (
        512, 10, (0, 256), None)
    assert (model.moe_score, model.moe_normalize_topk,
            model.moe_shared_expert_dim, model.moe_shared_expert_gated) == (
        "softmax", True, 512, True)
    assert (model.position, model.rope_theta, model.rope_dim,
            model.rope_scaling) == ("rope", 10_000_000.0, 64, None)
    assert model.attn_output_gate and model.qk_norm
    assert model.norm == "rms" and model.norm_unit_offset
    assert not model.tie_embeddings and not model.use_bias
    ref = manifest_lib.reference_module("qwen3_next")
    dims = ref.dims_of(cfg)
    tree = jax.eval_shape(lambda: ref.to_program_params(
        jax.eval_shape(lambda: ref.make_weights(1, dims))))
    mine = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda s: s.shape, tree) == \
        jax.tree.map(lambda s: s.shape, mine)
    assert {str(s.dtype) for s in jax.tree.leaves(tree)} == {"bfloat16"}
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree)) == \
        3_677_613_120
    # the cache as the batcher lays it out
    from tfde_tpu.inference.decode import init_cache

    rows = cfg["batcher"]["batch_size"]
    cache = jax.eval_shape(lambda: init_cache(model, rows, 32768))
    for l in range(3):
        leaves = cache["decoder"][f"block_{l}"]["delta"]
        assert (leaves["delta_state"].shape, str(
            leaves["delta_state"].dtype)) == ((rows, 32, 128, 128),
                                              "float32")
        assert (leaves["conv_tail"].shape, str(leaves["conv_tail"].dtype)) \
            == ((rows, 3, 8192), "bfloat16")
    attn = cache["decoder"]["block_3"]["attn"]
    assert attn["cached_key"].shape == attn["cached_value"].shape == (
        rows, 32768, 2, 256)
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache) if leaf.ndim >= 3)
    assert held == rows * (67_108_864 + 6_438_912)


def test_the_bytes_and_operations_counted_are_the_mathematics(real):
    cfg = real.config(CONFIG)
    # the dense weights of a tick: everything but the held experts and the
    # embedding, 2 B a parameter
    dense = 2 * (3_677_613_120 - 75968 * 2048 - 4 * 805_306_368)
    assert gdn_flops.dense_bytes(cfg) == dense == 601_610_368
    # two ticks that touched 300 (layer, expert) slots, 10,000 B of live
    # cells and 5,000 B of state, read and written
    assert gdn_flops.decode_least_bytes(cfg, 2, 300, 10_000, 5_000) == (
        2 * dense + 300 * 2 * 3_145_728 + 10_000 + 2 * 5_000)
    # a token outside the experts: three delta-rule layers (projections,
    # taps, the rule at 7 K V a value head), the attention's projections,
    # four routers over 512 and four gated shared experts
    rule = 7 * 32 * 128 * 128
    assert rule == 3_670_016
    assert gdn_flops.token_flops_outside_experts(cfg) == (
        3 * (2.0 * 33_685_504 + 2 * 4 * 8192 + rule)
        + 2.0 * 27_262_976 + 4 * 2.0 * 4_196_352)
    assert gdn_flops.attention_flops(1, cfg) == 16 * 4 * 256 == 16_384
    # 8,192 tokens: 2.5 T outside the experts, 0.55 T of attention, and
    # five held pairs a token and layer in the mean (10 of 512 over 256)
    n = 8192
    flops = gdn_flops.prefill_flops(cfg, n, n * (n + 1) // 2, 5 * 4 * n)
    assert flops == pytest.approx(
        n * gdn_flops.token_flops_outside_experts(cfg)
        + n * (n + 1) // 2 * 16_384 + 5 * 4 * n * 2 * 3_145_728)
    assert 4.0e12 < flops < 4.2e12


def test_traffic_of_the_cell(real):
    m = real.traffic(MIX)
    assert set(m) == {"generator", "prompt", "output", "rate_per_s",
                      "after_window", "trace_seconds", "why"}
    assert m["generator"] == "open_loop" and m["after_window"] == "stop"
    assert m["prompt"] == {"median": 8192, "sigma": 0.8, "min": 1024,
                           "max": 30720}
    assert m["output"] == {"median": 384, "sigma": 0.7, "min": 64,
                           "max": 1536}
    assert m["trace_seconds"] >= 4.0
    assert real.config(CONFIG)["feed"]["max_unadmitted"] in (1, 2)
    a = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=75968)
    b = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=75968)
    assert len(a) == round(m["rate_per_s"] * 35) and len(a) >= 35
    assert max(r.due_s for r in a) < 35.0
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    sizes = np.array([r.prompt.size for r in a])
    assert sizes.min() >= 1024 and sizes.max() <= 30720
    assert 0.75 * 8192 <= np.median(sizes) <= 1.25 * 8192
    budgets = np.array([r.max_new_tokens for r in a])
    assert budgets.min() >= 64 and budgets.max() <= 1536
    # answers four times cell 9's: the scan keeps the rows' state turning
    assert 0.75 * 384 <= np.median(budgets) <= 1.25 * 384
    assert all(0 <= r.prompt.min() and r.prompt.max() < 75968 for r in a)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_every_seed_offers_the_same_work_at_the_same_times(real, seed):
    """The order of arrival is the cell's (the generator's at
    `ARRIVALS_SEED`), the ids are the seed's, from the held slice."""
    driver = manifest_lib.driver_module("serve_qwen3next")
    m = real.traffic(MIX)
    one = traffic_lib.generate(m, driver.ARRIVALS_SEED, 35.0, vocab=75968)
    a = driver.offered(m, seed, 35.0, 75968)
    other = driver.offered(m, seed + 1, 35.0, 75968)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in one] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in other]
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, other))
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 75968 for r in a)


def test_sweep_wrapper_names_the_driver_and_puts_it_back(monkeypatch):
    from benchmarks import sweep, sweep_qwen3next

    seen = {}

    def fake_main(argv):
        seen["driver"] = manifest_lib.driver_module("serve")
        return 0

    before = manifest_lib.driver_module
    monkeypatch.setattr(sweep, "main", fake_main)
    assert sweep_qwen3next.main([]) == 0
    assert seen["driver"].build_server.__module__.endswith("serve_qwen3next")
    assert callable(seen["driver"].serve_window)
    assert manifest_lib.driver_module is before


def test_a_program_without_the_mixer_stops_at_once(real, monkeypatch):
    """What the parent commit does with this cell: `GPT` has no field for
    the delta rule's widths nor for the output gate, so the driver stops
    where it builds the model, before a weight is drawn."""
    from tfde_tpu.models import gpt

    driver = manifest_lib.driver_module("serve_qwen3next")
    fields = {f for f in gpt.GPT.__dataclass_fields__
              if f not in ("gdn", "attn_output_gate")}

    class Parent:
        def __init__(self, **kw):
            unknown = set(kw) - fields
            if unknown:
                raise TypeError(f"unexpected keyword argument {unknown}")

    monkeypatch.setattr(gpt, "GPT", Parent)
    with pytest.raises((TypeError, ImportError), match="gdn|gated_delta"):
        driver.build_model(real.config(CONFIG))


# ---------------------------------------------------------------------------
# the toy twin through run_cell
# ---------------------------------------------------------------------------

def _run(seed=2 ** 31 + 11, seconds=2.0, control=False, tracer=None):
    out = io.StringIO()
    line = runner.run_cell(
        TINY, "tiny-qwen3next-over", seed, seconds, tracer, jax.devices(),
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
    assert set(line["metrics"]) >= {"serve_tokens_per_s", "setup_s"}
    notes = tagged["notes"][0]
    assert notes["window_compiles"] == 0
    delta = notes["delta"]
    assert 0 < delta["moe_pairs_held"] < delta["moe_pairs"]
    assert delta["gdn_steps"] > 0 < delta["gdn_chunks"]
    assert delta["gdn_state_bytes"] > 0 < delta["kv_cell_bytes"]
    assert delta["kv_pairs_prefilled"] > 0
    flips = notes["routing_flips"]
    assert 0 <= flips["bf16_for_float32"] < flips["control_for_float32"]
    # the controls: the reference one precision down, the rule without
    # what it read back and the attention ungated are not correct
    assert [(c["name"], c["fails_as_it_must"]) for c in tagged["control"]] \
        == [("served_token_gap_mean", True),
            ("served_token_gap_mean.without_delta_term", True),
            ("served_token_gap_mean.without_output_gate", True)]
    gaps, lowered = notes["served_token_gaps"], notes["control_token_gaps"]
    assert gaps["n"] == lowered["n"] == notes["checked_tokens"]
    by_name = {c["name"]: c for c in tagged["compared"]}
    assert by_name["served_token_gap_mean"]["value"] == gaps["mean"]
    assert by_name["served_tokens_far_off"]["value"] == gaps["far_off"] == 0
    assert tagged["control"][0]["value"] == lowered["mean"] > gaps["max"]
    assert all(s["n"] == gaps["n"]
               for s in notes["dropped_term_token_gaps"].values())


def test_toy_twin_traced_carries_every_new_metric(recorded_trace):
    line, _ = _run(tracer=recorded_trace)
    assert line["correct"] is True
    assert set(line["metrics"]) >= GDN_METRICS | {"compile_s"}
    value = lambda name: line["metrics"][name]["value"]
    assert all(value(name) is not None for name in GDN_METRICS)
    # shares of something: none can pass 100
    for name in ("device_idle_pct.gdn", "decode_least_bytes_pct.gdn",
                 "cache_state_share_pct.gdn", "prefill_mfu_pct.gdn",
                 "moe_touched_pct.gdn"):
        assert 0.0 < value(name) <= 100.0
    assert 1.0 <= value("moe_busiest_over_mean.gdn") <= 8.0
    assert value("decode_tick_ms.gdn") > 0
    assert value("prefill_ms_per_ktoken.gdn") > 0
    assert 0.0 < value("rows_per_tick.gdn") <= 5.0
    assert 0.0 < value("syncs_per_token.gdn") < 1.0


@pytest.mark.parametrize("name", sorted(GDN_METRICS))
def test_a_reader_finds_nothing_in_a_program_without_the_counters(real,
                                                                  name):
    """What another cell's program (or the parent's) hands over: no reader
    of this cell reads a number from it, and none raises."""
    cfg = real.config(CONFIG)
    obs = {"counters": {"generated": 10, "rounds": 5, "syncs": 2,
                        "decode_ns": 10 ** 9, "prefill_ns": 10 ** 9,
                        "prefill_tokens": 1000, "prefill_waves": 3,
                        "decode_least_bytes": 10 ** 9, "moe_pairs": 40000,
                        "moe_pairs_held": 20000, "moe_pairs_busiest": 400,
                        "moe_experts_touched": 2048},
           "trace": {"busy_s": 1.0, "window_s": 2.0},
           "device_kind": "TPU v5 lite", "config": cfg}
    read = real.metric_reader(name)
    assert read(obs) is None
    assert read({"counters": {}, "config": {}}) is None
    obs["counters"].update(gdn_steps=720, gdn_chunks=96,
                           gdn_state_bytes=3 * 10 ** 8,
                           kv_cell_bytes=10 ** 8,
                           kv_pairs_prefilled=1000 * 1001 // 2)
    # every pair routed was a prefill's: 1000 tokens x 4 layers x 10
    flops = (1000 * gdn_flops.token_flops_outside_experts(cfg)
             + 1000 * 1001 // 2 * 16_384 + 20000 * 2 * 3_145_728)
    least = (5 * 601_610_368 + 2048 * 2 * 3_145_728 + 10 ** 8
             + 2 * 3 * 10 ** 8)
    assert read(obs) == pytest.approx({
        "device_idle_pct.gdn": 50.0, "rows_per_tick.gdn": 2.0,
        "syncs_per_token.gdn": 0.2, "decode_tick_ms.gdn": 200.0,
        "prefill_ms_per_ktoken.gdn": 1024.0,
        "decode_least_bytes_pct.gdn": 100 * least / 819e9,
        "cache_state_share_pct.gdn": 75.0,
        "prefill_mfu_pct.gdn": 100 * flops / 197e12,
        "moe_busiest_over_mean.gdn": 400 * 256 / 20000,
        "moe_touched_pct.gdn": 100 * 2048 / (256 * 4 * 8),
    }[name])


# the timed path broken ends `correct: false`
def test_a_broken_timed_path_is_not_correct(monkeypatch):
    from tfde_tpu.ops import gated_delta

    real_step = gated_delta.decode_step
    monkeypatch.setattr(
        gated_delta, "decode_step",
        lambda qkv, beta, g, *a: real_step(qkv, beta, 0 * g, *a))
    # the batcher's programs are jitted by the model: one traced before
    # the break (or with it) must not serve another test
    jax.clear_caches()
    try:
        line, tagged = _run()
    finally:
        jax.clear_caches()
    assert line["correct"] is False
    failed = {c["name"] for c in tagged["compared"] if not c["ok"]}
    assert "served_token_gap_mean" in failed
    assert failed <= {"served_token_gap_mean", "served_tokens_far_off"}
