"""The configuration `sarvam-105b-serve-32k`, its driver and its cell's
files: the real manifest stays consistent with the cell added, the published
widths are kept and the cut is written down, what the operation counts come
to, and a toy twin of the configuration (fixtures/tiny_sarvam) runs through
`run_cell` on the CPU, traced and untraced, and ends not correct when the
timed path is broken."""

import io
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import run as runner
from benchmarks.lib import manifest as manifest_lib
from benchmarks.lib import mla_flops
from benchmarks.lib import traffic as traffic_lib
from benchmarks.lib.manifest import Manifest, check

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = Manifest(os.path.join(HERE, "fixtures", "tiny_sarvam"))
CONFIG, CELL, MIX = ("sarvam-105b-serve-32k",
                     "sarvam105b-serve-longctx-over", "longctx-poisson-over")
MLA_METRICS = {"device_idle_pct.mla", "rows_per_tick.mla",
               "syncs_per_token.mla", "decode_tick_ms.mla",
               "prefill_ms_per_ktoken.mla", "decode_least_bytes_pct.mla",
               "kv_latent_share_pct.mla", "prefill_mfu_pct.mla",
               "moe_busiest_over_mean.mla", "moe_touched_pct.mla"}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
# https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json as the
# catalog of public architectures holds it
PUBLISHED = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(manifest_lib.REPO_ROOT)


def test_manifest_is_consistent_with_the_cell(real):
    assert check(real) == []
    assert CELL in real.cells and len(real.cells) >= 9
    assert CONFIG in real.configs and len(real.configs) >= 6
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
    assert (cfg["driver"], cfg["reference"]) == ("serve_sarvam",
                                                 "sarvam_mla")
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
    # every bucket tiles the flash forward and the absorbed path's blocks
    assert all(b % 512 == 0 for b in cfg["batcher"]["prompt_buckets"])


def test_the_cell_reports_the_metrics_the_issue_names(real):
    assert {m["name"] for m in real.cell_metrics(CELL, "per_layer")} >= \
        MLA_METRICS | {"compile_s"}
    for name in MLA_METRICS:
        entry = real.per_layer[name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"
        assert callable(real.metric_reader(name))
    # no place in a list is asserted: the next PR appends after this cell
    assert CELL in real.end_to_end["serve_tokens_per_s"]["workloads"]
    assert {w["name"] for w in real.data["workloads"]} >= {
        "gpt2m-train-s4096-1chip", "gpt2l-serve-chat-r80",
        "gpt2l-serve-chat-over", "gpt2m-train-s4096-4chip",
        "gpt2l-serve-long-over", "evabyte-serve-longdoc-over",
        "granite4h-serve-rag-over", "smallthinker-serve-mixed-over"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_keys_are_kept(real, key):
    cfg = real.config(CONFIG)
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] != PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_the_share_of_one_of_four_chips(real):
    cfg = real.config(CONFIG)
    entry = real.configs[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == REDUCED
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json")
    # the floors: the leading dense layer and four routed ones, at least
    # eight experts a routed layer, at least an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (5, 32, 65536, 32768)
    assert cfg["deployment_share"] == {
        "chips_per_layer": 4, "experts": [0, 32],
        "vocabulary_rows": [0, 65536]}
    assert len(cfg["deployment"]) > 100
    # the three readings the config has no key for, each with its reason
    for reading in ("use_qk_norm", "router_score",
                    "no_group_limited_routing"):
        assert len(cfg["assumed"][reading]) > 100


def test_driver_builds_the_published_blocks(real):
    cfg = real.config(CONFIG)
    model = manifest_lib.driver_module("serve_sarvam").build_model(cfg)
    assert (model.hidden_size, model.num_heads, model.mlp_dim,
            model.moe_mlp_dim, model.vocab_size, model.depth) == (
        4096, 64, 16384, 2048, 65536, 5)
    assert tuple(model.mla) == (512, 128, 64, 128)
    assert (model.mla.cell, model.mla.query) == (576, 192)
    assert model.mixers == ("latent",) * 5
    assert model.mlps == ("dense", "experts", "experts", "experts",
                          "experts")
    assert (model.num_experts, model.experts_per_token,
            model.moe_held_experts, model.moe_capacity_factor) == (
        128, 8, (0, 32), None)
    assert (model.moe_score, model.moe_selection_bias,
            model.moe_routed_scale, model.moe_normalize_topk,
            model.moe_shared_expert_dim, model.moe_shared_expert_gated) == (
        "sigmoid", True, 2.5, True, 2048, False)
    assert (model.position, model.rope_theta) == ("rope", 10000.0)
    kind, factor, fast, slow, original, temperature, _ = model.rope_scaling
    assert (kind, factor, fast, slow, original) == ("yarn", 40, 32, 1, 4096)
    assert temperature == pytest.approx(1.3689, abs=5e-5)
    assert not model.tie_embeddings and not model.use_bias
    assert model.norm == "rms" and model.mlp_act == "swiglu"
    ref = manifest_lib.reference_module("sarvam_mla")
    dims = ref.dims_of(cfg)
    tree = jax.eval_shape(lambda: ref.to_program_params(
        jax.eval_shape(lambda: ref.make_weights(1, dims))))
    mine = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda s: s.shape, tree) == \
        jax.tree.map(lambda s: s.shape, mine)
    assert {str(s.dtype) for s in jax.tree.leaves(tree)} == {"bfloat16"}
    assert ref.num_params(dims) == sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(tree)) == 4_535_401_472
    # the cache as the batcher lays it out: 576 values a token a layer and
    # nothing per head: rows x 32,768 x 5 x 1,152 B
    from tfde_tpu.inference.decode import init_cache

    rows = cfg["batcher"]["batch_size"]
    cache = jax.eval_shape(lambda: init_cache(model, rows, 32768))
    for l in range(5):
        leaves = cache["decoder"][f"block_{l}"]["attn"]
        assert {k: v.shape for k, v in leaves.items()
                if k.startswith("cached_")} == {
            "cached_latent": (rows, 32768, 512),
            "cached_rope_key": (rows, 32768, 64)}
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache) if leaf.ndim == 3) == \
        rows * 32768 * 5 * 1152 == 3_019_898_880 * rows // 16


def test_the_operations_counted_are_the_mathematics(real):
    cfg = real.config(CONFIG)
    assert mla_flops.prefill_attention_flops(1, cfg) == 40_960
    assert mla_flops.decode_attention_flops(1, cfg) == 139_264
    assert mla_flops.projection_params(cfg) == 94_633_984
    assert mla_flops.expert_params(cfg) == 25_165_824
    # a token outside the experts: five attention layers, the dense MLP,
    # four routers over 128 and four shared experts, 2 FLOP a parameter
    assert mla_flops.token_flops_outside_experts(cfg) == 2.0 * (
        5 * 94_633_984 + 3 * 4096 * 16384
        + 4 * (4096 * 128 + 25_165_824))
    # 12,288 tokens: about 19 + 15.5 + 5 TFLOP; two held pairs a token
    # and routed layer in the mean (8 of 128 over 32 held)
    n = 12288
    flops = mla_flops.prefill_flops(cfg, n, 5 * n * (n + 1) // 2,
                                    2 * 4 * n)
    assert 39e12 < flops < 41e12


def test_traffic_of_the_cell(real):
    m = real.traffic(MIX)
    # the issue's traffic and nothing else: Poisson arrivals into a window
    # that opens on an empty batcher and closes on time
    assert set(m) == {"generator", "prompt", "output", "rate_per_s",
                      "after_window", "trace_seconds", "why"}
    assert m["generator"] == "open_loop" and m["after_window"] == "stop"
    assert m["prompt"] == {"median": 12288, "sigma": 0.7, "min": 4096,
                           "max": 30720}
    assert m["output"] == {"median": 96, "sigma": 0.6, "min": 32,
                           "max": 256}
    # at least two waves and a dozen scans in the traced seconds
    assert m["trace_seconds"] >= 4.0
    assert real.config(CONFIG)["feed"] == {"max_unadmitted": 1}
    a = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=65536)
    b = traffic_lib.generate(m, 2 ** 31 + 5, 35.0, vocab=65536)
    assert len(a) == round(m["rate_per_s"] * 35) and len(a) >= 35
    assert max(r.due_s for r in a) < 35.0
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    sizes = np.array([r.prompt.size for r in a])
    assert sizes.min() >= 4096 and sizes.max() <= 30720
    assert 0.8 * 12288 <= np.median(sizes) <= 1.2 * 12288
    # no existing cell passes 16,384 positions: a third of these do
    assert 0.2 < (sizes > 16384).mean() < 0.5
    assert all(0 <= r.prompt.min() and r.prompt.max() < 65536 for r in a)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_every_seed_offers_the_same_work_at_the_same_times(real, seed):
    """The order of arrival is the cell's (the generator's at
    `ARRIVALS_SEED`), the ids are the seed's, from the held slice."""
    driver = manifest_lib.driver_module("serve_sarvam")
    m = real.traffic(MIX)
    one = traffic_lib.generate(m, driver.ARRIVALS_SEED, 35.0, vocab=65536)
    a = driver.offered(m, seed, 35.0, 65536)
    b = driver.offered(m, seed, 35.0, 65536)
    other = driver.offered(m, seed + 1, 35.0, 65536)
    assert [(r.due_s, r.prompt.size, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in one] == \
        [(r.due_s, r.prompt.size, r.max_new_tokens) for r in other]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all((x.prompt != y.prompt).any() for x, y in zip(a, other))
    assert all(r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
               and r.prompt.max() < 65536 for r in a)


def test_sweep_wrapper_names_the_driver_and_puts_it_back(monkeypatch):
    from benchmarks import sweep, sweep_sarvam

    seen = {}

    def fake_main(argv):
        seen["driver"] = manifest_lib.driver_module("serve")
        return 0

    before = manifest_lib.driver_module
    monkeypatch.setattr(sweep, "main", fake_main)
    assert sweep_sarvam.main([]) == 0
    assert seen["driver"].build_server.__module__.endswith("serve_sarvam")
    assert callable(seen["driver"].serve_window)
    assert manifest_lib.driver_module is before


def test_a_program_without_the_layers_stops_at_once(real, monkeypatch):
    """What the parent commit does with this cell: `GPT` has no field for
    the latent layer's widths, so the driver stops where it builds the
    model, before a weight is drawn."""
    from tfde_tpu.models import gpt

    driver = manifest_lib.driver_module("serve_sarvam")
    fields = {f for f in gpt.GPT.__dataclass_fields__ if f != "mla"}

    class Parent:
        def __init__(self, **kw):
            unknown = set(kw) - fields
            if unknown:
                raise TypeError(f"unexpected keyword argument {unknown}")

    monkeypatch.setattr(gpt, "GPT", Parent)
    with pytest.raises(TypeError, match="mla"):
        driver.build_model(real.config(CONFIG))


# ---------------------------------------------------------------------------
# the toy twin through run_cell
# ---------------------------------------------------------------------------

def _run(seed=2 ** 31 + 11, seconds=2.0, control=False, tracer=None):
    out = io.StringIO()
    line = runner.run_cell(
        TINY, "tiny-sarvam-over", seed, seconds, tracer, jax.devices(),
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
    latent = notes["latent"]
    assert 0 < latent["moe_pairs_held"] < latent["moe_pairs"]
    assert latent["latent_cells_read"] > 0 < latent["latent_cells_committed"]
    assert latent["latent_pairs_prefilled"] > 0
    # the seeded bias is no formality: it changes over a tenth of the
    # longest checked request's choices, and bfloat16 few of them
    flips = notes["routing_flips"]
    assert flips["without_selection_bias"] >= 0.1 * flips["of_routings"]
    assert 0 <= flips["bf16_for_float32"] < flips["control_for_float32"]
    # the controls: the reference one precision down, and the reference
    # without the rotary key's term or without the bias, are not correct
    assert [(c["name"], c["fails_as_it_must"]) for c in tagged["control"]] \
        == [("served_token_gap_mean", True),
            ("served_token_gap_mean.without_rope_term", True),
            ("served_token_gap_mean.without_selection_bias", True)]
    # what is compared is the summary's mean, over every checked token
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
    assert set(line["metrics"]) >= MLA_METRICS | {"compile_s"}
    value = lambda name: line["metrics"][name]["value"]
    assert all(value(name) is not None for name in MLA_METRICS)
    # shares of something: none can pass 100
    for name in ("device_idle_pct.mla", "decode_least_bytes_pct.mla",
                 "kv_latent_share_pct.mla", "prefill_mfu_pct.mla",
                 "moe_touched_pct.mla"):
        assert 0.0 < value(name) <= 100.0
    assert 1.0 <= value("moe_busiest_over_mean.mla") <= 4.0
    assert value("decode_tick_ms.mla") > 0
    assert value("prefill_ms_per_ktoken.mla") > 0
    # a wave's first tokens count as generated, so a little over the rows
    assert 0.0 < value("rows_per_tick.mla") <= 5.0
    assert 0.0 < value("syncs_per_token.mla") < 1.0


@pytest.mark.parametrize("name", sorted(MLA_METRICS))
def test_a_reader_finds_nothing_in_a_program_without_the_counters(real,
                                                                  name):
    """What another cell's program (or the parent's) hands over: no reader
    of this cell reads a number from it, and none raises."""
    cfg = real.config(CONFIG)
    obs = {"counters": {"generated": 10, "rounds": 5, "syncs": 2,
                        "decode_ns": 10 ** 9, "prefill_ns": 10 ** 9,
                        "prefill_tokens": 1000, "prefill_waves": 3,
                        "decode_least_bytes": 10 ** 9, "moe_pairs": 32000,
                        "moe_pairs_held": 8000, "moe_pairs_busiest": 400,
                        "moe_experts_touched": 512},
           "trace": {"busy_s": 1.0, "window_s": 2.0},
           "device_kind": "TPU v5 lite", "config": cfg}
    read = real.metric_reader(name)
    assert read(obs) is None
    assert read({"counters": {}, "config": {}}) is None
    obs["counters"].update(latent_cells_read=200_000,
                           latent_cells_committed=6000,
                           latent_pairs_prefilled=5 * 1000 * 1001 // 2)
    held = 8000 * 1.0      # every pair routed was a prefill's: 1000 x 4 x 8
    flops = (1000 * mla_flops.token_flops_outside_experts(cfg)
             + 5 * 1000 * 1001 // 2 * 40_960 + held * 2 * 25_165_824)
    assert read(obs) == pytest.approx({
        "device_idle_pct.mla": 50.0, "rows_per_tick.mla": 2.0,
        "syncs_per_token.mla": 0.2, "decode_tick_ms.mla": 200.0,
        "prefill_ms_per_ktoken.mla": 1024.0,
        "decode_least_bytes_pct.mla": 100 / 819.0,
        "kv_latent_share_pct.mla": 100 * 200_000 * 1152 / 10 ** 9,
        "prefill_mfu_pct.mla": 100 * flops / 197e12,
        "moe_busiest_over_mean.mla": 400 * 32 / 8000,
        "moe_touched_pct.mla": 100 * 512 / (32 * 4 * 8),
    }[name])


# the timed path broken ends `correct: false` (a served token altered where
# it is fetched is `test_benchmark_smallthinker.py`'s, through the same fetch)
def _the_rotary_key_left_out_of_a_tick(monkeypatch):
    from tfde_tpu.ops import mla

    real_attention = mla.absorbed_attention
    monkeypatch.setattr(
        mla, "absorbed_attention",
        lambda q_abs, q_rope, *a, **kw: real_attention(
            q_abs, 0 * q_rope, *a, **kw))


def test_a_broken_timed_path_is_not_correct(monkeypatch):
    _the_rotary_key_left_out_of_a_tick(monkeypatch)
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
