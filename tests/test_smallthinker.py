"""Window layers that keep a ring of cells beside the global layers' slab,
a router that reads the attention's input, and ReGLU experts
(models/transformer.py `_rolling_attention`, models/moe.py, ops/moe_gmm.py)
against the plain reference (benchmarks/reference/smallthinker.py, which
imports nothing of the program), at a small size on the CPU with seeded
weights, comparing LOGITS.

Size: hidden 64; two periods of global, window, window, window; 4 heads of
16 over 2 K/V heads; window 8 with rotary positions (theta 1.5e6) on the
window layers and no positions on the global ones; 8 ReGLU experts of
width 32, 2 a token; an untied head over 96. Everything runs in float32 at
the highest matmul precision, so the two computations differ by the order
of float32 sums alone: measured 3e-6 on logits of magnitude 3.6 through
prefill and decode. The tolerance is 1e-4; the same model with bfloat16
activations must fail it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from functools import partial

from benchmarks.reference import smallthinker as ref
from teacher_forced import programs, served_logits, worst_gap
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import init_cache
from tfde_tpu.inference.server import (ContinuousBatcher,
                                       SpeculativeContinuousBatcher)
from tfde_tpu.models import moe, transformer
from tfde_tpu.models.cache_state import layout_of
from tfde_tpu.models.gpt import GPT, gpt_tiny_test
from tfde_tpu.observability.capacity import CapacityLedger, kv_slab_bytes

LAYOUT = (0, 1, 1, 1, 0, 1, 1, 1)
WINDOW, EXPERTS, PER_TOKEN, VOCAB = 8, 8, 2, 96
DIMS = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=8, moe_ffn_hidden_size=32,
    moe_num_primary_experts=EXPERTS,
    moe_num_active_primary_experts=PER_TOKEN, rms_norm_eps=1e-6,
    rope_theta=1.5e6, sliding_window_size=WINDOW, vocab_size=VOCAB,
    window_layers=tuple(bool(w) for w in LAYOUT))
TOL = 1e-4


def window_model(dtype=jnp.float32, **kw):
    fields = dict(
        vocab_size=VOCAB, hidden_size=64, depth=8, num_heads=4,
        num_kv_heads=2, head_dim=16, mlp_dim=32, max_position=4096,
        dtype=dtype, position="rope", rope_theta=1.5e6, rope_layers=LAYOUT,
        windows=tuple(WINDOW if w else None for w in LAYOUT), norm="rms",
        ln_eps=1e-6, mlp_act="reglu", use_bias=False, tie_embeddings=False,
        num_experts=EXPERTS, moe_every=1, experts_per_token=PER_TOKEN,
        moe_capacity_factor=None, moe_router_pre_attention=True)
    return GPT(**dict(fields, **kw))


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, DIMS)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        ref.to_program_params(weights))


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def forward():
    """The whole forward of the model as it is written, jitted: a shape
    compiles once, where an eager apply compiles every primitive."""
    model = window_model()
    return jax.jit(lambda params, rows: model.apply({"params": params}, rows))


def rows_of(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(weights, row) -> np.ndarray:
    return np.asarray(ref.forward(weights, jnp.asarray(row), DIMS))


# ---------------------------------------------------------------------------
# the full forward, and what the configuration's numbers come to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 8, 9, 17, 40])
def test_full_forward_matches_the_reference(weights, params, forward,
                                            length):
    (row,) = rows_of(length, [length])
    got = forward(params, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        < TOL


def test_bfloat16_for_float32_fails_the_tolerance(weights, params):
    (row,) = rows_of(1, [40])
    got = jax.jit(window_model(jnp.bfloat16).apply)(
        {"params": params}, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        > 10 * TOL


def test_init_builds_what_the_reference_draws(params):
    tree = jax.eval_shape(lambda: window_model().init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, params)
    assert ref.num_params(DIMS) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_the_configurations_parameters_are_pinned():
    """The arithmetic of the configuration file, at published widths:
    20,971,520 of attention, 163,840 of router and 64 experts of 5,898,240
    a layer (and two norm gains), 777,912,320 of embedding and head."""
    from benchmarks.lib.manifest import Manifest
    from benchmarks.run import ROOT

    cfg = Manifest(ROOT).config("smallthinker-21b-serve-16k")
    dims = ref.dims_of(cfg)
    layer = 20_971_520 + 163_840 + 64 * 5_898_240 + 2 * 2560
    assert ref.num_params(dims) == 8 * layer + 2 * 151_936 * 2560 + 2560
    assert ref.num_params(dims) == 3_966_937_600
    assert dims["window_layers"] == (False, True, True, True) * 2
    assert cfg["published"]["num_hidden_layers"] == 52
    assert len(cfg["sliding_window_layout"]) == 52


@pytest.mark.parametrize("pattern,windows", [
    ("all", (6, 6, 6, 6)), ("alternate", (6, None, 6, None))])
def test_the_two_patterns_are_two_ways_of_writing_the_tuple(pattern,
                                                           windows):
    """`sliding_window_pattern` builds the windows it built before, as the
    per-layer tuple, and the model it names is the model the tuple names."""
    by_word = gpt_tiny_test(sliding_window=6,
                            sliding_window_pattern=pattern).clone(depth=4)
    by_tuple = gpt_tiny_test(windows=windows).clone(depth=4)
    assert by_word.layer_windows() == by_tuple.layer_windows() == windows
    tokens = np.arange(12, dtype=np.int32)[None] % 97
    p = by_word.init(jax.random.key(0), tokens)["params"]
    assert np.array_equal(np.asarray(by_word.apply({"params": p}, tokens)),
                          np.asarray(by_tuple.apply({"params": p}, tokens)))
    assert gpt_tiny_test().layer_windows() is None
    assert gpt_tiny_test(windows=(None, 0)).layer_windows() is None
    with pytest.raises(ValueError, match="not both"):
        gpt_tiny_test(sliding_window=4, windows=(4, 4)).layer_windows()
    with pytest.raises(ValueError, match="sliding_window_pattern"):
        gpt_tiny_test(sliding_window=4,
                      sliding_window_pattern="thirds").layer_windows()
    with pytest.raises(ValueError, match="depth"):
        gpt_tiny_test(windows=(4,)).apply({"params": p}, tokens)
    with pytest.raises(ValueError, match="rope_layers"):
        gpt_tiny_test(rope_layers=(1, 0)).apply({"params": p}, tokens)


# ---------------------------------------------------------------------------
# prefill of a padded bucket, then decode through the cache, one logit
# vector a step: rows of different true lengths in one wave
# ---------------------------------------------------------------------------

# three rows in one wave, each padded up the ladder to a bucket of 32: a
# prompt shorter than the window (5) whose decode turns the ring at
# position 8 and twice more, one longer than the window (20: the ring
# keeps its last 8 true tokens, positions 12-19, not the padded tail's),
# and one of exactly the window (8: its first step overwrites slot 0)
SERVED = dict(lengths=[5, 20, 8], totals=[30, 44, 26], bucket=32,
              max_len=48)


def window_programs(model=None, rolling=True):
    # the expert layers sow their counts, as under the batcher
    return programs(model or window_model(), rolling=rolling,
                    mutable=("cache", "counters"))


@pytest.fixture(scope="module")
def honest():
    """The model as it is written, its window layers on rings, traced once
    for the tests that only read what it serves."""
    return window_programs()


def serve(weights, params, progs, **kw):
    rows = rows_of(3, SERVED["totals"])
    got, cache = served_logits(progs, params, rows, SERVED["lengths"],
                               SERVED["bucket"], SERVED["max_len"], **kw)
    gap = worst_gap(partial(reference_logits, weights), rows,
                    SERVED["lengths"], got)
    return gap, got, cache


def test_prefill_and_decode_match_the_reference(weights, params, honest):
    gap, got, cache = serve(weights, params, honest)
    assert [len(g) for g in got] == [
        t - n + 1 for t, n in zip(SERVED["totals"], SERVED["lengths"])]
    assert gap < TOL
    # a window layer holds min(window, max_len) cells a row, a global
    # layer the row's whole length
    for l, windowed in enumerate(LAYOUT):
        attn = cache["decoder"][f"block_{l}"]["attn"]
        assert attn["cached_key"].shape == (
            3, WINDOW if windowed else SERVED["max_len"], 2, 16)
        assert ("feed_pad" in attn) == bool(windowed)


def test_a_frozen_row_leaves_the_others_alone(weights, params, honest):
    """Row 2 stops after 3 steps and is fed padding at a frozen index for
    the rest of the run: what it emitted and what the other rows emit
    still agree with the reference."""
    gap, got, _ = serve(weights, params, honest, freeze=(2, 3))
    assert len(got[2]) == 4
    assert gap < TOL


def test_the_ring_equals_the_slab_with_a_band_mask(weights, params, honest):
    """The same model and requests with every layer on a slab and the band
    as a mask over it (`rolling` off): the same logits, and the window
    layers then hold `max_len` cells a row where the ring holds 8."""
    _, ring, _ = serve(weights, params, honest)
    gap, slab, cache = serve(weights, params, window_programs(rolling=False))
    assert gap < TOL
    assert max(np.abs(a - b).max() for a, b in zip(ring, slab)) < 1e-5
    assert cache["decoder"]["block_1"]["attn"]["cached_key"].shape[1] == \
        SERVED["max_len"]


def test_a_long_prefill_goes_through_the_dispatcher(weights, params,
                                                    monkeypatch):
    """Past `_PREFILL_SCORES_BYTES` of scores a wave's window layers attend
    through `ops/attention.attention` with the window passed on (on the
    chip: the flash forward), its global layers as PR 31 wrote: the same
    logits."""
    seen = []
    real = transformer.attn_lib.attention
    monkeypatch.setattr(
        transformer.attn_lib, "attention",
        lambda *a, **kw: seen.append(kw.get("window")) or real(*a, **kw))
    monkeypatch.setattr(transformer, "_PREFILL_SCORES_BYTES", 0)
    monkeypatch.setattr(transformer, "_PREFILL_QUERY_BLOCK", 8)
    gap, _, _ = serve(weights, params, window_programs())
    assert gap < TOL
    assert seen.count(WINDOW) == 6 and seen.count(None) == 2


# ways to get the model wrong, each of which must show
def _band_dropped_on_a_window_layer(monkeypatch):
    return dict(windows=(None,) * 5 + (WINDOW,) * 3)


def _ring_slot_off_by_one(monkeypatch):
    real = transformer._ring_put
    monkeypatch.setattr(transformer, "_ring_put",
                        lambda ring, new, pos: real(ring, new, pos + 1))


def _cell_one_window_back_not_overwritten(monkeypatch):
    real = transformer._ring_put
    monkeypatch.setattr(
        transformer, "_ring_put", lambda ring, new, pos: jnp.where(
            (pos < ring.shape[1])[:, None, None, None],
            real(ring, new, pos), ring))


def _the_padded_tail_kept_for_the_true_tokens(monkeypatch):
    real = transformer._ring_of
    monkeypatch.setattr(
        transformer, "_ring_of", lambda x, lengths, ring: real(
            x, jnp.full_like(lengths, x.shape[1]), ring))


def _rotary_positions_on_a_global_layer(monkeypatch):
    return dict(rope_layers=(1,) * 8)


def _router_fed_the_expert_layers_input(monkeypatch):
    return dict(moe_router_pre_attention=False)


def _silu_for_relu(monkeypatch):
    return dict(mlp_act="swiglu")


def _six_weights_not_renormalised(monkeypatch):
    return dict(moe_normalize_topk=False)


@pytest.mark.parametrize("break_it", [
    _band_dropped_on_a_window_layer, _ring_slot_off_by_one,
    _cell_one_window_back_not_overwritten,
    _the_padded_tail_kept_for_the_true_tokens,
    _rotary_positions_on_a_global_layer,
    _router_fed_the_expert_layers_input, _silu_for_relu,
    _six_weights_not_renormalised])
def test_a_broken_model_fails_the_tolerance(weights, params, monkeypatch,
                                            break_it):
    fields = break_it(monkeypatch) or {}
    gap, _, _ = serve(weights, params,
                      window_programs(window_model(**fields)))
    assert gap > 100 * TOL


# ---------------------------------------------------------------------------
# through ContinuousBatcher
# ---------------------------------------------------------------------------

# (prompt, budget): a first wave of three (the ladder pads it to four by
# repeating its first row), prompts under and over the window, budgets of 3
# and 5 that finish and freeze while the others run and turn their rings,
# later requests into the freed rows
REQUESTS = ((5, 20), (20, 3), (8, 14), (13, 5), (30, 12), (2, 25))


@pytest.fixture(scope="module")
def served(params):
    with jax.default_matmul_precision("highest"):
        srv = ContinuousBatcher(window_model(), params, batch_size=4,
                                max_len=48, scan_depth=4,
                                prompt_buckets=(16, 32, 48))
        prompts = rows_of(11, [n for n, _ in REQUESTS])
        rids = [srv.submit(p, b) for p, (_, b) in zip(prompts[:3], REQUESTS)]
        out = dict(srv.step())
        rids += [srv.submit(p, b)
                 for p, (_, b) in zip(prompts[3:], REQUESTS[3:])]
        out.update(srv.run())
    return srv, prompts, [out[r] for r in rids]


@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_batcher_serves_the_references_first_choice(weights, served, i):
    _, prompts, outs = served
    assert outs[i].size == REQUESTS[i][1]
    gaps = ref.served_token_gaps(weights, prompts[i], outs[i], DIMS, 64)
    # greedy in float32: the served token is the reference's argmax, or a
    # tie within the tolerance on the logits
    assert float(gaps["gap"].max()) < TOL
    assert gaps["routes"].shape == (8, sum(REQUESTS[i]), PER_TOKEN)


def test_the_batchers_window_layers_hold_a_ring(served):
    srv, _, _ = served
    for l, windowed in enumerate(LAYOUT):
        attn = srv._cache["decoder"][f"block_{l}"]["attn"]
        for leaf in ("cached_key", "cached_value"):
            assert attn[leaf].shape == (4, WINDOW if windowed else 48, 2, 16)
    assert srv._decode_model.rolling_cache
    assert srv.stats()["prefill_rows_padded"] >= 1


def test_batcher_counts_both_kinds_of_cell_and_the_routing(served):
    srv, _, _ = served
    stats = srv.stats()
    assert srv._ledger.kinds == {"kv", "ring"}
    assert set(CapacityLedger.RING_KEYS) <= set(stats)
    assert set(CapacityLedger.HYBRID_KEYS) <= set(stats)
    assert stats["kv_cells_read"] == (stats["kv_full_cells_read"]
                                      + stats["kv_window_cells_read"])
    # two global layers read every committed cell, six window layers at
    # most eight each: the window's share is under three quarters, and
    # well under it once rows run past the window
    assert 0 < stats["kv_window_cells_read"] < 3 * stats["kv_full_cells_read"]
    assert 0 < stats["kv_window_wraps"] <= 4 * stats["scans"]
    fed = sum(p + t - 1 for p, t in REQUESTS)
    assert stats["moe_pairs"] >= 8 * PER_TOKEN * fed
    assert stats["moe_pairs_held"] == stats["moe_pairs"]      # all held
    assert 0 < stats["moe_experts_touched"] <= 8 * EXPERTS * (
        stats["rounds"] + stats["prefill_waves"])
    assert stats["decode_least_bytes"] > 0
    assert stats["syncs"] == stats["prefill_waves"] + stats["scans"]


def ledger_of(model, params, cache, positions):
    layout = layout_of(model, positions)
    return CapacityLedger(
        2, positions, kv_slab_bytes(cache), layout.layers,
        moe.held_experts(params) if layout.uncapped_experts else None)


def test_the_ledger_counts_each_layers_cells_as_the_layer_says(params):
    model = window_model()
    cache = init_cache(model, 2, 48, rolling=True)
    ledger = ledger_of(model, params, cache, 48)
    assert ledger.kinds == {"kv", "ring"}
    cell = 2 * 2 * 16 * 4              # K and V of 2 heads of 16, float32
    assert ledger.cells_per_row == 2 * 48 + 6 * WINDOW
    assert ledger.cell_bytes == cell
    assert ledger.row_cells(5) == 8 * 5
    assert ledger.read_cells(20) == 2 * 20 + 6 * WINDOW
    ledger.note_scan([5, 8, 20], 4)
    assert ledger.counters["kv_full_cells_read"] == 4 * 2 * 33
    assert ledger.counters["kv_window_cells_read"] == 4 * 6 * (5 + 8 + 8)
    assert ledger.counters["kv_window_wraps"] == 2
    # least bytes of 2 ticks that touched 9 (layer, expert) slots
    expert = 3 * 64 * 32 * 4
    outside = 1_000_000
    got = ledger.scan_least_bytes(outside + 8 * EXPERTS * expert, 7_000, 2,
                                  [40, 40, 9, 3, 100])
    assert got == 2 * (outside + 7_000) + 9 * expert
    # no window is left behind in 8 positions: slabs alone, and the
    # experts' family of counters with no ring's beside it
    slabs = ledger_of(model, params, init_cache(model, 2, 8), 8)
    assert slabs.kinds == {"kv"}
    assert set(slabs.counters) == set(CapacityLedger.HYBRID_KEYS)


@pytest.mark.parametrize("kw,word", [
    (dict(paged=True), "paged"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
])
def test_batcher_refuses_what_a_ring_cannot_give_back(params, kw, word):
    with pytest.raises(NotImplementedError, match=word) as refused:
        ContinuousBatcher(window_model(), params, batch_size=2, max_len=48,
                          **kw)
    assert "ring" in str(refused.value)


def test_speculation_and_the_primed_hand_off_are_refused(params):
    with pytest.raises(NotImplementedError, match="Speculative.*ring"):
        SpeculativeContinuousBatcher(window_model(), window_model(), params,
                                     params, batch_size=2, max_len=48)
    srv = ContinuousBatcher(window_model(), params, batch_size=2, max_len=48)
    with pytest.raises(NotImplementedError, match="prime.*ring"):
        srv.prime(np.arange(8, dtype=np.int32), 4)
    primed = server.PrimedRequest(np.arange(8, dtype=np.int32), 1, 4, {})
    with pytest.raises(NotImplementedError, match="submit_primed.*ring"):
        srv.submit_primed(primed)
    assert "ring" in layout_of(
        gpt_tiny_test(sliding_window=8)).not_by_position
    assert "ring" in layout_of(
        gpt_tiny_test(sliding_window=8), 9).not_by_position
    assert layout_of(
        gpt_tiny_test(sliding_window=8), 8).not_by_position is None
    assert layout_of(gpt_tiny_test()).not_by_position is None


@pytest.mark.parametrize("kw", [dict(), dict(paged=True)],
                         ids=["slab", "paged"])
def test_a_window_no_row_outgrows_keeps_the_slab_and_its_features(
        weights, params, kw):
    """A window of `max_len` or more never leaves a cell behind: no ring,
    the slab under its band mask, and the pool is built for it as it was
    before window layers could roll."""
    wide = window_model(windows=tuple(48 if w else None for w in LAYOUT))
    srv = ContinuousBatcher(wide, params, batch_size=2, max_len=48,
                            scan_depth=4, prompt_buckets=(16, 32, 48), **kw)
    assert not srv._ring and not srv._decode_model.rolling_cache
    assert "ring" not in srv._ledger.kinds
    assert not set(CapacityLedger.RING_KEYS) & set(srv.stats())
    prompts, budgets = rows_of(3, [5, 20]), (12, 6)
    rids = [srv.submit(p, b) for p, b in zip(prompts, budgets)]
    out = dict(srv.run())
    dims = dict(DIMS, sliding_window_size=48)
    for rid, prompt, budget in zip(rids, prompts, budgets):
        assert out[rid].size == budget
        gaps = ref.served_token_gaps(weights, prompt, out[rid], dims, 64)
        assert float(gaps["gap"].max()) < TOL


@pytest.mark.parametrize("kw", [dict(paged=True), dict(kv_quant="int8"),
                                dict(prefix_cache=True), dict(role="prefill")],
                         ids=["paged", "int8", "prefix_cache", "role"])
def test_what_a_ring_refuses_is_built_where_the_window_is_no_ring(kw):
    """The dense model of `sliding_window=` under a batcher whose rows
    cannot outgrow the window: everything the ring refuses is built, and
    what it serves is what the plain batcher over the slab serves."""
    model = gpt_tiny_test(sliding_window=24)
    tree = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    prompts = rows_of(5, [6, 17])

    def tokens(**kw):
        srv = ContinuousBatcher(model, tree, batch_size=2, max_len=24,
                                prompt_buckets=(8, 24), **kw)
        assert not srv._ring
        if kw.get("role") == "prefill":
            return [srv.prime(p, 5).first_token for p in prompts]
        rids = [srv.submit(p, 5) for p in prompts]
        out = dict(srv.run())
        return [out[r].tolist() for r in rids]

    plain = tokens()
    got = tokens(**kw)
    if "role" in kw:
        assert got == [row[0] for row in plain]
    elif "kv_quant" not in kw:          # int8 cells round, the rest is exact
        assert got == plain
    with pytest.raises(NotImplementedError, match="ring"):
        ContinuousBatcher(model, tree, batch_size=2, max_len=25, **kw)


@pytest.mark.parametrize("fields", [dict(windows=(4, None)),
                                    dict(rope_layers=(1, 0))],
                         ids=["windows", "rope_layers"])
def test_no_exporter_drops_a_per_layer_layout(fields):
    """`models/convert.py` writes one window and one position scheme for a
    model: one that gives them per layer is refused by every GPT exporter,
    before anything is built."""
    from tfde_tpu.models import convert

    exporters = [getattr(convert, name) for name in dir(convert)
                 if name.endswith("_to_hf")
                 and not name.startswith(("bert", "t5", "_"))]
    assert len(exporters) == 13
    for to_hf in exporters:
        with pytest.raises(NotImplementedError, match="per\\s+layer"):
            to_hf(gpt_tiny_test(**fields), {})
