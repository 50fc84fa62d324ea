"""attention='eva' (ops/eva_attention.py) against the plain reference
(benchmarks/reference/evabyte.py, which imports nothing of the program), at
a small size on the CPU with seeded weights, comparing LOGITS.

Size: hidden 64, 4 heads of 16, window 32, chunk 4, 3 layers, vocabulary
320. Everything runs in float32 at the highest matmul precision, so the
two computations differ by the order of float32 sums alone: measured
5e-6 on logits of magnitude 5. The tolerance is 1e-4, twenty times that;
the same model with bfloat16 activations stands 1e-2 off and must fail it
(`test_bfloat16_for_float32_fails_the_tolerance`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from functools import partial

from benchmarks.reference import evabyte as ref
from teacher_forced import programs, served_logits, worst_gap
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import generate, init_cache
from tfde_tpu.inference.server import (ContinuousBatcher,
                                       SpeculativeContinuousBatcher)
from tfde_tpu.models.gpt import GPT, gpt_tiny_test
from tfde_tpu.models.transformer import UnitOffsetRMSNorm
from tfde_tpu.models.cache_state import CacheState, layout_of
from tfde_tpu.observability.capacity import CapacityLedger, kv_slab_bytes
from tfde_tpu.ops import eva_attention as eva_lib

W, C, HEADS, LAYERS, VOCAB = 32, 4, 4, 3, 320
DIMS = dict(hidden_size=64, intermediate_size=160, num_attention_heads=HEADS,
            num_hidden_layers=LAYERS, vocab_size=VOCAB, rms_norm_eps=1e-5,
            rope_theta=100000, window_size=W, chunk_size=C, init_std=0.15)
TOL = 1e-4


def eva_model(dtype=jnp.float32, **kw):
    return GPT(vocab_size=VOCAB, hidden_size=64, depth=LAYERS,
               num_heads=HEADS, mlp_dim=160, max_position=4096, dtype=dtype,
               position="rope", rope_theta=1e5, norm="rms",
               norm_unit_offset=True, ln_eps=1e-5, mlp_act="swiglu",
               use_bias=False, tie_embeddings=False, attention="eva",
               eva_window=W, eva_chunk=C, fp32_residual=True, **kw)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, DIMS)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        ref.to_program_params(weights, HEADS))


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def forward():
    """The whole forward of the model as it is written, jitted: a shape
    compiles once, where an eager apply compiles every primitive."""
    model = eva_model()
    return jax.jit(lambda params, rows: model.apply({"params": params}, rows))


def rows_of(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(weights, row) -> np.ndarray:
    return np.asarray(ref.forward(weights, jnp.asarray(row), DIMS))


# ---------------------------------------------------------------------------
# the full forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 31, 32, 33, 100, 128])
def test_full_forward_matches_the_reference(weights, params, forward,
                                            length):
    (row,) = rows_of(length, [length])
    got = forward(params, row[None])[0]
    want = reference_logits(weights, row)
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_bfloat16_for_float32_fails_the_tolerance(weights, params):
    (row,) = rows_of(1, [100])
    got = jax.jit(eva_model(jnp.bfloat16).apply)(
        {"params": params}, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        > 10 * TOL


def test_init_creates_the_two_pooling_vectors_per_layer():
    model = eva_model()
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    attn = tree["decoder"]["block_1"]["attn"]
    assert attn["eva_phi"].shape == attn["eva_mu"].shape == (HEADS, 16)
    assert "wpe" not in tree and "lm_head" in tree


# ---------------------------------------------------------------------------
# prefill of a padded bucket, then decode through the cache, one logit
# vector a step: rows of different true lengths, a row frozen half way
# ---------------------------------------------------------------------------

# true lengths that end inside a chunk, on a chunk edge, on a window edge,
# and in the bucket's last window; every row decodes across several chunk
# edges, rows 0-2 across a window edge
SERVED = dict(lengths=[37, 60, 64, 101], totals=[80, 75, 100, 130],
              bucket=128, max_len=160)


@pytest.fixture(scope="module")
def honest():
    """The model as it is written, traced once for the tests that only
    read what it serves."""
    return programs(eva_model())


def serve(weights, params, progs, **kw):
    rows = rows_of(3, SERVED["totals"])
    got, cache = served_logits(progs, params, rows, SERVED["lengths"],
                               SERVED["bucket"], SERVED["max_len"], **kw)
    gap = worst_gap(partial(reference_logits, weights), rows,
                    SERVED["lengths"], got)
    return gap, got, cache


def test_prefill_and_decode_match_the_reference(weights, params, honest):
    gap, got, _ = serve(weights, params, honest)
    assert [len(g) for g in got] == [
        t - n + 1 for t, n in zip(SERVED["totals"], SERVED["lengths"])]
    assert gap < TOL


def test_a_frozen_row_leaves_the_others_and_its_summaries_alone(
        weights, params, honest):
    """Row 1 stops after 3 steps with its index at 63, where one more key
    would complete chunk 15; it is then fed padding 50 more times."""
    gap, got, cache = serve(weights, params, honest, freeze=(1, 3))
    assert len(got[1]) == 4
    assert gap < TOL
    table = cache["decoder"]["block_0"]["attn"]["eva_summary_key"]
    assert np.abs(np.asarray(table[1, 14])).max() > 0    # keys 56-59
    assert np.abs(np.asarray(table[1, 15:])).max() == 0  # never completed
    assert np.abs(np.asarray(table[0, 80 // C:])).max() == 0


def test_a_padded_tail_lands_in_no_summary(params, honest):
    """Bucket 128 behind true lengths 37 and 101: chunks 9.. and 25.. hold
    padding (or are cut by the true length) and stay zero; the window kept
    is the one the true length ends in."""
    rows = rows_of(5, [37, 101])
    _, cache = served_logits(honest, params, rows, [37, 101], 128, 160)
    attn = cache["decoder"]["block_2"]["attn"]
    table = np.asarray(attn["eva_summary_key"])
    assert np.abs(table[0, :9]).min(axis=(1, 2)).max() > 0
    assert np.abs(table[0, 9:]).max() == 0
    assert np.abs(table[1, :25]).min(axis=(1, 2)).max() > 0
    assert np.abs(table[1, 25:]).max() == 0
    assert attn["eva_window_key"].shape == (2, W, HEADS, 16)
    assert attn["eva_summary_key"].shape == (2, 160 // C, HEADS, 16)
    assert np.asarray(attn["feed_pad"]).tolist() == [0, 0]


# three ways to get the layer wrong, each of which must show
def _drop_summaries(monkeypatch):
    real = eva_lib._merged_softmax
    monkeypatch.setattr(
        eva_lib, "_merged_softmax",
        lambda s_loc, s_rem, ok_loc, ok_rem, v_loc, v_rem: real(
            s_loc, s_rem, ok_loc, jnp.zeros_like(ok_rem), v_loc, v_rem))


def _drop_mu(monkeypatch):
    real = eva_lib.chunk_summaries
    monkeypatch.setattr(
        eva_lib, "chunk_summaries",
        lambda k, v, phi, mu, scale, chunk: real(
            k, v, phi, jnp.zeros_like(mu), scale, chunk))


def _keep_the_old_window(monkeypatch):
    real = eva_lib._merged_softmax

    def stale(s_loc, s_rem, ok_loc, ok_rem, v_loc, v_rem):
        if s_loc.shape[2] == 1:    # a decode step: every slot stays visible
            ok_loc = jnp.ones_like(ok_loc)
        return real(s_loc, s_rem, ok_loc, ok_rem, v_loc, v_rem)

    monkeypatch.setattr(eva_lib, "_merged_softmax", stale)


@pytest.mark.parametrize("break_it", [_drop_summaries, _drop_mu,
                                      _keep_the_old_window])
def test_a_broken_layer_fails_the_tolerance(weights, params, monkeypatch,
                                            break_it):
    break_it(monkeypatch)
    gap, _, _ = serve(weights, params, programs(eva_model()))
    assert gap > 100 * TOL


# ---------------------------------------------------------------------------
# through ContinuousBatcher
# ---------------------------------------------------------------------------

REQUESTS = ((37, 40), (50, 9), (64, 70), (101, 30), (33, 5), (70, 60))


@pytest.fixture(scope="module")
def served(params):
    """Six requests through four rows: two lengths share each bucket,
    budgets of 5 and 9 finish (and freeze) while the others run, three
    requests cross a window edge, later ones reuse freed rows."""
    with jax.default_matmul_precision("highest"):
        srv = ContinuousBatcher(eva_model(), params, batch_size=4,
                                max_len=192, scan_depth=4,
                                prompt_buckets=(64, 128, 192))
        prompts = rows_of(11, [n for n, _ in REQUESTS])
        rids = [srv.submit(p, b) for p, (_, b) in zip(prompts, REQUESTS)]
        out = dict(srv.run())
    return srv, prompts, [out[r] for r in rids]


@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_batcher_serves_the_references_first_choice(weights, served, i):
    _, prompts, outs = served
    assert outs[i].size == REQUESTS[i][1]
    gaps = ref.served_token_gaps(weights, prompts[i], outs[i], DIMS, 192)
    # greedy in float32: the served byte is the reference's argmax, or a
    # tie within the tolerance on the logits
    assert float(gaps["gap"].max()) < TOL


def test_batcher_counts_summaries_turns_and_cells(served):
    srv, _, _ = served
    stats = srv.stats()
    # a request of prompt P and budget T commits P + T - 1 tokens
    ends = [p + t - 1 for p, t in REQUESTS]
    assert stats["eva_summaries_written"] == sum(e // C for e in ends)
    assert stats["eva_window_turns"] == sum(
        e // W - p // W for e, (p, _) in zip(ends, REQUESTS))
    assert stats["eva_window_cells_read"] > 0
    assert stats["eva_summary_cells_read"] > 0
    assert set(CapacityLedger.EVA_KEYS) <= set(stats)


def test_a_dense_batcher_keeps_no_eva_counters():
    model = gpt_tiny_test()
    params = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))[
        "params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=32)
    assert not set(CapacityLedger.EVA_KEYS) & set(srv.stats())
    cache = init_cache(model, 2, 32)
    same = server._set_feed_pad(cache, jnp.zeros(2, jnp.int32))
    assert all(a is b for a, b in zip(jax.tree.leaves(cache),
                                      jax.tree.leaves(same)))


def test_generate_goes_through_the_same_layer(params, served):
    """One prefill from position 0 and single steps at a shared index."""
    _, prompts, outs = served
    tokens = generate(eva_model(), params, jnp.asarray(prompts[0][None]),
                      max_new_tokens=12)
    tokens = tokens[0] if isinstance(tokens, tuple) else tokens
    assert np.asarray(tokens)[0, 37:49].tolist() == outs[0][:12].tolist()


# ---------------------------------------------------------------------------
# what the layout refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,word", [
    (dict(paged=True), "paged"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
])
def test_batcher_refuses_what_works_by_position(params, kw, word):
    with pytest.raises(NotImplementedError, match=word):
        ContinuousBatcher(eva_model(), params, batch_size=2, max_len=64,
                          **kw)


def test_speculative_batcher_refuses_the_layout(params):
    with pytest.raises(NotImplementedError, match="Speculative"):
        SpeculativeContinuousBatcher(eva_model(), eva_model(), params,
                                     params, batch_size=2, max_len=64)


def test_prime_and_submit_primed_are_refused(params):
    srv = ContinuousBatcher(eva_model(), params, batch_size=2, max_len=64)
    with pytest.raises(NotImplementedError, match="prime"):
        srv.prime(np.arange(8, dtype=np.int32), 4)
    primed = server.PrimedRequest(np.arange(8, dtype=np.int32), 1, 4, {})
    with pytest.raises(NotImplementedError, match="submit_primed"):
        srv.submit_primed(primed)


@pytest.mark.parametrize("kw", [
    dict(position="learned"), dict(sliding_window=8),
    dict(num_kv_heads=2), dict(attn_logit_cap=30.0),
])
def test_the_layer_refuses_what_it_does_not_compute(kw):
    model = eva_model().clone(**kw)
    with pytest.raises(NotImplementedError, match="eva"):
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), np.zeros((1, 8), np.int32)))


def test_window_must_be_whole_chunks():
    with pytest.raises(ValueError, match="multiple of chunk"):
        eva_lib.prefill(*(jnp.zeros((1, 8, 1, 4)),) * 3, jnp.zeros((1, 4)),
                        jnp.zeros((1, 4)), jnp.full((1,), 8), window=6,
                        chunk=4, scale=1.0)


# ---------------------------------------------------------------------------
# capacity and least bytes, against hand arithmetic
# ---------------------------------------------------------------------------

#: one layer of a cache of 192 positions: 32 window cells and 48 summaries
#: of K and V of 4 heads of 16 float32
EVA_LAYER = CacheState("eva", W + 48, 512, window=W, chunk=C)


def eva_ledger():
    return CapacityLedger(4, 192, 4 * 80 * 1536, [EVA_LAYER] * LAYERS)


@pytest.mark.parametrize("n,live,visible,held", [
    (0, 0, 0, 0),
    (31, 31, 0, 7),       # first window: nothing remote yet
    (32, 0, 8, 8),        # handed over: 8 summaries become visible
    (37, 5, 8, 9),
    (101, 5, 24, 25),     # 3 windows closed; chunk 24 written, not yet read
])
def test_ledger_counts_live_window_and_summaries(n, live, visible, held):
    ledger = eva_ledger()
    assert EVA_LAYER.attended(n) == (live, visible)
    # a cell is one layer's: the three layers hold and read alike
    assert ledger.read_cells(n) == LAYERS * (live + visible)
    assert ledger.row_cells(n) == LAYERS * (live + held)


@pytest.mark.parametrize("before,after,decoding,written,turns", [
    (0, 37, False, 9, 0),     # a prefill hands no window over
    (37, 41, True, 1, 0),     # chunk 9 completes at 40
    (62, 70, True, 2, 1),     # chunks 15 and 16, and the window at 64
    (40, 40, True, 0, 0),     # a frozen row commits nothing
])
def test_ledger_counts_summaries_and_turns(before, after, decoding, written,
                                           turns):
    ledger = eva_ledger()
    ledger.note_commit(before, after, decoding=decoding)
    ledger.note_scan([37, 101], 4)
    assert ledger.counters == {
        "eva_summaries_written": written, "eva_window_turns": turns,
        "eva_window_cells_read": 4 * (5 + 5),
        "eva_summary_cells_read": 4 * (8 + 24)}


def test_the_layers_say_what_the_ledger_counts():
    """The ledger is built from the layers' own descriptions; a slab of one
    cell per position has no counters of its own and its notes do nothing."""
    layers = layout_of(eva_model(), 64).layers
    eva = CapacityLedger(2, 64, kv_slab_bytes(init_cache(eva_model(), 2, 64)),
                         layers)
    assert eva.kinds == {"eva"} and set(eva.counters) == set(eva.EVA_KEYS)
    assert eva.cells_per_row == LAYERS * (W + 64 // C)
    assert layers[0].attended(37) == (5, 8)
    dense_model = gpt_tiny_test()
    dense = CapacityLedger(
        2, 32, kv_slab_bytes(init_cache(dense_model, 2, 32)),
        layout_of(dense_model, 32).layers)
    assert dense.kinds == {"kv"}
    dense.note_commit(0, 40)
    dense.note_scan([40], 4)
    assert dense.counters == {}


def test_batcher_capacity_and_least_bytes(params):
    srv = ContinuousBatcher(eva_model(), params, batch_size=4, max_len=192,
                            scan_depth=4, prompt_buckets=(64, 128, 192))
    # per row and layer: K and V of (32 window + 48 summary) cells of
    # 4 heads x 16 float32 = 2 x 80 x 256 B
    cell = 2 * HEADS * 16 * 4 * LAYERS
    assert srv.kv_stats()["allocated_bytes"] == 4 * (W + 192 // C) * cell
    for prompt in rows_of(2, [37, 101]):
        srv.submit(prompt, 6)
    srv.step()     # admits both, then one scan of 4 ticks at 37 and 101
    stats, param_bytes = srv.stats(), server._count_params(params)[1]
    assert stats["decode_least_bytes"] == 4 * (
        param_bytes + ((5 + 8) + (5 + 24)) * cell)
    assert stats["eva_window_cells_read"] == 4 * (5 + 5)
    assert stats["eva_summary_cells_read"] == 4 * (8 + 24)
    # now at 41 and 105 committed: 9 + 10 and 9 + 26 cells hold state in
    # each layer
    kv = srv.kv_stats()
    assert kv["used_cells"] == LAYERS * ((9 + 10) + (9 + 26))
    assert kv["used_bytes"] == kv["used_cells"] * cell // LAYERS


# ---------------------------------------------------------------------------
# the block's other two switches
# ---------------------------------------------------------------------------

def test_unit_offset_norm_is_rms_norm_with_gain_one_plus_scale():
    x = jax.random.normal(jax.random.key(0), (3, 5, 16)) * 3.0
    g = 0.1 * jax.random.normal(jax.random.key(1), (16,))
    got = UnitOffsetRMSNorm(epsilon=1e-5).apply({"params": {"scale": g}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * (1 + g)
    assert np.abs(np.asarray(got - want)).max() < 1e-6


def test_unit_offset_needs_rms():
    model = gpt_tiny_test(norm_unit_offset=True)
    with pytest.raises(ValueError, match="norm='rms'"):
        model.init(jax.random.key(0), np.zeros((1, 8), np.int32))


@pytest.mark.parametrize("fp32", [False, True])
def test_residual_stream_dtype(fp32):
    """With bfloat16 sublayers the stream is float32 only when asked."""
    model = gpt_tiny_test(fp32_residual=fp32).clone(dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))[
        "params"]
    _, state = model.apply({"params": params}, np.zeros((1, 8), np.int32),
                           capture_intermediates=lambda m, _: m.name
                           == "block_0")
    (out,) = jax.tree.leaves(state["intermediates"])
    assert out.dtype == (jnp.float32 if fp32 else jnp.bfloat16)
