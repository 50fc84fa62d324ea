"""The hybrid of state-space and attention layers with a routed expert layer
after each (models/transformer.py `Mamba2Mixer`, ops/ssm.py, models/moe.py
without a capacity) against the plain reference
(benchmarks/reference/granite_hybrid.py, which imports nothing of the
program), at a small size on the CPU with seeded weights, comparing LOGITS.

Size: hidden 64; a period of 3 `mamba` + 1 `attention`; Mamba-2 with 4
heads of 16, state 16, conv 4, chunks of 8; attention with 4 heads of 16
over 2 K/V heads; 8 experts of width 32, 3 a token, 4 held (0-3), a shared
expert of 48; 96 rows of a vocabulary of 192. Everything runs in float32
at the highest matmul precision, so the two computations differ by the
order of float32 sums alone (the chunked scan sums a chunk's pairs as a
matmul where the reference walks position by position): measured 5e-7 on
logits of magnitude 0.6 through prefill and decode. The tolerance is 1e-4,
two hundred times that; the same model with bfloat16 activations stands
7e-2 off and must fail it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from functools import partial

from benchmarks.reference import granite_hybrid as ref
from teacher_forced import programs, served_logits, worst_gap
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import _decode_clone, init_cache
from tfde_tpu.inference.server import (ContinuousBatcher,
                                       SpeculativeContinuousBatcher)
from tfde_tpu.models import moe
from tfde_tpu.models.cache_state import CacheState, layout_of
from tfde_tpu.models.gpt import GPT, gpt_tiny_test
from tfde_tpu.models.moe import MoEMlp
from tfde_tpu.models.transformer import Mamba2Mixer
from tfde_tpu.observability.capacity import CapacityLedger, kv_slab_bytes
from tfde_tpu.ops import ssm as ssm_lib

LAYERS = ("mamba", "mamba", "mamba", "attention")
EXPERTS, HELD, PER_TOKEN, VOCAB, CHUNK = 8, (0, 4), 3, 96, 8
DIMS = dict(
    hidden_size=64, intermediate_size=32, shared_intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    num_experts_per_tok=PER_TOKEN, vocab_size=VOCAB, rms_norm_eps=1e-5,
    attention_multiplier=1 / 16, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_chunk_size=CHUNK, mamba_expand=1, layer_types=LAYERS,
    published_experts=EXPERTS, held_experts=HELD)
SSM = ssm_lib.SSMShape(heads=4, head_dim=16, state=16, groups=1, conv=4,
                       chunk=CHUNK)
TOL = 1e-4


def hybrid_model(dtype=jnp.float32, held=HELD, vocab=VOCAB, **kw):
    fields = dict(
        vocab_size=vocab, hidden_size=64, depth=4, num_heads=4,
        num_kv_heads=2, mlp_dim=32, max_position=4096, dtype=dtype,
        position="none", norm="rms", ln_eps=1e-5, mlp_act="swiglu",
        use_bias=False, tie_embeddings=True, embed_scale=12.0,
        attn_scale=1 / 16, num_experts=EXPERTS, moe_every=1,
        experts_per_token=PER_TOKEN, moe_capacity_factor=None,
        moe_shared_expert_dim=48, moe_shared_expert_gated=False,
        moe_held_experts=held, mixers=LAYERS, ssm=SSM,
        residual_multiplier=0.22, logits_scaling=16.0)
    return GPT(**dict(fields, **kw))


def as_float32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, DIMS)


@pytest.fixture(scope="module")
def params(weights):
    return as_float32(ref.to_program_params(weights, DIMS))


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def forward():
    """The whole forward of the model as it is written, jitted: a shape
    compiles once, where an eager apply compiles every primitive."""
    model = hybrid_model()
    return jax.jit(lambda params, rows, last=None: model.apply(
        {"params": params}, rows, last=last))


def rows_of(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(weights, row, dims=DIMS) -> np.ndarray:
    return np.asarray(ref.forward(weights, jnp.asarray(row), dims))


# ---------------------------------------------------------------------------
# the mixer alone, and the full forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [1, 5, 8, 9, 37, 64])
def test_chunked_scan_matches_the_position_by_position_scan(weights, length):
    """`Mamba2Mixer` (chunks of 8 under a lax.scan) against the
    reference's `lax.scan` over positions, one layer's weights."""
    lw = weights["layers"][1]
    u = jax.random.normal(jax.random.key(length), (length, 64), jnp.float32)
    want = ref._mamba(u, lw, DIMS, "highest")
    block = as_float32(ref.to_program_params(weights, DIMS))[
        "decoder"]["block_1"]["mamba"]
    got = Mamba2Mixer(ssm=SSM, dtype=jnp.float32, ln_eps=1e-5).apply(
        {"params": block}, u[None])[0]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL * max(
        1.0, float(np.abs(np.asarray(want)).max()))


@pytest.mark.parametrize("length", [3, 8, 17, 40, 100])
def test_full_forward_matches_the_reference(weights, params, forward,
                                            length):
    (row,) = rows_of(length, [length])
    got = forward(params, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        < TOL


def test_bfloat16_for_float32_fails_the_tolerance(weights, params):
    (row,) = rows_of(1, [60])
    got = jax.jit(hybrid_model(jnp.bfloat16).apply)(
        {"params": params}, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        > 10 * TOL


def test_the_head_at_one_position_is_that_positions_logits(params, forward):
    rows = np.stack(rows_of(2, [24, 24]))
    full = forward(params, rows)
    last = jnp.asarray([5, 23])
    one = forward(params, rows, last)
    assert one.shape == (2, 1, VOCAB)
    assert np.abs(np.asarray(one[:, 0])
                  - np.asarray(full[jnp.arange(2), last])).max() < 1e-6


def test_init_creates_the_mixers_the_layer_list_names():
    tree = jax.eval_shape(lambda: hybrid_model().init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    blocks = tree["decoder"]
    assert ["mamba" in blocks[f"block_{i}"] for i in range(4)] == [
        True, True, True, False]
    assert "attn" in blocks["block_3"] and "wpe" not in tree
    moe = blocks["block_0"]["moe"]
    assert moe["experts_fc1"].shape == (4, 64, 32)       # the held four
    assert moe["router"]["kernel"].shape == (64, EXPERTS)  # routes over 8
    assert "shared_expert_gate" not in moe


def test_the_layer_list_is_as_long_as_the_depth(params):
    with pytest.raises(ValueError, match="depth"):
        hybrid_model(mixers=("mamba",)).apply(
            {"params": params}, np.zeros((1, 8), np.int32))


# ---------------------------------------------------------------------------
# prefill of a padded bucket, then decode through the cache, one logit
# vector a step: rows of different true lengths in one wave
# ---------------------------------------------------------------------------

# prompts that end inside a chunk (13), on a chunk edge (16), one token past
# it (17) and short of one conv tail (2), in one wave; every row decodes
# across chunk edges
SERVED = dict(lengths=[13, 16, 17, 2], totals=[40, 30, 44, 21], bucket=32,
              max_len=48)


@pytest.fixture(scope="module")
def honest():
    """The model as it is written, traced once for the tests that only
    read what it serves."""
    return programs(hybrid_model())


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
    mamba = cache["decoder"]["block_0"]["mamba"]
    assert mamba["ssm_state"].shape == (4, 4, 16, 16)      # no positions
    assert mamba["ssm_state"].dtype == jnp.float32
    assert mamba["conv_tail"].shape == (4, 3, 64 + 2 * 16)
    assert set(cache["decoder"]["block_3"]["attn"]) == {
        "cached_key", "cached_value", "cache_index"}


def test_a_frozen_rows_state_stands_to_the_bit(weights, params, honest):
    """Row 1 stops after 3 steps and is fed padding 20 more times: its
    state and its conv tail in every state-space layer stay as they were,
    and the other rows still agree with the reference."""
    shots = []
    gap, got, _ = serve(weights, params, honest, freeze=(1, 3),
                        snapshots=shots)
    assert len(got[1]) == 4
    assert gap < TOL
    for layer in ("block_0", "block_1", "block_2"):
        then, now = (s["decoder"][layer]["mamba"] for s in
                     (shots[2], shots[-1]))
        for leaf in ("ssm_state", "conv_tail"):
            assert np.array_equal(then[leaf][1], now[leaf][1]), (layer, leaf)
            assert not np.array_equal(then[leaf][0], now[leaf][0])


def test_a_long_prefill_takes_the_other_attention_paths(weights, params,
                                                       monkeypatch):
    """Past `_PREFILL_SCORES_BYTES` of scores a prefill into the slab
    attends over its own tokens alone where the cache is empty, and a
    block of queries at a time behind a cached prefix; a second
    multi-token call also continues the state-space layers from their
    cached state. Both are the reference's forward."""
    from tfde_tpu.models import transformer

    monkeypatch.setattr(transformer, "_PREFILL_SCORES_BYTES", 0)
    monkeypatch.setattr(transformer, "_PREFILL_QUERY_BLOCK", 8)
    gap, _, _ = serve(weights, params, programs(hybrid_model()))
    assert gap < TOL
    (row,) = rows_of(8, [32])
    model = _decode_clone(hybrid_model())
    cache = init_cache(hybrid_model(), 1, 48)
    first, mutated = model.apply({"params": params, "cache": cache},
                                 row[None, :16], mutable=["cache"])
    second, _ = model.apply({"params": params, "cache": mutated["cache"]},
                            row[None, 16:], mutable=["cache"])
    got = np.concatenate([np.asarray(first[0]), np.asarray(second[0])])
    assert np.abs(got - reference_logits(weights, row)).max() < TOL


# ways to get the model wrong, each of which must show
def _dt_not_zeroed_on_the_pads(monkeypatch):
    real = ssm_lib.prefill
    monkeypatch.setattr(
        ssm_lib, "prefill",
        lambda xbc, dt, a, d, state, lengths, shape: real(
            xbc, dt, a, d, state, jnp.full_like(lengths, xbc.shape[1]),
            shape))


def _conv_tail_at_the_buckets_end(monkeypatch):
    real = ssm_lib.causal_conv
    monkeypatch.setattr(
        ssm_lib, "causal_conv",
        lambda xbc, tail, kernel, bias, lengths: real(
            xbc, tail, kernel, bias, jnp.full_like(lengths, xbc.shape[1])))


def _d_dropped(monkeypatch):
    for name in ("prefill", "decode_step"):
        real = getattr(ssm_lib, name)
        monkeypatch.setattr(
            ssm_lib, name, lambda xbc, dt, a, d, *rest, _real=real: _real(
                xbc, dt, a, jnp.zeros_like(d), *rest))


def _gate_dropped(monkeypatch):
    real = ssm_lib.gated_rms_norm
    monkeypatch.setattr(
        ssm_lib, "gated_rms_norm",
        # silu(z) = 1 at z = 1.2785
        lambda y, z, gain, eps: real(y, jnp.full_like(z, 1.2785), gain, eps))


def _residual_multiplier_one(monkeypatch):
    return dict(residual_multiplier=1.0)


def _logits_scaling_dropped(monkeypatch):
    return dict(logits_scaling=None)


@pytest.mark.parametrize("break_it", [
    _dt_not_zeroed_on_the_pads, _conv_tail_at_the_buckets_end, _d_dropped,
    _gate_dropped, _residual_multiplier_one, _logits_scaling_dropped])
def test_a_broken_model_fails_the_tolerance(weights, params, monkeypatch,
                                            break_it):
    fields = break_it(monkeypatch) or {}
    gap, _, _ = serve(weights, params, programs(hybrid_model(**fields)))
    assert gap > 100 * TOL


# ---------------------------------------------------------------------------
# the expert layer without a capacity
# ---------------------------------------------------------------------------

def _layer_reference(lw, v, dims):
    out, _ = ref._moe(v, lw, dims, "highest")
    return np.asarray(out)


def _moe_layer(held, **kw):
    return MoEMlp(num_experts=EXPERTS, mlp_dim=32,
                  experts_per_token=PER_TOKEN, capacity_factor=None,
                  act="swiglu", use_bias=False, shared_expert_dim=48,
                  shared_expert_gated=False, held_experts=held,
                  dtype=jnp.float32, **kw)


def test_no_token_is_dropped_when_all_choose_one_expert(weights, params):
    """A router that sends every token's first choice to expert 2: with a
    capacity that expert overflows and tokens lose its part; without one
    every token keeps all three of its experts."""
    lw = dict(weights["layers"][0])
    router = np.asarray(lw["router"], np.float32)
    router[:, 2] = 0.5            # inputs below are positive
    lw["router"] = jnp.asarray(router)
    moe = dict(params["decoder"]["block_0"]["moe"])
    moe["router"] = {"kernel": lw["router"]}
    v = jnp.abs(jax.random.normal(jax.random.key(0), (2, 24, 64))) + 0.1
    want = _layer_reference(lw, v.reshape(48, 64), DIMS).reshape(2, 24, 64)
    got = _moe_layer(HELD).apply({"params": moe}, v)
    assert np.abs(np.asarray(got) - want).max() < TOL
    capped = MoEMlp(num_experts=EXPERTS, mlp_dim=32,
                    experts_per_token=PER_TOKEN, capacity_factor=1.25,
                    act="swiglu", use_bias=False, shared_expert_dim=48,
                    shared_expert_gated=False, dtype=jnp.float32)
    whole = {k: (jnp.concatenate([x, x]) if k.startswith("experts_") else x)
             for k, x in moe.items()}
    dropped = capped.apply({"params": whole}, v)
    assert dropped.shape == got.shape       # it runs, and loses tokens


@pytest.mark.parametrize("shape,held", [
    ((1, 1), (0, 4)), ((32, 1), (0, 4)), ((32, 1), (2, 6)),
    ((2, 24), (4, 8)), ((3, 50), (1, 5))])
def test_the_expert_layer_alone_matches_the_reference(weights, params, shape,
                                                      held, monkeypatch):
    """One token, a tick of 32 rows, held ranges that start above 0, and
    150 tokens in blocks of 64 (the last one padded): the layer with its
    shared expert against the reference's, which walks the held experts
    one by one over all tokens."""
    from tfde_tpu.models import moe as moe_lib

    monkeypatch.setattr(moe_lib, "token_block",
                        lambda n, *shape: min(n, 64))
    lw = weights["layers"][0]
    v = jax.random.normal(jax.random.key(5), shape + (64,))
    want = _layer_reference(lw, v.reshape(-1, 64),
                            dict(DIMS, held_experts=held)).reshape(v.shape)
    got, sown = _moe_layer(held).apply(
        {"params": params["decoder"]["block_0"]["moe"]}, v,
        mutable=["counters"])
    assert np.abs(np.asarray(got) - want).max() < TOL
    pairs, held_pairs, touched, busiest, moved, passes = np.asarray(
        jax.tree.leaves(sown)[0])
    tokens = shape[0] * shape[1]
    assert pairs == PER_TOKEN * tokens and 0 < held_pairs < pairs
    assert 0 < touched <= 4 and held_pairs / 4 <= busiest <= tokens
    # every pair is fetched back, and a slot for every pair and more is
    # filled on the way in
    assert moved >= 2 * pairs
    assert passes == -(-tokens // 64)       # one a block of 64 tokens


def test_a_rows_logits_are_the_same_alone_and_in_a_wave_of_four(params,
                                                                forward):
    rows = np.stack(rows_of(9, [24] * 4))
    together = np.asarray(forward(params, rows))
    for r in range(4):
        alone = np.asarray(forward(params, rows[r:r + 1]))[0]
        assert np.abs(alone - together[r]).max() < 1e-5


def test_the_shares_add_up_to_the_uncut_layer_and_vocabulary():
    """Guide section 4: the parts of one layer's result that the shares
    0-3 and 4-7 of 8 experts give, with the shared expert (which every
    chip computes alike) counted once, add up to the uncut reference's
    layer; the two halves of the vocabulary concatenate to the uncut
    logits."""
    uncut = dict(DIMS, held_experts=(0, EXPERTS), vocab_size=2 * VOCAB)
    w = ref.make_weights(11, uncut)
    lw = w["layers"][0]
    v = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    want = _layer_reference(lw, v, uncut)
    shared = np.asarray(ref._swiglu(v, lw["s_gate"], lw["s_up"],
                                    lw["s_down"], "highest"))
    whole = as_float32(ref.to_program_params(w, uncut))
    moe = whole["decoder"]["block_0"]["moe"]
    parts = []
    for first, end in ((0, 4), (4, 8)):
        mine = {k: (x[first:end] if k.startswith("experts_") else x)
                for k, x in moe.items()}
        parts.append(np.asarray(_moe_layer((first, end)).apply(
            {"params": mine}, v[None])[0]))
    assert np.abs(parts[0] + parts[1] - shared - want).max() < TOL
    assert np.abs(parts[0] - parts[1]).max() > 100 * TOL   # not one twice
    # the vocabulary: this chip's half through the program, the partner's
    # through the reference's head on the same hidden states
    (row,) = rows_of(4, [30])
    logits = reference_logits(w, row, uncut)
    half = dict(whole, wte={"embedding": whole["wte"]["embedding"][:VOCAB]})
    mine = np.asarray(jax.jit(hybrid_model(held=(0, EXPERTS)).apply)(
        {"params": half}, row[None])[0])
    theirs = reference_logits(dict(w, wte=jnp.concatenate(
        [w["wte"][:VOCAB], w["wte"][VOCAB:]])), row, uncut)[:, VOCAB:]
    assert np.abs(np.concatenate([mine, theirs], -1) - logits).max() < TOL


def test_a_share_needs_the_form_without_a_capacity():
    layer = MoEMlp(num_experts=8, mlp_dim=16, held_experts=(0, 4))
    with pytest.raises(NotImplementedError, match="capacity"):
        jax.eval_shape(lambda: layer.init(jax.random.key(0),
                                          jnp.zeros((1, 4, 8))))
    with pytest.raises(ValueError, match="range"):
        jax.eval_shape(lambda: MoEMlp(
            num_experts=8, mlp_dim=16, capacity_factor=None,
            held_experts=(4, 9)).init(jax.random.key(0),
                                      jnp.zeros((1, 4, 8))))


# ---------------------------------------------------------------------------
# through ContinuousBatcher
# ---------------------------------------------------------------------------

REQUESTS = ((13, 20), (16, 5), (17, 30), (30, 9), (2, 12), (24, 25))


@pytest.fixture(scope="module")
def served(params):
    """Six requests through four rows and two buckets: a wave of four with
    different true lengths, budgets of 5 and 9 that finish (and freeze)
    while the others run, later requests into freed rows. Every blocking
    fetch is counted."""
    fetches = []
    real = server._fetch
    server._fetch = lambda tree: fetches.append(1) or real(tree)
    try:
        with jax.default_matmul_precision("highest"):
            srv = ContinuousBatcher(hybrid_model(), params, batch_size=4,
                                    max_len=64, scan_depth=4,
                                    prompt_buckets=(16, 32, 64))
            prompts = rows_of(11, [n for n, _ in REQUESTS])
            rids = [srv.submit(p, b) for p, (_, b) in zip(prompts, REQUESTS)]
            out = dict(srv.run())
    finally:
        server._fetch = real
    return srv, prompts, [out[r] for r in rids], len(fetches)


@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_batcher_serves_the_references_first_choice(weights, served, i):
    _, prompts, outs, _ = served
    assert outs[i].size == REQUESTS[i][1]
    gaps = ref.served_token_gaps(weights, prompts[i], outs[i], DIMS, 64)
    # greedy in float32: the served token is the reference's argmax, or a
    # tie within the tolerance on the logits
    assert float(gaps["gap"].max()) < TOL
    assert gaps["routes"].shape == (4, REQUESTS[i][0] + REQUESTS[i][1],
                                    PER_TOKEN)


def test_batcher_counts_state_cells_and_routing(served):
    srv, _, _, fetches = served
    stats = srv.stats()
    assert set(CapacityLedger.HYBRID_KEYS) <= set(stats)
    # every real token fed, in a wave or a tick, is routed in 4 layers to 3
    # experts; a request of prompt P and budget T feeds P + T - 1 tokens,
    # and a wave of three repeats its first row to fill the ladder
    fed = sum(p + t - 1 for p, t in REQUESTS)
    assert stats["moe_pairs"] >= 4 * PER_TOKEN * fed
    assert stats["moe_pairs"] <= 4 * PER_TOKEN * (fed + 64)
    assert 0.3 < stats["moe_pairs_held"] / stats["moe_pairs"] < 0.7
    assert stats["moe_pairs_busiest"] * 4 >= stats["moe_pairs_held"] / 4
    assert 0 < stats["moe_experts_touched"] <= 4 * 4 * (
        stats["rounds"] + stats["prefill_waves"])
    assert stats["ssm_state_bytes_touched"] > 0 and stats["kv_cells_read"] > 0
    # rows copied into and out of sorted order: every pair comes back and
    # at least as many slots are filled, padding tokens' among them
    assert stats["moe_rows_moved"] >= 2 * stats["moe_pairs"]
    assert stats["moe_rows_moved"] < 40 * stats["moe_pairs"]
    # a pass over the held weights a block, layer and call: every wave and
    # every tick here is one block in each of the four expert layers
    assert stats["moe_weight_passes"] == 4 * (
        stats["prefill_waves"] + stats["rounds"])
    # the counts ride the fetch each wave and each scan already makes
    assert fetches == stats["syncs"] == stats["prefill_waves"] + stats["scans"]


def test_a_dense_batcher_keeps_no_hybrid_counters_and_sows_nothing():
    model = gpt_tiny_test()
    params = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))[
        "params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=32)
    assert not set(CapacityLedger.HYBRID_KEYS) & set(srv.stats())
    assert "moe_weight_passes" in CapacityLedger.HYBRID_KEYS
    assert srv._ledger.kinds == {"kv"} and srv._ledger.counters == {}
    srv.submit(np.arange(5, dtype=np.int32), 6)
    assert len(srv.run()) == 1
    assert server._sown_counters({"cache": {}}) is None


@pytest.mark.parametrize("kw,word", [
    (dict(paged=True), "paged"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
])
def test_batcher_refuses_what_works_by_position(params, kw, word):
    with pytest.raises(NotImplementedError, match=word):
        ContinuousBatcher(hybrid_model(), params, batch_size=2, max_len=64,
                          **kw)


def test_speculation_and_the_primed_hand_off_are_refused(params):
    with pytest.raises(NotImplementedError, match="Speculative"):
        SpeculativeContinuousBatcher(hybrid_model(), hybrid_model(), params,
                                     params, batch_size=2, max_len=64)
    srv = ContinuousBatcher(hybrid_model(), params, batch_size=2, max_len=64)
    with pytest.raises(NotImplementedError, match="prime"):
        srv.prime(np.arange(8, dtype=np.int32), 4)
    primed = server.PrimedRequest(np.arange(8, dtype=np.int32), 1, 4, {})
    with pytest.raises(NotImplementedError, match="submit_primed"):
        srv.submit_primed(primed)


def test_the_refusal_is_asked_of_the_model_not_of_a_family():
    assert layout_of(gpt_tiny_test()).not_by_position is None
    assert "mamba" in layout_of(hybrid_model()).not_by_position
    assert "eva" in layout_of(
        gpt_tiny_test(position="rope", attention="eva")).not_by_position


# ---------------------------------------------------------------------------
# capacity and least bytes, against hand arithmetic
# ---------------------------------------------------------------------------

def test_the_layers_give_the_ledger_states_beside_cells(params):
    model = hybrid_model()
    cache = init_cache(model, 2, 64)
    ledger = CapacityLedger(2, 64, kv_slab_bytes(cache),
                            layout_of(model, 64).layers,
                            moe.held_experts(params))
    assert ledger.kinds == {"kv", "state"}
    assert set(ledger.counters) == set(ledger.HYBRID_KEYS)
    # a position: one attention layer, K and V of 2 heads of 16, float32
    per_position = 2 * 2 * 16 * 4
    # a row's state: three layers of [4, 16, 16] float32 and a tail of
    # 3 x 96 float32
    state = 3 * (4 * 16 * 16 * 4 + 3 * 96 * 4)
    assert ledger.slab_bytes == 2 * (64 * per_position + state)
    # a state is bytes and no cell; a tick reads it and writes it back
    assert ledger.cells_per_row == 64
    assert ledger.row_bytes == 64 * per_position + state
    assert ledger.row_cells(0) == 0
    assert ledger.row_cells(37) == 37             # grows with attention only
    assert ledger.read_cells(37) == 37
    assert ledger.read_bytes([37]) == 37 * per_position + 2 * state
    assert ledger.observe([0, 37], [1, 2])["used_bytes"] == (
        37 * per_position + 2 * state)


def test_least_bytes_count_the_touched_experts_only():
    expert = 3 * 64 * 32 * 4                      # one expert of one layer
    ledger = CapacityLedger(
        batch_size=2, positions=64, slab_bytes=2 * (64 * 256 + 4096),
        states=[CacheState("kv", 64, 256),
                CacheState("state", fixed_bytes=4096)],
        experts=moe.HeldExperts(4 * 4 * expert, 4 * 4))
    params = 100_000 + 16 * expert
    assert ledger.read_bytes([10]) == 10 * 256 + 2 * 4096
    # two ticks in which 5 and 7 (layer, expert) slots received a pair
    got = ledger.scan_least_bytes(params, 9_000, 2, [60, 30, 12, 9, 400])
    assert got == 2 * (100_000 + 9_000) + 12 * expert
    ledger.note_scan([10, 20], 2)
    ledger.note_routed([60, 30, 12, 9, 400, 7])
    ledger.note_routed(None)
    assert ledger.counters == {
        "ssm_state_bytes_touched": 2 * 2 * 2 * 4096,
        "kv_cells_read": 2 * 30, "moe_pairs": 60, "moe_pairs_held": 30,
        "moe_experts_touched": 12, "moe_pairs_busiest": 9,
        "moe_rows_moved": 400, "moe_weight_passes": 7}
    dense = CapacityLedger(2, 64, 2 * 64 * 256)
    assert dense.scan_least_bytes(1000, 50, 3, None) == 3 * 1050
    dense.note_routed([1, 1, 1, 1, 1, 1])
    assert dense.counters == {}


def test_batcher_least_bytes_follow_the_ledger(params, served):
    srv, _, _, _ = served
    stats = srv.stats()
    ledger = srv._ledger
    outside = srv._param_bytes - ledger._experts.bytes
    assert ledger._experts == (4 * 4 * 3 * 64 * 32 * 4, 4 * 4)
    low = stats["rounds"] * outside
    assert low < stats["decode_least_bytes"] < (
        stats["rounds"] * (srv._param_bytes + 4 * ledger.row_bytes))
