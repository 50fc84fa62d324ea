"""MoE tests: routing conservation, capacity behavior, aux-loss wiring into
the default train step, expert-parallel sharding + numerics parity with DP
(SURVEY.md §4 fake-device methodology)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tfde_tpu.models import moe as moe_lib
from tfde_tpu.models.moe import MoEMlp, dispatch_shape, group_capacity
from tfde_tpu.models.transformer import Encoder
from tfde_tpu.ops import moe_gmm
from tfde_tpu.parallel.strategies import (
    ExpertParallelStrategy,
    MirroredStrategy,
    MultiWorkerMirroredStrategy,
)


def test_moe_output_shape_and_aux_loss(rng):
    m = MoEMlp(num_experts=4, mlp_dim=32, dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    v = m.init(jax.random.key(0), x)
    # init itself sows into 'losses'; the training path (init_state) keeps
    # only params/batch_stats, so mirror that here
    y, mutated = m.apply({"params": v["params"]}, x, mutable=["losses"])
    assert y.shape == x.shape
    aux = jax.tree_util.tree_leaves(mutated["losses"])
    assert len(aux) == 1
    # balanced-ish random routing: aux ~ weight * E * sum(f*p) ~ weight
    assert 0.0 < float(aux[0]) < 1.0


def test_moe_router_z_loss(rng):
    """ST-MoE z-loss: off by default (one sown loss — the numerics every
    existing test pins); when enabled, a second sown loss appears, equal
    to weight * mean(logsumexp(router logits)^2), and scaling the router
    weights up increases it (the drift it exists to penalize)."""
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    m0 = MoEMlp(num_experts=4, mlp_dim=32, dtype=jnp.float32)
    v = m0.init(jax.random.key(0), x)
    _, mut = m0.apply({"params": v["params"]}, x, mutable=["losses"])
    assert len(jax.tree_util.tree_leaves(mut["losses"])) == 1  # off

    mz = MoEMlp(num_experts=4, mlp_dim=32, dtype=jnp.float32,
                router_z_loss_weight=1e-3)
    _, mut = mz.apply({"params": v["params"]}, x, mutable=["losses"])
    losses = mut["losses"]
    assert "moe_z" in losses and "moe_aux" in losses
    (z,) = jax.tree_util.tree_leaves(losses["moe_z"])
    logits = x.reshape(2, 8, 16).astype(jnp.float32) @ np.asarray(
        v["params"]["router"]["kernel"]
    )
    expect = 1e-3 * float(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    np.testing.assert_allclose(float(z), expect, rtol=1e-5)

    # bigger router logits -> bigger z penalty
    import flax

    v2 = flax.core.unfreeze(jax.tree_util.tree_map(lambda a: a, v["params"]))
    v2["router"]["kernel"] = v2["router"]["kernel"] * 5.0
    _, mut2 = mz.apply({"params": v2}, x, mutable=["losses"])
    (z2,) = jax.tree_util.tree_leaves(mut2["losses"]["moe_z"])
    assert float(z2) > float(z)


def test_moe_full_capacity_top1_is_lossless_combine(rng):
    """With capacity >= all tokens and k=1, every token is processed by its
    top expert: output must equal the hand-computed per-expert MLP."""
    m = MoEMlp(
        num_experts=2, mlp_dim=8, experts_per_token=1,
        capacity_factor=4.0, dtype=jnp.float32,
    )
    x = jnp.asarray(rng.standard_normal((1, 6, 4)), jnp.float32)
    v = m.init(jax.random.key(0), x)
    y = m.apply(v, x, mutable=["losses"])[0]

    p = v["params"]
    tokens = np.asarray(x).reshape(6, 4)
    logits = tokens @ np.asarray(p["router"]["kernel"])
    top = logits.argmax(-1)
    expect = np.zeros((6, 4), np.float32)
    for i, e in enumerate(top):
        h = tokens[i] @ np.asarray(p["experts_fc1"])[e] + np.asarray(p["experts_b1"])[e, 0]
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        expect[i] = h @ np.asarray(p["experts_fc2"])[e] + np.asarray(p["experts_b2"])[e, 0]
    np.testing.assert_allclose(np.asarray(y).reshape(6, 4), expect,
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_overflow(rng):
    """capacity_factor tiny -> most tokens dropped -> output mostly zeros
    (the residual path handles them in a full block)."""
    m = MoEMlp(
        num_experts=2, mlp_dim=8, experts_per_token=1,
        capacity_factor=0.01, dtype=jnp.float32,
    )
    x = jnp.asarray(rng.standard_normal((1, 64, 4)), jnp.float32)
    v = m.init(jax.random.key(0), x)
    y = m.apply(v, x, mutable=["losses"])[0]
    zero_rows = np.sum(np.all(np.asarray(y).reshape(64, 4) == 0.0, axis=-1))
    assert zero_rows >= 60  # capacity 1 per expert -> <= 2 processed


def _run_encoder(strategy, steps=3):
    from tfde_tpu.training.step import init_state, make_train_step

    import flax.linen as nn

    class Clf(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape(x.shape[0], 8, 8)
            x = nn.Dense(16, dtype=jnp.float32, name="embed")(x)
            x = Encoder(
                depth=2, num_heads=2, head_dim=8, mlp_dim=32,
                dtype=jnp.float32, num_experts=4, moe_every=2,
                name="encoder",
            )(x, train=train)
            return nn.Dense(10, dtype=jnp.float32, name="head")(
                jnp.mean(x, axis=1)
            )

    m = Clf()
    sample = np.zeros((16, 64), np.float32)
    # SGD, not Adam: layout parity is asserted to float tolerance, and
    # Adam's m/sqrt(v) early steps amplify reduction-order noise to O(lr)
    state, _ = init_state(m, optax.sgd(0.1), strategy, sample, seed=0)
    step = make_train_step(strategy, state, donate=False)
    rng = np.random.default_rng(0)
    images = rng.random((16, 64), np.float32)
    labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
    key = jax.random.key(0)
    first = None
    for _ in range(steps):
        state, metrics = step(state, (images, labels), key)
        if first is None:
            first = float(metrics["loss"])
    return jax.device_get(state.params), first, float(metrics["loss"])


def test_dispatch_tensor_linear_in_tokens_at_fixed_group_size():
    """The GShard per-group formulation (VERDICT r2 weak #4): at fixed group
    size, doubling the token count doubles the dispatch tensor — capacity is
    per-group, NOT proportional to the global token count."""
    import math

    base = dispatch_shape(batch=8, seq=512, num_experts=16)
    doubled = dispatch_shape(batch=16, seq=512, num_experts=16)
    assert doubled[0] == 2 * base[0]          # twice the groups
    assert doubled[1:] == base[1:]            # same per-group shape
    assert math.prod(doubled) == 2 * math.prod(base)  # linear, not quadratic

    # BERT-base scale-config sanity (the round-2 blowup case: 256x512 tokens
    # where global capacity c ∝ n made the [n,e,c] dispatch ~TB-scale):
    # per-group fp32 dispatch now stays under 1 GB.
    g, m, e, c = dispatch_shape(batch=256, seq=512, num_experts=64)
    assert c == group_capacity(512, 64, 2, 1.25)  # ∝ seq, not batch*seq
    assert g * m * e * c * 4 < 1e9


def test_group_capacity_is_per_group():
    # 128 tokens/group, 8 experts, k=2, cf=1.0 -> 32 slots per expert/group,
    # independent of how many groups exist
    assert group_capacity(128, 8, 2, 1.0) == 32
    assert dispatch_shape(batch=4, seq=128, num_experts=8,
                          capacity_factor=1.0)[3] == 32
    assert dispatch_shape(batch=400, seq=128, num_experts=8,
                          capacity_factor=1.0)[3] == 32


def test_moe_grouped_routing_matches_reference_per_group(rng):
    """With two identical sequences, full capacity, and k=1, per-group
    routing must give both sequences identical outputs (groups are
    independent)."""
    m = MoEMlp(num_experts=2, mlp_dim=8, experts_per_token=1,
               capacity_factor=4.0, dtype=jnp.float32)
    one = rng.standard_normal((1, 6, 4))
    x = jnp.asarray(np.concatenate([one, one], axis=0), jnp.float32)
    v = m.init(jax.random.key(0), x)
    y = m.apply(v, x, mutable=["losses"])[0]
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y[1]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_moe_encoder_trains_and_ep_matches_dp():
    p_dp, first_dp, last_dp = _run_encoder(MultiWorkerMirroredStrategy())
    assert last_dp < first_dp  # training works with the sown aux loss
    p_ep, first_ep, last_ep = _run_encoder(ExpertParallelStrategy(data=2))
    np.testing.assert_allclose(first_dp, first_ep, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5),
        p_dp, p_ep,
    )


def test_ep_weights_actually_sharded():
    from tfde_tpu.training.step import init_state

    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return MoEMlp(num_experts=8, mlp_dim=32, dtype=jnp.float32)(
                x, train=train
            )

    s = ExpertParallelStrategy(data=1)  # expert=8
    state, _ = init_state(
        M(), optax.sgd(0.1), s, np.zeros((4, 4, 16), np.float32)
    )
    fc1 = state.params["MoEMlp_0"]["experts_fc1"]
    assert fc1.sharding.spec == P("expert", None, None)
    assert state.params["MoEMlp_0"]["router"]["kernel"].sharding.spec in (
        P(), P(None, None),
    )


@pytest.mark.slow
def test_moe_gpt_custom_path_trains_with_sown_losses():
    """VERDICT r4 weak #5 follow-on: the custom-LM path (next_token_loss)
    must collect the sown MoE losses — sow() into an immutable collection
    is a silent no-op, which would train routing unbalanced. The aux and
    z losses must appear in metrics and join the objective."""
    from tfde_tpu.models.gpt import gpt_tiny_test, next_token_loss
    from tfde_tpu.training.step import init_state, make_custom_train_step

    s = MirroredStrategy()
    m = gpt_tiny_test(num_experts=4, moe_every=2, router_z_loss_weight=1e-3)
    sample = np.zeros((8, 16), np.int32)
    state, _ = init_state(m, optax.sgd(0.01), s, sample, seed=0)
    step = make_custom_train_step(s, state, next_token_loss)
    toks = np.random.default_rng(0).integers(0, 97, (8, 16)).astype(np.int32)
    state, metr = step(state, (toks,), jax.random.key(0))
    assert "moe_aux" in metr and "moe_z" in metr
    aux = float(metr["moe_aux"])
    z = float(metr["moe_z"])
    assert aux > 0.0 and z > 0.0
    # dense model through the same path: no sown keys, still trains
    m2 = gpt_tiny_test()
    state2, _ = init_state(m2, optax.sgd(0.01), s, sample, seed=0)
    step2 = make_custom_train_step(s, state2, next_token_loss)
    _, metr2 = step2(state2, (toks,), jax.random.key(0))
    assert "moe_aux" not in metr2


# ---------------------------------------------------------------------------
# Without a capacity (capacity_factor=None): the layout by expert, the
# Mosaic grouped matmul (interpreted here) and the way back, against a plain
# per-token loop. 24 experts, TEN a token as the served model; float32 at
# the highest matmul precision, so layer and loop differ by the order of
# float32 sums alone: measured 2e-7 .. 6e-7 on outputs of magnitude 0.3 .. 2.
# The tolerance is 1e-5; a broken form must miss it by 100 x (1e-3).
# ---------------------------------------------------------------------------

UNCAPPED_TOL = 1e-5
_E, _K, _D, _F = 24, 10, 16, 8

#: name -> (x's [rows, seq], held range, token block, what to do to the
#: router, feed_pad per row or None)
UNCAPPED_CASES = {
    "every_token_chooses_one_expert": ((2, 24), (0, 12), 2048, "one", None),
    "no_pair_held": ((2, 24), (0, 4), 2048, "none", None),
    "held_range_starts_above_zero": ((2, 24), (7, 19), 2048, None, None),
    "every_pair_held": ((2, 24), None, 2048, None, None),
    "n_not_a_multiple_of_the_block": ((3, 50), (0, 12), 32, None, None),
    "rows_with_feed_pad": ((3, 20), (0, 12), 2048, None, (0, 5, 19)),
    "one_token": ((1, 1), (0, 12), 2048, None, None),
    "a_tick_of_32_rows": ((32, 1), (0, 12), 2048, None, None),
    # relu on the gate, and a router that reads an input of its own (the
    # attention sublayer's, in the block that has one): the two shares
    "reglu_own_router_input_lower_share": ((2, 24), (0, 12), 2048, None,
                                           None),
    "reglu_own_router_input_upper_share": ((2, 24), (12, 24), 2048, None,
                                           None),
}


def _reglu(name) -> bool:
    return name.startswith("reglu_own_router_input")


def _router_input(name, x):
    """What the router reads in case `name`: another array than x in the
    cases named so, x itself (None) in the others."""
    if not _reglu(name):
        return None
    return jax.random.normal(jax.random.key(9), x.shape)


def _uncapped_layer(held, **kw):
    return MoEMlp(**{**dict(
        num_experts=_E, mlp_dim=_F, experts_per_token=_K,
        capacity_factor=None, act="swiglu", use_bias=False,
        held_experts=held, dtype=jnp.float32), **kw})


def _uncapped_case(name):
    (rows, seq), held, block, router, pad = UNCAPPED_CASES[name]
    x = jnp.abs(jax.random.normal(jax.random.key(3), (rows, seq, _D))) + 0.1
    layer = _uncapped_layer(held, decode=pad is not None,
                            act="reglu" if _reglu(name) else "swiglu")
    params = jax.tree.map(np.asarray, layer.init(
        jax.random.key(4), x)["params"])
    kernel = np.array(params["router"]["kernel"])
    if router == "one":         # x is positive: every token's first choice
        kernel[:, 2] = 0.5
    if router == "none":        # ... and nobody's choice is a held expert
        kernel[:, :held[1]] = -0.5
    params["router"]["kernel"] = kernel
    return layer, params, x, held or (0, _E), block, pad


def _loop_reference(params, x, lo, hi, real, router_input=None,
                    relu=False):
    """Token by token, choice by choice, in float32: (y [rows, seq, d],
    the four routing counts over the tokens marked `real`)."""
    x2 = np.asarray(x, np.float32).reshape(-1, x.shape[-1])
    r2 = x2 if router_input is None else np.asarray(
        router_input, np.float32).reshape(x2.shape)
    silu = (lambda a: np.maximum(a, 0)) if relu else (
        lambda a: a / (np.float32(1) + np.exp(-a)))
    y = np.zeros_like(x2)
    per_expert = np.zeros(hi - lo, np.int64)
    for t, v in enumerate(x2):
        logits = r2[t] @ params["router"]["kernel"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        chosen = np.argsort(-probs, kind="stable")[:_K]
        gates = probs[chosen] / probs[chosen].sum()
        for e, g in zip(chosen, gates):
            if lo <= e < hi:
                h = silu(v @ params["experts_gate"][e - lo]) * (
                    v @ params["experts_fc1"][e - lo])
                y[t] += g * (h @ params["experts_fc2"][e - lo])
                per_expert[e - lo] += bool(real[t])
    counts = [int(real.sum()) * _K, int(per_expert.sum()),
              int((per_expert > 0).sum()), int(per_expert.max())]
    return y.reshape(x.shape), counts


def _apply_uncapped(layer, params, x, block, pad, monkeypatch,
                    router_input=None):
    monkeypatch.setattr(moe_lib, "token_block",
                        lambda n, *shape: min(n, block))
    variables = {"params": params}
    if pad is not None:
        variables["cache"] = {"feed_pad": jnp.asarray(pad, jnp.int32)}
    with jax.default_matmul_precision("highest"):
        y, mutated = layer.apply(variables, x, router_input=router_input,
                                 mutable=["counters", "cache"])
    return np.asarray(y), np.asarray(jax.tree.leaves(mutated["counters"])[0])


def _real_tokens(x, pad):
    rows, seq = x.shape[:2]
    pad = np.zeros(rows, int) if pad is None else np.asarray(pad)
    return (np.arange(seq)[None, :] < seq - pad[:, None]).reshape(-1)


@pytest.mark.parametrize("name", list(UNCAPPED_CASES))
def test_uncapped_layer_matches_the_per_token_loop(name, monkeypatch):
    layer, params, x, (lo, hi), block, pad = _uncapped_case(name)
    r = _router_input(name, x)
    want, counts = _loop_reference(params, x, lo, hi, _real_tokens(x, pad),
                                   r, _reglu(name))
    got, counted = _apply_uncapped(layer, params, x, block, pad, monkeypatch,
                                   r)
    assert np.abs(got - want).max() < UNCAPPED_TOL
    if name == "no_pair_held":
        assert counts[1] == 0 and not got.any()
    if name == "every_token_chooses_one_expert":
        assert counts[3] == x.shape[0] * x.shape[1]
    # the four routing counts are the loop's, whatever the dispatch
    assert counted[:4].tolist() == counts


@pytest.mark.parametrize("name", list(UNCAPPED_CASES))
def test_rows_moved_reads_what_the_form_moves(name, monkeypatch):
    """Every slot of every block's layout is gathered on the way in (pads
    and unused tiles too), every pair fetched on the way back."""
    layer, params, x, (lo, hi), block, pad = _uncapped_case(name)
    _, counted = _apply_uncapped(layer, params, x, block, pad, monkeypatch)
    n = x.shape[0] * x.shape[1]
    block = min(n, block)
    pairs = block * _K
    tile = moe_gmm.tile_rows(pairs, _E)
    slots = moe_gmm.tiles_bound(pairs, hi - lo, tile) * tile
    assert counted[4] == -(-n // block) * (slots + pairs)
    assert slots >= pairs and tile % 16 == 0
    # ... in as many passes over the held weights as the call has blocks
    assert counted[5] == -(-n // block)


def test_blocks_of_a_call_agree_to_float32_rounding(monkeypatch):
    """A token's result does not depend on which tokens share its block:
    150 tokens in one block, two and three (the last one filled) differ
    by the order of float32 sums alone, and the four routing counts are
    the same; the rows moved and the passes follow the blocks."""
    layer, params, x, _, _, _ = _uncapped_case(
        "n_not_a_multiple_of_the_block")
    got = {block: _apply_uncapped(layer, params, x, block, None, monkeypatch)
           for block in (150, 75, 64)}
    whole, counted = got[150]
    assert np.abs(whole).max() > 0.1 and counted[5] == 1
    for block, passes in ((75, 2), (64, 3)):
        y, c = got[block]
        assert np.abs(y - whole).max() < UNCAPPED_TOL
        assert c[:4].tolist() == counted[:4].tolist() and c[5] == passes


#: (experts routed over, held, k, d) of the four served cells' expert
#: layers, all bfloat16
_CELL_7, _CELL_8 = (72, 36, 10, 4096), (64, 64, 6, 2560)
_CELL_9, _CELL_10 = (128, 32, 8, 4096), (512, 256, 10, 2048)


@pytest.mark.parametrize("n,shape,block,tile", [
    # a wave of 8,192 tokens in each cell: the least multiple of 2,048 at
    # which an expert's even share reaches 256 rows (284, 384, 256; and
    # 8,192 for 160 of them where 512 experts share the pairs: the next
    # step's copy would pass the byte budget)
    (8192, _CELL_7, 2048, 128), (8192, _CELL_8, 4096, 128),
    (8192, _CELL_9, 4096, 128), (8192, _CELL_10, 8192, 64),
    (16384, _CELL_10, 8192, 64),
    # the widest waves, in equal blocks
    (30720, _CELL_10, 7680, 64), (61440, _CELL_10, 7680, 64),
    (30720, _CELL_9, 3840, 64),
    (28672, _CELL_8, 4096, 128), (14336, _CELL_8, 3584, 128),
    # a decode tick and a call under 2,048 tokens are one block
    (48, _CELL_10, 48, 16), (1024, _CELL_8, 1024, 32),
    # 12,288 tokens are two blocks of 6,144, not 8,192 and a half-empty
    # one
    (12288, _CELL_10, 6144, 32),
    # where 2,048 is the target the call is cut as it was: 3,072 tokens
    # are two blocks of 2,048, the second half filled, not two of 1,536
    (3072, _CELL_7, 2048, 128),
    # 10,240 tokens under a target of 4,096: three blocks of 3,584 would
    # hold 512 fill tokens more than the call has today, four of 2,560
    (10240, _CELL_9, 2560, 64),
    # the cap wins: at d = 16,384 the sorted copy of 4,096 tokens' pairs
    # would be 0.81 GB, so the block stays at 2,048 and its share at 40
    (8192, (512, 256, 10, 16384), 2048, 16),
])
def test_the_block_follows_the_shapes(n, shape, block, tile):
    experts, held, k, d = shape
    got = moe_lib.token_block(n, k, experts, held, d, 2)
    assert got == block
    assert moe_gmm.tile_rows(got * k, experts) == tile
    pairs = got * k
    copy = moe_gmm.tiles_bound(pairs, held, tile) * tile * d * 2
    assert got <= 2048 or copy <= moe_lib._SORTED_COPY_BYTES


@pytest.mark.parametrize("shape", [_CELL_7, _CELL_8, _CELL_9, _CELL_10])
def test_no_call_carries_more_fill_than_rounding_up_to_2048(shape):
    """Whatever the call's tokens: the blocks hold them all, are whole
    steps of 256 tokens past 2,048, never fewer than 2,048 each where the
    call has that many, and together no larger than the call rounded up
    to 2,048, which is what it was cut into before."""
    experts, held, k, d = shape
    rng = np.random.default_rng(experts)
    calls = set(rng.integers(1, 70000, 400).tolist()) | {
        2048 * m for m in range(1, 33)} | {1, 2047, 2049, 65536}
    for n in sorted(calls):
        block = moe_lib.token_block(n, k, experts, held, d, 2)
        blocks = -(-n // block)
        assert blocks * block >= n
        assert blocks * block <= -(-n // 2048) * 2048 or n < 2048
        if n <= 2048:
            assert block == n
        else:
            assert block >= 2048 and block % 256 == 0


def _one_tile_left_out(monkeypatch):
    real = moe_lib._layout

    def broken(*args):
        slot, tile_expert, live, counted = real(*args)
        return slot, tile_expert, live - 1, counted

    monkeypatch.setattr(moe_lib, "_layout", broken)


def _choices_unweighted(monkeypatch):
    real = moe_lib._rows_back
    monkeypatch.setattr(
        moe_lib, "_rows_back",
        lambda out, slot, here, vals: real(out, slot, here,
                                           jnp.ones_like(vals)))


@pytest.mark.parametrize("break_it", [_one_tile_left_out,
                                      _choices_unweighted])
@pytest.mark.parametrize("name", [n for n in UNCAPPED_CASES
                                  if n != "no_pair_held"])
def test_a_broken_dispatch_fails_the_tolerance_a_hundredfold(
        name, break_it, monkeypatch):
    """The last tile of held rows never multiplied; the k results summed
    without their gate weights. (With no pair held there is nothing to
    break: that case is left out.)"""
    layer, params, x, (lo, hi), block, pad = _uncapped_case(name)
    r = _router_input(name, x)
    want, _ = _loop_reference(params, x, lo, hi, _real_tokens(x, pad), r,
                              _reglu(name))
    break_it(monkeypatch)
    # `_held_pairs` is jitted: a trace from before the break (or with it)
    # must not serve this test (or the next)
    moe_lib._held_pairs.clear_cache()
    try:
        got, _ = _apply_uncapped(layer, params, x, block, pad, monkeypatch,
                                 r)
    finally:
        moe_lib._held_pairs.clear_cache()
    assert not np.abs(got - want).max() < 100 * UNCAPPED_TOL


@pytest.mark.parametrize("act", ["swiglu", "reglu"])
def test_two_shares_add_up_to_the_uncut_layer(act, monkeypatch):
    """Experts 0-11 here and 12-23 on the partner, the router reading an
    input of its own on both: the two partial results add up to what the
    layer that holds all 24 gives, and neither is the whole."""
    x = jax.random.normal(jax.random.key(3), (2, 24, _D))
    r = jax.random.normal(jax.random.key(9), x.shape)
    whole = _uncapped_layer(None, act=act)
    params = whole.init(jax.random.key(4), x)["params"]
    want, _ = _apply_uncapped(whole, params, x, 2048, None, monkeypatch, r)
    parts = []
    for lo, hi in ((0, 12), (12, 24)):
        mine = {k: (v[lo:hi] if k.startswith("experts_") else v)
                for k, v in params.items()}
        parts.append(_apply_uncapped(_uncapped_layer((lo, hi), act=act),
                                     mine, x, 2048, None, monkeypatch, r)[0])
    assert np.abs(parts[0] + parts[1] - want).max() < UNCAPPED_TOL
    assert np.abs(parts[0] - want).max() > 100 * UNCAPPED_TOL
    # the router's own input decided: x in its place is another result
    other, _ = _apply_uncapped(whole, params, x, 2048, None, monkeypatch)
    assert np.abs(other - want).max() > 100 * UNCAPPED_TOL


#: sha256 of the Mosaic module `expert_mlps` lowers to with `silu` (the
#: locations left out: they hold this file tree's line numbers), taken on
#: the tree before the activation became a parameter, at the hybrid cell's
#: widths (36 held experts of 4096 x 768, ten of 72 a token)
_SILU_KERNEL = {
    2048: ("5f689ecd408248887090541ef07a364c"
           "1c7bb48549fa8d2e395dd48106364a2c"),
    32: ("1551b925523d2f0289bfec6695ced6ec"
         "a040173dc18c512316c0cc59325c13c5"),
}


def _mosaic_module(lowered_text: str) -> str:
    """The kernel of the one `tpu_custom_call` in a lowered text, as MLIR
    assembly without locations."""
    import base64
    import json
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    config = re.search(r'backend_config = "(.*?)"\s*[,}]', lowered_text,
                       re.S).group(1).replace("\\22", '"')
    body = base64.b64decode(
        json.loads(config)["custom_call_config"]["body"])
    context = jax_mlir.make_ir_context()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True
    with context:
        return ir.Module.parse(body).operation.get_asm(
            enable_debug_info=False)


def _lowered_for_tpu(tokens: int, held=36, experts=72, k=10, d=4096, f=768,
                     **kw) -> str:
    pairs = tokens * k
    tile = moe_gmm.tile_rows(pairs, experts)
    tiles = moe_gmm.tiles_bound(pairs, held, tile)
    of = jax.ShapeDtypeStruct
    return moe_gmm.expert_mlps.trace(
        of((tiles * tile, d), jnp.bfloat16), of((held, d, f), jnp.bfloat16),
        of((held, d, f), jnp.bfloat16), of((held, f, d), jnp.bfloat16),
        of((tiles,), jnp.int32), of((1,), jnp.int32), tile=tile, **kw,
    ).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("tokens", sorted(_SILU_KERNEL))
def test_the_silu_kernel_is_the_module_it_was(tokens):
    """The activation is a static parameter of the one kernel: with `silu`
    (the default, what the hybrid cell runs) the Mosaic module is, to the
    character, the one the kernel lowered to while `silu` was written into
    it; with `relu` it is another."""
    import hashlib

    silu = _mosaic_module(_lowered_for_tpu(tokens))
    assert hashlib.sha256(silu.encode()).hexdigest() == _SILU_KERNEL[tokens]
    assert silu == _mosaic_module(_lowered_for_tpu(tokens, act="silu"))
    relu = _mosaic_module(_lowered_for_tpu(tokens, act="relu"))
    assert relu != silu and "maximumf" in relu and "maximumf" not in silu


def test_the_relu_kernel_matches_its_jax_numpy_twin():
    """`(relu(x Wg) * (x W1)) W2` a tile's expert at a time, interpreted,
    against the same in plain jax.numpy: two experts' tiles in use, one
    tile beyond `live` left alone."""
    tile, d, f, experts = 16, 32, 24, 3
    key = jax.random.split(jax.random.key(0), 4)
    rows = jax.random.normal(key[0], (3 * tile, d), jnp.float32)
    wg, w1 = (jax.random.normal(k, (experts, d, f), jnp.float32) / 6
              for k in key[1:3])
    w2 = jax.random.normal(key[3], (experts, f, d), jnp.float32) / 5
    group = jnp.asarray([2, 0, 0], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = moe_gmm.expert_mlps(rows, wg, w1, w2, group,
                                  jnp.asarray([2], jnp.int32), tile=tile,
                                  act="relu", interpret=True)
        for t, e in enumerate((2, 0)):
            x = rows[t * tile:(t + 1) * tile]
            want = (jnp.maximum(x @ wg[e], 0) * (x @ w1[e])) @ w2[e]
            assert np.abs(np.asarray(got[t * tile:(t + 1) * tile])
                          - np.asarray(want)).max() < 1e-5
            silu = (jax.nn.silu(x @ wg[e]) * (x @ w1[e])) @ w2[e]
            assert np.abs(np.asarray(want - silu)).max() > 1e-2
    with pytest.raises(ValueError, match="act"):
        moe_gmm.expert_mlps(rows, wg, w1, w2, group,
                            jnp.asarray([2], jnp.int32), tile=tile,
                            act="gelu", interpret=True)


@pytest.mark.parametrize("pairs,experts,tile", [
    (20480, 72, 128), (320, 72, 16), (10, 24, 16), (40960, 72, 256),
    (2048 * 2, 8, 256)])
def test_the_tile_follows_the_block(pairs, experts, tile):
    assert moe_gmm.tile_rows(pairs, experts) == tile
