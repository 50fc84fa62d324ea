"""The gated delta-rule mixer in its two forms and its cache, output-gated
attention with unit-offset q/k norms and partial rotary, and a softmax
router over a held share of the experts beside a sigmoid-gated shared
expert (ops/gated_delta.py, models/transformer.py `GatedDeltaMixer` and
`MultiHeadAttention.output_gate`, models/moe.py) against the plain
reference (benchmarks/reference/qwen3_next.py, which imports nothing of
the program and works the delta rule token by token), at a small size on
the CPU with seeded weights, comparing LOGITS.

Size: hidden 64; two periods of four layers (three delta-rule layers of 2
key and 4 value heads of 8 x 8, then attention of 4 query to 2 key/value
heads of 16 with 4 rotated features); every layer routes 3 of 16 experts of
width 32 (8 held here) beside a gated shared expert; an untied head over
96. Everything runs in float32 at the highest matmul precision, so the two
computations differ by the order of float32 sums alone (and, in the
chunked form, by the inverse of a 64 x 64 triangular system in the
recurrence's place): measured under 2e-5 on logits of magnitude 4
through prefill and decode.
The tolerance is 1e-4; the same model with bfloat16 activations must fail
it, and so must the reference in fp8, without the delta term or without
the output gate.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from teacher_forced import programs, served_logits, worst_gap
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import init_cache
from tfde_tpu.inference.server import (ContinuousBatcher,
                                       SpeculativeContinuousBatcher)
from tfde_tpu.models import moe
from tfde_tpu.models.cache_state import layout_of
from tfde_tpu.models.gpt import GPT, gpt_tiny_test
from tfde_tpu.models.moe import MoEMlp
from tfde_tpu.models.transformer import MultiHeadAttention
from tfde_tpu.observability import counters
from tfde_tpu.observability.capacity import CapacityLedger, kv_slab_bytes
from tfde_tpu.ops import gated_delta as gdn

VOCAB, LAYERS, EXPERTS, HELD, PER_TOKEN = 96, 8, 16, (0, 8), 3
SHAPE = gdn.GatedDeltaShape(key_heads=2, value_heads=4, key_dim=8,
                            value_dim=8)
DIMS = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=10000.0,
    full_attention_interval=4, linear_conv_kernel_dim=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts_per_tok=PER_TOKEN,
    num_hidden_layers=LAYERS, rms_norm_eps=1e-6, vocab_size=VOCAB,
    published_experts=EXPERTS, held_experts=HELD)
MIXERS = (("gated_delta",) * 3 + ("attention",)) * 2
TOL = 1e-4


def delta_model(dtype=jnp.float32, held=HELD, vocab=VOCAB, **kw):
    fields = dict(
        vocab_size=vocab, hidden_size=64, depth=LAYERS, num_heads=4,
        num_kv_heads=2, head_dim=16, mixers=MIXERS, gdn=SHAPE,
        max_position=4096, dtype=dtype, position="rope", rope_theta=10000.0,
        rope_dim=4, norm="rms", norm_unit_offset=True, qk_norm=True,
        attn_output_gate=True, ln_eps=1e-6, use_bias=False,
        tie_embeddings=False, mlp_act="swiglu", mlp_dim=32,
        mlps=("experts",) * LAYERS, num_experts=EXPERTS,
        experts_per_token=PER_TOKEN, moe_capacity_factor=None,
        moe_normalize_topk=True, moe_shared_expert_dim=32,
        moe_shared_expert_gated=True, moe_held_experts=held)
    return GPT(**dict(fields, **kw))


def as_float32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(7, DIMS)


@pytest.fixture(scope="module")
def params(weights):
    return as_float32(ref.to_program_params(weights))


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def forward():
    model = delta_model()
    return jax.jit(lambda params, rows: model.apply({"params": params}, rows))


@pytest.fixture(scope="module")
def honest():
    return programs(delta_model(), mutable=("cache", "counters"))


def rows_of(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(weights, row, dims=DIMS, **kw) -> np.ndarray:
    return np.asarray(ref.forward(weights, jnp.asarray(row), dims, **kw))


# ---------------------------------------------------------------------------
# the rule's two forms
# ---------------------------------------------------------------------------

def _operands(s, b=2, seed=0):
    keys = jax.random.split(jax.random.key(seed + s), 4)
    qkv = jax.random.normal(keys[0], (b, s, SHAPE.conv_channels))
    beta = jax.nn.sigmoid(jax.random.normal(keys[1],
                                            (b, s, SHAPE.value_heads)))
    g = -0.5 * jax.nn.softplus(jax.random.normal(keys[2],
                                                 (b, s, SHAPE.value_heads)))
    state = jax.random.normal(keys[3], (b, SHAPE.value_heads, SHAPE.key_dim,
                                        SHAPE.value_dim))
    return qkv, beta, g, state


def _token_by_token(qkv, beta, g, state, lengths):
    outs = []
    for t in range(qkv.shape[1]):
        o, state = gdn.decode_step(qkv[:, t], beta[:, t], g[:, t], state,
                                   t < lengths, SHAPE)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_the_chunked_prefill_is_the_recurrence(length):
    """Lengths that are and are not multiples of the chunk of 64, two rows
    of which the second is right-padded: the outputs at the true positions
    and the state at the true length."""
    qkv, beta, g, state = _operands(length)
    lengths = jnp.asarray([length, max(1, length - 7)])
    o, end = jax.jit(functools.partial(gdn.prefill, shape=SHAPE))(
        qkv, beta, g, state, lengths)
    want, want_end = _token_by_token(qkv, beta, g, state, lengths)
    real = (jnp.arange(length)[None, :] < lengths[:, None])[..., None, None]
    assert np.abs(np.asarray((o - want) * real)).max() < 1e-5
    assert np.abs(np.asarray(end - want_end)).max() < 1e-5
    assert float(jnp.abs(want).max()) > 0.1


def _chunk_systems(size, repeated, count=64, width=128):
    """`count` chunks of `size` positions as `prefill` builds them, in
    float64: l2-normalised keys of `width`, the unit lower triangular
    system and its right-hand side [beta K exp(c), beta V]. `repeated`:
    every key 16 times with 1e-3 of noise, beta in (0.9, 1), decay near
    1, so that the strict part has entries near 1 far from the diagonal."""
    rng = np.random.default_rng(size)
    if repeated:
        k = np.repeat(rng.standard_normal((count, -(-size // 16), width)),
                      16, axis=1)[:, :size]
        k = k + 1e-3 * rng.standard_normal(k.shape)
        beta = rng.uniform(0.9, 1.0, (count, size))
        g = -rng.uniform(0.0, 1e-3, (count, size))
    else:
        k = rng.standard_normal((count, size, width))
        beta = rng.uniform(0.0, 1.0, (count, size))
        g = -0.5 * np.log1p(np.exp(rng.standard_normal((count, size))))
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    cum = np.cumsum(g, -1)
    seg = np.exp(np.tril(cum[:, :, None] - cum[:, None, :]))
    system = np.eye(size) + np.tril(
        beta[:, :, None] * (k @ k.transpose(0, 2, 1)) * seg, -1)
    v = rng.standard_normal((count, size, width))
    return system, beta[:, :, None] * np.concatenate(
        [k * np.exp(cum)[..., None], v], -1)


@pytest.mark.parametrize("repeated", [False, True],
                         ids=["random_keys", "repeated_keys"])
@pytest.mark.parametrize("size", [1, 3, 17, 63, 64])
def test_the_inverse_solves_the_chunk_systems(size, repeated):
    """`inverse(system) @ rhs` against float64 `numpy.linalg.solve`, at
    the chunk's 64 and at what short prompts and tests give. The repeated
    keys are the case that the nilpotent product
    (I - N)(I + N^2)(I + N^4)... fails by 1e6 and more in float32 (N^2,
    N^4, ... grow before they vanish and cancel): the substitution only
    ever adds products of the system's entries with rows of the inverse,
    which stay of the solution's size."""
    system, rhs = _chunk_systems(size, repeated)
    want = np.linalg.solve(system, rhs)
    got = jax.jit(lambda a, b: jnp.einsum(
        "nij,njw->niw", gdn.inverse(a), b,
        precision=jax.lax.Precision.HIGHEST))(
            jnp.asarray(system, jnp.float32), jnp.asarray(rhs, jnp.float32))
    assert got.dtype == jnp.float32
    assert np.abs(np.asarray(got, np.float64) - want).max() \
        < 1e-6 * np.abs(want).max()


def test_a_traced_prefill_says_it_holds_the_batched_inverse():
    """`gdn/block_inverse_traces` (the issue's name for it) goes up once a
    traced `prefill` and not at all under `decode_step`: a program in hand
    can be asked whether it inverts its chunk systems side by side."""
    qkv, beta, g, state = _operands(70)
    name = "gdn/block_inverse_traces"
    before = counters.value(name)
    jax.jit(functools.partial(gdn.prefill, shape=SHAPE)).lower(
        qkv, beta, g, state, jnp.asarray([70, 70]))
    assert counters.value(name) - before == 1
    jax.jit(functools.partial(gdn.decode_step, shape=SHAPE)).lower(
        qkv[:, 0], beta[:, 0], g[:, 0], state, jnp.asarray([True, True]))
    assert counters.value(name) - before == 1


def test_a_prefill_continues_from_a_cached_state():
    qkv, beta, g, state = _operands(160)
    everything = jnp.asarray([160, 160])
    run = jax.jit(functools.partial(gdn.prefill, shape=SHAPE))
    o, end = run(qkv, beta, g, state, everything)
    o1, mid = run(qkv[:, :100], beta[:, :100], g[:, :100], state,
                  jnp.asarray([100, 100]))
    o2, end2 = run(qkv[:, 100:], beta[:, 100:], g[:, 100:], mid,
                   jnp.asarray([60, 60]))
    assert np.abs(np.asarray(jnp.concatenate([o1, o2], 1) - o)).max() < 1e-5
    assert np.abs(np.asarray(end2 - end)).max() < 1e-5


def test_padding_leaves_a_state_as_it_stands():
    """A row whose every position is padding: decay 1 and nothing written,
    in the chunked form and in the step, to the bit."""
    qkv, beta, g, state = _operands(128)
    _, end = gdn.prefill(qkv, beta, g, state, jnp.asarray([128, 0]), SHAPE)
    assert (np.asarray(end[1]) == np.asarray(state[1])).all()
    assert np.abs(np.asarray(end[0] - state[0])).max() > 0.1
    _, stepped = gdn.decode_step(qkv[:, 0], beta[:, 0], g[:, 0], state,
                                 jnp.asarray([True, False]), SHAPE)
    assert (np.asarray(stepped[1]) == np.asarray(state[1])).all()
    assert np.abs(np.asarray(stepped[0] - state[0])).max() > 1e-3


def test_the_norm_comes_before_the_gate_and_the_gain_is_as_stored():
    o = jax.random.normal(jax.random.key(0), (3, 8))
    z = jax.random.normal(jax.random.key(1), (3, 8))
    gain = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (8,))
    want = (o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * gain
            * z * jax.nn.sigmoid(z))
    assert np.allclose(np.asarray(gdn.norm_then_gate(o, z, gain, 1e-6)),
                       np.asarray(want), atol=1e-6)


def test_the_shape_says_what_it_cannot_be():
    with pytest.raises(ValueError, match="multiple of"):
        gdn.GatedDeltaShape(key_heads=3, value_heads=4, key_dim=8,
                            value_dim=8)
    assert SHAPE.conv_channels == 2 * 16 + 32 and SHAPE.in_features == 96


# ---------------------------------------------------------------------------
# the full forward, and what the model builds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 9, 17, 70])
def test_full_forward_matches_the_reference(weights, params, forward,
                                            length):
    (row,) = rows_of(length, [length])
    got = forward(params, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        < TOL


def test_bfloat16_for_float32_fails_the_tolerance(weights, params):
    (row,) = rows_of(1, [40])
    got = jax.jit(delta_model(jnp.bfloat16).apply)(
        {"params": params}, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        > 10 * TOL


@pytest.mark.parametrize("control", [dict(precision="fp8"),
                                     dict(precision="bf16"),
                                     dict(drop="delta_term"),
                                     dict(drop="output_gate")],
                         ids=["fp8", "bf16", "delta_term", "output_gate"])
def test_each_control_is_another_model(weights, params, forward, control):
    """Every product in a lower precision, the rule without what it read
    back, the attention ungated: each carries weight at these spreads, and
    the program no longer matches such a reference."""
    (row,) = rows_of(2, [40])
    got = np.asarray(forward(params, row[None])[0])
    assert np.abs(got - reference_logits(weights, row, **control)).max() \
        > 100 * TOL


def test_init_builds_what_the_reference_draws(params):
    tree = jax.eval_shape(lambda: delta_model().init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, params)
    assert ref.num_params(DIMS) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert set(tree["decoder"]["block_0"]) == {"ln_attn", "ln_mlp", "delta",
                                               "moe"}
    assert set(tree["decoder"]["block_3"]) == {"ln_attn", "ln_mlp", "attn",
                                               "moe"}
    assert tree["decoder"]["block_3"]["attn"]["query"]["kernel"].shape == (
        64, 4, 32)
    assert tree["decoder"]["block_0"]["delta"]["norm_scale"].shape == (8,)
    assert [ref.layer_kind(DIMS, l) for l in range(4)] == [
        "delta", "delta", "delta", "attention"]


def test_attention_without_the_new_fields_is_what_it_was():
    x = jax.random.normal(jax.random.key(0), (1, 6, 32))
    kw = dict(num_heads=2, head_dim=16, dtype=jnp.float32, causal=True,
              qk_norm=True, use_bias=False)
    plain = MultiHeadAttention(**kw)
    p = plain.init(jax.random.key(1), x)["params"]
    assert p["query"]["kernel"].shape == (32, 2, 16)
    # the unit offset moves the gain by one and changes no shape
    offset = MultiHeadAttention(norm_unit_offset=True, **kw)
    shifted = jax.tree.map(lambda a: a, p)
    shifted["q_norm"] = {"scale": p["q_norm"]["scale"] - 1.0}
    shifted["k_norm"] = {"scale": p["k_norm"]["scale"] - 1.0}
    assert np.allclose(np.asarray(plain.apply({"params": p}, x)),
                       np.asarray(offset.apply({"params": shifted}, x)),
                       atol=1e-6)
    gated = MultiHeadAttention(output_gate=True, **kw)
    assert gated.init(jax.random.key(1), x)["params"]["query"][
        "kernel"].shape == (32, 2, 32)
    with pytest.raises(NotImplementedError, match="output gate"):
        MultiHeadAttention(output_gate=True, fused_qkv=True, **kw).init(
            jax.random.key(1), x)


def test_a_block_asks_for_the_mixers_widths():
    x = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="gdn"):
        delta_model(gdn=None).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="'gated_delta'"):
        delta_model(mixers=("delta",) * LAYERS).init(jax.random.key(0), x)


# ---------------------------------------------------------------------------
# prefill, then decode, over the cache
# ---------------------------------------------------------------------------

def test_prefill_and_decode_match_the_reference(weights, params, honest):
    """A wave of right-padded rows in a bucket of two chunks, then steps
    under per-row indices."""
    rows = rows_of(3, [110, 80, 126, 90])
    lengths = [100, 7, 120, 65]
    got, cache = served_logits(honest, params, rows, lengths, 128, 160)
    assert worst_gap(lambda row: reference_logits(weights, row), rows,
                     lengths, got) < TOL
    leaf = cache["decoder"]["block_1"]["delta"]
    assert set(leaf) == {"delta_state", "conv_tail", "feed_pad"}
    assert leaf["delta_state"].shape == (4, 4, 8, 8)
    assert leaf["delta_state"].dtype == jnp.float32
    assert leaf["conv_tail"].shape == (4, 3, SHAPE.conv_channels)
    assert cache["decoder"]["block_3"]["attn"]["cached_key"].shape == (
        4, 160, 2, 16)


def test_a_frozen_rows_state_stands_to_the_bit(weights, params, honest):
    rows = rows_of(4, [40, 40, 40])
    lengths = [12, 9, 20]
    snapshots = []
    got, _ = served_logits(honest, params, rows, lengths, 64, 96,
                           freeze=(1, 4), snapshots=snapshots)
    state = lambda snap: snap["decoder"]["block_5"]["delta"]
    frozen_at = state(snapshots[3])
    for snap in snapshots[4:]:
        for name in ("delta_state", "conv_tail"):
            assert (state(snap)[name][1] == frozen_at[name][1]).all()
    assert (state(snapshots[-1])["delta_state"][0]
            != frozen_at["delta_state"][0]).any()
    assert worst_gap(lambda row: reference_logits(weights, row),
                     [rows[0], rows[2]], [12, 20], [got[0], got[2]]) < TOL


def test_a_row_alone_equals_the_row_in_a_wave_of_four(params, honest):
    rows = rows_of(5, [50, 30, 44, 61])
    lengths = [33, 9, 40, 50]
    together, _ = served_logits(honest, params, rows, lengths, 64, 96)
    alone, _ = served_logits(honest, params, rows[2:3], lengths[2:3], 64, 96)
    assert np.abs(together[2] - alone[0]).max() < 1e-5


@pytest.mark.parametrize("break_it", ["no_decay", "stale_state",
                                      "gate_before_norm"])
def test_a_broken_mixer_fails_the_tolerance(weights, params, monkeypatch,
                                            break_it):
    if break_it == "no_decay":
        real = gdn.decode_step
        monkeypatch.setattr(
            gdn, "decode_step",
            lambda qkv, beta, g, *a: real(qkv, beta, 0 * g, *a))
    elif break_it == "stale_state":
        real = gdn.decode_step
        monkeypatch.setattr(
            gdn, "decode_step",
            lambda qkv, beta, g, state, live, shape: real(
                qkv, beta, g, state, live & False, shape))
    else:
        from tfde_tpu.ops import ssm

        monkeypatch.setattr(gdn, "norm_then_gate", ssm.gated_rms_norm)
    rows = rows_of(6, [40, 28])
    lengths = [20, 11]
    got, _ = served_logits(programs(delta_model()), params, rows, lengths,
                           64, 96)
    assert worst_gap(lambda row: reference_logits(weights, row), rows,
                     lengths, got) > 100 * TOL


# ---------------------------------------------------------------------------
# the shares: experts over two chips, the vocabulary over two
# ---------------------------------------------------------------------------

def _uncut(seed=11):
    dims = dict(DIMS, held_experts=(0, EXPERTS))
    return dims, as_float32(ref.make_weights(seed, dims))


def _layer(held):
    return MoEMlp(num_experts=EXPERTS, mlp_dim=32,
                  experts_per_token=PER_TOKEN, capacity_factor=None,
                  act="swiglu", use_bias=False, normalize_topk=True,
                  shared_expert_dim=32, shared_expert_gated=True,
                  held_experts=held, dtype=jnp.float32)


def _share(lw, lo, hi):
    return {"router": {"kernel": lw["router"]},
            "experts_gate": lw["e_gate"][lo:hi],
            "experts_fc1": lw["e_up"][lo:hi],
            "experts_fc2": lw["e_down"][lo:hi],
            "shared_gate": {"kernel": lw["s_gate"]},
            "shared_fc1": {"kernel": lw["s_up"]},
            "shared_fc2": {"kernel": lw["s_down"]},
            "shared_expert_gate": {"kernel": lw["s_mix"]}}


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0-7 and 8-15 on two chips, router and shared expert alike
    on both: the two partial results, with the gated shared expert counted
    once, are the uncut reference's layer; one share alone is not."""
    dims, w = _uncut()
    lw = w["layers"][1]
    x = jax.random.normal(jax.random.key(3), (2, 24, 64))
    flat = x.reshape(48, 64)
    routed, _ = ref.routed_part(flat, lw, dims)
    shared = ref.shared_part(flat, lw)
    parts = []
    for lo in (0, 8):
        y, _ = jax.jit(functools.partial(
            _layer((lo, lo + 8)).apply, mutable=["losses", "counters"]))(
            {"params": _share(lw, lo, lo + 8)}, x)
        parts.append(np.asarray(y).reshape(48, 64))
        mine, _ = ref.routed_part(
            flat, dict(lw, e_gate=lw["e_gate"][lo:lo + 8],
                       e_up=lw["e_up"][lo:lo + 8],
                       e_down=lw["e_down"][lo:lo + 8]), dims,
            held=(lo, lo + 8))
        assert np.abs(parts[-1] - np.asarray(mine + shared)).max() < TOL
    total = sum(parts) - np.asarray(shared)
    assert np.abs(total - np.asarray(routed + shared)).max() < TOL
    assert np.abs(parts[0] - np.asarray(routed + shared)).max() > 100 * TOL


def test_the_two_vocabulary_slices_add_up_to_the_uncut_head():
    """Rows 0-47 and 48-95 of the head on two chips: each slice's logits
    are the uncut reference's over its rows (ids drawn from the first
    slice, whose rows of the embedding both are given)."""
    w = ref.make_weights(13, DIMS)
    row = np.random.default_rng(0).integers(0, 48, 30).astype(np.int32)
    want = reference_logits(w, row)
    halves = []
    for lo in (0, 48):
        mine = dict(w, wte=w["wte"][:48], lm_head=w["lm_head"][:, lo:lo + 48])
        got = jax.jit(delta_model(vocab=48).apply)(
            {"params": as_float32(ref.to_program_params(mine))}, row[None])
        halves.append(np.asarray(got[0]))
    assert np.abs(np.concatenate(halves, -1) - want).max() < TOL


# ---------------------------------------------------------------------------
# through ContinuousBatcher
# ---------------------------------------------------------------------------

REQUESTS = [(20, 12), (70, 30), (50, 9), (100, 20), (16, 5), (120, 30)]


@pytest.fixture(scope="module")
def served(params):
    with jax.default_matmul_precision("highest"):
        srv = ContinuousBatcher(delta_model(), params, batch_size=4,
                                max_len=160, scan_depth=4,
                                prompt_buckets=(64, 128, 160))
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
                   for n, _ in REQUESTS]
        rids = [srv.submit(p, m) for p, (_, m) in zip(prompts, REQUESTS)]
        out = dict(srv.run())
    return srv, prompts, [np.asarray(out[r]) for r in rids]


@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_batcher_serves_the_references_first_choice(weights, served, i):
    _, prompts, tokens = served
    assert tokens[i].size == REQUESTS[i][1]
    gaps = ref.served_token_gaps(weights, prompts[i], tokens[i], DIMS, 160)
    assert gaps["gap"].max() < TOL
    assert gaps["routes"].shape == (LAYERS, prompts[i].size + tokens[i].size,
                                    PER_TOKEN)


def test_the_batchers_cache_is_states_beside_cells(served):
    srv, _, _ = served
    leaves = jax.tree_util.tree_leaves_with_path(srv._cache)
    names = [str(getattr(p[-1], "key", p[-1])) for p, _ in leaves]
    assert names.count("delta_state") == names.count("conv_tail") == 6
    assert names.count("cached_key") == names.count("cached_value") == 2
    ledger = srv._ledger
    assert ledger.kinds == {"kv", "state"}
    # a row's state: six layers of [4, 8, 8] float32 and a tail of 3 x 64;
    # a position: two attention layers' K and V of 2 heads of 16
    state = 6 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    position = 2 * 2 * 2 * 16 * 4
    assert ledger.slab_bytes == 4 * (160 * position + state)
    # a state is bytes and no cell; a cell is one attention layer's
    assert ledger.row_cells(0) == 0 and ledger.row_cells(37) == 2 * 37
    assert ledger.row_bytes == 160 * position + state
    assert ledger.read_bytes([37]) == 37 * position + 2 * state


def test_batcher_counts_what_a_known_schedule_makes(params):
    """Two requests, prompts of 20 and 70 into buckets of 64 and 128 and
    five tokens each: one token from each wave, then one scan of four
    ticks over two rows."""
    srv = ContinuousBatcher(delta_model(), params, batch_size=4, max_len=160,
                            scan_depth=4, prompt_buckets=(64, 128, 160))
    rng = np.random.default_rng(2)
    for n in (20, 70):
        srv.submit(rng.integers(0, VOCAB, n).astype(np.int32), 5)
    assert len(srv.run()) == 2
    stats = srv.stats()
    assert set(CapacityLedger.GDN_KEYS) <= set(stats)
    assert set(CapacityLedger.HYBRID_KEYS) <= set(stats)
    assert (stats["prefill_waves"], stats["scans"], stats["rounds"]) == (
        2, 1, 4)
    # six delta-rule layers: a chunk for the bucket of 64, two for 128
    assert stats["gdn_chunks"] == 6 * (1 + 2)
    assert stats["gdn_steps"] == 6 * 4 * 2
    assert stats["kv_pairs_prefilled"] == 2 * (20 * 21 // 2 + 70 * 71 // 2)
    state = 6 * (4 * 8 * 8 * 4 + 3 * 64 * 4)
    assert stats["gdn_state_bytes"] == 4 * 2 * state
    assert stats["ssm_state_bytes_touched"] == 2 * stats["gdn_state_bytes"]
    assert stats["kv_cell_bytes"] == 4 * (20 + 70) * 2 * 2 * 2 * 16 * 4
    assert stats["kv_cells_read"] == 4 * (20 + 70) * 2   # attention layers
    fed = (20 + 4) + (70 + 4)
    assert stats["moe_pairs"] == LAYERS * PER_TOKEN * fed
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs"]
    # a pass over the held experts' weights a block, expert layer and
    # call: both waves and all four ticks are one block each
    assert stats["moe_weight_passes"] == 1 * LAYERS * (2 + 4)
    assert stats["decode_least_bytes"] > 0


def test_the_ledger_counts_the_layers_the_model_describes(params):
    model = delta_model()
    cache = init_cache(model, 4, 160)
    ledger = CapacityLedger(4, 160, kv_slab_bytes(cache),
                            layout_of(model, 160).layers,
                            moe.held_experts(params))
    assert set(ledger.counters) == set(ledger.HYBRID_KEYS + ledger.GDN_KEYS)
    ledger.note_admission("cold", 128, 100)
    ledger.note_commit(0, 100, decoding=False)
    ledger.note_commit(100, 104)
    ledger.note_scan([104, 30], 4)
    counted = ledger.counters
    assert counted["gdn_chunks"] == 6 * 2
    assert counted["kv_pairs_prefilled"] == 2 * 5050
    assert counted["gdn_steps"] == 6 * 4 * 2
    assert counted["kv_cell_bytes"] == 4 * 134 * 512
    assert counted["kv_cells_read"] == 4 * 134 * 2


@pytest.mark.parametrize("kw,word", [
    (dict(paged=True), "paged"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
])
def test_batcher_refuses_what_works_by_position(params, kw, word):
    with pytest.raises(NotImplementedError, match=word) as why:
        ContinuousBatcher(delta_model(), params, batch_size=2, max_len=64,
                          **kw)
    assert "one matrix per value head" in str(why.value)


def test_speculation_and_the_primed_hand_off_are_refused(params):
    with pytest.raises(NotImplementedError, match="Speculative"):
        SpeculativeContinuousBatcher(delta_model(), delta_model(), params,
                                     params, batch_size=2, max_len=64)
    srv = ContinuousBatcher(delta_model(), params, batch_size=2, max_len=64)
    with pytest.raises(NotImplementedError, match="prime"):
        srv.prime(np.arange(8, dtype=np.int32), 4)
    primed = server.PrimedRequest(np.arange(8, dtype=np.int32), 1, 4, {})
    with pytest.raises(NotImplementedError, match="submit_primed"):
        srv.submit_primed(primed)


def test_the_refusal_is_asked_of_the_model_not_of_a_family():
    assert layout_of(gpt_tiny_test()).not_by_position is None
    assert "gated_delta" in layout_of(delta_model()).not_by_position
    assert "GatedDeltaMixer" in layout_of(delta_model(), 64).not_by_position
