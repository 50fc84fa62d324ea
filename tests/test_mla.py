"""Latent (multi-head latent) attention, its two paths and its cache, a
sigmoid router with a selection bias over a held share of the experts, and
a leading dense layer (models/transformer.py `LatentAttention`, ops/mla.py,
models/moe.py, models/gpt.py `mlps`) against the plain reference
(benchmarks/reference/sarvam_mla.py, which imports nothing of the program
and attends per head at every position), at a small size on the CPU with
seeded weights, comparing LOGITS.

Size: hidden 64; three layers, the first with a dense SwiGLU of 96, the
others routing 3 of 16 experts of width 32 (4 held here) beside a shared
expert; 4 heads of 8 + 4 (scores) / 8 (values) over a latent of 16; yarn
frequencies (factor 40 over an original length of 16) and the temperature
on the whole score; an untied head over 96. Everything runs in float32 at
the highest matmul precision, so the two computations differ by the order
of float32 sums alone: measured 3e-6 on logits of magnitude 3.7 through
prefill and decode. The tolerance is 1e-4; the same model with bfloat16
activations must fail it, and so must the reference without the rotary
key's term or without the selection bias.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sarvam_mla as ref
from teacher_forced import programs, served_logits, worst_gap
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import _decode_clone, init_cache
from tfde_tpu.inference.server import ContinuousBatcher
from tfde_tpu.inference.speculative import _set_index_counters
from tfde_tpu.models import moe, transformer
from tfde_tpu.models.cache_state import layout_of
from tfde_tpu.models.gpt import GPT
from tfde_tpu.models.moe import MoEMlp
from tfde_tpu.models.transformer import LatentAttention
from tfde_tpu.observability.capacity import CapacityLedger, kv_slab_bytes
from tfde_tpu.ops import mla as mla_lib
from tfde_tpu.ops import rotary

VOCAB, HEADS, LAYERS, EXPERTS, HELD, PER_TOKEN = 96, 4, 3, 16, (0, 4), 3
SHAPE = mla_lib.MLAShape(latent=16, nope=8, rope=4, value=8)
YARN = (40.0, 32.0, 1.0, 16, 1.0)
DIMS = dict(
    hidden_size=64, num_attention_heads=HEADS, kv_lora_rank=SHAPE.latent,
    qk_nope_head_dim=SHAPE.nope, qk_rope_head_dim=SHAPE.rope,
    v_head_dim=SHAPE.value, num_hidden_layers=LAYERS,
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    num_experts_per_tok=PER_TOKEN, num_shared_experts=1,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000,
    vocab_size=VOCAB, yarn=YARN, published_experts=EXPERTS,
    held_experts=HELD)
TOL = 1e-4


def latent_model(dtype=jnp.float32, held=HELD, **kw):
    fields = dict(
        vocab_size=VOCAB, hidden_size=64, depth=LAYERS, num_heads=HEADS,
        mixers=("latent",) * LAYERS, mla=SHAPE, max_position=4096,
        dtype=dtype, position="rope", rope_theta=10000.0,
        rope_scaling=("yarn", 40, 32, 1, 16,
                      rotary.yarn_temperature(40, 1), True),
        norm="rms", ln_eps=1e-6, use_bias=False, tie_embeddings=False,
        mlp_act="swiglu", mlp_dim=96, moe_mlp_dim=32,
        mlps=("dense", "experts", "experts"), num_experts=EXPERTS,
        experts_per_token=PER_TOKEN, moe_capacity_factor=None,
        moe_score="sigmoid", moe_selection_bias=True, moe_routed_scale=2.5,
        moe_shared_expert_dim=32, moe_shared_expert_gated=False,
        moe_held_experts=held)
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
    model = latent_model()
    return jax.jit(lambda params, rows: model.apply({"params": params}, rows))


@pytest.fixture(scope="module")
def honest():
    return programs(latent_model(), mutable=("cache", "counters"))


def rows_of(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def reference_logits(weights, row, **kw) -> np.ndarray:
    return np.asarray(ref.forward(weights, jnp.asarray(row), DIMS, **kw))


# ---------------------------------------------------------------------------
# the full forward, and what the configuration's numbers come to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 9, 17, 40])
def test_full_forward_matches_the_reference(weights, params, forward,
                                            length):
    (row,) = rows_of(length, [length])
    got = forward(params, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        < TOL


def test_bfloat16_for_float32_fails_the_tolerance(weights, params):
    (row,) = rows_of(1, [40])
    got = jax.jit(latent_model(jnp.bfloat16).apply)(
        {"params": params}, row[None])[0]
    assert np.abs(np.asarray(got) - reference_logits(weights, row)).max() \
        > 10 * TOL


@pytest.mark.parametrize("drop", ["rope_term", "selection_bias"])
def test_the_reference_without_a_term_is_another_model(weights, params,
                                                       forward, drop):
    """The rotary key's term and the selection bias both carry weight at
    these spreads: left out of the reference, the program no longer
    matches it."""
    (row,) = rows_of(2, [40])
    got = np.asarray(forward(params, row[None])[0])
    assert np.abs(got - reference_logits(weights, row, drop=drop)).max() \
        > 100 * TOL


def test_init_builds_what_the_reference_draws(params):
    tree = jax.eval_shape(lambda: latent_model().init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, params)
    assert ref.num_params(DIMS) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert "mlp" in tree["decoder"]["block_0"]
    assert "moe" in tree["decoder"]["block_1"]
    assert tree["decoder"]["block_1"]["moe"]["router_bias"].shape == (
        EXPERTS,)
    assert tree["decoder"]["block_1"]["moe"]["experts_fc1"].shape == (
        HELD[1] - HELD[0], 64, 32)


def test_the_configurations_parameters_are_pinned():
    """The arithmetic of the configuration file, at published widths: 94.6
    M of attention a layer, 201.3 M of dense MLP, and a routed layer's
    shared expert (25.2 M), router (0.5 M) and 32 held experts of 25.2 M;
    268.4 M each of embedding and head: 4,535 M, 9.07 GB in bfloat16."""
    from benchmarks.lib.manifest import Manifest
    from benchmarks.run import ROOT

    cfg = Manifest(ROOT).config("sarvam-105b-serve-32k")
    dims = ref.dims_of(cfg)
    attention = (4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256
                 + 64 * 128 * 4096)
    assert attention == 94_633_984
    expert = 3 * 4096 * 2048
    routed = expert + 4096 * 128 + 32 * expert
    matrices = (5 * attention + 3 * 4096 * 16384 + 4 * routed
                + 2 * 65536 * 4096)
    assert matrices == 4_535_353_344
    gains = 5 * (2 * 4096 + 512) + 4 * 128 + 4096
    assert ref.num_params(dims) == matrices + gains == 4_535_401_472
    assert round(2 * ref.num_params(dims) / 1e9, 2) == 9.07
    # the cache: 16 rows x 32,768 cells x 5 layers x 1,152 B
    assert 16 * 32768 * 5 * 2 * (512 + 64) == 3_019_898_880


def test_the_mlp_kinds_are_one_entry_a_layer():
    x = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="mlps names 2 blocks"):
        latent_model(mlps=("dense", "experts")).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="'dense' or 'experts'"):
        latent_model(mlps=("dense", "moe", "moe")).init(jax.random.key(0), x)
    # without `mlps` the old rule stands: every `moe_every`-th layer routes
    tree = jax.eval_shape(lambda: latent_model(mlps=None, moe_every=2).init(
        jax.random.key(0), x)["params"])["decoder"]
    assert ["moe" in tree[f"block_{l}"] for l in range(3)] == [
        False, True, False]


def test_yarns_temperature_is_handed_to_a_caller_that_asks():
    scaling = ("yarn", 40, 32, 1, 4096, 1.3689, True)
    assert rotary.attention_temperature(scaling) == 1.3689
    assert rotary.attention_temperature(None) == 1.0
    assert rotary.attention_temperature(("linear", 4.0)) == 1.0
    assert rotary.yarn_temperature(40, 1) == pytest.approx(1.3689, abs=5e-5)
    assert rotary.yarn_temperature(1.0) == 1.0
    pos = jnp.arange(12)
    folded = rotary.rotary_angles(pos, 8, scaling=scaling)
    plain = rotary.rotary_angles(pos, 8, scaling=scaling,
                                 fold_temperature=False)
    for a, b in zip(folded, plain):
        assert np.allclose(np.asarray(a), 1.3689 * np.asarray(b))
    # the tables the caller gets are the reference's own frequencies
    freqs = ref.yarn_frequencies(8, 10000.0, (40.0, 32.0, 1.0, 4096, 1.0))
    assert np.allclose(np.asarray(plain[0]),
                       np.cos(np.asarray(pos)[:, None] * np.asarray(freqs)),
                       atol=1e-6)
    x = jax.random.normal(jax.random.key(0), (1, 12, 2, 8))
    assert np.allclose(
        np.asarray(rotary.apply_rotary(x, pos, scaling=scaling)),
        1.3689 * np.asarray(rotary.apply_rotary(
            x, pos, scaling=scaling, fold_temperature=False)), atol=1e-5)


# ---------------------------------------------------------------------------
# the two attention paths
# ---------------------------------------------------------------------------

def _latent_operands(s=24, b=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 6)
    normal = jax.random.normal
    q_nope = normal(keys[0], (b, s, HEADS, SHAPE.nope))
    q_rope = normal(keys[1], (b, s, HEADS, SHAPE.rope))
    c = normal(keys[2], (b, s, SHAPE.latent))
    k_rope = normal(keys[3], (b, s, SHAPE.rope))
    w_up = normal(keys[4], (SHAPE.latent, HEADS, SHAPE.nope + SHAPE.value))
    return q_nope, q_rope, c, k_rope, w_up


def test_the_absorbed_path_equals_the_per_head_path_on_the_same_cache():
    """Keys and values up-projected per head and attended causally, against
    the up-projections moved to the query's side over the cells [c, k_r]:
    the same numbers, and a cache longer than the call changes nothing."""
    q_nope, q_rope, c, k_rope, w_up = _latent_operands()
    b, s = c.shape[:2]
    kv = jnp.einsum("bsc,chk->bshk", c, w_up)
    want = mla_lib.prefill_attention(
        q_nope, q_rope, kv[..., :SHAPE.nope], k_rope, kv[..., SHAPE.nope:],
        scale=0.3)
    latents = jnp.zeros((b, 40, SHAPE.latent)).at[:, :s].set(c)
    rope_keys = jnp.zeros((b, 40, SHAPE.rope)).at[:, :s].set(k_rope)
    valid = jnp.broadcast_to(
        jnp.arange(40)[None, :] <= jnp.arange(s)[:, None], (b, s, 40))
    q_abs = jnp.einsum("bshn,chn->bshc", q_nope, w_up[..., :SHAPE.nope])
    o_lat = mla_lib.absorbed_attention(q_abs, q_rope, latents, rope_keys,
                                       valid, scale=0.3)
    got = jnp.einsum("bshc,chv->bshv", o_lat, w_up[..., SHAPE.nope:])
    assert got.shape == want.shape == (b, s, HEADS, SHAPE.value)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    # another scale, or a rope term left out, is another result
    other = mla_lib.absorbed_attention(q_abs, 0 * q_rope, latents,
                                       rope_keys, valid, scale=0.3)
    assert np.abs(np.asarray(jnp.einsum(
        "bshc,chv->bshv", other, w_up[..., SHAPE.nope:]) - want)).max() > 0.01


@pytest.mark.parametrize("name,heads,nope,rope,value,widths", [
    # the toy widths: scores over 12 columns, values of 8, each padded to
    # the lane block above it
    ("both_under_a_lane_block", HEADS, SHAPE.nope, SHAPE.rope, SHAPE.value,
     (128, 128)),
    # the published widths' ratio: scores over 128 + 64, padded to 256;
    # values of 128 go as they are
    ("score_192_value_128", 2, 128, 64, 128, (256, 128)),
    ("score_192_value_96", 2, 128, 64, 96, (256, 128)),
    ("score_128_value_256", 2, 96, 32, 256, (128, 256)),
])
def test_a_long_call_pads_each_width_to_its_own_lane_multiple(
        monkeypatch, name, heads, nope, rope, value, widths):
    """Past `_SCORES_BYTES` the per-head path goes through the dispatcher
    with [q_nope, q_rope] and [k_nope, k_r] padded to the lane multiple of
    the score width and v to that of ITS width (as it is where that is a
    lane multiple: the flash forward takes the two apart): the same
    attention as the two score products."""
    keys = jax.random.split(jax.random.key(1), 5)
    q_nope, k_nope = (jax.random.normal(key, (1, 128, heads, nope))
                      for key in keys[:2])
    q_rope = jax.random.normal(keys[2], (1, 128, heads, rope))
    k_rope = jax.random.normal(keys[3], (1, 128, rope))
    v = jax.random.normal(keys[4], (1, 128, heads, value))
    args = (q_nope, q_rope, k_nope, k_rope, v)
    want = mla_lib.prefill_attention(*args, scale=0.3)
    seen = []
    real = mla_lib.attn_lib.attention

    def spy(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["scale"], kw["causal"]))
        return real(q, k, v, **kw)

    monkeypatch.setattr(mla_lib, "_SCORES_BYTES", 0)
    monkeypatch.setattr(mla_lib.attn_lib, "attention", spy)
    got = mla_lib.prefill_attention(*args, scale=0.3)
    score, own = widths
    assert seen == [((1, 128, heads, score),) * 2
                    + ((1, 128, heads, own), 0.3, True)]
    assert got.shape == want.shape == v.shape
    # values of magnitude 8: the order of float32 sums differs
    assert np.abs(np.asarray(got - want)).max() < TOL


def test_a_long_call_reaches_the_two_width_flash_forward():
    """The same call through the interpreted flash forward: the lane
    kernel with the score width (256) and the value width (128) apart,
    counted once, against the two score products."""
    from tfde_tpu.observability import counters

    keys = jax.random.split(jax.random.key(2), 5)
    q_nope, k_nope, v = (jax.random.normal(key, (1, 256, 2, 128))
                         for key in keys[:3])
    q_rope = jax.random.normal(keys[3], (1, 256, 2, 64))
    k_rope = jax.random.normal(keys[4], (1, 256, 64))
    args = (q_nope, q_rope, k_nope, k_rope, v)
    want = mla_lib.prefill_attention(*args, scale=0.07)
    before = counters.snapshot()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mla_lib, "_SCORES_BYTES", 0)
        got = mla_lib.prefill_attention(*args, scale=0.07, impl="flash")
    after = counters.snapshot()
    assert {name: after.get(f"flash/{name}", 0) - before.get(
        f"flash/{name}", 0) for name in (
            "fwd_lane_traces", "fwd_grid_traces", "fwd_two_width_traces")
            } == {"fwd_lane_traces": 1, "fwd_grid_traces": 0,
                  "fwd_two_width_traces": 1}
    assert got.shape == v.shape
    assert np.abs(np.asarray(got - want)).max() < TOL


def test_prefill_and_decode_match_the_reference(weights, params, honest):
    """A wave from position 0 (per head, the cells written), then steps
    under per-row indices (absorbed over the cache), rows of several
    lengths in one padded bucket."""
    rows = rows_of(3, [44, 21, 60, 33])
    lengths = [20, 7, 41, 33]
    got, cache = served_logits(honest, params, rows, lengths, 48, 64)
    assert worst_gap(lambda row: reference_logits(weights, row), rows,
                     lengths, got) < TOL
    leaf = cache["decoder"]["block_2"]["attn"]
    assert set(leaf) == {"cached_latent", "cached_rope_key", "cache_index"}
    assert leaf["cached_latent"].shape == (4, 64, SHAPE.latent)
    assert leaf["cached_rope_key"].shape == (4, 64, SHAPE.rope)


def test_a_frozen_row_leaves_the_others_alone(weights, params, honest):
    rows = rows_of(4, [30, 30, 30])
    lengths = [12, 9, 20]
    got, _ = served_logits(honest, params, rows, lengths, 32, 64,
                           freeze=(1, 4))
    assert worst_gap(lambda row: reference_logits(weights, row),
                     [rows[0], rows[2]], [12, 20], [got[0], got[2]]) < TOL


def test_a_call_behind_cached_cells_attends_absorbed(weights, params,
                                                     monkeypatch):
    """A second multi-token call continues from the cached cells (one
    shared index, not 0): absorbed over the cache, here a block of queries
    and a chunk of heads at a time, and equal to the reference."""
    monkeypatch.setattr(mla_lib, "_SCORES_BYTES", 0)
    monkeypatch.setattr(mla_lib, "_QUERY_BLOCKS", (8,))
    monkeypatch.setattr(mla_lib, "HEAD_CHUNK", 2)
    model = _decode_clone(latent_model())
    (row,) = rows_of(5, [48])
    cache = init_cache(latent_model(), 1, 64)
    out = []
    for part in (row[None, :32], row[None, 32:]):
        logits, mutated = jax.jit(lambda cache, part: model.apply(
            {"params": params, "cache": cache}, part,
            mutable=["cache", "counters"]))(cache, part)
        cache = mutated["cache"]
        out.append(np.asarray(logits[0]))
    assert np.abs(np.concatenate(out)
                  - reference_logits(weights, row)).max() < TOL


def test_no_decode_tick_forms_a_key_or_value_per_head_over_the_cache(params):
    """The lowered decode scan holds no array with a head axis beside the
    cache's length and a key's or value's width: the cells are read as
    they lie, [rows, max_len, latent] and [rows, max_len, rope]."""
    model, rows, cells = latent_model(), 3, 96     # rows: not the heads' 4
    cache = jax.eval_shape(lambda: _set_index_counters(
        init_cache(model, rows, cells), np.zeros(rows, np.int32)))
    vec = jax.ShapeDtypeStruct((rows,), jnp.int32)
    text = server._decode_scan.lower(
        _decode_clone(model), cache, jax.eval_shape(lambda: params), vec, vec,
        vec, jax.ShapeDtypeStruct((rows,), jnp.bool_), None, None, depth=2,
        temperature=0.0, top_k=None, top_p=None, min_p=None,
        repetition_penalty=1.0, eos_id=None, pad_id=0).as_text()
    assert f"{rows}x{cells}x{SHAPE.latent}xf32" in text
    assert f"{rows}x{cells}x{SHAPE.rope}xf32" in text
    widths = "|".join(str(w) for w in sorted({
        SHAPE.nope, SHAPE.value, SHAPE.query, SHAPE.nope + SHAPE.value}))
    per_head = re.findall(
        rf"tensor<(?:\d+x)?(?:{HEADS}x{cells}|{cells}x{HEADS})x"
        rf"(?:{widths})xf32>", text)
    assert per_head == []


@pytest.mark.parametrize("break_it", ["no_rope_key", "stale_cells",
                                      "temperature_in_the_tables"])
def test_a_broken_layer_fails_the_tolerance(weights, params, monkeypatch,
                                            break_it):
    if break_it == "no_rope_key":
        real = mla_lib.absorbed_attention
        monkeypatch.setattr(
            mla_lib, "absorbed_attention",
            lambda q_abs, q_rope, *a, **kw: real(q_abs, 0 * q_rope, *a, **kw))
    elif break_it == "stale_cells":
        from tfde_tpu.ops import eva_attention

        monkeypatch.setattr(eva_attention, "_rows_update",
                            lambda x, new, start: x)
    else:
        monkeypatch.setattr(
            transformer, "attention_temperature", lambda scaling: 1.0)
    rows = rows_of(6, [40, 28])
    lengths = [20, 11]
    got, _ = served_logits(programs(latent_model()), params, rows, lengths,
                           32, 64)
    assert worst_gap(lambda row: reference_logits(weights, row), rows,
                     lengths, got) > 100 * TOL


# ---------------------------------------------------------------------------
# the router: sigmoid scores, a bias for the choice alone, a held share
# ---------------------------------------------------------------------------

def _layer(held, **kw):
    return MoEMlp(**dict(dict(
        num_experts=EXPERTS, mlp_dim=32, experts_per_token=PER_TOKEN,
        capacity_factor=None, act="swiglu", use_bias=False,
        normalize_topk=True, score="sigmoid", selection_bias=True,
        routed_scale=2.5, shared_expert_dim=32, shared_expert_gated=False,
        held_experts=held, dtype=jnp.float32), **kw))


def _uncut_weights(seed=11):
    """One routed layer's weights with every published expert held."""
    dims = dict(DIMS, held_experts=(0, EXPERTS))
    return dims, ref.make_weights(seed, dims)["layers"][1]


def _share(lw, lo, hi):
    return {"router": {"kernel": lw["router"]},
            "router_bias": lw["router_bias"],
            "experts_gate": lw["e_gate"][lo:hi],
            "experts_fc1": lw["e_up"][lo:hi],
            "experts_fc2": lw["e_down"][lo:hi],
            "shared_gate": {"kernel": lw["s_gate"]},
            "shared_fc1": {"kernel": lw["s_up"]},
            "shared_fc2": {"kernel": lw["s_down"]}}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips, router, bias and
    shared expert alike on all: the four partial results, with the shared
    expert counted once, are the uncut reference's layer; one share alone
    is not."""
    dims, lw = _uncut_weights()
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = jax.random.normal(jax.random.key(3), (2, 24, 64))
    want, _ = ref._moe(x.reshape(48, 64), lw, dims, "highest", None)
    shared = ref._swiglu(x.reshape(48, 64), lw["s_gate"], lw["s_up"],
                         lw["s_down"], "highest")
    parts = []
    for lo in range(0, EXPERTS, 4):
        y, _ = jax.jit(functools.partial(
            _layer((lo, lo + 4)).apply, mutable=["losses", "counters"]))(
            {"params": _share(lw, lo, lo + 4)}, x)
        parts.append(np.asarray(y).reshape(48, 64))
    total = sum(parts) - 3 * np.asarray(shared)
    assert np.abs(total - np.asarray(want)).max() < TOL
    assert np.abs(parts[0] - np.asarray(want)).max() > 100 * TOL


def test_the_bias_changes_the_choice_and_never_a_weight():
    """With the seeded bias over a tenth of the tokens choose other
    experts than without it; where the choice stands, the layer's result
    is the same to rounding: the bias is in no weight."""
    dims, lw = _uncut_weights()
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = jax.random.normal(jax.random.key(5), (4, 64, 64))
    flat = x.reshape(-1, 64)
    _, with_bias = ref._moe(flat, lw, dims, "highest", None)
    _, without = ref._moe(flat, lw, dims, "highest", "selection_bias")
    moved = np.asarray((with_bias != without).any(-1))
    assert 0.1 < moved.mean() < 0.9
    layer = _layer((0, EXPERTS))
    mine = _share(lw, 0, EXPERTS)
    y, _ = layer.apply({"params": mine}, x, mutable=["losses", "counters"])
    y0, _ = layer.apply(
        {"params": dict(mine, router_bias=jnp.zeros((EXPERTS,)))}, x,
        mutable=["losses", "counters"])
    gap = np.abs(np.asarray(y - y0)).reshape(-1, 64).max(-1)
    assert gap[~moved].max() < TOL
    assert (gap[moved] > 100 * TOL).all()
    # and the weights are the scaled shares of the chosen scores: they sum
    # to the scale, whatever the bias
    scores = jax.nn.sigmoid(flat @ lw["router"])
    picked = jnp.take_along_axis(scores, with_bias, -1)
    assert np.allclose(np.asarray(
        2.5 * picked / picked.sum(-1, keepdims=True)).sum(-1), 2.5)


def test_a_softmax_router_is_what_it_was():
    """The default score function and no bias: the parameters and the
    result of a layer built before the new fields."""
    x = jax.random.normal(jax.random.key(1), (2, 8, 64))
    old = MoEMlp(num_experts=4, mlp_dim=16, capacity_factor=None,
                 act="swiglu", use_bias=False, dtype=jnp.float32)
    p = old.init(jax.random.key(0), x)["params"]
    assert "router_bias" not in p
    new = MoEMlp(num_experts=4, mlp_dim=16, capacity_factor=None,
                 act="swiglu", use_bias=False, dtype=jnp.float32,
                 score="softmax", selection_bias=False, routed_scale=None)
    a, _ = old.apply({"params": p}, x, mutable=["losses", "counters"])
    b, _ = new.apply({"params": p}, x, mutable=["losses", "counters"])
    assert (np.asarray(a) == np.asarray(b)).all()
    with pytest.raises(ValueError, match="score must be"):
        MoEMlp(num_experts=4, mlp_dim=16, score="tanh").init(
            jax.random.key(0), x)


# ---------------------------------------------------------------------------
# through ContinuousBatcher
# ---------------------------------------------------------------------------

REQUESTS = [(20, 12), (7, 30), (50, 9), (33, 20), (16, 5), (60, 30)]


@pytest.fixture(scope="module")
def served(params):
    with jax.default_matmul_precision("highest"):
        srv = ContinuousBatcher(latent_model(), params, batch_size=4,
                                max_len=96, scan_depth=4,
                                prompt_buckets=(16, 32, 64, 96))
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
    gaps = ref.served_token_gaps(weights, prompts[i], tokens[i], DIMS, 96)
    assert gaps["gap"].max() < TOL
    assert gaps["routes"].shape == (LAYERS - 1, prompts[i].size
                                    + tokens[i].size, PER_TOKEN)


def test_the_batchers_cache_is_latent_cells_and_nothing_per_head(served):
    srv, _, _ = served
    leaves = jax.tree_util.tree_leaves_with_path(srv._cache)
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in leaves}
    assert names == {"cached_latent", "cached_rope_key", "cache_index",
                     "feed_pad"}
    for name, width in (("cached_latent", SHAPE.latent),
                        ("cached_rope_key", SHAPE.rope)):
        assert [leaf.shape for p, leaf in leaves
                if str(getattr(p[-1], "key", p[-1])) == name] == [
            (4, 96, width)] * LAYERS
    ledger = srv._ledger
    assert ledger.kinds == {"latent"}
    # the program's own account: rows x cells x layers x a cell's bytes
    assert ledger.slab_bytes == 4 * 96 * LAYERS * 4 * SHAPE.cell
    assert ledger.cell_bytes == 4 * SHAPE.cell
    assert ledger.row_cells(10) == ledger.read_cells(10) == LAYERS * 10


def test_batcher_counts_the_cells_at_true_lengths_and_the_routing(served):
    srv, prompts, tokens = served
    stats = srv.stats()
    # every prompt token and every fed served token (all but a request's
    # last) wrote one cell a layer
    written = sum(p.size + t.size - 1 for p, t in zip(prompts, tokens))
    assert stats["latent_cells_committed"] == LAYERS * written
    assert stats["latent_pairs_prefilled"] == LAYERS * sum(
        p.size * (p.size + 1) // 2 for p in prompts)
    # a scan reads, a tick, the cells its active rows had committed at
    # its start, never max_len: at least the prompt's in every tick
    ticks = stats["rounds"]
    assert 0 < stats["latent_cells_read"] < LAYERS * 4 * 96 * ticks
    assert stats["latent_cells_read"] >= LAYERS * sum(
        p.size * (t.size - 1) for p, t in zip(prompts, tokens))
    real = sum(p.size + t.size - 1 for p, t in zip(prompts, tokens))
    assert stats["moe_pairs"] == (LAYERS - 1) * PER_TOKEN * real
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs"]
    assert stats["decode_least_bytes"] > 0


def test_the_ledger_counts_the_layers_the_model_describes(params):
    model = latent_model()
    cache = init_cache(model, 4, 96)
    ledger = CapacityLedger(4, 96, kv_slab_bytes(cache),
                            layout_of(model, 96).layers,
                            moe.held_experts(params))
    assert set(ledger.counters) == set(
        ledger.HYBRID_KEYS + ledger.LATENT_KEYS)
    assert ledger.cells_per_row == LAYERS * 96
    ledger.note_commit(0, 10, decoding=False)
    ledger.note_commit(10, 12)
    ledger.note_scan([12, 30], 4)
    assert ledger.counters["latent_cells_committed"] == LAYERS * 12
    assert ledger.counters["latent_pairs_prefilled"] == LAYERS * 55
    assert ledger.counters["latent_cells_read"] == 4 * LAYERS * 42
    # a tick's least bytes: the parameters outside the experts, the cells
    # read, and of the held experts those the device counted as touched
    experts = sum(int(np.prod(v.shape)) * 4 for block in
                  params["decoder"].values() if "moe" in block
                  for k, v in block["moe"].items()
                  if k.startswith("experts_"))
    total = sum(int(np.prod(a.shape)) * 4 for a in jax.tree.leaves(params))
    routed = np.array([100, 30, 5, 9, 0])
    assert ledger.scan_least_bytes(total, 1000, 2, routed) == int(
        2 * (total - experts + 1000) + 5 * experts / (2 * 4))


@pytest.mark.parametrize("kw", [dict(paged=True), dict(kv_quant="int8")],
                         ids=["the block pool", "int8 cells"])
def test_what_the_latent_leaf_is_not_built_for_is_refused(params, kw):
    with pytest.raises(NotImplementedError, match="latent attention"):
        ContinuousBatcher(latent_model(), params, batch_size=2, max_len=32,
                          **kw)


def test_the_prefix_cache_is_refused_over_a_feed_pad_leaf(params):
    """The latent cells are a cell per position and could be shared; the
    expert layers' `feed_pad` leaf beside them has no positions, and the
    trie would fail on it at the first admission."""
    from tfde_tpu.inference.prefix_cache import PrefixCache

    with pytest.raises(NotImplementedError, match="feed_pad"):
        ContinuousBatcher(latent_model(), params, batch_size=2, max_len=32,
                          prefix_cache=PrefixCache(block=4))


def test_a_latent_cache_is_a_cell_per_position():
    assert layout_of(latent_model(), 96).not_by_position is None
    assert "cached_latent" in LatentAttention.cache_state.__doc__
