"""The plain GPT-2 reference agrees with `models/gpt.py` at a tiny size in
float32, forward and through three AdamW steps; its lower precisions stand
further off than float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import traffic
from benchmarks.lib.manifest import reference_module

ref = reference_module("gpt2")
DIMS = {"n_layer": 2, "n_embd": 32, "n_head": 4, "n_positions": 48,
        "vocab_size": 97, "layer_norm_epsilon": 1e-5}
HYPER = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
         "weight_decay": 0.1}


def _program():
    from tfde_tpu.models.gpt import GPT

    return GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4,
               mlp_dim=128, max_position=48, ln_eps=1e-5,
               dtype=jnp.float32, attn_impl="reference")


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 9])
def test_forward_agrees_with_the_program_in_float32(seed):
    w = ref.make_weights(seed, DIMS)
    tokens = traffic.markov_tokens(3, 48, 97, seed)
    want = ref.forward(w, jnp.asarray(tokens), DIMS)
    with jax.default_matmul_precision("highest"):
        got = _program().apply(
            {"params": ref.to_program_params(w, DIMS["n_head"])}, tokens)
    assert got.shape == want.shape == (3, 48, 97)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max() + 1.0)


def test_same_seed_same_weights_and_the_tree_round_trips():
    a, b = ref.make_weights(11, DIMS), ref.make_weights(11, DIMS)
    c = ref.make_weights(12, DIMS)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wte"], c["wte"])
    assert ref.num_params(DIMS) == sum(int(np.prod(v.shape))
                                      for v in a.values())
    back = ref.from_program_params(ref.to_program_params(a, DIMS["n_head"]))
    assert set(back) == set(a)
    assert all(np.array_equal(a[k], back[k]) for k in a)
    tree = ref.to_program_params(a, DIMS["n_head"])
    want = jax.eval_shape(lambda: _program().init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    assert jax.tree.map(lambda x: x.shape, tree) == \
        jax.tree.map(lambda x: x.shape, want)


def test_three_adamw_steps_agree_with_optax_on_the_program():
    import optax

    from tfde_tpu.training.optimizers import adamw as masked_adamw

    seed = 3
    pool = traffic.markov_tokens(6, 48, 97, seed)
    batches = [pool[0:2], pool[2:4], pool[4:6]]
    want = ref.train_steps(ref.make_weights(seed, DIMS), batches, DIMS, HYPER)

    model = _program()
    params = ref.to_program_params(ref.make_weights(seed, DIMS),
                                   DIMS["n_head"])
    start = params
    tx = masked_adamw(HYPER["learning_rate"], weight_decay=0.1)
    opt = tx.init(params)

    def loss_fn(p, rows):
        logits = model.apply({"params": p}, rows)[:, :-1]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, rows[:, 1:]).mean()

    losses = []
    with jax.default_matmul_precision("highest"):
        for rows in batches:
            loss, grads = jax.value_and_grad(loss_fn)(params,
                                                      jnp.asarray(rows))
            if not losses:
                first = ref.leaf_norms(ref.from_program_params(grads))
            losses.append(float(loss))
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
    got_delta = ref.delta_norms(ref.from_program_params(params),
                                ref.from_program_params(start))
    assert losses == pytest.approx(want["losses"], rel=1e-5)
    assert ref.worst_norm_gap(jax.device_get(first),
                              want["grad_norms"])[0] < 1e-3
    assert ref.worst_norm_gap(jax.device_get(got_delta),
                              want["delta_norms"], updates=True)[0] < 1e-2


@pytest.mark.parametrize("precision", ["bf16", "fp8"])
def test_lower_precisions_stand_off_the_reference(precision):
    w = ref.make_weights(1, DIMS)
    tokens = jnp.asarray(traffic.markov_tokens(2, 48, 97, 1))
    exact = ref.forward(w, tokens, DIMS)
    lower = ref.forward(w, tokens, DIMS, precision)
    off = float(jnp.abs(lower - exact).max())
    assert 1e-5 < off < 0.5
    with pytest.raises(ValueError):
        ref.forward(w, tokens, DIMS, "int3")


def test_served_token_gaps_are_zero_for_the_reference_s_own_choices():
    w = ref.make_weights(2, DIMS)
    prompt = traffic.markov_tokens(1, 10, 97, 2)[0]
    served = []
    for _ in range(6):          # greedy decoding by the full forward
        tokens = jnp.asarray(np.concatenate([prompt, served]).astype(
            np.int32))[None]
        served.append(int(ref.forward(w, tokens, DIMS)[0, -1].argmax()))
    gaps = ref.served_token_gaps(w, prompt, served, DIMS, pad_to=48)
    assert gaps["gap"].shape == (6,) and float(gaps["gap"].max()) < 1e-5
    assert list(gaps["argmax"]) == served
    altered = list(served)
    altered[3] = (altered[3] + 1) % 97
    off = ref.gaps_of_choices(w, prompt, served, altered, DIMS, pad_to=48)
    assert off[3] > 0 and float(np.delete(off, 3).max()) < 1e-5


def test_worst_norm_gap_names_the_leaf_and_skips_the_key_bias():
    want = {"attn_b": np.ones((2, 3)), "wte": np.array(4.0)}
    got = {"attn_b": np.array([[1.0, 3.0, 1.0], [1.0, 1.0, 1.1]]),
           "wte": np.array(4.2)}
    assert ref.worst_norm_gap(got, want) == (2.0, "attn_b[0, 1]")
    gap, leaf = ref.worst_norm_gap(got, want, updates=True)
    assert leaf == "attn_b[1, 2]" and gap == pytest.approx(0.1)
    assert ref.worst_norm_gap(got, want, leaves=("wte",))[1] == "wte[]"
