"""Sliding-window attention (the Mistral-family capability): band masking
in the reference einsum, windowed tile skipping in the flash kernel, the
decode-cache band mask, and the GPT `sliding_window` field end to end.

The oracle chain: hand-built band mask -> reference_attention(window=) ->
flash_attention(window=) -> windowed decode == windowed full forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.models.gpt import gpt_tiny_test
from tfde_tpu.ops.attention import grouped_attention, reference_attention
from tfde_tpu.ops.flash_attention import flash_attention


def _qkv(rng, b=1, s=64, h=2, d=8, dtype=jnp.float32):
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
        for _ in range(3)
    )


def test_window_matches_explicit_band_mask(rng):
    q, k, v = _qkv(rng)
    s = q.shape[1]
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    band = jnp.logical_and(rows >= cols, rows - cols < 7)
    ref = reference_attention(q, k, v, mask=band)
    win = reference_attention(q, k, v, causal=True, window=7)
    np.testing.assert_allclose(np.asarray(win), np.asarray(ref), atol=1e-6)


def test_window_geq_seq_equals_plain_causal(rng):
    q, k, v = _qkv(rng)
    full = reference_attention(q, k, v, causal=True)
    win = reference_attention(q, k, v, causal=True, window=q.shape[1])
    np.testing.assert_allclose(np.asarray(win), np.asarray(full), atol=1e-6)


def test_window_one_attends_self_only(rng):
    q, k, v = _qkv(rng)
    win = reference_attention(q, k, v, causal=True, window=1)
    # softmax over a single position == that position's value row
    np.testing.assert_allclose(np.asarray(win), np.asarray(v), atol=1e-5)


def test_window_requires_causal(rng):
    q, k, v = _qkv(rng)
    with pytest.raises(ValueError, match="causal"):
        reference_attention(q, k, v, window=4)


def test_window_with_gqa(rng):
    q, _, _ = _qkv(rng, h=4)
    _, k, v = _qkv(rng, h=2)
    s = q.shape[1]
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    band = jnp.logical_and(rows >= cols, rows - cols < 5)
    ref = grouped_attention(q, k, v, mask=band)
    win = grouped_attention(q, k, v, causal=True, window=5)
    np.testing.assert_allclose(np.asarray(win), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_window_matches_reference(rng, window):
    q, k, v = _qkv(rng, s=256, d=16)
    ref = reference_attention(q, k, v, causal=True, window=window)
    fl = flash_attention(q, k, v, causal=True, window=window,
                         block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bwd", ["recurrence", "kernel"])
def test_flash_window_backward_matches_reference(rng, bwd, monkeypatch):
    if bwd == "recurrence":  # nothing fits: what `_bwd` observes
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    q, k, v = _qkv(rng, s=128, d=8)

    def ref_loss(q, k, v):
        return jnp.sum(
            reference_attention(q, k, v, causal=True, window=48) ** 2
        )

    def fl_loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, window=48,
                            block_q=32, block_k=32, interpret=True) ** 2
        )

    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(fl_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_window_requires_causal(rng):
    q, k, v = _qkv(rng, s=128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8, interpret=True)


def test_gpt_sliding_window_is_banded(rng):
    """Full-sequence forward: logits at position i must be independent of
    tokens older than i - window + 1 (change them; logits stay put) and
    dependent on tokens inside the band."""
    model = gpt_tiny_test(sliding_window=4)
    tokens = jnp.asarray(rng.integers(0, 97, size=(1, 16)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    base = model.apply({"params": params}, tokens, train=False)
    # mutate a token far outside the last position's band
    far = tokens.at[0, 2].set((tokens[0, 2] + 1) % 97)
    out_far = model.apply({"params": params}, far, train=False)
    np.testing.assert_allclose(np.asarray(base[0, -1]),
                               np.asarray(out_far[0, -1]), atol=1e-5)
    # mutate a token inside the band: logits must move
    near = tokens.at[0, 14].set((tokens[0, 14] + 1) % 97)
    out_near = model.apply({"params": params}, near, train=False)
    assert float(jnp.max(jnp.abs(base[0, -1] - out_near[0, -1]))) > 1e-4


@pytest.mark.slow
def test_windowed_decode_matches_windowed_forward(rng):
    """Greedy generation with the cache must reproduce the windowed
    full-forward rollout token for token (the decode-path band mask is the
    same math as the training band)."""
    from tfde_tpu.inference.decode import generate

    model = gpt_tiny_test(sliding_window=6)
    prompt = jnp.asarray(rng.integers(0, 97, size=(2, 5)), jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    toks, _ = generate(model, params, prompt, 10)

    # rollout oracle: repeatedly run the full windowed forward
    cur = prompt
    for _ in range(10):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(cur))


def test_windowed_decode_prefill_longer_than_window(rng):
    """Prefill LONGER than the window: the band must clip cache columns
    already during the prompt forward (the sq>1 branch of the decode
    mask), not just during single-token steps."""
    from tfde_tpu.inference.decode import generate

    model = gpt_tiny_test(sliding_window=3)
    prompt = jnp.asarray(rng.integers(0, 97, size=(2, 9)), jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    toks, _ = generate(model, params, prompt, 6)

    cur = prompt
    for _ in range(6):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(cur))


@pytest.mark.slow
def test_windowed_decode_with_rope_and_gqa(rng):
    from tfde_tpu.inference.decode import generate

    model = gpt_tiny_test(sliding_window=5, position="rope", num_kv_heads=2)
    prompt = jnp.asarray(rng.integers(0, 97, size=(2, 4)), jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    toks, _ = generate(model, params, prompt, 8)

    cur = prompt
    for _ in range(8):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(cur))


@pytest.mark.parametrize("window", [4, 8, 100])
def test_window_through_seq_ring_matches_reference(rng, window):
    """The sliding band composes with the 'seq' ring: the ring body masks
    on GLOBAL positions, so bands that span shard boundaries (window > the
    8-position shard) are exact — long-context sliding-window models train
    under sequence parallelism."""
    from tfde_tpu.ops.attention import attention, reference_attention
    from tfde_tpu.parallel import axes as axes_lib
    from tfde_tpu.runtime.mesh import make_mesh

    q, k, v = _qkv(rng, b=2, s=32)
    mesh = make_mesh({"seq": 4, "data": 2})
    expect = reference_attention(q, k, v, causal=True, window=window)
    with axes_lib.use_axes(mesh):
        got = jax.jit(
            lambda q, k, v: attention(q, k, v, causal=True, window=window)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_windowed_gqa_mistral_trains_under_seq_ring(rng):
    """The full Mistral combination — sliding window + GQA + sequence
    parallelism — trains end to end: band and grouping both ride the ring
    body, loss falls."""
    import optax

    from tfde_tpu.data.datasets import synthetic_tokens
    from tfde_tpu.models.gpt import GPT, next_token_loss
    from tfde_tpu.parallel.strategies import SequenceParallelStrategy
    from tfde_tpu.training.step import init_state, make_custom_train_step

    model = GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4,
                mlp_dim=64, max_position=32, dtype=jnp.float32,
                num_kv_heads=2, sliding_window=8, position="rope")
    strategy = SequenceParallelStrategy(data=2)
    state, _ = init_state(model, optax.adamw(3e-3), strategy,
                          np.zeros((8, 32), np.int32))
    step = make_custom_train_step(strategy, state, next_token_loss,
                                  donate=False)
    toks = synthetic_tokens(128, 32, vocab=96)
    gen = np.random.default_rng(0)
    first = None
    for _ in range(25):
        idx = gen.integers(0, len(toks), 8)
        state, m = step(state, (jnp.asarray(toks[idx]),), jax.random.key(0))
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first - 0.2, (first, float(m["loss"]))
