"""What the chip runs carries a name a profile can show: the Pallas calls
by `name=`, the rest by `jax.named_scope`. Metadata only; each name is
found in the lowered text on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.inference import server
from tfde_tpu.models.gpt import gpt_tiny_test, next_token_loss
from tfde_tpu.ops.flash_attention import flash_attention
from tfde_tpu.training.train_state import TrainState


def _flash_grad_text(monkeypatch, bwd, kv_heads=2):
    if bwd == "recurrence":  # nothing fits: what `_bwd` observes
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    k = jnp.zeros((1, 256, kv_heads, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, 128, 128, True).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).as_text(debug_info=True)


def _train_text(_monkeypatch):
    model = gpt_tiny_test()
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    tx = optax.adamw(1e-3)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params),
                       apply_fn=model.apply, tx=tx)

    def step(state, tokens, rng):
        (loss, _), grads = jax.value_and_grad(
            lambda p: next_token_loss(state, p, tokens, rng), has_aux=True
        )(state.params)
        return state.apply_gradients(grads), loss

    return jax.jit(step).lower(state, tokens, jax.random.key(1)).as_text(
        debug_info=True)


def _serve_text(which):
    model = gpt_tiny_test()
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = server.ContinuousBatcher(model, params, batch_size=2, max_len=32,
                                   scan_depth=2)
    if which == "prefill":
        lowered = server._prefill_rows.lower(
            srv._decode_model, srv._row_template(1), params,
            jnp.zeros((1, 8), jnp.int32), jnp.zeros(1, jnp.int32), None,
            None, **srv._sampling)
    else:
        zeros = jnp.zeros(2, jnp.int32)
        lowered = server._decode_scan.lower(
            srv._scan_model, srv._cache, params, zeros, zeros, zeros,
            jnp.zeros(2, bool), None, None, depth=2, eos_id=None, pad_id=0,
            **srv._sampling)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("name,text", [
    ("flash_fwd", lambda mp: _flash_grad_text(mp, "recurrence")),
    # grouped-query takes the grid forward: the same name
    ("flash_fwd", lambda mp: _flash_grad_text(mp, "kernel", 1)),
    ("flash_bwd_pair_scan", lambda mp: _flash_grad_text(mp, "recurrence")),
    ("flash_bwd", lambda mp: _flash_grad_text(mp, "kernel")),
    # grouped-query is the recurrence's with the budget as it is
    ("flash_bwd_pair_scan", lambda mp: _flash_grad_text(mp, "kernel", 1)),
    ("optimizer_update", _train_text),
    ("head_loss", _train_text),
    ("prefill_rows", lambda mp: _serve_text("prefill")),
    ("decode_tick", lambda mp: _serve_text("decode")),
])
def test_name_is_in_the_lowered_text(monkeypatch, name, text):
    assert name in text(monkeypatch)
