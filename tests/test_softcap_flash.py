"""Softcap (Gemma-2 tanh logit capping) and custom scale inside the fused
flash kernels, the reference einsum, the ring body, and the dispatcher.

Oracle chain: hand-built einsum with cap * tanh(s * scale / cap) ->
reference/grouped_attention(scale=, logit_cap=) -> flash_attention in
interpret mode (multi-tile shapes, both backward implementations, GQA) ->
the seq ring -> models/gpt.py end to end with window_pattern='alternate'.
Forward pins at 1e-5 relative Frobenius, grads at 1e-4 (the acceptance
bars)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.ops.attention import grouped_attention, reference_attention
from tfde_tpu.ops.flash_attention import flash_attention


def _qkv(rng, b=1, s=256, h=2, d=8, kv=None, dtype=jnp.float32):
    kv = kv or h
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    return q, k, v


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def test_reference_softcap_matches_hand_einsum(rng):
    """Ground truth for the whole chain: cap applied AFTER the scale and
    BEFORE the causal mask, s -> cap * tanh(s * scale / cap)."""
    q, k, v = _qkv(rng, s=32)
    cap, scale = 30.0, 0.2
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s_ = cap * jnp.tanh(s_ / cap)
    n = q.shape[1]
    mask = jnp.tril(jnp.ones((n, n), bool))
    s_ = jnp.where(mask, s_, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s_, axis=-1), v)
    got = reference_attention(q, k, v, causal=True, scale=scale,
                              logit_cap=cap)
    assert _rel(got, out) <= 1e-6


def test_reference_rejects_nonpositive_cap(rng):
    q, k, v = _qkv(rng, s=16)
    with pytest.raises(ValueError, match="logit_cap"):
        reference_attention(q, k, v, causal=True, logit_cap=0.0)


# (causal, window, scale, cap, kv_heads): MHA and GQA, every knob combo the
# Gemma-2 family exercises; s=256 with 64-blocks -> 4x4 tiles (multi-tile)
CASES = [
    ("cap", True, None, None, 50.0, None),
    ("cap_win", True, 64, None, 30.0, None),
    ("cap_win_scale_gqa", True, 64, 0.125, 30.0, 2),
    ("cap_bidir", False, None, 0.2, 20.0, None),
    ("scale_only", True, None, 0.5, None, None),
    ("cap_scale_gqa_bidir", False, None, 0.25, 40.0, 2),
]


@pytest.mark.parametrize("name,causal,window,scale,cap,kv",
                         CASES, ids=[c[0] for c in CASES])
def test_flash_softcap_forward_parity(rng, name, causal, window, scale,
                                      cap, kv):
    h = 4 if kv else 2
    q, k, v = _qkv(rng, s=256, h=h, kv=kv, d=16)
    ref = grouped_attention(q, k, v, causal=causal, window=window,
                            scale=scale, logit_cap=cap)
    got = flash_attention(q, k, v, causal, 64, 64, True, window, scale, cap)
    assert _rel(got, ref) <= 1e-5


@pytest.mark.parametrize("bwd", ["recurrence", "kernel"])
@pytest.mark.parametrize("name,causal,window,scale,cap,kv",
                         CASES, ids=[c[0] for c in CASES])
def test_flash_softcap_grads_parity(rng, monkeypatch, bwd, name, causal,
                                    window, scale, cap, kv):
    """All three gradients against the grouped oracle, 1e-4 relative
    Frobenius, through BOTH backward implementations (the fused kernel
    serves causal MHA; GQA and non-causal take the recurrence either
    way)."""
    if bwd == "recurrence":  # nothing fits: what `_bwd` observes
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    h = 4 if kv else 2
    q, k, v = _qkv(rng, s=128, h=h, kv=kv, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, 32, 32, True, window, scale,
                            cap) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            grouped_attention(q, k, v, causal=causal, window=window,
                              scale=scale, logit_cap=cap) ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert _rel(a, b) <= 1e-4


def test_ring_softcap_matches_reference(rng):
    """scale + cap ride the ring body's online-softmax chunk step — exact
    across shard boundaries under the seq mesh."""
    from tfde_tpu.ops.attention import attention
    from tfde_tpu.parallel import axes as axes_lib
    from tfde_tpu.runtime.mesh import make_mesh

    q, k, v = _qkv(rng, b=2, s=32)
    expect = reference_attention(q, k, v, causal=True, scale=0.2,
                                 logit_cap=25.0)
    mesh = make_mesh({"seq": 4, "data": 2})
    with axes_lib.use_axes(mesh):
        got = jax.jit(
            lambda q, k, v: attention(q, k, v, causal=True, scale=0.2,
                                      logit_cap=25.0)
        )(q, k, v)
    assert _rel(got, expect) <= 1e-5


def test_tfde_flash_typo_warns_and_keeps_default(monkeypatch):
    """A typo like TFDE_FLASH=ture used to silently LOWER the auto-dispatch
    threshold to 1024; it must now warn and keep the measured default."""
    import tfde_tpu.ops.attention as att

    monkeypatch.setenv("TFDE_FLASH", "ture")
    with pytest.warns(UserWarning, match="TFDE_FLASH"):
        assert att._flash_min_seq(causal=True) == 2048
    with pytest.warns(UserWarning, match="TFDE_FLASH"):
        assert att._flash_min_seq(causal=False) == 4096


def test_tfde_flash_recognized_values_do_not_warn(monkeypatch):
    import warnings

    import tfde_tpu.ops.attention as att

    expect = {"0": None, "false": None, "1": 1024, "true": 1024,
              "auto": 2048, "": 2048}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for env, want in expect.items():
            monkeypatch.setenv("TFDE_FLASH", env)
            assert att._flash_min_seq(causal=True) == want
        monkeypatch.delenv("TFDE_FLASH")
        assert att._flash_min_seq(causal=True) == 2048


def test_auto_dispatch_picks_flash_with_softcap(monkeypatch):
    """Gemma-2-style capped/scaled attention must still auto-pick the
    flash kernel on TPU-eligible shapes (the old transformer.py hard-coded
    grouped_attention whenever a cap was set), with both knobs forwarded
    into the kernel call."""
    import tfde_tpu.ops.attention as att
    import tfde_tpu.ops.flash_attention as fa

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    monkeypatch.delenv("TFDE_FLASH", raising=False)
    seen = []

    def fake_flash(q, k, v, causal=False, **kw):
        seen.append(("flash", kw.get("scale"), kw.get("logit_cap")))
        return q

    def fake_ref(q, k, v, **kw):
        seen.append(("reference", kw.get("scale"), kw.get("logit_cap")))
        return q

    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    monkeypatch.setattr(att, "reference_attention", fake_ref)

    long = jnp.zeros((1, 2048, 1, 4), jnp.bfloat16)
    att.attention(long, long, long, causal=True, scale=0.0625,
                  logit_cap=50.0)
    assert seen == [("flash", 0.0625, 50.0)]

    # below the threshold the reference path gets the same knobs
    seen.clear()
    short = jnp.zeros((1, 512, 1, 4), jnp.bfloat16)
    att.attention(short, short, short, causal=True, logit_cap=50.0)
    assert seen == [("reference", None, 50.0)]


def test_cap_on_incapable_impl_warns_and_falls_back(monkeypatch, rng):
    """The safety net: if a selected impl ever drops out of _CAP_IMPLS,
    capped calls warn and run the grouped reference einsum instead of
    refusing (the model keeps training)."""
    import tfde_tpu.ops.attention as att

    monkeypatch.setattr(att, "_CAP_IMPLS", frozenset({"reference"}))
    used = []
    real_ref = att.reference_attention

    def spy_ref(q, k, v, **kw):
        used.append("reference")
        return real_ref(q, k, v, **kw)

    monkeypatch.setattr(att, "reference_attention", spy_ref)
    q, k, v = _qkv(rng, s=64)
    with pytest.warns(UserWarning, match="scale/logit_cap"):
        got = att.attention(q, k, v, causal=True, impl="flash",
                            logit_cap=30.0)
    assert used == ["reference"]
    expect = real_ref(q, k, v, causal=True, logit_cap=30.0)
    assert _rel(got, expect) <= 1e-6


def test_gpt_alternate_softcap_flash_matches_reference(rng):
    """models/gpt.py end to end: sliding_window_pattern='alternate' +
    attn_logit_cap + GQA routed through the attention() dispatcher — the
    forced-flash model (interpret kernels on CPU) must reproduce the
    reference-impl model on the same params, logits and grads."""
    from tfde_tpu.models.gpt import gpt_tiny_test

    kw = dict(sliding_window=8, sliding_window_pattern="alternate",
              attn_logit_cap=30.0, num_kv_heads=2, position="rope")
    m_ref = gpt_tiny_test(attn_impl="reference", **kw)
    m_fl = gpt_tiny_test(attn_impl="flash", **kw)
    tokens = jnp.asarray(rng.integers(0, 97, size=(2, 64)), jnp.int32)
    params = m_ref.init(jax.random.key(0), tokens)["params"]

    a = m_ref.apply({"params": params}, tokens, train=False)
    b = m_fl.apply({"params": params}, tokens, train=False)
    assert _rel(b, a) <= 1e-5

    def loss(m, p):
        return jnp.mean(m.apply({"params": p}, tokens, train=False) ** 2)

    ga = jax.grad(lambda p: loss(m_ref, p))(params)
    gb = jax.grad(lambda p: loss(m_fl, p))(params)
    flat_a = jax.tree_util.tree_leaves(ga)
    flat_b = jax.tree_util.tree_leaves(gb)
    assert len(flat_a) == len(flat_b)
    for a_, b_ in zip(flat_a, flat_b):
        assert _rel(b_, a_) <= 1e-4
