"""Flash-attention kernel tests — interpret mode on CPU (the fake-backend
methodology of SURVEY.md §4 applied to Pallas kernels); numerics + grads
against the reference einsum implementation."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.ops.attention import reference_attention
from tfde_tpu.ops.flash_attention import flash_attention


def _qkv(rng, b=2, s=256, h=2, d=8, dtype=jnp.float32):
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    expect = reference_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 128, 64, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_single_block(rng):
    q, k, v = _qkv(rng, s=64)
    got = flash_attention(q, k, v, False, 128, 128, True)  # blocks clamp to 64
    expect = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(rng, causal):
    q, k, v = _qkv(rng, s=128, d=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 64, 32, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 64)])
def test_flash_gqa_matches_grouped_reference(rng, causal, window):
    """GQA shapes (k/v with fewer heads): forward and all three gradients
    must match the grouped-einsum oracle — the K/V index maps fold each q
    head onto its serving KV head, the kernel body is unchanged."""
    from tfde_tpu.ops.attention import grouped_attention

    b, s, h, kv, d = 2, 128, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, 64, 32, True, window) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            grouped_attention(q, k, v, causal=causal, window=window) ** 2
        )

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal, 64, 32, True, window)),
        np.asarray(grouped_attention(q, k, v, causal=causal, window=window)),
        rtol=2e-5, atol=2e-5,
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (b, s, kv, d) and gf[2].shape == (b, s, kv, d)
    for a, bb in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_flash_rejects_bad_gqa_heads(rng):
    q = jnp.zeros((1, 128, 4, 8), jnp.float32)
    k = v = jnp.zeros((1, 128, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k, v, False, 64, 64, True)


def test_flash_rejects_indivisible_seq(rng):
    q, k, v = _qkv(rng, s=100)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, False, 64, 64, True)


def test_flash_bf16_inputs(rng):
    q, k, v = _qkv(rng, s=128, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, False, 64, 64, True)
    assert got.dtype == jnp.bfloat16
    expect = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(expect, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_rejects_cross_attention_shapes(rng):
    """All tiling derives from q.shape; Sk != Sq must be a loud error, not a
    silent wrong-range attend (ADVICE r1)."""
    from tfde_tpu.ops.flash_attention import flash_attention

    q = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 256, 2, 16)), jnp.float32)
    with pytest.raises(ValueError, match="cross-attention"):
        flash_attention(q, kv, kv, interpret=True)


def test_auto_dispatch_flash_on_tpu_threshold(monkeypatch):
    """Auto-dispatch: flash on TPU from S>=2048, reference below;
    TFDE_FLASH=0 disables, =1 lowers the threshold."""
    import tfde_tpu.ops.attention as att
    import tfde_tpu.ops.flash_attention as fa

    chosen = []
    monkeypatch.setattr(att, "_on_tpu", lambda: True)

    def fake_flash(q, k, v, causal=False, **kw):
        chosen.append("flash")
        return q

    def fake_ref(q, k, v, mask=None, causal=False, window=None, **kw):
        chosen.append("reference")
        return q

    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    monkeypatch.setattr(att, "reference_attention", fake_ref)
    monkeypatch.delenv("TFDE_FLASH", raising=False)

    long = jnp.zeros((1, 2048, 1, 4), jnp.bfloat16)
    # strictly between the TFDE_FLASH=1 threshold (1024) and the causal
    # default (2048): proves the two thresholds are distinct
    mid = jnp.zeros((1, 1536, 1, 4), jnp.bfloat16)
    longer = jnp.zeros((1, 4096, 1, 4), jnp.bfloat16)

    att.attention(long, long, long, causal=True)
    att.attention(mid, mid, mid, causal=True)
    assert chosen == ["flash", "reference"]

    # non-causal: the flash win is the causal tile skip — threshold 4096
    # (memory-motivated; r04 A/B measured 0.87-0.97x there)
    chosen.clear()
    att.attention(long, long, long)
    att.attention(longer, longer, longer)
    assert chosen == ["reference", "flash"]

    chosen.clear()
    monkeypatch.setenv("TFDE_FLASH", "0")
    att.attention(long, long, long, causal=True)
    assert chosen == ["reference"]

    chosen.clear()
    monkeypatch.setenv("TFDE_FLASH", "1")
    att.attention(mid, mid, mid, causal=True)
    assert chosen == ["flash"]

    # cross-attention shapes never auto-pick flash
    chosen.clear()
    monkeypatch.delenv("TFDE_FLASH", raising=False)
    kv = jnp.zeros((1, 8192, 1, 4), jnp.bfloat16)
    att.attention(long, kv, kv)
    assert chosen == ["reference"]


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_jax_backward(rng, causal, monkeypatch):
    """What `_bwd` picks (the fused kernel for causal, the recurrence for
    non-causal) against the recurrence it takes when nothing fits VMEM,
    asymmetric tile sizes, bf16 inputs."""
    q, k, v = _qkv(rng, s=128, d=8, dtype=jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, 64, 32, True).astype(jnp.float32)
            ** 2
        )

    gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(
        "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    gj = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2,  # bf16 grads
        )


def _bumped(before, *names):
    """How far each `flash/<name>` counter moved since `before`."""
    from tfde_tpu.observability import counters

    after = counters.snapshot()
    return {
        name: after.get(f"flash/{name}", 0) - before.get(f"flash/{name}", 0)
        for name in names
    }


def _grads_and_path(q, k, v, causal, bq, bk, window=None, cap=None):
    """Flash gradients (interpreted), the path `_bwd` took and what it
    counted, beside autodiff through the float32 reference."""
    from tfde_tpu.observability import counters
    from tfde_tpu.ops import flash_attention as fa
    from tfde_tpu.ops.attention import grouped_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal, bq, bk, True, window, None,
                              cap)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(grouped_attention(q, k, v, causal=causal,
                                         window=window, logit_cap=cap) ** 2)

    before = counters.snapshot()
    with fa.record_tile_visits() as counts:
        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        jax.block_until_ready(got)
        jax.effects_barrier()
    bumped = _bumped(before, "bwd_kernel_traces", "bwd_recurrence_traces")
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v)))
    return got, want, dict(counts), bumped


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 64), (64, 128)])
def test_fused_backward_matches_reference(rng, bq, bk, dtype, tol):
    """The fused kernel over several tiles, batch 2, a head pair in one
    128-lane block (two heads of 64): all three gradients against autodiff
    through the reference, and the pairs its loop ran against the plan."""
    from tfde_tpu.ops.flash_attention import bwd_tile_plan

    q, k, v = _qkv(rng, b=2, s=512, h=2, d=64, dtype=dtype)
    got, want, counts, bumped = _grads_and_path(q, k, v, True, bq, bk)
    assert counts["bwd_path"] == "kernel"
    assert bumped == {"bwd_kernel_traces": 1, "bwd_recurrence_traces": 0}
    plan = bwd_tile_plan(512, bq, bk)
    assert counts["bwd_dq_visits"] == counts["bwd_dkv_visits"] \
        == plan["visits"]
    assert counts["bwd_steps_executed"] == plan["visits"]
    for a, b in zip(got, want):
        assert a.dtype == dtype
        assert _rel(a, b) <= tol


@pytest.mark.parametrize("window,cap", [(96, None), (None, 30.0),
                                        (160, 20.0)])
def test_fused_backward_takes_window_and_cap(rng, window, cap):
    """Sliding windows and the logit cap run through the kernel (two
    blocks of two heads): its Q-tile loop follows the band."""
    from tfde_tpu.ops.flash_attention import bwd_tile_plan

    q, k, v = _qkv(rng, b=1, s=256, h=4, d=64)
    got, want, counts, _ = _grads_and_path(q, k, v, True, 64, 64, window,
                                           cap)
    assert counts["bwd_path"] == "kernel"
    plan = bwd_tile_plan(256, 64, 64, window=window)
    assert counts["bwd_steps_executed"] == plan["visits"]
    for a, b in zip(got, want):
        assert _rel(a, b) <= 1e-5


@pytest.mark.parametrize("name,causal,kv,budget,path", [
    ("mha_causal", True, 2, None, "kernel"),
    ("one_head_of_128", True, 1, None, "kernel"),
    ("grouped_query", True, 1, None, "recurrence"),
    ("non_causal", False, 2, None, "recurrence"),
    ("past_vmem", True, 2, 0, "recurrence"),
])
def test_bwd_chooses_from_its_operands(rng, monkeypatch, name, causal, kv,
                                       budget, path):
    """`_bwd` counts the path it took at trace time: the kernel for causal
    multi-head attention, the recurrence for grouped-query, non-causal,
    and a head block past the VMEM budget; gradients match either way."""
    if budget is not None:
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", budget)
    h, d = (1, 128) if name == "one_head_of_128" else (2, 64)
    q = jnp.asarray(rng.standard_normal((1, 128, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, 128, kv, d)), jnp.float32)
            for _ in range(2))
    got, want, counts, bumped = _grads_and_path(q, k, v, causal, 64, 64)
    assert counts["bwd_path"] == path
    assert bumped == {"bwd_kernel_traces": int(path == "kernel"),
                      "bwd_recurrence_traces": int(path == "recurrence")}
    for a, b in zip(got, want):
        assert _rel(a, b) <= 1e-5


def test_bwd_leaves_what_does_not_fit_vmem_to_the_recurrence(
        rng, monkeypatch):
    """A head block's working set over the budget (here by shrinking the
    budget) is the recurrence's."""
    from tfde_tpu.ops import flash_attention as fa

    assert fa._bwd_kernel_vmem_bytes(4096, 128, 2, 512, 512) \
        <= fa._BWD_KERNEL_VMEM_BUDGET
    assert fa._bwd_kernel_vmem_bytes(131072, 128, 2, 512, 512) \
        > fa._BWD_KERNEL_VMEM_BUDGET
    monkeypatch.setattr(fa, "_BWD_KERNEL_VMEM_BUDGET", 1 << 16)
    q, k, v = _qkv(rng, b=1, s=128, h=2, d=64)
    got, want, counts, _ = _grads_and_path(q, k, v, True, 64, 64)
    assert counts["bwd_path"] == "recurrence"
    for a, b in zip(got, want):
        assert _rel(a, b) <= 1e-5


@pytest.mark.parametrize("h,d,heads", [(16, 64, 2), (8, 128, 1), (4, 256, 1),
                                       (2, 8, 2), (8, 32, 4), (3, 64, None),
                                       (4, 96, None)])
def test_bwd_heads_per_block(h, d, heads):
    from tfde_tpu.ops.flash_attention import _bwd_heads_per_block

    assert _bwd_heads_per_block(h, d) == heads


@pytest.mark.parametrize("s,bq,bk,window", [(512, 64, 64, None),
                                            (512, 128, 64, None),
                                            (512, 64, 128, 100),
                                            (1024, 64, 64, 128)])
def test_q_tile_range_is_the_band(s, bq, bk, window):
    """The kernel's loop bounds per K tile enumerate `_band_tile_pairs`."""
    from tfde_tpu.ops import flash_attention as fa

    n_q = s // bq
    pairs = set()
    for kb in range(s // bk):
        lo, hi = fa._q_tile_range(kb, bq, bk, n_q, window)
        pairs |= {(qi, kb) for qi in range(int(lo), int(hi) + 1)}
    assert pairs == set(fa._band_tile_pairs(s, bq, bk, True, window))


@pytest.mark.parametrize("path", ["kernel", "recurrence"])
def test_model_gradient_bumps_the_kernel_once_a_layer(rng, monkeypatch,
                                                      path):
    """Tracing a causal LM's gradient through attn_impl='flash' takes the
    fused kernel in every layer and the recurrence in none; with no room
    in VMEM, the reverse."""
    from tfde_tpu.models.gpt import gpt_tiny_test
    from tfde_tpu.observability import counters

    if path == "recurrence":
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    model = gpt_tiny_test(attn_impl="flash")
    tokens = jnp.asarray(rng.integers(0, 97, size=(2, 64)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        return jnp.sum(model.apply({"params": p}, tokens, train=False) ** 2)

    before = counters.snapshot()
    jax.jit(jax.grad(loss)).lower(params)
    after = counters.snapshot()
    other = "recurrence" if path == "kernel" else "kernel"
    assert after.get(f"flash/bwd_{path}_traces", 0) \
        - before.get(f"flash/bwd_{path}_traces", 0) == model.depth == 2
    assert after.get(f"flash/bwd_{other}_traces", 0) \
        == before.get(f"flash/bwd_{other}_traces", 0)


def _forward_and_path(q, k, v, causal, bq, bk, window=None, scale=None,
                      cap=None):
    """(out, lse [B, H, S]) of the interpreted forward, the path
    `_flash_forward` took and what it counted."""
    from tfde_tpu.observability import counters
    from tfde_tpu.ops import flash_attention as fa

    before = counters.snapshot()
    with fa.record_tile_visits() as counts:
        out, lse = fa._flash_forward(q, k, v, causal, bq, bk, True, window,
                                     scale, cap)
        jax.block_until_ready(out)
        jax.effects_barrier()
    return (out, fa._lse_bhs(lse), dict(counts),
            _bumped(before, "fwd_lane_traces", "fwd_grid_traces",
                    "fwd_two_width_traces"))


def _reference_out_and_lse(q, k, v, causal, window, scale, cap):
    """Plain float32 softmax attention with its log-sum-exp [B, H, S]."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    s, d = q.shape[1], q.shape[3]
    z = np.einsum("bqhd,bkhd->bhqk", q, k) * (
        1.0 / d ** 0.5 if scale is None else scale)
    if cap is not None:
        z = cap * np.tanh(z / cap)
    if causal:
        i, j = np.arange(s)[:, None], np.arange(s)[None, :]
        keep = i >= j
        if window is not None:
            keep &= i - j < window
        z = np.where(keep, z, -np.inf)
    m = z.max(-1, keepdims=True)
    e = np.exp(z - m)
    lse = (m + np.log(e.sum(-1, keepdims=True)))[..., 0]
    out = np.einsum("bhqk,bkhd->bqhd", e / e.sum(-1, keepdims=True), v)
    return out, lse


@pytest.mark.parametrize(
    "name,b,s,h,d,dtype,bq,bk,causal,window,scale,cap", [
        ("head_pairs_of_64", 2, 512, 4, 64, jnp.float32, 128, 128, True,
         None, None, None),
        ("asymmetric_tiles", 1, 512, 2, 64, jnp.float32, 128, 64, True,
         None, None, None),
        ("wide_q_tile", 1, 512, 2, 64, jnp.float32, 256, 128, True,
         None, None, None),
        ("heads_of_128", 1, 256, 2, 128, jnp.float32, 64, 64, True,
         None, None, None),
        ("all_heads_in_one_block", 2, 256, 4, 16, jnp.float32, 64, 64, True,
         None, None, None),
        ("odd_count_of_head_pairs", 1, 256, 6, 64, jnp.float32, 64, 64,
         True, None, None, None),
        ("bf16", 2, 512, 2, 64, jnp.bfloat16, 128, 128, True,
         None, None, None),
        ("s2176_tiles_of_128", 1, 2176, 2, 64, jnp.float32, 128, 128, True,
         None, None, None),
        ("window_1000", 1, 2048, 2, 64, jnp.float32, 256, 256, True,
         1000, None, None),
        ("window_under_a_tile", 2, 512, 2, 64, jnp.float32, 128, 64, True,
         96, None, None),
        ("cap_30_with_a_scale", 1, 256, 2, 64, jnp.float32, 64, 64, True,
         None, 0.25, 30.0),
        ("window_cap_scale", 1, 512, 4, 64, jnp.float32, 64, 128, True,
         160, 0.3, 20.0),
        ("non_causal", 2, 256, 2, 64, jnp.float32, 64, 128, False,
         None, None, None),
        ("non_causal_cap", 1, 256, 4, 16, jnp.float32, 128, 64, False,
         None, None, 30.0),
    ])
def test_lane_forward_matches_reference_and_grid(
        rng, monkeypatch, name, b, s, h, d, dtype, bq, bk, causal, window,
        scale, cap):
    """The lane forward's out and lse against float32 softmax attention
    and against the grid kernel, reached through what `_flash_forward`
    observes (no room in VMEM); the K steps its loop ran against the
    plan."""
    from tfde_tpu.ops.flash_attention import bwd_tile_plan

    q, k, v = _qkv(rng, b=b, s=s, h=h, d=d, dtype=dtype)
    out, lse, counts, bumped = _forward_and_path(q, k, v, causal, bq, bk,
                                                 window, scale, cap)
    assert counts["fwd_path"] == "lane"
    assert bumped == {"fwd_lane_traces": 1, "fwd_grid_traces": 0,
                      "fwd_two_width_traces": 0}
    plan = bwd_tile_plan(s, bq, bk, causal, window)
    assert counts["fwd_visits"] == plan["visits"]
    assert counts["fwd_steps_executed"] == plan["visits"]
    assert out.dtype == dtype and out.shape == q.shape
    assert lse.dtype == jnp.float32 and lse.shape == (b, h, s)

    monkeypatch.setattr(
        "tfde_tpu.ops.flash_attention._FWD_KERNEL_VMEM_BUDGET", 0)
    grid_out, grid_lse, grid_counts, _ = _forward_and_path(
        q, k, v, causal, bq, bk, window, scale, cap)
    assert grid_counts["fwd_path"] == "grid"
    assert "fwd_steps_executed" not in grid_counts

    want_out, want_lse = _reference_out_and_lse(q, k, v, causal, window,
                                                scale, cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for got_out, got_lse in ((out, lse), (grid_out, grid_lse)):
        np.testing.assert_allclose(np.asarray(got_out, np.float64),
                                   want_out, rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(got_lse, np.float64),
                                   want_lse, rtol=tol, atol=tol)
    # the two kernels against each other: the same tile order and
    # arithmetic, only the order inside a tile's sums differs
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(grid_out, np.float32),
        rtol=0, atol=1e-5 if dtype == jnp.float32 else 2 ** -7)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(grid_lse),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("name,s,h,kv,d,dv,causal,window,budget,path", [
    ("mha_causal", 128, 2, 2, 64, 64, True, None, None, "lane"),
    ("mha_non_causal", 128, 2, 2, 64, 64, False, None, None, "lane"),
    ("one_head_of_128", 128, 1, 1, 128, 128, True, None, None, "lane"),
    ("heads_under_a_lane_block", 128, 4, 4, 8, 8, True, None, None, "lane"),
    ("grouped_query", 128, 2, 1, 64, 64, True, None, None, "grid"),
    ("head_width_no_lane_block_tiles", 128, 3, 3, 64, 64, True, None, None,
     "grid"),
    ("head_width_96", 128, 4, 4, 96, 96, True, None, None, "grid"),
    ("past_vmem", 128, 2, 2, 64, 64, True, None, 0, "grid"),
    ("grouped_query_of_128", 128, 4, 2, 128, 128, True, None, None, "lane"),
    ("seven_over_one_of_128", 128, 7, 1, 128, 128, True, None, None, "lane"),
    ("grouped_query_of_128_non_causal", 128, 4, 2, 128, 128, False, None,
     None, "lane"),
    ("grouped_query_of_256", 128, 4, 1, 256, 256, True, None, None, "lane"),
    ("grouped_window_across_tile_edges", 384, 6, 2, 128, 128, True, 100,
     None, "lane"),
    ("grouped_window_of_two_tiles", 256, 4, 2, 128, 128, True, 128, None,
     "lane"),
    ("grouped_query_of_64_pairs", 128, 4, 2, 64, 64, True, None, None,
     "grid"),
    ("grouped_query_of_128_past_vmem", 128, 4, 2, 128, 128, True, None, 0,
     "grid"),
    # values of a width of their own: both widths whole lane blocks stay
    # apart in the lane kernel, any other unequal pair is padded to one
    # width inside `_flash_forward` and takes what that width takes
    ("score_256_value_128", 128, 2, 2, 256, 128, True, None, None,
     "two_widths"),
    ("score_256_value_128_of_two_tiles_non_causal", 256, 3, 3, 256, 128,
     False, None, None, "two_widths"),
    ("score_128_value_256", 128, 2, 2, 128, 256, True, None, None,
     "two_widths"),
    ("score_256_value_128_window", 384, 2, 2, 256, 128, True, 100, None,
     "two_widths"),
    ("grouped_score_256_value_128", 128, 4, 2, 256, 128, True, None, None,
     "two_widths"),
    ("score_64_value_32_padded", 128, 2, 2, 64, 32, True, None, None,
     "lane"),
    ("score_32_value_64_padded", 128, 2, 2, 32, 64, True, None, None,
     "lane"),
    ("score_192_value_128_padded", 128, 3, 3, 192, 128, True, None, None,
     "grid"),
    ("score_256_value_128_past_vmem_padded", 128, 2, 2, 256, 128, True,
     None, 0, "grid"),
])
def test_fwd_chooses_from_its_operands(rng, monkeypatch, name, s, h, kv, d,
                                       dv, causal, window, budget, path):
    """`_flash_forward` counts the kernel it took at trace time: the lane
    kernel for multi-head attention whose heads tile the lanes and for
    grouped-query attention whose K/V heads are whole lane blocks (heads
    of 128), the grid kernel for grouped-query heads of 64, head widths no
    lane block tiles, and a block past the VMEM budget; out and lse match
    the float32 reference either way, and the lane kernel's loop ran the
    K steps of the plan, once for the whole group. Values `dv` wide where
    the scores are `d` wide: counted as two widths only where the lane
    kernel took them apart ("two_widths"), and out is `dv` wide whichever
    way."""
    from tfde_tpu.ops.attention import grouped_attention

    if budget is not None:
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._FWD_KERNEL_VMEM_BUDGET", budget)
    two_widths = path == "two_widths"
    path = "lane" if two_widths else path
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kv, dv)), jnp.float32)
    out, lse, counts, bumped = _forward_and_path(q, k, v, causal, 64, 64,
                                                 window)
    assert counts["fwd_path"] == path
    assert bumped == {"fwd_lane_traces": int(path == "lane"),
                      "fwd_grid_traces": int(path == "grid"),
                      "fwd_two_width_traces": int(two_widths)}
    assert ("fwd_steps_executed" in counts) == (path == "lane")
    if path == "lane":
        assert counts["fwd_steps_executed"] == counts["fwd_visits"]
    assert out.shape == (1, s, h, dv) and lse.shape == (1, h, s)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(grouped_attention(
            q, k, v, causal=causal, window=window)),
        rtol=2e-5, atol=2e-5)
    # a query head h reads K/V head h // group: out and lse in that order
    want_out, want_lse = _reference_out_and_lse(
        q, np.repeat(k, h // kv, axis=2), np.repeat(v, h // kv, axis=2),
        causal, window, None, None)
    np.testing.assert_allclose(np.asarray(out, np.float64), want_out,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse, np.float64), want_lse,
                               rtol=2e-5, atol=2e-5)


def test_two_widths_give_what_the_padded_call_gives_to_the_bit(rng):
    """Only products with zeros go: values at their own width against the
    same values padded to the score width, through the same kernel."""
    q, k = (jnp.asarray(rng.standard_normal((1, 256, 2, 256)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.bfloat16)
    own, own_lse, _, bumped = _forward_and_path(q, k, v, True, 64, 128)
    wide, wide_lse, _, _ = _forward_and_path(
        q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 128),)), True, 64, 128)
    assert bumped["fwd_two_width_traces"] == 1
    assert own.shape == v.shape and wide.shape == q.shape
    np.testing.assert_array_equal(np.asarray(own, np.float32),
                                  np.asarray(wide[..., :128], np.float32))
    np.testing.assert_array_equal(np.asarray(wide[..., 128:], np.float32), 0)
    np.testing.assert_array_equal(np.asarray(own_lse), np.asarray(wide_lse))


def test_k_and_v_must_agree_in_all_but_their_width(rng):
    q, k, _ = _qkv(rng, s=128, h=2, d=64)
    for shape in ((2, 128, 1, 64), (2, 64, 2, 64), (1, 128, 2, 64)):
        with pytest.raises(ValueError, match="must match in batch, length"):
            flash_attention(q, k, jnp.zeros(shape, q.dtype), causal=True,
                            interpret=True)


@pytest.mark.parametrize("name,d,dv", [("lane_kernel_two_widths", 256, 128),
                                       ("padded_to_one_width", 64, 32)])
def test_gradient_through_unequal_widths_raises_by_name(rng, name, d, dv):
    """Only the forward takes two widths: no backward reads them, and a
    silently wrong gradient is the thing to rule out."""
    q, k = (jnp.asarray(rng.standard_normal((1, 128, 2, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 128, 2, dv)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    assert out.shape == v.shape
    with pytest.raises(NotImplementedError,
                       match="only the forward takes values of a width"):
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64,
            interpret=True).sum(), argnums=(0, 1, 2))(q, k, v)


#: sha256 of the Mosaic module the forward lowers to at the training
#: cells' shape ([2, 4096, 16, 64] bf16, causal, two heads of 64 a lane
#: block), without locations; taken on the tree before grouped-query heads
#: of 128 took the lane kernel (PR 35's parent)
_TRAINING_CELLS_FORWARD = ("f126b7619c156011bee783b575dd0b38"
                           "d0cdf5819b4bbe38e5de4fea39233433")


def test_the_training_cells_forward_lowers_to_the_module_it_lowered_to():
    """What the group adds to the lane kernel is decided while it is
    traced: at as many K/V heads as query heads the kernel Mosaic is
    handed is the same to the character."""
    import hashlib

    from test_moe import _mosaic_module

    q = jax.ShapeDtypeStruct((2, 4096, 16, 64), jnp.bfloat16)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True)).trace(q, q, q).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert hashlib.sha256(_mosaic_module(text).encode()).hexdigest() \
        == _TRAINING_CELLS_FORWARD


def test_fwd_leaves_what_does_not_fit_vmem_to_the_grid():
    """K and V of a head block whole in VMEM: the training cells' 4,096
    fit many times over, 131,072 bf16 positions do not."""
    from tfde_tpu.ops import flash_attention as fa

    assert fa._fwd_lane_vmem_bytes(4096, 2, 128, 2, 512, 512) \
        <= fa._FWD_KERNEL_VMEM_BUDGET // 4
    assert fa._fwd_lane_vmem_bytes(65536, 2, 128, 2, 512, 512) \
        <= fa._FWD_KERNEL_VMEM_BUDGET
    assert fa._fwd_lane_vmem_bytes(131072, 2, 128, 2, 512, 512) \
        > fa._FWD_KERNEL_VMEM_BUDGET
    assert fa._fwd_lane_vmem_bytes(65536, 2, 128, 4, 512, 512) \
        > fa._FWD_KERNEL_VMEM_BUDGET
    # grouped-query: one K/V head of 128 whole, seven query heads' tiles
    # and scores beside it (the window-and-global cell's longest wave)
    assert fa._fwd_lane_vmem_bytes(14336, 1, 128, 2, 512, 512, 7) \
        <= fa._FWD_KERNEL_VMEM_BUDGET // 2
    assert fa._fwd_lane_vmem_bytes(131072, 1, 128, 2, 512, 512, 7) \
        > fa._FWD_KERNEL_VMEM_BUDGET
    # values of a width of their own: the latent cell's longest wave keeps
    # K at 256 and V at 128 whole, 47 MB where both at 256 are 63, and
    # equal widths named twice count what one width counted
    resident = lambda wv: fa._fwd_lane_vmem_bytes(
        30720, 1, 256, 2, 512, 512, 1, wv) - fa._fwd_lane_vmem_bytes(
            0, 1, 256, 2, 512, 512, 1, wv)
    assert resident(256) == 2 * 2 * 30720 * 256 * 2
    assert resident(128) == 2 * 30720 * (256 + 128) * 2
    assert fa._fwd_lane_vmem_bytes(30720, 1, 256, 2, 512, 512, 1, 128) \
        < fa._fwd_lane_vmem_bytes(30720, 1, 256, 2, 512, 512) \
        == fa._fwd_lane_vmem_bytes(30720, 1, 256, 2, 512, 512, 1, 256) \
        <= fa._FWD_KERNEL_VMEM_BUDGET
    assert fa._fwd_lane_vmem_bytes(4096, 2, 128, 2, 512, 512, 1, 128) \
        == fa._fwd_lane_vmem_bytes(4096, 2, 128, 2, 512, 512)
    # a K of 256 that fits with V at 128 and not with V at 256
    assert fa._fwd_lane_vmem_bytes(57344, 1, 256, 2, 512, 512, 1, 128) \
        <= fa._FWD_KERNEL_VMEM_BUDGET \
        < fa._fwd_lane_vmem_bytes(57344, 1, 256, 2, 512, 512)


@pytest.mark.parametrize("s,bq,bk,causal,window", [
    (512, 64, 64, True, None), (512, 128, 64, True, None),
    (512, 64, 128, True, 100), (1024, 64, 64, True, 128),
    (2048, 256, 256, True, 1000), (512, 128, 64, False, None)])
def test_k_tile_range_is_the_band(s, bq, bk, causal, window):
    """The lane forward's loop bounds per Q tile enumerate
    `_band_tile_pairs`."""
    from tfde_tpu.ops import flash_attention as fa

    n_k = s // bk
    pairs = set()
    for qi in range(s // bq):
        lo, hi = fa._k_tile_range(qi, bq, bk, n_k, causal, window)
        pairs |= {(qi, kb) for kb in range(int(lo), int(hi) + 1)}
    assert pairs == set(fa._band_tile_pairs(s, bq, bk, causal, window))


@pytest.mark.parametrize("name,heads,causal,fwd_budget,bwd_budget,paths", [
    ("lane_into_fused", (4, 4, 64), True, None, None, ("lane", "kernel")),
    ("lane_into_pair_scan", (4, 4, 64), True, None, 0,
     ("lane", "recurrence")),
    ("lane_into_k_tile_scan", (4, 4, 64), False, None, None,
     ("lane", "recurrence")),
    ("grid_into_fused", (4, 4, 64), True, 0, None, ("grid", "kernel")),
    ("grid_into_pair_scan", (4, 4, 64), True, 0, 0, ("grid", "recurrence")),
    ("grouped_lane_into_pair_scan", (4, 2, 128), True, None, None,
     ("lane", "recurrence")),
    ("seven_over_one_lane_into_pair_scan", (7, 1, 128), True, None, None,
     ("lane", "recurrence")),
    ("grouped_lane_into_k_tile_scan", (4, 2, 128), False, None, None,
     ("lane", "recurrence")),
    ("grouped_grid_into_pair_scan", (4, 2, 128), True, 0, None,
     ("grid", "recurrence")),
])
@pytest.mark.parametrize("window,cap", [(None, None), (96, 20.0)],
                         ids=["plain", "window_and_cap"])
def test_gradients_through_each_forward_and_backward(
        rng, monkeypatch, name, heads, causal, fwd_budget, bwd_budget, paths,
        window, cap):
    """`jax.grad` of `flash_attention` against autodiff through the
    reference, for each forward handing its lse (rows or [B, H, S]) to
    each backward; under grouped-query (query heads, K/V heads, width) the
    recurrence reads the lane forward's rows through `_lse_bhs`, a K/V
    head's group side by side."""
    if not causal:
        window = None
    if fwd_budget is not None:
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._FWD_KERNEL_VMEM_BUDGET",
            fwd_budget)
    if bwd_budget is not None:
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET",
            bwd_budget)
    h, kv, d = heads
    q = jnp.asarray(rng.standard_normal((2, 256, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 256, kv, d)), jnp.float32)
            for _ in range(2))
    got, want, counts, _ = _grads_and_path(q, k, v, causal, 64, 128, window,
                                           cap)
    assert (counts["fwd_path"], counts["bwd_path"]) == paths
    for a, b in zip(got, want):
        assert _rel(a, b) <= 1e-5


@pytest.mark.parametrize("path", ["lane", "grid"])
def test_model_forward_bumps_the_lane_kernel_once_a_layer(rng, monkeypatch,
                                                          path):
    """Tracing a causal LM's step through attn_impl='flash' takes the lane
    forward in every layer and the grid forward in none; with no room in
    VMEM, the reverse."""
    from tfde_tpu.models.gpt import gpt_tiny_test
    from tfde_tpu.observability import counters

    if path == "grid":
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._FWD_KERNEL_VMEM_BUDGET", 0)
    model = gpt_tiny_test(attn_impl="flash")
    tokens = jnp.asarray(rng.integers(0, 97, size=(2, 64)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        return jnp.sum(model.apply({"params": p}, tokens, train=False) ** 2)

    before = counters.snapshot()
    jax.jit(jax.grad(loss)).lower(params)
    other = "grid" if path == "lane" else "lane"
    assert model.depth == 2
    assert _bumped(before, "fwd_lane_traces", "fwd_grid_traces") == {
        f"fwd_{path}_traces": 2, f"fwd_{other}_traces": 0}


def test_flash_dispatch_keeps_batch_sharded():
    """pallas_call under plain jit GATHERS sharded operands and replicates
    the kernel (silently destroying DP); the dispatcher must shard_map the
    flash path over the active mesh's batch axes instead — output stays
    batch-sharded and numerics match the reference."""
    import tfde_tpu.ops.attention as att
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tfde_tpu.parallel import axes as axes_lib
    from tfde_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 4}, jax.devices()[:4])
    rng = np.random.default_rng(0)
    q = jax.device_put(
        jnp.asarray(rng.standard_normal((8, 128, 2, 16)), jnp.float32),
        NamedSharding(mesh, P("data")),
    )

    @jax.jit
    def f(q):
        with axes_lib.use_axes(mesh):
            return att.attention(q, q, q, causal=True, impl="flash")

    out = f(q)
    assert out.sharding.spec == P("data"), out.sharding
    want = att.reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    # grads flow through the shard_map'd custom_vjp
    @jax.jit
    def loss(q):
        with axes_lib.use_axes(mesh):
            return jnp.sum(att.attention(q, q, q, causal=True,
                                         impl="flash") ** 2)

    g = jax.grad(loss)(q)
    g_ref = jax.grad(
        lambda q: jnp.sum(att.reference_attention(q, q, q, causal=True) ** 2)
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-3, atol=2e-4)
