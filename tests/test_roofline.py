"""Roofline accounting (ops/roofline.py): the analytic attention-flop
model that MFU is credited with, and the tile-visit pins proving the
flash forward AND backward execute only in-band tiles — the acceptance
gate for the tile-skipping backward (visits <= O(S * window / block^2)
per Q tile for both the dq and dk/dv passes).

Oracle chain: brute-force position loops -> closed-form flop model ->
static tile plan (same predicate the kernels branch on) -> interpret-mode
traced visit counts -> runtime-executed scan steps."""

import numpy as np
import pytest

from tfde_tpu.ops import flash_attention as fa
from tfde_tpu.ops import roofline as rl


# ---------------------------------------------------------------- flop model


def test_mean_attended_keys_bidirectional_is_full():
    assert rl.mean_attended_keys(512, causal=False) == 512.0
    assert rl.mean_attended_keys(512, causal=False, window=9999) == 512.0


def test_mean_attended_keys_causal_is_exact_triangle():
    # query i attends i+1 keys; the model must be the EXACT mean, not S/2
    for s in (1, 7, 64, 4096):
        brute = sum(i + 1 for i in range(s)) / s
        assert rl.mean_attended_keys(s, causal=True) == pytest.approx(brute)
    assert rl.mean_attended_keys(4096) == 4097 / 2


@pytest.mark.parametrize("s,w", [(37, 5), (64, 64), (256, 1), (512, 128)])
def test_mean_attended_keys_windowed_matches_brute_force(s, w):
    brute = sum(min(i + 1, w) for i in range(s)) / s
    assert rl.mean_attended_keys(s, True, w) == pytest.approx(brute)


def test_mean_attended_keys_window_geq_seq_is_plain_causal():
    assert rl.mean_attended_keys(64, True, 1000) == \
        rl.mean_attended_keys(64, True)


def test_mean_attended_keys_rejects_bad_window():
    with pytest.raises(ValueError, match="window"):
        rl.mean_attended_keys(64, True, 0)


def test_attention_flops_per_token_is_4_width_meankeys():
    assert rl.attention_flops_per_token(768, 512, causal=False) \
        == 4.0 * 768 * 512
    assert rl.attention_flops_per_token(768, 512, causal=True) \
        == pytest.approx(4.0 * 768 * 513 / 2)


def test_stacked_alternate_windows_even_layers_only():
    # transformer.Encoder 'alternate': even block indices banded -> with
    # depth=3 that is layers {0, 2}, i.e. ceil(depth/2) banded layers
    full = rl.attention_flops_per_token(64, 256, True, None)
    band = rl.attention_flops_per_token(64, 256, True, 32)
    got = rl.stacked_attention_flops_per_token(64, 256, 3, True, 32,
                                               "alternate")
    assert got == pytest.approx(2 * band + 1 * full)
    assert rl.stacked_attention_flops_per_token(
        64, 256, 4, True, 32, "all") == pytest.approx(4 * band)
    # no window -> pattern is irrelevant, every layer full
    assert rl.stacked_attention_flops_per_token(
        64, 256, 4, True, None, "alternate") == pytest.approx(4 * full)


def test_stacked_rejects_unknown_pattern():
    with pytest.raises(ValueError, match="window_pattern"):
        rl.stacked_attention_flops_per_token(64, 256, 2,
                                             window_pattern="every_third")


def test_bench_flop_model_credits_windowed_configs():
    """gpt_train_flops_per_token must charge windowed/alternate configs
    their true in-band work (the MFU denominator), and the delta from
    plain causal must be exactly the attention term."""
    h, m, d, s, v = 768, 3072, 12, 4096, 50257
    full = rl.gpt_train_flops_per_token(h, m, d, s, v)
    alt = rl.gpt_train_flops_per_token(h, m, d, s, v, window=1024,
                                       window_pattern="alternate")
    allw = rl.gpt_train_flops_per_token(h, m, d, s, v, window=1024,
                                        window_pattern="all")
    assert allw < alt < full
    want_delta = 3.0 * (
        rl.stacked_attention_flops_per_token(h, s, d, True)
        - rl.stacked_attention_flops_per_token(h, s, d, True, 1024,
                                               "alternate")
    )
    assert full - alt == pytest.approx(want_delta)


# ------------------------------------------------------------ static plan


def test_static_causal_plan_is_exact_triangle():
    plan = rl.tile_visits(512, 64, 64, causal=True)
    n = 512 // 64
    assert plan["fwd"] == n * (n + 1) // 2 == 36
    assert plan["bwd_dq"] == plan["bwd_dkv"] == plan["fwd"]
    assert plan["grid"] == n * n


def test_static_windowed_plan_respects_band_ceiling():
    """The acceptance bound: per Q tile, at most O(window / block) K tiles
    (window/block in-band plus diagonal/partial straddles) for BOTH
    backward passes — and the total is far below the causal triangle."""
    s, b, w = 1024, 64, 128
    plan = rl.tile_visits(s, b, b, causal=True, window=w)
    ceiling = rl.max_band_tiles_per_q_tile(b, b, w)
    n_q = s // b
    assert plan["max_visits_per_q_tile"] <= ceiling
    assert plan["bwd_dq"] <= n_q * ceiling
    assert plan["bwd_dkv"] <= n_q * ceiling
    causal_full = rl.tile_visits(s, b, b, causal=True)["fwd"]
    assert plan["fwd"] < causal_full / 2  # 46 visits vs the 136 triangle


def test_band_pairs_match_positionwise_brute_force():
    """The tile predicate against the mask semantics themselves: a tile is
    in-band iff it contains at least one (row, col) with row >= col and
    row - col < window. Asymmetric block sizes on purpose."""
    s, bq, bk, w = 256, 64, 32, 48
    rows = np.arange(s)[:, None]
    cols = np.arange(s)[None, :]
    band = (rows >= cols) & (rows - cols < w)
    live = band.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
    brute = {(qi, kb) for qi, kb in zip(*np.nonzero(live))}
    plan = fa.bwd_tile_plan(s, bq, bk, causal=True, window=w)
    assert {tuple(p) for p in plan["pairs"]} == brute
    assert plan["visits"] == len(brute)


# ------------------------------------- traced + runtime-executed schedule


def test_measured_visits_match_plan_causal():
    st = rl.tile_visits(256, 64, 64, causal=True)
    m = rl.measured_tile_visits(seq=256, block_q=64, block_k=64)
    assert m["fwd_visits"] == st["fwd"]
    assert m["bwd_dq_visits"] == st["bwd_dq"]
    assert m["bwd_dkv_visits"] == st["bwd_dkv"]
    # the scan genuinely RAN only the in-band steps (runtime counter
    # bumped from inside the backward's scan body)
    assert m["bwd_steps_executed"] == st["bwd_dq"]


def test_measured_windowed_backward_skips_out_of_band_tiles():
    """The tentpole claim, asserted end to end: with a window the backward
    executes only O(S * window / block^2) tile visits — strictly fewer
    than the causal triangle — and the runtime-executed count agrees.
    Softcap on, so the capped kernels keep the same schedule."""
    s, b, w = 512, 64, 128
    st = rl.tile_visits(s, b, b, causal=True, window=w)
    m = rl.measured_tile_visits(seq=s, block_q=b, block_k=b, window=w,
                                logit_cap=50.0)
    n_q = s // b
    ceiling = rl.max_band_tiles_per_q_tile(b, b, w)
    triangle = n_q * (n_q + 1) // 2
    for key in ("bwd_dq", "bwd_dkv"):
        assert m[f"{key}_visits"] == st[key]
        assert st[key] <= n_q * ceiling < triangle
    assert m["fwd_visits"] == st["fwd"]
    assert m["bwd_steps_executed"] == st["bwd_dq"]


@pytest.mark.parametrize("bwd", ["kernel", "recurrence"])
def test_measured_backward_visits_match_plan(monkeypatch, bwd):
    """The fused kernel walks the same band as the recurrence: the traced
    visit counts equal the plan, and so do the pairs its in-kernel loop
    ran."""
    if bwd == "recurrence":  # nothing fits: what `_bwd` observes
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._BWD_KERNEL_VMEM_BUDGET", 0)
    st = rl.tile_visits(256, 64, 64, causal=True, window=64)
    m = rl.measured_tile_visits(seq=256, block_q=64, block_k=64, window=64)
    assert m["bwd_path"] == bwd
    assert m["bwd_dq_visits"] == st["bwd_dq"]
    assert m["bwd_dkv_visits"] == st["bwd_dkv"]
    assert m["bwd_steps_executed"] == st["bwd_dq"]


@pytest.mark.parametrize("fwd", ["lane", "grid"])
@pytest.mark.parametrize("window", [None, 64, 200])
def test_measured_forward_visits_match_plan(monkeypatch, fwd, window):
    """The lane forward walks the band the grid kernel predicates: the
    traced visit count equals the plan either way, and the K steps the
    lane kernel's loop ran for one block of heads equal it too (the grid
    kernel has no loop to count)."""
    if fwd == "grid":  # nothing fits: what `_flash_forward` observes
        monkeypatch.setattr(
            "tfde_tpu.ops.flash_attention._FWD_KERNEL_VMEM_BUDGET", 0)
    st = rl.tile_visits(256, 64, 64, causal=True, window=window)
    m = rl.measured_tile_visits(seq=256, block_q=64, block_k=64,
                                window=window)
    assert m["fwd_path"] == fwd
    assert m["fwd_visits"] == st["fwd"]
    assert m.get("fwd_steps_executed") == (st["fwd"] if fwd == "lane"
                                           else None)


def test_check_tile_visits_gate_passes():
    """The tile-visit gate, whole (covers the GQA head-folded case
    too)."""
    assert rl.check_tile_visits() == []
