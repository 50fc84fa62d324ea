"""Unit tests for bench.py's trust layer — the pure logic only (peak table,
gating, FLOP formulas, JSON salvage); the measurement paths run on the
chip and in TFDE_BENCH_SMOKE mode."""

import json

import pytest

import bench


def test_chip_peak_table_known_kinds():
    assert bench.chip_peak_flops("TPU v5 lite") == 197e12
    assert bench.chip_peak_flops("TPU v5e") == 197e12
    assert bench.chip_peak_flops("TPU v4") == 275e12
    assert bench.chip_peak_flops("TPU v6e") == 918e12


def test_chip_peak_unknown_kind_raises():
    """A device missing from the table is an error, never a default peak
    (a guessed peak turns every MFU into fiction)."""
    for kind in ("weird", "cpu", ""):
        with pytest.raises(ValueError, match="no peak"):
            bench.chip_peak_flops(kind)


def test_gate_withholds_impossible_numbers():
    """The round-2 failure mode (2531 TFLOPs on a 197-TFLOP chip) must be a
    refusal, not a headline."""
    r = {}
    assert not bench._gate(r, "bert", achieved=2531e12, peak=197e12)
    assert "withheld" in r["bert_error"]
    r2 = {}
    assert bench._gate(r2, "bert", achieved=88e12, peak=197e12)
    assert r2 == {}
    # 5% tolerance: just over peak passes (clock jitter), 6% over fails
    assert bench._gate({}, "x", 197e12 * 1.04, 197e12)
    assert not bench._gate({}, "x", 197e12 * 1.06, 197e12)


def test_bert_flops_formula_scales_correctly():
    f = bench.bert_train_flops_per_token
    base = f(768, 3072, 12, 512, 32768)
    # attention term is the only seq-dependent piece: doubling seq adds
    # exactly 3 * depth * 4 * seq * hidden
    assert f(768, 3072, 12, 1024, 32768) - base == 3 * 12 * 4 * 512 * 768
    # BERT-base fwd+bwd ~ 5.8 TFLOP at 8192 tokens/step (the sanity figure
    # VERDICT r2 quoted)
    assert 5e12 < base * 8192 < 7e12


def test_gpt_flops_formula_vs_bert():
    # GPT drops the MLM transform dense (2H^2) and counts causal attention
    # at the EXACT in-band figure — mean (S+1)/2 attended keys vs BERT's
    # bidirectional S (ops/roofline.py; the flash kernels skip future
    # tiles in forward AND backward, so counting full would inflate MFU)
    b = bench.bert_train_flops_per_token(768, 3072, 12, 512, 50257)
    g = bench.gpt_train_flops_per_token(768, 3072, 12, 512, 50257)
    attn_delta = 12 * (4 * 512 * 768 - 4 * 768 * (512 + 1) / 2)
    assert b - g == 3 * (2 * 768 * 768 + attn_delta)


def test_last_json_salvages_cumulative_lines():
    out = "\n".join([
        "some stderr-ish noise",
        json.dumps({"metric": "m", "value": 1, "partial": True}),
        "not json {",
        json.dumps({"metric": "m", "value": 2, "partial": True}),
    ])
    parsed = bench._last_json(out)
    assert parsed["value"] == 2
    assert bench._last_json("no json here") is None
    assert bench._last_json("") is None


def test_moe_flops_formula():
    """Routed FLOPs: k=1 with E tiny reduces to ~dense; k=2 on half the
    layers adds exactly n_moe * 3 * (4HF + 2HE) over dense."""
    h, f, d, s, v = 768, 3072, 12, 1024, 50257
    dense = bench.gpt_train_flops_per_token(h, f, d, s, v)
    moe = bench.moe_gpt_train_flops_per_token(h, f, d, s, v,
                                              num_experts=8,
                                              experts_per_token=2,
                                              moe_every=2)
    n_moe = d // 2
    assert moe - dense == 3 * n_moe * (4 * h * f + 2 * h * 8)


def test_bench_meta_structure(monkeypatch):
    """Every emitted line's provenance block: schema version, git sha,
    backend identity, and the active TFDE_* knob snapshot."""
    monkeypatch.setenv("TFDE_BENCH_SMOKE", "1")
    monkeypatch.setenv("TFDE_PROFILE", "every:100")
    meta = bench._bench_meta("tpu", "TPU v4", 4)
    assert meta["schema"] == bench.BENCH_SCHEMA_VERSION == 2
    assert meta["backend"] == {"platform": "tpu", "device_kind": "TPU v4",
                               "n_chips": 4}
    # this repo is a git checkout, so the sha must resolve here
    assert isinstance(meta["git_sha"], str) and len(meta["git_sha"]) == 40
    assert meta["knobs"]["TFDE_BENCH_SMOKE"] == "1"
    assert meta["knobs"]["TFDE_PROFILE"] == "every:100"
    assert all(k.startswith("TFDE_") for k in meta["knobs"])
    # without a backend identity the block is omitted
    assert "backend" not in bench._bench_meta()
