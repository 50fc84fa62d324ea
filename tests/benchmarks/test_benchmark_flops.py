"""The copied FLOP functions equal the program's at both configurations."""

import pytest

from benchmarks.lib import flops
from benchmarks.lib.manifest import Manifest

CONFIGS = {name: Manifest().config(name)
           for name in ("gpt2-medium-s4096", "gpt2-large-serve-1k")}


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("seq", [1024, 4096])
def test_train_flops_equal_bench_py(name, seq):
    import bench

    cfg = CONFIGS[name]
    hidden, depth, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    assert flops.gpt_train_flops_per_token(
        hidden, 4 * hidden, depth, seq, vocab
    ) == bench.gpt_train_flops_per_token(hidden, 4 * hidden, depth, seq, vocab)


@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("seq", [512, 4096])
def test_attention_flops_equal_ops_roofline(name, seq):
    from tfde_tpu.ops import roofline

    cfg = CONFIGS[name]
    hidden, depth = cfg["n_embd"], cfg["n_layer"]
    assert flops.mean_attended_keys(seq) == roofline.mean_attended_keys(seq)
    assert flops.mean_attended_keys(seq, causal=False) == \
        roofline.mean_attended_keys(seq, causal=False)
    assert depth * flops.attention_flops_per_token(hidden, seq) == \
        roofline.stacked_attention_flops_per_token(hidden, seq, depth)


def test_medium_s4096_is_2_72_gflop_a_token():
    cfg = CONFIGS["gpt2-medium-s4096"]
    per_token = flops.gpt_train_flops_per_token(
        cfg["n_embd"], 4 * cfg["n_embd"], cfg["n_layer"], 4096,
        cfg["vocab_size"])
    assert per_token == pytest.approx(2.72e9, rel=0.01)


def test_flash_forward_is_compute_bound_at_the_cell_s_shape():
    from benchmarks.lib.peaks import peaks_for

    peaks = peaks_for("TPU v5 lite")
    args = (2, 16, 4096, 64)
    by_flops = flops.flash_forward_flops(*args) / peaks["bf16_flops"]
    by_bytes = flops.flash_forward_bytes(*args) / peaks["hbm_bytes_per_s"]
    assert flops.flash_forward_flops(*args) == 2 * 4096 * 4 * 1024 * 2048.5
    assert by_flops > 4 * by_bytes
