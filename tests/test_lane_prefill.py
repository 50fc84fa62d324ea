"""Grouped-query heads of 128 take the lane flash forward in a long cold
wave (PR 35), in both configurations that serve such heads: the hybrid's
one attention layer in four (test_granite_hybrid.py's model) and the
window-and-global model's eight (test_smallthinker.py's)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_granite_hybrid as granite
import test_smallthinker as smallthinker
from teacher_forced import programs, served_logits
from tfde_tpu.models import transformer
from tfde_tpu.observability import counters
from tfde_tpu.ops import ssm as ssm_lib

CASES = {
    # two query heads of 128 over one K/V head
    "granite": dict(
        ref=granite.ref,
        dims=dict(granite.DIMS, hidden_size=256, num_attention_heads=2,
                  num_key_value_heads=1, mamba_n_heads=16),
        model=lambda: granite.hybrid_model(
            hidden_size=256, num_heads=2, num_kv_heads=1, attn_impl="flash",
            ssm=ssm_lib.SSMShape(heads=16, head_dim=16, state=16, groups=1,
                                 conv=4, chunk=granite.CHUNK)),
        params=lambda w, dims: granite.ref.to_program_params(w, dims),
        rolling=False, mutable=("cache",),
        lane_layers=granite.LAYERS.count("attention")),
    # four query heads of 128 over two K/V heads, the window of 8 across
    # the tiles' edges
    "smallthinker": dict(
        ref=smallthinker.ref,
        dims=dict(smallthinker.DIMS, head_dim=128),
        model=lambda: smallthinker.window_model(head_dim=128,
                                                attn_impl="flash"),
        params=lambda w, dims: smallthinker.ref.to_program_params(w),
        rolling=True, mutable=("cache", "counters"),
        lane_layers=len(smallthinker.LAYOUT)),
}


@pytest.mark.parametrize("name", CASES)
def test_a_long_prefill_of_heads_of_128_takes_the_lane_forward(monkeypatch,
                                                               name):
    """A cold wave of 384 positions (three tiles of 128) past
    `_PREFILL_SCORES_BYTES`: every attention layer traces the lane flash
    forward once and the grid forward never, and the wave and the steps
    after it serve the reference's logits."""
    case = CASES[name]
    ref, dims = case["ref"], case["dims"]
    weights = ref.make_weights(11, dims)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          case["params"](weights, dims))
    monkeypatch.setattr(transformer, "_PREFILL_SCORES_BYTES", 0)
    monkeypatch.setattr(transformer, "_PREFILL_QUERY_BLOCK", 128)
    rng = np.random.default_rng(5)
    rows = [rng.integers(0, dims["vocab_size"], n).astype(np.int32)
            for n in (300, 386)]
    lengths = [298, 384]
    before = counters.snapshot()
    with jax.default_matmul_precision("highest"):
        progs = programs(case["model"](), rolling=case["rolling"],
                         mutable=case["mutable"])
        got, _ = served_logits(progs, params, rows, lengths, 384, 400)
        traced = {k: counters.value(f"flash/{k}")
                  - before.get(f"flash/{k}", 0)
                  for k in ("fwd_lane_traces", "fwd_grid_traces")}
        assert traced == {"fwd_lane_traces": case["lane_layers"],
                          "fwd_grid_traces": 0}
        for row, n, logits in zip(rows, lengths, got):
            want = np.asarray(ref.forward(weights, jnp.asarray(row), dims))
            assert np.abs(logits - want[n - 1:]).max() < granite.TOL
