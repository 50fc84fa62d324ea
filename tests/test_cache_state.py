"""The layers' descriptions of what they cache (models/cache_state.py),
held to the cache they describe, and the one ledger built from them
(observability/capacity.py `CapacityLedger`): which families of counters
a kind of model reports, and that two kinds in one model give both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_eva_attention import eva_model
from test_granite_hybrid import SSM, hybrid_model
from test_mla import latent_model
from test_qwen3_next import delta_model
from test_smallthinker import WINDOW, window_model
from tfde_tpu.inference import server
from tfde_tpu.inference.decode import _decode_clone, init_cache
from tfde_tpu.inference.server import ContinuousBatcher
from tfde_tpu.models.cache_state import CacheLayout, CacheState, layout_of
from tfde_tpu.models.gpt import gpt_tiny_test
from tfde_tpu.observability.capacity import (
    CapacityLedger,
    CapacityModel,
    kv_slab_bytes,
)

L = CapacityLedger
#: a ring in six layers of eight and a state in one of the other two
RING_AND_STATE = ("mamba",) + ("attention",) * 7


def ring_and_state_model(**kw):
    return window_model(mixers=RING_AND_STATE, ssm=SSM, **kw)


# name -> (model, how `init_cache` is asked for its cache, the kinds its
# layers are, the families of counters its batcher reports)
MODELS = {
    "slabs": (gpt_tiny_test, {}, {"kv"}, ()),
    "int8 slabs": (gpt_tiny_test, dict(kv_quant="int8"), {"kv"}, ()),
    "bfloat16 slabs": (lambda: gpt_tiny_test().clone(dtype=jnp.bfloat16),
                       {}, {"kv"}, ()),
    "ring": (window_model, dict(rolling=True), {"kv", "ring"},
             L.HYBRID_KEYS + L.RING_KEYS),
    "eva": (eva_model, {}, {"eva"}, L.EVA_KEYS),
    "state": (hybrid_model, {}, {"kv", "state"}, L.HYBRID_KEYS),
    "latent": (latent_model, {}, {"latent"},
               L.HYBRID_KEYS + L.LATENT_KEYS),
    "delta": (delta_model, {}, {"kv", "state"}, L.HYBRID_KEYS + L.GDN_KEYS),
    "bfloat16 delta": (lambda: delta_model(dtype=jnp.bfloat16), {},
                       {"kv", "state"}, L.HYBRID_KEYS + L.GDN_KEYS),
    "ring and state": (ring_and_state_model, dict(rolling=True),
                       {"kv", "ring", "state"},
                       L.HYBRID_KEYS + L.RING_KEYS),
}


@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("max_len", [48, 6])
def test_the_descriptions_are_the_cache_to_the_byte(name, max_len):
    """What the layers say a full row holds, times the rows, is what
    `init_cache` allocates: a module that caches another leaf, width or
    dtype without saying so fails here. At 6 positions no window of 8
    is left behind, so no layer rings."""
    build, how, kinds, _ = MODELS[name]
    model = build()
    clone = _decode_clone(model, **how)
    layout = layout_of(clone, max_len)
    assert len(layout.layers) == model.depth
    rings = max_len > WINDOW
    assert {s.kind for s in layout.layers} == (
        kinds if rings else kinds - {"ring"})
    assert layout.rings == (rings and "ring" in kinds)
    # the model not yet cloned is described as the batcher clones it
    assert layout_of(model, max_len).rings == layout.rings
    cache = init_cache(model, 3, max_len, **how)
    slab = kv_slab_bytes(cache)
    assert 3 * sum(s.row_bytes for s in layout.layers) == slab
    for s in layout.layers:
        assert s.held_bytes(max_len) <= s.row_bytes
        assert (s.not_by_position is None) == (s.kind in ("kv", "latent"))
    # and the ledger sums these and no arithmetic of its own: three full
    # rows hold the slab, but for the eva summaries a row never fills
    ledger = CapacityLedger(3, max_len, slab, layout.layers)
    assert 3 * ledger.row_bytes == slab
    used = ledger.observe([max_len] * 3, [1, 2, 3])["used_bytes"]
    assert used == 3 * sum(s.held_bytes(max_len) for s in layout.layers)
    assert "eva" in kinds or used == slab
    assert ledger.read_bytes([max_len, 1]) == sum(
        s.read_bytes(max_len) + s.read_bytes(1) for s in layout.layers)


def zero_params(model):
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), np.zeros((1, 8), np.int32)))["params"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


DENSE_KEYS = {"rounds", "generated", "tokens_per_round", "dispatches",
              "syncs", "dispatches_per_token", "syncs_per_token",
              *server._PHASE_KEYS}


@pytest.mark.parametrize("name", ["slabs", "eva", "state", "ring", "latent",
                                  "delta", "ring and state"])
def test_a_kind_of_model_reports_its_families_and_no_other(name):
    build, _, kinds, families = MODELS[name]
    model = build()
    srv = ContinuousBatcher(model, zero_params(model), batch_size=2,
                            max_len=48)
    assert srv._ledger.kinds == kinds
    assert set(srv.stats()) == DENSE_KEYS | set(families)
    assert all(srv.stats()[key] == 0 for key in families)


def test_a_ring_and_a_state_in_one_model_are_both_counted():
    """What `RingCapacityLedger(HybridCapacityLedger)` gave by inheritance,
    by composition: the state's bytes and the ring's cells."""
    model = ring_and_state_model()
    layout = layout_of(_decode_clone(model, rolling=True), 48)
    cache = init_cache(model, 2, 48, rolling=True)
    ledger = CapacityLedger(2, 48, kv_slab_bytes(cache), layout.layers)
    assert set(ledger.counters) == set(L.HYBRID_KEYS + L.RING_KEYS)
    cell = 2 * 2 * 16 * 4              # K and V of 2 heads of 16, float32
    state = 4 * 16 * 16 * 4 + 3 * 96 * 4
    # of the two layers without a window the first is the state's: one
    # state, one slab of 48, six rings of 8
    assert ledger.cells_per_row == 1 * 48 + 6 * WINDOW
    assert ledger.row_bytes == (48 + 6 * WINDOW) * cell + state
    assert ledger.row_cells(20) == 20 + 6 * WINDOW
    assert ledger.read_bytes([20]) == (20 + 6 * WINDOW) * cell + 2 * state
    ledger.note_scan([5, 20], 4)
    assert ledger.counters["ssm_state_bytes_touched"] == 4 * 2 * 2 * state
    assert ledger.counters["kv_full_cells_read"] == 4 * 25
    assert ledger.counters["kv_window_cells_read"] == 4 * 6 * (5 + 8)
    assert ledger.counters["kv_cells_read"] == 4 * (25 + 6 * 13)
    assert ledger.counters["kv_window_wraps"] == 1
    kv = ledger.observe([5, 20], [7, 8])
    assert kv["used_cells"] == 25 + 6 * 13
    assert kv["used_bytes"] == kv["used_cells"] * cell + 2 * state
    assert kv["allocated_bytes"] == 2 * ledger.row_bytes
    # what `/load` and the router's admission gate read counts tokens,
    # whatever the layers keep of one: a free row is 48 of them, and 0.76
    # of a row's bytes buy 36
    free = dict(kv, rows_free=1)
    assert CapacityModel(ledger, 0).headroom(free) == {
        "headroom_rows": 1, "headroom_tokens": 48}
    budget = int(kv["used_bytes"] + 0.76 * ledger.row_bytes)
    assert CapacityModel(ledger, budget).headroom(free) == {
        "headroom_rows": 0, "headroom_tokens": 36}
    # the first layer that is not a cell per position gives the refusal
    assert "Mamba2Mixer" in layout.not_by_position


def test_a_model_that_describes_nothing_is_slabs():
    class Plain:
        pass

    assert layout_of(Plain(), 32) == CacheLayout()
    assert CacheLayout().not_by_position is None and not CacheLayout().rings
    ledger = CapacityLedger(4, 32, 1024, CacheLayout().layers)
    assert ledger.kinds == {"kv"} and ledger.counters == {}
    # and so is a model whose layers all say they are slabs: a position of
    # all its layers is one cell, as `/load` counts tokens
    slabs = CapacityLedger(4, 32, 1024, [CacheState("kv", 32, 4)] * 2)
    assert slabs.cells_per_row == ledger.cells_per_row == 32
    assert slabs.cell_bytes == ledger.cell_bytes == 8.0
