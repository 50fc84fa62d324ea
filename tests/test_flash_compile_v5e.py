"""The flash kernels of the training cells, the hybrid's grouped matmul
(`ops/moe_gmm.py`) and the gated delta rule's two forms
(`ops/gated_delta.py`, plain XLA), compiled for a described v5e at the
cells' widths: what Mosaic or the compiler refuses (tiling, VMEM, a
lowering it lacks, temporaries of a wave's length) shows here, with no chip.
Nothing runs, so this says nothing about results or times. All in this one
file: the worker that gets it loads libtpu."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tfde_tpu.models import moe as moe_lib
from tfde_tpu.ops import gated_delta, moe_gmm
from tfde_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,shape,dtype,window,cap", [
    ("gpt2m_cell", (2, 4096, 16, 64), jnp.bfloat16, None, None),
    ("window_and_cap", (1, 4096, 8, 64), jnp.bfloat16, 1024, 30.0),
    ("one_head_of_128", (1, 2048, 4, 128), jnp.bfloat16, None, None),
    ("float32", (1, 2048, 4, 64), jnp.float32, None, None),
])
def test_flash_gradient_compiles_for_v5e(one_chip, name, shape, dtype,
                                         window, cap):
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              logit_cap=cap)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    # forward and the fused backward are Mosaic calls; no recurrence loop
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "while(" not in text
    # both kernels read the model's layout: no [B, H, S, D] array exists,
    # so nothing transposes or copies q, k, v or out into one
    b, s, h, d = shape
    assert f"[{b},{h},{s},{d}]" not in text


@pytest.mark.parametrize("name,shape,kv_heads,causal,window", [
    ("non_causal", (1, 4096, 4, 64), 4, False, None),
    ("four_heads_of_32", (1, 2048, 4, 32), 4, True, None),
    ("a_lane_block_of_64", (1, 2048, 2, 32), 2, True, None),
    ("heads_of_256", (1, 2048, 2, 256), 2, True, None),
    ("s2176_window_1000", (1, 2176, 4, 64), 4, True, 1000),
    ("s65536", (1, 65536, 2, 64), 2, True, None),
    ("grouped_query_grid", (1, 2048, 4, 64), 2, True, None),
    ("s131072_grid", (1, 131072, 2, 64), 2, True, None),
    ("window_layer_wave", (1, 14336, 28, 128), 4, True, 4096),
    ("global_layer_wave", (1, 14336, 28, 128), 4, True, None),
    ("two_rows_of_9216", (2, 9216, 28, 128), 4, True, None),
    ("hybrid_attention_wave", (1, 6144, 32, 128), 8, True, None),
    ("latent_wave_heads_of_256", (1, 30720, 8, 256), 8, True, None),
    ("gated_attention_wave_grid", (1, 30720, 16, 256), 2, True, None),
    ("gated_attention_short_wave", (1, 2048, 16, 256), 2, True, None),
])
def test_flash_forward_compiles_for_v5e(one_chip, name, shape, kv_heads,
                                        causal, window):
    """The forward alone, over operands the gradient cases above do not
    reach: each arrangement `_flash_forward` can choose is one Mosaic call
    the v5e compiler takes (lane widths under and over 128, K and V of a
    long sequence whole in VMEM, the window-and-global cell's seven query
    heads of 128 and the hybrid's four against the K/V head they share, in
    their longest waves, eight heads of 256 over the latent cell's longest
    wave, and the grid kernel where none of that holds)."""
    b, s, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window)).lower(
            q, k, k).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd" in text
    assert "while(" not in text
    # the grid kernel and the lane kernel's grouped-query form read the
    # [B, H, S, D] view; multi-head attention reads the model's layout
    head_major = name.endswith("_grid") or kv_heads < h
    assert (f"[{b},{h},{s},{d}]" in text) == head_major
    # the grid kernel's lse is a column a head, the lane kernel's rows
    assert (f"f32[{b},{h},{s},1]" in text) == name.endswith("_grid")


@pytest.mark.parametrize("name,s,heads,kv_heads", [
    ("latent_wave_of_6144", 6144, 8, 8),
    ("latent_wave_of_30720", 30720, 8, 8),
    ("grouped_query", 6144, 8, 2),
])
def test_two_width_forward_compiles_for_v5e(one_chip, name, s, heads,
                                            kv_heads):
    """A latent layer's chunk of eight heads as its waves hand it over:
    scores over 256 columns (192 padded), values of 128 as they are. One
    Mosaic call with V, out and the accumulator at 128: no padded copy of
    v and no slice of out exist around it."""
    on = lambda heads, width: jax.ShapeDtypeStruct(
        (1, s, heads, width), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True)).lower(
            on(heads, 256), on(kv_heads, 256), on(kv_heads, 128)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "flash_fwd" in text
    assert "while(" not in text and " pad(" not in text
    assert compiled.out_info.shape == (1, s, heads, 128)


#: (experts held, experts routed over, choices a token, hidden, expert
#: width, the gate's activation) of the three cells that run the kernel
_EXPERT_LAYERS = {"hybrid": (36, 72, 10, 4096, 768, "silu"),
                  "latent": (32, 128, 8, 4096, 2048, "silu"),
                  "window_and_global": (64, 64, 6, 2560, 768, "relu"),
                  "delta_rule_cell": (256, 512, 10, 2048, 512, "silu")}


@pytest.mark.parametrize("layer", sorted(_EXPERT_LAYERS))
@pytest.mark.parametrize("name,tokens", [("a_prefill_block", 2048),
                                         ("a_decode_tick", 32),
                                         ("a_long_waves_block", None)])
def test_moe_gmm_compiles_for_v5e(one_chip, name, tokens, layer):
    """The hybrid cell's expert layer: 36 held experts of 4096 x 768, ten
    choices a token over 72, SwiGLU; and the window-and-global cell's: all
    64 experts of 2560 x 768, six a token, ReGLU; and the latent cell's:
    32 held of 128 experts of 4096 x 2048, eight a token, SwiGLU, the
    widest it has met. An expert's three matrices, twice buffered, are 38
    MB of VMEM beside the row tiles (100 MB at 2,048): the kernel asks for
    its own limit, and the v5e compiler has to grant it. A long wave's
    block is what `moe.token_block` gives a call of 65,536 tokens: 8,192
    and a tile of 64 in the delta-rule cell, 4,096 and 128 in the latent
    and the window-and-global cell, 2,048 as before in the hybrid."""
    held, experts, k, d, f, act = _EXPERT_LAYERS[layer]
    if tokens is None:
        tokens = moe_lib.token_block(65536, k, experts, held, d, 2)
        assert (tokens, moe_gmm.tile_rows(tokens * k, experts)) == {
            "hybrid": (2048, 128), "latent": (4096, 128),
            "window_and_global": (4096, 128),
            "delta_rule_cell": (8192, 64)}[layer]
    pairs = tokens * k
    tile = moe_gmm.tile_rows(pairs, experts)
    tiles = moe_gmm.tiles_bound(pairs, held, tile)
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    text = moe_gmm.expert_mlps.lower(
        on((tiles * tile, d), jnp.bfloat16), on((held, d, f), jnp.bfloat16),
        on((held, d, f), jnp.bfloat16), on((held, f, d), jnp.bfloat16),
        on((tiles,), jnp.int32), on((1,), jnp.int32),
        tile=tile, act=act).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "moe_gmm" in text


#: the delta-rule cell's published widths: 16 key heads and 32 value heads
#: of 128, four taps, chunks of 64
_DELTA = gated_delta.GatedDeltaShape(key_heads=16, value_heads=32,
                                     key_dim=128, value_dim=128)


@pytest.mark.parametrize("name,rows,positions,temporaries_gib", [
    ("a_short_wave", 1, 2048, 0.5),
    ("two_rows_of_the_longest_bucket", 2, 30720, 2.0),
])
def test_delta_rule_prefill_compiles_for_v5e(one_chip, name, rows,
                                             positions, temporaries_gib):
    """The chunked form over a wave as the mixer hands it over (bfloat16
    q, k, v after the convolution and z, float32 beta and g, the norm and
    the gate inside the scan): the 64 x 64 unit triangular systems are
    inverted side by side on the vector unit, so the program holds no
    triangular solve and not the custom call that XLA expands one into, a
    system at a time,
    and no temporary of the wave's length beyond the output survives
    (float32 copies of q, k, v or o a wave long did, before the groups
    were cut where the arrays lie: 3.5 GiB at 30,720)."""
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    wave = (rows, positions)
    compiled = jax.jit(
        lambda qkv, z, beta, g, gain, state, lengths: gated_delta.prefill(
            qkv, beta, g, state, lengths, _DELTA, gate=(z, gain, 1e-6))
    ).lower(
        on(wave + (_DELTA.conv_channels,), jnp.bfloat16),
        on(wave + (_DELTA.value_width,), jnp.bfloat16),
        on(wave + (32,), jnp.float32), on(wave + (32,), jnp.float32),
        on((128,), jnp.float32), on((rows, 32, 128, 128), jnp.float32),
        on((rows,), jnp.int32)).compile()
    out, state = compiled.out_info
    assert out.shape == wave + (32, 128) and out.dtype == jnp.bfloat16
    assert state.shape == (rows, 32, 128, 128) and state.dtype == jnp.float32
    text = compiled.as_text()
    assert "triangular-solve" not in text
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < \
        temporaries_gib * 2 ** 30


def test_delta_rule_step_compiles_for_v5e(one_chip):
    """One tick of the cell's 48 rows: the state is read and written once
    (its 100 MB in and out, aliased or not) and nothing else of its size
    is laid out."""
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    compiled = jax.jit(
        lambda qkv, beta, g, state, live: gated_delta.decode_step(
            qkv, beta, g, state, live, _DELTA), donate_argnums=(3,)
    ).lower(
        on((48, _DELTA.conv_channels), jnp.bfloat16),
        on((48, 32), jnp.float32), on((48, 32), jnp.float32),
        on((48, 32, 128, 128), jnp.float32), on((48,), jnp.bool_)).compile()
    out, state = compiled.out_info
    assert out.shape == (48, 32, 128) and state.shape == (48, 32, 128, 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 48 * 2 ** 21
