"""The expert layer's three grouped matmuls as one Mosaic kernel.

`models/moe.py` routes without a capacity by laying the (token, choice)
pairs of a block out by expert, each expert's run starting at a multiple
of `tile` rows, so that a tile of rows belongs to ONE expert. The kernel
(`pallas_call(name="moe_gmm")`) walks the tiles in use: for a tile of rows
x of expert e it computes `(act(x Wg[e]) * (x W1[e])) W2[e]` (`act` is
silu, SwiGLU, or relu, ReGLU: a static parameter of the one kernel),
bfloat16 (the layer's dtype) operands, float32 accumulation in all three products, the
gated product in float32 before its cast: the arithmetic of three grouped
matmuls, without the two float32 round trips through memory between them.

Which expert a tile belongs to and how many tiles are in use are scalars
the kernel is handed before it starts (`PrefetchScalarGridSpec`): they
choose the weight blocks, so an expert's three matrices are fetched once
for its run of tiles and an expert with no pair is never read; a tile past
the last one in use maps onto that one's blocks (nothing is fetched) and
is skipped. The pad rows that complete an expert's last tile are
multiplied like the others and read by nobody.

The tile follows the block (`tile_rows`), and the caller chooses the block
so that an expert's even share of its pairs reaches `ROWS_A_FETCH` where
the sorted copy's bytes allow (`models/moe.py::token_block`): 2,048 tokens
of ten choices over 72 experts give an expert 284 rows (tile 128), 4,096
of six over 64 give 384 and of eight over 128 give 256 (tile 128), and
8,192 of ten over 512 give 160 (tile 64, the byte budget's block). A
decode tick gives an expert a handful, and the kernel is bound there by
fetching the weights whatever the tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows an expert needs for one fetch of its three matrices before the
#: arithmetic and not the fetch sets the time: the matrices are 6 d f bytes
#: in bfloat16 and a row costs 6 d f FLOP, so the rows a fetch are the
#: chip's FLOP a byte whatever d and f; a v5e does 197 TFLOP/s over
#: 819 GB/s = 240, and 256 is the power of two above it
ROWS_A_FETCH = 256
#: the largest tile: an expert with that many rows has paid for its
#: fetch, and a larger tile only adds pads (half a tile an expert)
_TILE_MAX = ROWS_A_FETCH
#: the smallest: one packed bfloat16 sublane group
_TILE_MIN = 16


def tile_rows(pairs: int, num_experts: int) -> int:
    """Rows of a tile for a block of `pairs` (token, choice) pairs routed
    over `num_experts`: the largest power of two no larger than half an
    expert's even share, within [16, 256]."""
    share = max(pairs // max(num_experts, 1) // 2, 1)
    return max(_TILE_MIN, min(_TILE_MAX, 1 << (share.bit_length() - 1)))


def tiles_bound(pairs: int, held: int, tile: int) -> int:
    """Tiles that hold any split of at most `pairs` rows into `held` runs,
    each run starting on a tile."""
    return pairs // tile + held


#: the gate's activation by name: 'silu' (SwiGLU) | 'relu' (ReGLU)
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _kernel(group_ref, live_ref, x_ref, wg_ref, w1_ref, w2_ref, o_ref, *,
            act):
    del group_ref

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        h = (act(gate) * up).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, w2_ref[...], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def _vmem_bytes(tile: int, d: int, f: int, itemsize: int) -> int:
    """Two buffers of the three matrices and of the row tiles in and out,
    the float32 intermediates, and room for the compiler's own."""
    weights = 2 * 3 * d * f * itemsize
    tiles = 2 * 2 * tile * d * itemsize
    scratch = tile * (3 * f + d) * 4
    return weights + tiles + scratch + (8 << 20)


@functools.partial(jax.jit, static_argnames=("tile", "act", "interpret"))
def expert_mlps(rows, wg, w1, w2, tile_group, live, *, tile: int,
                act: str = "silu", interpret: bool = False):
    """rows [T * tile, d], tile t of them belonging to expert
    `tile_group[t]` for t < `live` ([1] int32); wg / w1 [E, d, f], w2
    [E, f, d]; `act` names the gate's activation (`ACTS`). Returns
    [T * tile, d]: the gated MLP of its tile's expert for every row of a
    tile in use, undefined rows beyond."""
    if act not in ACTS:
        raise ValueError(f"act must be one of {sorted(ACTS)}, got {act!r}")
    m, d = rows.shape
    f = wg.shape[-1]
    tiles = m // tile
    at = lambda t, live: jnp.maximum(jnp.minimum(t, live[0] - 1), 0)
    row_spec = pl.BlockSpec((tile, d), lambda t, g, n: (at(t, n), 0))
    expert = lambda t, g, n: (g[at(t, n)], 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, act=ACTS[act]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[row_spec,
                      pl.BlockSpec((None, d, f), expert),
                      pl.BlockSpec((None, d, f), expert),
                      pl.BlockSpec((None, f, d), expert)],
            out_specs=row_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(tile, d, f, rows.dtype.itemsize)),
        interpret=interpret,
        name="moe_gmm",
    )(tile_group, live, rows, wg, w1, w2)
