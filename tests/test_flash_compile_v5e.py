"""The flash kernels of the training cells, compiled for a described v5e at
the cells' widths: what Mosaic refuses (tiling, VMEM, a lowering it lacks)
shows here, with no chip. Nothing runs, so this says nothing about results
or times. All in this one file: the worker that gets it loads libtpu."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
    assert "flash_bwd" in text
    assert "while(" not in text
