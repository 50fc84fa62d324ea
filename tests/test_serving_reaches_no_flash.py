"""The serving programs never enter the flash kernels: `ContinuousBatcher`
builds its model with decode=True, whose attention is the cached-position
path (or ops/eva_attention.py), for prefill waves as for decode ticks. A
kernel PR's "the serve cells run the parent's programs" rests on this, so
it is pinned on the path, not on a length threshold: the dispatcher is told
it is on a TPU, the flash entry raises, and once more with the threshold
lowered under the prefill bucket."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tfde_tpu.ops.attention as att
from tfde_tpu.inference.server import ContinuousBatcher
from tfde_tpu.models.gpt import GPT


def _dense():
    return GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4,
               mlp_dim=64, max_position=256, dtype=jnp.float32)


def _eva():
    return GPT(vocab_size=320, hidden_size=64, depth=2, num_heads=4,
               mlp_dim=160, max_position=4096, dtype=jnp.float32,
               position="rope", rope_theta=1e5, norm="rms",
               norm_unit_offset=True, ln_eps=1e-5, mlp_act="swiglu",
               use_bias=False, tie_embeddings=False, attention="eva",
               eva_window=32, eva_chunk=4, fp32_residual=True)


@pytest.mark.parametrize("min_seq", [None, 128],
                         ids=["threshold_as_is", "threshold_128"])
@pytest.mark.parametrize("build", [_dense, _eva], ids=["gpt", "eva"])
def test_batcher_serves_without_entering_flash(monkeypatch, build, min_seq):
    def entered(*args, **kwargs):
        raise AssertionError("a serving program entered _flash_sharded")

    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    monkeypatch.setattr(att, "_flash_sharded", entered)
    if min_seq is not None:
        monkeypatch.setattr(att, "_flash_min_seq", lambda causal: min_seq)
    model = build()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=256,
                            scan_depth=4, prompt_buckets=(128, 256))
    rng = np.random.default_rng(3)
    # a prefill wave at the largest bucket, then a decode scan of full depth
    prompts = [rng.integers(0, model.vocab_size, n).astype(np.int64)
               for n in (200, 150)]
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    done = dict(srv.run())
    assert [done[r].size for r in rids] == [6, 6]
    stats = srv.stats()
    assert stats["prefill_waves"] >= 1 and stats["scans"] >= 1
    assert stats["prefill_cells"] >= 256
    if min_seq is not None and build is _dense:
        # the pin bites: the same model outside decode mode, at the same
        # length, does go to the flash entry under these patches
        with pytest.raises(AssertionError, match="_flash_sharded"):
            model.apply({"params": params}, jnp.zeros((1, 256), jnp.int32))
