"""Pipeline-parallelism tests: GPipe schedule equals sequential stage
application (forward + gradients), microbatch order preserved
(SURVEY.md §4 fake-device methodology)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from tfde_tpu.runtime.mesh import make_mesh


def _mesh(shape):
    import math

    n = math.prod(shape.values())
    return make_mesh(shape, jax.devices()[:n])


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stages(rng, s, d):
    return [
        {
            "w": jnp.asarray(rng.standard_normal((d, d)) * 0.5, jnp.float32),
            "b": jnp.asarray(rng.standard_normal((d,)) * 0.1, jnp.float32),
        }
        for _ in range(s)
    ]


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x)
    return x


@pytest.mark.parametrize("s,m", [(4, 6), (2, 2), (8, 8)])
def test_pipeline_matches_sequential(rng, s, m):
    mesh = _mesh({"pipe": s})
    d = 8
    stages = _stages(rng, s, d)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.standard_normal((m, 4, d)), jnp.float32)

    got = jax.jit(
        lambda p, x: pipeline_apply(_stage_fn, p, x, mesh)
    )(stacked, x)
    expect = jnp.stack([_sequential(stages, x[i]) for i in range(m)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential(rng):
    mesh = _mesh({"pipe": 4})
    d, m = 8, 6
    stages = _stages(rng, 4, d)
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.standard_normal((m, 4, d)), jnp.float32)

    def loss_pipe(p):
        return jnp.sum(pipeline_apply(_stage_fn, p, x, mesh) ** 2)

    def loss_seq(p):
        ys = jnp.stack([
            _sequential(
                [jax.tree_util.tree_map(lambda l: l[i], p) for i in range(4)],
                x[j],
            )
            for j in range(m)
        ])
        return jnp.sum(ys ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g_pipe, g_seq,
    )


def test_pipeline_requires_pipe_axis(rng):
    mesh = _mesh({"data": 8})
    stages = _stages(rng, 2, 4)
    with pytest.raises(ValueError, match="pipe"):
        pipeline_apply(
            _stage_fn, stack_stage_params(stages),
            jnp.zeros((2, 2, 4)), mesh,
        )


def test_pipeline_rejects_stage_count_mismatch(rng):
    """4 stacked stages on a 2-rank pipe must error, not silently skip
    stages (regression: shard_map would slice [4,...] to [2,...] and run
    stage2(stage0(x)))."""
    mesh = _mesh({"pipe": 2})
    stages = _stages(rng, 4, 4)
    with pytest.raises(ValueError, match="leading dim"):
        pipeline_apply(
            _stage_fn, stack_stage_params(stages), jnp.zeros((2, 2, 4)), mesh
        )


@pytest.mark.parametrize("s,m", [(2, 4), (4, 8)])
def test_pipeline_auto_mode_matches_sequential(rng, s, m):
    """mode='auto' (manual over 'pipe' only; data under the automatic
    partitioner) must equal the sequential stage application — same contract
    as the fully-manual mode."""
    mesh = _mesh({"data": 2, "pipe": s})
    stages = _stages(rng, s, 8)
    x = jnp.asarray(rng.standard_normal((m, 4, 8)), jnp.float32)
    got = jax.jit(
        lambda p, x: pipeline_apply(_stage_fn, p, x, mesh, mode="auto")
    )(stack_stage_params(stages), x)
    want = _sequential(stages, x.reshape(m * 4, 8)).reshape(m, 4, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_auto_mode_gradients_match_manual(rng):
    mesh = _mesh({"data": 2, "pipe": 2})
    stages = stack_stage_params(_stages(rng, 2, 8))
    x = jnp.asarray(rng.standard_normal((4, 4, 8)), jnp.float32)

    def loss(mode):
        def fn(p):
            return jnp.sum(pipeline_apply(_stage_fn, p, x, mesh, mode=mode) ** 2)
        return fn

    va, ga = jax.jit(jax.value_and_grad(loss("auto")))(stages)
    vm, gm = jax.jit(jax.value_and_grad(loss("manual")))(stages)
    np.testing.assert_allclose(float(va), float(vm), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        ga, gm,
    )
