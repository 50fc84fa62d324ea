"""Pipeline-parallel LM tests: the pipelined execution path must be
numerically identical to (a) the sequential scan fallback and (b) plain DP
training — the TPU-native analog of the reference's requirement that a
distribution strategy not change the math (SURVEY.md §2c; VERDICT round-1
item 3: "test training a small GPT at pipe=2 to DP-identical numerics")."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.models.gpt import next_token_loss
from tfde_tpu.models.pipelined import PipelinedLM, pipelined_tiny_test
from tfde_tpu.parallel.strategies import (
    MultiWorkerMirroredStrategy,
    PipelineParallelStrategy,
)
from tfde_tpu.runtime.mesh import make_mesh
from tfde_tpu.training.step import init_state, make_custom_train_step


@pytest.fixture(scope="module")
def model():
    return pipelined_tiny_test()


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    return rng.integers(0, 97, (16, 32)).astype(np.int32)


def test_pipelined_forward_matches_sequential(model, tokens):
    """Same params, same tokens: pipe=2 logits == no-mesh sequential logits."""
    from tfde_tpu.parallel import axes as axes_lib

    variables = model.init(jax.random.key(0), tokens)
    seq_logits = jax.jit(
        lambda v, t: model.apply(v, t)
    )(variables, tokens)

    mesh = make_mesh({"data": 2, "pipe": 2}, jax.devices()[:4])

    def pipe_forward(v, t):
        with axes_lib.use_axes(mesh):
            return model.apply(v, t)

    pipe_logits = jax.jit(pipe_forward)(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(pipe_logits), np.asarray(seq_logits), rtol=1e-4, atol=1e-5
    )


def test_pipelined_train_matches_dp(model, tokens):
    """5 AdamW steps at pipe=2 x data=2 == 5 steps at data=4 (exact math,
    fp32 tolerance)."""
    strat_p = PipelineParallelStrategy(data=2, pipe=2)
    state_p, _ = init_state(model, optax.adam(1e-3), strat_p, tokens)
    step_p = make_custom_train_step(strat_p, state_p, next_token_loss,
                                    donate=False)

    strat_d = MultiWorkerMirroredStrategy(
        make_mesh({"data": 4}, jax.devices()[:4])
    )
    state_d, _ = init_state(model, optax.adam(1e-3), strat_d, tokens)
    step_d = make_custom_train_step(strat_d, state_d, next_token_loss,
                                    donate=False)

    rng = jax.random.key(0)
    for _ in range(5):
        state_p, m_p = step_p(state_p, (tokens,), rng)
        state_d, m_d = step_d(state_d, (tokens,), rng)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_d["loss"]), rtol=2e-5
    )
    assert float(m_p["loss"]) < 4.6  # loss actually moved off init (~ln 97)


def test_stage_params_sharded_over_pipe(model, tokens):
    """Each pipe rank must hold only its stage's weights — the memory point
    of pipelining (round-1 VERDICT: replicated microbatches/stages defeat
    it)."""
    strat = PipelineParallelStrategy(data=2, pipe=2)
    state, _ = init_state(model, optax.adam(1e-3), strat, tokens)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        state.params["stages"]
    ):
        spec = leaf.sharding.spec
        assert spec and spec[0] == "pipe", (
            f"stage leaf {jax.tree_util.keystr(path)} not sharded over "
            f"'pipe': {spec}"
        )
    # embedding + head stay replicated
    assert state.params["wte"].sharding.spec == ()
    # optimizer state follows params: stage moments sharded too
    mu = state.opt_state[0].mu["stages"]
    leaf = jax.tree_util.tree_leaves(mu)[0]
    assert leaf.sharding.spec[0] == "pipe"


def test_microbatch_divisibility_error(model):
    strat = PipelineParallelStrategy(data=1, pipe=2)
    bad = np.zeros((6, 32), np.int32)  # 6 % microbatches(4) != 0
    state, _ = init_state(model, optax.adam(1e-3), strat,
                          np.zeros((8, 32), np.int32))
    step = make_custom_train_step(strat, state, next_token_loss, donate=False)
    with pytest.raises(ValueError, match="microbatches"):
        step(state, (bad,), jax.random.key(0))


def test_pipelined_respects_max_position(model):
    too_long = np.zeros((8, 128), np.int32)
    variables = model.init(jax.random.key(0), np.zeros((8, 32), np.int32))
    with pytest.raises(ValueError, match="max_position"):
        model.apply(variables, too_long)


def test_loss_reduce_path_matches_broadcast_path(model, tokens):
    """loss_and_metrics (last-stage reduction, 3-scalar psum) must equal the
    full-logit broadcast path's next_token_loss — values AND grads."""
    from tfde_tpu.parallel import axes as axes_lib

    variables = model.init(jax.random.key(0), tokens)
    mesh = make_mesh({"data": 2, "pipe": 2}, jax.devices()[:4])

    def loss_reduce(params):
        with axes_lib.use_axes(mesh):
            loss, _ = model.loss_and_metrics({"params": params}, tokens)
        return loss

    def loss_broadcast(params):
        from tfde_tpu.ops.losses import masked_lm_loss

        with axes_lib.use_axes(mesh):
            logits = model.apply({"params": params}, tokens)
        loss, _ = masked_lm_loss(
            logits[:, :-1], tokens[:, 1:].astype(jnp.int32)
        )
        return loss

    v_r, g_r = jax.jit(jax.value_and_grad(loss_reduce))(variables["params"])
    v_b, g_b = jax.jit(jax.value_and_grad(loss_broadcast))(variables["params"])
    np.testing.assert_allclose(float(v_r), float(v_b), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        ),
        g_r, g_b,
    )


@pytest.mark.slow
def test_pipelined_train_reduce_path_matches_dp(model, tokens):
    """Training through pipelined_next_token_loss (last-stage reduction) at
    pipe=2 x data=2 == plain DP at data=4 — the VERDICT r2 #9 'done' bar."""
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    strat_p = PipelineParallelStrategy(data=2, pipe=2)
    state_p, _ = init_state(model, optax.adam(1e-3), strat_p, tokens)
    step_p = make_custom_train_step(strat_p, state_p, pipelined_next_token_loss,
                                    donate=False)

    strat_d = MultiWorkerMirroredStrategy(
        make_mesh({"data": 4}, jax.devices()[:4])
    )
    state_d, _ = init_state(model, optax.adam(1e-3), strat_d, tokens)
    step_d = make_custom_train_step(strat_d, state_d, next_token_loss,
                                    donate=False)

    rng = jax.random.key(0)
    for _ in range(5):
        state_p, m_p = step_p(state_p, (tokens,), rng)
        state_d, m_d = step_d(state_d, (tokens,), rng)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_d["loss"]), rtol=2e-5
    )


@pytest.mark.slow
def test_pipelined_dropout_in_pipe(tokens):
    """Dropout on (VERDICT r2 weak #8 capability cliff closed): the pipe
    path fires dropout deterministically per seed, with masks UNCORRELATED
    across microbatches and data shards (a naive per-shard mask from one key
    would silently repeat across shards). Exact-numerics parity tests stay
    at dropout 0, like every framework's."""
    from tfde_tpu.parallel import axes as axes_lib

    model = pipelined_tiny_test(dropout_rate=0.5)
    mesh = make_mesh({"data": 2, "pipe": 2}, jax.devices()[:4])
    # identical rows: output rows can only differ through dropout masks
    one_row = tokens[:1]
    same = np.broadcast_to(one_row, tokens.shape).copy()
    variables = model.init(jax.random.key(0), same)
    rngs = {"dropout": jax.random.key(7)}

    def pipe_forward(v, t, r):
        with axes_lib.use_axes(mesh):
            return model.apply(v, t, train=True, rngs=r)

    pipe_fn = jax.jit(pipe_forward)
    a = np.asarray(pipe_fn(variables, same, rngs))
    # deterministic per seed
    b = np.asarray(pipe_fn(variables, same, rngs))
    np.testing.assert_array_equal(a, b)
    # different seed -> different masks
    c = np.asarray(pipe_fn(variables, same, {"dropout": jax.random.key(8)}))
    assert not np.allclose(a, c, atol=1e-3)
    # eval mode (no dropout) differs from train mode
    with axes_lib.use_axes(mesh):
        ev = np.asarray(model.apply(variables, same))
    assert not np.allclose(a, ev, atol=1e-3)
    # no two example rows share a mask: identical inputs, all outputs
    # pairwise distinct across microbatches AND data shards
    rows = a.reshape(a.shape[0], -1)
    for i in range(rows.shape[0]):
        for j in range(i + 1, rows.shape[0]):
            assert not np.allclose(rows[i], rows[j], atol=1e-5), (i, j)
    # the reduce-path loss trains with dropout too (smoke)
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    strat = PipelineParallelStrategy(data=2, pipe=2)
    state, _ = init_state(model, optax.adam(1e-3), strat, tokens)
    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    state, m = step(state, (tokens,), jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_3d_dp_pp_tp_matches_dp(model, tokens):
    """3D parallelism (dp=2 x pipe=2 x tensor=2, 8 devices): stage weights
    shard over BOTH 'pipe' (stage dim) and 'tensor' (Megatron column/row
    dims), the pipe runs in partial-manual mode, and 5 training steps match
    plain dp=4 numerics — parallelism is layout, never math."""
    from jax.sharding import PartitionSpec as P

    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    strat3d = PipelineParallelStrategy(data=2, pipe=2, tensor=2)
    state3, _ = init_state(model, optax.adam(1e-3), strat3d, tokens)

    # qkv kernel [S, L, embed, heads, hd]: pipe on the stage dim, tensor on
    # heads; fc2 kernel [S, L, ffn, embed]: tensor on ffn (row-parallel)
    qkv = state3.params["stages"]["attn"]["query"]["kernel"]
    assert qkv.sharding.spec == P("pipe", None, None, "tensor", None)
    fc2 = state3.params["stages"]["mlp"]["fc2"]["kernel"]
    assert fc2.sharding.spec == P("pipe", None, "tensor", None)
    # Adam moments follow
    mu_qkv = state3.opt_state[0].mu["stages"]["attn"]["query"]["kernel"]
    assert mu_qkv.sharding.spec == P("pipe", None, None, "tensor", None)

    step3 = make_custom_train_step(strat3d, state3, pipelined_next_token_loss,
                                   donate=False)
    strat_d = MultiWorkerMirroredStrategy(
        make_mesh({"data": 4}, jax.devices()[:4])
    )
    state_d, _ = init_state(model, optax.adam(1e-3), strat_d, tokens)
    step_d = make_custom_train_step(strat_d, state_d, next_token_loss,
                                    donate=False)

    rng = jax.random.key(0)
    for _ in range(5):
        state3, m3 = step3(state3, (tokens,), rng)
        state_d, m_d = step_d(state_d, (tokens,), rng)
    np.testing.assert_allclose(
        float(m3["loss"]), float(m_d["loss"]), rtol=5e-5
    )
    assert float(m3["loss"]) < 4.6  # moved off init (~ln 97)


def test_tensor_without_pipe_rejected():
    """tensor>1 with pipe<=1 would silently replicate everything across the
    tensor devices — must be a loud error."""
    strat = PipelineParallelStrategy(data=2, pipe=1, tensor=2)
    with pytest.raises(ValueError, match="tensor"):
        strat.params_spec({"stages": {"w": jnp.zeros((1, 2, 4, 4))}})


def test_3d_with_dropout_trains(tokens):
    """3D mesh + dropout: auto-mode global masks, one finite training step
    through the last-stage-reduction loss."""
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    model = pipelined_tiny_test(dropout_rate=0.1)
    strat = PipelineParallelStrategy(data=2, pipe=2, tensor=2)
    state, _ = init_state(model, optax.adam(1e-3), strat, tokens)
    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    state, m = step(state, (tokens,), jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_3d_with_remat_dots_trains(tokens):
    """jax.checkpoint('dots' policy) inside the partial-manual pipe: one
    finite training step on the 3D mesh."""
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    model = pipelined_tiny_test(remat="dots")
    strat = PipelineParallelStrategy(data=2, pipe=2, tensor=2)
    state, _ = init_state(model, optax.adam(1e-3), strat, tokens)
    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    state, m = step(state, (tokens,), jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_flash_refused_inside_partial_manual_pipe(tokens):
    """Explicit flash inside the partial-manual 3D pipe must error with
    guidance (the kernel's custom-VJP variance doesn't compose with a
    nested shard_map), and 'auto' must quietly pick the reference einsum
    there — never a silent replicate-or-crash."""
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    strat = PipelineParallelStrategy(data=2, pipe=2, tensor=2)
    m_flash = pipelined_tiny_test(attn_impl="flash")
    state_f, _ = init_state(m_flash, optax.adam(1e-3), strat, tokens)
    step_f = make_custom_train_step(strat, state_f, pipelined_next_token_loss,
                                    donate=False)
    with pytest.raises(NotImplementedError, match="partial-manual"):
        step_f(state_f, (tokens,), jax.random.key(0))


def test_auto_dispatch_skips_flash_under_abstract_mesh(monkeypatch):
    """'auto' never picks flash inside a partial-manual region, even at
    flash-eligible lengths on TPU."""
    import tfde_tpu.ops.attention as att
    from tfde_tpu.parallel import axes as axes_lib

    chosen = []
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        att, "reference_attention",
        lambda q, k, v, mask=None, causal=False, window=None, **kw:
        (chosen.append("reference"), q)[1],
    )
    q = jnp.zeros((1, 4096, 1, 4), jnp.bfloat16)
    abstract = jax.sharding.AbstractMesh((2,), ("data",))
    with axes_lib.use_axes(abstract):
        att.attention(q, q, q)
    assert chosen == ["reference"]


# --------------------------------------------------------------------------
# 1F1B schedule (parallel/pipeline.pipeline_train_1f1b)
# --------------------------------------------------------------------------

def test_1f1b_loss_and_grads_match_gpipe(model, tokens):
    """The hand-scheduled 1F1B backward must produce the SAME loss and
    gradients as AD through the GPipe forward (both compute exact math;
    only summation order differs -> fp32 tolerance)."""
    from tfde_tpu.parallel import axes as axes_lib

    m_1f1b = pipelined_tiny_test(schedule="1f1b")
    variables = model.init(jax.random.key(0), tokens)
    mesh = make_mesh({"data": 2, "pipe": 2}, jax.devices()[:4])

    def loss_with(mdl):
        def f(params):
            with axes_lib.use_axes(mesh):
                loss, _ = mdl.loss_and_metrics(
                    {"params": params}, tokens, train=True
                )
            return loss
        return f

    v_g, g_g = jax.jit(jax.value_and_grad(loss_with(model)))(
        variables["params"]
    )
    v_1, g_1 = jax.jit(jax.value_and_grad(loss_with(m_1f1b)))(
        variables["params"]
    )
    np.testing.assert_allclose(float(v_1), float(v_g), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        g_1, g_g,
    )


@pytest.mark.slow
def test_1f1b_train_matches_dp(tokens):
    """5 Adam steps through the 1F1B schedule at pipe=2 x data=2 == plain
    DP at data=4 — the same oracle as the GPipe path (VERDICT r3 #5 'done'
    bar)."""
    from tfde_tpu.models.gpt import next_token_loss
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    m_1f1b = pipelined_tiny_test(schedule="1f1b")
    strat_p = PipelineParallelStrategy(data=2, pipe=2)
    state_p, _ = init_state(m_1f1b, optax.adam(1e-3), strat_p, tokens)
    step_p = make_custom_train_step(strat_p, state_p,
                                    pipelined_next_token_loss, donate=False)

    strat_d = MultiWorkerMirroredStrategy(
        make_mesh({"data": 4}, jax.devices()[:4])
    )
    plain = pipelined_tiny_test()  # sequential fallback on the DP mesh
    state_d, _ = init_state(plain, optax.adam(1e-3), strat_d, tokens)
    step_d = make_custom_train_step(strat_d, state_d, next_token_loss,
                                    donate=False)

    rng = jax.random.key(0)
    for _ in range(5):
        state_p, m_p = step_p(state_p, (tokens,), rng)
        state_d, m_d = step_d(state_d, (tokens,), rng)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_d["loss"]), rtol=2e-5
    )
    assert float(m_p["loss"]) < 4.6


def test_1f1b_single_stage_direct():
    """Degenerate S=1 of pipeline_train_1f1b called directly (the model
    path falls back to the sequential stack at pipe=1, so the schedule's
    S=1 edge — stash_n=1, ticks=M, last rank == rank 0 — only gets
    coverage here)."""
    import jax.numpy as jnp

    from tfde_tpu.parallel.pipeline import pipeline_train_1f1b

    mesh = make_mesh({"data": 1, "pipe": 1}, jax.devices()[:1])
    rng = np.random.default_rng(2)
    stacked = jnp.asarray(rng.normal(size=(1, 3)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 2, 3)), jnp.float32)
    aux = jnp.asarray(rng.normal(size=(4, 2, 3)), jnp.float32)
    extra = jnp.asarray(rng.normal(size=(3,)), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h * p)

    def loss_fn(e, y, a):
        return {"loss_sum": jnp.sum(e * y * a),
                "count": jnp.asarray(y.size, jnp.float32)}

    sums, grads = jax.jit(lambda s, xx, a, e: pipeline_train_1f1b(
        stage_fn, s, xx, mesh, loss_fn=loss_fn, loss_aux=a, extra_params=e
    ))(stacked, x, aux, extra)

    def ref(s, xx, e):
        return jnp.sum(e * jnp.tanh(xx * s[0]) * aux)

    v, (g_s, g_x, g_e) = jax.value_and_grad(ref, argnums=(0, 1, 2))(
        stacked, x, extra
    )
    np.testing.assert_allclose(float(sums["loss_sum"]), float(v), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["stages"]), np.asarray(g_s),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["x"]), np.asarray(g_x),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["extra"]), np.asarray(g_e),
                               rtol=1e-5)


def test_1f1b_many_microbatches(tokens):
    """M > 2S runs the schedule correctly (steady-state dominates)."""
    from tfde_tpu.parallel import axes as axes_lib

    m8 = pipelined_tiny_test(schedule="1f1b", microbatches=8)
    g8 = pipelined_tiny_test(microbatches=8)
    variables = m8.init(jax.random.key(1), tokens)
    mesh = make_mesh({"data": 1, "pipe": 2}, jax.devices()[:2])

    def loss_fn(mdl):
        def f(params):
            with axes_lib.use_axes(mesh):
                loss, _ = mdl.loss_and_metrics(
                    {"params": params}, tokens, train=True
                )
            return loss
        return f

    v_1, g_1 = jax.jit(jax.value_and_grad(loss_fn(m8)))(variables["params"])
    v_g, g_g = jax.jit(jax.value_and_grad(loss_fn(g8)))(variables["params"])
    np.testing.assert_allclose(float(v_1), float(v_g), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        g_1, g_g,
    )


def test_1f1b_dropout_trains(tokens):
    """Dropout keys pass through the custom_vjp as an explicit argument;
    masks reproduce between the fwd slot and the bwd recompute, so training
    stays finite and deterministic per seed."""
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    model = pipelined_tiny_test(schedule="1f1b", dropout_rate=0.3)
    strat = PipelineParallelStrategy(data=2, pipe=2)
    state, _ = init_state(model, optax.adam(1e-3), strat, tokens)
    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    state, m = step(state, (tokens,), jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_1f1b_refused_with_tensor_axis(tokens):
    """dp x pp x tp uses AD for its backward; 1F1B must refuse loudly."""
    m = pipelined_tiny_test(schedule="1f1b")
    strat = PipelineParallelStrategy(data=2, pipe=2, tensor=2)
    state, _ = init_state(m, optax.adam(1e-3), strat, tokens)
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    with pytest.raises(NotImplementedError, match="1f1b"):
        step(state, (tokens,), jax.random.key(0))


# --------------------------------------------------------------------------
# pp x sp: ring attention inside the fully-manual pipe
# --------------------------------------------------------------------------

def test_pp_sp_forward_matches_sequential(model, tokens):
    """dp x pipe x seq: sequence sharded over the ring INSIDE pipeline
    stages (ring_attention_manual in the flat manual region) must equal
    the no-mesh sequential forward."""
    from tfde_tpu.parallel import axes as axes_lib

    variables = model.init(jax.random.key(0), tokens)
    seq_logits = jax.jit(lambda v, t: model.apply(v, t))(variables, tokens)

    mesh = make_mesh({"data": 2, "pipe": 2, "seq": 2}, jax.devices()[:8])

    def pipe_forward(v, t):
        with axes_lib.use_axes(mesh):
            return model.apply(v, t)

    pipe_logits = jax.jit(pipe_forward)(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(pipe_logits), np.asarray(seq_logits), rtol=1e-4,
        atol=1e-5,
    )


@pytest.mark.slow
def test_pp_sp_train_matches_dp(model, tokens):
    """5 Adam steps at dp=2 x pipe=2 x seq=2 == plain DP at data=4 — the
    same numerics oracle as every other strategy family."""
    from tfde_tpu.models.gpt import next_token_loss

    strat_p = PipelineParallelStrategy(data=2, pipe=2, seq=2)
    state_p, _ = init_state(model, optax.adam(1e-3), strat_p, tokens)
    step_p = make_custom_train_step(strat_p, state_p, next_token_loss,
                                    donate=False)

    strat_d = MultiWorkerMirroredStrategy(
        make_mesh({"data": 4}, jax.devices()[:4])
    )
    state_d, _ = init_state(model, optax.adam(1e-3), strat_d, tokens)
    step_d = make_custom_train_step(strat_d, state_d, next_token_loss,
                                    donate=False)

    rng = jax.random.key(0)
    for _ in range(5):
        state_p, m_p = step_p(state_p, (tokens,), rng)
        state_d, m_d = step_d(state_d, (tokens,), rng)
    np.testing.assert_allclose(
        float(m_p["loss"]), float(m_d["loss"]), rtol=2e-5
    )
    assert float(m_p["loss"]) < 4.6


def test_pp_sp_loss_and_metrics_routes_outside(model, tokens):
    """loss_and_metrics under a seq axis must route through the full-logit
    path (shift correctness across shard boundaries) and still match the
    sequential loss."""
    from tfde_tpu.parallel import axes as axes_lib

    variables = model.init(jax.random.key(0), tokens)
    ref_loss, _ = model.loss_and_metrics(variables, tokens)  # no mesh
    mesh = make_mesh({"data": 2, "pipe": 2, "seq": 2}, jax.devices()[:8])

    def f(v, t):
        with axes_lib.use_axes(mesh):
            return model.loss_and_metrics(v, t)

    loss, metrics = jax.jit(f)(variables, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)


def test_pp_sp_tp_refused(tokens):
    strat = PipelineParallelStrategy(data=1, pipe=2, tensor=2, seq=2)
    with pytest.raises(ValueError, match="pp x sp x tp"):
        init_state(pipelined_tiny_test(), optax.adam(1e-3), strat,
                   np.zeros((8, 32), np.int32))


@pytest.mark.slow
def test_pp_sp_1f1b_refused(model, tokens):
    from tfde_tpu.models.pipelined import pipelined_next_token_loss

    m = pipelined_tiny_test(schedule="1f1b")
    strat = PipelineParallelStrategy(data=2, pipe=2, seq=2)
    state, _ = init_state(m, optax.adam(1e-3), strat, tokens)
    step = make_custom_train_step(strat, state, pipelined_next_token_loss,
                                  donate=False)
    with pytest.raises(NotImplementedError, match="1f1b"):
        step(state, (tokens,), jax.random.key(0))


def test_1f1b_four_stages(tokens):
    """S=4 (one layer per stage, M=8): the stash ring (2S-1=7 slots) and
    deeper warmup/cooldown windows still reproduce the GPipe grads."""
    from tfde_tpu.parallel import axes as axes_lib

    m4 = pipelined_tiny_test(num_stages=4, layers_per_stage=1,
                             microbatches=8, schedule="1f1b")
    g4 = pipelined_tiny_test(num_stages=4, layers_per_stage=1,
                             microbatches=8)
    variables = m4.init(jax.random.key(0), tokens)
    mesh = make_mesh({"data": 2, "pipe": 4}, jax.devices()[:8])

    def loss(mdl):
        def f(p):
            with axes_lib.use_axes(mesh):
                l, _ = mdl.loss_and_metrics({"params": p}, tokens,
                                            train=True)
            return l
        return f

    v1, g1 = jax.jit(jax.value_and_grad(loss(m4)))(variables["params"])
    vg, gg = jax.jit(jax.value_and_grad(loss(g4)))(variables["params"])
    np.testing.assert_allclose(float(v1), float(vg), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=1e-6
        ),
        g1, gg,
    )


def test_pp_sp_ring_of_four(model, tokens):
    """seq=4 inside pipe=2: multi-hop KV rotation in the manual region."""
    from tfde_tpu.parallel import axes as axes_lib

    variables = model.init(jax.random.key(0), tokens)
    ref = jax.jit(lambda v, t: model.apply(v, t))(variables, tokens)
    mesh = make_mesh({"data": 1, "pipe": 2, "seq": 4}, jax.devices()[:8])

    def fwd(v, t):
        with axes_lib.use_axes(mesh):
            return model.apply(v, t)

    got = jax.jit(fwd)(variables, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_pp_sp_dropout_trains(tokens):
    """Dropout under pp x sp: keys fold the seq-shard index too, masks are
    deterministic per seed, loss stays finite."""
    from tfde_tpu.parallel import axes as axes_lib

    model = pipelined_tiny_test(dropout_rate=0.3)
    mesh = make_mesh({"data": 2, "pipe": 2, "seq": 2}, jax.devices()[:8])
    variables = model.init(jax.random.key(0), tokens)

    def f(v, t, key):
        with axes_lib.use_axes(mesh):
            return model.apply(v, t, train=True, rngs={"dropout": key})

    fn = jax.jit(f)
    a = np.asarray(fn(variables, tokens, jax.random.key(5)))
    b = np.asarray(fn(variables, tokens, jax.random.key(5)))
    np.testing.assert_array_equal(a, b)  # deterministic per seed
    c = np.asarray(fn(variables, tokens, jax.random.key(6)))
    assert not np.allclose(a, c, atol=1e-3)  # seed moves the masks
    assert np.all(np.isfinite(a))
