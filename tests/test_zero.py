"""ZeRO weight-update sharding (parallel/zero.py): knob resolution and
strategy/RunConfig plumbing, the packed two-segment layout round-trips,
chunk-update bit-parity with the replicated per-leaf update, sharded
init_state on the 8-device mesh (opt-state memory accounting + shardings +
gauges), the eligibility warn-fallbacks, loss-trajectory parity for every
transport x sharding combo, and checkpoint cross-format resume in both
directions against an uninterrupted oracle.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tfde_tpu.checkpoint.manager import CheckpointManager
from tfde_tpu.models.cnn import PlainCNN
from tfde_tpu.observability import metrics as obs_metrics
from tfde_tpu.parallel import comms, zero
from tfde_tpu.parallel.strategies import FSDPStrategy, MirroredStrategy
from tfde_tpu.runtime.mesh import make_mesh
from tfde_tpu.training import optimizers
from tfde_tpu.training.lifecycle import Estimator, RunConfig
from tfde_tpu.training.step import init_state, make_train_step


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    # every test below states its mode explicitly; the operator's (or
    # tier1.sh's) $TFDE_OPT_SHARDING must not leak in
    monkeypatch.delenv(zero.ENV_OPT_SHARDING, raising=False)


def _dp_mesh(n=8):
    return make_mesh({"data": -1}, jax.devices()[:n])


def _setup(opt_sharding, transport="fp32", n=8, tx=None, model=None,
           grad_accum=1, strategy=None):
    strategy = strategy or MirroredStrategy(
        mesh=_dp_mesh(n), grad_transport=transport, opt_sharding=opt_sharding)
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
    state, _ = init_state(model or PlainCNN(), tx or optax.adam(1e-2),
                          strategy, images)
    step = make_train_step(strategy, state, donate=False,
                           grad_accum=grad_accum)
    return strategy, state, step, (images, labels)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


# -- knob resolution + plumbing -----------------------------------------------
def test_resolve_sugar(monkeypatch):
    assert zero.resolve(None) == "replicated"
    assert zero.resolve("shard") == "shard"
    monkeypatch.setenv(zero.ENV_OPT_SHARDING, "shard")
    assert zero.resolve(None) == "shard"
    with pytest.raises(ValueError):
        zero.resolve("zero1")
    with pytest.raises(TypeError):
        zero.resolve(123)


def test_strategy_knob_plumbing(monkeypatch):
    assert MirroredStrategy(
        mesh=_dp_mesh(4), opt_sharding="shard").opt_sharding == "shard"
    # None defers to the env, resolved lazily at first use
    s = MirroredStrategy(mesh=_dp_mesh(4))
    monkeypatch.setenv(zero.ENV_OPT_SHARDING, "shard")
    assert s.opt_sharding == "shard"
    s.opt_sharding = "replicated"
    assert s.opt_sharding == "replicated"


def test_runconfig_overrides_strategy_knob(tmp_path):
    est = Estimator(
        PlainCNN(), optax.sgd(0.1),
        config=RunConfig(model_dir=str(tmp_path), opt_sharding="shard"),
    )
    assert est.strategy.opt_sharding == "shard"


# -- the packed layout --------------------------------------------------------
def _toy_params():
    return {
        "w": jnp.arange(5000, dtype=jnp.float32).reshape(50, 100) / 7.0,
        "b": jnp.arange(7, dtype=jnp.float32) - 3.0,
        "scale": jnp.full((3,), 1.5, jnp.bfloat16),
    }


def test_layout_and_pack_roundtrip():
    params = _toy_params()
    ccfg = comms.CommsConfig()
    layout = zero.build_layout(params, ccfg, 4)
    # big segment pads to the int8 quantum so fp32- and int8-written
    # sharded checkpoints share chunk boundaries
    assert layout.total_big == 5000 and layout.total_small == 10
    assert layout.padded_big % (4 * ccfg.block) == 0
    assert layout.padded_small % 4 == 0
    packed = zero.pack_params(params, layout)
    assert packed[zero.BIG].shape == (4, layout.chunk_big)
    assert packed[zero.SMALL].shape == (4, layout.chunk_small)
    rt = zero.unpack_packed(packed, layout)
    for k in params:
        assert rt[k].dtype == params[k].dtype
        np.testing.assert_array_equal(np.asarray(rt[k], np.float32),
                                      np.asarray(params[k], np.float32))
    with pytest.raises(ValueError):
        zero.build_layout(params, ccfg, 1)


def test_pack_opt_state_roundtrip_bitwise():
    params = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
              "b": jnp.ones((5,), jnp.float32)}
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    layout = zero.build_layout(params, comms.CommsConfig(), 4)
    packed = zero.pack_opt_state(opt, layout)
    # params-congruent slots became [N, C] chunk trees, scalars untouched
    mu = packed[0].mu
    assert set(mu.keys()) == {zero.BIG, zero.SMALL}
    assert mu[zero.BIG].shape == (4, layout.chunk_big)
    assert packed[0].count.shape == ()
    rt = zero.unpack_opt_state(packed, layout)
    for a, b in zip(jax.tree_util.tree_leaves(opt),
                    jax.tree_util.tree_leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_update_bitwise_matches_per_leaf_update():
    """The correctness contract: for an elementwise transform (adam), the
    packed-chunk update is bit-identical to the replicated per-leaf one."""
    params = _toy_params()
    params = {k: v.astype(jnp.float32) for k, v in params.items()}
    grads = jax.tree_util.tree_map(lambda p: jnp.cos(p) * 0.1, params)
    tx = optax.adam(1e-2)

    # replicated oracle: two per-leaf updates
    opt = tx.init(params)
    p_ref = params
    for _ in range(2):
        upd, opt = tx.update(grads, opt, p_ref)
        p_ref = optax.apply_updates(p_ref, upd)

    # packed: same numbers, [N, C] chunks (zero-padded tails)
    layout = zero.build_layout(params, comms.CommsConfig(), 4)
    p_pack = zero.pack_params(params, layout)
    g_pack = zero.pack_params(grads, layout)
    opt_p = tx.init(p_pack)
    for _ in range(2):
        upd, opt_p = tx.update(g_pack, opt_p, p_pack)
        p_pack = optax.apply_updates(p_pack, upd)

    out = zero.unpack_packed(p_pack, layout)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_ref[k]),
                                      np.asarray(out[k]))


# -- sharded init_state -------------------------------------------------------
def test_init_state_shards_opt_state_and_cuts_memory():
    _, sharded, _, _ = _setup("shard")
    _, replicated, _, _ = _setup("replicated")
    assert sharded.opt_sharded and sharded.opt_layout.nshards == 8
    assert not replicated.opt_sharded

    chunk_leaves = [
        l for l in jax.tree_util.tree_leaves(sharded.opt_state)
        if getattr(l, "ndim", 0) >= 1 and l.shape[0] == 8
    ]
    assert chunk_leaves, "no packed [N, C] slots found"
    for leaf in chunk_leaves:
        # genuinely distributed: row-sharded over the data axis
        assert leaf.sharding.spec == P("data")

    rep_bytes = zero.state_bytes(replicated.opt_state)
    sh_bytes = zero.state_bytes(sharded.opt_state, sharded.opt_layout)
    # acceptance floor is 1/4; padding keeps it from the exact 1/8
    assert sh_bytes <= rep_bytes / 4.0
    assert sh_bytes == pytest.approx(rep_bytes / 8.0, rel=0.2)


def test_opt_gauges_exported_at_step_build():
    _, state, _, _ = _setup("shard")
    reg = obs_metrics.default_registry()
    assert reg.gauge("opt/state_bytes").value == pytest.approx(
        zero.state_bytes(state.opt_state, state.opt_layout))
    assert reg.gauge("opt/param_gather_bytes").value > 0.0
    _setup("replicated")
    assert reg.gauge("opt/param_gather_bytes").value == 0.0


@pytest.mark.parametrize("name", ["toy", "bert_base"])
def test_comm_bytes_accounts_param_gather_leg(name, bert_base_shapes):
    """Counted from shapes. For BERT-base also what the sharding is for:
    Adam's state a device on 8 shards, an eighth of the replicated 877 MB
    but for the padding (the bar is a quarter), and the gather leg that
    `comm_bytes` charges is the layout's own."""
    if name == "toy":
        tree = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((5,))}
    else:
        tree = bert_base_shapes
    rep = comms.comm_bytes(tree, comms.CommsConfig(), 8)
    sh = comms.comm_bytes(tree, comms.CommsConfig(), 8,
                          opt_sharding="shard")
    assert rep["param_gather"] == 0.0
    assert sh["param_gather"] > 0.0
    if name == "bert_base":
        tx = optax.adam(1e-3)
        layout = zero.build_layout(tree, comms.CommsConfig(), 8)
        rep_bytes = zero.state_bytes(jax.eval_shape(tx.init, tree))
        sh_bytes = zero.state_bytes(
            jax.eval_shape(lambda p: tx.init(zero.pack_params(p, layout)),
                           tree), layout)
        assert rep_bytes == pytest.approx(877e6, rel=0.01)
        assert sh_bytes <= rep_bytes / 4.0
        assert sh_bytes == pytest.approx(rep_bytes / 8.0, rel=0.01)
        assert sh["param_gather"] == zero.param_gather_bytes(layout)


def test_sharded_step_census_budget_and_payloads():
    """The lowered sharded step through the census helper
    (analysis/hlolint.py — the tools/lintgate.py pin): the ZeRO budget
    triple at 8-way, no host callback, and the wire asymmetry the packed
    layout promises — the full-param all-gather result outweighs the
    1/8-shard reduce-scatter result."""
    from tfde_tpu.analysis import hlolint

    _, state, step, batch = _setup("shard")
    assert state.opt_sharded
    c = hlolint.census(step.jitted, state, batch, jax.random.key(0))
    assert c.collective_counts == (1, 1, 1)
    assert c.callbacks == 0
    assert c.f64_tensors == 0
    assert c.collective_bytes["all_gather"] > c.collective_bytes[
        "reduce_scatter"]


# -- eligibility fallbacks ----------------------------------------------------
def test_fsdp_falls_back_to_replicated(caplog):
    if len(jax.devices()) < 2:
        pytest.skip("needs multiple devices")
    strategy = FSDPStrategy(min_shard_elems=1, opt_sharding="shard")
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    with caplog.at_level("WARNING"):
        state, _ = init_state(PlainCNN(), optax.adam(1e-2), strategy, images)
    assert state.opt_layout is None
    assert any("replicated params" in r.message for r in caplog.records)


def test_masked_optimizer_falls_back_to_replicated(caplog):
    """optimizers.adamw carries a path-keyed decay mask (MaskedState): the
    packed tree would silently change what the mask saw, so init_state
    warn-falls-back."""
    with caplog.at_level("WARNING"):
        _, state, step, batch = _setup("shard", tx=optimizers.adamw(1e-3))
    assert state.opt_layout is None
    assert any("masked" in r.message for r in caplog.records)
    new_state, m = step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_state_without_layout_falls_back(caplog):
    """Asking for 'shard' at step-build time against a replicated state
    downgrades with a warning instead of crashing (mirrors the int8
    missing-residual fallback)."""
    strategy, state, _, batch = _setup("replicated")
    with caplog.at_level("WARNING"):
        step = make_train_step(strategy, state, donate=False,
                               opt_sharding="shard")
    assert any("falling back to the replicated update" in r.message
               for r in caplog.records)
    new_state, m = step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


# -- step parity --------------------------------------------------------------
def _assert_same_trajectory(got, want):
    """Parameters after a few Adam steps of 1e-2 through two compiled
    programs that compute the same float32 mathematics (psum against
    psum-scatter + chunk update + all-gather). The two are not equal to
    the bit: XLA orders the reductions of each program as it likes, a
    gradient element near zero then differs in its last place, and Adam's
    m / (sqrt(v) + eps) turns that into up to 2e-4 of one step (read here:
    1.6e-6 absolute on Dense_0's kernel after 4 steps, jax 0.9.0). So:
    1e-5 absolute, a thousandth of a step, beside 1e-5 relative. A shard
    that missed its update or its gather is off by a whole step, 1e-2."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))


def test_fp32_shard_trajectory_bitwise_matches_replicated():
    """fp32 x shard follows the replicated fp32 oracle: the psum-scatter +
    chunk update + all-gather computes the same fp32 mathematics, in
    another program (the name is from when the two also agreed to the
    bit): losses within 1e-6 relative, parameters within
    `_assert_same_trajectory`'s tolerance."""
    _, rep_state, rep_step, batch = _setup("replicated")
    _, sh_state, sh_step, _ = _setup("shard")
    for i in range(4):
        rep_state, mr = rep_step(rep_state, batch, jax.random.key(i))
        sh_state, ms = sh_step(sh_state, batch, jax.random.key(i))
        assert float(ms["loss"]) == pytest.approx(float(mr["loss"]),
                                                  rel=1e-6)
    _assert_same_trajectory(sh_state.params, rep_state.params)


def test_fp32_shard_with_grad_accum_tracks_replicated():
    """Under grad_accum the comms-style body accumulates LOCAL weighted
    sums and exchanges once, while the replicated custom body psums every
    microbatch — same math, different summation order, so parity is tight
    but not bitwise (the int8 grad_accum contract)."""
    _, rep_state, rep_step, batch = _setup("replicated", grad_accum=2)
    _, sh_state, sh_step, _ = _setup("shard", grad_accum=2)
    for i in range(3):
        rep_state, mr = rep_step(rep_state, batch, jax.random.key(i))
        sh_state, ms = sh_step(sh_state, batch, jax.random.key(i))
        assert abs(float(mr["loss"]) - float(ms["loss"])) < 5e-3


def test_int8_shard_tracks_fp32_oracle():
    """int8 x shard composes: quantized scatter + sharded update stays
    within the documented int8 tolerance of the fp32 oracle."""
    tx = optax.sgd(0.1, momentum=0.9)
    _, f_state, f_step, batch = _setup("replicated", transport="fp32", tx=tx)
    _, i_state, i_step, _ = _setup("shard", transport="int8", tx=tx)
    assert i_state.opt_sharded and i_state.comm_residual is not None
    diffs = []
    for i in range(6):
        f_state, mf = f_step(f_state, batch, jax.random.key(0))
        i_state, mi = i_step(i_state, batch, jax.random.key(0))
        diffs.append(abs(float(mf["loss"]) - float(mi["loss"])))
    assert max(diffs) < 0.05, diffs
    # grad_norm still reported (folded into the param-gather payload)
    assert float(mi["grad_norm"]) > 0.0


# -- checkpoint cross-compat --------------------------------------------------
def _run_steps(state, step, batch, keys):
    for k in keys:
        state, _ = step(state, batch, jax.random.key(k))
    return state


@pytest.mark.parametrize("write_mode,resume_mode", [
    ("replicated", "shard"),
    ("shard", "replicated"),
])
def test_checkpoint_cross_format_resume_bit_exact(tmp_path, write_mode,
                                                  resume_mode):
    """A checkpoint written under one opt_sharding mode resumes under the
    other: what the restore hands back is the writer's parameters and
    step to the bit (pack/unpack are pure reshapes of the same numbers),
    and two further steps under the other format land on the
    uninterrupted oracle within `_assert_same_trajectory`'s tolerance
    (another program computes them)."""
    _, oracle, oracle_step, batch = _setup(write_mode)
    oracle = _run_steps(oracle, oracle_step, batch, range(4))

    _, writer, writer_step, _ = _setup(write_mode)
    writer = _run_steps(writer, writer_step, batch, range(2))
    mngr = CheckpointManager(str(tmp_path), async_save=False)
    assert mngr.save(writer, force=True)
    mngr.wait()

    _, fresh, resume_step, _ = _setup(resume_mode)
    resumed = mngr.restore_latest(fresh)
    mngr.close()
    assert resumed is not None
    assert int(resumed.step) == 2
    assert resumed.opt_sharded == (resume_mode == "shard")
    assert _digest(resumed.params) == _digest(writer.params)
    resumed = _run_steps(resumed, resume_step, batch, range(2, 4))
    _assert_same_trajectory(resumed.params, oracle.params)
