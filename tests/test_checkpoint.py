"""Checkpoint save/auto-resume tests (SURVEY.md §5 checkpoint/resume)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.checkpoint.manager import CheckpointManager
from tfde_tpu.models.cnn import PlainCNN
from tfde_tpu.parallel.strategies import MultiWorkerMirroredStrategy, ParameterServerStrategy
from tfde_tpu.training.step import init_state, make_train_step


def _state(strategy, seed=0):
    state, _ = init_state(
        PlainCNN(), optax.sgd(0.1, momentum=0.9), strategy, jnp.zeros((8, 28, 28, 1)), seed=seed
    )
    return state


def test_save_and_restore_roundtrip(tmp_path, monkeypatch):
    strat = MultiWorkerMirroredStrategy()
    state = _state(strat)
    # a RELATIVE directory, as `--working-dir out` passes it: orbax itself
    # refuses one at the first save
    monkeypatch.chdir(tmp_path)
    mngr = CheckpointManager("ckpt", async_save=False)
    assert mngr.latest_step is None
    assert mngr.restore_latest(state) is None

    state = state.replace(step=state.step + 5)
    mngr.save(state, force=True)
    mngr.wait()
    assert mngr.latest_step == 5

    fresh = _state(strat, seed=1)  # different init
    restored = mngr.restore_latest(fresh)
    assert int(jax.device_get(restored.step)) == 5
    for a, b in zip(
        jax.tree_util.tree_leaves(restored.params), jax.tree_util.tree_leaves(state.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mngr.close()


def test_restore_respects_sharded_opt_state(tmp_path):
    """ZeRO-1 sharded optimizer state must restore with its shardings."""
    strat = ParameterServerStrategy(min_shard_elems=1024)
    state = _state(strat)
    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(state, force=True)
    mngr.wait()
    restored = mngr.restore_latest(_state(strat, seed=1))
    for a, b in zip(
        jax.tree_util.tree_leaves(restored.opt_state),
        jax.tree_util.tree_leaves(state.opt_state),
    ):
        assert a.sharding == b.sharding
    mngr.close()


def test_training_resumes_from_checkpoint(tmp_path):
    """Kill-and-restart: a new process (fresh state) continues at saved step
    with saved params — the Estimator restart contract (SURVEY.md §5)."""
    strat = MultiWorkerMirroredStrategy()
    state = _state(strat)
    step_fn = make_train_step(strat, state)
    rng = jax.random.key(0)
    batch = (
        jnp.ones((16, 28, 28, 1)),
        jnp.zeros((16, 1), jnp.int32),
    )
    from tfde_tpu.data.device import device_prefetch

    dev_batch = next(iter(device_prefetch([batch], strat.mesh)))
    for _ in range(3):
        state, _ = step_fn(state, dev_batch, rng)

    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(state, force=True)
    mngr.wait()
    mngr.close()

    # "restart": fresh process state, fresh compiled step
    mngr2 = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    resumed = mngr2.restore_latest(_state(strat, seed=9))
    assert int(jax.device_get(resumed.step)) == 3
    step_fn2 = make_train_step(strat, resumed)
    state2, _ = step_fn2(resumed, dev_batch, rng)
    assert int(jax.device_get(state2.step)) == 4
    mngr2.close()


@pytest.mark.parametrize("transport", ["fp32", "int8"])
@pytest.mark.parametrize("opt_sharding", ["replicated", "shard"])
@pytest.mark.parametrize("save_n,restore_n", [(2, 4), (4, 2)])
def test_cross_world_restore_matrix(tmp_path, save_n, restore_n, opt_sharding,
                                    transport):
    """Elastic restore: an M-way checkpoint restores onto an N-way mesh,
    both directions, replicated and ZeRO-packed optimizer state, fp32 and
    int8 gradient transport. Params must be bit-exact and the unpacked
    optimizer slots must match the writer's values (the ZeRO cells force
    the packed re-chunk path — the M-way packed shapes cannot restore
    directly into the N-way layout)."""
    from tfde_tpu.data.device import device_prefetch
    from tfde_tpu.parallel import zero as zero_lib
    from tfde_tpu.runtime.mesh import make_mesh

    def strat(n):
        return MultiWorkerMirroredStrategy(
            mesh=make_mesh({"data": n}, jax.devices()[:n]),
            grad_transport=transport, opt_sharding=opt_sharding,
        )

    src = strat(save_n)
    state = _state(src)
    # advance a few steps so the momentum slots hold non-trivial values
    step_fn = make_train_step(src, state)
    rng = jax.random.key(0)
    batch = (jnp.ones((8, 28, 28, 1)), jnp.zeros((8, 1), jnp.int32))
    dev_batch = next(iter(device_prefetch([batch], src.mesh)))
    for _ in range(3):
        state, _ = step_fn(state, dev_batch, rng)
    if opt_sharding == "shard":
        assert state.opt_layout is not None, "ZeRO cell did not pack"

    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(state, force=True)
    mngr.wait()
    mngr.close()

    dst = strat(restore_n)
    mngr2 = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    restored = mngr2.restore_latest(_state(dst, seed=9))
    mngr2.close()
    assert int(jax.device_get(restored.step)) == 3

    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def opt_values(st):
        opt = jax.device_get(st.opt_state)
        layout = getattr(st, "opt_layout", None)
        if layout is not None:
            opt = zero_lib.unpack_opt_state(opt, layout)
        return jax.tree_util.tree_leaves(opt)

    got, want = opt_values(restored), opt_values(state)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=0)

    # and the restored state must keep training at the new world size
    step_fn2 = make_train_step(dst, restored)
    dev_batch2 = next(iter(device_prefetch([batch], dst.mesh)))
    again, _ = step_fn2(restored, dev_batch2, rng)
    assert int(jax.device_get(again.step)) == 4


def test_packed_geometry_check_discriminates(tmp_path):
    """_packed_geometry_differs: True only when both sides hold ZeRO-packed
    slots with different chunk geometry — the trigger for the packed
    re-chunk branch of _restore_cross_format."""
    from tfde_tpu.runtime.mesh import make_mesh

    def strat(n):
        return MultiWorkerMirroredStrategy(
            mesh=make_mesh({"data": n}, jax.devices()[:n]),
            opt_sharding="shard",
        )

    state2 = _state(strat(2))
    assert state2.opt_layout is not None
    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(state2, force=True)
    mngr.wait()
    step = mngr.latest_step

    assert not mngr._packed_geometry_differs(step, state2)
    state4 = _state(strat(4), seed=1)
    assert mngr._packed_geometry_differs(step, state4)
    # replicated live state: no layout, never this trigger (the
    # replicated<->sharded bridge owns that direction)
    rep = _state(MultiWorkerMirroredStrategy(
        mesh=make_mesh({"data": 4}, jax.devices()[:4]),
        opt_sharding="replicated"), seed=2)
    assert not mngr._packed_geometry_differs(step, rep)
    mngr.close()


def test_optimizer_change_relabeled_with_guidance(tmp_path):
    """Restoring an adamw checkpoint into an sgd(momentum) state must fail
    with the optimizer-changed guidance (a genuine structure mismatch,
    detected via orbax metadata — not error-text sniffing)."""
    strat = MultiWorkerMirroredStrategy()
    saved, _ = init_state(
        PlainCNN(), optax.adamw(1e-3), strat, jnp.zeros((8, 28, 28, 1))
    )
    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(saved, force=True)
    mngr.wait()
    with pytest.raises(ValueError, match="optimizer configuration"):
        mngr.restore_latest(_state(strat, seed=1))
    mngr.close()


def test_structure_check_discriminates(tmp_path):
    """_saved_structure_differs: False for the matching state (so unrelated
    restore errors keep their original message), True for a changed
    optimizer."""
    strat = MultiWorkerMirroredStrategy()
    saved, _ = init_state(
        PlainCNN(), optax.adamw(1e-3), strat, jnp.zeros((8, 28, 28, 1))
    )
    mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    mngr.save(saved, force=True)
    mngr.wait()
    step = mngr.latest_step

    def abstract_of(state):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            mngr._tree(state),
        )

    assert not mngr._saved_structure_differs(step, abstract_of(saved))
    changed, _ = init_state(
        PlainCNN(), optax.sgd(0.1, momentum=0.9), strat,
        jnp.zeros((8, 28, 28, 1)),
    )
    assert mngr._saved_structure_differs(step, abstract_of(changed))
    mngr.close()
