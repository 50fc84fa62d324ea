"""Quantized gradient exchange (parallel/comms.py): config resolution and
mesh eligibility, the packed-buffer plumbing, exchange correctness + the
error-feedback identity on a real multi-device mesh, the fixed-collective
and no-callback guarantees from the lowered HLO, the fp32 no-op
bit-identity, loss-trajectory parity vs the uncompressed oracle, and the
overflow -> numerics-sentry path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tfde_tpu.analysis import hlolint
from tfde_tpu.models.cnn import PlainCNN
from tfde_tpu.observability.sentry import (
    FLAG_COMM_OVERFLOW,
    SentryConfig,
    init_state as sentry_init,
)
from tfde_tpu.parallel import comms
from tfde_tpu.parallel.strategies import MirroredStrategy
from tfde_tpu.runtime.mesh import make_mesh
from tfde_tpu.training.step import (
    init_state,
    make_custom_train_step,
    make_train_step,
)


def _dp_mesh(n=4):
    return make_mesh({"data": -1}, jax.devices()[:n])


# -- config resolution --------------------------------------------------------
def test_resolve_sugar(monkeypatch):
    monkeypatch.delenv(comms.ENV_TRANSPORT, raising=False)
    assert comms.resolve(None).transport == "fp32"
    assert comms.resolve("int8").transport == "int8"
    cfg = comms.CommsConfig(transport="int8", block=64)
    assert comms.resolve(cfg) is cfg
    monkeypatch.setenv(comms.ENV_TRANSPORT, "int8")
    assert comms.resolve(None).transport == "int8"
    with pytest.raises(TypeError):
        comms.resolve(123)
    with pytest.raises(ValueError):
        comms.CommsConfig(transport="int4")
    with pytest.raises(ValueError):
        comms.CommsConfig(block=0)


def test_effective_downgrades_ineligible_meshes():
    int8 = comms.CommsConfig(transport="int8")
    # pure-DP multi-device mesh: int8 survives
    assert comms.effective(int8, _dp_mesh(4)).transport == "int8"
    # single data shard: nothing to exchange
    assert comms.effective(int8, _dp_mesh(1)).transport == "fp32"
    # model axis > 1: params not replicated over the exchange axis
    tp = make_mesh({"data": 2, "tensor": 4}, jax.devices())
    assert comms.effective(int8, tp).transport == "fp32"
    # fp32 passes through untouched regardless of mesh
    fp = comms.CommsConfig()
    assert comms.effective(fp, tp) is fp


def test_strategy_knob_and_env(monkeypatch):
    monkeypatch.delenv(comms.ENV_TRANSPORT, raising=False)
    assert MirroredStrategy().comms.transport == "fp32"
    assert MirroredStrategy(grad_transport="int8").comms.transport == "int8"
    monkeypatch.setenv(comms.ENV_TRANSPORT, "int8")
    assert MirroredStrategy().comms.transport == "int8"
    s = MirroredStrategy()
    s.comms = "fp32"  # explicit setter wins over env
    assert s.comms.transport == "fp32"


# -- packing + residual structure ---------------------------------------------
def test_pack_unpack_roundtrip(rng):
    leaves = [
        jnp.asarray(rng.normal(size=s), jnp.float32)
        for s in [(3, 4), (7,), (2, 2, 2)]
    ]
    vec, shapes = comms.pack(leaves)
    assert vec.shape == (3 * 4 + 7 + 8,)
    out = comms.unpack(vec, shapes)
    for a, b in zip(leaves, out):
        assert jnp.array_equal(a, b)
    empty, eshapes = comms.pack([])
    assert empty.size == 0 and comms.unpack(empty, eshapes) == []


def test_compress_mask_and_residual_structure():
    cfg = comms.CommsConfig(transport="int8", min_elems=100)
    params = {"big": jnp.zeros((50, 4)), "small": jnp.zeros((3,)),
              "nest": {"w": jnp.zeros((200,))}}
    mask = comms.compress_mask(params, cfg)
    assert mask == {"big": True, "small": False, "nest": {"w": True}}
    res = comms.init_residual(params, cfg)
    # congruent structure: compressed leaves full-shape, others scalar stubs
    assert res["big"].shape == (50, 4)
    assert res["small"].shape == ()
    assert res["nest"]["w"].shape == (200,)
    assert jax.tree_util.tree_structure(res) == \
        jax.tree_util.tree_structure(params)


@pytest.mark.parametrize("name", ["toy", "bert_base"])
def test_comm_bytes_ratio_under_bar(name, bert_base_shapes):
    """Bytes a step on the wire, counted from shapes: the int8 transport
    is under 0.3 of the fp32 ring on 8 shards, for a toy tree and for
    BERT-base (110 M parameters, nearly all of them in leaves the
    transport compresses)."""
    cfg = comms.CommsConfig(transport="int8")
    if name == "toy":
        tree = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((1024,))}
    else:
        tree = bert_base_shapes
    b = comms.comm_bytes(tree, cfg, nshards=8)
    assert b["ratio"] <= 0.3, b
    if name == "toy":
        assert b["compressed_elems"] == 1024 * 1024
        assert b["fp32_elems"] == 1024
    else:
        assert b["compressed_elems"] > 100e6 > 1e6 > b["fp32_elems"] > 0
    # fp32 transport reports identical wire cost on both keys
    b32 = comms.comm_bytes(tree, comms.CommsConfig(), nshards=8)
    assert b32["int8"] == b32["fp32"]


# -- the exchange itself ------------------------------------------------------
def _run_exchange(vecs, residuals, cfg, mesh):
    """Run int8_reduce inside shard_map; returns per-device stacked
    (out, new_res, overflow)."""
    n = mesh.devices.size

    def body(v, r):
        out, new_r, ov = comms.int8_reduce(
            v.reshape(-1), r.reshape(-1), cfg, "data", n,
            rng=jax.random.key(0) if cfg.stochastic else None,
        )
        # keep per-device outputs visible: fake a leading device dim
        return out[None], new_r[None], ov[None]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False,
    )
    return f(jnp.stack(vecs), jnp.stack(residuals))


@pytest.mark.parametrize("stochastic", [False, True])
def test_int8_reduce_matches_fp32_sum(rng, stochastic):
    mesh = _dp_mesh(4)
    L = 1000  # deliberately not a multiple of nshards*block
    cfg = comms.CommsConfig(transport="int8", block=64, stochastic=stochastic)
    vecs = [jnp.asarray(rng.normal(size=(L,)), jnp.float32) for _ in range(4)]
    res = [jnp.zeros((L,), jnp.float32) for _ in range(4)]
    out, new_res, ov = _run_exchange(vecs, res, cfg, mesh)
    ref = sum(vecs)
    # every device reconstructs the same bytes
    for d in range(1, 4):
        assert jnp.array_equal(out[0], out[d])
    # blockwise int8 against the shared absmax: per-element error is
    # bounded by ~2 quantization steps of the block absmax (two stages)
    err = jnp.max(jnp.abs(out[0] - ref))
    bound = 2.5 * jnp.max(jnp.abs(ref)) / 127
    assert err < bound, (err, bound)
    assert float(jnp.max(ov)) == 0.0


def test_int8_reduce_error_feedback_identity(rng):
    """The EF invariant: output + sum_devices(new_residual) ==
    sum_devices(input + old_residual) exactly (up to fp32 rounding) — no
    gradient signal is ever lost, only delayed."""
    mesh = _dp_mesh(4)
    L = 512
    cfg = comms.CommsConfig(transport="int8", block=64, stochastic=False)
    vecs = [jnp.asarray(rng.normal(size=(L,)), jnp.float32) for _ in range(4)]
    res = [jnp.asarray(rng.normal(size=(L,)) * 0.01, jnp.float32)
           for _ in range(4)]
    out, new_res, _ = _run_exchange(vecs, res, cfg, mesh)
    total_in = sum(vecs) + sum(res)
    recovered = out[0] + jnp.sum(new_res, axis=0)
    assert jnp.max(jnp.abs(recovered - total_in)) < 1e-4


def test_int8_reduce_overflow_flag(rng):
    mesh = _dp_mesh(4)
    cfg = comms.CommsConfig(transport="int8", block=64, stochastic=False)
    vecs = [jnp.asarray(rng.normal(size=(256,)), jnp.float32)
            for _ in range(4)]
    vecs[2] = vecs[2].at[10].set(jnp.nan)
    res = [jnp.zeros((256,), jnp.float32) for _ in range(4)]
    _, _, ov = _run_exchange(vecs, res, cfg, mesh)
    assert float(jnp.max(ov)) == 1.0


# -- step integration ---------------------------------------------------------
def _cnn_setup(transport, n=4, batch=16, grad_accum=1, sentry=None,
               opt_sharding=None):
    strategy = MirroredStrategy(mesh=_dp_mesh(n), grad_transport=transport,
                                opt_sharding=opt_sharding)
    rng = np.random.default_rng(0)
    images = rng.random((batch, 784), np.float32)
    labels = rng.integers(0, 10, (batch, 1)).astype(np.int32)
    state, _ = init_state(PlainCNN(), optax.sgd(0.1), strategy, images)
    step = make_train_step(strategy, state, grad_accum=grad_accum,
                           sentry=sentry, donate=False)
    return step, state, (images, labels)


def test_fp32_default_is_bit_identical_noop(monkeypatch):
    """grad_transport='fp32' (and unset) must not change the traced program
    at all: identical lowered HLO text."""
    from tfde_tpu.parallel import zero

    monkeypatch.delenv(comms.ENV_TRANSPORT, raising=False)
    monkeypatch.delenv(zero.ENV_OPT_SHARDING, raising=False)
    strategy = MirroredStrategy(mesh=_dp_mesh(4))
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    labels = np.zeros((16, 1), np.int32)
    state, _ = init_state(PlainCNN(), optax.sgd(0.1), strategy, images)
    assert state.comm_residual is None  # state structure untouched

    def loss_fn(state, params, batch, rng):
        from tfde_tpu.training.step import _classification_loss
        return _classification_loss(state, params, batch, rng)

    args = (state, (images, labels), jax.random.key(0))
    base = make_custom_train_step(strategy, state, loss_fn, donate=False)
    explicit = make_custom_train_step(strategy, state, loss_fn, donate=False,
                                      comms="fp32")
    assert base.jitted.lower(*args).as_text() == \
        explicit.jitted.lower(*args).as_text()


def test_int8_without_residual_falls_back(caplog):
    """A state built under fp32 has no residual; asking for int8 at
    step-build time downgrades with a warning instead of crashing."""
    strategy = MirroredStrategy(mesh=_dp_mesh(4))  # fp32 default
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    state, _ = init_state(PlainCNN(), optax.sgd(0.1), strategy, images)
    step = make_train_step(strategy, state, comms="int8", donate=False)
    new_state, m = step(state, (images, np.zeros((16, 1), np.int32)),
                        jax.random.key(0))
    assert "comm_overflow" not in m  # fp32 path ran


def test_int8_step_lowering_collective_count_and_no_callback():
    """The fixed-five-collectives guarantee, pinned from the lowered HLO:
    pmax + fp32-sidecar psum (all_reduce x2), int8 reduce_scatter x1,
    all_gather x2 — independent of model tensor count — and no host
    callback sneaks in (the sentry/async-dispatch contract). Pins the
    REPLICATED budget explicitly — under opt_sharding='shard' the trailing
    gradient all-gather becomes a param all-gather (see
    test_sharded_step_lowering_collective_counts)."""
    step, state, batch = _cnn_setup("int8", opt_sharding="replicated")
    c = hlolint.census(step.jitted, state, batch, jax.random.key(0))
    assert c.callbacks == 0
    assert c.collective_counts == (2, 1, 2)


def test_int8_collective_count_independent_of_grad_accum():
    """Compression happens once per update, AFTER accumulation: the
    collective count must not scale with grad_accum."""
    step, state, batch = _cnn_setup("int8", grad_accum=4,
                                    opt_sharding="replicated")
    c = hlolint.census(step.jitted, state, batch, jax.random.key(0))
    assert c.collective_counts == (2, 1, 2)


def test_sharded_step_lowering_collective_counts():
    """The ZeRO x transport collective budgets, pinned from the lowered
    HLO: fp32 x shard = 3 (fp32-sidecar psum + fp32 reduce_scatter + the
    param all_gather), int8 x shard = 4 (sidecar + pmax all_reduce x2 +
    int8 reduce_scatter + param all_gather). The trailing gradient
    all-gather of the replicated int8 path is REPLACED by the updated-
    param all-gather (grad_norm rides its payload), so every combo stays
    within PR 5's five-collective budget — and no host callback."""
    for transport, budget in [("fp32", (1, 1, 1)), ("int8", (2, 1, 1))]:
        step, state, batch = _cnn_setup(transport, opt_sharding="shard")
        assert state.opt_sharded
        c = hlolint.census(step.jitted, state, batch, jax.random.key(0))
        assert c.callbacks == 0
        assert c.collective_counts == budget, transport


def test_explicit_replicated_pin_keeps_int8_budget_exact(monkeypatch):
    """opt_sharding='replicated' (explicit, env cleared) must leave the
    int8 step exactly as before the ZeRO work: five collectives, no packed
    opt state — the tier1.sh TFDE_OPT_SHARDING=replicated contract."""
    from tfde_tpu.parallel import zero

    monkeypatch.delenv(zero.ENV_OPT_SHARDING, raising=False)
    step, state, batch = _cnn_setup("int8", opt_sharding="replicated")
    assert not state.opt_sharded
    c = hlolint.census(step.jitted, state, batch, jax.random.key(0))
    assert c.collective_counts == (2, 1, 2)


def test_int8_step_runs_and_reports_comm_metrics():
    step, state, batch = _cnn_setup("int8")
    state, m = step(state, batch, jax.random.key(0))
    assert {"loss", "grad_norm", "comm_residual_norm",
            "comm_overflow"} <= set(m)
    assert float(m["comm_overflow"]) == 0.0
    assert np.isfinite(float(m["loss"]))
    # residual becomes nonzero after the first exchange
    state, m = step(state, batch, jax.random.key(0))
    assert float(m["comm_residual_norm"]) > 0.0


def test_int8_loss_trajectory_tracks_fp32_oracle():
    """Short-horizon parity on synthetic data: the compressed trajectory
    must stay within a tight tolerance of the uncompressed psum oracle."""
    steps = 6
    f32_step, f32_state, batch = _cnn_setup("fp32")
    i8_step, i8_state, _ = _cnn_setup("int8")
    key = jax.random.key(0)
    diffs = []
    for _ in range(steps):
        f32_state, mf = f32_step(f32_state, batch, key)
        i8_state, mi = i8_step(i8_state, batch, key)
        diffs.append(abs(float(mf["loss"]) - float(mi["loss"])))
    assert max(diffs) < 0.05, diffs


def test_int8_with_grad_accum_tracks_fp32():
    f32_step, f32_state, batch = _cnn_setup("fp32", grad_accum=4)
    i8_step, i8_state, _ = _cnn_setup("int8", grad_accum=4)
    key = jax.random.key(1)
    for _ in range(4):
        f32_state, mf = f32_step(f32_state, batch, key)
        i8_state, mi = i8_step(i8_state, batch, key)
    assert abs(float(mf["loss"]) - float(mi["loss"])) < 0.05


def test_overflow_trips_sentry_flag():
    """NaN input -> non-finite quantizer scale -> FLAG_COMM_OVERFLOW in the
    fused sentry carry (saturation never passes silently)."""
    step, state, batch = _cnn_setup(
        "int8", sentry=SentryConfig(action="warn"))
    images, labels = batch
    images = images.copy()
    images[0, 0] = np.nan
    sstate = sentry_init()
    state, m, sstate = step(state, (images, labels), jax.random.key(0),
                            sstate)
    assert float(m["comm_overflow"]) == 1.0
    assert int(sstate["flag"]) & FLAG_COMM_OVERFLOW


def test_sentry_res_ewma_tracks_residual():
    step, state, batch = _cnn_setup(
        "int8", sentry=SentryConfig(action="warn"))
    sstate = sentry_init()
    for _ in range(3):
        state, m, sstate = step(state, batch, jax.random.key(0), sstate)
    assert int(sstate["flag"]) == 0
    assert float(sstate["res_ewma"]) > 0.0


@pytest.mark.slow
def test_int8_mnist_trajectory_parity_slow():
    """The satellite acceptance run: int8 + error feedback matches the fp32
    psum oracle's loss trajectory over a short MNIST training run on the
    4-device CPU mesh."""
    from tfde_tpu.data import datasets

    (tx, ty), _ = datasets.mnist(flatten=True, n_train=512, n_test=1)
    batches = [(tx[i * 64:(i + 1) * 64], ty[i * 64:(i + 1) * 64])
               for i in range(8)]

    def run(transport):
        strategy = MirroredStrategy(mesh=_dp_mesh(4),
                                    grad_transport=transport)
        state, _ = init_state(PlainCNN(), optax.sgd(0.2), strategy,
                              batches[0][0])
        step = make_train_step(strategy, state, donate=False)
        key = jax.random.key(0)
        losses = []
        for b in batches * 2:  # 16 steps
            state, m = step(state, b, key)
            losses.append(float(m["loss"]))
        return losses

    fp32 = run("fp32")
    int8 = run("int8")
    # both train...
    assert np.mean(fp32[-3:]) < np.mean(fp32[:3])
    assert np.mean(int8[-3:]) < np.mean(int8[:3])
    # ...and the compressed trajectory tracks the oracle step for step
    diffs = [abs(a - b) for a, b in zip(fp32, int8)]
    assert max(diffs) < 0.1, diffs
