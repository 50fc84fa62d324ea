"""Measured memory ledger (observability/memwatch.py): program
registration publishes honest per-program byte gauges, the donated-alias
estimate keeps the peak below naive arg+out, `device_bytes` agrees with
the ZeRO layer's analytic accounting on the 8-way CPU mesh for both
replicated and sharded optimizer states, the live-array sampler rides
the registry snapshot cadence, and the mem/* gauges round-trip through
the Prometheus text exposition."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tfde_tpu.models.cnn import PlainCNN
from tfde_tpu.observability import exposition, memwatch, metrics, recompile
from tfde_tpu.parallel import zero
from tfde_tpu.parallel.strategies import MirroredStrategy
from tfde_tpu.runtime.mesh import make_mesh
from tfde_tpu.training.step import init_state


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # the ledger must see its default 'on' mode, not tier1.sh's override,
    # and every test starts from an empty program table / compile ledger
    monkeypatch.delenv(memwatch.ENV_MEMWATCH, raising=False)
    memwatch.reset()
    recompile.reset()
    yield
    memwatch.reset()
    recompile.reset()


def test_resolve_modes():
    assert memwatch.resolve("on") == "on"
    assert memwatch.resolve("") == "on"
    assert memwatch.resolve("1") == "on"
    assert memwatch.resolve("off") == "off"
    assert memwatch.resolve("0") == "off"
    assert memwatch.resolve("full") == "full"
    assert memwatch.resolve("measured") == "full"
    assert memwatch.resolve("garbage") == "on"  # warn + default


def test_register_publishes_gauges():
    @jax.jit
    def f(x):
        return x @ x.T

    x = jnp.ones((16, 32), jnp.float32)
    pm = memwatch.register("t/matmul", f, args=(x,))
    assert pm is not None
    assert pm.argument_bytes == x.nbytes
    assert pm.output_bytes == 16 * 16 * 4
    assert pm.peak_bytes >= max(pm.argument_bytes, pm.output_bytes)
    reg = metrics.default_registry()
    flat = metrics.flatten_snapshot(reg.snapshot())
    assert flat["mem/t/matmul/peak_bytes"] == pm.peak_bytes
    assert flat["mem/t/matmul/argument_bytes"] == x.nbytes
    assert "mem/t/matmul/measured" in flat
    assert memwatch.programs()["t/matmul"].name == "t/matmul"


def test_donated_args_reduce_peak_estimate():
    @jax.jit
    def f(x):
        return x + 1.0

    x = jnp.ones((64, 64), jnp.float32)
    no_alias = memwatch.register("t/plain", f, args=(x,))
    aliased = memwatch.register("t/donated", f, args=(x,), donated=x)
    assert aliased.alias_bytes == x.nbytes
    # arg+out-alias collapses to one buffer's worth; plain pays for two
    assert aliased.peak_bytes < no_alias.peak_bytes
    assert aliased.peak_bytes == max(aliased.argument_bytes,
                                     aliased.output_bytes)


def test_register_off_mode_is_noop():
    pm = memwatch.register("t/off", lambda x: x, args=(jnp.ones(4),),
                           mode="off")
    assert pm is None
    assert "t/off" not in memwatch.programs()


def test_register_never_raises_on_bad_program():
    # eval_shape on a fn that throws: the ledger logs once and moves on
    def bad(x):
        raise ValueError("boom")

    assert memwatch.register("t/bad", bad, args=(jnp.ones(4),)) is None
    assert "t/bad" not in memwatch.programs()


def test_full_mode_compile_is_suppressed_from_sentinel():
    recompile.install()

    @jax.jit
    def f(x):
        return jnp.sin(x) * 2.0

    # build the argument first: jnp.ones is itself a (legitimate) process
    # compile and must not be confused with the ledger's AOT compile
    x = jax.block_until_ready(jnp.ones((8, 8)))
    before = recompile.process_compiles()
    pm = memwatch.register("t/full", f, args=(x,), mode="full")
    assert pm is not None
    assert pm.peak_bytes > 0
    # the AOT lower+compile for the ledger must not read as a process
    # compile (it runs under recompile.suppress())
    assert recompile.process_compiles() == before


def _dp_mesh(n=8):
    return make_mesh({"data": -1}, jax.devices()[:n])


def _opt_state(opt_sharding):
    strategy = MirroredStrategy(mesh=_dp_mesh(), grad_transport="fp32",
                                opt_sharding=opt_sharding)
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    state, _ = init_state(PlainCNN(), optax.adam(1e-2), strategy, images)
    return state


def test_device_bytes_vs_analytic_zero_accounting(monkeypatch):
    monkeypatch.delenv(zero.ENV_OPT_SHARDING, raising=False)
    rep = _opt_state("replicated")
    shd = _opt_state("shard")
    for state in (rep, shd):
        analytic = zero.state_bytes(state.opt_state, state.opt_layout)
        measured = memwatch.device_bytes(state.opt_state)
        assert measured == pytest.approx(analytic, rel=0.2)
        assert zero.measured_state_bytes(state.opt_state) == measured
    # the point of ZeRO: per-device measured bytes drop ~8x on the 8-way
    # mesh (padding keeps it from being exactly 1/8)
    ratio = (memwatch.device_bytes(shd.opt_state)
             / memwatch.device_bytes(rep.opt_state))
    assert ratio == pytest.approx(1 / 8, rel=0.2)


def test_live_sampler_sees_device_buffers():
    marker = jnp.ones((128, 128), jnp.float32)  # 64 KiB, easy to spot
    sample = memwatch.sample_live(top_k=4)
    assert sample["bytes"] >= marker.nbytes
    assert sample["buffers"] >= 1
    assert len(sample["top"]) <= 4
    sizes = [row["bytes"] for row in sample["top"]]
    assert sizes == sorted(sizes, reverse=True)
    # among ALL live buffers: whether 64 KiB is one of the four largest
    # depends on what the files this xdist worker ran before still hold
    everything = memwatch.sample_live(top_k=sample["buffers"] + 1)["top"]
    assert any(row["shape"] == [128, 128] for row in everything)
    del marker


def test_collector_rides_snapshot_cadence():
    reg = metrics.Registry()
    ledger = memwatch.MemoryLedger(registry=reg)
    assert "mem/live/bytes" not in reg.snapshot()
    ledger.install_collector()
    ledger.install_collector()  # idempotent
    marker = jnp.ones((64, 64), jnp.float32)  # keep one buffer live
    flat = metrics.flatten_snapshot(reg.snapshot())
    del marker
    assert flat["mem/live/bytes"] > 0
    assert flat["mem/live/buffers"] >= 1
    assert flat["mem/live/largest_bytes"] <= flat["mem/live/bytes"]


def test_mem_gauges_roundtrip_prometheus():
    reg = metrics.Registry()
    ledger = memwatch.MemoryLedger(registry=reg)

    @jax.jit
    def f(x):
        return x * 2

    pm = ledger.register("t/rt", f, args=(jnp.ones((32, 8)),))
    text = exposition.to_prometheus_text(registry=reg)
    parsed = exposition.parse_prometheus_text(text)
    pname = exposition.prom_name("mem/t/rt/peak_bytes")
    assert parsed[pname]["type"] == "gauge"
    assert parsed[pname]["value"] == float(pm.peak_bytes)
