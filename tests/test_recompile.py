"""Recompile sentinel (observability/recompile.py): hit/miss counting
against real XLA compiles, bucket-churn storm escalation through the
flight recorder, compile/miss trace breadcrumbs carrying the victim
request ids, the steady-state decode pin (a draining ContinuousBatcher
must produce ZERO unexpected misses), and the memgate gate logic that
turns these counters into a tier-1 failure."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.observability import (flightrec, memwatch, metrics, recompile,
                                    trace)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    recompile.reset()
    memwatch.reset()
    yield
    recompile.reset()
    memwatch.reset()
    trace.disable()


def _flat():
    return metrics.flatten_snapshot(metrics.default_registry().snapshot())


def test_hit_miss_counting():
    @jax.jit
    def f(x):
        return x * 3.0

    s = recompile.site("t/probe")
    with s.watch((4,)):
        f(jnp.ones(4))  # novel fingerprint, real compile -> expected miss
    with s.watch((4,)):
        f(jnp.ones(4))  # cache hit
    with s.watch((8,)):
        f(jnp.ones(8))  # second bucket: novel again
    snap = s.snapshot()
    assert snap["hits"] == 1
    assert snap["misses"] == 2
    assert snap["signatures"] == 2
    assert snap["unexpected"] == 0
    flat = _flat()
    assert flat["compile/t/probe/misses"] == 2
    assert flat["compile/t/probe/cache_hits"] == 1
    assert flat["compile/t/probe/signatures"] == 2
    if recompile.install():  # monitoring hook present on this JAX
        assert flat["compile/t/probe/seconds_total"] > 0
        assert recompile.process_compiles() >= 2
        assert recompile.seconds_total() > 0
    assert recompile.sites()["t/probe"]["misses"] == 2


def test_stable_site_flags_signatures_past_budget():
    @jax.jit
    def f(x):
        return x + 1.0

    s = recompile.site("t/stable", stable=True, expect=1)
    with s.watch("a"):
        f(jnp.ones(3))
    assert s.unexpected == 0  # first signature is within the budget
    with s.watch("b"):
        f(jnp.ones(5))  # novel, but past expect=1 on a stable site
    assert s.unexpected == 1
    assert _flat()["compile/t/stable/unexpected"] == 1


def test_storm_detection_and_breadcrumbs():
    @jax.jit
    def f(x):
        return jnp.cos(x)

    s = recompile.site("t/storm", storm_threshold=2)
    rec = flightrec.default_recorder()
    for i in range(5):
        with s.watch("pinned-bucket"):
            # a DIFFERENT shape every call forces a real compile while
            # the fingerprint claims nothing changed — cache thrash
            f(jnp.ones(16 + i))
    assert s.misses == 5
    assert s.unexpected == 4  # first call was genuinely novel
    # select by this test's unique site name, not by buffer position:
    # the recorder is a bounded ring shared with every test before this
    # one, so len(events()) plateaus at capacity and an index slice
    # taken when full would always come back empty
    new = [e for e in rec.events() if e.get("site") == "t/storm"]
    crumbs = [e for e in new if e["kind"] == "recompile"]
    assert len(crumbs) == 5
    assert all(e["site"] == "t/storm" for e in crumbs)
    assert [e["unexpected"] for e in crumbs] == [False, True, True, True,
                                                 True]
    storms = [e for e in new if e["kind"] == "recompile_storm"]
    assert len(storms) == 1  # escalates once, not per miss
    assert storms[0]["site"] == "t/storm"
    assert _flat()["compile/storms"] == 1


def test_miss_emits_trace_event_with_victims():
    trace.enable(256)
    trace.clear()

    @jax.jit
    def f(x):
        return x - 1.0

    s = recompile.site("t/traced")
    with s.watch((7,), traces=["req-a", "req-b"]):
        f(jnp.ones(7))
    evs = [e for e in trace.events() if e["name"] == "compile/miss"]
    assert len(evs) == 1
    assert evs[0]["site"] == "t/traced"
    assert evs[0]["traces"] == ["req-a", "req-b"]
    # the victim's own waterfall shows the compile that stalled it
    assert any(e["name"] == "compile/miss"
               for e in trace.events("req-a"))


def test_suppress_routes_to_ledger_overhead():
    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")

    @jax.jit
    def f(x):
        return x @ x

    before = recompile.process_compiles()
    with recompile.suppress():
        f(jnp.ones((6, 6)))
    assert recompile.process_compiles() == before
    assert _flat()["compile/memwatch_seconds_total"] > 0


@pytest.mark.parametrize("transport", ["fp32", "int8"])
@pytest.mark.parametrize("opt_sharding", ["replicated", "shard"])
def test_train_step_compiles_in_its_first_call_only(opt_sharding, transport):
    """Each transport x sharding combination of the data-parallel step
    compiles when first called and never again: the second and third
    steps (their inputs are the first step's outputs, the shapes and
    shardings a steady loop feeds back) add no process compile."""
    import optax

    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.parallel.strategies import MirroredStrategy
    from tfde_tpu.training.step import init_state, make_train_step

    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")
    strategy = MirroredStrategy(grad_transport=transport,
                                opt_sharding=opt_sharding)
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
    state, _ = init_state(PlainCNN(), optax.adam(1e-2), strategy, images)
    step = make_train_step(strategy, state, donate=False)
    before = recompile.process_compiles()
    state, m = step(state, (images, labels), jax.random.key(0))
    jax.block_until_ready(m["loss"])
    first = recompile.process_compiles()
    assert first > before
    for i in (1, 2):
        state, m = step(state, (images, labels), jax.random.key(i))
        jax.block_until_ready(m["loss"])
    assert recompile.process_compiles() == first


def test_steady_state_decode_has_zero_unexpected_misses(rng):
    from tfde_tpu.inference.server import ContinuousBatcher
    from tfde_tpu.models.gpt import GPT

    # deliberately odd sizes: flax modules hash by field values, so a
    # config another test already decoded with would land warm in the
    # process-wide jit cache and this batcher would (correctly) report
    # all hits — the pin below tolerates that, but a fresh program
    # exercises the novel-miss path too
    model = GPT(vocab_size=89, hidden_size=24, depth=2, num_heads=3,
                mlp_dim=48, max_position=64, dtype=jnp.float32)
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=32,
                            scan_depth=4)
    for plen, n in [(3, 10), (5, 8), (4, 12)]:
        srv.submit(rng.integers(0, 88, plen).astype(np.int64), n)
    srv.run()
    assert srv.idle
    snap = recompile.sites()["serve/decode"]
    # THE pin: the depth ladder (1,2,4) compiles at most once per depth,
    # every one of them a novel fingerprint; steady-state full-depth
    # steps must all be cache hits — zero unexpected misses
    assert snap["unexpected"] == 0
    assert snap["misses"] <= 3
    assert snap["hits"] >= 1
    for name, s in recompile.sites().items():
        if name.startswith("serve/"):
            assert s["unexpected"] == 0, name


def _memgate():
    spec = importlib.util.spec_from_file_location(
        "memgate", os.path.join(ROOT, "tools", "memgate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_memgate_check_fails_on_recompile_regression():
    mg = _memgate()
    base = {"sites": {"serve/decode": {"misses": 3}},
            "programs": {"serve/decode/k4": {"peak_bytes": 1000}}}
    ok = {"sites": {"serve/decode": {"misses": 3}},
          "programs": {"serve/decode/k4": {"peak_bytes": 1000}}}
    assert mg.check(ok, base) == []
    # the injected per-token-recompile pathology: miss count blows past
    # the pinned budget -> the gate must fail
    thrash = {"sites": {"serve/decode": {"misses": 40}},
              "programs": {"serve/decode/k4": {"peak_bytes": 1000}}}
    fails = mg.check(thrash, base)
    assert len(fails) == 1 and "serve/decode" in fails[0]
    assert "40" in fails[0] and "baseline 3" in fails[0]
    # a site the baseline has never seen fails loudly with the
    # re-baseline instruction
    novel = {"sites": {"serve/decode": {"misses": 3},
                       "serve/prefill/new": {"misses": 1}},
             "programs": {"serve/decode/k4": {"peak_bytes": 1000}}}
    assert any("--update" in f for f in mg.check(novel, base))
    # peak-HBM ceiling: slack absorbs drift, a blow-up fails
    within = {"sites": {"serve/decode": {"misses": 3}},
              "programs": {"serve/decode/k4": {"peak_bytes": 1100}}}
    assert mg.check(within, base) == []
    blowup = {"sites": {"serve/decode": {"misses": 3}},
              "programs": {"serve/decode/k4": {"peak_bytes": 1101}}}
    fails = mg.check(blowup, base)
    assert len(fails) == 1 and "ceiling" in fails[0]


def test_memgate_committed_baseline_is_self_consistent():
    mg = _memgate()
    with open(os.path.join(ROOT, "tools", "memgate_baseline.json")) as f:
        base = json.load(f)
    # the baseline must gate the exact observation it was generated from
    obs = {"sites": base["sites"], "programs": base["programs"]}
    assert mg.check(obs, base) == []
    assert "train_step" in base["sites"]
    assert "serve/decode" in base["sites"]
    assert any(n.startswith("serve/prefill") for n in base["programs"])


@pytest.mark.slow
def test_memgate_injection_fails_end_to_end():
    """Acceptance pin: the real gate binary, the real batcher, a genuine
    per-token static-arg churn — memgate --check must exit nonzero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TFDE_MEMWATCH="on",
               TFDE_MEMGATE_INJECT="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "memgate.py"),
         "--check"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "compiles > baseline" in proc.stdout
