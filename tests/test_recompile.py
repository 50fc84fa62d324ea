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
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=200)
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "compiles > baseline" in proc.stdout


# --- the set-up ledger (PR 37) ---------------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
WRITTEN = "/jax/compilation_cache/cache_misses"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"
STAGES = ("trace_ns", "lower_ns", "backend_ns")


def _ms(x):
    return x * 1e-3   # the scripts below are written in milliseconds


def _program(name, t, trace=2.0, lower=3.0, backend=5.0, children=(),
             cache=None, read=0.0):
    """Play jax's events for one top-level program `name` starting at `t`
    ms: its nested traces (name, offset, length), the outermost trace, the
    lowering, what the cache said, the backend compile. Returns the end."""
    for child, off, length in children:
        recompile._on_span(TRACE, _ms(t + off), _ms(t + off + length),
                           fun_name=child)
    recompile._on_span(TRACE, _ms(t), _ms(t + trace), fun_name=name)
    t += trace
    recompile._on_span(LOWER, _ms(t), _ms(t + lower), fun_name=f"jit({name})")
    t += lower
    if cache is not None:
        recompile._on_count(ASKED)
        if cache:
            recompile._on_count(HIT)
            recompile._on_duration(READ, _ms(read))
        else:
            recompile._on_count(WRITTEN)
    recompile._on_span(BACKEND, _ms(t), _ms(t + backend),
                       fun_name=f"jit({name})")
    return t + backend


def _near(ns, ms):
    return abs(ns - ms * 1e6) <= 2   # float seconds to whole nanoseconds


def test_scripted_stream_gives_the_stage_integers():
    s = recompile.site("t/script")
    with s.watch("fp", 1):
        _program("wave", 1000.0, trace=2.0, lower=3.0, backend=5.0)
    snap = s.snapshot()
    (ep,) = snap["episodes"]
    assert ep["fun_name"] == "jit(wave)" and ep["fingerprint"] == ("fp", 1)
    assert _near(ep["trace_ns"], 2.0) and _near(ep["lower_ns"], 3.0)
    assert _near(ep["backend_ns"], 5.0)
    assert ep["cache_read_ns"] == 0 and ep["cache_hit"] is None
    assert ep["nested_traces"] == 0 and ep["compiles"] == 1
    assert all(isinstance(ep[k], int) for k in STAGES)
    for k in STAGES + ("cache_read_ns", "programs", "nested_traces"):
        assert snap[k] == recompile.setup()["sited"][k]
    assert snap["programs"] == 1 and snap["misses"] == 1


def test_nested_traces_fold_into_the_outermost_and_are_counted():
    s = recompile.site("t/nested")
    children = [("tanh", 0.2, 0.1), ("matmul", 0.4, 0.2),
                ("inner", 0.1, 0.6),           # holds the two before it
                ("inner", 0.8, 0.0), ("inner", 0.9, 0.0),   # cached: no time
                ("_reduce_sum", 1.0, 0.3)]
    with s.watch("fp"):
        _program("outer", 50.0, trace=2.9, children=children)
    (ep,) = s.snapshot()["episodes"]
    assert _near(ep["trace_ns"], 2.9)   # the outermost span alone
    assert ep["nested_traces"] == 6
    assert recompile._thread.spans == []   # no child outlives its parent


def test_a_trace_inside_the_lowering_adds_no_time():
    s = recompile.site("t/lowering")
    with s.watch("fp"):
        recompile._on_span(TRACE, _ms(10.0), _ms(12.0), fun_name="f")
        # a lowering rule traces a jitted helper: inside the lowering span
        recompile._on_span(TRACE, _ms(12.5), _ms(13.0), fun_name="helper")
        recompile._on_span(LOWER, _ms(12.0), _ms(15.0), fun_name="jit(f)")
        recompile._on_span(BACKEND, _ms(15.0), _ms(16.0), fun_name="jit(f)")
    (ep,) = s.snapshot()["episodes"]
    assert _near(ep["trace_ns"], 2.0) and _near(ep["lower_ns"], 3.0)
    assert ep["nested_traces"] == 1


@pytest.mark.parametrize("cache, read, hit, misses", [
    (None, 0.0, None, 0),      # the program never asked the cache
    (False, 0.0, False, 1),    # asked, compiled, written
    (True, 1.5, True, 0),      # found: the read is INSIDE the backend span
])
def test_cache_events_mark_the_episode(cache, read, hit, misses):
    s = recompile.site("t/cache")
    with s.watch("fp"):
        _program("wave", 0.0, backend=5.0, cache=cache, read=read)
    snap = s.snapshot()
    (ep,) = snap["episodes"]
    assert ep["cache_hit"] is hit
    assert _near(ep["cache_read_ns"], read)
    assert _near(ep["backend_ns"], 5.0)   # never backend + read
    assert snap["cache_misses"] == misses
    assert _near(snap["seconds"] * 1e9, 2.0 + 3.0 + 5.0)
    # the next program starts clean
    with s.watch("fp2"):
        _program("wave", 100.0)
    assert s.snapshot()["episodes"][1]["cache_hit"] is None


def test_asked_and_neither_found_nor_written_is_a_miss():
    """Below jax's thresholds a compiled program is not written, and no
    `cache_misses` event fires: it still was not found."""
    s = recompile.site("t/unwritten")
    with s.watch("fp"):
        recompile._on_span(TRACE, 0.0, 0.001, fun_name="f")
        recompile._on_span(LOWER, 0.001, 0.002, fun_name="jit(f)")
        recompile._on_count(ASKED)
        recompile._on_span(BACKEND, 0.002, 0.003, fun_name="jit(f)")
    assert s.snapshot()["episodes"][0]["cache_hit"] is False
    assert recompile.setup()["sited"]["cache_misses"] == 1


def test_a_wave_keeps_each_of_its_programs_and_the_calls_wall():
    s = recompile.site("t/wave")
    with s.watch("b", 512, 2):
        t = _program("_prefill_rows", 0.0)
        t = _program("_zero_rows", t + 1.0, trace=0.5, lower=0.5, backend=1.0)
        _program("_scatter", t + 1.0, trace=0.5, lower=0.5, backend=1.0)
    snap = s.snapshot()
    assert [e["fun_name"] for e in snap["episodes"]] == [
        "jit(_prefill_rows)", "jit(_zero_rows)", "jit(_scatter)"]
    walls = {e["wall_ns"] for e in snap["episodes"]}
    assert len(walls) == 1 and walls.pop() == snap["first_call_ns"] > 0
    assert len({e["t0_ns"] for e in snap["episodes"]}) == 1
    assert snap["programs"] == 3 and snap["misses"] == 1
    # a second call of the shape compiles nothing and adds nothing
    with s.watch("b", 512, 2):
        pass
    again = s.snapshot()
    assert again["hits"] == 1 and again["episodes"] == snap["episodes"]
    assert again["first_call_ns"] == snap["first_call_ns"]


def test_a_program_that_compiles_again_adds_to_its_entry():
    s = recompile.site("t/thrash")
    for t in (0.0, 100.0):
        with s.watch("pinned"):
            _program("scan", t, cache=False)
    snap = s.snapshot()
    (ep,) = snap["episodes"]
    assert ep["compiles"] == 2 and _near(ep["backend_ns"], 10.0)
    assert snap["programs"] == 2 and snap["cache_misses"] == 2
    assert snap["unexpected"] == 1


def test_claim_takes_the_top_level_program_and_leaves_the_nested_alone():
    s = recompile.site("t/claimed").claim("step")
    # `step` traced inside another program is that program's nested trace
    _program("wrapper", 0.0, trace=3.0, children=[("step", 0.5, 2.0)])
    assert s.snapshot()["episodes"] == [] and s.snapshot()["programs"] == 0
    ledger = recompile.setup()
    assert ledger["unsited"]["programs"] == 1
    assert ledger["unsited"]["nested_traces"] == 1
    # top level, no watch open: the claim's
    _program("step", 100.0, cache=False)
    snap = s.snapshot()
    (ep,) = snap["episodes"]
    assert ep["fun_name"] == "jit(step)" and ep["fingerprint"] == ()
    assert ep["wall_ns"] is None and ep["t0_ns"] > 0
    assert snap["programs"] == 1 and snap["cache_misses"] == 1
    # a watch counts its calls; a claim has none
    assert snap["misses"] == 0 and snap["hits"] == 0
    assert recompile.setup()["sited"]["first_call_ns"] == 0
    assert "compile/t/claimed/seconds_total" not in {
        k for k, v in _flat().items() if v}
    # inside a watch the watch's site has it
    w = recompile.site("t/watcher")
    with w.watch("fp"):
        _program("step", 200.0)
    assert w.snapshot()["programs"] == 1 and s.snapshot()["programs"] == 1


def test_traces_and_lowerings_that_make_no_program_are_still_counted():
    """`jax.eval_shape` traces and compiles nothing; `.lower()` stops
    before the backend: their time is in the totals, no episode is made."""
    recompile._on_span(TRACE, _ms(0.0), _ms(4.0), fun_name="shape_only")
    recompile._on_span(TRACE, _ms(10.0), _ms(11.0), fun_name="aot")
    recompile._on_span(LOWER, _ms(11.0), _ms(13.0), fun_name="jit(aot)")
    assert recompile.setup()["unsited"]["trace_ns"] > 0   # eval_shape's
    assert list(recompile._thread.traced) == ["shape_only"]
    _program("next", 20.0)   # the next program finds both settled
    u = recompile.setup()["unsited"]
    assert _near(u["trace_ns"], 4.0 + 1.0 + 2.0)
    assert _near(u["lower_ns"], 2.0 + 3.0)
    assert u["programs"] == 1
    # compiled later from that lowering: a program with a backend stage only
    recompile._on_span(BACKEND, _ms(40.0), _ms(41.0), fun_name="jit(aot)")
    u = recompile.setup()["unsited"]
    assert u["programs"] == 2 and _near(u["backend_ns"], 5.0 + 1.0)


def test_suppress_diverts_lowering_and_compile_and_keeps_the_trace():
    """memwatch's AOT compile of a program is the ledger's overhead and no
    program of the process; the trace it pays is the program's own, which
    the call that follows finds in jax's cache."""
    s = recompile.site("t/interrogated")
    with s.watch("fp"):
        with recompile.suppress():
            _program("wave", 0.0, trace=2.0, lower=3.0, backend=5.0,
                     cache=False, children=[("layer", 0.5, 1.0)])
            recompile._on_span(TRACE, 1.0, 1.001, fun_name="shape_only")
        assert recompile.process_compiles() == 0
        snap = s.snapshot()
        assert snap["episodes"] == [] and snap["programs"] == 0
        assert _near(snap["trace_ns"], 2.0 + 1.0)
        assert snap["lower_ns"] == snap["backend_ns"] == 0
        assert snap["nested_traces"] == 1 and snap["cache_misses"] == 0
        assert _flat()["compile/memwatch_seconds_total"] == pytest.approx(
            0.003 + 0.005)
        # the program's own call: traced already, lowered and compiled now
        _program("wave", 2000.0, trace=0.04, lower=3.0, backend=5.0)
    snap = s.snapshot()
    assert snap["programs"] == snap["misses"] == 1
    assert _near(snap["trace_ns"], 3.0 + 0.04)
    # its own line shows the trace its interrogation paid, counted once
    (ep,) = snap["episodes"]
    assert _near(ep["trace_ns"], 2.0 + 0.04) and ep["nested_traces"] == 1
    # what nobody has called yet waits under its name, and only that
    assert list(recompile._thread.traced) == ["shape_only"]
    assert recompile.process_compiles() == 1
    assert recompile.setup()["unsited"]["trace_ns"] == 0


def test_one_count_the_old_readers_and_the_ledger_agree():
    s = recompile.site("t/sum")
    with s.watch("fp"):
        _program("a", 0.0, trace=2.9, children=[("inner", 0.1, 0.6)])
    _program("eager", 100.0, trace=0.5, lower=1.0, backend=2.0)
    ledger = recompile.setup()
    ns = sum(ledger[part][k] for part in ("sited", "unsited") for k in STAGES)
    assert recompile.seconds_total() == pytest.approx(ns * 1e-9, rel=1e-12)
    assert _near(ns, 2.9 + 3.0 + 5.0 + 0.5 + 1.0 + 2.0)   # `inner` once
    assert recompile.process_compiles() == 2 == (
        ledger["sited"]["programs"] + ledger["unsited"]["programs"])
    flat = _flat()
    assert flat["compile/seconds_total"] == pytest.approx(ns * 1e-9)
    assert flat["compile/process_compiles"] == 2
    assert flat["compile/t/sum/seconds_total"] == pytest.approx(
        s.seconds) == pytest.approx(0.0109)
    snap = s.snapshot()
    assert {"hits", "misses", "seconds", "signatures",
            "unexpected"} <= set(snap)
    assert (snap["hits"], snap["misses"], snap["signatures"],
            snap["unexpected"]) == (0, 1, 1, 0)
    assert ledger["sites"]["t/sum"] == snap
    assert set(ledger["sited"]) == set(ledger["unsited"]) == {
        "trace_ns", "lower_ns", "backend_ns", "cache_read_ns",
        "first_call_ns", "programs", "cache_misses", "nested_traces"}


def test_reset_starts_the_ledger_from_zero():
    recompile.site("t/reset").claim("f")
    _program("f", 0.0)
    recompile.reset()
    ledger = recompile.setup()
    assert not any(ledger["sited"].values())
    assert not any(ledger["unsited"].values()) and ledger["sites"] == {}
    _program("f", 10.0)   # the claim went with the site
    assert recompile.setup()["unsited"]["programs"] == 1


def test_a_clock_stepped_back_reads_zero_and_not_below():
    # jax times its stages on time.time(); a counter refuses to go down
    s = recompile.site("t/clock")
    with s.watch("fp"):
        recompile._on_span(TRACE, 5.0, 4.0, fun_name="f")
        recompile._on_span(LOWER, 4.0, 3.5, fun_name="jit(f)")
        recompile._on_span(BACKEND, 3.5, 3.0, fun_name="jit(f)")
    (ep,) = s.snapshot()["episodes"]
    assert [ep[k] for k in STAGES] == [0, 0, 0]
    assert recompile.process_compiles() == 1
    assert recompile.seconds_total() == 0.0


@pytest.mark.parametrize("listener, args", [
    ("_on_span", (BACKEND, 0.0, 0.001)),
    ("_on_count", (HIT,)),
    ("_on_duration", (READ, 0.001)),
])
def test_a_fault_in_the_ledger_is_logged_and_never_raised(
        listener, args, monkeypatch, caplog):
    class Broken:
        def __getattr__(self, name):
            raise RuntimeError("the ledger's own fault")

        __setattr__ = __getattr__

    monkeypatch.setattr(recompile, "_thread", Broken())
    with caplog.at_level("WARNING", logger=recompile.log.name):
        assert getattr(recompile, listener)(*args, fun_name="jit(f)") is None
    assert "dropped an event" in caplog.text


def test_real_jit_nested_and_eager():
    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")

    @jax.jit
    def inner(x):
        return jnp.tanh(x) @ x

    @jax.jit
    def outer(x):
        for _ in range(3):
            x = inner(x)
        return x.sum()

    # eager, at a shape no other test uses: compiles outside every site
    x = jnp.ones((13, 13))
    unsited = recompile.setup()["unsited"]
    assert unsited["programs"] >= 1 and unsited["backend_ns"] > 0
    s = recompile.site("t/real")
    with s.watch((13, 13)):
        outer(x).block_until_ready()
    snap = s.snapshot()
    (ep,) = [e for e in snap["episodes"] if e["fun_name"] == "jit(outer)"]
    assert ep["nested_traces"] >= 5   # tanh, matmul, inner x 3, the sum
    assert ep["trace_ns"] > 0 and ep["lower_ns"] > 0 and ep["backend_ns"] > 0
    assert ep["wall_ns"] >= sum(
        e[k] for e in snap["episodes"] for k in STAGES)
    assert recompile.setup()["unsited"] == unsited
    assert recompile.seconds_total() == pytest.approx(1e-9 * sum(
        recompile.setup()[p][k] for p in ("sited", "unsited")
        for k in STAGES))


@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache in a directory of the test's
    own, every program kept; off again afterwards, as conftest leaves it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_persistent_cache_miss_then_hit(persistent_cache):
    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")

    def f(x):
        return jnp.sin(x) * 41.0 + x   # no other test compiles this

    s = recompile.site("t/persistent")
    x = jnp.ones((6, 7))
    with s.watch("cold"):
        jax.jit(f)(x).block_until_ready()
    (cold,) = [e for e in s.snapshot()["episodes"]
               if e["fun_name"] == "jit(f)"]
    assert cold["cache_hit"] is False and cold["cache_read_ns"] == 0
    assert os.listdir(persistent_cache)   # written
    jax.clear_caches()   # the process forgets; the directory does not
    with s.watch("warm"):
        jax.jit(f)(x).block_until_ready()
    (warm,) = [e for e in s.snapshot()["episodes"]
               if e["fun_name"] == "jit(f)" and e["fingerprint"] == ("warm",)]
    assert warm["cache_hit"] is True
    assert 0 < warm["cache_read_ns"] <= warm["backend_ns"]
    assert warm["trace_ns"] > 0 and warm["lower_ns"] > 0   # still paid
    assert s.snapshot()["cache_misses"] == sum(
        e["cache_hit"] is False for e in s.snapshot()["episodes"])


def test_train_step_is_claimed_without_a_wrapper():
    import optax

    from tfde_tpu.models.cnn import PlainCNN
    from tfde_tpu.parallel.strategies import MirroredStrategy
    from tfde_tpu.training import step as step_lib

    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")
    strategy = MirroredStrategy()
    rng = np.random.default_rng(0)
    images = rng.random((16, 784), np.float32)
    labels = rng.integers(0, 10, (16, 1)).astype(np.int32)
    state, _ = step_lib.init_state(PlainCNN(), optax.sgd(0.1), strategy,
                                   images)
    init = recompile.sites()["train/init"]
    assert [e["fun_name"] for e in init["episodes"]] == ["jit(init_fn)"]
    # `jax.eval_shape(init_fn)` traced it first and the jit found it
    # traced: the site's time and the program's line hold that trace once
    assert init["trace_ns"] == init["episodes"][0]["trace_ns"] > 100_000

    def loss_fn(state, params, batch, rng):
        x, y = batch
        logits = state.apply_fn({"params": params}, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y[:, 0]).mean(), {}

    step = step_lib.make_custom_train_step(strategy, state, loss_fn,
                                           donate=False)
    # nothing stands between the caller and jax's own jitted function
    assert step.lower == step.jitted.lower
    assert recompile.sites()["train/step"]["programs"] == 0
    for i in range(3):
        state, m = step(state, (images, labels), jax.random.key(i))
    jax.block_until_ready(m["loss"])
    snap = recompile.sites()["train/step"]
    (ep,) = snap["episodes"]
    assert ep["fun_name"] == f"jit({step.jitted.__name__})"
    assert ep["compiles"] == 1 and ep["wall_ns"] is None
    assert ep["trace_ns"] > 0 and ep["backend_ns"] > 0
    assert ep["nested_traces"] > 0
    assert snap["hits"] == snap["misses"] == 0   # no watch, no call counted
    assert recompile.setup()["sited"]["programs"] >= 2


def test_batcher_warm_up_files_every_program_under_its_fingerprint(rng):
    from tfde_tpu.inference.server import ContinuousBatcher
    from tfde_tpu.models.gpt import GPT

    if not recompile.install():
        pytest.skip("no jax.monitoring hook on this JAX")
    model = GPT(vocab_size=83, hidden_size=24, depth=2, num_heads=3,
                mlp_dim=48, max_position=64, dtype=jnp.float32)
    params = model.init(jax.random.key(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=32,
                            scan_depth=4, prompt_buckets=(8, 32))

    def wave(plens, n):
        for plen in plens:
            srv.submit(rng.integers(0, 82, plen).astype(np.int64), n)
        srv.run()

    wave((3, 5), 9)      # bucket 8, two rows wide; depths 4, ...
    wave((12,), 3)       # bucket 32, one row
    ledger = recompile.setup()
    prefill = ledger["sites"]["serve/prefill_cold"]
    decode = ledger["sites"]["serve/decode"]
    shapes = {e["fingerprint"][-2:] for e in prefill["episodes"]}
    assert shapes == {(8, 2), (32, 1)}
    depths = {e["fingerprint"][-1] for e in decode["episodes"]}
    assert depths and depths <= {1, 2, 4}
    assert decode["programs"] == decode["misses"] == len(depths)
    for snap in (prefill, decode):
        by_call = {}
        for e in snap["episodes"]:
            assert e["compiles"] == 1
            by_call.setdefault(e["fingerprint"], []).append(e)
        for episodes in by_call.values():
            wall = {e["wall_ns"] for e in episodes}
            assert len(wall) == 1   # one watched call compiled them all
            # a scan is traced where the memory ledger interrogates it,
            # before its watch opens; a wave inside its watch
            inside = STAGES if snap is prefill else STAGES[1:]
            assert wall.pop() >= sum(e[k] for e in episodes for k in inside)
        assert snap["trace_ns"] == sum(e["trace_ns"]
                                       for e in snap["episodes"])
    assert prefill["first_call_ns"] >= (
        prefill["trace_ns"] + prefill["lower_ns"] + prefill["backend_ns"])
    assert decode["first_call_ns"] >= (
        decode["lower_ns"] + decode["backend_ns"])
    assert all(e["trace_ns"] > 100_000 for e in prefill["episodes"]
               if e["fun_name"] == "jit(_prefill_rows)")
    assert all(e["trace_ns"] > 100_000 for e in decode["episodes"])
    assert ledger["sited"]["programs"] == (
        prefill["programs"] + decode["programs"])
    # the same two shapes again: every call a hit, the ledger as it was
    wave((4, 6), 9)
    wave((11,), 3)
    again = recompile.setup()
    assert again["sited"] == ledger["sited"]
    for name in ("serve/prefill_cold", "serve/decode"):
        assert again["sites"][name]["episodes"] == (
            ledger["sites"][name]["episodes"])
        assert again["sites"][name]["hits"] > ledger["sites"][name]["hits"]
