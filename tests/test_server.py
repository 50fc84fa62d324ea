"""Continuous batching (inference/server.py): every request's greedy
output must equal its solo generate() run, no matter what shares the
batch, when it was admitted, or which recycled row it landed on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.inference.decode import generate
from tfde_tpu.inference.server import ContinuousBatcher
from tfde_tpu.models.gpt import GPT, gpt_tiny_test


@pytest.fixture(scope="module")
def lm():
    m = gpt_tiny_test()
    params = m.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return m, params


# generate() is rolling-window and therefore always full-precision
# (int8 KV refuses rolling), so tests that pin a batcher bit-exact
# against this reference construct it with kv_quant="fp" — the
# TFDE_KV_QUANT=int8 tier-1 sweep would otherwise flip near-tie
# argmaxes (int8 parity is statistical, tests/test_kv_quant.py).
def _solo(model, params, prompt, n, **kw):
    toks, lengths = generate(
        model, params, jnp.asarray(prompt[None, :], jnp.int32),
        max_new_tokens=n, **kw,
    )
    p = prompt.size
    return np.asarray(toks)[0, p : int(lengths[0])]


@pytest.mark.slow
def test_batch_of_varied_requests_matches_solo(lm, rng):
    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=3, max_len=48)
    reqs = {}
    for i, (plen, n) in enumerate([(3, 9), (5, 4), (2, 12), (7, 7), (4, 1),
                                   (6, 10), (3, 3)]):
        prompt = rng.integers(0, 97, plen).astype(np.int64)
        rid = srv.submit(prompt, max_new_tokens=n)
        reqs[rid] = (prompt, n)
    done = dict(srv.run())
    assert srv.idle
    assert set(done) == set(reqs)
    for rid, (prompt, n) in reqs.items():
        np.testing.assert_array_equal(
            done[rid], _solo(model, params, prompt, n), err_msg=f"req {rid}"
        )


def test_staggered_submission_mid_flight(lm, rng):
    """Requests submitted while others are mid-generation take freed rows
    and still match solo runs — the continuous part of the batching."""
    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=48)
    p0 = rng.integers(0, 97, 4).astype(np.int64)
    p1 = rng.integers(0, 97, 3).astype(np.int64)
    r0 = srv.submit(p0, max_new_tokens=3)   # finishes quickly
    r1 = srv.submit(p1, max_new_tokens=10)  # keeps running
    done = {}
    for _ in range(3):
        done.update(srv.step())
    assert r0 in done  # the short request already finished
    p2 = rng.integers(0, 97, 5).astype(np.int64)  # lands in r0's old row
    r2 = srv.submit(p2, max_new_tokens=6)
    done.update(srv.run())
    assert set(done) == {r0, r1, r2}
    np.testing.assert_array_equal(done[r0], _solo(model, params, p0, 3))
    np.testing.assert_array_equal(done[r1], _solo(model, params, p1, 10))
    np.testing.assert_array_equal(done[r2], _solo(model, params, p2, 6))


def test_eos_and_instant_finish(lm, rng):
    model, params = lm
    prompt = rng.integers(0, 97, 4).astype(np.int64)
    free = _solo(model, params, prompt, 10)
    eos = int(free[2])  # third generated token
    ref = _solo(model, params, prompt, 10, eos_id=eos, pad_id=0)
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=48,
                            eos_id=eos)
    rid = srv.submit(prompt, max_new_tokens=10)
    one = srv.submit(prompt, max_new_tokens=1)  # budget-1: first token only
    done = dict(srv.run())
    np.testing.assert_array_equal(done[rid], ref)
    np.testing.assert_array_equal(done[one], free[:1])


@pytest.mark.slow
def test_rope_gqa_model(rng):
    m = GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4, mlp_dim=64,
            max_position=64, dtype=jnp.float32, position="rope",
            num_kv_heads=2)
    params = m.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ContinuousBatcher(m, params, kv_quant="fp", batch_size=2, max_len=40)
    prompts = [rng.integers(0, 97, p).astype(np.int64) for p in (3, 5, 4)]
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    done = dict(srv.run())
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(done[rid], _solo(m, params, p, 6))


def test_queue_longer_than_batch_and_validation(lm, rng):
    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=1, max_len=32)
    with pytest.raises(ValueError, match="max_len"):
        srv.submit(np.zeros(30, np.int64), max_new_tokens=10)
    with pytest.raises(ValueError, match="at least one"):
        srv.submit(np.zeros(0, np.int64), max_new_tokens=4)
    rids = [srv.submit(rng.integers(0, 97, 3).astype(np.int64), 4)
            for _ in range(5)]
    done = dict(srv.run())
    assert set(done) == set(rids)
    assert all(len(v) == 4 for v in done.values())


# --------------------------------------------------------------------------
# SpeculativeContinuousBatcher: draft-accelerated continuous serving
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def draft():
    m = GPT(vocab_size=97, hidden_size=16, depth=1, num_heads=2, mlp_dim=32,
            max_position=64, dtype=jnp.float32)
    params = m.init(jax.random.key(9), jnp.zeros((1, 8), jnp.int32))["params"]
    return m, params


@pytest.mark.slow
def test_speculative_batcher_matches_solo(lm, draft, rng):
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    model, params = lm
    dmodel, dparams = draft
    srv = SpeculativeContinuousBatcher(
        model, dmodel, params, dparams, batch_size=2, max_len=40,
        num_draft=3,
    )
    reqs = {}
    for plen, n in [(3, 8), (5, 5), (2, 11), (6, 4), (4, 9)]:
        prompt = rng.integers(0, 97, plen).astype(np.int64)
        reqs[srv.submit(prompt, max_new_tokens=n)] = (prompt, n)
    done = dict(srv.run())
    assert srv.idle
    assert set(done) == set(reqs)
    for rid, (prompt, n) in reqs.items():
        np.testing.assert_array_equal(
            done[rid], _solo(model, params, prompt, n), err_msg=f"req {rid}"
        )
    assert srv.stats()["generated"] == sum(n for _, n in reqs.values())
    assert srv.stats()["rounds"] > 0


def test_speculative_batcher_perfect_draft_accelerates(lm, rng):
    """Draft == target: every proposal accepted — tokens/round approaches
    num_draft+1, the speedup the batcher exists for."""
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    model, params = lm
    srv = SpeculativeContinuousBatcher(
        model, model, params, params, batch_size=2, max_len=48, num_draft=3,
    )
    prompts = [rng.integers(0, 97, 4).astype(np.int64) for _ in range(2)]
    rids = [srv.submit(p, max_new_tokens=12) for p in prompts]
    done = dict(srv.run())
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(done[rid], _solo(model, params, p, 12))
    assert srv.stats()["tokens_per_round"] > 2.0, srv.stats()
    # a perfect draft is accepted except where max_new truncation discards
    # the round's tail, and the stats ride the registry (the /metrics
    # export path) as serving/speculative/* gauges
    assert srv.stats()["acceptance_rate"] > 0.8
    from tfde_tpu.observability import metrics

    reg = metrics.default_registry()
    assert (reg.get("serving/speculative/acceptance_rate").value
            == pytest.approx(srv.stats()["acceptance_rate"]))
    assert (reg.get("serving/speculative/generated").value
            == srv.stats()["generated"])


def test_speculative_batcher_eos_and_staggering(lm, draft, rng):
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    model, params = lm
    dmodel, dparams = draft
    p0 = rng.integers(0, 97, 4).astype(np.int64)
    free = _solo(model, params, p0, 10)
    eos = int(free[3])
    ref = _solo(model, params, p0, 10, eos_id=eos, pad_id=0)
    srv = SpeculativeContinuousBatcher(
        model, dmodel, params, dparams, batch_size=1, max_len=40,
        num_draft=4, eos_id=eos,
    )
    r0 = srv.submit(p0, max_new_tokens=10)
    # second request queued behind the first on the single row
    p1 = rng.integers(0, 97, 3).astype(np.int64)
    r1 = srv.submit(p1, max_new_tokens=5)
    done = dict(srv.run())
    np.testing.assert_array_equal(done[r0], ref)
    np.testing.assert_array_equal(
        done[r1], _solo(model, params, p1, 5, eos_id=eos, pad_id=0)
    )


def test_speculative_batcher_sampled_mode(lm, draft, rng):
    """temperature > 0: the sampled rounds drain the queue, outputs are
    reproducible per rng, and budgets/EOS hold per row."""
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    model, params = lm
    dmodel, dparams = draft

    def serve(key):
        srv = SpeculativeContinuousBatcher(
            model, dmodel, params, dparams, batch_size=2, max_len=40,
            num_draft=3, temperature=0.8, rng=jax.random.key(key),
        )
        prompts = [rng.integers(0, 97, p).astype(np.int64)
                   for p in (3, 5, 4)]
        # rng fixture advances between calls; pin prompts instead
        prompts = [np.asarray([7, 11, 2]), np.asarray([3, 1, 4, 1, 5]),
                   np.asarray([9, 2, 6, 5])]
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        return {r: tuple(v.tolist()) for r, v in dict(srv.run()).items()}

    a, b, c = serve(11), serve(11), serve(12)
    assert a == b          # deterministic per key
    assert a != c          # key moves the draws
    assert all(len(v) == 6 for v in a.values())


def test_speculative_batcher_rope_gqa(rng):
    """Per-row spec rounds + admission compose with rotary positions and
    grouped-query caches."""
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    m = GPT(vocab_size=97, hidden_size=32, depth=2, num_heads=4, mlp_dim=64,
            max_position=64, dtype=jnp.float32, position="rope",
            num_kv_heads=2)
    params = m.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]
    d = GPT(vocab_size=97, hidden_size=16, depth=1, num_heads=2, mlp_dim=32,
            max_position=64, dtype=jnp.float32)
    dparams = d.init(jax.random.key(9), jnp.zeros((1, 8), jnp.int32))["params"]
    srv = SpeculativeContinuousBatcher(m, d, params, dparams, batch_size=2,
                                       max_len=36, num_draft=3)
    prompts = [rng.integers(0, 97, p).astype(np.int64) for p in (3, 5, 4)]
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    done = dict(srv.run())
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(done[rid], _solo(m, params, p, 6))


def test_prompt_buckets():
    """Bucket arithmetic: defaults are powers of two capped by max_len;
    prompts pad to the smallest fitting bucket with logits read at the
    true last position."""
    from tfde_tpu.inference.server import _bucketed, _normalize_buckets

    assert _normalize_buckets(None, 100) == (8, 16, 32, 64, 100)
    assert _normalize_buckets((32, 8, 64), 64) == (8, 32, 64)
    # oversized buckets clamp to max_len (a larger bucket would overflow
    # the row cache at admission time)
    assert _normalize_buckets((16, 128), 64) == (16, 64)
    with pytest.raises(ValueError, match="cover max_len"):
        _normalize_buckets((8, 16), 64)
    ids, last = _bucketed(np.asarray([5, 6, 7]), (8, 16), pad_id=0)
    assert ids.shape == (1, 8) and last == 2
    assert ids[0, :3].tolist() == [5, 6, 7]
    assert ids[0, 3:].tolist() == [0] * 5
    ids, last = _bucketed(np.arange(9), (8, 16), pad_id=0)
    assert ids.shape == (1, 16) and last == 8


# --------------------------------------------------------------------------
# Device-resident loop: K-step scan parity, adaptive depth, host-cost bound
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_scan_depth_staggered_parity_sweep(lm, rng):
    """Greedy outputs stay bit-identical to solo generate() across scan
    depths with requests admitted mid-flight — the fused K-tick scan must
    freeze finishing rows and admit into their place without perturbing
    the surviving rows' streams."""
    model, params = lm
    reqs = [(rng.integers(0, 97, plen).astype(np.int64), n)
            for plen, n in [(3, 9), (5, 4), (2, 12), (7, 1), (4, 7)]]
    refs = [_solo(model, params, p, n) for p, n in reqs]
    for depth in (1, 2, 4):
        srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=48,
                                scan_depth=depth)
        rids = [srv.submit(p, max_new_tokens=n) for p, n in reqs[:3]]
        done = dict(srv.step())  # late arrivals land on recycled rows
        rids += [srv.submit(p, max_new_tokens=n) for p, n in reqs[3:]]
        done.update(srv.run())
        assert srv.idle
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(
                done[rid], ref, err_msg=f"depth {depth} req {rid}"
            )


def test_eos_mid_scan(lm, rng):
    """An EOS landing in the middle of a K-tick scan must freeze the row
    on device: no post-EOS tokens leak out, and the emitted stream equals
    the solo run's."""
    model, params = lm
    prompt = rng.integers(0, 97, 4).astype(np.int64)
    free = _solo(model, params, prompt, 12)
    # EOS on the 4th generated token: admission emits token 1, the first
    # depth-4 scan hits EOS on its 3rd tick — strictly mid-scan
    eos = int(free[3])
    ref = _solo(model, params, prompt, 12, eos_id=eos, pad_id=0)
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=48,
                            eos_id=eos, scan_depth=4)
    rid = srv.submit(prompt, max_new_tokens=12)
    done = dict(srv.run())
    np.testing.assert_array_equal(done[rid], ref)
    # EOS truncated the stream (possibly even earlier than free[3] when
    # the greedy stream repeats that id) and the EOS token itself is kept
    assert len(done[rid]) < 12
    assert int(done[rid][-1]) == eos


def test_budget_one_admitted_mid_flight(lm, rng):
    """A budget-1 request queued behind a full batch finishes AT admission
    (its only token samples inside the prefill program) the moment a row
    frees mid-flight, without touching the surviving rows' parity."""
    model, params = lm
    p_long = rng.integers(0, 97, 3).astype(np.int64)
    p_short = rng.integers(0, 97, 5).astype(np.int64)
    p_one = rng.integers(0, 97, 4).astype(np.int64)
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=48,
                            scan_depth=2)
    r_long = srv.submit(p_long, max_new_tokens=12)
    r_short = srv.submit(p_short, max_new_tokens=3)
    done = dict(srv.step())  # both admitted, batch full
    r_one = srv.submit(p_one, max_new_tokens=1)  # queues behind them
    done.update(srv.run())
    assert set(done) == {r_long, r_short, r_one}
    np.testing.assert_array_equal(done[r_one], _solo(model, params, p_one, 1))
    np.testing.assert_array_equal(
        done[r_long], _solo(model, params, p_long, 12)
    )
    np.testing.assert_array_equal(
        done[r_short], _solo(model, params, p_short, 3)
    )


def test_ladder_depth():
    """Adaptive K picks from the power-of-two ladder {1, 2, 4, ..., cap}
    (cap included), never exceeding the completion bound — the compile-
    count/admission-latency compromise."""
    from tfde_tpu.inference.server import _ladder_depth

    assert _ladder_depth(4, 9) == 4    # bound beyond cap: full depth
    assert _ladder_depth(4, 4) == 4
    assert _ladder_depth(4, 3) == 2    # shrink toward the completion
    assert _ladder_depth(4, 1) == 1
    assert _ladder_depth(4, 0) == 1    # degenerate bounds clamp to 1
    assert _ladder_depth(1, 99) == 1
    assert _ladder_depth(8, 6) == 4
    assert _ladder_depth(6, 5) == 4    # non-power cap still ladders below


def test_steady_state_host_cost_bound(lm, rng, monkeypatch):
    """Regression guard for the device-resident loop: in steady state
    (full batch, empty queue) one step of the depth-K scan costs ONE
    jitted dispatch and ONE host sync for K tokens per row — so
    dispatches + syncs per generated token must stay <= 2/K, where the
    old per-token loop paid >= 3. Host syncs are counted by intercepting
    the module's single fetch seam, so a stray np.asarray() on a device
    array elsewhere in the loop would show up as a count mismatch."""
    import tfde_tpu.inference.server as server_mod

    model, params = lm
    depth = 4
    fetches = {"n": 0}
    real_fetch = server_mod._fetch

    def counting_fetch(tree):
        fetches["n"] += 1
        return real_fetch(tree)

    monkeypatch.setattr(server_mod, "_fetch", counting_fetch)
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=96,
                            scan_depth=depth)
    for _ in range(2):
        srv.submit(rng.integers(0, 97, 4).astype(np.int64),
                   max_new_tokens=60)
    srv.step()  # admission + first scan: compile + upload, not steady state
    before = srv.stats()
    f0 = fetches["n"]
    steps = 4
    for _ in range(steps):
        srv.step()
    after = srv.stats()
    d_disp = after["dispatches"] - before["dispatches"]
    d_sync = after["syncs"] - before["syncs"]
    d_tok = after["generated"] - before["generated"]
    assert d_tok == steps * depth * 2  # 2 rows x K tokens per step
    # the monkeypatched seam agrees with the batcher's own accounting
    assert fetches["n"] - f0 == d_sync == steps
    assert d_disp == steps  # ONE jitted call per step, state stays resident
    assert (d_disp + d_sync) / d_tok <= 2.0 / depth
    # and the published per-token stats reflect the amortization
    assert after["syncs_per_token"] < 1.0


def test_prefill_buffers_are_donated(lm):
    """The admission prefills must alias the freshly-allocated row cache
    into their output (donate_argnums) so a wave's scratch K/V is not
    double-resident. Pin the `tf.aliasing_output` markers in the lowered
    StableHLO for BOTH the cold path (`_prefill_rows`) and the warm
    suffix path (`_prefill_suffix`) — a dropped donation shows up here
    before it shows up as an HBM regression."""
    import tfde_tpu.inference.server as server_mod
    from tfde_tpu.inference.prefix_cache import is_index_leaf, leaf_name

    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=64)
    tpl = srv._row_template(1)
    low = server_mod._prefill_rows.lower(
        srv._decode_model, tpl, params, jnp.zeros((1, 8), jnp.int32),
        jnp.zeros((1,), jnp.int32), None, None, temperature=0.0,
        top_k=None, top_p=None, min_p=None, repetition_penalty=1.0,
    )
    assert low.as_text().count("tf.aliasing_output") >= 2

    tpl = srv._row_template(1)
    prefix_kv = {
        leaf_name(p): jnp.zeros((1, 4) + leaf.shape[2:], leaf.dtype)
        for p, leaf in jax.tree_util.tree_leaves_with_path(tpl)
        if not is_index_leaf(p)
    }
    low = server_mod._prefill_suffix.lower(
        srv._decode_model, tpl, params, prefix_kv,
        jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32),
        None, None, None, temperature=0.0, top_k=None, top_p=None,
        min_p=None, repetition_penalty=1.0,
    )
    assert low.as_text().count("tf.aliasing_output") >= 2


def _all_zero(tree) -> bool:
    return all(not np.asarray(leaf).any() for leaf in jax.tree.leaves(tree))


@pytest.mark.parametrize("kv_quant", ["fp", "int8"])
@pytest.mark.parametrize("rp", [1, 2, 4])
def test_row_template_is_one_fresh_zero_program(lm, rp, kv_quant, monkeypatch):
    """A wave's zero row cache comes from ONE compiled program per width:
    (a) the tree `init_cache` builds, all zero; (b) new buffers every
    call, so the one a prefill donated takes nothing from the next; (c)
    after a width's first call nothing compiles and no per-leaf
    `jnp.zeros` runs — a call is one launch (counts, valid on the CPU)."""
    import tfde_tpu.inference.server as server_mod
    from tfde_tpu.inference.decode import init_cache
    from tfde_tpu.observability import recompile

    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=4, max_len=64,
                            kv_quant=kv_quant)
    quant = None if kv_quant == "fp" else kv_quant
    want = init_cache(model, rp, 64, kv_quant=quant)
    first = srv._row_template(rp)
    assert jax.tree.structure(first) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(first), jax.tree.leaves(want)):
        assert (got.shape, got.dtype) == (ref.shape, ref.dtype)
    assert _all_zero(first)
    # no two leaves of one tree, and no two trees, share a buffer
    second = srv._row_template(rp)
    ptrs = [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves((first, second))]
    assert len(set(ptrs)) == len(ptrs)

    filled, _tok, _seen, _routed = server_mod._prefill_rows(
        srv._decode_model, first, params,
        jnp.ones((rp, 8), jnp.int32), jnp.full((rp,), 7, jnp.int32),
        None, None, **srv._sampling,
    )
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(first))
    assert not _all_zero(filled)
    assert _all_zero(second)  # untouched by the donated wave
    assert _all_zero(srv._row_template(rp))  # and so is the next one

    assert recompile.install()
    calls = {"zeros": 0}
    real_zeros = jnp.zeros

    def counting_zeros(*a, **k):
        calls["zeros"] += 1
        return real_zeros(*a, **k)

    monkeypatch.setattr(jnp, "zeros", counting_zeros)
    compiles = recompile.process_compiles()
    before = srv.stats()
    for _ in range(3):
        srv._row_template(rp)
    after = srv.stats()
    assert recompile.process_compiles() == compiles
    assert calls["zeros"] == 0
    assert after["dispatches"] - before["dispatches"] == 3
    key = (model, rp, 64, quant)
    assert srv._zero_programs[key]._cache_size() == 1


def test_speculative_templates_come_from_the_shared_helper(lm, draft, rng,
                                                           monkeypatch):
    """Both caches of a speculative wave, target and draft, are zeroed by
    `_BatcherBase._zero_rows`, the helper `_row_template` calls too: one
    program per (model, width, cache length), one launch each a wave."""
    import tfde_tpu.inference.server as server_mod
    from tfde_tpu.inference.server import SpeculativeContinuousBatcher

    model, params = lm
    dmodel, dparams = draft
    srv = SpeculativeContinuousBatcher(
        model, dmodel, params, dparams, batch_size=2, max_len=40,
        num_draft=3,
    )
    assert not hasattr(srv, "_template")
    asked = []
    real = server_mod._BatcherBase._zero_rows

    def recording(self, m, rp, length, kv_quant=None):
        asked.append((m, rp, length, kv_quant))
        return real(self, m, rp, length, kv_quant)

    monkeypatch.setattr(server_mod._BatcherBase, "_zero_rows", recording)
    prompt = rng.integers(0, 97, 4).astype(np.int64)
    rid = srv.submit(prompt, max_new_tokens=5)
    done = dict(srv.run())
    np.testing.assert_array_equal(done[rid], _solo(model, params, prompt, 5))
    cache_len = 40 + 3 + 1
    assert asked == [(model, 1, cache_len, None), (dmodel, 1, cache_len, None)]
    assert set(srv._zero_programs) == set(asked)
    assert all(p._cache_size() == 1 for p in srv._zero_programs.values())
    assert srv.stats()["prefill_template_ns"] > 0


def test_role_split_primed_handoff_parity(lm, rng):
    """Disaggregated prefill: a prefill-role batcher primes prompts, a
    decode-role batcher scatters the shipped K/V and streams — primed
    requests must match solo bit for bit, and may mix in one wave with
    plainly-submitted ones."""
    model, params = lm
    prompts = [rng.integers(1, 90, k).astype(np.int64) for k in (3, 7, 5, 4)]
    pre = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=64,
                            role="prefill")
    dec = ContinuousBatcher(model, params, kv_quant="fp", batch_size=4, max_len=64,
                            role="decode")
    primed = [pre.prime(p, 8) for p in prompts[:3]]
    rids = [dec.submit_primed(pr) for pr in primed]
    rid_plain = dec.submit(prompts[3], 8)
    done = dict(dec.run())
    for rid, p in zip(rids + [rid_plain], prompts):
        np.testing.assert_array_equal(done[rid], _solo(model, params, p, 8))
    # role guards: each half of the split rejects the other's entry point
    with pytest.raises(RuntimeError):
        pre.submit(prompts[0], 4)
    with pytest.raises(RuntimeError):
        dec.prime(prompts[0], 4)


def test_progress_streaming_matches_final_output(lm, rng):
    """take_progress chunks, concatenated, must equal the request's final
    output — the SSE streaming surface (router.py) rides on this."""
    model, params = lm
    p = rng.integers(1, 90, 5).astype(np.int64)
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2, max_len=64)
    srv.enable_progress()
    rid = srv.submit(p, 6)
    got, done = [], False
    while not srv.idle:
        srv.step()
        if not done:
            toks, done = srv.take_progress(rid)
            got.extend(int(t) for t in toks)
    assert done
    np.testing.assert_array_equal(
        np.asarray(got, np.int32), _solo(model, params, p, 6)
    )


def test_batcher_repetition_penalty_no_repeats(rng):
    """repetition_penalty at extreme strength: every token a request emits
    is distinct from its prompt and its own prior output, across
    admission recycling — the presence mask resets per row."""
    model = gpt_tiny_test()
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=48,
                            repetition_penalty=1e9)
    prompts = {}
    for i in range(5):
        p = rng.integers(0, model.vocab_size, int(rng.integers(2, 6)))
        rid = srv.submit(p, 8)
        prompts[rid] = list(p)
    done = srv.run()
    assert len(done) == 5
    for rid, toks in done:
        emitted = list(prompts[rid])
        for t in toks:
            assert t not in emitted, (rid, t, emitted)
            emitted.append(int(t))


def test_cancel_frees_row_and_queue(lm, rng):
    """cancel() abandons a request whose consumer is gone (router client
    disconnect): queued entries drop, active rows free so the decode
    scan stops spending ticks on them, and the progress entry never
    leaks. The recycled row must then serve fresh work bit-identically."""
    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=64)
    srv.enable_progress()
    p = rng.integers(1, 90, 5).astype(np.int64)
    active = srv.submit(p, 40)
    queued = srv.submit(p, 6)
    srv.step()                       # admits `active`; `queued` waits
    assert srv.free_rows == 0 and len(srv._queue) == 1
    assert srv.cancel(queued)
    assert queued not in srv._stream and len(srv._queue) == 0
    assert srv.cancel(active)
    assert active not in srv._stream
    assert srv.free_rows == 1 and srv.idle
    assert not srv.cancel(active)    # already gone
    rid = srv.submit(p, 6)
    done = dict(srv.run())
    np.testing.assert_array_equal(done[rid], _solo(model, params, p, 6))


# --------------------------------------------------------------------------
# Admission control: caps, priority classes, deadline shedding (PR 14)
# --------------------------------------------------------------------------

def test_admission_depth_cap_rejects_with_queue_full(lm, rng):
    """max_queue bounds QUEUED requests: the overflow submit raises a
    typed QueueFull carrying depth + drain estimate, and everything that
    WAS admitted still decodes bit-identical to solo."""
    from tfde_tpu.inference.admission import (
        AdmissionController, QueueFull, MIN_RETRY_AFTER_S,
    )

    model, params = lm
    srv = ContinuousBatcher(
        model, params, kv_quant="fp", batch_size=1, max_len=48,
        admission_ctl=AdmissionController(max_queue=1),
    )
    p = rng.integers(1, 90, 4).astype(np.int64)
    admitted = srv.submit(p, 6)        # queue depth 0 -> in
    with pytest.raises(QueueFull) as ei:
        srv.submit(p, 6)               # queue depth 1 >= cap
    e = ei.value
    assert e.reason == "queue_depth"
    assert e.queue_depth == 1 and e.queued_tokens == 6
    assert e.retry_after_s >= MIN_RETRY_AFTER_S
    # QueueFull is a RuntimeError: overload-unaware callers stay correct
    assert isinstance(e, RuntimeError)
    body = e.as_json()
    assert set(body) == {"error", "reason", "queue_depth",
                         "queued_tokens", "retry_after_s"}
    done = dict(srv.run())
    np.testing.assert_array_equal(done[admitted],
                                  _solo(model, params, p, 6))
    # the queue drained: the same submit is admitted now
    rid = srv.submit(p, 4)
    np.testing.assert_array_equal(dict(srv.run())[rid],
                                  _solo(model, params, p, 4))


def test_admission_token_budget_cap(lm, rng):
    """max_queued_tokens bounds the queued OUTPUT-token backlog — the
    unit the drain rate is measured in, so the Retry-After estimate
    derived from it is honest."""
    from tfde_tpu.inference.admission import AdmissionController, QueueFull

    model, params = lm
    srv = ContinuousBatcher(
        model, params, batch_size=1, max_len=48,
        admission_ctl=AdmissionController(max_queued_tokens=10),
    )
    p = rng.integers(1, 90, 3).astype(np.int64)
    srv.submit(p, 8)                   # backlog 8 <= 10
    with pytest.raises(QueueFull) as ei:
        srv.submit(p, 8)               # 8 + 8 > 10
    assert ei.value.reason == "queued_tokens"
    srv.submit(p, 2)                   # 8 + 2 == 10: exactly at cap is in
    done = dict(srv.run())
    assert len(done) == 2


def test_priority_ordered_dequeue(lm, rng):
    """The queue drains interactive > batch > best_effort regardless of
    submission order (FIFO within a class), and every admitted request
    still matches its solo run."""
    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=1, max_len=48)
    p = rng.integers(1, 90, 4).astype(np.int64)
    blocker = srv.submit(p, 8)
    srv.step()                         # blocker occupies the single row
    r_be = srv.submit(p, 3, priority="best_effort")
    r_ba = srv.submit(p, 3, priority="batch")
    r_in = srv.submit(p, 3)            # unlabeled == interactive
    assert srv._queue.depths() == {
        "interactive": 1, "batch": 1, "best_effort": 1}
    order = []
    while not srv.idle:
        for rid, _toks in srv.step():
            order.append(rid)
    assert order == [blocker, r_in, r_ba, r_be]
    # parity rode along: re-run one of each against solo
    srv2 = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=48)
    rid = srv2.submit(p, 3, priority="best_effort")
    np.testing.assert_array_equal(dict(srv2.run())[rid],
                                  _solo(model, params, p, 3))


def test_expired_deadline_shed_before_prefill(lm, rng):
    """A queued request whose wait already blew its TTFT deadline is
    dropped AT DEQUEUE — no prefill is spent on it, was_shed() answers
    exactly once, and the shed counters tick."""
    import time as _time

    from tfde_tpu.observability import metrics

    model, params = lm
    reg = metrics.default_registry()
    reg.reset("serving/shed")
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=48)
    srv.enable_progress()
    p = rng.integers(1, 90, 4).astype(np.int64)
    blocker = srv.submit(p, 6)
    doomed = srv.submit(p, 5, priority="batch", ttft_deadline_ms=1.0)
    _time.sleep(0.01)                  # the deadline expires in queue
    done = dict(srv.run())
    assert blocker in done and doomed not in done
    np.testing.assert_array_equal(done[blocker],
                                  _solo(model, params, p, 6))
    toks, fin = srv.take_progress(doomed)
    assert toks == [] and fin is True
    assert srv.was_shed(doomed) is True
    assert srv.was_shed(doomed) is False   # answers once
    assert reg.get("serving/shed_expired").value == 1
    assert reg.get("serving/shed_batch").value == 1
    assert reg.get("serving/shed_tokens").value == 5
    assert srv.idle and not srv._deadline_at and not srv._priority


def test_forced_overload_fault_rejects_then_recovers(lm, rng):
    """resilience.OverloadFault arms the module-wide saturation lever:
    while armed every submit is rejected as forced_overload; after
    clear_overload the same batcher admits again."""
    from tfde_tpu.inference import admission
    from tfde_tpu.inference.admission import QueueFull
    from tfde_tpu.resilience.faults import OverloadFault

    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=48)
    p = rng.integers(1, 90, 3).astype(np.int64)
    OverloadFault(seconds=30.0).fire("test")
    try:
        with pytest.raises(QueueFull) as ei:
            srv.submit(p, 4)
        assert ei.value.reason == "forced_overload"
    finally:
        admission.clear_overload()
    rid = srv.submit(p, 4)
    np.testing.assert_array_equal(dict(srv.run())[rid],
                                  _solo(model, params, p, 4))


def test_unknown_priority_rejected_loudly(lm, rng):
    """A typo'd priority class must raise, not silently become
    best_effort (which would get it brownout-shed in production)."""
    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=1, max_len=48)
    p = rng.integers(1, 90, 3).astype(np.int64)
    with pytest.raises(ValueError, match="priority"):
        srv.submit(p, 4, priority="urgent")
    assert len(srv._queue) == 0


# --------------------------------------------------------------------------
# KV-headroom admission: reject on memory before queue depth collapses
# --------------------------------------------------------------------------

def test_kv_headroom_gate_rejects_with_kv_payload(lm, rng):
    """min_headroom_rows armed: once the slab's free rows fall below the
    floor the submit is rejected as kv_headroom, the QueueFull carries
    the ledger's kv block, and Retry-After falls back to the drain-rate
    estimate over the OUTSTANDING tokens (the queue is empty — queued
    backlog alone would undersell the wait). Draining restores
    admission; everything admitted still matches solo."""
    from tfde_tpu.inference.admission import (
        AdmissionController, QueueFull, MIN_RETRY_AFTER_S,
    )

    model, params = lm
    srv = ContinuousBatcher(
        model, params, kv_quant="fp", batch_size=2, max_len=48,
        admission_ctl=AdmissionController(min_headroom_rows=2),
    )
    p = rng.integers(1, 90, 4).astype(np.int64)
    admitted = srv.submit(p, 6)        # 2 free rows == floor: in
    srv.step()                         # admitted to a row: 1 free < 2
    with pytest.raises(QueueFull) as ei:
        srv.submit(p, 6)
    e = ei.value
    assert e.reason == "kv_headroom"
    assert e.kv is not None
    assert e.kv["headroom_rows"] == 1 and e.kv["rows_active"] == 1
    assert e.kv["used_bytes"] > 0
    body = e.as_json()
    assert body["reason"] == "kv_headroom"
    assert body["kv"]["headroom_rows"] == 1
    assert e.retry_after_s >= MIN_RETRY_AFTER_S
    done = dict(srv.run())
    np.testing.assert_array_equal(done[admitted],
                                  _solo(model, params, p, 6))
    rid = srv.submit(p, 4)             # slab drained: admitted again
    np.testing.assert_array_equal(dict(srv.run())[rid],
                                  _solo(model, params, p, 4))


def test_kv_headroom_env_knob_and_low_budget_drill(lm, rng, monkeypatch):
    """The forced low-budget drill: TFDE_ADMIT_KV_HEADROOM armed via env
    with a TFDE_CAPACITY_BUDGET_BYTES far below one row's cost — every
    submit 429s with the kv payload showing zero headroom BEFORE any
    request can stall waiting on a row that memory could never back."""
    from tfde_tpu.inference.admission import QueueFull

    monkeypatch.setenv("TFDE_ADMIT_KV_HEADROOM", "1")
    monkeypatch.setenv("TFDE_CAPACITY_BUDGET_BYTES", "64")
    model, params = lm
    srv = ContinuousBatcher(model, params, batch_size=2, max_len=48)
    assert srv._cap_model.budget_bytes == 64
    assert srv._ledger.row_bytes > 64   # the budget can't back one row
    p = rng.integers(1, 90, 4).astype(np.int64)
    with pytest.raises(QueueFull) as ei:
        srv.submit(p, 6)               # rejected with all rows still free
    e = ei.value
    assert e.reason == "kv_headroom"
    assert e.kv["headroom_rows"] == 0 and e.kv["rows_free"] == 2
    assert len(srv._queue) == 0 and srv.idle


def test_kv_headroom_default_off_admits_identically(lm, rng, monkeypatch):
    """Default-off parity: with the knob unset the gate never consults
    the ledger, and a full batch plus a deep queue admits exactly as
    before this PR — memory pressure alone must not reject."""
    monkeypatch.delenv("TFDE_ADMIT_KV_HEADROOM", raising=False)
    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=1, max_len=48)
    assert srv._admission.min_headroom_rows == 0
    assert not srv._admission.enabled
    p = rng.integers(1, 90, 4).astype(np.int64)
    rids = [srv.submit(p, 4) for _ in range(4)]  # 1 row, 3 queued: all in
    done = dict(srv.run())
    assert set(done) == set(rids)
    for rid in rids:
        np.testing.assert_array_equal(done[rid],
                                      _solo(model, params, p, 4))


# -- the step's own account (stats()'s ledger, spans.span) --------------------
def _ledger_run(srv, rng, waves=2):
    """Drive `srv` through staggered waves; stats() after every step."""
    shots = [srv.stats()]
    for _ in range(waves):
        for plen, n in [(3, 9), (5, 4), (2, 12), (7, 7), (4, 1), (6, 10)]:
            srv.submit(rng.integers(0, 97, plen).astype(np.int64), n)
        while not srv.idle:
            srv.step()
            shots.append(srv.stats())
    return shots


@pytest.fixture(scope="module")
def ledger_run(lm):
    from tfde_tpu.observability import metrics

    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=3,
                            max_len=48, scan_depth=4)
    hist = metrics.default_registry().histogram("serving/queue_wait_ms")
    before = (hist.sum, hist.count)
    shots = _ledger_run(srv, np.random.default_rng(5))
    return {"shots": shots, "last": shots[-1],
            "queue_wait_ms": (hist.sum - before[0], hist.count - before[1])}


def _ledger_case_ints_never_fall(run):
    from tfde_tpu.inference.server import _PHASE_KEYS

    for prev, cur in zip(run["shots"], run["shots"][1:]):
        for k in _PHASE_KEYS:
            assert type(cur[k]) is int, (k, type(cur[k]))
            assert cur[k] >= prev[k], k
    assert all(run["last"][k] > 0 for k in _PHASE_KEYS)


def _ledger_case_a_step_is_admit_decode_emit(run):
    s = run["last"]
    parts = s["admit_ns"] + s["decode_ns"] + s["emit_ns"]
    assert parts <= s["step_ns"]
    assert parts >= 0.98 * s["step_ns"], parts / s["step_ns"]
    assert s["steps"] == len(run["shots"]) - 1


def _ledger_case_children_fit_their_parents(run):
    s = run["last"]
    assert (s["prefill_pack_ns"] + s["prefill_template_ns"]
            + s["prefill_run_ns"] + s["prefill_scatter_ns"]
            ) <= s["prefill_ns"] <= s["admit_ns"]
    assert s["decode_upload_ns"] + s["decode_dispatch_ns"] <= s["decode_ns"]
    assert s["device_wait_ns"] <= s["prefill_ns"] + s["decode_ns"]
    assert s["uploads"] <= s["scans"] <= s["steps"]


def _ledger_case_rows_and_cells(run):
    s = run["last"]
    # the real rows of the waves are the requests admitted
    assert s["admitted"] == 12 <= s["prefill_rows_padded"]
    # every prompt of the run, and each padded to the 8 bucket
    assert s["prefill_tokens"] == 2 * (3 + 5 + 2 + 7 + 4 + 6)
    assert s["prefill_cells"] == 8 * s["prefill_rows_padded"]
    assert s["prefill_tokens"] <= s["prefill_cells"]


def _ledger_case_queue_wait_agrees_with_the_histogram(run):
    total_ms, count = run["queue_wait_ms"]
    s = run["last"]
    assert count == s["admitted"]
    assert s["queue_wait_ns"] / s["admitted"] / 1e6 == pytest.approx(
        total_ms / count, rel=1e-6)


def _ledger_case_first_tokens_are_held_for_the_round(run):
    s = run["last"]
    # a first token waits out the rest of its step: less than the steps
    # themselves, more than nothing
    assert 0 < s["first_token_hold_ns"] <= s["admitted"] * s["step_ns"]
    assert s["first_token_hold_ns"] / s["admitted"] <= (
        s["step_ns"] - s["admit_ns"] + s["prefill_ns"])


@pytest.mark.parametrize("case", [
    _ledger_case_ints_never_fall,
    _ledger_case_a_step_is_admit_decode_emit,
    _ledger_case_children_fit_their_parents,
    _ledger_case_rows_and_cells,
    _ledger_case_queue_wait_agrees_with_the_histogram,
    _ledger_case_first_tokens_are_held_for_the_round,
], ids=lambda f: f.__name__[len("_ledger_case_"):])
def test_step_ledger(ledger_run, case):
    case(ledger_run)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_decode_least_bytes_is_the_hand_count(lm, paged):
    """Two rows, one scan: depth x (the parameters + the committed cells
    of both rows), every size from the toy model's shapes. The pool holds
    more cells than rows x max_len (a block past the end a row, and the
    null block) and a cell costs what it costs in the slab."""
    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2,
                            max_len=32, scan_depth=4, paged=paged)
    srv.submit(np.arange(1, 4), 3)
    srv.submit(np.arange(1, 6), 3)
    srv.run()
    s = srv.stats()
    # fp32 GPT: 97x32 + 64x32 embeddings; per block 2 LN (2x32 each), qkv
    # and out projections (32x32+32 each), MLP 32x64+64 and 64x32+32; a
    # final LN; the head is tied
    block = 2 * 64 + 4 * (32 * 32 + 32) + 32 * 64 + 64 + 64 * 32 + 32
    param_bytes = 4 * (97 * 32 + 64 * 32 + 2 * block + 64)
    assert param_bytes == sum(
        a.size * 4 for a in jax.tree_util.tree_leaves(params))
    # a cell: K and V of 4 heads x 8 over 2 layers, fp32
    cell_bytes = 2 * 2 * 4 * 8 * 4
    # both first tokens come with the prefill; the two that remain take one
    # scan of depth 2, started with 3 and 5 cells committed
    assert (s["scans"], s["rounds"]) == (1, 2)
    assert s["decode_least_bytes"] == 2 * (param_bytes
                                           + (3 + 5) * cell_bytes)


class _Annotations:
    """Stands where spans.py holds jax: counts TraceAnnotations and keeps
    the order they open and close in."""

    def __init__(self):
        self.log = []
        self.profiler = self

    def TraceAnnotation(self, name):
        outer = self

        class _One:
            def __enter__(self):
                outer.log.append(("open", name))

            def __exit__(self, *exc):
                outer.log.append(("close", name))

        return _One()


@pytest.mark.parametrize("active", [False, True])
def test_leaf_spans_open_inside_their_parents(lm, monkeypatch, active):
    from tfde_tpu.observability import spans

    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2,
                            max_len=32, scan_depth=2)
    stand_in = _Annotations()
    monkeypatch.setattr(spans, "_jax", stand_in)
    monkeypatch.setattr(spans, "_trace_active", active)
    srv.submit(np.arange(1, 4), 4)
    srv.step()
    if not active:
        assert stand_in.log == []
        return
    opened = [name for what, name in stand_in.log if what == "open"]
    assert opened == [
        "serving/step", "serving/admit", "serving/prefill",
        "serving/prefill/pack", "serving/prefill/template",
        "serving/prefill/run", "serving/prefill/scatter",
        "serving/prefill/fetch", "serving/decode", "serving/decode/upload",
        "serving/decode/scan", "serving/decode/fetch", "serving/emit"]
    parent_of = {"serving/prefill": "serving/admit"}
    stack = []
    for what, name in stand_in.log:
        if what == "close":
            assert stack.pop() == name
            continue
        want = parent_of.get(name) or (
            name.rsplit("/", 1)[0] if name.count("/") == 2
            else "serving/step" if name != "serving/step" else None)
        assert (stack[-1] if stack else None) == want, (name, stack)
        stack.append(name)
    assert stack == []


def test_ring_events_name_the_span_that_caused_them(lm):
    from tfde_tpu.observability import trace

    model, params = lm
    srv = ContinuousBatcher(model, params, kv_quant="fp", batch_size=2,
                            max_len=32, scan_depth=2)
    was = trace.active()
    trace.enable()
    try:
        trace.clear()
        srv.submit(np.arange(1, 4), 4)
        srv.step()
        parents = {e["name"]: e.get("parent") for e in trace.events()
                   if e["name"].startswith("serving/")}
    finally:
        if not was:
            trace.disable()
    assert parents["serving/step"] is None
    assert parents["serving/prefill"] == "serving/admit"
    assert parents["serving/prefill/template"] == "serving/prefill"
    assert parents["serving/decode/upload"] == "serving/decode"
    assert parents["serving/emit"] == "serving/step"


def test_speculative_batcher_keeps_the_same_ledger(lm, draft, rng):
    from tfde_tpu.inference.server import (
        _PHASE_KEYS, SpeculativeContinuousBatcher,
    )

    model, params = lm
    dmodel, dparams = draft
    srv = SpeculativeContinuousBatcher(model, dmodel, params, dparams,
                                       batch_size=2, max_len=40, num_draft=2)
    shots = _ledger_run(srv, rng, waves=1)
    s = shots[-1]
    plain = ContinuousBatcher(model, params, batch_size=2, max_len=40)
    assert set(_PHASE_KEYS) <= set(s)
    assert {k for k in s if type(s[k]) is int} >= {
        k for k, v in plain.stats().items() if type(v) is int}
    assert all(type(s[k]) is int for k in _PHASE_KEYS)
    parts = s["admit_ns"] + s["decode_ns"] + s["emit_ns"]
    assert 0.98 * s["step_ns"] <= parts <= s["step_ns"]
    assert s["scans"] == s["uploads"] == s["rounds"] > 0
    assert s["decode_least_bytes"] > 0 and s["device_wait_ns"] > 0
