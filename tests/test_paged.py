"""Paged KV (inference/paged.py + the ContinuousBatcher paged mode):
the BlockPool allocator's refcount/free-list/defrag invariants unit by
unit, the block-table gather pinned bit-exact against the dense slab,
greedy serving parity dense-vs-paged through the REAL batcher (multi-
wave row reuse, warm trie sharing, solo-generate cross-check), cancel
returning blocks to the pool, and the one-paged-prefill-program compile
sentinel across mixed prompt lengths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tfde_tpu.inference import paged, server
from tfde_tpu.inference.decode import generate
from tfde_tpu.inference.prefix_cache import DEFAULT_BLOCK
from tfde_tpu.inference.server import ContinuousBatcher
from tfde_tpu.models.gpt import gpt_tiny_test


@pytest.fixture(scope="module")
def lm():
    m = gpt_tiny_test()
    params = m.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return m, params


def _drain(b, reqs, budgets, max_steps=60):
    ids = [b.submit(p, n) for p, n in zip(reqs, budgets)]
    out = {}
    for _ in range(max_steps):
        for rid, toks in b.step():
            out[rid] = list(map(int, toks))
        if len(out) == len(ids):
            break
    assert len(out) == len(ids), "batcher did not drain"
    return [out[i] for i in ids]


# five requests through three rows: two admission waves, one row freed
# and re-used mid-flight, one duplicate prompt (the warm-sharing case —
# 19 tokens, so its first block is COMPLETE and trie-shareable; a
# shorter duplicate would share nothing), and rider rows decoding while
# a later wave chunk-prefills — the exact shape that once poisoned the
# pool with non-finite junk writes
_PROMPTS = [np.arange(3, 10) % 97, np.arange(5, 11) % 97,
            np.arange(40, 59) % 97, np.arange(7, 12) % 97,
            np.arange(40, 59) % 97]
_BUDGETS = [8, 5, 12, 6, 9]


# --------------------------------------------------------------------------
# BlockPool: allocator unit matrix
# --------------------------------------------------------------------------

def test_blocks_for():
    assert paged.blocks_for(0, 16) == 0
    assert paged.blocks_for(1, 16) == 1
    assert paged.blocks_for(16, 16) == 1
    assert paged.blocks_for(17, 16) == 2
    assert paged.blocks_for(48, 16) == 3


def test_pool_alloc_free_refcount():
    pool = paged.BlockPool(8, 16)
    assert pool.free_blocks == 7            # null excluded
    a = pool.alloc(3)
    assert a == [1, 2, 3]                   # lowest-id-first, deterministic
    assert all(pool.refcount(b) == 1 for b in a)
    pool.incref([2])
    assert pool.refcount(2) == 2
    pool.free([2])                          # one ref down, still held
    assert pool.refcount(2) == 1 and pool.free_blocks == 4
    pool.free(a)                            # all the way back
    assert pool.free_blocks == 7
    s = pool.stats()
    assert s == {"total": 7, "free": 7, "active": 0, "block": 16}
    with pytest.raises(ValueError):
        pool.free([1])                      # double free
    with pytest.raises(ValueError):
        pool.free([paged.NULL_BLOCK])       # null pinned
    with pytest.raises(ValueError):
        pool.incref([5])                    # unallocated


def test_pool_exhausted_rolls_back_and_evictor_drains():
    pool = paged.BlockPool(4, 16)           # 3 allocatable
    pool.alloc(2)
    with pytest.raises(paged.PoolExhausted):
        pool.alloc(2)
    assert pool.free_blocks == 1            # partial take rolled back
    # an evictor that frees one of the held blocks on demand
    held = pool.alloc(1)
    freed = []

    def evictor(need):
        pool.free([held[0]])
        freed.append(need)
        return 1

    pool.set_evictor(evictor)
    got = pool.alloc(1)                     # starves -> evictor -> satisfied
    assert freed == [1] and len(got) == 1
    assert pool.available(evictable=5) == pool.free_blocks + 5


def test_pool_defrag_compacts_to_lowest_ids():
    pool = paged.BlockPool(10, 16)
    a = pool.alloc(6)                       # 1..6
    pool.incref([a[5]])                     # block 6 shared (ref 2)
    pool.free([a[0], a[2], a[4]])           # holes at 1, 3, 5
    plan = pool.defrag()
    # live blocks {2, 4, 6} compact to {1, 2, 3}; refcounts move intact
    assert plan == {2: 1, 4: 2, 6: 3}
    assert pool.refcount(1) == 1 and pool.refcount(2) == 1
    assert pool.refcount(3) == 2            # the shared ref followed
    assert pool.free_blocks == 6
    # idempotent: already compact -> empty plan
    assert pool.defrag() == {}


def test_apply_defrag_moves_pool_rows_and_tables():
    # synthetic 1-leaf cache: pool rows hold their own id as payload
    n, blk = 6, 4
    cache = {"layer": {"pool_key": jnp.arange(n, dtype=jnp.float32)[
        :, None, None, None] * jnp.ones((n, blk, 1, 1), jnp.float32),
        "pool_value": jnp.zeros((n, blk, 1, 1), jnp.float32)}}
    tables = np.asarray([[4, 2, 0]], np.int32)
    plan = {2: 1, 4: 2}
    cache, tables = paged.apply_defrag(cache, tables, plan)
    assert tables.tolist() == [[2, 1, 0]]
    got = np.asarray(cache["layer"]["pool_key"])[:, 0, 0, 0]
    # new id 1 holds old block 2's payload, new id 2 holds old block 4's
    assert got[1] == 2.0 and got[2] == 4.0


# --------------------------------------------------------------------------
# Table gather == dense slab, column for column
# --------------------------------------------------------------------------

def _kv_leaves(cache, names):
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        nm = str(getattr(path[-1], "key", path[-1]))
        if nm in names:
            out.setdefault(nm, []).append(np.asarray(leaf))
    return out


def test_paged_gather_bit_exact_vs_dense(lm):
    """After one admission wave + scan, gathering each row's block table
    into position order must reproduce the dense cached_key/cached_value
    cells: the same committed counts and a table that addresses every
    one of them, exactly; the cells themselves to 1e-5 (the name is from
    when they also agreed to the bit). Two batchers compute them in two
    sets of compiled programs (the ladder's prefill and the chunked one,
    two decode scans), so a K or V of magnitude 3 differs in its last
    places: 1.7e-6 at most here, float32, two layers, jax 0.9.0. A cell
    read through the wrong block or position is another token's, off
    by about 1."""
    model, params = lm
    kw = dict(batch_size=3, max_len=48, scan_depth=4, prefix_cache=False)
    bd = ContinuousBatcher(model, params, paged=False, **kw)
    bp = ContinuousBatcher(model, params, paged=True, **kw)
    for b in (bd, bp):
        for p, n in zip(_PROMPTS[:3], _BUDGETS[:3]):
            b.submit(p, n)
        b.step()
    dense = _kv_leaves(bd._cache, ("cached_key", "cached_value"))
    pool = _kv_leaves(bp._cache, ("pool_key", "pool_value"))
    tables = _kv_leaves(bp._cache, ("block_table",))["block_table"][0]
    # the device table mirrors the host's unless a row was released
    # mid-step — then the host row is zeroed and the upload is deferred
    # to the next program (_tables_dirty); the gather below uses the
    # DEVICE tables, the state the scan actually ran with
    assert bp._tables_dirty or (tables == bp._tables).all()
    assert bp._committed.tolist() == bd._committed.tolist()
    for dname, pname in (("cached_key", "pool_key"),
                         ("cached_value", "pool_value")):
        for dl, pl in zip(dense[dname], pool[pname]):
            gathered = pl[tables].reshape(tables.shape[0], -1,
                                          *pl.shape[2:])
            for r in range(3):
                c = int(bd._committed[r])
                np.testing.assert_allclose(gathered[r, :c], dl[r, :c],
                                           rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Greedy parity through the real batcher
# --------------------------------------------------------------------------

@pytest.mark.parametrize("prefix", [False, True])
def test_paged_greedy_parity_multiwave(lm, prefix):
    """Dense and paged batchers fed the identical 5-request stream
    (2 admission waves, rows freed and re-used, a duplicate prompt for
    the warm path when the trie is on) must emit bit-identical greedy
    tokens."""
    model, params = lm
    kw = dict(batch_size=3, max_len=48, scan_depth=4, prefix_cache=prefix)
    got_d = _drain(ContinuousBatcher(model, params, paged=False, **kw),
                   _PROMPTS, _BUDGETS)
    bp = ContinuousBatcher(model, params, paged=True, **kw)
    got_p = _drain(bp, _PROMPTS, _BUDGETS)
    assert got_p == got_d
    # drain returns every row's blocks; only the trie may keep blocks
    st = bp.block_pool.stats()
    trie = bp._prefix.segments if prefix else 0
    assert st["active"] == trie
    if prefix:
        assert bp._prefix.stats()["hits"] >= 1   # the duplicate prompt


def test_paged_parity_vs_solo_generate(lm):
    """Each batched-paged output must equal the same request run alone
    through decode.generate — the no-scheduler reference."""
    model, params = lm
    bp = ContinuousBatcher(model, params, kv_quant="fp", batch_size=3, max_len=48,
                           scan_depth=4, paged=True, prefix_cache=False)
    got = _drain(bp, _PROMPTS, _BUDGETS)
    for p, n, toks in zip(_PROMPTS, _BUDGETS, got):
        solo, lengths = generate(model, params,
                                 jnp.asarray(p[None, :], jnp.int32),
                                 max_new_tokens=n)
        ref = list(map(int, np.asarray(solo)[0, p.size:int(lengths[0])]))
        assert toks == ref


def test_warm_admission_shares_trie_blocks(lm):
    """A second request with a cached prompt must adopt the trie's
    blocks by refcount (no recompute): after warm admission the shared
    blocks carry refcount 2 — one trie ref, one row ref."""
    model, params = lm
    bp = ContinuousBatcher(model, params, batch_size=2, max_len=48,
                           scan_depth=4, paged=True, prefix_cache=True)
    prompt = (np.arange(0, 33) * 3) % 97     # 33 tokens = 2 full blocks
    rid = bp.submit(prompt, 4)
    while rid not in dict(bp.step()):
        pass
    before = bp._prefix.stats()["hits"]
    trie_blocks = [b for b in range(1, bp.block_pool.num_blocks)
                   if bp.block_pool.refcount(b) == 1]
    assert bp._prefix.segments >= 2          # the prompt's complete blocks
    bp.submit(prompt, 4)
    bp._admit()                              # warm wave runs
    assert bp._prefix.stats()["hits"] == before + 1
    shared = [b for b in trie_blocks if bp.block_pool.refcount(b) == 2]
    assert len(shared) >= 1                  # trie ref + row ref
    while not bp.idle:
        bp.step()


def test_env_flag_selects_paged(lm, monkeypatch):
    model, params = lm
    monkeypatch.setenv("TFDE_PAGED_KV", "on")
    b = ContinuousBatcher(model, params, batch_size=2, max_len=32,
                          scan_depth=2)
    assert b.paged and b.block_pool is not None
    monkeypatch.setenv("TFDE_PAGED_KV", "off")
    b = ContinuousBatcher(model, params, batch_size=2, max_len=32,
                          scan_depth=2)
    assert not b.paged and b.block_pool is None


# --------------------------------------------------------------------------
# Lifecycle: cancel / completion return blocks
# --------------------------------------------------------------------------

def test_cancel_returns_blocks_to_pool(lm):
    model, params = lm
    bp = ContinuousBatcher(model, params, batch_size=2, max_len=48,
                           scan_depth=2, paged=True, prefix_cache=False)
    rid = bp.submit(np.arange(5, 30) % 97, 16)
    bp.step()                                # admitted, decoding
    held = bp.block_pool.stats()["active"]
    assert held >= paged.blocks_for(25, DEFAULT_BLOCK)
    assert bp.cancel(rid)
    assert bp.block_pool.stats()["active"] == 0
    assert bp.block_pool.free_blocks == bp.block_pool.num_blocks - 1
    # the freed row's table is re-pointed at null before the next program
    assert bp._tables_dirty or (bp._tables == 0).all()
    bp.step()                                # no crash on the empty batch
    assert bp.idle


def test_paged_capacity_ledger_blocks_account(lm):
    """kv_stats in paged mode: the pool split must add up, and
    waste_frac is intra-block slack — bounded by (block-1)/block of the
    held cells, 0 when every committed count fills its blocks."""
    model, params = lm
    bp = ContinuousBatcher(model, params, batch_size=3, max_len=48,
                           scan_depth=4, paged=True, prefix_cache=False)
    for p, n in zip(_PROMPTS[:3], _BUDGETS[:3]):
        bp.submit(p, n)
    bp.step()
    s = bp.kv_stats()
    assert s["pool_blocks_total"] == bp.block_pool.num_blocks - 1
    assert (s["pool_blocks_free"] + s["pool_blocks_active"]
            + s["pool_blocks_trie"]) == s["pool_blocks_total"]
    assert 0.0 <= s["waste_frac"] <= 1.0
    # headroom speaks blocks: free pool blocks cap admissible rows
    assert s["headroom_tokens"] == s["pool_blocks_free"] * DEFAULT_BLOCK
    while not bp.idle:
        bp.step()


# --------------------------------------------------------------------------
# Compile discipline: ONE paged prefill program across prompt shapes
# --------------------------------------------------------------------------

def test_paged_prefill_single_compile_across_lengths(lm):
    """Mixed prompt lengths (1 token .. near max_len, crossing chunk
    boundaries) must all run through the same [B, C] chunk program: the
    jit cache grows by exactly one signature for the whole stream."""
    model, params = lm
    bp = ContinuousBatcher(model, params, batch_size=3, max_len=48,
                           scan_depth=4, paged=True, prefix_cache=False)
    before = server._paged_prefill_chunk._cache_size()
    lens = [1, 3, 7, 16, 17, 31, 40]
    reqs = [(np.arange(L) + L) % 97 for L in lens]
    _drain(bp, reqs, [4] * len(reqs))
    grew = server._paged_prefill_chunk._cache_size() - before
    assert grew <= 1, (
        f"paged prefill compiled {grew} programs for {len(lens)} prompt "
        f"lengths — the one-static-program claim regressed"
    )
