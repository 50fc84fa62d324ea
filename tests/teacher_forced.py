"""Teacher-forced serving of a wave of rows as ContinuousBatcher does it,
for the model files that compare LOGITS with a plain reference
(test_eva_attention.py, test_granite_hybrid.py, test_smallthinker.py)."""

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tfde_tpu.inference import server
from tfde_tpu.inference.decode import _decode_clone, init_cache
from tfde_tpu.inference.speculative import _set_index_counters


class Programs(NamedTuple):
    """A model's prefill of a padded bucket and its one-token step, jitted.
    Held in a module's fixture they compile once a shape for every test
    that only reads; a test that patches what a trace reads builds its own
    after patching."""
    model: object
    rolling: bool
    prefill: Callable
    step: Callable


def programs(model, rolling: bool = False, mutable=("cache",)) -> Programs:
    """`rolling`: window layers keep a ring of cells (false: every layer a
    slab, the band a mask). `mutable`: the collections an apply may write;
    with "counters" the expert layers sow their routing counts."""
    decode_model = _decode_clone(model, rolling=rolling)
    mutable = list(mutable)

    @jax.jit
    def prefill(params, cache, prompts, last):
        # true lengths told through `feed_pad`, the head at the last true
        # token alone
        cache = server._set_feed_pad(cache, prompts.shape[1] - 1 - last)
        logits, mutated = decode_model.apply(
            {"params": params, "cache": cache}, prompts, last=last,
            mutable=mutable)
        return mutated["cache"], logits[:, 0]

    @jax.jit
    def step(params, cache, feed, idx, done):
        cache = _set_index_counters(cache, idx)
        cache = server._set_feed_pad(cache, done)
        logits, mutated = decode_model.apply(
            {"params": params, "cache": cache}, feed[:, None],
            mutable=mutable)
        return mutated["cache"], logits[:, 0]

    return Programs(model, rolling, prefill, step)


def served_logits(progs: Programs, params, rows, lengths, bucket, max_len,
                  freeze=None, snapshots=None):
    """Serve `rows` (each a full sequence): prefill the first lengths[r]
    tokens right-padded to `bucket` into a fresh row cache, rewind the
    index to the true lengths as admission does, then feed the rest one
    token a step under per-row indices as `_decode_scan` does. `freeze` =
    (row, step): from that step on the row is fed padding at a frozen
    index. `snapshots`, a list, takes the cache after every step. Returns
    per row the logits at positions lengths[r]-1 .. (one vector a fed
    position), and the cache."""
    n = len(rows)
    lengths = np.asarray(lengths, np.int32)
    prompts = np.zeros((n, bucket), np.int32)
    for r, row in enumerate(rows):
        prompts[r, :lengths[r]] = row[:lengths[r]]
    cache = init_cache(progs.model, n, max_len, rolling=progs.rolling)
    cache, first = progs.prefill(params, cache, jnp.asarray(prompts),
                                 jnp.asarray(lengths - 1))
    out = [[np.asarray(first[r])] for r in range(n)]
    idx = lengths.copy()
    steps = max(len(row) for row in rows) - int(lengths.min())
    for t in range(steps):
        done = np.asarray([idx[r] >= len(rows[r]) or (
            freeze is not None and r == freeze[0] and t >= freeze[1])
            for r in range(n)])
        feed = np.asarray([0 if done[r] else rows[r][idx[r]]
                           for r in range(n)], np.int32)
        cache, logits = progs.step(params, cache, jnp.asarray(feed),
                                   jnp.asarray(idx), jnp.asarray(done))
        if snapshots is not None:
            snapshots.append(jax.device_get(cache))
        for r in range(n):
            if not done[r]:
                out[r].append(np.asarray(logits[r]))
                idx[r] += 1
    return [np.stack(o) for o in out], cache


def worst_gap(reference: Callable, rows, lengths, got) -> float:
    """The largest distance of the served logits from `reference(row)`'s,
    over every row and fed position."""
    worst = 0.0
    for row, n, logits in zip(rows, lengths, got):
        want = reference(row)[n - 1:n - 1 + len(logits)]
        worst = max(worst, float(np.abs(logits - want).max()))
    return worst
