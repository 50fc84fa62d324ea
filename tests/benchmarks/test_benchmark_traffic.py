"""The traffic generator is a pure function of (mix, seed, seconds), and
every seed gets the same work in another order."""

import numpy as np
import pytest

from benchmarks.lib import traffic
from benchmarks.lib.manifest import Manifest

CHAT = Manifest().traffic("chat-poisson-r80")
BIG = 2**31 + 12345


def _lengths(requests):
    return (sorted(r.prompt.size for r in requests),
            sorted(r.max_new_tokens for r in requests))


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_same_seed_same_requests(seed):
    a = traffic.generate(CHAT, seed, 35.0, vocab=50257)
    b = traffic.generate(CHAT, seed, 35.0, vocab=50257)
    assert len(a) == len(b) == round(CHAT["rate_per_s"] * 35.0)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = traffic.generate(CHAT, 1, 35.0, vocab=50257)
    b = traffic.generate(CHAT, BIG, 35.0, vocab=50257)
    assert _lengths(a) == _lengths(b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    # the gaps are one multiset too: all but the one that the permutation
    # put first (the first request opens the window) show as differences
    def gaps(rs):
        return np.sort(np.diff([r.due_s for r in rs]))

    assert len(a) == len(b)
    assert np.abs(gaps(a) - gaps(b)).max() < 0.5 * gaps(a).max()
    assert np.median(gaps(a)) == pytest.approx(np.median(gaps(b)), rel=0.02)


def test_requests_fit_the_window_the_limits_and_the_cache():
    requests = traffic.generate(CHAT, 3, 35.0, vocab=50257)
    p, o = CHAT["prompt"], CHAT["output"]
    assert requests[0].due_s == 0.0
    assert all(0.0 <= r.due_s < 35.0 for r in requests)
    assert all(a.due_s <= b.due_s for a, b in zip(requests, requests[1:]))
    assert all(p["min"] <= r.prompt.size <= p["max"] for r in requests)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in requests)
    assert all(r.prompt.size + r.max_new_tokens <= 1024 for r in requests)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50257
               for r in requests)
    sizes = np.array([r.prompt.size for r in requests])
    assert 150 <= np.median(sizes) <= 240


def test_gaps_are_exponential_with_the_rate_as_their_mean():
    gaps = traffic.gap_quantiles(400, 4.0)
    assert gaps.mean() == pytest.approx(0.25)
    assert 0.9 < gaps.std() / gaps.mean() < 1.05


def test_train_pool_rows_all_differ_and_depend_on_the_seed():
    mix = {"generator": "train_steady", "sequences_per_chip": 2,
           "pool_rows_per_chip": 8}
    a = traffic.generate(mix, 1, 1.0, vocab=97, seq_len=32, chips=4)
    b = traffic.generate(mix, 1, 1.0, vocab=97, seq_len=32, chips=4)
    c = traffic.generate(mix, BIG, 1.0, vocab=97, seq_len=32, chips=4)
    assert a["global_batch"] == 8 and a["pool"].shape == (32, 32)
    assert np.array_equal(a["pool"], b["pool"])
    assert not np.array_equal(a["pool"], c["pool"])
    assert len({row.tobytes() for row in a["pool"]}) == 32


def test_unknown_generator_is_an_error():
    with pytest.raises(KeyError):
        traffic.generate({"generator": "nope"}, 1, 1.0)
