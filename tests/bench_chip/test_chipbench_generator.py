"""The benchmark's token generator: one seed gives one pool, every seed
gives the same sizes, and the statistics are the ones it states."""
import numpy as np
import pytest

from chipbench_paths import BENCH  # noqa: F401

import token_generator as tg

ARGS = dict(vocab=1000, n_batches=3, batch=4, seq=256)


def test_same_seed_same_pool():
    a = tg.token_pool(7, **ARGS)
    b = tg.token_pool(7, **ARGS)
    assert a.dtype == np.int32 and a.shape == (3, 4, 257)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 31 + 12345,
                                  2 ** 40 + 3])
def test_any_seed_same_sizes_other_tokens(seed):
    a = tg.token_pool(seed, **ARGS)
    b = tg.token_pool(seed + 1, **ARGS)
    assert a.shape == b.shape == (3, 4, 257)
    assert a.min() >= 0 and a.max() < 1000
    assert (a != b).mean() > 0.5


def test_statistics():
    pool = tg.token_pool(3, vocab=500, n_batches=8, batch=8, seq=512,
                         follow_prob=0.7, successors=4)
    succ = np.random.default_rng(np.random.SeedSequence(
        [3, tg.SUCCESSOR_STREAM])).integers(0, 500, size=(500, 4))
    rows = pool.reshape(-1, 513)
    prev, nxt = rows[:, :-1].ravel(), rows[:, 1:].ravel()
    followed = (succ[prev] == nxt[:, None]).any(axis=1).mean()
    # 70% follow a successor; a fresh Zipf draw hits one now and then
    assert 0.68 < followed < 0.76
    # rank 1 (id 0) is the most frequent fresh draw
    counts = np.bincount(rows[:, 0], minlength=500)
    assert counts[0] == counts.max()


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        tg.token_pool(-1, **ARGS)
