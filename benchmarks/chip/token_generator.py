"""The benchmark's token generator: one general generator that every
traffic file parameterises.

It draws the statistics of the program's ``SyntheticLMStream`` (a
Zipf unigram over the vocabulary, and a fixed table of a few successors
per token that the next token follows with a set probability), but
vectorised over every row of a pool, so that a pool of many batches is
made in well under a second.  The program's stream rebuilds an
O(vocab) table at each of the ``seq + 1`` positions of every batch,
which costs about 0.8 s a batch at vocab 49152; this copy lives with
the benchmark so that no later change to the program can move the
yardstick.

Everything is drawn from ``numpy.random.SeedSequence(seed)``, which
takes any non-negative whole number, so every seed gives the same sizes
in another order of tokens.
"""
from __future__ import annotations

import numpy as np

# stream ids: one child of the run's seed per use, so that the tokens,
# the successor table and the weights never share random numbers
SUCCESSOR_STREAM = 11
TOKEN_STREAM = 12


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    """Cumulative unigram distribution, p(rank r) proportional to
    1 / r**exponent over ranks 1..vocab (token id = rank - 1)."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def token_pool(seed: int, vocab: int, n_batches: int, batch: int,
               seq: int, *, zipf_exponent: float = 1.0,
               follow_prob: float = 0.7, successors: int = 4) -> np.ndarray:
    """``(n_batches, batch, seq + 1)`` int32 token ids in ``[0, vocab)``.

    Each row starts with a unigram draw; every later token is, with
    probability ``follow_prob``, one of its predecessor's ``successors``
    fixed successors (picked uniformly), else a fresh unigram draw.
    """
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    succ_rng = np.random.default_rng(np.random.SeedSequence(
        [seed, SUCCESSOR_STREAM]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, TOKEN_STREAM]))
    succ = succ_rng.integers(0, vocab, size=(vocab, successors),
                             dtype=np.int64)
    cdf = zipf_cdf(vocab, zipf_exponent)
    rows, width = n_batches * batch, seq + 1
    fresh = np.searchsorted(cdf, rng.random((rows, width)), side="right")
    fresh = np.minimum(fresh, vocab - 1)
    follow = rng.random((rows, width)) < follow_prob
    pick = rng.integers(0, successors, size=(rows, width))
    out = np.empty((rows, width), np.int64)
    out[:, 0] = fresh[:, 0]
    for t in range(1, width):
        nxt = succ[out[:, t - 1], pick[:, t]]
        out[:, t] = np.where(follow[:, t], nxt, fresh[:, t])
    return out.astype(np.int32).reshape(n_batches, batch, width)


def pool_for(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """The pool a traffic file asks for, over ``vocab`` ids."""
    gen = traffic["generator"]
    return token_pool(seed, vocab, traffic["pool_batches"], traffic["batch"],
                      traffic["seq"], zipf_exponent=gen["zipf_exponent"],
                      follow_prob=gen["follow_prob"],
                      successors=gen["successors"])
