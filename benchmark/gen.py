"""Seeded traffic: zipf ids and Poisson due times.

The arithmetic is a copy of ``brpc_tpu/press.py`` (``zipf_weights``, the
exponential inter-arrival loop of ``build_ops``), kept here because later
PRs may change the program and not the yardstick. One general generator
reads a mix's parameters from ``benchmark/traffic/<mix>.json``; nothing in
this file knows a cell by name.

Every seed gives the same SIZES (ids per call, calls per worker, rate) and
only another order of ids and another set of gaps, so that two seeds
differ no more than two runs of one seed.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for one named stream of one seed. ``seed``
    may be any non-negative whole number (the driver's pass 2**31)."""
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), *[int(s) for s in stream]])))


def zipf_weights(vocab: int, s: float) -> np.ndarray:
    """Normalized zipf(s) pmf over ``vocab`` ranks (rank 1 hottest)."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    w = ranks ** -float(s)
    return w / w.sum()


class ZipfIds:
    """Draws ids whose RANKS are zipf(s) over the vocabulary. A seeded
    permutation scatters the hot ranks over the table's rows, as a real
    vocabulary's frequent tokens are; rank r falls into row range
    ``r % stripes`` of ``stripes`` equal ranges, so that every seed loads
    the ranges (the shards) alike and only the rows inside them differ."""

    def __init__(self, vocab: int, s: float, seed: int, stripes: int = 1):
        self.cdf = np.cumsum(zipf_weights(vocab, s))
        self.cdf[-1] = 1.0
        per = vocab // stripes
        within = rng_for(seed, 1).permutation(per)
        ranks = np.arange(per * stripes)
        self.rank_to_id = ((ranks % stripes) * per +
                           within[ranks // stripes]).astype(np.int32)
        if per * stripes < vocab:      # ranks past the stripes keep their row
            self.rank_to_id = np.r_[self.rank_to_id, np.arange(
                per * stripes, vocab, dtype=np.int32)]

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, rng.random(n), side="right")
        return self.rank_to_id[ranks]


def poisson_due_times(rng: np.random.Generator, rate_per_s: float,
                      seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson process of the given rate:
    cumulated exponential gaps, as ``press.build_ops`` draws them."""
    n_guess = int(rate_per_s * seconds * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / rate_per_s, n_guess))
    while t[-1] < seconds:
        more = np.cumsum(rng.exponential(1.0 / rate_per_s, n_guess)) + t[-1]
        t = np.concatenate([t, more])
    return t[t < seconds]


def normal_table(seed: int, rows: int, dim: int, threads: int = 8,
                 chunks: int = 64) -> np.ndarray:
    """A rows x dim float32 table of standard normals drawn once, in
    ``chunks`` fixed row blocks (so the values do not depend on the thread
    count), each from its own stream of the seed."""
    from concurrent.futures import ThreadPoolExecutor

    out = np.empty((rows, dim), np.float32)
    edges = np.linspace(0, rows, min(chunks, rows) + 1).astype(int)

    def one(i):
        rng_for(seed, 2, i).standard_normal(
            out=out[edges[i]:edges[i + 1]], dtype=np.float32)

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, range(len(edges) - 1)))
    return out
