"""Seeded data: the corpus, the query streams and the arrival schedule.

Everything is drawn from ``--seed`` through numpy's ``SeedSequence``,
which takes any non-negative whole number, so the same seed gives the
same inputs on any machine. Each stream has its own spawn key, so adding
a stream never moves another.

The corpus is the repository's ``data/synthetic.make_corpus`` (isotropic
Gaussian clusters around centres of scale 1.5), copied here so that the
yardstick cannot move with the program; it draws in float32, which is
what the rows are stored in, so 1M x 384 takes seconds instead of the
float64 original's 15-20 s.
"""
from __future__ import annotations

import numpy as np

CORPUS, QUERIES, WARMUP, ORDER, SAMPLE = range(5)
# a fixed stream for the multiset of inter-arrival gaps, the same for
# every seed: seeds differ in the order of the gaps, not in their sizes
GAPS_KEY = 0x6A9
CHUNK = 4096


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream]))


def corpus(seed: int, rows: int, dim: int, clusters: int,
           center_scale: float) -> np.ndarray:
    """[rows, dim] float32: a Gaussian cluster member per row."""
    g = rng(seed, CORPUS)
    centers = g.standard_normal((clusters, dim), dtype=np.float32)
    centers *= np.float32(center_scale)
    assign = g.integers(0, clusters, rows)
    x = g.standard_normal((rows, dim), dtype=np.float32)
    for lo in range(0, rows, 1 << 16):
        x[lo:lo + (1 << 16)] += centers[assign[lo:lo + (1 << 16)]]
    return x


class QueryStream:
    """Unique queries: a corpus row plus ``noise``·N(0, 1), drawn in
    chunks of CHUNK so that query i is the same however many are taken."""

    def __init__(self, rows: np.ndarray, seed: int, stream: int,
                 noise: float):
        self.rows, self.seed, self.stream = rows, seed, stream
        self.noise = np.float32(noise)
        self._chunks: list[np.ndarray] = []

    def _chunk(self, c: int) -> np.ndarray:
        while len(self._chunks) <= c:
            g = rng(self.seed, self.stream, len(self._chunks))
            pick = g.integers(0, len(self.rows), CHUNK)
            q = g.standard_normal((CHUNK, self.rows.shape[1]),
                                  dtype=np.float32)
            q *= self.noise
            q += self.rows[pick]
            self._chunks.append(q)
        return self._chunks[c]

    def ensure(self, n: int) -> None:
        """Draw the first ``n`` queries now (in set-up, not the window)."""
        if n > 0:
            self._chunk((n - 1) // CHUNK)

    def __getitem__(self, i: int) -> np.ndarray:
        return self._chunk(i // CHUNK)[i % CHUNK]

    def take(self, idx) -> np.ndarray:
        return np.stack([self[int(i)] for i in idx])


def poisson_arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of round(rate·seconds) arrivals. The gaps
    are one fixed multiset of exponential draws, scaled so the arrivals
    fill the window, in an order drawn from the seed."""
    n = max(int(round(rate * seconds)), 1)
    gaps = rng(GAPS_KEY, n).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    gaps = gaps[rng(seed, ORDER).permutation(n + 1)]
    return np.cumsum(gaps[:n])
