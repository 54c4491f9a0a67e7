"""Deployment rows and Zipf query traffic.

This is the benchmark's own copy of the generators, so that a change to
the program cannot change what the benchmark feeds it:

* :func:`make_rows` follows ``make_deployment`` of the repository's
  ``chip_smoke.py``: clustered points on a random low-dimensional
  manifold of R^d plus isotropic noise, float32;
* :class:`ZipfWorkload` follows ``repro.core.workload.ZipfWorkload`` (the
  paper's workload, section 5.1.2): a query targets row i with
  probability proportional to (rank + 1)^-beta and is that row plus
  fresh Gaussian noise scaled to the data's spread.

A deployment (its rows, which rows are popular, the history its hot
index and tree were fitted on) comes from the configuration's own
``data_seed``, as a real deployment is one dataset; ``--seed`` draws the
traffic of the window (:meth:`ZipfWorkload.stream`), so every seed sends
the same kind of work in another order.
"""

from __future__ import annotations

import numpy as np

__all__ = ["seed_rng", "make_rows", "zipf_probs", "ZipfWorkload",
           "QueryStream"]

_MASK64 = (1 << 64) - 1


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one named use of a seed.

    Any integer seed works, negative or above 2**63: it is folded into
    64 bits before it reaches numpy.
    """
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & _MASK64, int(stream)]))


def make_rows(n: int, d: int, latent: int, clusters: int,
              center_scale: float, noise: float,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` rows of width ``d``: ``clusters`` Gaussian clusters with
    centres ``center_scale`` sigma apart on a random ``latent``-dim
    subspace, plus isotropic noise of standard deviation ``noise``."""
    centers = center_scale * rng.standard_normal((clusters, latent))
    z = centers[rng.integers(0, clusters, n)] \
        + rng.standard_normal((n, latent))
    basis = rng.standard_normal((latent, d)) / np.sqrt(latent)
    x = z @ basis + noise * rng.standard_normal((n, d))
    return np.ascontiguousarray(x, np.float32)


def zipf_probs(n: int, beta: float) -> np.ndarray:
    """P(rank r) proportional to r^-beta, r = 1..n."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-beta)
    return p / p.sum()


class ZipfWorkload:
    """Zipf-skewed queries over the rows ``x``.  ``rng`` fixes which rows
    are popular and draws :meth:`sample`'s history."""

    def __init__(self, x: np.ndarray, beta: float, sigma: float,
                 rng: np.random.Generator):
        self.x = x
        self._rng = rng
        n = x.shape[0]
        self.rank_to_point = rng.permutation(n)
        self.probs = zipf_probs(n, beta)
        self.noise_scale = float(x.std()) * sigma

    def targets(self, num: int, rng: np.random.Generator) -> np.ndarray:
        ranks = rng.choice(self.probs.size, size=num, p=self.probs)
        return self.rank_to_point[ranks]

    def noise(self, num: int, rng: np.random.Generator) -> np.ndarray:
        z = rng.standard_normal((num, self.x.shape[1]), dtype=np.float32)
        return z * np.float32(self.noise_scale)

    def sample(self, num: int) -> np.ndarray:
        """``num`` queries, each a target row plus fresh noise."""
        t = self.targets(num, self._rng)
        return self.x[t] + self.noise(num, self._rng)

    def stream(self, rng: np.random.Generator,
               chunk: int = 16384) -> "QueryStream":
        """The window's queries, drawn from ``rng``."""
        return QueryStream(self, rng, chunk)


class QueryStream:
    """The window's queries, in a fixed order from its generator.

    Targets and noise are drawn ``chunk`` queries at a time, every query
    with noise of its own, so the order does not depend on how many
    queries each call takes.
    """

    def __init__(self, wl: ZipfWorkload, rng: np.random.Generator,
                 chunk: int):
        self._wl = wl
        self._rng = rng
        self._chunk = chunk
        self.dim = wl.x.shape[1]
        self._targets = np.zeros(0, np.int64)
        self._queries = np.zeros((0, wl.x.shape[1]), np.float32)
        self.count = 0

    def take(self, m: int):
        """The next ``m`` queries and their targets."""
        need = self.count + m
        while self._targets.size < need:
            t = self._wl.targets(self._chunk, self._rng)
            q = self._wl.x[t] + self._wl.noise(self._chunk, self._rng)
            keep = self.count
            self._targets = np.concatenate([self._targets[keep:], t])
            self._queries = np.concatenate([self._queries[keep:], q])
            self.count -= keep
            need -= keep
        s = slice(self.count, need)
        self.count = need
        return self._queries[s], self._targets[s]
