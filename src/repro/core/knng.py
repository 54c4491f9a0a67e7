"""K-nearest-neighbor graph construction (the paper's EFANNA stage).

The paper bootstraps SSG pruning from a pre-built approximate KNN graph built
with EFANNA (divide-and-conquer + NN-descent).  We provide two builders:

* :func:`exact_knn` — chunked brute force on top of XLA matmuls.  On TPU this
  is MXU-bound and is the *right* choice up to a few hundred thousand rows;
  it is also the oracle for tests.
* :func:`nn_descent` — a vectorized NN-descent refinement (the EFANNA
  workhorse) for larger tables: start from a random graph and repeatedly
  join each node's neighborhood with its neighbors' neighborhoods, keeping
  the k best.  Converges in a handful of rounds on real data.

Both return ``(n, k) int32`` neighbor ids excluding self.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["exact_knn", "nn_descent", "build_knng"]


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_chunk(chunk: jnp.ndarray, x: jnp.ndarray, x_sq: jnp.ndarray,
               row0: jnp.ndarray, k: int):
    """Top-(k) neighbors of ``chunk`` rows against the full table ``x``."""
    # ||c - x||^2 = ||c||^2 - 2 c.x + ||x||^2 ; ||c||^2 is rank-constant.
    # HIGHEST: exact f32 dots on TPU too (the default is one bf16 pass)
    dots = jnp.matmul(chunk, x.T,
                      precision=jax.lax.Precision.HIGHEST)  # (C, n)
    d2 = x_sq[None, :] - 2.0 * dots                       # (C, n) + const
    # Mask self-matches by row id (exact duplicates of other rows are kept —
    # they are legitimate neighbors).
    n = x.shape[0]
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    rows = row0 + jnp.arange(chunk.shape[0], dtype=jnp.int32)[:, None]
    d2 = jnp.where(cols == rows, jnp.inf, d2)
    neg_d, idx = jax.lax.top_k(-d2, k)
    return idx.astype(jnp.int32), -neg_d + jnp.sum(chunk * chunk, -1)[:, None]


def exact_knn(x: np.ndarray, k: int, chunk: int = 1024):
    """Exact KNN ids ``(n, k)`` and squared distances, chunked over rows."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    if k >= n:
        raise ValueError(f"k={k} must be < n={n}")
    x_sq = jnp.sum(x * x, axis=-1)
    ids_out = np.empty((n, k), np.int32)
    d_out = np.empty((n, k), np.float32)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        ids, d = _knn_chunk(x[s:e], x, x_sq, jnp.int32(s), k)
        ids_out[s:e] = np.asarray(ids)
        d_out[s:e] = np.asarray(d)
    return ids_out, d_out


def _pairwise_sq(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Squared L2 between row sets, numpy (used inside NN-descent rounds)."""
    return (
        np.sum(a * a, -1)[:, None]
        - 2.0 * (a @ b.T)
        + np.sum(b * b, -1)[None, :]
    )


def nn_descent(
    x: np.ndarray,
    k: int,
    *,
    rounds: int = 8,
    sample: int = 16,
    seed: int = 0,
    tol: float = 0.001,
) -> np.ndarray:
    """Vectorized NN-descent: ``(n, k) int32`` approximate KNN ids.

    Each round joins every node's current neighborhood with a sample of its
    neighbors' neighborhoods (the local-join of NN-descent, batched with
    numpy gathers rather than per-node hash sets).
    """
    rng = np.random.default_rng(seed)
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    if k >= n:
        raise ValueError(f"k={k} must be < n={n}")

    # Random initial graph (without self loops).
    ids = rng.integers(0, n - 1, size=(n, k), dtype=np.int64)
    ids += ids >= np.arange(n)[:, None]  # skip self
    dists = _gather_dists(x, ids)
    order = np.argsort(dists, axis=1)
    ids = np.take_along_axis(ids, order, 1)
    dists = np.take_along_axis(dists, order, 1)

    for _ in range(rounds):
        s = min(sample, k)
        picked = ids[:, rng.permutation(k)[:s]]                 # (n, s)
        # neighbors-of-neighbors: gather each picked neighbor's own list.
        non = ids[picked.reshape(-1)].reshape(n, s * k)         # (n, s*k)
        rev = _reverse_sample(ids, n, s, rng)                   # (n, s)
        cand = np.concatenate([picked, non, rev], axis=1)       # (n, C)
        # Replace self-references with an existing neighbor (harmless dup —
        # the unique pass below pushes duplicates to +inf).
        cand = np.where(cand == np.arange(n)[:, None], ids[:, :1], cand)
        cd = _gather_dists(x, cand)
        # Merge candidates with the current list and keep the k smallest
        # unique ids.
        all_ids = np.concatenate([ids, cand], 1)
        all_d = np.concatenate([dists, cd], 1)
        # unique-per-row: sort by (id), mark first occurrence, push dups to inf
        o = np.argsort(all_ids, 1, kind="stable")
        si = np.take_along_axis(all_ids, o, 1)
        sd = np.take_along_axis(all_d, o, 1)
        dup = np.zeros_like(sd, bool)
        dup[:, 1:] = si[:, 1:] == si[:, :-1]
        sd[dup] = np.inf
        o2 = np.argsort(sd, 1, kind="stable")[:, :k]
        new_ids = np.take_along_axis(si, o2, 1)
        new_d = np.take_along_axis(sd, o2, 1)
        changed = np.mean(new_ids != ids)
        ids, dists = new_ids, new_d
        if changed < tol:
            break
    return ids.astype(np.int32)


def _gather_dists(x: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """d2(x[i], x[ids[i, j]]) computed in row blocks to bound memory."""
    n, c = ids.shape
    out = np.empty((n, c), np.float32)
    blk = max(1, int(4e7 // max(1, c * x.shape[1])))
    for s in range(0, n, blk):
        e = min(s + blk, n)
        g = x[ids[s:e]]                       # (b, c, d)
        diff = g - x[s:e, None, :]
        out[s:e] = np.einsum("bcd,bcd->bc", diff, diff)
    return out


def _reverse_sample(ids: np.ndarray, n: int, s: int, rng) -> np.ndarray:
    """Sample of reverse edges: for each node, s nodes that point at it."""
    k = ids.shape[1]
    src = np.repeat(np.arange(n), k)
    dst = ids.reshape(-1)
    perm = rng.permutation(n * k)
    rev = np.full((n, s), -1, np.int64)
    fill = np.zeros(n, np.int64)
    # First-come-first-served fill of up to s reverse slots per node.
    for p in perm[: min(n * k, 4 * n * s)]:
        d = dst[p]
        f = fill[d]
        if f < s:
            rev[d, f] = src[p]
            fill[d] = f + 1
    # Backfill unfilled slots with random ids.
    mask = rev < 0
    rev[mask] = rng.integers(0, n, size=int(mask.sum()))
    return rev


def build_knng(x: np.ndarray, k: int, *, exact_threshold: int = 60_000,
               seed: int = 0) -> np.ndarray:
    """EFANNA-stage dispatcher: exact below the threshold, NN-descent above."""
    if x.shape[0] <= exact_threshold:
        ids, _ = exact_knn(x, k)
        return np.asarray(ids)
    return nn_descent(x, k, seed=seed)
