"""Ground truth + recall@k (paper Eq. 3)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ground_truth", "recall_at_k"]


@functools.partial(jax.jit, static_argnames=("k",))
def _gt_chunk(q: jnp.ndarray, x: jnp.ndarray, x_sq: jnp.ndarray, k: int):
    # HIGHEST: the TPU's default matmul precision is one bf16 pass, which
    # would rank the exact neighbours by rounded distances.
    dots = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = x_sq[None, :] - 2.0 * dots
    _, idx = jax.lax.top_k(-d2, k)
    return idx.astype(jnp.int32)


def ground_truth(x: np.ndarray, queries: np.ndarray, k: int,
                 chunk: int = 256) -> np.ndarray:
    """Exact top-k ids (nq, k) by chunked brute force."""
    x = jnp.asarray(x, jnp.float32)
    queries = np.asarray(queries, np.float32)
    x_sq = jnp.sum(x * x, axis=-1)
    out = np.empty((queries.shape[0], k), np.int32)
    for s in range(0, queries.shape[0], chunk):
        e = min(s + chunk, queries.shape[0])
        out[s:e] = np.asarray(_gt_chunk(jnp.asarray(queries[s:e]), x, x_sq, k))
    return out


def recall_at_k(pred_ids: np.ndarray, gt_ids: np.ndarray) -> float:
    """|A_k ∩ N_k| / k averaged over queries (Eq. 3)."""
    pred_ids = np.asarray(pred_ids)
    gt_ids = np.asarray(gt_ids)
    if pred_ids.shape != gt_ids.shape:
        raise ValueError(f"shape mismatch {pred_ids.shape} vs {gt_ids.shape}")
    k = gt_ids.shape[1]
    hits = 0
    for p, g in zip(pred_ids, gt_ids):
        hits += np.intersect1d(p, g).size
    return hits / (k * gt_ids.shape[0])
