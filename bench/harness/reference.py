"""The plain reference and the comparison that decides ``correct``.

The reference is an exact blocked top-k under squared L2 on the device.
It imports nothing of the program and takes nothing the program made:
rows and queries come from the benchmark's own generator.  Candidates are
ranked by a matmul at ``Precision.HIGHEST`` (the TPU's default would be
one bf16 pass) and the best ``k + CAND_EXTRA`` are re-scored by a direct
float32 sum of squared differences, which is also how the served
distances are checked.

The control computes the same top-k in the precision just below:
``high`` is three bf16 passes (``bf16_3x``), written out here so that it
means the same on every backend; ``bf16`` is one pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference", "judge", "CHECK_ORDER"]

CAND_EXTRA = 22          # candidates beyond k that the exact rescoring sees
BLOCK = 1024             # queries per device block

# the numbers compared, in the order they are printed
CHECK_ORDER = ("unanswered", "not_ok", "malformed", "dist_gap",
               "recall_deficit")


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    survives XLA's excess-precision rewrites, which may drop a round trip
    through ``astype(bfloat16)``."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dots(q, x, precision: str):
    highest = functools.partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return highest(q, x.T)
    # products of bfloat16 values are exact in float32, so a HIGHEST
    # matmul of the rounded parts is what the MXU's bf16 passes compute
    qh, xh = _bf16(q), _bf16(x)
    out = highest(qh, xh.T)
    if precision == "high":
        ql, xl = _bf16(q - qh), _bf16(x - xh)
        out = out + highest(qh, xl.T) + highest(ql, xh.T)
    elif precision != "bf16":
        raise ValueError(f"unknown precision {precision!r}")
    return out


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _ranked(q, x, x_sq, m: int, precision: str):
    """Best ``m`` rows per query by the expanded distance, and that
    distance (|q|^2 + |x|^2 - 2 q.x) at ``precision``."""
    q_sq = jnp.sum(q * q, axis=1, keepdims=True)
    d2 = q_sq + x_sq[None, :] - 2.0 * _dots(q, x, precision)
    neg, idx = jax.lax.top_k(-d2, m)
    return idx.astype(jnp.int32), -neg


@jax.jit
def _direct(q, x, ids):
    """Squared L2 of each query against rows ``ids``, by a direct sum."""
    diff = x[ids] - q[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def _exact_topk(q, x, x_sq, k: int):
    cand, _ = _ranked(q, x, x_sq, k + CAND_EXTRA, "highest")
    d = _direct(q, x, cand)
    order = jnp.argsort(d, axis=1)[:, :k]
    return (jnp.take_along_axis(cand, order, 1),
            jnp.take_along_axis(d, order, 1))


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _lower_topk(q, x, x_sq, k: int, precision: str):
    return _ranked(q, x, x_sq, k, precision)


class Reference:
    """Exact top-k and exact distances over rows held on the device."""

    def __init__(self, x: np.ndarray):
        self.n = x.shape[0]
        self.x = jnp.asarray(x)
        self.x_sq = jnp.sum(self.x * self.x, axis=1)
        self.eps = float(1e-6 * np.mean(np.sum(
            np.asarray(x, np.float64) ** 2, axis=1)))

    def _blocks(self, queries, fn):
        outs = []
        for s in range(0, len(queries), BLOCK):
            m = min(BLOCK, len(queries) - s)
            q = np.zeros((BLOCK, queries.shape[1]), np.float32)
            q[:m] = queries[s:s + m]
            outs.append([np.asarray(a)[:m]
                         for a in fn(jnp.asarray(q), s, m)])
        return [np.concatenate(parts) for parts in zip(*outs)]

    def top_k(self, queries, k: int, precision="highest"):
        """``(ids, dists)`` of the k nearest rows; ``highest`` is the
        reference, ``high`` and ``bf16`` are the controls."""
        if precision == "highest":
            fn = lambda q, s, m: _exact_topk(q, self.x, self.x_sq, k)
        else:
            fn = lambda q, s, m: _lower_topk(q, self.x, self.x_sq, k,
                                             precision)
        return self._blocks(queries, fn)

    def distances(self, queries, ids):
        """Exact squared L2 of each query to each of its ``ids``."""
        safe = np.clip(ids, 0, self.n - 1).astype(np.int32)

        def fn(q, s, m):
            blk = np.zeros((BLOCK, ids.shape[1]), np.int32)
            blk[:m] = safe[s:s + m]
            return (_direct(q, self.x, jnp.asarray(blk)),)
        return self._blocks(queries, fn)[0]


def judge(ids, dists, answered, ok, ref_ids, exact_d, *, n: int,
          eps: float, limits: dict) -> dict:
    """The numbers compared, each ``{"value": v, "limit": l}``.

    ``ids``/``dists`` are the served results of every request the window
    was due to answer (rows of unanswered requests are ignored),
    ``ref_ids`` the reference's top-k, ``exact_d`` the exact distance of
    each served id.
    """
    a = np.asarray(answered, bool)
    got, gd, ed = ids[a], dists[a], exact_d[a]
    in_range = (got >= 0) & (got < n)
    srt = np.sort(got, axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    bad = (~in_range.all(axis=1)) | dup | (~np.isfinite(gd).all(axis=1)) \
        | (np.diff(gd, axis=1) < 0).any(axis=1)
    good = ~bad
    if good.any():
        gap = np.abs(gd[good] - ed[good]) / np.maximum(ed[good], eps)
        dist_gap = float(gap.max())
    else:
        dist_gap = float("inf")
    if a.any():
        hit = (got[:, :, None] == ref_ids[a][:, None, :]).any(-1)
        recall = float(hit.mean())
    else:
        recall = 0.0
    values = {"unanswered": int((~a).sum()),
              "not_ok": int((a & ~np.asarray(ok, bool)).sum()),
              "malformed": int(bad.sum()),
              "dist_gap": dist_gap,
              "recall_deficit": 1.0 - recall}
    return {name: {"value": values[name], "limit": limits[name]}
            for name in CHECK_ORDER}
