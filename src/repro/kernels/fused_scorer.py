"""Pallas TPU kernel: fused brute-force scoring + running top-k.

The beyond-paper hot layer (DESIGN.md §2.1): score a query tile against the
whole hot set block by block, keeping a (bq, k) running top-k accumulator in
VMEM scratch across the sequential N-block grid dimension — the (B, N)
distance matrix never exists in HBM.  This is the TPU-KNN formulation of
exact small-corpus search: MXU does the distances, a bitonic network does
the merge, arithmetic intensity stays at matmul level.

Grid: (B/bq, N/bn), N innermost & sequential ("arbitrary"); the scratch is
(re)initialized at block 0 and flushed to the output on the last block.

Oracle: :func:`repro.kernels.ref.fused_topk_l2`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitonic import bitonic_sort_kv, next_pow2

__all__ = ["fused_topk_l2_pallas"]


def _scorer_kernel(q_ref, x_ref, od_ref, oi_ref, run_d, run_i, *,
                   k: int, bn: int, n_blocks: int, sort_len: int,
                   id_sentinel: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full(run_d.shape, jnp.inf, run_d.dtype)
        run_i[...] = jnp.full(run_i.shape, id_sentinel, run_i.dtype)

    q = q_ref[...].astype(jnp.float32)                     # (bq, d)
    x = x_ref[...].astype(jnp.float32)                     # (bn, d)
    q_sq = jnp.sum(q * q, axis=-1, keepdims=True)
    x_sq = jnp.sum(x * x, axis=-1)
    dots = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dist = q_sq + x_sq[None, :] - 2.0 * dots               # (bq, bn)
    ids = (j * bn
           + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1))

    bq = dist.shape[0]
    pad = sort_len - (k + bn)
    keys = jnp.concatenate([run_d[...], dist], axis=1)
    vals = jnp.concatenate([run_i[...], ids], axis=1)
    if pad:
        keys = jnp.concatenate(
            [keys, jnp.full((bq, pad), jnp.inf, keys.dtype)], axis=1)
        vals = jnp.concatenate(
            [vals, jnp.full((bq, pad), id_sentinel, vals.dtype)], axis=1)
    keys, vals = bitonic_sort_kv(keys, vals)
    run_d[...] = keys[:, :k]
    run_i[...] = vals[:, :k]

    @pl.when(j == n_blocks - 1)
    def _flush():
        od_ref[...] = run_d[...]
        oi_ref[...] = run_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def fused_topk_l2_pallas(q: jnp.ndarray, x: jnp.ndarray, *, k: int,
                         bq: int = 128, bn: int = 128,
                         interpret: bool = False):
    """(dists, ids) of the k nearest rows of x per query; both (B, k).

    Matches :func:`repro.kernels.ref.fused_topk_l2` including the k > N
    padding convention (+inf / id N).
    """
    from jax.experimental.pallas import tpu as pltpu  # deferred: CPU-safe

    B, d = q.shape
    N = x.shape[0]
    Bp = -(-B // bq) * bq
    Np = -(-N // bn) * bn
    qp = jnp.zeros((Bp, d), q.dtype).at[:B].set(q)
    # Pad x with huge rows: their distances dominate everything real.
    xp = jnp.full((Np, d), 1e9, x.dtype).at[:N].set(x)
    n_blocks = Np // bn
    sort_len = next_pow2(k + bn)

    kernel = functools.partial(
        _scorer_kernel, k=k, bn=bn, n_blocks=n_blocks, sort_len=sort_len,
        id_sentinel=Np)
    dists, ids = pl.pallas_call(
        kernel,
        grid=(Bp // bq, n_blocks),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, k), jnp.float32),
            jax.ShapeDtypeStruct((Bp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qp, xp)
    dists, ids = dists[:B], ids[:B]
    # Padded rows (and k > N tails) → sentinel id N, +inf distance.
    invalid = ids >= N
    return (jnp.where(invalid, jnp.inf, dists),
            jnp.where(invalid, N, ids).astype(jnp.int32))
