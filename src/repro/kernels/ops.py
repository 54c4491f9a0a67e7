"""Jitted public wrappers for the Pallas kernels.

Dispatch policy: on a TPU backend the native kernels are attempted, with
no fallback — and none of them compiles for the chip yet (Mosaic refuses
``fused_hop`` at lowering and ``fused_topk_l2`` at layout inference; see
``tests/test_tpu_compile.py``), so on a TPU only the composed path of the
default ``DQFConfig`` (``fused=False``, ``hot_mode="graph"``) runs.  On CPU
the kernels run under ``interpret=True`` when explicitly requested and
otherwise fall back to the jnp oracles in :mod:`repro.kernels.ref`, which
XLA:CPU compiles well.  The function contracts are identical — tests
assert kernel ≡ ref in interpret mode.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .distance import pairwise_l2_pallas
from .fused_hop import fused_hop_paged_pallas, fused_hop_pallas
from .fused_scorer import fused_topk_l2_pallas
from .pq_adc import pq_adc_pallas
from .sq_distance import sq8_pairwise_l2_pallas
from .topk_merge import pool_merge_pallas

__all__ = ["pairwise_l2", "fused_topk_l2", "pool_merge", "sq8_pairwise_l2",
           "pq_adc", "fused_hop", "fused_hop_paged", "table_spec",
           "kernels_native"]


def kernels_native() -> bool:
    """True when the Pallas kernels can compile for the local backend."""
    return jax.default_backend() == "tpu"


def _mode(interpret: Optional[bool]) -> Optional[bool]:
    """Resolve the dispatch: True=interpret, False=native, None=use ref."""
    if interpret is not None:
        return interpret
    return False if kernels_native() else None


def pairwise_l2(q: jnp.ndarray, x: jnp.ndarray, *,
                interpret: Optional[bool] = None, bq: int = 128,
                bn: int = 128) -> jnp.ndarray:
    m = _mode(interpret)
    if m is None:
        return ref.pairwise_l2(q, x)
    return pairwise_l2_pallas(q, x, bq=bq, bn=bn, interpret=m)


def fused_topk_l2(q: jnp.ndarray, x: jnp.ndarray, *, k: int,
                  interpret: Optional[bool] = None, bq: int = 128,
                  bn: int = 128):
    m = _mode(interpret)
    if m is None:
        return ref.fused_topk_l2(q, x, k=k)
    return fused_topk_l2_pallas(q, x, k=k, bq=bq, bn=bn, interpret=m)


def sq8_pairwise_l2(q: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                    zero: jnp.ndarray, *, interpret: Optional[bool] = None,
                    bq: int = 128, bn: int = 128) -> jnp.ndarray:
    m = _mode(interpret)
    if m is None:
        return ref.sq8_pairwise_l2(q, codes, scale, zero)
    return sq8_pairwise_l2_pallas(q, codes, scale, zero, bq=bq, bn=bn,
                                  interpret=m)


def pq_adc(luts: jnp.ndarray, codes: jnp.ndarray, *,
           interpret: Optional[bool] = None, bq: int = 128,
           bn: int = 128) -> jnp.ndarray:
    m = _mode(interpret)
    if m is None:
        return ref.pq_adc(luts, codes)
    return pq_adc_pallas(luts, codes, bq=bq, bn=bn, interpret=m)


def pool_merge(pool_dists, pool_ids, cand_dists, cand_ids, *,
               interpret: Optional[bool] = None, bb: int = 8):
    m = _mode(interpret)
    if m is None:
        return ref.pool_merge(pool_dists, pool_ids, cand_dists, cand_ids)
    return pool_merge_pallas(pool_dists, pool_ids, cand_dists, cand_ids,
                             bb=bb, interpret=m)


def table_spec(table):
    """Unpack a score table into the fused-hop kernel's (mode, t0, t1, t2).

    Accepts the device-resident tables only: a float32 ``x_pad`` array, an
    ``SQTable``, or a per-search ``PQView`` (``PQTable.with_queries``
    output).  A :class:`~repro.tiering.TieredTable` raises — its host
    faults cannot run inside the kernel, so callers keep tiered lanes on
    the composed path (the select-after-score seam).
    """
    if isinstance(table, jnp.ndarray):
        return "f32", table, None, None
    from repro.quant.types import PQView, SQTable  # deferred: no cycle
    if isinstance(table, SQTable):
        return "sq8", table.codes, table.scale, table.zero
    if isinstance(table, PQView):
        return "pq", table.codes, table.luts, None
    raise TypeError(
        f"fused hop needs a device-resident score table, got "
        f"{type(table).__name__} — tiered lanes must use the composed path")


def fused_hop(hs: "ref.HopState", adj_pad, queries, live_pad, table,
              tree=None, hot_first=None, hot_ratio=None, *, hops: int,
              max_hops: int, k: int = 1, eval_gap: int = 1,
              add_step: int = 0, tree_depth: int = 1,
              interpret: Optional[bool] = None, bl: int = 8
              ) -> "ref.HopState":
    """Advance a wave ``hops`` fused beam expansions (one kernel launch).

    ``table`` is a device-resident score table (see :func:`table_spec`);
    ``tree`` the unpacked decision-tree arrays or None.  Bit-identical to
    running the composed expand→gather→score→merge chain ``hops`` times.
    """
    mode, t0, t1, t2 = table_spec(table)
    m = _mode(interpret)
    # named_scope tags the launch in device profiles (jax.profiler)
    with jax.named_scope("dqf.fused_hop"):
        if m is None:
            return ref.fused_hop(
                hs, adj_pad, queries, live_pad, mode, t0, t1, t2, tree,
                hot_first, hot_ratio, hops=hops, max_hops=max_hops, k=k,
                eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth)
        return fused_hop_pallas(
            hs, adj_pad, queries, live_pad, mode, t0, t1, t2, tree,
            hot_first, hot_ratio, hops=hops, max_hops=max_hops, k=k,
            eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth,
            bl=bl, interpret=m)


def fused_hop_paged(hs: "ref.HopState", pt, adj_pad, queries, live_pad,
                    table, tree=None, hot_first=None, hot_ratio=None, *,
                    page_cols: int, hops: int, max_hops: int, k: int = 1,
                    eval_gap: int = 1, add_step: int = 0,
                    tree_depth: int = 1, interpret: Optional[bool] = None,
                    bl: int = 8) -> "ref.HopState":
    """Paged-seen fused hop: ``hs.seen`` is the page pool, ``pt`` the lane
    page table.  Same contract as :func:`fused_hop` otherwise; returns the
    updated pool in ``seen``.
    """
    mode, t0, t1, t2 = table_spec(table)
    m = _mode(interpret)
    with jax.named_scope("dqf.fused_hop_paged"):
        if m is None:
            return ref.fused_hop_paged(
                hs, pt, adj_pad, queries, live_pad, mode, t0, t1, t2, tree,
                hot_first, hot_ratio, page_cols=page_cols, hops=hops,
                max_hops=max_hops, k=k, eval_gap=eval_gap,
                add_step=add_step, tree_depth=tree_depth)
        return fused_hop_paged_pallas(
            hs, pt, adj_pad, queries, live_pad, mode, t0, t1, t2, tree,
            hot_first, hot_ratio, hops=hops, max_hops=max_hops, k=k,
            eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth,
            bl=bl, interpret=m)
