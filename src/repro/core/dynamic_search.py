"""Dynamic dual-index search with decision-tree early termination (Alg 4).

Phase 1 searches the hot index — either the paper-faithful NSSG subgraph
(``hot_mode="graph"``) or the beyond-paper MXU brute-force scorer
(``hot_mode="mxu"``, see :mod:`repro.kernels`).  Its pool seeds phase 2 over
the full graph, where every lane re-evaluates the decision tree each time its
(full-phase) distance count crosses a multiple of ``eval_gap``; a stop verdict
(+ optional ``add_step`` grace distance computations) retires the lane.

All ids in phase 2 are global.  The hot graph uses local ids 0..H-1 with its
own sentinel H; ``hot_ids`` maps local→global.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import beam_search as bs
from .decision_tree import TreeArrays, predict_jax
from .features import feature_matrix, hot_features
from .types import (INF_DIST, DQFConfig, HotFeatures, PoolState, SearchResult,
                    SearchStats)

__all__ = ["dynamic_search", "hot_phase", "hot_phase_stacked",
           "DynamicState"]

_INT_MAX = jnp.iinfo(jnp.int32).max


class DynamicState(NamedTuple):
    beam: bs.BeamState
    evals_done: jnp.ndarray   # (B,) int32 — DT evaluations performed
    stop_at: jnp.ndarray      # (B,) int32 — dist_count deadline (add_step)


def hot_phase_graph(x_hot_pad, adj_hot_pad, hot_entries, queries, *,
                    pool_size: int, max_hops: int):
    """Phase 1, paper-faithful: beam search over the hot NSSG."""
    state = bs.init_state(x_hot_pad, queries, hot_entries, pool_size)
    state = bs.beam_loop(x_hot_pad, adj_hot_pad, queries, state, max_hops)
    return state.pool, state.stats


def hot_phase_mxu(x_hot, queries, *, pool_size: int, use_kernel: bool = False):
    """Phase 1, beyond-paper: exact brute-force over the (tiny) hot set.

    ``use_kernel`` routes it through the fused Pallas distance+top-k
    scorer, which does not compile for a TPU yet; otherwise the jnp
    reference scorer runs.
    """
    H = x_hot.shape[0]
    if use_kernel:
        from repro.kernels import ops as kops
        dists, ids = kops.fused_topk_l2(queries, x_hot, k=pool_size)
    else:
        from repro.kernels import ref as kref
        dists, ids = kref.fused_topk_l2(queries, x_hot, k=pool_size)
    B = queries.shape[0]
    pool = PoolState(
        ids=ids.astype(jnp.int32),
        dists=dists.astype(jnp.float32),
        expanded=jnp.zeros((B, pool_size), bool),
    )
    stats = SearchStats(
        dist_count=jnp.full((B,), H, jnp.int32),
        update_count=jnp.zeros((B,), jnp.int32),
        hops=jnp.zeros((B,), jnp.int32),
        terminated_early=jnp.zeros((B,), bool),
    )
    return pool, stats


def hot_phase(x_hot_pad, adj_hot_pad, hot_entries, queries, *, pool_size,
              max_hops, mode: str = "graph", use_kernel: bool = False):
    if mode == "graph":
        return hot_phase_graph(x_hot_pad, adj_hot_pad, hot_entries, queries,
                               pool_size=pool_size, max_hops=max_hops)
    return hot_phase_mxu(x_hot_pad[:-1], queries, pool_size=pool_size,
                         use_kernel=use_kernel)


@functools.partial(
    jax.jit, static_argnames=("pool_size", "max_hops", "mode"))
def hot_phase_stacked(xs_hot, adjs_hot, entries_hot, mask_hot, tenant_idx,
                      queries, *, pool_size, max_hops, mode: str = "graph"):
    """Phase 1 over *stacked* per-tenant hot tables (:mod:`repro.tenancy`).

    ``xs_hot (T, H+1, d)`` / ``adjs_hot (T, H+1, R)`` / ``entries_hot
    (T, E)`` / ``mask_hot (T, H+1)`` hold every tenant's hot index in one
    set of arrays; ``tenant_idx (B,)`` routes each query to its tenant's
    row by gather, so a mixed-tenant batch runs as one jitted search with
    no per-tenant recompilation.  Returns local-id pool + stats, same
    contract as :func:`hot_phase` (local sentinel = H).
    """
    x = xs_hot[tenant_idx]                                 # (B, H+1, d)
    ent = entries_hot[tenant_idx]                          # (B, E)
    if mode == "graph":
        adj = adjs_hot[tenant_idx]                         # (B, H+1, R)
        state = bs.init_state(x, queries, ent, pool_size)
        state = bs.beam_loop(x, adj, queries, state, max_hops)
        return state.pool, state.stats
    # "mxu" mode: brute-force each lane against its tenant's hot rows.
    # (On TPU the shared-table Pallas scorer doesn't apply per lane; a
    # batched einsum keeps the same semantics at stacked-hot scale.)
    B = queries.shape[0]
    H = x.shape[1] - 1
    valid = mask_hot[tenant_idx][:, :H]                    # (B, H)
    d2 = jnp.sum((x[:, :H, :] - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, INF_DIST)
    take = min(pool_size, H)
    neg, ids = jax.lax.top_k(-d2, take)
    dists = -neg
    ids = jnp.where(dists >= INF_DIST, H, ids).astype(jnp.int32)
    pad = pool_size - take
    pool = PoolState(
        ids=jnp.concatenate(
            [ids, jnp.full((B, pad), H, jnp.int32)], axis=1),
        dists=jnp.concatenate(
            [dists, jnp.full((B, pad), INF_DIST, jnp.float32)], axis=1),
        expanded=jnp.zeros((B, pool_size), bool),
    )
    stats = SearchStats(
        dist_count=jnp.sum(valid.astype(jnp.int32), axis=1),
        update_count=jnp.zeros((B,), jnp.int32),
        hops=jnp.zeros((B,), jnp.int32),
        terminated_early=jnp.zeros((B,), bool),
    )
    return pool, stats


def _seed_full_state(hot_pool: PoolState, hot_ids_pad: jnp.ndarray,
                     n: int, pool_size: int,
                     live_pad: Optional[jnp.ndarray] = None) -> bs.BeamState:
    """Map the hot pool to global ids and seed the phase-2 state.

    Implements Alg 4 line 11 ("reset visit status of nodes in L"): all
    entries arrive unexpanded.  ``live_pad`` masks hot results whose global
    row was tombstoned after the hot index was last rebuilt.
    ``hot_ids_pad`` is the shared ``(H+1,)`` local→global map, or per-lane
    ``(B, H+1)`` rows gathered from a stacked multi-tenant table.
    """
    B, s_l = hot_pool.ids.shape
    if hot_ids_pad.ndim == 2:                             # per-lane map
        gids = jnp.take_along_axis(hot_ids_pad, hot_pool.ids, axis=1)
    else:
        gids = hot_ids_pad[hot_pool.ids]                  # (B, s_l) global
    gids = jnp.where(hot_pool.dists >= INF_DIST, n, gids).astype(jnp.int32)
    dists = hot_pool.dists
    if live_pad is not None:
        dead = ~live_pad[gids]
        gids = jnp.where(dead, n, gids)
        dists = jnp.where(dead, INF_DIST, dists)
    take = min(s_l, pool_size)
    order = jnp.argsort(dists, axis=1)[:, :take]
    gids = jnp.take_along_axis(gids, order, 1)
    gdist = jnp.take_along_axis(dists, order, 1)
    pad = pool_size - take
    pool = PoolState(
        ids=jnp.concatenate([gids, jnp.full((B, pad), n, jnp.int32)], 1),
        dists=jnp.concatenate(
            [gdist, jnp.full((B, pad), INF_DIST, jnp.float32)], 1),
        expanded=jnp.zeros((B, pool_size), bool),
    )
    seen = jnp.zeros((B, n + 1), bool)
    seen = seen.at[jnp.arange(B)[:, None],
                   jnp.where(pool.ids == n, n, pool.ids)].set(True)
    seen = seen.at[:, n].set(True)
    stats = SearchStats(                                   # line 12 reset
        dist_count=jnp.zeros((B,), jnp.int32),
        update_count=jnp.zeros((B,), jnp.int32),
        hops=jnp.zeros((B,), jnp.int32),
        terminated_early=jnp.zeros((B,), bool),
    )
    return bs.BeamState(pool, seen, stats, jnp.ones((B,), bool))


def _exact_rerank(x_pad, queries, pool: PoolState, *, k: int,
                  rerank_k: int, live_pad: Optional[jnp.ndarray] = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Re-score the pool's best ``rerank_k`` entries in float32, keep top-k.

    The quantized full phase ranks the pool by approximate (compressed-
    domain) distances; this recovers the exact ordering among the head of
    the pool so quantization error only costs recall when the true
    neighbor fell *out* of the rerank window entirely.

    ``x_pad`` may itself be a tiered float32 table (:mod:`repro.tiering`):
    the rerank rows then ride the host tier through the same gather.
    """
    n = bs.table_n(x_pad)
    rr = min(max(rerank_k, k), pool.ids.shape[1])
    ids = pool.ids[:, :rr]
    d2 = bs.score_rows(x_pad, queries, ids)
    d2 = jnp.where(ids == n, INF_DIST, d2)
    if live_pad is not None:
        d2 = jnp.where(live_pad[ids], d2, INF_DIST)
    order = jnp.argsort(d2, axis=1)[:, :k]
    return (jnp.take_along_axis(ids, order, 1),
            jnp.take_along_axis(d2, order, 1))


def _full_phase(x_pad, adj_pad, queries, state: bs.BeamState,
                hot: HotFeatures, tree: Optional[TreeArrays], *,
                k: int, eval_gap: int, add_step: int, tree_depth: int,
                max_hops: int,
                live_pad: Optional[jnp.ndarray] = None) -> bs.BeamState:
    """Phase 2 with periodic decision-tree termination checks."""
    B = queries.shape[0]
    dstate = DynamicState(
        beam=state,
        evals_done=jnp.zeros((B,), jnp.int32),
        stop_at=jnp.full((B,), _INT_MAX, jnp.int32),
    )

    def cond(ds: DynamicState):
        return jnp.any(ds.beam.active)

    def body(ds: DynamicState):
        s = bs.expand_step(x_pad, adj_pad, queries, ds.beam, live_pad)
        s = s._replace(active=s.active & (s.stats.hops < max_hops))
        evals_done, stop_at = ds.evals_done, ds.stop_at
        if tree is not None:
            due = (s.stats.dist_count // eval_gap) > evals_done   # (B,)
            due = due & s.active
            feats = feature_matrix(hot, s.pool, s.stats, k)
            p_continue = predict_jax(tree, feats, tree_depth)
            verdict_stop = p_continue < 0.5
            newly = due & verdict_stop & (stop_at == _INT_MAX)
            stop_at = jnp.where(
                newly, s.stats.dist_count + add_step, stop_at)
            evals_done = jnp.where(due, s.stats.dist_count // eval_gap,
                                   evals_done)
            stop_now = s.stats.dist_count >= stop_at
            s = s._replace(
                active=s.active & ~stop_now,
                stats=s.stats._replace(
                    terminated_early=s.stats.terminated_early
                    | (stop_now & s.active)),
            )
        return DynamicState(s, evals_done, stop_at)

    return jax.lax.while_loop(cond, body, dstate).beam


@functools.partial(jax.jit, static_argnames=(
    "k", "hot_pool_size", "full_pool_size", "eval_gap", "add_step",
    "tree_depth", "max_hops", "hot_mode", "use_kernel", "rerank_k",
    "fused", "fused_hops"))
def dynamic_search(
    x_pad: jnp.ndarray,            # (n+1, d) padded dataset
    adj_pad: jnp.ndarray,          # (n+1, R) padded full adjacency
    x_hot_pad: jnp.ndarray,        # (H+1, d) padded hot vectors
    adj_hot_pad: jnp.ndarray,      # (H+1, Rh) padded hot adjacency
    hot_ids_pad: jnp.ndarray,      # (H+1,) local→global (pad slot → n)
    hot_entries: jnp.ndarray,      # (E,) local entry ids into the hot graph
    tree: Optional[TreeArrays],
    queries: jnp.ndarray,          # (B, d)
    *,
    k: int,
    hot_pool_size: int,
    full_pool_size: int,
    eval_gap: int,
    add_step: int,
    tree_depth: int,
    max_hops: int = 512,
    hot_mode: str = "graph",
    use_kernel: bool = False,
    qtable=None,                   # quantized score table (repro.quant)
    rerank_k: int = 0,
    live_pad: Optional[jnp.ndarray] = None,   # (n+1,) liveness bitmap
    fused: bool = False,           # fused wave-hop megakernel full phase
    fused_hops: int = 8,
) -> tuple[SearchResult, SearchStats, HotFeatures]:
    """Algorithm 4 end to end. Returns (result, hot_phase_stats, hot_feats).

    ``result.stats`` covers the full phase only (post line-12 reset);
    ``hot_phase_stats`` carries the hot phase cost for total-cost reporting.

    When ``qtable`` is given, phase 2 scores against the compressed codes
    (the hot phase stays float32) and, with ``rerank_k > 0``, the pool's
    head is re-scored exactly from ``x_pad`` before the final top-k.

    ``fused=True`` routes the full phase through the fused wave-hop
    megakernel (:mod:`repro.kernels.fused_hop`) — bit-identical results
    from one kernel launch per ``fused_hops`` hops.  Device-resident
    tables only; tiered callers must keep ``fused=False``.

    With a tiered store (:mod:`repro.tiering`) both ``x_pad`` and
    ``qtable`` are cache-aware :class:`~repro.tiering.TieredTable`
    snapshots; the search semantics (and, bit-for-bit, its results) are
    unchanged — only where the bytes come from moves.
    """
    n = bs.table_n(x_pad)
    # named_scope annotates the HLO, so device profiles (jax.profiler
    # traces) show the phase structure; zero cost outside a capture.
    with jax.named_scope("dqf.hot_phase"):
        hot_pool, hot_stats = hot_phase(
            x_hot_pad, adj_hot_pad, hot_entries, queries,
            pool_size=hot_pool_size, max_hops=max_hops, mode=hot_mode,
            use_kernel=use_kernel)
        hfeats = hot_features(hot_pool, k)
        state = _seed_full_state(hot_pool, hot_ids_pad, n, full_pool_size,
                                 live_pad)
    table = x_pad if qtable is None else qtable.with_queries(queries)
    with jax.named_scope("dqf.full_phase"):
        if fused:
            # phase 2 through the megakernel: the kernel's per-hop body is
            # _full_phase's body verbatim (inactive lanes are exact no-ops,
            # so the chunked launches stay bit-identical)
            state = bs.fused_beam_loop(
                table, adj_pad, queries, state, max_hops, live_pad,
                fused_hops=fused_hops, tree=tree, hot=hfeats, k=k,
                eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth)
        else:
            state = _full_phase(
                table, adj_pad, queries, state, hfeats, tree,
                k=k, eval_gap=eval_gap, add_step=add_step,
                tree_depth=tree_depth, max_hops=max_hops, live_pad=live_pad)
    with jax.named_scope("dqf.rerank"):
        if qtable is not None and rerank_k > 0:
            ids, dists = _exact_rerank(x_pad, queries, state.pool, k=k,
                                       rerank_k=rerank_k, live_pad=live_pad)
        else:
            ids, dists = bs.topk_from_pool(state.pool, k)
    return (SearchResult(ids=ids, dists=dists, stats=state.stats),
            hot_stats, hfeats)


def config_kwargs(cfg: DQFConfig) -> dict:
    """Static kwargs for :func:`dynamic_search` from a DQFConfig."""
    return dict(
        k=cfg.k, hot_pool_size=cfg.hot_pool, full_pool_size=cfg.full_pool,
        eval_gap=cfg.eval_gap, add_step=cfg.add_step,
        tree_depth=cfg.tree_depth, max_hops=cfg.max_hops,
        hot_mode=cfg.hot_mode,
        rerank_k=cfg.quant.rerank_k if cfg.quant.enabled else 0)
