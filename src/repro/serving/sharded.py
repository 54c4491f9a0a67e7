"""Distributed DQF serving: shard-per-device subgraph search (DESIGN §2.2).

The database is row-partitioned into one segment per ``model``-axis device;
each segment gets its own NSSG built offline.  At query time every device
runs the batched beam search over its local subgraph for its ``data``-axis
slice of the query batch, then the per-segment top-k are all-gathered over
``model`` and merged — one collective per *batch*, not per hop.

The hot index stays replicated (it is ~1 MB — paper Table 6) so the hot
phase never leaves the chip.

Fault tolerance: ``merge_with_dropout`` renormalizes the merge over the
segments that responded — a lost host degrades recall by roughly its data
share instead of failing the query (measured in tests/test_distributed.py).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import beam_search as bs
from repro.core.ssg import SSGParams, build_ssg
from repro.core.types import DQFConfig

__all__ = ["ShardedIndex", "build_sharded_index", "sharded_search",
           "merge_with_dropout"]


@dataclasses.dataclass
class ShardedIndex:
    """Host-side bundle of per-segment artifacts, stacked for shard_map."""

    x_pad: np.ndarray         # (S, n_seg+1, d)
    adj_pad: np.ndarray       # (S, n_seg+1, R)
    entries: np.ndarray       # (S, E)
    offsets: np.ndarray       # (S,) global row offset of each segment
    n_total: int

    @property
    def num_shards(self) -> int:
        return self.x_pad.shape[0]


def build_sharded_index(x: np.ndarray, num_shards: int,
                        params: SSGParams | None = None,
                        n_entry: int = 8, seed: int = 0) -> ShardedIndex:
    """Round-robin rows into segments; independent NSSG per segment.

    ``n`` need not divide ``num_shards``: segments differ by at most one
    row, and shorter segments are padded to the common width with
    unreachable sentinel rows (distance-1e9 vectors whose adjacency points
    at the segment sentinel, global id ``-1``) — the external-id mapping
    in ``offsets`` stays exact for every real row.
    """
    params = params or SSGParams()
    n, d = x.shape
    n_seg = -(-n // num_shards)                  # ceil: common segment width
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)                    # density-balance segments
    xs, adjs, ents, offs = [], [], [], []
    R = 0
    segs = [np.sort(perm[s::num_shards]) for s in range(num_shards)]
    if min(len(r) for r in segs) < 2:
        raise ValueError(
            f"n={n} leaves a segment with < 2 rows over {num_shards} shards")
    for rows in segs:
        n_s = rows.size
        seg = np.ascontiguousarray(x[rows], np.float32)
        idx = build_ssg(seg, params, n_entry=n_entry)
        xp = np.full((n_seg + 1, d), 1e9, np.float32)
        xp[:n_s] = seg
        R = max(R, idx.adj.shape[1])
        ap = np.full((n_seg + 1, idx.adj.shape[1]), n_seg, np.int32)
        a = idx.adj
        ap[:n_s] = np.where((a < 0) | (a >= n_s), n_seg, a)
        xs.append(xp)
        adjs.append(ap)
        e = idx.entries
        if e.size < n_entry:                    # pad entries to equal width
            e = np.concatenate([e, np.full(n_entry - e.size, e[0], e.dtype)])
        ents.append(e[:n_entry])
        rp = np.full(n_seg, -1, np.int64)
        rp[:n_s] = rows                          # global ids; -1 = padding
        offs.append(rp)
    adjs = [np.pad(a, ((0, 0), (0, R - a.shape[1])),
                   constant_values=n_seg) for a in adjs]
    return ShardedIndex(
        x_pad=np.stack(xs), adj_pad=np.stack(adjs),
        entries=np.stack(ents).astype(np.int32),
        offsets=np.stack(offs).astype(np.int32), n_total=n)


def _segment_search(x_pad, adj_pad, entries, rows, queries, *, pool_size,
                    k, max_hops):
    """Search one segment (runs per device under shard_map)."""
    res = bs.beam_search(x_pad[0], adj_pad[0], entries[0], queries,
                         pool_size=pool_size, k=k, max_hops=max_hops)
    n_seg = rows.shape[1]
    local = jnp.minimum(res.ids, n_seg - 1)
    # invalid = pool sentinel OR a remainder-padding row (global id -1)
    bad = (res.ids >= n_seg) | (rows[0][local] < 0)
    gids = jnp.where(bad, -1, rows[0][local])
    dists = jnp.where(bad, jnp.inf, res.dists)
    return gids.astype(jnp.int32), dists


def sharded_search(index: ShardedIndex, queries: np.ndarray, mesh: Mesh, *,
                   cfg: DQFConfig, model_axis: str = "model",
                   data_axis: str = "data"):
    """Distributed batched search: (B, k) global ids + dists.

    queries shard over ``data_axis``; segments live on ``model_axis``.
    """
    S = index.num_shards
    if mesh.shape[model_axis] != S:
        raise ValueError(f"{S} shards need model axis of size {S}")
    k, pool, hops = cfg.k, cfg.full_pool, cfg.max_hops

    def per_shard(x_pad, adj_pad, entries, rows, q):
        gids, dists = _segment_search(
            x_pad, adj_pad, entries, rows, q,
            pool_size=pool, k=k, max_hops=hops)
        # merge across segments: gather every segment's top-k, re-top-k
        all_ids = jax.lax.all_gather(gids, model_axis, axis=1, tiled=True)
        all_d = jax.lax.all_gather(dists, model_axis, axis=1, tiled=True)
        neg, idx = jax.lax.top_k(-all_d, k)
        return jnp.take_along_axis(all_ids, idx, axis=1), -neg

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(model_axis), P(model_axis), P(model_axis),
                  P(model_axis), P(data_axis)),
        out_specs=(P(data_axis), P(data_axis)),
        check_vma=False)   # fresh while-loop carries are unvarying by design
    ids, dists = jax.jit(fn)(
        jnp.asarray(index.x_pad), jnp.asarray(index.adj_pad),
        jnp.asarray(index.entries), jnp.asarray(index.offsets),
        jnp.asarray(queries, jnp.float32))
    return np.asarray(ids), np.asarray(dists)


def merge_with_dropout(per_shard_ids: list, per_shard_dists: list,
                       alive: list, k: int, *, registry=None):
    """Host-side degraded merge: skip shards flagged dead (stragglers that
    timed out / failed hosts).  Returns (ids, dists, coverage).

    With a :class:`repro.obs.MetricsRegistry`, every degraded merge is
    visible in ``scrape()``/``exposition()``: responding shards count into
    ``shard_responses_total{shard=i}`` and each dead shard into
    ``shard_dropout_total``.
    """
    if registry is not None:
        resp = registry.counter(
            "shard_responses_total",
            "per-shard responses folded into degraded merges")
        for s, a in enumerate(alive):
            if a:
                resp.inc(1.0, shard=s)
        dead = len(alive) - sum(bool(a) for a in alive)
        if dead:
            registry.counter(
                "shard_dropout_total",
                "shards dropped from degraded merges").inc(float(dead))
    ids = [i for i, a in zip(per_shard_ids, alive) if a]
    ds = [d for d, a in zip(per_shard_dists, alive) if a]
    if not ids:
        raise RuntimeError("all shards lost")
    cat_i = np.concatenate(ids, axis=1)
    cat_d = np.concatenate(ds, axis=1)
    order = np.argsort(cat_d, axis=1)[:, :k]
    return (np.take_along_axis(cat_i, order, 1),
            np.take_along_axis(cat_d, order, 1),
            sum(alive) / len(alive))
