"""Pallas TPU megakernel: fused wave-hop beam ticks.

One kernel advances a whole wave of search lanes ``hops`` expansions —
frontier selection, adjacency gather, visited-set dedup, neighbor scoring
(float32 / int8-dequant / PQ-ADC, chosen at trace time), and the sorted
pool merge — with the pool, seen bitmap, per-lane counters and queries
resident in VMEM across every hop.  The composed path runs the same hop as
a chain of separate kernels (adjacency gather → ``gather_distance`` /
``sq_distance`` / ``pq_adc`` → ``topk_merge``) with the beam state
round-tripping through HBM between each; here HBM traffic per hop drops to
the expanded adjacency rows plus the gathered vector/code rows, fetched
with double-buffered async copies (lane ``i+1``'s rows stream in while
lane ``i`` scores).

Bit-identity: every arithmetic expression mirrors the composed path
verbatim (see :func:`repro.kernels.ref.fused_hop_body`, the semantics
contract), and the pool merge uses the tie-broken *stable* bitonic network
(:func:`repro.kernels.bitonic.bitonic_sort_stable`), whose permutation is
exactly the stable ``jnp.argsort`` the composed merge performs — so the
fused tick is bit-identical to the composed tick, not just close.

Grid: ``(B/bl,)`` lane blocks; each grid step owns its lanes for the whole
``hops`` loop, so state never leaves VMEM mid-tick.  Masks travel as int32
at the kernel boundary (the dispatch wrapper converts, TPU memory ops
dislike 1-bit vectors); inactive and padding lanes are exact no-ops.

Oracle: :func:`repro.kernels.ref.fused_hop`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

from .bitonic import bitonic_sort_stable, next_pow2
from .ref import INF_DIST, HopState, _EPS, _INT_MAX

__all__ = ["fused_hop_pallas", "fused_hop_paged_pallas"]

# INF_DIST as an inlineable numpy scalar: a jax array constant would be
# *captured* by the kernel trace, which pallas_call rejects.
_INF32 = np.float32(3.0e38)


def _hop_kernel(refs, *, pltpu, mode: str, has_tree: bool, has_live: bool,
                bl: int, R: int, L: int, n: int, hops: int, max_hops: int,
                k: int, eval_gap: int, add_step: int, tree_depth: int,
                sort_len: int, pq_k: int, paged: bool = False,
                ppl: int = 0, page_cols: int = 0):
    """Kernel body; ``refs`` laid out by :func:`fused_hop_pallas`.

    ``paged=True`` swaps the dense per-lane seen block for a page walk:
    the lane input carries the page table ``(bl, ppl)`` instead of the
    bitmap, the pool lives in an aliased ANY-space in/out buffer, and the
    kernel DMAs each lane's pages into a VMEM scratch bitmap on entry and
    back out at exit.  The hop arithmetic in between is byte-for-byte the
    dense kernel.
    """
    it = iter(refs)
    ids_i, dists_i, exp_i = [next(it) for _ in range(3)]
    pt_i = seen_i = None
    if paged:
        pt_i = next(it)
    else:
        seen_i = next(it)
    stat_i, q_ref = next(it), next(it)
    adj_hbm, tab_hbm = next(it), next(it)
    scale_ref = zero_ref = luts_ref = None
    if mode == "sq8":
        scale_ref, zero_ref = next(it), next(it)
    elif mode == "pq":
        luts_ref = next(it)
    live_ref = next(it) if has_live else None
    tree_refs = hot_ref = None
    if has_tree:
        tree_refs = [next(it) for _ in range(5)]
        hot_ref = next(it)
    if paged:
        next(it)  # pool input ref; aliased — all access goes via pool_o
    ids_o, dists_o, exp_o = [next(it) for _ in range(3)]
    seen_o = pool_o = None
    if paged:
        stat_o, pool_o = next(it), next(it)
    else:
        seen_o, stat_o = next(it), next(it)
    adj_s, rows_s, d2_s, sem_adj, sem_rows = [next(it) for _ in range(5)]
    seen_s = sem_seen = None
    if paged:
        seen_s, sem_seen = next(it), next(it)

    # The output blocks are the VMEM-resident working state for every hop.
    ids_o[...] = ids_i[...]
    dists_o[...] = dists_i[...]
    exp_o[...] = exp_i[...]
    stat_o[...] = stat_i[...]
    if paged:
        # Gather this block's pages into the dense VMEM bitmap.  All
        # copies launch before any waits; live lanes own disjoint pages
        # and duplicate (scratch-lane) rows carry identical bytes.
        ptv = pt_i[...]                                    # (bl, ppl)

        def page_dma(i: int, j: int):
            return pltpu.make_async_copy(
                pool_o.at[pl.ds(ptv[i, j], 1)],
                seen_s.at[pl.ds(i, 1),
                          pl.ds(j * page_cols, page_cols)],
                sem_seen.at[i, j])

        for i in range(bl):
            for j in range(ppl):
                page_dma(i, j).start()
        for i in range(bl):
            for j in range(ppl):
                page_dma(i, j).wait()
    else:
        seen_o[...] = seen_i[...]

    queries = q_ref[...]                                   # (bl, d)
    live = live_ref[0, :] != 0 if has_live else None       # (n+1,)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (bl, L), 1)

    def row_dma(buf: int, r: int, col):
        return pltpu.make_async_copy(
            tab_hbm.at[pl.ds(col, 1)], rows_s.at[buf, pl.ds(r, 1)],
            sem_rows.at[buf, r])

    def score_lane(rows, q):
        """(R,) distances of one lane; mirrors ref._gather_score.

        Shaped (1, R, d) — the rank of the composed batch expression —
        because XLA picks its reduction strategy by rank, and a rank-2
        sum can round differently by an ulp.
        """
        if mode == "sq8":
            rows = (rows.astype(jnp.float32) * scale_ref[0, :]
                    + zero_ref[0, :])
        diff = rows[None] - q[None, None, :]               # (1, R, d)
        return jnp.sum(diff * diff, axis=-1)[0].astype(jnp.float32)

    def score_lane_pq(rows, lut):
        # Rank-4 with a unit lane axis, exactly the composed ADC gather
        # (``PQView.gather_score``): the rank decides XLA's reduction
        # strategy, so a rank-2 formulation here would drift by an ulp.
        c1 = rows.astype(jnp.int32)[None]                  # (1, R, M)
        vals = jnp.take_along_axis(lut[None][:, None], c1[..., None],
                                   axis=3)                 # (1, R, M, 1)
        return jnp.sum(vals[..., 0], axis=-1)[0].astype(jnp.float32)

    seen_ref = seen_s if paged else seen_o

    def hop(_, carry):
        ids = ids_o[...]
        dists = dists_o[...]
        exp = exp_o[...] != 0
        seen = seen_ref[...] != 0
        stat = stat_o[...]
        active = stat[:, 0] != 0
        dist_count, update_count = stat[:, 1], stat[:, 2]
        hops_ct = stat[:, 3]
        terminated = stat[:, 4] != 0
        evals_done, stop_at = stat[:, 5], stat[:, 6]

        # --- expansion target ---
        unexp = (~exp) & (ids != n)
        lane = active & jnp.any(unexp, axis=1)
        slot = jnp.argmax(unexp, axis=1)                   # (bl,)
        p = jnp.where(
            lane, jnp.take_along_axis(ids, slot[:, None], axis=1)[:, 0], n)
        exp = exp | ((col_iota == slot[:, None]) & lane[:, None])

        # --- adjacency rows: one async copy per lane, all in flight ---
        for i in range(bl):
            pltpu.make_async_copy(adj_hbm.at[pl.ds(p[i], 1)],
                                  adj_s.at[pl.ds(i, 1)],
                                  sem_adj.at[i]).start()
        for i in range(bl):
            pltpu.make_async_copy(adj_hbm.at[pl.ds(p[i], 1)],
                                  adj_s.at[pl.ds(i, 1)],
                                  sem_adj.at[i]).wait()
        nbrs = adj_s[...]                                  # (bl, R)

        already = jnp.take_along_axis(seen, nbrs, axis=1)
        valid = (nbrs != n) & (~already) & lane[:, None]
        if has_live:
            valid &= live[nbrs]
        cols = jnp.where(valid, nbrs, n)
        rows2 = jax.lax.broadcasted_iota(jnp.int32, (bl, 1), 0)
        seen = seen.at[rows2, cols].set(True)

        # --- vector/code rows: double-buffered gather + score ---
        def start_rows(buf: int, i: int):
            for r in range(R):
                row_dma(buf, r, cols[i, r]).start()

        def wait_rows(buf: int, i: int):
            for r in range(R):
                row_dma(buf, r, cols[i, r]).wait()

        start_rows(0, 0)
        for i in range(bl):
            if i + 1 < bl:
                start_rows((i + 1) % 2, i + 1)             # overlap
            wait_rows(i % 2, i)
            rows = rows_s[i % 2]                           # (R, w)
            if mode == "pq":
                lut = luts_ref[i, :].reshape(-1, pq_k)     # (M, K)
                d2_s[i, :] = score_lane_pq(rows, lut)
            else:
                d2_s[i, :] = score_lane(rows, queries[i])
        d2 = jnp.where(valid, d2_s[...], _INF32)

        # --- merge (stable bitonic ≡ composed stable argsort) ---
        worst = dists[:, -1]
        inserted = jnp.sum((d2 < worst[:, None]).astype(jnp.int32), axis=1)
        pad = sort_len - (L + R)
        cat = lambda a, b, fill, dt: jnp.concatenate(
            [a, b] + ([jnp.full((bl, pad), fill, dt)] if pad else []),
            axis=1)
        keys = cat(dists, d2, jnp.inf, jnp.float32)
        vi = cat(ids, cols, 0, jnp.int32)
        ve = cat(exp.astype(jnp.int32), jnp.zeros((bl, R), jnp.int32), 0,
                 jnp.int32)
        skeys, svi, sve = bitonic_sort_stable(keys, vi, ve)
        lane_c = lane[:, None]
        ids = jnp.where(lane_c, svi[:, :L], ids)
        dists = jnp.where(lane_c, skeys[:, :L], dists)
        exp = jnp.where(lane_c, sve[:, :L] != 0, exp)

        # --- counters + liveness ---
        dist_count = dist_count + jnp.where(
            lane, jnp.sum(valid.astype(jnp.int32), 1), 0)
        update_count = update_count + jnp.where(lane, inserted, 0)
        hops_ct = hops_ct + lane.astype(jnp.int32)
        still = jnp.any((~exp) & (ids != n), axis=1)
        active = active & still & (hops_ct < max_hops)

        # --- decision-tree termination ---
        if has_tree:
            tf, tt, tl, tr, tv = [t[0, :] for t in tree_refs]
            due = ((dist_count // eval_gap) > evals_done) & active
            first = dists[:, 0]
            kth = dists[:, min(k, L) - 1]
            feats = jnp.stack(
                [hot_ref[:, 0], hot_ref[:, 1], first, first / (kth + _EPS),
                 dist_count.astype(jnp.float32),
                 update_count.astype(jnp.float32)], axis=1)

            def tstep(_, node):
                f = jnp.maximum(tf[node], 0)
                val = jnp.take_along_axis(feats, f[:, None], axis=1)[:, 0]
                return jnp.where(val <= tt[node], tl[node], tr[node])

            node = jax.lax.fori_loop(0, tree_depth, tstep,
                                     jnp.zeros((bl,), jnp.int32))
            verdict_stop = tv[node] < 0.5
            newly = due & verdict_stop & (stop_at == _INT_MAX)
            stop_at = jnp.where(newly, dist_count + add_step, stop_at)
            evals_done = jnp.where(due, dist_count // eval_gap, evals_done)
            stop_now = dist_count >= stop_at
            terminated = terminated | (stop_now & active)
            active = active & ~stop_now

        ids_o[...] = ids
        dists_o[...] = dists
        exp_o[...] = exp.astype(jnp.int32)
        seen_ref[...] = seen.astype(jnp.int32)
        stat_o[...] = jnp.stack(
            [active.astype(jnp.int32), dist_count, update_count, hops_ct,
             terminated.astype(jnp.int32), evals_done, stop_at,
             jnp.zeros((bl,), jnp.int32)], axis=1)
        return carry

    jax.lax.fori_loop(0, hops, hop, 0)

    if paged:
        # Scatter the updated bitmap back through the page table.
        # Duplicate destination rows (padding lanes on the scratch
        # pages) write identical bytes, so overlap is benign.
        def page_wb(i: int, j: int):
            return pltpu.make_async_copy(
                seen_s.at[pl.ds(i, 1),
                          pl.ds(j * page_cols, page_cols)],
                pool_o.at[pl.ds(ptv[i, j], 1)],
                sem_seen.at[i, j])

        for i in range(bl):
            for j in range(ppl):
                page_wb(i, j).start()
        for i in range(bl):
            for j in range(ppl):
                page_wb(i, j).wait()


@functools.partial(jax.jit, static_argnames=(
    "mode", "hops", "max_hops", "k", "eval_gap", "add_step", "tree_depth",
    "bl", "interpret"))
def fused_hop_pallas(hs: HopState, adj_pad, queries, live_pad, mode: str,
                     t0, t1=None, t2=None, tree=None, hot_first=None,
                     hot_ratio=None, *, hops: int, max_hops: int,
                     k: int = 1, eval_gap: int = 1, add_step: int = 0,
                     tree_depth: int = 1, bl: int = 8,
                     interpret: bool = False) -> HopState:
    """Advance a wave ``hops`` fused expansions; contract = ref.fused_hop.

    ``bl`` is the lane-block size (lanes per grid step); the wave is
    padded to a multiple with inert lanes, which the hop treats as exact
    no-ops.  Mask state crosses the kernel boundary as int32.
    """
    from jax.experimental.pallas import tpu as pltpu  # deferred: CPU-safe

    B, L = hs.ids.shape
    n1 = hs.seen.shape[1]
    n = n1 - 1
    R = adj_pad.shape[1]
    d = queries.shape[1]
    Bp = -(-B // bl) * bl
    has_tree = tree is not None

    def pad_b(a, fill):
        if Bp == B:
            return a
        filler = jnp.full((Bp - B,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, filler], axis=0)

    i32 = lambda a: a.astype(jnp.int32)
    ids = pad_b(i32(hs.ids), n)
    dists = pad_b(hs.dists, INF_DIST)
    exp = pad_b(i32(hs.expanded), 0)
    seen = pad_b(i32(hs.seen), 0)
    stat = pad_b(jnp.stack(
        [i32(hs.active), i32(hs.dist_count), i32(hs.update_count),
         i32(hs.hops), i32(hs.terminated), i32(hs.evals_done),
         i32(hs.stop_at), jnp.zeros((B,), jnp.int32)], axis=1), 0)
    q = pad_b(queries.astype(jnp.float32), 0.0)
    has_live = live_pad is not None

    lane_spec = lambda w: pl.BlockSpec((bl, w), lambda i: (i, 0))
    full_spec = lambda s: pl.BlockSpec(s, lambda i: (0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    inputs = [ids, dists, exp, seen, stat, q]
    in_specs = [lane_spec(L), lane_spec(L), lane_spec(L), lane_spec(n1),
                lane_spec(8), lane_spec(d)]
    inputs += [adj_pad, t0]
    in_specs += [any_spec, any_spec]
    pq_k = 1
    if mode == "sq8":
        inputs += [t1.reshape(1, d).astype(jnp.float32),
                   t2.reshape(1, d).astype(jnp.float32)]
        in_specs += [full_spec((1, d)), full_spec((1, d))]
    elif mode == "pq":
        _, M, pq_k = t1.shape
        inputs += [pad_b(t1.astype(jnp.float32).reshape(B, M * pq_k), 0.0)]
        in_specs += [lane_spec(M * pq_k)]
    elif mode != "f32":
        raise ValueError(f"unknown score mode {mode!r}")
    if has_live:        # no liveness bitmap → no VMEM row, no per-hop gather
        inputs += [i32(live_pad).reshape(1, n1)]
        in_specs += [full_spec((1, n1))]
    if has_tree:
        tf, tt, tl, tr, tv = tree
        T = tf.shape[0]
        inputs += [i32(tf).reshape(1, T), tt.reshape(1, T),
                   i32(tl).reshape(1, T), i32(tr).reshape(1, T),
                   tv.reshape(1, T),
                   pad_b(jnp.stack([hot_first, hot_ratio], axis=1)
                         .astype(jnp.float32), 0.0)]
        in_specs += [full_spec((1, T))] * 5 + [lane_spec(2)]

    sort_len = next_pow2(L + R)
    kernel = functools.partial(
        lambda *refs, **kw: _hop_kernel(refs, **kw),
        pltpu=pltpu, mode=mode, has_tree=has_tree, has_live=has_live,
        bl=bl, R=R, L=L, n=n, hops=hops, max_hops=max_hops, k=k,
        eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth,
        sort_len=sort_len, pq_k=pq_k)

    out = pl.pallas_call(
        kernel,
        grid=(Bp // bl,),
        in_specs=in_specs,
        out_specs=[lane_spec(L), lane_spec(L), lane_spec(L), lane_spec(n1),
                   lane_spec(8)],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, L), jnp.int32),
            jax.ShapeDtypeStruct((Bp, L), jnp.float32),
            jax.ShapeDtypeStruct((Bp, L), jnp.int32),
            jax.ShapeDtypeStruct((Bp, n1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 8), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bl, R), jnp.int32),                # adjacency rows
            pltpu.VMEM((2, R, t0.shape[1]), t0.dtype),     # double buffer
            pltpu.VMEM((bl, R), jnp.float32),              # lane distances
            pltpu.SemaphoreType.DMA((bl,)),
            pltpu.SemaphoreType.DMA((2, R)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*inputs)

    o_ids, o_dists, o_exp, o_seen, o_stat = [a[:B] for a in out]
    return HopState(
        ids=o_ids, dists=o_dists, expanded=o_exp != 0, seen=o_seen != 0,
        active=o_stat[:, 0] != 0, dist_count=o_stat[:, 1],
        update_count=o_stat[:, 2], hops=o_stat[:, 3],
        terminated=o_stat[:, 4] != 0, evals_done=o_stat[:, 5],
        stop_at=o_stat[:, 6])


@functools.partial(jax.jit, static_argnames=(
    "mode", "hops", "max_hops", "k", "eval_gap", "add_step", "tree_depth",
    "bl", "interpret"))
def fused_hop_paged_pallas(hs: HopState, pt, adj_pad, queries, live_pad,
                           mode: str, t0, t1=None, t2=None, tree=None,
                           hot_first=None, hot_ratio=None, *, hops: int,
                           max_hops: int, k: int = 1, eval_gap: int = 1,
                           add_step: int = 0, tree_depth: int = 1,
                           bl: int = 8,
                           interpret: bool = False) -> HopState:
    """Paged-seen megakernel; contract = :func:`ref.fused_hop_paged`.

    ``hs.seen`` carries the whole page pool ``(n_pages, page_cols)``
    instead of a per-lane bitmap; ``pt`` is the lane page table ``(B,
    pages_per_lane)``.  The kernel walks the page table itself: per grid
    step it DMAs the block's pages into VMEM, runs the exact dense hop
    loop, and DMAs the pages back — returning the updated pool in
    ``seen``.  The wave must already be a multiple of ``bl`` (the engine
    admits power-of-two buckets ≥ ``bl``); inert padding lanes must point
    at the allocator's scratch pages so duplicate write-backs carry
    identical bytes.
    """
    from jax.experimental.pallas import tpu as pltpu  # deferred: CPU-safe

    B, L = hs.ids.shape
    if B % bl:
        raise ValueError(
            f"paged wave width {B} must be a multiple of bl={bl}; pad the "
            "bucket with scratch lanes before dispatch")
    pool = hs.seen
    page_cols = pool.shape[1]
    ppl = pt.shape[1]
    n1 = adj_pad.shape[0]
    n = n1 - 1
    R = adj_pad.shape[1]
    d = queries.shape[1]
    has_tree = tree is not None
    has_live = live_pad is not None

    i32 = lambda a: a.astype(jnp.int32)
    stat = jnp.stack(
        [i32(hs.active), i32(hs.dist_count), i32(hs.update_count),
         i32(hs.hops), i32(hs.terminated), i32(hs.evals_done),
         i32(hs.stop_at), jnp.zeros((B,), jnp.int32)], axis=1)

    lane_spec = lambda w: pl.BlockSpec((bl, w), lambda i: (i, 0))
    full_spec = lambda s: pl.BlockSpec(s, lambda i: (0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    inputs = [i32(hs.ids), hs.dists, i32(hs.expanded), i32(pt), stat,
              queries.astype(jnp.float32)]
    in_specs = [lane_spec(L), lane_spec(L), lane_spec(L), lane_spec(ppl),
                lane_spec(8), lane_spec(d)]
    inputs += [adj_pad, t0]
    in_specs += [any_spec, any_spec]
    pq_k = 1
    if mode == "sq8":
        inputs += [t1.reshape(1, d).astype(jnp.float32),
                   t2.reshape(1, d).astype(jnp.float32)]
        in_specs += [full_spec((1, d)), full_spec((1, d))]
    elif mode == "pq":
        _, M, pq_k = t1.shape
        inputs += [t1.astype(jnp.float32).reshape(B, M * pq_k)]
        in_specs += [lane_spec(M * pq_k)]
    elif mode != "f32":
        raise ValueError(f"unknown score mode {mode!r}")
    if has_live:
        inputs += [i32(live_pad).reshape(1, n1)]
        in_specs += [full_spec((1, n1))]
    if has_tree:
        tf, tt, tl, tr, tv = tree
        T = tf.shape[0]
        inputs += [i32(tf).reshape(1, T), tt.reshape(1, T),
                   i32(tl).reshape(1, T), i32(tr).reshape(1, T),
                   tv.reshape(1, T),
                   jnp.stack([hot_first, hot_ratio], axis=1)
                   .astype(jnp.float32)]
        in_specs += [full_spec((1, T))] * 5 + [lane_spec(2)]
    inputs += [i32(pool)]
    in_specs += [any_spec]
    pool_idx = len(inputs) - 1

    sort_len = next_pow2(L + R)
    kernel = functools.partial(
        lambda *refs, **kw: _hop_kernel(refs, **kw),
        pltpu=pltpu, mode=mode, has_tree=has_tree, has_live=has_live,
        bl=bl, R=R, L=L, n=n, hops=hops, max_hops=max_hops, k=k,
        eval_gap=eval_gap, add_step=add_step, tree_depth=tree_depth,
        sort_len=sort_len, pq_k=pq_k, paged=True, ppl=ppl,
        page_cols=page_cols)

    out = pl.pallas_call(
        kernel,
        grid=(B // bl,),
        in_specs=in_specs,
        out_specs=[lane_spec(L), lane_spec(L), lane_spec(L), lane_spec(8),
                   any_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, L), jnp.int32),
            jax.ShapeDtypeStruct((B, L), jnp.float32),
            jax.ShapeDtypeStruct((B, L), jnp.int32),
            jax.ShapeDtypeStruct((B, 8), jnp.int32),
            jax.ShapeDtypeStruct(pool.shape, jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bl, R), jnp.int32),                # adjacency rows
            pltpu.VMEM((2, R, t0.shape[1]), t0.dtype),     # double buffer
            pltpu.VMEM((bl, R), jnp.float32),              # lane distances
            pltpu.SemaphoreType.DMA((bl,)),
            pltpu.SemaphoreType.DMA((2, R)),
            pltpu.VMEM((bl, ppl * page_cols), jnp.int32),  # lane bitmaps
            pltpu.SemaphoreType.DMA((bl, ppl)),
        ],
        input_output_aliases={pool_idx: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*inputs)

    o_ids, o_dists, o_exp, o_stat, o_pool = out
    return HopState(
        ids=o_ids, dists=o_dists, expanded=o_exp != 0, seen=o_pool != 0,
        active=o_stat[:, 0] != 0, dist_count=o_stat[:, 1],
        update_count=o_stat[:, 2], hops=o_stat[:, 3],
        terminated=o_stat[:, 4] != 0, evals_done=o_stat[:, 5],
        stop_at=o_stat[:, 6])
