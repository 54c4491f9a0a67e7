"""Paged wave state: page-table-indexed lane storage for ragged serving.

The fixed-shape :class:`~repro.serving.engine.WaveEngine` holds one
max-padded array per state field, sized ``(wave_size, ...)``: every tick
pays for ``wave_size`` lanes whether 3 or 64 of them are live, and a new
lane can only be admitted into a free slot of that fixed wave.  This
module restructures the wave the way sglang-jax's ragged paged attention
restructures ragged KV: per-lane state lives in a device *page pool*
indexed by a per-lane *page table*, with cu-len bookkeeping on the
allocator, so

* lanes retire and admit continuously mid-stream (a free-list allocator
  hands out lane slots and ``seen`` pages; admission and retirement are
  device ``.at[]`` scatters, never a host round-trip of the wave state);
* per-tick work tracks the number of *live* lanes, not pool capacity —
  each tick gathers the live lanes into a dense bucket (width rounded to
  a power of two so recompiles stay bounded) and scatters results back;
* a straggler never holds the wave: it occupies one lane slot and its
  ``seen`` pages while every other slot keeps turning over.

Layout
------
Per-lane scratch (pool ids/dists/expanded, counters, query, hot features)
lives in *slot arrays* of shape ``(P+1, ...)`` — one row per lane page,
row ``P`` reserved as an inert scratch lane that padding entries of a
gather bucket point at.  The per-lane ``seen`` bitmap — the big array,
``n+1`` bools per lane — is *paged*: a shared pool ``(n_pages,
page_cols)`` plus a page table ``(P+1, pages_per_lane)``; logical bit
``(lane, id)`` lives at physical ``(page_table[lane, id >> s], id & m)``
with ``page_cols = 2**s``.  Pages are recycled through a free list in
arbitrary order, so the indirection is real — a lane's pages are not
contiguous, and admission overwrites whatever a recycled page held.

Bit-identity: :func:`expand_step_paged` mirrors
:func:`repro.core.beam_search.expand_step` expression for expression —
only the ``seen`` reads/writes walk the page table — so a paged engine
produces bitwise-identical per-query results (ids, dists, tie order) to
the fixed-wave engine.  :func:`dense_seen` is the oracle seam: tests
assert the paged bitmap round-trips exactly against the dense one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import beam_search as bs
from repro.core.beam_search import _merge_pool
from repro.core.dynamic_search import _seed_full_state
from repro.core.features import hot_features
from repro.core.types import INF_DIST, PoolState, SearchStats

__all__ = ["PagedState", "PagePool", "PageAllocDenied", "expand_step_paged",
           "gather_wave", "scatter_wave", "admit_wave", "seed_admit",
           "dense_seen", "bucket_width", "zero_paged_state",
           "DEFAULT_PAGE_COLS"]

DEFAULT_PAGE_COLS = 256          # bools per seen page (must be a power of 2)
MIN_BUCKET = 8                   # smallest gather-bucket width


class PagedState(NamedTuple):
    """Device-resident paged wave state (a pytree; jit in, jit out).

    Slot arrays carry ``P+1`` rows (row ``P`` = inert scratch lane);
    ``seen_pages`` is the shared page pool the per-lane page table
    indexes into.
    """

    ids: jnp.ndarray            # (P+1, L) int32, sentinel = n
    dists: jnp.ndarray          # (P+1, L) float32
    expanded: jnp.ndarray       # (P+1, L) bool
    dist_count: jnp.ndarray     # (P+1,) int32
    update_count: jnp.ndarray   # (P+1,) int32
    hops: jnp.ndarray           # (P+1,) int32
    terminated: jnp.ndarray     # (P+1,) bool
    active: jnp.ndarray         # (P+1,) bool
    evals: jnp.ndarray          # (P+1,) int32 — tree evaluations done
    queries: jnp.ndarray        # (P+1, d) float32
    hot_first: jnp.ndarray      # (P+1,) float32
    hot_ratio: jnp.ndarray      # (P+1,) float32
    seen_pages: jnp.ndarray     # (n_pages, page_cols) bool


class WaveView(NamedTuple):
    """A gathered (dense) bucket of live lanes — one tick's working set."""

    beam: bs.BeamState          # .seen holds the PAGE POOL, not dense rows
    evals: jnp.ndarray          # (Wb,) int32
    queries: jnp.ndarray        # (Wb, d)
    hot_first: jnp.ndarray      # (Wb,)
    hot_ratio: jnp.ndarray      # (Wb,)


def _check_pow2(v: int, name: str) -> None:
    if v <= 0 or (v & (v - 1)):
        raise ValueError(f"{name} must be a positive power of two, got {v}")


def bucket_width(count: int, cap: int, lo: int = MIN_BUCKET) -> int:
    """Smallest power-of-two width ≥ ``count`` (≥ lo, ≤ next_pow2(cap)).

    Gather buckets are padded to these widths so the jitted tick compiles
    once per width — O(log cap) executables — instead of once per live
    count.
    """
    w = lo
    while w < count:
        w *= 2
    return w


def zero_paged_state(capacity: int, pool_len: int, d: int, n_pages: int,
                     page_cols: int, sentinel: int) -> PagedState:
    """All-lanes-idle paged state (no scoring; lanes are admitted later)."""
    P1 = capacity + 1
    return PagedState(
        ids=jnp.full((P1, pool_len), sentinel, jnp.int32),
        dists=jnp.full((P1, pool_len), INF_DIST, jnp.float32),
        expanded=jnp.zeros((P1, pool_len), bool),
        dist_count=jnp.zeros((P1,), jnp.int32),
        update_count=jnp.zeros((P1,), jnp.int32),
        hops=jnp.zeros((P1,), jnp.int32),
        terminated=jnp.zeros((P1,), bool),
        active=jnp.zeros((P1,), bool),
        evals=jnp.zeros((P1,), jnp.int32),
        queries=jnp.zeros((P1, d), jnp.float32),
        hot_first=jnp.zeros((P1,), jnp.float32),
        hot_ratio=jnp.zeros((P1,), jnp.float32),
        seen_pages=jnp.zeros((n_pages, page_cols), bool),
    )


class PageAllocDenied(RuntimeError):
    """A chaos plan denied this allocation (transient — retry next tick).

    Distinct from the bare ``RuntimeError`` real exhaustion raises so the
    engines can requeue the admission batch instead of treating an
    injected denial as a sizing bug.
    """


class PagePool:
    """Host-side allocator: lane slots + ``seen`` pages + page table.

    The page table and free lists are authoritative on the host (the
    allocator is pure bookkeeping — tiny, mutation-heavy, and consulted
    every admission); each tick ships only the gathered rows
    ``page_table[lanes]`` to the device, a few hundred int32s.

    ``cu_lens`` is the ragged-batch contract: ``cu_lens[i]`` is the total
    page count of the first ``i`` live lanes (exclusive prefix), which is
    how the allocator carves page ranges for a multi-lane admission and
    how tests audit that live lanes exactly partition the allocated
    pages.
    """

    def __init__(self, capacity: int, n_ids: int,
                 page_cols: int = DEFAULT_PAGE_COLS, *,
                 registry=None, name: str = "pool"):
        _check_pow2(page_cols, "page_cols")
        self.capacity = int(capacity)
        self.page_cols = int(page_cols)
        self.page_shift = int(page_cols).bit_length() - 1
        # lifecycle counters (repro.obs, optional): page churn is the
        # allocator's traffic signal — alloc/free rates show admission
        # throughput, grows mark store-capacity epochs, and the in-use
        # gauge is the paged analogue of wave occupancy
        self.name = str(name)
        self._registry = registry
        if registry is not None:
            self._c_alloc = registry.counter(
                "page_pool_alloc_total", "seen pages handed to lanes")
            self._c_free = registry.counter(
                "page_pool_free_total", "seen pages returned to free list")
            self._c_grow = registry.counter(
                "page_pool_grow_total", "pool rebuilds for a new store size")
            self._g_in_use = registry.gauge(
                "page_pool_pages_in_use", "allocated (non-free) seen pages")
        self._prev_n_ids: Optional[int] = None
        self.chaos = None           # fault hook (repro.chaos), None = off
        self.reset(n_ids)

    def _publish(self) -> None:
        if self._registry is not None:
            self._g_in_use.set(
                self.capacity * self.pages_per_lane - len(self._free_pages),
                pool=self.name)

    # ------------------------------------------------------------- lifecycle
    def reset(self, n_ids: int) -> None:
        """(Re)build for a store of ``n_ids`` rows; frees every lane."""
        if self._registry is not None and self._prev_n_ids is not None \
                and int(n_ids) != self._prev_n_ids:
            self._c_grow.inc(pool=self.name)
        self._prev_n_ids = int(n_ids)
        self.n_ids = int(n_ids)
        self.pages_per_lane = -(-(self.n_ids + 1) // self.page_cols)
        ppl, P = self.pages_per_lane, self.capacity
        self.n_pages = (P + 1) * ppl
        # scratch lane P permanently owns the last ppl pages
        self._scratch_pages = np.arange(P * ppl, (P + 1) * ppl,
                                        dtype=np.int32)
        self.page_table = np.tile(self._scratch_pages, (P + 1, 1))
        # LIFO free lists: recycled lanes/pages are reused first, so the
        # physical page order genuinely diverges from the logical one
        self._free_lanes = list(range(P - 1, -1, -1))
        self._free_pages = list(range(P * ppl - 1, -1, -1))
        self._live: list[int] = []
        self._publish()

    # ------------------------------------------------------------ allocation
    @property
    def free_lane_count(self) -> int:
        return len(self._free_lanes)

    @property
    def live_count(self) -> int:
        return len(self._live)

    def occupancy(self) -> float:
        return len(self._live) / self.capacity if self.capacity else 0.0

    def live_lanes(self) -> np.ndarray:
        """Live lane slots in admission order."""
        return np.asarray(self._live, np.int32)

    def cu_lens(self, lanes: Optional[np.ndarray] = None) -> np.ndarray:
        """Exclusive prefix of per-lane page counts over ``lanes``.

        With today's uniform ``pages_per_lane`` this is an affine ramp;
        keeping it explicit is what lets page counts go ragged (capacity
        growth mid-stream, bitpacked tails) without touching callers.
        """
        m = len(self._live) if lanes is None else len(lanes)
        counts = np.full(m, self.pages_per_lane, np.int64)
        return np.concatenate([[0], np.cumsum(counts)])

    def alloc(self, m: int) -> np.ndarray:
        """Claim ``m`` lane slots + their seen pages; fill page-table rows."""
        if m > len(self._free_lanes):
            raise RuntimeError(
                f"page pool exhausted: want {m} lanes, "
                f"{len(self._free_lanes)} free")
        if self.chaos is not None and self.chaos.deny_alloc():
            raise PageAllocDenied(
                f"chaos: page allocation denied (want {m} lanes)")
        lanes = np.asarray([self._free_lanes.pop() for _ in range(m)],
                           np.int32)
        cu = self.cu_lens(lanes)
        pages = np.asarray([self._free_pages.pop()
                            for _ in range(int(cu[-1]))], np.int32)
        for j, lane in enumerate(lanes):
            self.page_table[lane] = pages[cu[j]:cu[j + 1]]
        self._live.extend(int(v) for v in lanes)
        if self._registry is not None and len(pages):
            self._c_alloc.inc(float(len(pages)), pool=self.name)
            self._publish()
        return lanes

    def free(self, lanes) -> None:
        """Release lane slots and their pages back to the free lists."""
        n_freed = 0
        for lane in lanes:
            lane = int(lane)
            self._live.remove(lane)
            self._free_pages.extend(
                int(p) for p in self.page_table[lane])
            n_freed += self.pages_per_lane
            self.page_table[lane] = self._scratch_pages
            self._free_lanes.append(lane)
        if self._registry is not None and n_freed:
            self._c_free.inc(float(n_freed), pool=self.name)
            self._publish()

    def adopt(self, lanes) -> None:
        """Re-claim *specific* lane slots after :meth:`reset`, in order.

        Capacity growth rebuilds the pool (pages per lane changed) but
        in-flight lanes must keep their slot indices — host metadata and
        the device slot arrays are keyed by them.  Fresh pages are
        allocated for each adopted lane; the caller scatters the regrown
        seen rows into them.
        """
        n_adopted = 0
        for lane in lanes:
            lane = int(lane)
            self._free_lanes.remove(lane)
            cnt = self.pages_per_lane
            self.page_table[lane] = [self._free_pages.pop()
                                     for _ in range(cnt)]
            n_adopted += cnt
            self._live.append(lane)
        if self._registry is not None and n_adopted:
            self._c_alloc.inc(float(n_adopted), pool=self.name)
            self._publish()

    # ------------------------------------------------------------- gathering
    def pt_rows(self, lanes: np.ndarray) -> np.ndarray:
        """(len(lanes), pages_per_lane) page-table rows for a bucket."""
        return self.page_table[lanes]

    def live_bucket(self, lo: int = MIN_BUCKET
                    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Live lanes padded to a bucket width: (lanes, pt_rows, n_live).

        Padding entries point at the scratch lane ``P`` (inert: inactive,
        scratch seen pages), so the tick treats them as exact no-ops.
        """
        live = self.live_lanes()
        w = bucket_width(max(len(live), 1), self.capacity, lo)
        lanes = np.full(w, self.capacity, np.int32)
        lanes[:len(live)] = live
        return lanes, self.page_table[lanes], len(live)


# ---------------------------------------------------------------- jitted ops
def expand_step_paged(table, adj_pad: jnp.ndarray, queries: jnp.ndarray,
                      state: bs.BeamState, pt: jnp.ndarray, page_shift: int,
                      live_pad: Optional[jnp.ndarray] = None) -> bs.BeamState:
    """One expansion per active lane, ``seen`` walked through the page table.

    Mirrors :func:`repro.core.beam_search.expand_step` expression for
    expression — same frontier selection, scoring, merge and counters —
    except that ``state.seen`` is the shared page pool ``(n_pages,
    page_cols)`` and every seen read/write resolves ``(lane, id)`` to
    ``(pt[lane, id >> page_shift], id & (page_cols-1))``.  Bitwise
    equivalence to the dense step follows from the mapping being a
    bijection per lane.
    """
    n = bs.table_n(table)
    B, L = state.pool.ids.shape
    mask = (1 << page_shift) - 1

    unexp = (~state.pool.expanded) & (state.pool.ids != n)       # (B, L)
    has_work = jnp.any(unexp, axis=1)
    lane = state.active & has_work                               # (B,)
    slot = jnp.argmax(unexp, axis=1)                             # first True
    rows = jnp.arange(B)
    p = jnp.where(lane, state.pool.ids[rows, slot], n)           # (B,)

    expanded = state.pool.expanded.at[rows, slot].set(
        state.pool.expanded[rows, slot] | lane)

    nbrs = adj_pad[p]                                            # (B, R)
    # page-table walk replaces take_along_axis into the dense bitmap
    pg = jnp.take_along_axis(pt, nbrs >> page_shift, axis=1)     # (B, R)
    already = state.seen[pg, nbrs & mask]                        # (B, R)
    valid = (nbrs != n) & (~already) & lane[:, None]
    if live_pad is not None:
        valid &= live_pad[nbrs]
    cols = jnp.where(valid, nbrs, n)
    pgc = jnp.take_along_axis(pt, cols >> page_shift, axis=1)
    seen = state.seen.at[pgc, cols & mask].set(True)

    d2 = bs.score_rows(table, queries, cols)                     # (B, R)
    d2 = jnp.where(valid, d2, INF_DIST)

    pool = PoolState(state.pool.ids, state.pool.dists, expanded)
    pool, inserted = _merge_pool(
        pool, cols.astype(jnp.int32), d2, jnp.zeros_like(valid), lane)

    stats = SearchStats(
        dist_count=state.stats.dist_count
        + jnp.where(lane, jnp.sum(valid.astype(jnp.int32), 1), 0),
        update_count=state.stats.update_count + inserted,
        hops=state.stats.hops + lane.astype(jnp.int32),
        terminated_early=state.stats.terminated_early,
    )
    still = jnp.any((~pool.expanded) & (pool.ids != n), axis=1)
    return bs.BeamState(pool, seen, stats, state.active & still)


def gather_wave(ps: PagedState, lanes: jnp.ndarray) -> WaveView:
    """Gather a dense bucket of lanes out of the slot arrays.

    ``seen`` is NOT gathered — the returned beam's ``seen`` field carries
    the whole page pool, which :func:`expand_step_paged` indexes through
    the bucket's page-table rows.  Per-tick traffic therefore scales with
    the bucket width, not with ``capacity × n``.
    """
    pool = PoolState(ids=ps.ids[lanes], dists=ps.dists[lanes],
                     expanded=ps.expanded[lanes])
    stats = SearchStats(dist_count=ps.dist_count[lanes],
                        update_count=ps.update_count[lanes],
                        hops=ps.hops[lanes],
                        terminated_early=ps.terminated[lanes])
    beam = bs.BeamState(pool, ps.seen_pages, stats, ps.active[lanes])
    return WaveView(beam, ps.evals[lanes], ps.queries[lanes],
                    ps.hot_first[lanes], ps.hot_ratio[lanes])


def scatter_wave(ps: PagedState, lanes: jnp.ndarray, beam: bs.BeamState,
                 evals: jnp.ndarray) -> PagedState:
    """Write a ticked bucket back into the slot arrays (``.at[]`` scatter).

    ``beam.seen`` is the updated page pool and replaces ``seen_pages``
    wholesale (the tick mutated it in place through the page table).
    Duplicate scratch-lane entries in ``lanes`` collapse onto the inert
    row ``P``, which is forced back to idle afterwards.
    """
    P = ps.active.shape[0] - 1
    return PagedState(
        ids=ps.ids.at[lanes].set(beam.pool.ids),
        dists=ps.dists.at[lanes].set(beam.pool.dists),
        expanded=ps.expanded.at[lanes].set(beam.pool.expanded),
        dist_count=ps.dist_count.at[lanes].set(beam.stats.dist_count),
        update_count=ps.update_count.at[lanes].set(beam.stats.update_count),
        hops=ps.hops.at[lanes].set(beam.stats.hops),
        terminated=ps.terminated.at[lanes].set(beam.stats.terminated_early),
        active=ps.active.at[lanes].set(beam.active).at[P].set(False),
        evals=ps.evals.at[lanes].set(evals),
        queries=ps.queries,
        hot_first=ps.hot_first,
        hot_ratio=ps.hot_ratio,
        seen_pages=beam.seen,
    )


@functools.partial(jax.jit, static_argnames=("page_cols",))
def admit_wave(ps: PagedState, lanes: jnp.ndarray, pt: jnp.ndarray,
               seeded: bs.BeamState, queries: jnp.ndarray,
               hot_first: jnp.ndarray, hot_ratio: jnp.ndarray,
               admit_mask: jnp.ndarray, page_cols: int) -> PagedState:
    """Seed freshly-allocated lanes by device scatter (no host round-trip).

    ``seeded`` is the dense output of the refill hot phase +
    :func:`repro.core.dynamic_search._seed_full_state` for the admission
    bucket; its dense ``(m, n+1)`` seen rows are split into pages and
    scattered into the pool at the lanes' freshly-allocated page-table
    rows (overwriting whatever recycled pages held).  ``admit_mask``
    marks real admissions — padding entries target the scratch lane and
    are forced inert.
    """
    m, n1 = seeded.seen.shape
    ppl = pt.shape[1]
    pad = ppl * page_cols - n1
    pages = jnp.pad(seeded.seen, ((0, 0), (0, pad))).reshape(
        m, ppl, page_cols)
    P = ps.active.shape[0] - 1
    return PagedState(
        ids=ps.ids.at[lanes].set(seeded.pool.ids),
        dists=ps.dists.at[lanes].set(seeded.pool.dists),
        expanded=ps.expanded.at[lanes].set(seeded.pool.expanded),
        dist_count=ps.dist_count.at[lanes].set(seeded.stats.dist_count),
        update_count=ps.update_count.at[lanes].set(
            seeded.stats.update_count),
        hops=ps.hops.at[lanes].set(seeded.stats.hops),
        terminated=ps.terminated.at[lanes].set(
            seeded.stats.terminated_early),
        active=ps.active.at[lanes].set(admit_mask).at[P].set(False),
        evals=ps.evals.at[lanes].set(jnp.zeros((m,), jnp.int32)),
        queries=ps.queries.at[lanes].set(queries),
        hot_first=ps.hot_first.at[lanes].set(hot_first),
        hot_ratio=ps.hot_ratio.at[lanes].set(hot_ratio),
        seen_pages=ps.seen_pages.at[pt].set(pages),
    )


@functools.partial(jax.jit,
                   static_argnames=("k", "pool_size", "page_cols"))
def seed_admit(ps: PagedState, hot_pool: PoolState, hot_ids: jnp.ndarray,
               tenant_idx: jnp.ndarray, queries: jnp.ndarray,
               live_pad: jnp.ndarray, lanes_pt: jnp.ndarray, *, k: int,
               pool_size: int, page_cols: int) -> PagedState:
    """Seed an admission bucket and scatter it into the lanes, as one program.

    Runs :func:`repro.core.features.hot_features`, the local→global map
    ``hot_ids[tenant_idx]`` of the stacked ``(T, H+1)`` id table,
    :func:`repro.core.dynamic_search._seed_full_state` and
    :func:`admit_wave` in one trace, so an admission is one dispatch after
    the hot phase instead of one per op.  ``hot_pool`` is the stacked hot
    phase's local-id pool for the bucket.  ``lanes_pt`` packs each bucket
    entry's lane slot (column 0) and page-table row (the other columns)
    into one int32 array.  Padding entries target the scratch lane ``P``,
    so the admission mask is ``lanes < P``; the sentinel id is
    ``live_pad``'s last row.
    """
    lanes, pt = lanes_pt[:, 0], lanes_pt[:, 1:]
    P = ps.active.shape[0] - 1
    hf = hot_features(hot_pool, k)
    seeded = _seed_full_state(hot_pool, hot_ids[tenant_idx],
                              live_pad.shape[0] - 1, pool_size, live_pad)
    return admit_wave(ps, lanes, pt, seeded, queries, hf.first,
                      hf.first_div_kth, lanes < P, page_cols=page_cols)


@functools.partial(jax.jit, static_argnames=("n1",))
def dense_seen(seen_pages: jnp.ndarray, pt: jnp.ndarray, n1: int
               ) -> jnp.ndarray:
    """Materialize dense ``(m, n1)`` seen rows from the page pool (oracle).

    The parity seam for tests and for the fused-path jnp oracle: gather a
    bucket's pages, concatenate, truncate the tail padding.
    """
    m = pt.shape[0]
    return seen_pages[pt].reshape(m, -1)[:, :n1]
