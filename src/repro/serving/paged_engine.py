"""Ragged paged wave engine: continuous lane admission over paged state.

The fixed-shape :class:`~repro.serving.engine.WaveEngine` ticks
``wave_size`` max-padded lanes no matter how many are live, and seeds new
lanes with a host-side splice of the whole wave state.  This engine keeps
the same search semantics — bitwise-identical per-query results, see the
parity tests — but restructures the state the way sglang-jax's ragged
paged attention restructures ragged KV (:mod:`repro.serving.paged`):

* per-lane scratch lives in ``(P+1, ...)`` slot arrays and the big
  ``seen`` bitmaps in a shared page pool behind a per-lane page table
  with cu-len bookkeeping (a host free-list allocator hands out lane
  slots and pages);
* each tick gathers the *live* lanes into a dense bucket whose width is
  the live count rounded up to a power of two (compiles stay bounded:
  O(log capacity) tick executables), advances it ``tick_hops``
  expansions — composed scan or the paged fused megakernel — and
  scatters the bucket back.  Work tracks live lanes, not capacity;
* admission is two device programs, the stacked hot phase and
  :func:`repro.serving.paged.seed_admit` (hot features, full-state
  seeding and the ``.at[]`` scatter into the lanes); retirement frees
  slots on the host.  Neither round-trips wave state, so lanes stream in
  and out continuously and a straggler holds one lane slot, not a wave;
* with a tiered store, block pins follow the allocator's *pages*: the
  pin set each tick is derived from the page-table-live lanes only, so a
  retired lane's blocks become evictable the moment its pages free;
* the host's retirement runs while the device works: a step waits for
  the tick in flight, reads its outputs, copies what the retirement needs
  and frees the finished lanes, refills them, dispatches the next tick,
  and only then harvests the answers (``tick.overlap``).  The schedule —
  which request enters which lane at which tick — is the serial one: a
  retirement that trips an Alg-2 rebuild or precedes a drained
  compaction runs before the refill, as it must.

Occupancy (``engine_occupancy_ratio`` = live lanes / lane capacity) is
published through the same :mod:`repro.obs` registry as the fixed
engine, under the same collector key ``"engine"`` (the two
engine kinds publish the same series, so whichever engine was built
last owns the scrape surface — stale twins are replaced, never merged).
"""

from __future__ import annotations

import collections
import time
from typing import NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import beam_search as bs
from repro.core.decision_tree import predict_jax
from repro.core.dynamic_search import hot_phase_stacked
from repro.core.features import feature_matrix
from repro.core.types import DQFConfig, HotFeatures, PoolState
from repro.obs import (ObsConfig, PerfSentinel, Timeline, TraceLog,
                       sample_decision)
from repro.serving import paged as pg
from repro.serving.engine import LATENCY_WINDOW, EngineStats, retire_batch
from repro.serving.status import EngineConfig, QueryStatus, shed_victim
from repro.tenancy import DEFAULT_TENANT

__all__ = ["PagedWaveEngine"]


class _InFlight(NamedTuple):
    """A dispatched tick: its bucket and its device outputs."""

    lanes: np.ndarray           # bucket lane slots, padded
    n_live: int
    cap: int                    # the store capacity (sentinel id) it ran on
    outs: tuple                 # (active, hops, ids, dists) of the bucket


class _Retiring(NamedTuple):
    """One tick's finished lanes, copied before the refill re-uses their
    slots: per lane its ``_lane_meta``, trace, status and degraded flag,
    query, pool, hops and (traced lanes only) counters."""

    meta: list
    traces: list
    status: list
    degraded: list
    queries: np.ndarray
    ids: np.ndarray
    dists: np.ndarray
    hops: np.ndarray
    dist_evals: Optional[np.ndarray]
    terminated: Optional[np.ndarray]


class PagedWaveEngine:
    """Continuous-admission serving engine over paged wave state.

    ``capacity`` is the lane-slot count (the admission ceiling — the
    analogue of the fixed engine's ``wave_size``); ``page_cols`` the seen
    page width; ``min_bucket`` the smallest tick bucket.  Everything else
    mirrors :class:`~repro.serving.engine.WaveEngine`.
    """

    def __init__(self, dqf, *, capacity: int = 64, tick_hops: int = 8,
                 page_cols: int = pg.DEFAULT_PAGE_COLS,
                 min_bucket: int = pg.MIN_BUCKET,
                 latency_window: int = LATENCY_WINDOW,
                 auto_compact: bool = True, compact_ratio: float = 0.3,
                 prefetch: bool = True, obs: Optional[ObsConfig] = None,
                 engine_cfg: Optional[EngineConfig] = None, clock=None):
        if min_bucket < 1 or (min_bucket & (min_bucket - 1)):
            raise ValueError("min_bucket must be a power of two")
        self.dqf = dqf
        self.cfg: DQFConfig = dqf.cfg
        self.capacity = int(capacity)
        self.tick_hops = tick_hops
        self.page_cols = int(page_cols)
        self.min_bucket = int(min_bucket)
        self.auto_compact = auto_compact
        self.compact_ratio = compact_ratio
        self.prefetch = prefetch
        self.engine_cfg = engine_cfg if engine_cfg is not None \
            else EngineConfig()
        self._clock = clock if clock is not None else time.perf_counter
        self._shed_scale = 1.0      # tightened by AdmissionController
        self.queue: collections.deque = collections.deque()
        self.stats = EngineStats(
            latencies_ms=collections.deque(maxlen=latency_window),
            queue_wait_ms=collections.deque(maxlen=latency_window))
        self.obs = obs if obs is not None else ObsConfig()
        obs_on = bool(self.obs.enabled)
        self.registry = ((self.obs.registry
                          or getattr(dqf, "registry", None))
                         if obs_on else None)
        self.timeline = Timeline(enabled=obs_on and self.obs.timeline,
                                 capacity=self.obs.timeline_capacity)
        self.traces = TraceLog(self.obs.trace_capacity)
        self._trace_rate = float(self.obs.trace_rate) if obs_on else 0.0
        self._trace_seed = int(self.obs.trace_seed)
        self._lane_trace: list = [None] * self.capacity
        # (hot_stats, [(lane, row)]) of sampled admissions whose hot-phase
        # counters are read after the next tick's fetch, when the hot
        # phase has long completed, so the refill never waits on it
        self._hot_pending: list = []
        if self.registry is not None:
            r = self.registry
            self._h_service = r.histogram(
                "engine_service_ms", "seed→retire service time (ms)")
            self._h_qwait = r.histogram(
                "engine_queue_wait_ms", "submit→seed queue wait (ms)")
            self._h_hops = r.histogram(
                "engine_hops", "full-phase hops per retired query",
                lo=1.0, hi=1e5)
            self._g_tick_hit = r.gauge(
                "tier_tick_hit_rate",
                "block-cache hit rate over the last tick window")
            r.register_callback("engine", self._collect_metrics)
        self._fused = bool(self.cfg.fused) and not dqf.store.tiered
        dqf._sync_device()
        self._d = dqf.store.d
        self._epoch = dqf.store.epoch
        self._remap_epoch = dqf.store.remap_epoch
        self._cap = dqf.store.capacity
        self.pagepool = pg.PagePool(self.capacity, dqf.store.capacity,
                                    page_cols=self.page_cols,
                                    registry=self.registry, name="paged")
        self._tick_fn = self._build_tick()
        self._hot_phase = hot_phase_stacked
        self._admit = pg.seed_admit
        # Perf sentinel (ISSUE 9).  The paged tick's compile schedule is
        # the pow2 bucket ladder — min_bucket, 2·min_bucket, …,
        # next_pow2(capacity) — so its executable budget is declared up
        # front: one extra signature is a bucket leak, and the sentinel
        # flags it (``jit_schedule_violations_total``).
        self.sentinel = None
        self._n_widths = self._bucket_widths()
        if obs_on and self.obs.sentinel and self.registry is not None:
            self.sentinel = PerfSentinel.from_config(self.obs, self.registry)
            self._tick_fn = self.sentinel.wrap("paged_tick", self._tick_fn)
            self._hot_phase = self.sentinel.wrap("hot_phase_stacked",
                                                 hot_phase_stacked)
            self._admit = self.sentinel.wrap("paged_admit", pg.seed_admit)
            self.sentinel.expect("paged_tick", self._n_widths)
            self.sentinel.attach_capture(
                self, capture_ticks=self.obs.capture_ticks,
                bundle_dir=self.obs.capture_dir)
        self._lane_meta = [None] * self.capacity
        self._lane_status: list = [None] * self.capacity
        self._lane_degraded = [False] * self.capacity
        self._results: dict = {}
        self._state: Optional[pg.PagedState] = None
        self._inflight: Optional[_InFlight] = None
        self._queries = np.zeros((self.capacity + 1, self._d), np.float32)
        self._table = None
        self._table_key = None
        self._last_pinned = 0
        self._draining = False
        self._next_rid = 0

    def _bucket_widths(self) -> int:
        """Distinct pow2 tick-bucket widths: the compile-schedule budget."""
        n, w = 1, self.min_bucket
        top = pg.bucket_width(self.capacity, self.capacity, self.min_bucket)
        while w < top:
            w *= 2
            n += 1
        return n

    # ------------------------------------------------------------ jitted ops
    def _build_tick(self):
        cfg = self.cfg
        tree = self.dqf.tree.arrays if self.dqf.tree is not None else None
        shift = self.pagepool.page_shift
        hops = self.tick_hops

        if self._fused:
            from repro.kernels import ops as kops

            def fused_tick(ps: pg.PagedState, lanes, pt, table, adj_pad,
                           live_pad):
                wv = pg.gather_wave(ps, lanes)
                hs = kops.fused_hop_paged(
                    bs.to_hop_state(wv.beam, evals_done=wv.evals),
                    pt, adj_pad, wv.queries, live_pad, table, tree,
                    wv.hot_first, wv.hot_ratio, page_cols=self.page_cols,
                    hops=hops, max_hops=cfg.max_hops, k=cfg.k,
                    eval_gap=cfg.eval_gap, add_step=0,
                    tree_depth=cfg.tree_depth)
                beam, evals = bs.from_hop_state(hs), hs.evals_done
                ps = pg.scatter_wave(ps, lanes, beam, evals)
                return ps, (beam.active, beam.stats.hops,
                            beam.pool.ids, beam.pool.dists)

            return jax.jit(fused_tick)

        def tick(ps: pg.PagedState, lanes, pt, table, adj_pad, live_pad):
            # Same hop body as WaveEngine._build_tick, on a gathered
            # bucket with page-table seen access; recompiles once per
            # bucket width (power of two), not per live count.
            wv = pg.gather_wave(ps, lanes)

            def one(carry, _):
                s, ev = carry
                s = pg.expand_step_paged(table, adj_pad, wv.queries, s,
                                         pt, shift, live_pad)
                s = s._replace(
                    active=s.active & (s.stats.hops < cfg.max_hops))
                if tree is not None:
                    due = (s.stats.dist_count // cfg.eval_gap) > ev
                    due = due & s.active
                    feats = feature_matrix(
                        HotFeatures(wv.hot_first, wv.hot_ratio), s.pool,
                        s.stats, cfg.k)
                    stop = (predict_jax(tree, feats, cfg.tree_depth)
                            < 0.5) & due
                    ev = jnp.where(due,
                                   s.stats.dist_count // cfg.eval_gap, ev)
                    s = s._replace(
                        active=s.active & ~stop,
                        stats=s.stats._replace(
                            terminated_early=s.stats.terminated_early
                            | (stop & s.active)))
                return (s, ev), None

            (beam, evals), _ = jax.lax.scan(
                one, (wv.beam, wv.evals), None, length=hops)
            ps = pg.scatter_wave(ps, lanes, beam, evals)
            return ps, (beam.active, beam.stats.hops,
                        beam.pool.ids, beam.pool.dists)

        return jax.jit(tick)

    # ---------------------------------------------------------------- public
    def submit(self, queries: np.ndarray, *, tenant: str = DEFAULT_TENANT,
               deadline_ms: Optional[float] = None) -> list:
        """Enqueue queries for one tenant; returns their request ids.

        Deadline / bounded-admission semantics are identical to
        :meth:`WaveEngine.submit` (one shared status vocabulary).
        """
        t = self.dqf.tenants.get(tenant)       # unknown tenant → KeyError
        if t.hot is None:
            raise RuntimeError(
                f"tenant {tenant!r} has no hot index — warm() it before "
                "serving")
        queries = np.asarray(queries, np.float32)
        if queries.ndim != 2 or queries.shape[1] != self._d:
            raise ValueError(
                f"queries must be (B, {self._d}) for this index, got "
                f"{queries.shape}")
        if deadline_ms is None:
            deadline_ms = self.engine_cfg.default_deadline_ms
        now = self._clock()
        deadline = now + deadline_ms / 1e3 if deadline_ms is not None \
            else None
        ids = []
        for q in queries:
            rid = self._next_rid
            self._next_rid += 1
            entry = (rid, q, now, t.name, t.gen, deadline)
            limit = self.effective_max_queue()
            if limit is not None and len(self.queue) >= limit:
                victim = shed_victim(self.queue, entry,
                                     self.engine_cfg.shed_policy)
                self._results[victim[0]] = self._terminal_result(
                    victim[3], QueryStatus.SHED)
                self.timeline.async_span(
                    "req.queued", victim[0], victim[2], now,
                    tenant=victim[3], status=QueryStatus.SHED.value)
                self.stats.shed += 1
                self.stats.note_terminal(QueryStatus.SHED)
            else:
                self.queue.append(entry)
            ids.append(rid)
        return ids

    def effective_max_queue(self) -> Optional[int]:
        """Admission limit after SLO tightening (None = unbounded)."""
        mq = self.engine_cfg.max_queue
        if mq is None:
            return None
        return max(1, int(mq * self._shed_scale))

    def step(self) -> None:
        """Advance one tick; seeds lanes from the queue on first use.

        Returns with the next tick in flight whenever lanes are live, and
        with the answers of the tick it waited for in ``_results``.
        """
        if self._state is None:
            self._init_wave()
        self._tick()

    def run_until_drained(self, max_ticks: int = 10_000) -> dict:
        t0 = self._clock()
        # with lanes live a tick is in flight, and the first step refills
        # once it has waited for it, as a refill here would after it
        if self._state is None or not self._any_live():
            self._init_wave()
        while (self.queue or self._any_live()) \
                and self.stats.ticks < max_ticks:
            self._tick()
        if self._draining and not self._any_live():
            self._do_compact()
        wall = self._clock() - t0
        return {"results": self._results, "wall_s": wall,
                "qps": self.stats.qps(wall), "p99_ms": self.stats.p99_ms(),
                "queue_wait_p99_ms": self.stats.queue_wait_p99_ms(),
                "straggled": self.stats.straggled,
                "compactions": self.stats.compactions}

    def scrape(self) -> dict:
        return self.registry.scrape() if self.registry is not None else {}

    def export_timeline(self, path: Optional[str] = None):
        """Chrome trace-event JSON of the recorded tick spans (Perfetto)."""
        return self.timeline.export(path)

    def debug_bundle(self, out_dir: str, *, reason: str = "") -> str:
        """Write a black-box debug bundle (see :mod:`repro.obs.bundle`)."""
        from repro.obs import debug_bundle
        return debug_bundle(self, out_dir, reason=reason)

    def _collect_metrics(self) -> dict:
        """Registry scrape-time collector (keyed ``"engine"``)."""
        s = self.stats
        limit = self.effective_max_queue()
        out = {"engine_completed_total": float(s.completed),
               "engine_straggled_total": float(s.straggled),
               "engine_dropped_total": float(s.dropped),
               "engine_shed_total": float(s.shed),
               "engine_deadline_total": float(s.deadline_hit),
               "engine_degraded_total": float(s.degraded),
               "engine_admission_limit": float(limit if limit is not None
                                               else -1),
               "engine_ticks_total": float(s.ticks),
               "engine_hops_total": float(s.total_hops),
               "engine_compactions_total": float(s.compactions),
               "engine_rerank_rows_total": float(s.rerank_rows),
               "engine_overlapped_ticks_total": float(s.overlapped_ticks),
               "engine_queue_depth": float(len(self.queue)),
               "engine_live_lanes": float(self.pagepool.live_count),
               "engine_lane_capacity": float(self.capacity),
               "engine_occupancy_ratio": self.pagepool.occupancy(),
               "engine_traces_recorded": float(self.traces.total),
               "engine_traces_dropped": float(self.traces.dropped)}
        for status, count in s.terminal.items():
            out[f"engine_terminal_status_total{{status={status}}}"] = \
                float(count)
        return out

    # -------------------------------------------------------------- internals
    def _any_live(self) -> bool:
        return self.pagepool.live_count > 0

    def _init_wave(self):
        self._state = None          # growth path not needed: fresh build
        self._maybe_refresh()
        st = self.dqf.store
        self.pagepool.reset(st.capacity)
        self._state = pg.zero_paged_state(
            self.capacity, self.cfg.full_pool, self._d,
            self.pagepool.n_pages, self.page_cols, st.capacity)
        self._table_key = None
        self._refill()

    def _maybe_refresh(self):
        """Track the store epoch; mirror of WaveEngine._maybe_refresh."""
        st = self.dqf.store
        if st.epoch == self._epoch:
            return
        self._refuse_remap_in_flight()
        self.dqf._sync_device()
        if self._state is not None:
            if st.remap_epoch != self._remap_epoch:
                # external compaction, engine drained: rebuild from scratch
                self.pagepool.reset(st.capacity)
                self._state = pg.zero_paged_state(
                    self.capacity, self.cfg.full_pool, self._d,
                    self.pagepool.n_pages, self.page_cols, st.capacity)
            elif st.capacity != self._cap:
                self._grow_paged(self._cap, st.capacity)
            self._table_key = None
        self._cap = st.capacity
        self._epoch = st.epoch
        self._remap_epoch = st.remap_epoch

    def _refuse_remap_in_flight(self):
        if self.dqf.store.remap_epoch != self._remap_epoch \
                and self._any_live():
            raise RuntimeError(
                "store compacted while lanes are in flight — drain the "
                "engine before calling compact()")

    def _grow_paged(self, old_cap: int, new_cap: int):
        """Re-page live lanes after capacity growth (sentinel id moved).

        Rare host round-trip: densify the live lanes' seen rows at the
        old width, rebuild the pool for the new width (``pages_per_lane``
        changed), re-adopt the same lane slots, and re-paginate.  The
        pool shape changes, so the next tick recompiles — growth is an
        epoch event, not a steady-state one.
        """
        pool = self.pagepool
        live = pool.live_lanes()
        if live.size:
            dense = np.asarray(pg.dense_seen(
                self._state.seen_pages, jnp.asarray(pool.page_table[live]),
                old_cap + 1))
        pool.reset(new_cap)
        pool.adopt(live)
        pc = self.page_cols
        pages_np = np.zeros((pool.n_pages, pc), bool)
        for j, lane in enumerate(live):
            row = np.zeros(pool.pages_per_lane * pc, bool)
            row[:old_cap] = dense[j, :old_cap]   # old sentinel col dropped
            row[new_cap] = True
            pages_np[pool.page_table[lane]] = row.reshape(-1, pc)
        ids = np.asarray(self._state.ids)
        ids = np.where(ids == old_cap, new_cap, ids).astype(np.int32)
        self._state = self._state._replace(
            ids=jnp.asarray(ids), seen_pages=jnp.asarray(pages_np))
        if self.sentinel is not None:
            # growth changed the paged shapes: a fresh ladder of bucket
            # executables is legitimate, so the budget moves with it
            self.sentinel.expect(
                "paged_tick",
                self.sentinel.compile.executables("paged_tick")
                + self._n_widths)

    def _bind_table(self, lanes_np: np.ndarray):
        """Score table for this tick's bucket (PQ LUTs follow the bucket).

        Cached on ``(epoch, bucket lanes)`` — steady-state ticks with an
        unchanged bucket reuse the bound table; any admission/retirement
        or store mutation rebinds.  Tiered stores rebind every tick
        (``_tier_begin_tick`` clears the key: the cache arena moved).
        """
        key = (self._epoch, lanes_np.tobytes())
        if self._table is not None and key == self._table_key:
            return self._table
        qtable = self.dqf._quant_table()
        if qtable is None:
            self._table = self.dqf._row_table()
        else:
            self._table = qtable.with_queries(
                jnp.asarray(self._queries[lanes_np]))
        self._table_key = key
        return self._table

    def _refill(self):
        """Admit queued requests into freshly allocated lanes.

        The admission batch is padded to a power-of-two bucket (compile
        keys match the tick's) and runs as two device programs: the
        stacked-tenant hot phase, then :func:`repro.serving.paged.seed_admit`,
        which seeds the full phase and scatters the lanes device-side.
        The host copies three arrays: tenant slots, queries, and the
        padded lanes packed with their page-table rows.  Requests whose
        tenant was evicted (or re-created — the ``gen`` check) while
        queued drop immediately.  Nothing here reads back from the
        device: sampled lanes' hot-phase counters wait in
        ``_hot_pending`` for the next tick's fetch.
        """
        tl = self.timeline
        reg = self.dqf.tenants
        free = self.pagepool.free_lane_count
        reqs = []
        with tl.span("refill.queue"):
            now = self._clock()
            while self.queue and len(reqs) < free:
                r = self.queue.popleft()
                name, gen = r[3], r[4]
                if name not in reg or reg.get(name).gen != gen:
                    status = QueryStatus.DROPPED
                    self.stats.dropped += 1
                elif r[5] is not None and now >= r[5]:
                    status = QueryStatus.DEADLINE
                    self.stats.deadline_hit += 1
                else:
                    reqs.append(r)
                    continue
                self._results[r[0]] = self._terminal_result(name, status)
                self.stats.note_terminal(status)
                tl.async_span("req.queued", r[0], r[2], now, tenant=name,
                              status=status.value)
        if not reqs:
            return
        m = len(reqs)
        mp = pg.bucket_width(m, self.capacity, self.min_bucket)
        try:
            lanes = self.pagepool.alloc(m)
        except pg.PageAllocDenied:
            # transient injected denial: requeue in arrival order and try
            # again next tick — the requests stay live, never lost
            self.queue.extendleft(reversed(reqs))
            return
        with tl.span("refill.admit", admitted=m, bucket=mp):
            lanes_pad = np.full(mp, self.capacity, np.int32)
            lanes_pad[:m] = lanes
            lanes_pt = np.concatenate(
                [lanes_pad[:, None], self.pagepool.page_table[lanes_pad]],
                axis=1)
            qs = np.zeros((mp, self._d), np.float32)
            qs[:m] = np.stack([r[1] for r in reqs])
            tidx = np.zeros(mp, np.int32)
            tidx[:m] = [reg.slot_of(r[3]) for r in reqs]
            stk = reg.stacked(self.dqf.store)
            tidx_d = jnp.asarray(tidx)
            q_d = jnp.asarray(qs)
            hot_pool, hot_stats = self._hot_phase(
                stk.x, stk.adj, stk.entries, stk.mask, tidx_d, q_d,
                pool_size=self.cfg.hot_pool, max_hops=self.cfg.max_hops,
                mode=self.cfg.hot_mode)
            self._state = self._admit(
                self._state, hot_pool, stk.ids, tidx_d, q_d,
                self.dqf._dev["live_pad"], jnp.asarray(lanes_pt),
                k=self.cfg.k, pool_size=self.cfg.full_pool,
                page_cols=self.page_cols)
        with tl.span("refill.lanes"):
            # same sampling contract as the fixed engine: pure in (seed,
            # rid); hot-phase stats transfer only when some admitted lane
            # is sampled
            sampled = [sample_decision(self._trace_seed, r[0],
                                       self._trace_rate) for r in reqs]
            if any(sampled):
                self._hot_pending.append(
                    (hot_stats, [(int(lanes[j]), j) for j in range(m)
                                 if sampled[j]]))
            t_seed = self._clock()
            seed_tick = self.stats.ticks
            for j, lane in enumerate(lanes):
                lane = int(lane)
                self._queries[lane] = reqs[j][1]
                rid, t_in = reqs[j][0], reqs[j][2]
                self._lane_meta[lane] = (rid, t_in, t_seed, reqs[j][3],
                                         reqs[j][4], reqs[j][5], seed_tick)
                self._lane_status[lane] = None
                self._lane_degraded[lane] = False
                wait_ms = (t_seed - t_in) * 1e3
                self.stats.queue_wait_ms.append(wait_ms)
                if self.registry is not None:
                    self._h_qwait.observe(wait_ms)
                if sampled[j]:
                    self._lane_trace[lane] = {
                        "rid": rid, "tenant": reqs[j][3],
                        "hot_hops": None, "hot_dist_evals": None,
                        "seed_tick": seed_tick,
                    }
                else:
                    self._lane_trace[lane] = None
                if tl.enabled:
                    tl.async_span("req.queued", rid, t_in, t_seed,
                                  tenant=reqs[j][3])
            self._table_key = None

    def _fetch(self, *arrays) -> list:
        """Blocking device→host reads of tick outputs (``tick.fetch``)."""
        tl = self.timeline
        if not tl.enabled:
            return [np.asarray(a) for a in arrays]
        with tl.span("tick.fetch", arrays=len(arrays),
                     bytes=int(sum(a.nbytes for a in arrays))):
            return [np.asarray(a) for a in arrays]

    def _read_hot_stats(self):
        """Hot-phase counters of the lanes sampled at the last refill.

        Called after a tick's fetch: that tick ran on the state the
        admission wrote, so the hot phase before it has completed and
        this read waits on nothing.
        """
        for hot_stats, rows in self._hot_pending:
            hops, dist = self._fetch(hot_stats.hops, hot_stats.dist_count)
            for lane, j in rows:
                tr = self._lane_trace[lane]
                tr["hot_hops"] = int(hops[j])
                tr["hot_dist_evals"] = int(dist[j])
        self._hot_pending.clear()

    def _terminal_result(self, tenant: str, status: QueryStatus) -> dict:
        k = self.cfg.k
        return {"ids": np.full(k, self.dqf.store.capacity, np.int32),
                "dists": np.full(k, np.inf, np.float32),
                "hops": 0, "tenant": tenant, "degraded": False,
                "status": status.value}

    def _tier_begin_tick(self):
        """Tier housekeeping: pins follow the allocator's pages.

        The pin set is derived from the page-table-live lanes only — the
        moment a lane's pages free, its blocks stop being pinned and the
        cache can evict them.  Frontier prefetch predicts each live
        lane's next expansion from the slot arrays, same as the fixed
        engine's.
        """
        st = self.dqf.store
        if not st.tiered:
            return
        cache = st.full_phase_cache()
        for c in st.tier_caches():      # stale rows from out-of-band
            c.take_degraded_rows()      # searches don't map to lanes
        live = self.pagepool.live_lanes()
        if live.size:
            live_d = jnp.asarray(live)
            ids = np.asarray(self._state.ids[live_d])
            ids = ids[ids < st.n]
            bids = cache.blocks_of_rows(ids)
            cache.pin_blocks(bids)
            self._last_pinned = int(len(bids))
        else:
            cache.pin_blocks(())
            self._last_pinned = 0
        cache.apply_prefetch()
        cache.maintain()
        if self.registry is not None:
            self._g_tick_hit.set(cache.stats_snapshot()["hit_rate"])
        if self.prefetch and live.size:
            sub = bs.BeamState(
                PoolState(ids=self._state.ids[live_d],
                          dists=self._state.dists[live_d],
                          expanded=self._state.expanded[live_d]),
                None, None, self._state.active[live_d])
            nxt = np.asarray(bs.next_expansions(sub, st.capacity))
            nxt = nxt[nxt < st.n]
            if nxt.size:
                nbrs = self.dqf.full.adj[nxt]
                cache.prefetch_async(cache.blocks_of_rows(
                    np.concatenate([nxt, nbrs[nbrs >= 0]])))
        self._table_key = None      # cache arena moved: rebind the table

    def _do_compact(self):
        """Drained compaction at a safe tick boundary; serving resumes."""
        self.dqf.compact()
        self.stats.compactions += 1
        self._draining = False
        st = self.dqf.store
        self._epoch = st.epoch
        self._remap_epoch = st.remap_epoch
        self._cap = st.capacity
        self.pagepool.reset(st.capacity)
        self._state = pg.zero_paged_state(
            self.capacity, self.cfg.full_pool, self._d,
            self.pagepool.n_pages, self.page_cols, st.capacity)
        self._table_key = None

    def _tick(self):
        tl = self.timeline
        # before the harvest frees a lane: ids in flight are in the old
        # id space, so a compaction between steps must find them live
        self._refuse_remap_in_flight()
        with tl.span("tick", tick=self.stats.ticks):
            if self._inflight is None and self._any_live():
                # first step, or lanes seeded outside a step: no tick of
                # theirs is running yet
                self._housekeeping()
                self._dispatch()
            ret, now = self._harvest()
            self._housekeeping()
            if self.auto_compact and not self._draining \
                    and self.dqf.store.should_compact(self.compact_ratio):
                self._draining = True
            compact = self._draining and not self._any_live()
            if ret is not None and (compact or self._retire_first(ret)):
                self._retire(ret, now)
                ret = None
            if compact:
                self._do_compact()
            if not self._draining:
                with tl.span("tick.refill"):
                    self._refill()
            self._dispatch()
            if ret is not None:
                if self._inflight is None:      # nothing left to overlap
                    self._retire(ret, now)
                else:
                    with tl.span("tick.overlap", retiring=len(ret.meta)):
                        self._retire(ret, now)
                    self.stats.overlapped_ticks += 1
        if self.sentinel is not None:
            self.sentinel.on_tick()

    def _housekeeping(self):
        with self.timeline.span("tick.housekeeping"):
            self._maybe_refresh()

    def _dispatch(self):
        """Tier pins, then issue a tick over the live lanes; no wait."""
        tl = self.timeline
        with tl.span("tick.tier"):
            self._tier_begin_tick()
        lanes_np, pt_np, n_live = self.pagepool.live_bucket(self.min_bucket)
        if not n_live:
            return
        table = self._bind_table(lanes_np)
        with tl.span("tick.jit", bucket=len(lanes_np), live=n_live):
            self._state, outs = self._tick_fn(
                self._state, jnp.asarray(lanes_np), jnp.asarray(pt_np),
                table, self.dqf._dev["adj_pad"], self.dqf._dev["live_pad"])
        self._inflight = _InFlight(lanes_np, n_live, self._cap, outs)

    def _harvest(self) -> tuple[Optional[_Retiring], Optional[float]]:
        """Wait for the tick in flight, count it, read its outputs, expire
        overdue lanes, and copy out and free the lanes it finished.

        Every read is of that tick's own output state: nothing has been
        dispatched after it yet.  Returns the finished lanes (None if no
        lane retires) and the clock reading their latencies end at.
        """
        tl = self.timeline
        fl, self._inflight = self._inflight, None
        self.stats.ticks += 1
        if fl is None:
            return None, None
        lanes_np, n_live = fl.lanes, fl.n_live
        with tl.span("tick.jit", bucket=len(lanes_np), live=n_live):
            if tl.enabled:  # make the span cover device time
                jax.block_until_ready(self._state)
        act, hops_b, ids_b, dists_b = fl.outs
        (active,) = self._fetch(act)
        active = active.copy()  # writable: deadlines clear it
        self._read_hot_stats()
        now = self._clock()
        # degraded tier reads: host-fetch batch rows are bucket rows here
        # — map them through lanes_np to lane slots
        if self.dqf.store.tiered:
            for c in self.dqf.store.tier_caches():
                for row in c.take_degraded_rows():
                    if row < n_live and self._lane_meta[
                            lanes_np[row]] is not None:
                        self._lane_degraded[lanes_np[row]] = True
        # per-query deadlines: force-expire overdue bucket rows so they
        # retire this tick with their current best-k
        expired = [j for j in range(n_live)
                   if active[j]
                   and self._lane_meta[lanes_np[j]] is not None
                   and self._lane_meta[lanes_np[j]][5] is not None
                   and now >= self._lane_meta[lanes_np[j]][5]]
        if expired:
            lanes_x = lanes_np[expired]
            self._state = self._state._replace(
                active=self._state.active.at[
                    jnp.asarray(lanes_x)].set(False))
            active[expired] = False
            for lane in lanes_x:
                self._lane_status[int(lane)] = QueryStatus.DEADLINE
        retiring = [j for j in range(n_live) if not active[j]
                    and self._lane_meta[lanes_np[j]] is not None]
        if not retiring:
            return None, now
        ids_h, dists_h, hops_h = self._fetch(ids_b, dists_b, hops_b)
        ids_h = ids_h[retiring]
        st = self.dqf.store
        if fl.cap != st.capacity:
            # the store grew after the dispatch: the tick's empty slots
            # hold the old sentinel id, now a row id
            ids_h = np.where(ids_h == fl.cap, st.capacity, ids_h)
        rl = [int(lanes_np[j]) for j in retiring]
        traces = [self._lane_trace[ln] for ln in rl]
        evals = term = None
        # sampled-lane stats transfer once per retiring tick, never per lane
        if any(tr is not None for tr in traces):
            dist_all, term_all = self._fetch(self._state.dist_count,
                                             self._state.terminated)
            evals, term = dist_all[rl], term_all[rl]
        # the per-lane half of the retirement that must precede the
        # refill; the answers' half is the later ``retire.lanes``
        with tl.span("retire.lanes"):
            ret = _Retiring(
                meta=[self._lane_meta[ln] for ln in rl],
                traces=traces, status=[self._lane_status[ln] for ln in rl],
                degraded=[self._lane_degraded[ln] for ln in rl],
                queries=self._queries[rl], ids=ids_h,
                dists=dists_h[retiring], hops=hops_h[retiring],
                dist_evals=evals, terminated=term)
            for ln in rl:
                self._lane_meta[ln] = None
                self._lane_trace[ln] = None
                self._lane_status[ln] = None
                self._lane_degraded[ln] = False
            self.pagepool.free(rl)
        return ret, now

    def _retire_first(self, ret: _Retiring) -> bool:
        """Whether ``ret``'s answers trip some tenant's Alg-2 trigger.

        The rebuild they cause must land before the refill, so that every
        admission sees the hot index it would in a serial loop.
        """
        reg = self.dqf.tenants
        for (name, gen), n in collections.Counter(
                (m[3], m[4]) for m in ret.meta).items():
            t = reg.get(name) if name in reg else None
            if t is not None and t.gen == gen and t.counter.due_after(n):
                return True
        return False

    def _retire(self, ret: _Retiring, now: float):
        """Results, stats and Alg-2 feedback of retiring lanes, their
        latencies ending at ``now`` (``tick.retire``)."""
        tl = self.timeline
        with tl.span("tick.retire", retiring=len(ret.meta)):
            with tl.span("retire.results"):
                batch_ids, batch_dists = retire_batch(
                    self.dqf.store, self.dqf._rerank_k, self.cfg.k,
                    ret.ids, ret.dists, ret.queries, timeline=tl,
                    stats=self.stats)
            with tl.span("retire.lanes"):
                for i, (rid, t_in, t_seed, tenant, gen, _,
                        seed_tick) in enumerate(ret.meta):
                    ids, dists = batch_ids[i], batch_dists[i]
                    hops = int(ret.hops[i])
                    degraded = ret.degraded[i]
                    status = ret.status[i] or (
                        QueryStatus.DEGRADED if degraded else QueryStatus.OK)
                    self._results[rid] = {"ids": ids, "dists": dists,
                                          "hops": hops, "tenant": tenant,
                                          "degraded": bool(degraded),
                                          "status": status.value}
                    self.stats.completed += 1
                    self.stats.note_terminal(status)
                    if status is QueryStatus.DEADLINE:
                        self.stats.deadline_hit += 1
                    if degraded:
                        self.stats.degraded += 1
                    self.stats.total_hops += hops
                    straggled = hops >= self.cfg.max_hops
                    if straggled:
                        self.stats.straggled += 1
                    service_ms = (now - t_seed) * 1e3
                    self.stats.latencies_ms.append((now - t_in) * 1e3)
                    if self.registry is not None:
                        self._h_service.observe(service_ms)
                        self._h_hops.observe(hops)
                    if tl.enabled:
                        tl.async_span("req.lane", rid, t_seed, now,
                                      tenant=tenant, status=status.value,
                                      ticks=self.stats.ticks - seed_tick)
                    tr = ret.traces[i]
                    if tr is not None:
                        tr.update(
                            queue_wait_ms=(t_seed - t_in) * 1e3,
                            service_ms=service_ms,
                            total_ms=(now - t_in) * 1e3,
                            full_hops=hops,
                            full_dist_evals=int(ret.dist_evals[i]),
                            terminated_early=bool(ret.terminated[i]),
                            straggled=straggled,
                            rerank_k=int(self.dqf._rerank_k),
                            ticks_in_flight=self.stats.ticks - seed_tick,
                            top_id=int(ids[0]))
                        self.traces.add(tr)
                    t = self.dqf.tenants.get(tenant) \
                        if tenant in self.dqf.tenants else None
                    if t is not None and t.gen == gen:
                        self.dqf.record(ids[None, :], tenant=tenant)
                        if t.counter.due:  # Alg 2's trigger
                            with tl.span("hot.rebuild", tenant=tenant) as sp:
                                hot = self.dqf.rebuild_hot(tenant=tenant)
                                if tl.enabled:
                                    sp.args.update(hot_rows=hot.size,
                                                   version=hot.version)
