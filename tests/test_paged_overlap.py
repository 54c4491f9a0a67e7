"""The paged engine retires a tick's lanes while the next tick runs.

A step waits for the tick in flight, reads its outputs, frees the lanes
it finished, refills them, dispatches the next tick and only then
harvests the answers (``tick.overlap``).  The reference here is the same
engine forced to retire before every refill, the serial order of the
loop before the overlap.  Contracts under test:

* the trace log's counters are the fetched tick's, not the next one's;
* every admission sees the hot-index version of the serial order, also
  at a tick whose answers trip an Alg-2 rebuild, and only such ticks
  (and ticks after which nothing runs) retire without the overlap;
* capacity growth and a drained compaction while a tick is in flight
  serve every request once, with the serial order's answers;
* a ``step()`` returns with the answers of the tick it waited for.
"""

import numpy as np
import pytest

from repro.core import DQF, DQFConfig, ZipfWorkload
from repro.obs import ObsConfig
from repro.serving.paged_engine import PagedWaveEngine

from tests.conftest import make_clustered
from tests.test_fused_hop import _built, _fused_cfg

TIMING_KEYS = {"queue_wait_ms", "service_ms", "total_ms"}


class _Serial(PagedWaveEngine):
    """Retires every tick's lanes before the refill and the dispatch."""

    def _retire_first(self, ret):
        return True


def _pair(dqf_a, dqf_b=None, **kw):
    """The overlapping engine and its serial twin (on ``dqf_b`` if given)."""
    kw = dict(dict(capacity=8, tick_hops=6, min_bucket=4), **kw)
    return (PagedWaveEngine(dqf_a, **kw),
            _Serial(dqf_a if dqf_b is None else dqf_b, **kw))


def _same_results(ea, eb, rids):
    for r in rids:
        a, b = ea._results[r], eb._results[r]
        np.testing.assert_array_equal(a["ids"], b["ids"])
        np.testing.assert_array_equal(a["dists"], b["dists"])
        assert (a["hops"], a["status"]) == (b["hops"], b["status"])


def test_trace_log_counters_are_the_fetched_ticks(built_dqf):
    dqf, wl = built_dqf
    q = wl.sample(40)
    logs = []
    engines = _pair(dqf, obs=ObsConfig(trace_rate=1.0))
    for eng in engines:
        for i in range(0, len(q), 8):
            eng.submit(q[i:i + 8])
            eng.step()
        eng.run_until_drained()
        logs.append(sorted(eng.traces, key=lambda t: t["rid"]))
    got, want = logs
    assert len(got) == len(want) == len(q)
    for a, b in zip(got, want):
        assert {k: v for k, v in a.items() if k not in TIMING_KEYS} == \
            {k: v for k, v in b.items() if k not in TIMING_KEYS}
        assert a["full_dist_evals"] > 0 and a["hot_dist_evals"] > 0
    assert engines[0].stats.overlapped_ticks > 0
    assert engines[1].stats.overlapped_ticks == 0
    assert engines[0].stats.ticks == engines[1].stats.ticks


def _rebuilding(x):
    """A DQF whose Alg-2 trigger fires every 40 queries."""
    cfg = DQFConfig(knn_k=12, out_degree=12, index_ratio=0.03, k=10,
                    hot_pool=16, full_pool=32, eval_gap=40, max_hops=120,
                    n_query_trigger=40)
    dqf = DQF(cfg).build(x)
    _, targets = ZipfWorkload(x, beta=1.2, sigma=0.05, seed=2).sample(
        400, with_targets=True)
    dqf.counter.record(targets)
    dqf.rebuild_hot()
    return dqf


class _Versions:
    """Mixin: notes the hot-index version every refill admits under."""

    def _refill(self):
        if self.queue and self.pagepool.free_lane_count:
            self.seen.append((self.stats.ticks, self.dqf.hot.version,
                              self.queue[0][0]))
        super()._refill()


class _OverlapV(_Versions, PagedWaveEngine):
    pass


class _SerialV(_Versions, _Serial):
    pass


def test_rebuild_due_ticks_keep_the_serial_order(small_data):
    wl = ZipfWorkload(small_data, beta=1.2, sigma=0.05, seed=3)
    q = wl.sample(150)
    kw = dict(capacity=8, tick_hops=6, min_bucket=4, obs=ObsConfig())
    runs = []
    for cls in (_OverlapV, _SerialV):
        dqf = _rebuilding(small_data)
        eng = cls(dqf, **kw)
        eng.seen = []
        rids = eng.submit(q)
        expect = rebuilt = 0
        while eng.queue or eng._any_live():
            v0, done0 = dqf.hot.version, eng.stats.completed
            eng.step()
            if dqf.hot.version != v0:
                rebuilt += 1
            elif eng.stats.completed > done0 and eng._any_live():
                expect += 1         # retired with the next tick running
        runs.append((eng, rids, rebuilt, expect))
    (ea, ra, rebuilt, expect), (eb, rb, rebuilt_b, _) = runs
    assert rebuilt == rebuilt_b >= 2
    assert ea.seen == eb.seen
    _same_results(ea, eb, ra)
    assert ea.stats.ticks == eb.stats.ticks
    assert ea.stats.overlapped_ticks == expect > 0
    assert ea.scrape()["engine_overlapped_ticks_total"] == float(expect)
    assert eb.stats.overlapped_ticks == 0


@pytest.fixture(scope="module")
def world_x():
    return make_clustered(n=900, d=16, clusters=12, seed=31)


@pytest.mark.parametrize("change", ["grow", "compact"])
def test_store_change_with_a_tick_in_flight(world_x, change):
    """Rows inserted past capacity, or deleted until a drained compaction
    falls due, between two steps: each request is answered once, as the
    serial order answers it."""
    x = world_x
    wl = ZipfWorkload(x, seed=37)
    q = wl.sample(30)
    dqfs = [_built(_fused_cfg(False), x) for _ in range(2)]
    engines = _pair(*dqfs, tick_hops=4, page_cols=128, prefetch=False,
                    compact_ratio=0.05, obs=ObsConfig(trace_rate=1.0))
    rng = np.random.default_rng(5)
    dead = rng.choice(dqfs[0].store.live_ids(), 90, replace=False)
    for eng, dqf in zip(engines, dqfs):
        rids = eng.submit(q)
        eng.step()
        eng.step()
        assert eng._inflight is not None
        cap0 = dqf.store.capacity
        if change == "grow":
            dqf.insert(make_clustered(n=64, d=16, clusters=12, seed=53))
            assert dqf.store.capacity > cap0
        else:
            dqf.delete(dqf.store.to_external(dead))
            assert dqf.store.should_compact(eng.compact_ratio)
        out = eng.run_until_drained()
        assert sorted(out["results"]) == sorted(rids)
        assert sorted(t["rid"] for t in eng.traces) == sorted(rids)
        assert eng.stats.completed == len(rids)
        assert all(out["results"][r]["status"] == "ok" for r in rids)
        assert eng._cap == dqf.store.capacity == eng.pagepool.n_ids
        assert eng.pagepool.live_count == 0 and eng._inflight is None
    ea, eb = engines
    assert ea.stats.compactions == eb.stats.compactions == \
        (change == "compact")
    assert ea.stats.overlapped_ticks > 0
    _same_results(ea, eb, rids)
    # the answers fed Alg 2's counter before any remap, as in the serial order
    np.testing.assert_array_equal(dqfs[0].counter.counts,
                                  dqfs[1].counter.counts)


def test_a_step_returns_with_its_ticks_answers(built_dqf):
    dqf, wl = built_dqf
    q = wl.sample(36)
    ea, eb = _pair(dqf)
    sent, i = [], 0
    while i < len(q) or ea.queue or ea._any_live():
        if i < len(q):
            sent += ea.submit(q[i:i + 6])
            eb.submit(q[i:i + 6])
            i += 6
        ea.step()
        eb.step()
        answered = set(ea._results)
        live = {m[0] for m in ea._lane_meta if m is not None}
        queued = {r[0] for r in ea.queue}
        assert answered | live | queued == set(sent)
        assert not (answered & live or answered & queued or live & queued)
        assert answered == set(eb._results)
    assert len(ea._results) == len(q)
