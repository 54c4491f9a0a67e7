"""Mutable-index lifecycle invariants (repro.store + DQF.insert/delete/compact).

The contracts under test:

* search never returns a tombstoned id (any layer: batch search, baseline
  search, wave engine);
* external ids are stable across ``compact()`` — the same vector keeps the
  same handle while internal ids shift;
* a full insert → delete → compact → save → load roundtrip preserves search
  results exactly;
* after 10% churn on a quantized index, recall on live points stays within
  2 points of a from-scratch rebuild (ISSUE 2 acceptance bar).
"""

import numpy as np
import pytest

from repro.core import (DQF, DQFConfig, QuantConfig, ZipfWorkload,
                        ground_truth, recall_at_k)
from repro.core.hot_index import QueryCounter
from repro.store import VectorStore
from tests._hypothesis_compat import given, settings, st
from tests.conftest import make_clustered


def _small_cfg(**over):
    base = dict(knn_k=10, out_degree=10, index_ratio=0.03, k=10,
                hot_pool=16, full_pool=32, max_hops=100,
                n_query_trigger=10 ** 6)
    base.update(over)
    return DQFConfig(**base)


@pytest.fixture(scope="module")
def churn_world():
    """A built+warmed quantized DQF over clustered data, plus its workload."""
    x = make_clustered(n=1200, d=16, clusters=16, seed=11)
    cfg = _small_cfg(quant=QuantConfig(mode="sq8", rerank_k=32))
    dqf = DQF(cfg).build(x)
    wl = ZipfWorkload(x, seed=12)
    _, t = wl.sample(3000, with_targets=True)
    dqf.counter.record(t)
    dqf.rebuild_hot()
    return dqf, wl, x


# ----------------------------------------------------------------- VectorStore
def test_store_basic_lifecycle():
    x = np.arange(20, dtype=np.float32).reshape(10, 2)
    s = VectorStore(x)
    assert s.n == 10 and s.live_count == 10 and s.capacity == 10
    ext = s.add(np.full((3, 2), 7.0, np.float32))
    np.testing.assert_array_equal(ext, [10, 11, 12])
    assert s.n == 13 and s.capacity == 16          # geometric growth
    dead = s.mark_dead([0, 11])
    np.testing.assert_array_equal(dead, [0, 11])
    assert s.live_count == 11
    with pytest.raises(ValueError):
        s.mark_dead([0])                           # double delete
    res = s.compact()
    assert res.dropped == 2 and s.n == 11
    assert s.capacity == 16                        # capacity is sticky
    # external ids survive, internal ids shifted
    assert int(s.to_internal(np.asarray([10]))[0]) == 9
    np.testing.assert_array_equal(s.x[s.to_internal(np.asarray([12]))[0]],
                                  [7.0, 7.0])


def test_store_rejects_duplicate_ext_ids():
    s = VectorStore(np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError):
        s.add(np.zeros((1, 2), np.float32), ext_ids=np.asarray([2]))


def test_store_encodes_on_insert():
    from repro.quant import build_quantizer, sq_encode
    x = make_clustered(n=200, d=8, seed=3)
    q = build_quantizer(x, QuantConfig(mode="sq8"))
    s = VectorStore(x, quant=q)
    new = make_clustered(n=5, d=8, seed=4)
    s.add(new)
    assert s.quant.codes.shape[0] == 205
    np.testing.assert_array_equal(s.quant.codes[200:],
                                  sq_encode(new, s.quant.sq))


# ---------------------------------------------------------------- QueryCounter
def test_counter_counts_queries_not_ids():
    c = QueryCounter(n=100, trigger=10)
    c.record(np.arange(8).reshape(2, 4))       # 2 queries, 8 result ids
    assert c.since_rebuild == 2
    assert c.counts[:8].sum() == 8
    c.record(np.arange(9))                     # 9 single-target queries
    assert c.since_rebuild == 11
    assert c.due


def test_counter_grow_and_remap_preserve_mass():
    c = QueryCounter(n=6, trigger=100)
    c.record(np.asarray([[0, 1], [1, 5]]))
    c.grow(8)
    assert c.counts.shape == (8,) and c.counts[6:].sum() == 0
    remap = np.asarray([0, -1, 1, 2, 3, 4, 5, 6])     # drop old row 1
    c.remap(remap)
    assert c.n == 7
    assert c.counts[0] == 1.0 and c.counts[4] == 1.0  # old id 5 → new id 4
    assert c.counts.sum() == 2.0                      # row 1's mass dropped


def test_counter_never_promotes_dead():
    c = QueryCounter(n=50, trigger=100)
    c.record(np.tile(np.arange(10), (30, 1)))   # rows 0-9 are scorching hot
    alive = np.ones(50, bool)
    alive[:5] = False
    top = c.top(8, alive=alive)
    assert not np.isin(top, np.arange(5)).any()
    assert np.isin(np.arange(5, 10), top).all()


# ------------------------------------------------------------ DQF churn safety
def test_insert_is_searchable(churn_world):
    dqf, wl, x = churn_world
    rng = np.random.default_rng(0)
    new_rows = x[rng.choice(x.shape[0], 40)] \
        + 0.02 * rng.standard_normal((40, x.shape[1])).astype(np.float32)
    n_before = dqf.store.n
    ext = dqf.insert(new_rows)
    assert ext.shape == (40,)
    res = dqf.search(np.ascontiguousarray(new_rows[:16]), record=False)
    ids = np.asarray(res.ids)
    hit = (ids == np.arange(n_before, n_before + 16)[:, None]).any(axis=1)
    assert hit.mean() >= 0.8          # new rows reachable via local re-link


@pytest.fixture(scope="module")
def tombstone_world():
    """Dedicated world for the destructive property test: hypothesis re-runs
    the body many times (examples + shrinking), and each run deletes rows —
    sharing ``churn_world`` would couple later tests to the example count.
    (Module scope rather than function scope: hypothesis's health check
    rejects function-scoped fixtures under ``@given``.)"""
    x = make_clustered(n=1000, d=16, clusters=16, seed=41)
    cfg = _small_cfg(quant=QuantConfig(mode="sq8", rerank_k=32))
    dqf = DQF(cfg).build(x)
    wl = ZipfWorkload(x, seed=42)
    _, t = wl.sample(2500, with_targets=True)
    dqf.counter.record(t)
    dqf.rebuild_hot()
    return dqf, wl, x


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_search_never_returns_tombstoned(tombstone_world, seed):
    dqf, wl, x = tombstone_world
    rng = np.random.default_rng(seed)
    live = dqf.store.live_ids()
    victims = rng.choice(live, size=max(1, live.size // 20), replace=False)
    dqf.delete(dqf.store.to_external(victims))
    q = wl.sample(64)
    for res in (dqf.search(q, record=False), dqf.search_baseline(q),
                dqf.search_dual_beam(q)):
        ids = np.asarray(res.ids)
        real = ids[(ids >= 0) & (ids < dqf.store.n)]
        assert dqf.store.alive[real].all(), "tombstoned id returned"


def test_external_ids_stable_across_compact(churn_world):
    dqf, wl, x = churn_world
    live = dqf.store.live_ids()
    probe = live[:: max(1, live.size // 50)]
    ext = dqf.store.to_external(probe)
    vecs = dqf.store.x[probe].copy()
    out = dqf.compact()
    assert out["dropped"] >= 0
    back = dqf.store.to_internal(ext)
    np.testing.assert_array_equal(dqf.store.x[back], vecs)
    # search results round-trip through external ids coherently
    q = wl.sample(32)
    ids = np.asarray(dqf.search(q, record=False).ids)
    ext_ids = dqf.to_external(ids)
    valid = ext_ids >= 0
    np.testing.assert_array_equal(
        dqf.store.to_internal(ext_ids[valid]), ids[valid])


def test_churn_recall_matches_rebuild():
    """ISSUE 2 acceptance: 10% churn ≈ from-scratch rebuild (±2 recall pts),
    with quantization enabled end to end."""
    x = make_clustered(n=1200, d=16, clusters=16, seed=31)
    cfg = _small_cfg(quant=QuantConfig(mode="sq8", rerank_k=32))
    dqf = DQF(cfg).build(x)
    wl = ZipfWorkload(x, seed=32)
    _, t = wl.sample(3000, with_targets=True)
    dqf.counter.record(t)
    dqf.rebuild_hot()

    rng = np.random.default_rng(33)
    n = x.shape[0]
    n_churn = n // 10
    victims = rng.choice(n, size=n_churn, replace=False)
    new_rows = make_clustered(n=n_churn, d=16, clusters=16, seed=34)
    dqf.insert(new_rows)
    dqf.delete(dqf.store.to_external(victims))
    dqf.compact()

    live_x = dqf.store.x
    q = wl.sample(128)
    gt = ground_truth(live_x, q, cfg.k)
    rec_churned = recall_at_k(np.asarray(dqf.search(q, record=False).ids), gt)

    fresh = DQF(cfg).build(live_x)
    # seed the fresh counter with the same true-target heat, remapped via
    # the churned store's stable external ids (fresh shares its row order)
    _, t2 = wl.sample(3000, with_targets=True)
    surviving = np.isin(t2, dqf.store.ext_ids)
    fresh.counter.record(dqf.store.to_internal(t2[surviving]))
    fresh.rebuild_hot()
    rec_fresh = recall_at_k(np.asarray(fresh.search(q, record=False).ids), gt)

    assert rec_churned >= rec_fresh - 0.02, (rec_churned, rec_fresh)


def test_insert_delete_compact_save_load_roundtrip(tmp_path, churn_world):
    dqf, wl, x = churn_world
    rng = np.random.default_rng(5)
    dqf.insert(make_clustered(n=30, d=16, clusters=16, seed=6))
    live = dqf.store.live_ids()
    dqf.delete(dqf.store.to_external(
        rng.choice(live, size=25, replace=False)))
    dqf.compact()
    q = wl.sample(48)
    p = str(tmp_path / "churned.npz")
    dqf.save(p)
    loaded = DQF.load(p, dqf.cfg)
    a = dqf.search(q, record=False)
    b = loaded.search(q, record=False)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(dqf.store.ext_ids, loaded.store.ext_ids)
    np.testing.assert_array_equal(dqf.store.alive, loaded.store.alive)
    assert loaded.store.capacity == dqf.store.capacity
    assert loaded.counter.since_rebuild == dqf.counter.since_rebuild


def test_engine_serves_across_churn(churn_world):
    from repro.serving.engine import WaveEngine

    dqf, wl, x = churn_world
    eng = WaveEngine(dqf, wave_size=16, tick_hops=8)
    r0 = eng.submit(wl.sample(24))
    eng.run_until_drained()
    dqf.insert(make_clustered(n=20, d=16, clusters=16, seed=7))
    live = dqf.store.live_ids()
    rng = np.random.default_rng(8)
    dqf.delete(dqf.store.to_external(rng.choice(live, 20, replace=False)))
    r1 = eng.submit(wl.sample(24))
    out = eng.run_until_drained()
    assert all(r in out["results"] for r in r0 + r1)
    for rid in r1:                       # post-delete requests: no dead ids
        ids = out["results"][rid]["ids"]
        ids = ids[(ids >= 0) & (ids < dqf.store.n)]
        assert dqf.store.alive[ids].all()


def test_rebuild_same_instance_serves_new_data():
    """A second build() on the same DQF must drop every cached device table
    (the fresh store's epoch matches the stale cache's epoch)."""
    x1 = make_clustered(n=300, d=8, seed=51)
    x2 = make_clustered(n=300, d=8, seed=52) + 100.0
    dqf = DQF(_small_cfg(knn_k=8, out_degree=8)).build(x1)
    assert dqf.hot is None             # old hot referenced the old store
    dqf.build(x2)
    res = dqf.search_baseline(np.ascontiguousarray(x2[:8]))
    assert np.allclose(np.asarray(res.dists)[:, 0], 0.0, atol=1e-3)


def test_delete_refuses_to_empty_index(churn_world):
    dqf, wl, x = churn_world
    live_ext = dqf.store.to_external(dqf.store.live_ids())
    before_alive = dqf.store.alive.copy()
    with pytest.raises(ValueError, match="rebuild instead"):
        dqf.delete(live_ext)
    # refused *before* mutating: nothing was tombstoned
    np.testing.assert_array_equal(dqf.store.alive, before_alive)


def test_engine_refuses_compact_in_flight(churn_world):
    from repro.serving.engine import WaveEngine

    dqf, wl, x = churn_world
    eng = WaveEngine(dqf, wave_size=8, tick_hops=2)
    eng.submit(wl.sample(16))
    eng._init_wave()
    dqf.compact()
    with pytest.raises(RuntimeError, match="drain"):
        eng._tick()


@pytest.mark.parametrize("tick_hops", [2, 100], ids=["some_live",
                                                     "all_retire"])
def test_paged_engine_refuses_compact_in_flight(tick_hops):
    """A compaction between steps, with a tick in flight, is refused
    before that tick's lanes are freed: also when every lane of it
    finishes there (``tick_hops`` = ``max_hops``), and without losing an
    answer."""
    from repro.serving.paged_engine import PagedWaveEngine

    x = make_clustered(n=600, d=16, clusters=8, seed=13)
    dqf = DQF(_small_cfg()).build(x)
    wl = ZipfWorkload(x, seed=14)
    _, t = wl.sample(1000, with_targets=True)
    dqf.counter.record(t)
    dqf.rebuild_hot()
    eng = PagedWaveEngine(dqf, capacity=8, tick_hops=tick_hops,
                          min_bucket=4)
    eng.submit(wl.sample(16))
    eng.step()
    assert eng._inflight is not None and eng.pagepool.live_count == 8
    dqf.delete(dqf.store.to_external(np.arange(0, 60, 3)))
    dqf.compact()
    ticks, answered = eng.stats.ticks, dict(eng._results)
    with pytest.raises(RuntimeError, match="drain"):
        eng.step()
    assert eng._inflight is not None and eng.pagepool.live_count == 8
    assert eng.stats.ticks == ticks and set(eng._results) == set(answered)
    assert len(answered) + 8 + len(eng.queue) == 16
