"""The float32 rerank of retiring lanes: its span and its counter.

Contracts under test:

* with a quantized Full Index, ``retire.rerank`` nests inside
  ``retire.results``, once per retiring tick, and its ``rows`` arg is
  the retiring lanes times ``min(max(rerank_k, k), L)``;
* the ``rows`` args total ``EngineStats.rerank_rows``, in both engines
  (the fixed-wave engine has no ``retire.results``: its rerank nests in
  ``tick.retire``), and the registry exports the counter as
  ``engine_rerank_rows_total``;
* without a rerank (float32 Full Index, or ``rerank_k`` 0) there is no
  such span and the counter stays 0;
* ``retire_batch`` returns bit for bit what it returned before the
  rerank was timed, with the timeline on or off.
"""

import copy
import dataclasses
import types

import numpy as np
import pytest

from repro.core import DQF, DQFConfig, QuantConfig, ZipfWorkload
from repro.obs import ObsConfig, Timeline
from repro.serving.engine import EngineStats, WaveEngine, retire_batch
from repro.serving.paged_engine import PagedWaveEngine

RERANK_K = 24


@pytest.fixture(scope="module")
def sq8_dqf(small_data):
    cfg = DQFConfig(knn_k=12, out_degree=12, index_ratio=0.03, k=10,
                    hot_pool=16, full_pool=32, eval_gap=40, max_hops=120,
                    n_query_trigger=100_000,
                    quant=QuantConfig(mode="sq8", rerank_k=RERANK_K))
    dqf = DQF(cfg).build(small_data)
    wl = ZipfWorkload(small_data, beta=1.2, sigma=0.05, seed=1)
    dqf.warm(wl.sample(1000))
    dqf.fit_tree(wl.sample(200))
    return dqf, wl


def _at_depth(dqf, rerank_k):
    view = copy.copy(dqf)
    view.cfg = dataclasses.replace(
        dqf.cfg, quant=dataclasses.replace(dqf.cfg.quant, rerank_k=rerank_k))
    return view


def _x(eng, name):
    return [e for e in eng.timeline.events()
            if e["ph"] == "X" and e["name"] == name]


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def _serve(eng, queries):
    eng.submit(queries)
    eng.run_until_drained()


def _paged(dqf):
    return PagedWaveEngine(dqf, capacity=8, tick_hops=8, min_bucket=4,
                           obs=ObsConfig(timeline=True))


def test_rerank_nests_in_results_and_counts_its_rows(sq8_dqf):
    dqf, wl = sq8_dqf
    eng = _paged(dqf)
    _serve(eng, wl.sample(30))
    rr = min(max(RERANK_K, dqf.cfg.k), dqf.cfg.full_pool)
    results, retires = _x(eng, "retire.results"), _x(eng, "tick.retire")
    reranks = _x(eng, "retire.rerank")
    assert reranks and len(reranks) == len(results)
    for r in reranks:
        assert sum(_inside(r, p) for p in results) == 1, r
        (parent,) = [t for t in retires if _inside(r, t)]
        assert r["args"]["rows"] == parent["args"]["retiring"] * rr
    assert sum(r["args"]["rows"] for r in reranks) == \
        eng.stats.rerank_rows == 30 * rr
    assert eng.registry.scrape()["engine_rerank_rows_total"] == 30 * rr


def test_wave_engine_reranks_inside_its_retirement(sq8_dqf):
    dqf, wl = sq8_dqf
    eng = WaveEngine(dqf, wave_size=8, tick_hops=8,
                     obs=ObsConfig(timeline=True))
    _serve(eng, wl.sample(20))
    reranks = _x(eng, "retire.rerank")
    retires = _x(eng, "tick.retire")
    assert reranks and all(any(_inside(r, t) for t in retires)
                           for r in reranks)
    assert sum(r["args"]["rows"] for r in reranks) == \
        eng.stats.rerank_rows > 0
    assert eng.registry.scrape()["engine_rerank_rows_total"] == \
        eng.stats.rerank_rows


@pytest.mark.parametrize("which", ["float32", "rerank_k_0"])
def test_no_rerank_no_span_and_no_count(built_dqf, sq8_dqf, which):
    dqf, wl = built_dqf if which == "float32" else sq8_dqf
    if which == "rerank_k_0":
        dqf = _at_depth(dqf, 0)
    assert dqf._rerank_k == 0
    eng = _paged(dqf)
    _serve(eng, wl.sample(20))
    assert _x(eng, "retire.results") and not _x(eng, "retire.rerank")
    assert eng.stats.rerank_rows == 0
    assert eng.registry.scrape()["engine_rerank_rows_total"] == 0


def _retire_batch_before(store, rerank_k, k, pool_ids, pool_dists, queries):
    """``retire_batch`` as it was before its rerank was timed."""
    st = store
    m, L = pool_ids.shape
    keep = (pool_ids < st.n)
    keep &= st.alive[np.minimum(pool_ids, st.n - 1)]
    order = np.argsort(~keep, axis=1, kind="stable")
    rr = min(max(rerank_k, k), L)
    cand = np.take_along_axis(pool_ids, order, 1)[:, :rr]
    cd = np.take_along_axis(pool_dists, order, 1)[:, :rr]
    valid = np.take_along_axis(keep, order, 1)[:, :rr]
    if rerank_k:
        safe = np.where(valid, cand, 0)
        cd = np.sum((st.x[safe] - queries[:, None, :]) ** 2, axis=-1)
        cd[~valid] = np.inf
        top = np.argsort(cd, axis=1, kind="stable")[:, :k]
        ids = np.take_along_axis(cand, top, 1)
        dists = np.take_along_axis(cd, top, 1)
        valid = np.take_along_axis(valid, top, 1)
    else:
        ids, dists, valid = cand[:, :k], cd[:, :k], valid[:, :k]
    if ids.shape[1] < k:
        pad = k - ids.shape[1]
        ids = np.concatenate([ids, np.zeros((m, pad), ids.dtype)], axis=1)
        dists = np.concatenate(
            [dists, np.zeros((m, pad), dists.dtype)], axis=1)
        valid = np.concatenate([valid, np.zeros((m, pad), bool)], axis=1)
    ids = np.where(valid, ids, st.capacity).astype(np.int32)
    dists = np.where(valid, dists, np.inf).astype(np.float32)
    return ids, dists


@pytest.mark.parametrize("rerank_k, L", [(24, 32), (0, 32), (24, 6)])
def test_retire_batch_is_bit_for_bit_what_it_was(sq8_dqf, rerank_k, L):
    dqf, wl = sq8_dqf
    st = dqf.store
    rng = np.random.default_rng(5)
    m = 9
    # pools hold live rows, sentinel ids past the store and (through a
    # stand-in liveness mask) rows tombstoned in flight
    ids = rng.integers(0, st.n + 3, (m, L)).astype(np.int32)
    dists = np.sort(rng.random((m, L)).astype(np.float32), axis=1)
    queries = wl.sample(m)
    alive = st.alive.copy()
    alive[rng.integers(0, st.n, st.n // 10)] = False
    store = types.SimpleNamespace(n=st.n, x=st.x, capacity=st.capacity,
                                  alive=alive)
    want = _retire_batch_before(store, rerank_k, 10, ids, dists, queries)
    tl, stats = Timeline(enabled=True), EngineStats()
    for timeline in (Timeline(enabled=False), tl):
        got = retire_batch(store, rerank_k, 10, ids, dists, queries,
                           timeline=timeline, stats=stats)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
    spans = [e for e in tl.events() if e["name"] == "retire.rerank"]
    rows = m * min(max(rerank_k, 10), L) if rerank_k else 0
    assert stats.rerank_rows == 2 * rows
    assert [e["args"]["rows"] for e in spans] == ([rows] if rerank_k else [])
