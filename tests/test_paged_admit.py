"""The paged engine's admission as one device program.

Contracts under test:

* :func:`repro.serving.paged.seed_admit` writes the same ``PagedState``,
  array by array and the scratch lane included, as the op-by-op chain it
  replaces (``hot_features`` → ``_seed_full_state`` → ``admit_wave``), for
  mixed tenants, padding lanes and a row tombstoned after the hot index
  was built;
* a refill at a bucket width the engine has not seen lowers exactly two
  programs, the hot phase and the admission, and each refill calls each
  of them once.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import DQF, DQFConfig, ZipfWorkload
from repro.core.dynamic_search import _seed_full_state, hot_phase_stacked
from repro.core.features import hot_features
from repro.obs import MetricsRegistry, ObsConfig
from repro.serving import paged as pg
from repro.serving.paged_engine import PagedWaveEngine

from tests.conftest import make_clustered

CAPACITY = 64
PAGE_COLS = 128
TENANTS = (("t0", 101), ("t1", 202))
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def tenant_dqf():
    x = make_clustered(n=900, d=16, clusters=12, seed=41)
    cfg = DQFConfig(knn_k=12, out_degree=12, index_ratio=0.05, k=10,
                    hot_pool=16, full_pool=32, eval_gap=40, max_hops=120,
                    n_query_trigger=100_000)
    dqf = DQF(cfg).build(x)
    for name, seed in TENANTS:
        q, tg = ZipfWorkload(x, seed=seed).sample(500, with_targets=True)
        dqf.warm(q, tg, tenant=name)
    dqf.fit_tree(ZipfWorkload(x, seed=7).sample(200), tenant="t0")
    return dqf, x


def _random_state(rng, n, d, n_pages):
    """A paged state whose every array holds noise, so that any entry the
    admission should leave alone, or should overwrite, shows if it does
    otherwise."""
    P1, L = CAPACITY + 1, 32
    return pg.PagedState(
        ids=jnp.asarray(rng.integers(0, n + 1, (P1, L)), jnp.int32),
        dists=jnp.asarray(rng.random((P1, L)), jnp.float32),
        expanded=jnp.asarray(rng.random((P1, L)) < 0.5),
        dist_count=jnp.asarray(rng.integers(0, 99, P1), jnp.int32),
        update_count=jnp.asarray(rng.integers(0, 99, P1), jnp.int32),
        hops=jnp.asarray(rng.integers(0, 99, P1), jnp.int32),
        terminated=jnp.asarray(rng.random(P1) < 0.5),
        active=jnp.asarray(rng.random(P1) < 0.5),
        evals=jnp.asarray(rng.integers(0, 9, P1), jnp.int32),
        queries=jnp.asarray(rng.random((P1, d)), jnp.float32),
        hot_first=jnp.asarray(rng.random(P1), jnp.float32),
        hot_ratio=jnp.asarray(rng.random(P1), jnp.float32),
        seen_pages=jnp.asarray(rng.random((n_pages, PAGE_COLS)) < 0.5))


@pytest.mark.parametrize("width", [4, 8, 16, 64])
def test_seed_admit_equals_op_by_op_admission(tenant_dqf, width):
    dqf, x = tenant_dqf
    cfg, st, reg = dqf.cfg, dqf.store, dqf.tenants
    rng = np.random.default_rng(width)
    m = width - 1 - width // 8                 # padding in every bucket
    pool = pg.PagePool(CAPACITY, st.capacity, page_cols=PAGE_COLS)
    # recycle lanes and pages, so the bucket's page rows are scattered
    pool.free(pool.alloc(CAPACITY)[rng.permutation(CAPACITY)[:m + 3]])
    lanes = pool.alloc(m)
    lanes_pad = np.full(width, CAPACITY, np.int32)
    lanes_pad[:m] = lanes
    pt_pad = pool.page_table[lanes_pad]

    slots = [reg.slot_of(name) for name, _ in TENANTS]
    tidx = np.zeros(width, np.int32)
    tidx[:m] = [slots[j % 2] for j in range(m)]
    qs = np.zeros((width, st.d), np.float32)
    for i, (name, seed) in enumerate(TENANTS):
        own = np.flatnonzero(tidx[:m] == slots[i])
        qs[own] = ZipfWorkload(x, seed=seed + width).sample(len(own))
    stk = reg.stacked(st)
    tidx_d, q_d = jnp.asarray(tidx), jnp.asarray(qs)
    hot_pool, _ = hot_phase_stacked(
        stk.x, stk.adj, stk.entries, stk.mask, tidx_d, q_d,
        pool_size=cfg.hot_pool, max_hops=cfg.max_hops, mode=cfg.hot_mode)

    # tombstone the best hot hit of the first lane after the hot index
    # was built: seeding must drop it
    gids = np.take_along_axis(np.asarray(stk.ids)[tidx],
                              np.asarray(hot_pool.ids), axis=1)
    dead = int(gids[0, 0])
    assert dead < st.n
    live_pad = dqf._dev["live_pad"].at[dead].set(False)

    ps = _random_state(rng, st.capacity, st.d, pool.n_pages)
    hf = hot_features(hot_pool, cfg.k)
    seeded = _seed_full_state(hot_pool, stk.ids[tidx_d], st.capacity,
                              cfg.full_pool, live_pad)
    admit_mask = np.zeros(width, bool)
    admit_mask[:m] = True
    want = pg.admit_wave(ps, jnp.asarray(lanes_pad), jnp.asarray(pt_pad),
                         seeded, q_d, hf.first, hf.first_div_kth,
                         jnp.asarray(admit_mask), page_cols=PAGE_COLS)

    lanes_pt = np.concatenate([lanes_pad[:, None], pt_pad], axis=1)
    got = pg.seed_admit(ps, hot_pool, stk.ids, tidx_d, q_d, live_pad,
                        jnp.asarray(lanes_pt), k=cfg.k,
                        pool_size=cfg.full_pool, page_cols=PAGE_COLS)

    assert dead not in np.asarray(got.ids)[lanes]
    for field in pg.PagedState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=field)


def test_refill_at_a_new_width_lowers_two_programs(tenant_dqf):
    dqf, x = tenant_dqf
    obs = ObsConfig(registry=MetricsRegistry(), sentinel=True,
                    sentinel_interval_s=0.0)
    eng = PagedWaveEngine(dqf, capacity=16, tick_hops=8, min_bucket=4,
                          obs=obs)
    cs = eng.sentinel.compile
    wl = ZipfWorkload(x, seed=5)

    def refill(n, tenant):
        calls = (cs.calls("paged_admit"), cs.calls("hot_phase_stacked"))
        eng.submit(wl.sample(n), tenant=tenant)
        eng._refill()
        assert cs.calls("paged_admit") == calls[0] + 1
        assert cs.calls("hot_phase_stacked") == calls[1] + 1

    eng.submit(wl.sample(3), tenant="t0")
    eng.step()                                 # width 4
    eng.run_until_drained()
    refill(3, "t1")                            # width 4 again
    eng.run_until_drained()

    jax.clear_caches()                         # no program left from before
    lowered = []

    def on_event(event, duration, **_):
        if event == LOWERING:
            lowered.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        refill(6, "t0")                        # width 8: never seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert len(lowered) == 2
    assert cs.executables("paged_admit") == 2
    eng.run_until_drained()
    assert len(eng._results) == 12
