"""A 960-d int8 (``sq8``) Full Index served by the paged engine, judged
against a plain float64 brute force.

The rows follow the chip benchmark's GIST-960 deployment at a CPU size:
Gaussian clusters on a random 16-dim subspace of R^960 plus noise.  The
reference is a direct numpy float64 sum of squared differences over every
row; it uses nothing of the program's search.  The engine's full phase
scores int8 codes, so its pool distances are approximate; the retirement
re-scores each lane's best ``rerank_k`` rows exactly in float32, and only
that makes the served distances exact.  With ``rerank_k`` 0 the same
index serves the code distances, and the distance check has to fail.
"""

import copy
import dataclasses

import numpy as np
import pytest

from repro.core import DQF, DQFConfig, QuantConfig, ZipfWorkload
from repro.serving.paged_engine import PagedWaveEngine

N, D, LATENT, K = 2000, 960, 16, 10
# float32 sums of 960 squared differences against float64 ones differ by
# ~1e-7 relative (1.4e-7 read); int8 code distances by ~5e-2 (4.6e-2
# read).  1e-4 leaves 700x room above the first and lies 460x below the
# second: the benchmark configuration's own dist_gap limit.
DIST_RTOL = 1e-4
# the benchmark configuration's guarantee.recall_floor (0.98 read here)
RECALL_FLOOR = 0.90


def _rows(rng):
    centers = 2.0 * rng.standard_normal((256, LATENT))
    z = centers[rng.integers(0, 256, N)] + rng.standard_normal((N, LATENT))
    basis = rng.standard_normal((LATENT, D)) / np.sqrt(LATENT)
    x = z @ basis + 0.1 * rng.standard_normal((N, D))
    return np.ascontiguousarray(x, np.float32)


def _exact_sq_dists(x, q):
    """(n_queries, n_rows) squared L2 distances, summed in float64."""
    x64 = x.astype(np.float64)
    return np.stack([((x64 - qi) ** 2).sum(axis=1)
                     for qi in q.astype(np.float64)])


@pytest.fixture(scope="module")
def served():
    x = _rows(np.random.default_rng(0))
    cfg = DQFConfig(full_pool=192, k=K, n_query_trigger=10**9,
                    quant=QuantConfig(mode="sq8", rerank_k=64))
    dqf = DQF(cfg).build(x)
    wl = ZipfWorkload(x, beta=1.2, sigma=0.05, seed=3)
    dqf.warm(wl.sample(512))
    dqf.fit_tree(wl.sample(256))
    q = wl.sample(256)
    return dqf, q, _exact_sq_dists(x, q)


def _serve(dqf, q):
    eng = PagedWaveEngine(dqf, capacity=16)
    rids = eng.submit(q)
    res = eng.run_until_drained()["results"]
    assert all(res[r]["status"] == "ok" for r in rids)
    return (np.stack([res[r]["ids"] for r in rids]),
            np.stack([res[r]["dists"] for r in rids]))


@pytest.mark.parametrize("rerank_k, exact", [(64, True), (0, False)],
                         ids=["rerank64", "codes_served"])
def test_sq8_serving_against_a_float64_brute_force(served, rerank_k, exact):
    dqf, q, d64 = served
    view = copy.copy(dqf)      # the same index, served at another depth
    view.cfg = dataclasses.replace(
        dqf.cfg, quant=dataclasses.replace(dqf.cfg.quant, rerank_k=rerank_k))
    assert view._rerank_k == rerank_k
    ids, dists = _serve(view, q)
    assert ids.shape == (len(q), K) and (ids < N).all()
    served_exact = np.take_along_axis(d64, ids, axis=1)
    gap = np.max(np.abs(dists - served_exact) / served_exact)
    assert (gap <= DIST_RTOL) == exact, gap
    if exact:
        truth = np.argsort(d64, axis=1, kind="stable")[:, :K]
        recall = np.mean([len(set(a) & set(b)) / K
                          for a, b in zip(ids.tolist(), truth.tolist())])
        assert recall >= RECALL_FLOOR, recall
