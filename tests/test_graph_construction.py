"""KNNG + SSG construction tests (paper §4.2.1, Algorithm 1)."""

import numpy as np

from repro.core.knng import build_knng, exact_knn, nn_descent
from repro.core.recall import ground_truth
from repro.core.ssg import (SSGParams, build_ssg, ensure_connected, medoid,
                            ssg_prune)
from tests.conftest import make_clustered


def brute_knn(x, k):
    d = np.sum((x[:, None, :] - x[None, :, :]) ** 2, -1)
    np.fill_diagonal(d, np.inf)
    return np.argsort(d, 1)[:, :k]


def test_exact_knn_matches_bruteforce():
    x = make_clustered(n=300, d=8, seed=3)
    ids, _ = exact_knn(x, 5)
    want = brute_knn(x, 5)
    # compare as sets per row (ties can permute)
    for a, b in zip(ids, want):
        assert set(a.tolist()) == set(b.tolist())


def test_ground_truth_matches_float64_topk():
    """The exact reference ranks by float64 distances: same ids, same
    order, on clustered rows at SIFT width (d=128)."""
    rng = np.random.default_rng(6)
    x = make_clustered(n=700, d=128, seed=6)
    q = x[rng.choice(700, 40, replace=False)] \
        + 0.1 * rng.standard_normal((40, 128)).astype(np.float32)
    x64, q64 = x.astype(np.float64), q.astype(np.float64)
    d = np.sum((q64[:, None, :] - x64[None, :, :]) ** 2, -1)
    want = np.argsort(d, 1, kind="stable")[:, :10]
    np.testing.assert_array_equal(ground_truth(x, q, 10, chunk=16), want)


def test_nn_descent_high_recall():
    x = make_clustered(n=800, d=16, seed=4)
    approx = nn_descent(x, 10, rounds=10, seed=0)
    exact = brute_knn(x, 10)
    hits = sum(np.intersect1d(a, e).size for a, e in zip(approx, exact))
    assert hits / (800 * 10) > 0.90


def test_ssg_degree_bound_and_no_self_loops():
    x = make_clustered(n=400, d=12, seed=5)
    knng = build_knng(x, 10)
    adj = ssg_prune(x, knng, SSGParams(knn_k=10, out_degree=8))
    n = x.shape[0]
    assert adj.shape == (n, 8)
    valid = adj < n
    assert valid.any(axis=1).all()               # every node keeps an edge
    rows = np.arange(n)[:, None]
    assert not ((adj == rows) & valid).any()     # no self loops


def test_ssg_angle_property():
    """Kept out-edges of a node subtend pairwise angles >= alpha."""
    x = make_clustered(n=300, d=8, seed=6)
    knng = build_knng(x, 12)
    alpha = 60.0
    adj = ssg_prune(x, knng, SSGParams(knn_k=12, out_degree=10,
                                       alpha_deg=alpha))
    cos_a = np.cos(np.deg2rad(alpha))
    n = x.shape[0]
    for p in range(0, n, 17):
        nbrs = adj[p][adj[p] < n]
        if nbrs.size < 2:
            continue
        v = x[nbrs] - x[p]
        v = v / np.linalg.norm(v, axis=1, keepdims=True)
        cos = v @ v.T
        off = cos[~np.eye(nbrs.size, dtype=bool)]
        assert (off <= cos_a + 1e-5).all()


def test_ensure_connected_reaches_everything():
    x = make_clustered(n=250, d=6, clusters=12, spread=20.0, seed=7)
    knng = build_knng(x, 6)
    adj = ssg_prune(x, knng, SSGParams(knn_k=6, out_degree=6))
    entry = medoid(x)
    adj = ensure_connected(x, adj, entry)
    n = x.shape[0]
    seen = np.zeros(n, bool)
    stack = [entry]
    seen[entry] = True
    while stack:
        u = stack.pop()
        for v in adj[u]:
            if v < n and not seen[v]:
                seen[v] = True
                stack.append(int(v))
    assert seen.all()


def test_build_ssg_end_to_end():
    x = make_clustered(n=500, d=10, seed=8)
    idx = build_ssg(x, SSGParams(knn_k=10, out_degree=10), n_entry=4)
    assert idx.n == 500
    assert idx.adj.dtype == np.int32
    assert idx.entries.size >= 1
    assert (idx.entries < 500).all()
    hist = idx.degree_histogram
    assert hist.sum() == 500
