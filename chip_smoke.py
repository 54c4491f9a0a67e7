#!/usr/bin/env python3
"""Chip smoke test: the DQF main path, end to end, on one TPU.

Runs what a user of the library calls, in one process:

1. generate a SIFT-class deployment from ``--seed``: 128-d float32 rows
   under squared L2, clustered on a 16-d manifold, at the shapes of
   ANN-Benchmarks SIFT1M with the row count cut to ``ROWS`` (the index
   build's SSG prune is a per-row host loop, ``core/ssg.py``);
2. ``DQF(DQFConfig()).build`` -- the full NSSG index, default config;
3. ``warm`` and ``fit_tree`` from the paper's Zipf workload (beta=1.2);
4. serve ``N_QUERIES`` Zipf queries through ``PagedWaveEngine(capacity=64)``;
5. check recall@10 against the exact top-10, computed on the device in
   query blocks at ``precision=HIGHEST``, and that every query came back
   with status ``ok``.

``--chips 4`` runs the sharded path instead, and nothing else: a 4-shard
``ShardedDQF`` on a mesh of four devices, served by the merged
``search()`` and by the paged ``ShardedEngine``, both checked bit for bit
against ``ShardedDQF.search_oracle``.

The last line of standard output is one JSON object naming the device.
It is printed only when every phase ran and every check held.  Without a
TPU, or outside a checkout of the repository, the script exits non-zero
and does not print it.

Run from the repository root::

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ROWS = 50_000            # SIFT1M has 1M; cut for the host-side index build
DIM = 128                # SIFT1M row width
K = 10                   # recall@10, ANN-Benchmarks' operating point
N_HISTORY = 4096         # queries that warm the hot index
N_TREE = 1024            # queries whose traces train the termination tree
N_QUERIES = 512          # queries served through the engine
CAPACITY = 64            # engine lane slots
RECALL_FLOOR = 0.90

# lowering to MLIR and the XLA compile happen once per executable; tracing
# is left out because nested jits record overlapping trace events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def make_deployment(n: int, seed: int, d: int = DIM, latent: int = 16,
                    clusters: int = 256) -> np.ndarray:
    """SIFT-class rows: clustered points on a random ``latent``-dim
    subspace of R^d plus isotropic noise, float32 (SIFT descriptors have a
    low intrinsic dimension, which is what graph indexes exploit)."""
    rng = np.random.default_rng(seed)
    centers = 2.0 * rng.standard_normal((clusters, latent))
    z = centers[rng.integers(0, clusters, n)] \
        + rng.standard_normal((n, latent))
    basis = rng.standard_normal((latent, d)) / np.sqrt(latent)
    x = z @ basis + 0.1 * rng.standard_normal((n, d))
    return np.ascontiguousarray(x, np.float32)


class Phases:
    """Runs the phases; prints each one's wall seconds and the seconds JAX
    spent lowering and compiling executables in it."""

    def __init__(self):
        import jax
        self.compiling = 0.0
        self.compile_s = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compiling += duration

    def run(self, name, fn, *args, **kwargs):
        c0, t0 = self.compiling, time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        self.compile_s[name] = self.compiling - c0
        print(f"phase {name}: {wall:.3f} s wall, "
              f"{self.compile_s[name]:.3f} s compile", flush=True)
        return out


def forbid_kernel_fallbacks() -> None:
    """Make any call into a Pallas kernel or a ``kernels/ref.py`` oracle
    raise: the default config's composed path must reach neither."""
    import inspect

    from jax.experimental import pallas
    from repro.kernels import ref

    def refuse(name):
        def fn(*_, **__):
            raise RuntimeError(f"{name} called on the chip path")
        return fn

    pallas.pallas_call = refuse("pallas_call")
    for name, obj in vars(ref).items():
        if inspect.isfunction(obj) and obj.__module__ == ref.__name__:
            setattr(ref, name, refuse(f"kernels.ref.{name}"))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"check failed: {what}")


def one_chip(x: np.ndarray, seed: int, phases: Phases) -> None:
    """Build, warm, fit the tree and serve through the paged engine."""
    from repro.core import DQF, DQFConfig, ZipfWorkload, ground_truth, \
        recall_at_k
    from repro.serving.paged_engine import PagedWaveEngine

    cfg = DQFConfig()
    dqf = phases.run("build", DQF(cfg).build, x)
    print(f"build seconds: {dqf.timings.full_build:.3f}")
    wl = ZipfWorkload(x, beta=1.2, seed=seed + 1)
    phases.run("warm", dqf.warm, wl.sample(N_HISTORY))
    phases.run("fit_tree", dqf.fit_tree, wl.sample(N_TREE))
    queries = wl.sample(N_QUERIES)

    def serve():
        eng = PagedWaveEngine(dqf, capacity=CAPACITY)
        rids = eng.submit(queries)
        return eng, rids, eng.run_until_drained()

    eng, rids, out = phases.run("serve", serve)
    print(f"first-call compile seconds: {phases.compile_s['serve']:.3f}")
    gt = phases.run("exact_top10", ground_truth, x, queries, K)

    res = [out["results"][r] for r in rids]
    statuses = {}
    for r in res:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    print(f"served queries by status: {json.dumps(statuses, sort_keys=True)}")
    ids = np.stack([r["ids"] for r in res])
    dists = np.stack([r["dists"] for r in res])
    recall = recall_at_k(ids, gt)
    print(f"recall@{K}: {recall:.4f} over {len(res)} queries "
          f"({eng.stats.ticks} ticks)")
    check(ids.shape == (N_QUERIES, K), f"result shape {ids.shape}")
    check(statuses == {"ok": N_QUERIES}, f"statuses {statuses}")
    check(bool(np.isfinite(dists).all()), "finite distances")
    check(bool(((ids >= 0) & (ids < x.shape[0])).all()), "ids in range")
    check(recall >= RECALL_FLOOR, f"recall@{K} {recall:.4f} >= "
          f"{RECALL_FLOOR}")


def four_chips(x: np.ndarray, seed: int, phases: Phases) -> None:
    """Sharded path on a 4-device mesh, bitwise against the oracle."""
    from repro.core import DQFConfig, ZipfWorkload
    from repro.sharding import ShardConfig, ShardedDQF, ShardedEngine

    sd = phases.run("build", ShardedDQF(
        DQFConfig(), ShardConfig(num_shards=4, use_mesh=True)).build, x)
    wl = ZipfWorkload(x, beta=1.2, seed=seed + 1)
    phases.run("warm", sd.warm, wl.sample(N_HISTORY))
    phases.run("fit_tree", sd.fit_tree, wl.sample(N_TREE))
    queries = wl.sample(N_QUERIES)
    oracle = phases.run("oracle", sd.search_oracle, queries)
    merged = phases.run("merged_search", sd.search, queries, record=False)

    def serve():
        eng = ShardedEngine(sd, wave_size=CAPACITY, paged=True)
        rids = eng.submit(queries)
        return rids, eng.run_until_drained()

    rids, out = phases.run("sharded_engine", serve)
    res = [out["results"][r] for r in rids]

    for name, arr in sorted(sd._sync_stacked().items()):
        shards = sorted((s.device.id, s.index[0].start)
                        for s in arr.addressable_shards)
        print(f"stacked {name} {tuple(arr.shape)}: device set "
              f"{sorted(d.id for d in arr.sharding.device_set)}, "
              f"(device, shard) {shards}")
        check(len({dev for dev, _ in shards}) == 4
              and sorted(s for _, s in shards) == [0, 1, 2, 3],
              f"{name} spread over four devices")

    o_ids, o_d = np.asarray(oracle.ids), np.asarray(oracle.dists)
    for label, ids, dists in (
            ("merged search()", merged.ids, merged.dists),
            ("paged ShardedEngine", np.stack([r["ids"] for r in res]),
             np.stack([r["dists"] for r in res]))):
        same_ids = np.array_equal(np.asarray(ids), o_ids)
        same_d = np.array_equal(np.asarray(dists), o_d)
        print(f"{label} vs search_oracle: ids bitwise equal {same_ids}, "
              f"dists bitwise equal {same_d}")
        check(same_ids and same_d, f"{label} bitwise equals the oracle")
    statuses = sorted({r["status"] for r in res})
    print(f"sharded engine statuses: {statuses}")
    check(statuses == ["ok"], f"statuses {statuses}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"no repro package under {SRC}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {devs[0].platform} devices",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    dev = devs[0]
    print(f"device: {dev.device_kind} x{len(devs)} ({dev.platform}), "
          f"jax {jax.__version__}, compile cache {cache}")
    forbid_kernel_fallbacks()

    phases = Phases()
    x = make_deployment(ROWS, args.seed)
    print(f"deployment: n={x.shape[0]} d={x.shape[1]} float32 squared-L2, "
          f"seed {args.seed} (SIFT1M shapes, rows cut from 1,000,000 to "
          f"{ROWS} for the host-side build)")
    if args.chips == 4:
        four_chips(x, args.seed, phases)
    else:
        one_chip(x, args.seed, phases)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
