"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (the harness contract).  Scale is
CPU-sized (DESIGN.md §0): the dataset is a clustered stand-in for SIFT1M
and the speedups are judged on distance computations (hardware-independent)
alongside this host's wall-clock QPS.

Run: ``PYTHONPATH=src python -m benchmarks.run [--only SUBSTR]``
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--json-dir", default=".",
                    help="where BENCH_<section>.json files are written")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import bench_paper as bp
    from . import bench_kernels as bk
    from . import bench_multitenant as bm
    from . import bench_obs as bo
    from . import bench_serving as bsv
    from . import bench_sharded as bsh
    from . import bench_tiering as bt

    benches = [
        ("construction", bp.bench_construction),      # Table 5
        ("index_size", bp.bench_index_size),          # Table 6
        ("ablation", bp.bench_ablation),              # Fig 3
        ("recall_qps", bp.bench_recall_qps),          # Fig 5
        ("effect_k", bp.bench_k),                     # Fig 6
        ("index_ratio", bp.bench_ir),                 # Fig 7
        ("depth_freq", bp.bench_depth_freq),          # Figs 8-9
        ("add_step", bp.bench_addstep),               # Fig 10
        ("hot_mode", bp.bench_hot_mode),              # DESIGN §2.1
        ("features", bp.bench_features),              # Table 2
        ("drift", bp.bench_drift),                    # claim 3
        ("churn", bp.bench_churn),                    # insert/delete/compact
        ("multitenant", bm.bench_multitenant),        # tenancy layer
        ("tiering", bt.bench_tiering),                # disk tier + cache
        ("kernels", bk.bench_kernels),                # Pallas layer
        ("quant", bk.bench_quant_scoring),            # compressed scan
        ("engine", bk.bench_engine),                  # serving layer
        ("serving", bsv.bench_serving),               # open-loop paged/fixed
        ("obs", bo.bench_obs),                        # flight recorder
        ("sharded", bsh.bench_sharded),               # scale-out layer
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# section {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,failed")
            traceback.print_exc()
    from .common import dump_metrics
    for p in dump_metrics(args.json_dir):
        print(f"# wrote {p}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
