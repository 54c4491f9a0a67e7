#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, and spreads, on the chip.

    python3 bench/proof.py --workloads sift128-zipf-sat,sift128-zipf-paced \\
        --seeds 11,12,13 --seconds 20 [--controls 3] [--traced 1]

One process.  A configuration's deployment comes from its own
``data_seed``, not from ``--seed``, so it is built once and each run
gets a fresh copy of it.  For each cell and seed: one run as
``bench/run.py`` makes it (its set-up time aside); on the first
``--controls`` seeds also the controls, the reference's top-k computed
in the precision just below the configuration's (``high``, three bf16
passes, and ``bf16``, one) and judged in the program's place by the same
comparison; on the last ``--traced`` seeds a traced run instead.  Prints
one JSON line per run.  A sound limit lies above every program reading
and below every control reading.  Not part of a benchmark run.
"""

import time

import argparse
import copy
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _fresh(dqf):
    """A deep copy of the index that shares the original's metrics
    registry and instruments, whose locks cannot be copied."""
    reg = dqf.registry
    memo = {id(reg): reg}
    memo.update((id(m), m) for m in reg._metrics.values())
    return copy.deepcopy(dqf, memo)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--traced", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    from harness.cell import deploy, run
    from harness.spec import enable_compile_cache, load_cell

    enable_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("proof: no TPU", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    built = {}
    for name in args.workloads.split(","):
        cell = load_cell(name, ROOT)
        key = cell.config["name"]
        if key not in built:
            built[key] = deploy(cell, print)
        for i, seed in enumerate(seeds):
            dep = built[key]
            fresh = dataclasses.replace(dep, dqf=_fresh(dep.dqf))
            traced = i >= len(seeds) - args.traced
            controls = ("high", "bf16") if i < args.controls else ()
            t = time.perf_counter()
            out = run(cell, seed, args.seconds, traced, t,
                      controls=controls, deployment=fresh)
            print(json.dumps({
                "cell": name, "seed": seed, "traced": traced,
                "correct": out["correct"], "attempted": out["attempted"],
                "wall_s": time.perf_counter() - t,
                "program": {k: v["value"] for k, v in out["checks"].items()},
                "controls": {p: {k: v["value"] for k, v in c.items()}
                             for p, c in out.get("controls", {}).items()},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "device": out["device"], "window": out["window"]}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
