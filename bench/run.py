#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is looked up in ``BENCHMARK.json``;
its configuration and traffic mix are files under ``bench/``.  Set-up
builds the deployment from ``--seed`` and warms every program the window
will call; the window then drives ``PagedWaveEngine`` for ``--seconds``;
afterwards every answer due in the window is compared with an exact
top-k reference.  The last line of standard output is the result as one
JSON object; the numbers compared, each beside its limit, are the last
lines of standard error.  ``--trace 1`` profiles a stretch of the window
and reports the cell's per-layer metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    from harness.spec import load_cell
    try:
        cell = load_cell(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no system under test at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    from harness.spec import enable_compile_cache
    enable_compile_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    _log(f"device: {devs[0].device_kind} x{len(devs)}, jax "
         f"{jax.__version__}, cell {cell.name}, seed {args.seed}")

    from harness.cell import run
    out = run(cell, args.seed, args.seconds, bool(args.trace), T_START,
              log=_log)
    _log(json.dumps({"window": out.pop("window")}))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
