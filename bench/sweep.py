#!/usr/bin/env python3
"""Find the knee of a cell's configuration once, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --fractions 0.4,0.6,0.8,1.0 [--fixture <path>]

One process, one set-up.  A closed loop of ``--seconds`` first measures
the rate the engine sustains; then an open loop with Poisson arrivals
runs at each fraction of that rate, and for each rate prints the
answered rate, p50 and p99 latency from the due time, and the backlog
left at the close.  The knee is the highest rate whose p99 holds steady
with no growing backlog; the paced traffic mix is set to about 0.8 of it.

``--fixture`` also writes a short recorded trace (device ops, host
annotations and engine spans of a few ticks) for the harness's tests.
Not part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fractions", default="0.4,0.6,0.7,0.8,0.9,1.0")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    import numpy as np
    from harness import cell as hc
    from harness import trace
    from harness.spec import enable_compile_cache, load_cell
    from repro.obs import ObsConfig
    from repro.serving.paged_engine import PagedWaveEngine

    enable_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu" and args.fixture is None:
        print("sweep: no TPU", file=sys.stderr)
        return 1
    cell = load_cell(args.workload, ROOT)
    dep = hc.deploy(cell, print)
    dqf = dep.dqf
    stream = dep.workload.stream(hc.seed_rng(args.seed, 1))
    obs = ObsConfig(timeline=True) if args.fixture else None
    eng = PagedWaveEngine(dqf, capacity=cell.config["engine"]["capacity"],
                          obs=obs)
    hc._warm_up(eng, dqf, stream, cell.traffic["warmup_sizes"])
    print(f"set-up {time.perf_counter() - T_START:.3f} s", flush=True)

    if args.fixture:
        tmp = os.path.join(os.environ.get("TMPDIR", "/tmp"), "sweep-trace")
        book = hc.Book(dqf.cfg.k, cell.config["dim"])
        book.submit(eng, stream, 128, 0.0)
        for _ in range(3):
            eng.step()
            book.collect(eng)
        eng.timeline.clear()
        jax.profiler.start_trace(tmp)
        sync = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace.SYNC):
            pass
        lo = time.perf_counter()
        for _ in range(4):
            eng.step()
            book.collect(eng)
        hi = time.perf_counter()
        jax.profiler.stop_trace()
        tr = trace.extract(hc._xplane(tmp))
        off = trace.clock_offset_ns(tr["host"], sync)
        lo_ns, hi_ns = lo * 1e9 + off, hi * 1e9 + off
        ops = [o for o in tr["ops"] if o[0] + o[1] > lo_ns and o[0] < hi_ns]
        with open(args.fixture, "w") as f:
            json.dump({"device_kind": jax.devices()[0].device_kind,
                       "sync_perf_s": sync, "lo_ns": lo_ns, "hi_ns": hi_ns,
                       "ops": ops, "host": tr["host"],
                       "timeline": eng.timeline.events()}, f)
        print(f"fixture: {len(ops)} device ops, "
              f"{len(eng.timeline.events())} spans", flush=True)
        hc._drain(eng, book, 60.0)

    cell.traffic = dict(cell.traffic, loop="closed",
                        outstanding=2 * cell.config["engine"]["capacity"])
    book, t0, t_end, _, _ = hc._window(cell, eng, dqf, stream,
                                       args.seconds, args.seed, False,
                                       False)
    hc._drain(eng, book, 60.0)
    done = book.done[:book.n]
    base = float((done <= t_end).sum()) / args.seconds
    print(json.dumps({"loop": "closed", "qps": base}), flush=True)
    for frac in (float(f) for f in args.fractions.split(",")):
        rate = frac * base
        cell.traffic = dict(cell.traffic, loop="open", rate=rate)
        book, t0, t_end, _, _ = hc._window(cell, eng, dqf, stream,
                                           args.seconds, args.seed, False,
                                           False)
        backlog = book.pending()
        hc._drain(eng, book, 60.0)
        n = book.n
        lat = (book.done[:n] - book.due[:n]) * 1e3
        half = book.due[:n] >= t0 + args.seconds / 2
        print(json.dumps({
            "loop": "open", "fraction": frac, "rate": rate, "due": n,
            "answered_per_s": float((book.done[:n] <= t_end).sum())
            / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p99_first_half_ms": float(np.percentile(lat[~half], 99)),
            "p99_second_half_ms": float(np.percentile(lat[half], 99)),
            "backlog_at_close": backlog}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
