"""One run of one cell: set-up, warm-up, the measured window, the check.

The system under test is ``repro``: ``DQF.build`` -> ``warm`` ->
``fit_tree`` -> ``PagedWaveEngine``, driven through ``submit`` and
``step``.  Everything else here (rows, queries, arrivals, the reference,
the reduction of traces) belongs to the benchmark.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import tempfile
import time

import numpy as np

from . import reference, roofline, trace
from .spec import Cell, dqf_config, load_reader, uses_trace_log
from .workload import ZipfWorkload, make_rows, seed_rng

__all__ = ["run", "deploy", "Deployment", "Book"]

clock = time.perf_counter
GRACE_S = 60.0           # how long an answer due in the window is awaited
TRACE_AT = (0.35, 0.75)  # traced stretch, as shares of the window
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class _Compiles:
    """Counts lowerings (one per program compiled or fetched from the
    persistent cache) and the seconds spent lowering and compiling."""

    _inst = None

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENTS[0]:
            self.count += 1
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    @classmethod
    def get(cls) -> "_Compiles":
        if cls._inst is None:
            cls._inst = cls()
        return cls._inst


class Book:
    """Every request of the window: due, submitted and answered times,
    what it asked and what came back."""

    def __init__(self, k: int, d: int, cap: int = 1 << 15):
        self.k, self.d = k, d
        self.n = 0
        self.answered = 0
        self._rid0 = None
        self._alloc(cap)

    def _alloc(self, cap):
        old = getattr(self, "due", None)
        fields = dict(due=np.float64, sub=np.float64, done=np.float64,
                      target=np.int64, ok=bool)
        for name, dt in fields.items():
            a = np.full(cap, np.inf if dt is np.float64 else 0, dt)
            if old is not None:
                a[:self.n] = getattr(self, name)[:self.n]
            setattr(self, name, a)
        for name, dt, w in (("ids", np.int64, self.k),
                            ("dists", np.float32, self.k),
                            ("q", np.float32, self.d)):
            a = np.zeros((cap, w), dt)
            if old is not None:
                a[:self.n] = getattr(self, name)[:self.n]
            setattr(self, name, a)

    def pending(self) -> int:
        return self.n - self.answered

    def submit(self, eng, stream, m: int, due) -> None:
        if self.n + m > self.due.shape[0]:
            self._alloc(2 * (self.n + m))
        q, t = stream.take(m)
        rids = eng.submit(q)
        if self._rid0 is None:
            self._rid0 = rids[0]
        s = slice(self.n, self.n + m)
        assert rids[0] - self._rid0 == self.n
        self.due[s] = due
        self.sub[s] = clock()
        self.target[s], self.q[s] = t, q
        self.n += m

    def collect(self, eng) -> int:
        """Take the answers the engine holds; stamps them now."""
        res = eng._results
        if not res:
            return 0
        items = list(res.items())
        res.clear()
        now = clock()
        for rid, r in items:
            i = rid - self._rid0
            self.done[i] = now
            self.ids[i] = r["ids"]
            self.dists[i] = r["dists"]
            self.ok[i] = r["status"] == "ok"
        self.answered += len(items)
        return len(items)

    def rid(self, i: int) -> int:
        return self._rid0 + i


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets."""

    cell: Cell
    degree: int
    peaks: dict
    book: Book
    window: tuple          # (t0, t_end), perf_counter seconds
    traces: dict           # rid -> TraceLog entry
    timeline: list         # repro.obs.Timeline events (perf_counter us)
    ops: list              # device ops in the traced stretch (trace ns)
    stretch: tuple         # (lo, hi) of the traced stretch, trace ns
    work: dict             # dist evals and hops done in the stretch


@dataclasses.dataclass
class Deployment:
    """The configuration's rows and the index served over them, built,
    warmed on the deployment's history and with its tree fitted."""

    rows: np.ndarray
    workload: ZipfWorkload
    dqf: object
    build_s: float


def deploy(cell: Cell, log) -> Deployment:
    """Set-up before any traffic of the window: everything here comes
    from the configuration's ``data_seed``, none of it from ``--seed``.
    The program's config is built first, so a bad ``dqf`` block fails
    before the rows are made."""
    from repro.core import DQF

    cfg, tr = cell.config, cell.traffic
    dqf_cfg = dqf_config(cfg)
    g, ds = cfg["generator"], cfg["data_seed"]
    rows = make_rows(cfg["rows"], cfg["dim"], g["latent"], g["clusters"],
                     g["center_scale"], g["noise"], seed_rng(ds, 0))
    wl = ZipfWorkload(rows, tr["beta"], tr["sigma"], seed_rng(ds, 1))
    t = clock()
    dqf = DQF(dqf_cfg).build(rows)
    build_s = clock() - t
    log(f"build: {build_s:.3f} s ({dqf.timings.full_build:.3f} s in "
        f"build_ssg)")
    t = clock()
    dqf.warm(wl.sample(tr["history"]))
    dqf.fit_tree(wl.sample(tr["tree"]))
    log(f"warm + fit_tree: {clock() - t:.3f} s")
    return Deployment(rows, wl, dqf, build_s)


def _warm_up(eng, dqf, stream, sizes) -> None:
    """Compile every program the window can call, at every bucket width:
    refill, hot phase and admission at each width in ``sizes``, and the
    tick at each width as the lanes drain; then the per-slot update of
    the stacked hot tables that an Alg-2 rebuild takes."""
    import jax.numpy as jnp
    from repro.core.dynamic_search import hot_phase_stacked

    book = Book(dqf.cfg.k, stream.dim)
    for m in sizes:
        book.submit(eng, stream, m, 0.0)
        eng.run_until_drained()
    dqf.rebuild_hot()
    book.submit(eng, stream, sizes[-1], 0.0)
    eng.run_until_drained()
    eng._results.clear()
    # An Alg-2 rebuild draws the hot graph's entry points with
    # np.unique, so one rebuild in about thirty holds n_entry - 1 of them
    # and the stacked tables change shape: compile both widths at every
    # size.  The rebuild above may have changed the width in place too,
    # after the traffic of every size but the last, so compile that one.
    stk = dqf.tenants.stacked(dqf.store)
    c = dqf.cfg
    e = stk.entries.shape[1]
    for width in {c.n_entry, c.n_entry - 1, e}:
        if width < 1:
            continue
        ent = np.full((stk.entries.shape[0], width), stk.x.shape[1] - 1,
                      np.int32)
        ent[:, :min(e, width)] = np.asarray(stk.entries)[:, :width]
        for m in sizes:
            hot_phase_stacked(
                stk.x, stk.adj, jnp.asarray(ent), stk.mask,
                jnp.zeros((m,), jnp.int32),
                jnp.zeros((m, stk.x.shape[2]), jnp.float32),
                pool_size=c.hot_pool, max_hops=c.max_hops, mode=c.hot_mode)


class _Steps:
    """Steps the engine, and keeps the longest step of the window, the
    Alg-2 hot-index rebuilds and the full garbage collections in it."""

    def __init__(self, eng, dqf, t0: float):
        self.eng, self.dqf, self.t0 = eng, dqf, t0
        self.max_s, self.max_at, self.max_rebuilt = 0.0, None, False
        self.rebuilds, self.rebuild_max_s = 0, 0.0
        self.gc_full, self.gc_full_max_s, self._gc_t = 0, 0.0, None

    def _on_gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t = clock()
        elif self._gc_t is not None:
            dt = clock() - self._gc_t
            self.gc_full += 1
            self.gc_full_max_s = max(self.gc_full_max_s, dt)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)

    def __call__(self) -> None:
        v = self.dqf.hot.version
        t = clock()
        self.eng.step()
        dt = clock() - t
        rebuilt = self.dqf.hot.version != v
        if rebuilt:
            self.rebuilds += 1
            self.rebuild_max_s = max(self.rebuild_max_s, dt)
        if dt > self.max_s:
            self.max_s, self.max_at, self.max_rebuilt = dt, t - self.t0, \
                rebuilt

    def report(self) -> dict:
        return {"step_max_ms": self.max_s * 1e3,
                "step_max_at_s": self.max_at,
                "step_max_rebuilt_hot": self.max_rebuilt,
                "hot_rebuilds": self.rebuilds,
                "hot_rebuild_step_max_ms": self.rebuild_max_s * 1e3,
                "gc_full": self.gc_full,
                "gc_full_max_ms": self.gc_full_max_s * 1e3}


class _Tracer:
    """Profiles one stretch of the window, between two engine steps.
    With the trace log on it also reads the lanes' counters at both ends
    of the stretch, for the work done inside it.  The profiler runs on
    until :meth:`stop`, after the window and its drain: stopping it
    writes the trace, which blocks the host for tens of seconds on the
    chip, and inside the window that would stall the traffic."""

    def __init__(self, eng, book, t0: float, seconds: float,
                 counters: bool):
        self.eng, self.book, self.counters = eng, book, counters
        self.at = (t0 + TRACE_AT[0] * seconds, t0 + TRACE_AT[1] * seconds)
        self.dir = None
        self.state = 0
        self.sync = self.lo = self.hi = None
        self.live0 = self.live1 = None

    def _live(self):
        """rid -> (dist evals, hops) of every lane in flight."""
        eng = self.eng
        if not self.counters:
            return None
        lanes = eng.pagepool.live_lanes()
        if not lanes.size:
            return {}
        dc = np.asarray(eng._state.dist_count)[lanes]
        hp = np.asarray(eng._state.hops)[lanes]
        return {eng._lane_meta[int(ln)][0]: (int(a), int(b))
                for ln, a, b in zip(lanes, dc, hp)}

    def poll(self, now: float) -> None:
        import jax
        if self.state == 0 and now >= self.at[0]:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            # the Python tracer would hook every call of the host loop and
            # slow it about threefold; host annotations need only the host
            # tracer
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.sync = clock()
            with jax.profiler.TraceAnnotation(trace.SYNC):
                pass
            self.live0 = self._live()
            self.lo = clock()
            self.state = 1
        elif self.state == 1 and now >= self.at[1]:
            self.end_stretch()

    def end_stretch(self) -> None:
        if self.state != 1:
            return
        self.live1 = self._live()
        self.hi = clock()
        self.state = 2

    def stop(self) -> None:
        import jax
        self.end_stretch()
        if self.state == 2:
            jax.profiler.stop_trace()
            self.state = 3


def _closed_loop(eng, book, stream, outstanding, t0, t_end, tracer,
                 step=None):
    step = step or eng.step
    book.submit(eng, stream, outstanding, t0)
    while True:
        step()
        n = book.collect(eng)
        if tracer is not None:
            tracer.poll(clock())
        now = clock()
        if now >= t_end:
            return
        if n:
            book.submit(eng, stream, n, now)


def _open_loop(eng, book, stream, dues, t_end, tracer, step=None):
    step = step or eng.step
    i, n_due = 0, len(dues)
    while True:
        now = clock()
        if tracer is not None:
            tracer.poll(now)
        if now >= t_end:
            break
        j = int(np.searchsorted(dues, now, side="right"))
        if j > i:
            book.submit(eng, stream, j - i, dues[i:j])
            i = j
        if book.pending():
            step()
            book.collect(eng)
        else:
            wait = (dues[i] if i < n_due else t_end) - clock()
            if wait > 2e-4:
                time.sleep(wait - 1e-4)
    if i < n_due:                  # due before the close, sent late
        book.submit(eng, stream, n_due - i, dues[i:])


def _drain(eng, book, grace_s: float) -> None:
    stop = clock() + grace_s
    while book.pending() and clock() < stop:
        eng.step()
        book.collect(eng)


def _window(cell, eng, dqf, stream, seconds, seed, traced, trace_log):
    tr = cell.traffic
    book = Book(eng.cfg.k, cell.config["dim"])
    if tr["loop"] == "open":
        rng = seed_rng(seed, 2)
        n = int(tr["rate"] * seconds * 1.5) + 64
        rel = np.cumsum(rng.exponential(1.0 / tr["rate"], n))
        rel = rel[rel < seconds]
    t0 = clock()
    t_end = t0 + seconds
    tracer = _Tracer(eng, book, t0, seconds, trace_log) if traced else None
    with _Steps(eng, dqf, t0) as steps:
        if tr["loop"] == "closed":
            _closed_loop(eng, book, stream, tr["outstanding"], t0, t_end,
                         tracer, steps)
        elif tr["loop"] == "open":
            _open_loop(eng, book, stream, t0 + rel, t_end, tracer, steps)
        else:
            raise ValueError(f"unknown loop {tr['loop']!r}")
    if tracer is not None:
        tracer.end_stretch()
    return book, t0, t_end, tracer, steps.report()


def _end_to_end(book, t_end, seconds, setup_s, recall) -> dict:
    n = book.n
    late = book.sub[:n] - book.due[:n]
    lat_ms = (book.done[:n] - book.due[:n]) * 1e3
    in_window = book.done[:n] <= t_end
    out = {"qps": float(in_window.sum()) / seconds,
           "recall_at_10": recall, "setup_s": setup_s,
           "late_p50_ms": float(np.percentile(late, 50)) * 1e3,
           "late_max_ms": float(late.max()) * 1e3}
    if np.isfinite(lat_ms).all():
        out["p50_ms"] = float(np.percentile(lat_ms, 50))
        out["p99_ms"] = float(np.percentile(lat_ms, 99))
    return out


def _work(book, tracer, traces):
    """Distance evaluations and hops done inside the traced stretch:
    what the lanes retired in it had done in all, minus what the lanes in
    flight at its start had done before it, plus what the lanes in
    flight at its end have done so far.  None without the trace log."""
    if tracer.live0 is None:
        return None
    n = book.n
    done = book.done[:n]
    dist = hops = 0
    for i in np.nonzero((done >= tracer.lo) & (done < tracer.hi))[0]:
        t = traces[book.rid(int(i))]
        dist += t["full_dist_evals"]
        hops += t["full_hops"]
    for sign, live in ((-1, tracer.live0), (1, tracer.live1)):
        for a, b in live.values():
            dist += sign * a
            hops += sign * b
    return {"dist_evals": dist, "hops": hops}


def _per_layer(cell, eng, book, t0, t_end, tracer, device_kind):
    tr = trace.extract(_xplane(tracer.dir))
    shutil.rmtree(tracer.dir, ignore_errors=True)
    off = trace.clock_offset_ns(tr["host"], tracer.sync)
    lo, hi = tracer.lo * 1e9 + off, tracer.hi * 1e9 + off
    traces = {t["rid"]: t for t in eng.traces.snapshot()}
    run = Run(cell=cell, degree=eng.cfg.out_degree,
        peaks=roofline.peaks(device_kind), book=book,
        window=(t0, t_end), traces=traces, timeline=eng.timeline.events(),
        ops=tr["ops"], stretch=(lo, hi), work=_work(book, tracer, traces))
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    busy = trace.busy_ns(tr["ops"], lo, hi) * 1e-9
    mods = trace.module_seconds(tr["ops"], lo, hi)
    spans = [(e["ts"] * 1e3 + off, (e["ts"] + e["dur"]) * 1e3 + off,
              e["name"]) for e in run.timeline if e.get("ph") == "X"]
    gaps = trace.label_gaps(trace.idle_gaps(tr["ops"], lo, hi)[:10], spans,
                            "bench loop, outside engine.step")
    breakdown = {"device_ops": sorted(([k, v] for k, v in mods.items()),
                                      key=lambda kv: -kv[1])[:10],
                 "idle_gaps": gaps}
    extra = {"busy_s": busy / tr["devices"], "window_s": (hi - lo) * 1e-9}
    return metrics, breakdown, extra


def _xplane(d: str) -> str:
    for root, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def run(cell: Cell, seed: int, seconds: float, traced: bool, t_start: float,
        *, log=print, controls=(), grace_s: float = GRACE_S,
        engine_hook=None, deployment: Deployment | None = None) -> dict:
    """One run; returns the result line's object (``checks`` last).

    ``controls`` names lower precisions whose reference top-k is judged
    in the program's place (``high``, ``bf16``); ``engine_hook`` may
    replace parts of the engine before the window, for fault tests;
    ``deployment`` is a fresh copy of what :func:`deploy` returns for this
    cell's configuration, for readings of many seeds in one process.
    """
    import jax
    from repro.obs import ObsConfig
    from repro.serving.paged_engine import PagedWaveEngine

    compiles = _Compiles.get()
    cfg, tr = cell.config, cell.traffic
    dep = deployment or deploy(cell, log)
    rows, dqf, build_s = dep.rows, dep.dqf, dep.build_s
    stream = dep.workload.stream(seed_rng(seed, 1))
    obs = None
    trace_log = traced and any(uses_trace_log(m["name"])
                               for m in cell.per_layer)
    if traced:
        obs = ObsConfig(trace_rate=1.0 if trace_log else 0.0,
                        trace_capacity=1 << 21, timeline=True,
                        timeline_capacity=1 << 22)
    eng = PagedWaveEngine(dqf, capacity=cfg["engine"]["capacity"], obs=obs)
    _warm_up(eng, dqf, stream, tr["warmup_sizes"])
    if engine_hook is not None:
        engine_hook(eng)
    if traced:
        eng.timeline.clear()
    c0 = compiles.count
    setup_s = clock() - t_start
    log(f"set-up: {setup_s:.3f} s, of it {compiles.seconds:.3f} s lowering "
        f"and compiling ({c0} programs)")

    book, t0, t_end, tracer, steps = _window(cell, eng, dqf, stream,
                                             seconds, seed, traced,
                                             trace_log)
    in_window = compiles.count - c0
    _drain(eng, book, grace_s)
    if tracer is not None:
        tracer.stop()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    out_layer = None
    if traced:
        out_layer = _per_layer(cell, eng, book, t0, t_end, tracer,
                               dev.device_kind)
    del eng, dqf, dep
    gc.collect()

    n = book.n
    k = book.k
    ref = reference.Reference(rows)
    t = clock()
    queries = book.q[:n]
    ref_ids, _ = ref.top_k(queries, k)
    exact_d = ref.distances(queries, book.ids[:n])
    answered = np.isfinite(book.done[:n])
    limits = {**cfg["checks"], "recall_deficit": round(
        1.0 - cfg["guarantee"]["recall_floor"], 12)}
    checks = reference.judge(book.ids[:n], book.dists[:n], answered,
                             book.ok[:n], ref_ids, exact_d, n=cfg["rows"],
                             eps=ref.eps, limits=limits)
    ctl = {}
    for p in controls:
        c_ids, c_d = ref.top_k(queries, k, p)
        c_exact = ref.distances(queries, c_ids)
        ctl[p] = reference.judge(c_ids, c_d, np.ones(n, bool),
                                 np.ones(n, bool), ref_ids, c_exact,
                                 n=cfg["rows"], eps=ref.eps, limits=limits)
    log(f"reference: {clock() - t:.3f} s over {n} requests")

    recall = 1.0 - checks["recall_deficit"]["value"]
    e2e = _end_to_end(book, t_end, seconds, setup_s, recall)
    log(f"window: {in_window} compilations inside it; {n} requests due, "
        f"{int(answered.sum())} answered; generator late p50 "
        f"{e2e['late_p50_ms']:.3f} ms, max {e2e['late_max_ms']:.3f} ms; "
        f"build {build_s:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = checks["unanswered"]["value"] + checks["not_ok"]["value"]
    out = {"correct": bool(correct), "attempted": int(n),
           "failed": int(failed)}
    if traced:
        metrics, breakdown, extra = out_layer
        device.update(extra)
        out.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        out.update(metrics={m["name"]: {"value": e2e[m["name"]],
                                        "unit": m["unit"]}
                            for m in cell.end_to_end
                            if m["name"] in e2e},
                   device=device)
    out["window"] = {"compiles_in_window": in_window,
                     "answered_in_window": int(e2e["qps"] * seconds),
                     "late_p50_ms": e2e["late_p50_ms"],
                     "late_max_ms": e2e["late_max_ms"],
                     "p50_ms": e2e.get("p50_ms"),
                     "p99_ms": e2e.get("p99_ms"), "build_s": build_s,
                     **steps}
    if controls:
        out["controls"] = ctl
    out["checks"] = checks
    return out
