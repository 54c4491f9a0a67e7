"""Spans of the paged engine's tick and of each request's life.

Contracts under test:

* the tick's children nest inside ``tick`` (and inside ``tick.retire`` /
  ``tick.refill``) under exactly the names the benchmark's readers use;
  the tick's reads come before its refill, the next tick's dispatch after
  it, and the retirement last, inside ``tick.overlap`` while that next
  tick runs (or right under ``tick`` when it must precede the refill or
  no tick follows); the finished lanes' copy and free, which must precede
  the refill, is a ``retire.lanes`` right under ``tick``;
* one ``req.queued`` and one ``req.lane`` "b"/"e" pair per served
  request, as long as the trace log's queue wait and service time;
* a ``hot.rebuild`` span exactly when an Alg-2 rebuild runs;
* with the timeline off: no event, no ``TraceAnnotation``, and results
  bit-identical to a traced run;
* every span is mirrored into a ``jax.profiler`` capture, on one clock
  offset from the timeline's;
* the trace log's hot-phase counters, read after the next tick instead
  of inside the refill, keep their values.
"""

import collections

import jax
import numpy as np
import pytest

from repro.core import DQF, DQFConfig, ZipfWorkload
from repro.obs import ObsConfig
from repro.serving.paged_engine import PagedWaveEngine

PARENT = {"tick.housekeeping": "tick", "tick.tier": "tick",
          "tick.jit": "tick", "tick.fetch": "tick",
          "tick.overlap": "tick", "tick.retire": ("tick.overlap", "tick"),
          "tick.refill": "tick",
          "retire.results": "tick.retire",
          "retire.lanes": ("tick.retire", "tick"),
          "hot.rebuild": "retire.lanes", "refill.queue": "tick.refill",
          "refill.admit": "tick.refill", "refill.lanes": "tick.refill"}
TIMING_KEYS = {"queue_wait_ms", "service_ms", "total_ms"}


def _serve(eng, queries, per_step=6):
    """Submit ``per_step`` queries before each step until all are in,
    then step until drained."""
    rids = []
    for i in range(0, len(queries), per_step):
        rids += eng.submit(queries[i:i + per_step])
        eng.step()
    while eng.queue or eng._any_live():
        eng.step()
    return rids


def _engine(dqf, **obs):
    """An engine past its first step, whose refill runs outside a tick;
    every later refill runs inside one."""
    eng = PagedWaveEngine(dqf, capacity=8, tick_hops=8, min_bucket=4,
                          obs=ObsConfig(**obs))
    eng.step()
    eng.timeline.clear()
    return eng


def _x(events, name=None):
    return [e for e in events if e["ph"] == "X"
            and (name is None or e["name"] == name)]


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def test_tick_children_nest_under_their_names(built_dqf):
    dqf, wl = built_dqf
    eng = _engine(dqf, timeline=True, trace_rate=1.0)
    t0 = eng.stats.ticks
    _serve(eng, wl.sample(30))
    evs = eng.timeline.events()
    names = {e["name"] for e in _x(evs)}
    assert names == {"tick"} | set(PARENT) - {"hot.rebuild"}
    ticks = _x(evs, "tick")
    assert len(ticks) == eng.stats.ticks - t0
    for e in _x(evs):
        if e["name"] == "tick":
            continue
        parents = PARENT[e["name"]]
        parents = (parents,) if isinstance(parents, str) else parents
        assert any(_inside(e, p) for p in _x(evs) if p["name"] in parents
                   and p is not e), e
        assert sum(_inside(e, t) for t in ticks) == 1, e
    # the retirement overlaps the next tick: it starts after the refill
    # and the dispatch that follows it, inside the same tick
    overlaps = _x(evs, "tick.overlap")
    assert overlaps
    for o in overlaps:
        (t,) = [t for t in ticks if _inside(o, t)]
        (r,) = [r for r in _x(evs, "tick.refill") if _inside(r, t)]
        assert any(r["ts"] + r["dur"] <= j["ts"]
                   and j["ts"] + j["dur"] <= o["ts"]
                   for j in _x(evs, "tick.jit") if _inside(j, t)), o
        # the lanes it retires were freed before that refill, under a
        # ``retire.lanes`` of the tick's own
        assert any(f["ts"] + f["dur"] <= r["ts"]
                   and not any(_inside(f, p)
                               for p in _x(evs, "tick.retire"))
                   for f in _x(evs, "retire.lanes") if _inside(f, t)), o
    fetches = _x(evs, "tick.fetch")
    assert all(f["args"]["arrays"] >= 1 and f["args"]["bytes"] > 0
               for f in fetches)
    admits = _x(evs, "refill.admit")
    assert admits and all(a["args"]["bucket"] >= a["args"]["admitted"] >= 1
                          for a in admits)


def test_one_request_pair_per_served_request(built_dqf):
    dqf, wl = built_dqf
    eng = _engine(dqf, timeline=True, trace_rate=1.0)
    rids = _serve(eng, wl.sample(30))
    evs = eng.timeline.events()
    traces = {t["rid"]: t for t in eng.traces}
    assert set(traces) == set(rids)
    for name, key in (("req.queued", "queue_wait_ms"),
                      ("req.lane", "service_ms")):
        pairs = collections.defaultdict(dict)
        for e in evs:
            if e["name"] == name:
                assert e["ph"] in ("b", "e")
                assert e["ph"] not in pairs[e["id"]], (name, e)
                pairs[e["id"]][e["ph"]] = e
        assert set(pairs) == set(rids)
        for rid, p in pairs.items():
            assert p["b"]["args"]["tenant"] == traces[rid]["tenant"]
            dur_ms = (p["e"]["ts"] - p["b"]["ts"]) * 1e-3
            assert dur_ms == pytest.approx(traces[rid][key], abs=1e-6)
            if name == "req.lane":
                assert p["b"]["args"]["status"] == "ok"
                assert p["b"]["args"]["ticks"] == \
                    traces[rid]["ticks_in_flight"]


def test_queued_pair_ends_at_a_terminal_status(built_dqf):
    dqf, wl = built_dqf
    eng = _engine(dqf, timeline=True)
    rids = eng.submit(wl.sample(3), deadline_ms=0.0)
    eng.step()
    pairs = collections.defaultdict(dict)
    for e in eng.timeline.events():
        if e["name"] == "req.queued":
            pairs[e["id"]][e["ph"]] = e
    assert set(pairs) == set(rids)
    for p in pairs.values():
        assert p["b"]["args"]["status"] == "deadline"
        assert p["e"]["ts"] >= p["b"]["ts"]
    assert not any(e["name"] == "req.lane" for e in eng.timeline.events())


@pytest.fixture(scope="module")
def rebuilding_dqf(small_data):
    """A DQF of its own whose Alg-2 trigger fires every 40 queries."""
    cfg = DQFConfig(knn_k=12, out_degree=12, index_ratio=0.03, k=10,
                    hot_pool=16, full_pool=32, eval_gap=40, max_hops=120,
                    n_query_trigger=40)
    dqf = DQF(cfg).build(small_data)
    wl = ZipfWorkload(small_data, beta=1.2, sigma=0.05, seed=2)
    _, targets = wl.sample(400, with_targets=True)
    dqf.counter.record(targets)
    dqf.rebuild_hot()
    return dqf, wl


def test_hot_rebuild_span_exactly_when_the_version_changes(rebuilding_dqf):
    dqf, wl = rebuilding_dqf
    eng = _engine(dqf, timeline=True)
    eng.submit(wl.sample(120))
    changed = 0
    while eng.queue or eng._any_live():
        n0, v0 = len(eng.timeline.events()), dqf.hot.version
        eng.step()
        new = [e for e in eng.timeline.events()[n0:]
               if e["name"] == "hot.rebuild"]
        v1 = dqf.hot.version
        assert len(new) == v1 - v0
        if v1 != v0:
            changed += 1
            assert new[-1]["args"] == {"tenant": "default",
                                       "hot_rows": dqf.hot.size,
                                       "version": v1}
    assert changed >= 2


class _CountingAnnotation(jax.profiler.TraceAnnotation):
    built = 0

    def __init__(self, name, **kw):
        type(self).built += 1
        super().__init__(name, **kw)


def test_timeline_off_builds_nothing_and_serves_the_same(built_dqf,
                                                         monkeypatch):
    dqf, wl = built_dqf
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    q = wl.sample(30)
    off = _engine(dqf)
    off_rids = _serve(off, q)
    assert off.timeline.events() == [] and _CountingAnnotation.built == 0
    on = _engine(dqf, timeline=True, trace_rate=1.0)
    built = _CountingAnnotation.built
    on_rids = _serve(on, q)
    assert _CountingAnnotation.built - built == len(_x(on.timeline.events()))
    for a, b in zip(off_rids, on_rids):
        ra, rb = off._results[a], on._results[b]
        np.testing.assert_array_equal(ra["ids"], rb["ids"])
        np.testing.assert_array_equal(ra["dists"], rb["dists"])
        assert ra["hops"] == rb["hops"] and ra["status"] == rb["status"]


def test_spans_are_mirrored_on_the_profilers_clock(built_dqf, tmp_path):
    from jax.profiler import ProfileData

    dqf, wl = built_dqf
    eng = _engine(dqf, timeline=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, wl.sample(24))
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = ("tick.retire", "tick.fetch", "refill.admit")
    host = collections.defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        host[e.name].append(e.start_ns)
    offsets = []
    for name in names:
        mine = sorted(e["ts"] for e in _x(eng.timeline.events(), name))
        assert mine and len(host[name]) == len(mine), name
        offsets += [h - t * 1e3 for h, t in zip(sorted(host[name]), mine)]
    assert max(offsets) - min(offsets) < 1e6


class _ImmediateHotRead(PagedWaveEngine):
    """Reads the sampled lanes' hot-phase counters inside the refill,
    right after issuing the hot phase, as the engine once did."""

    def _refill(self):
        super()._refill()
        self._read_hot_stats()


def test_deferred_hot_stats_keep_the_trace_log(built_dqf):
    dqf, wl = built_dqf
    q = wl.sample(30)
    kw = dict(capacity=8, tick_hops=8, min_bucket=4,
              obs=ObsConfig(trace_rate=1.0))
    logs = []
    for cls in (PagedWaveEngine, _ImmediateHotRead):
        eng = cls(dqf, **kw)
        for i in (0, 6):
            eng.submit(q[i:i + 6])
            eng.step()
        # the deferred engine read nothing back in the tick's refill
        assert bool(eng._hot_pending) == (cls is PagedWaveEngine)
        _serve(eng, q[12:])
        logs.append(sorted(eng.traces, key=lambda t: t["rid"]))
    got, want = logs
    assert len(got) == len(want) == 30
    for a, b in zip(got, want):
        assert list(a) == list(b)
        assert {k: v for k, v in a.items() if k not in TIMING_KEYS} == \
            {k: v for k, v in b.items() if k not in TIMING_KEYS}
        assert a["hot_hops"] > 0 and a["hot_dist_evals"] > 0
