"""Tests of the chip benchmark's harness, on the CPU.

Run by hand from the checkout root (the repository's tier-1 suite
collects only ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace-to-metrics reduction on a recorded trace, the
latency of the open loop from each request's due time, the lookup of
cells, mixes and metric readers by name, the program's config built from
a configuration's blocks (nested ones too, unknown keys refused before
any rows are made), the bytes a hop reads by stored code width, the
refusal to run without a TPU, and the comparison that decides
``correct``: it passes the program at a small size, float32 and ``sq8``,
and fails the control and each fault the cells can have.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import cell as hc  # noqa: E402
from harness import reference, roofline, trace  # noqa: E402
from harness.spec import (dqf_config, load_cell, load_reader,  # noqa: E402
                          uses_trace_log)
from harness.workload import ZipfWorkload, make_rows, seed_rng  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ lookup by name
def test_every_cell_finds_its_config_traffic_and_readers():
    spec = _spec()
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        for key in ("data_seed", "rows", "dim", "generator", "dqf",
                    "engine", "guarantee", "checks"):
            assert key in cell.config
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(load_reader(m["name"]))


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = _spec()
    traffic = dict(load_cell("sift128-zipf-sat").traffic, outstanding=256)
    (tmp_path / "bench" / "traffic" / "zipf-deep.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "answered.py").write_text(
        "def read(run):\n    return run.book.answered\n")
    spec["workloads"].append({"name": "sift128-zipf-deep",
                              "config": "sift128-l2-50k",
                              "traffic": "zipf-deep", "chips": 1,
                              "why": "deeper queue"})
    spec["per_layer"].append({"name": "answered", "unit": "queries",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "serving engine", "moves": "qps",
                              "workloads": ["sift128-zipf-deep"]})
    spec["end_to_end"][0]["workloads"].append("sift128-zipf-deep")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = load_cell("sift128-zipf-deep", str(tmp_path))
    assert cell.traffic["outstanding"] == 256
    assert [m["name"] for m in cell.per_layer] == ["answered"]
    read = load_reader("answered", str(tmp_path))

    class _Run:
        book = hc.Book(10, 8)
    assert read(_Run()) == 0
    with pytest.raises(KeyError):
        load_cell("no-such-cell", str(tmp_path))


# ------------------------------------------- the configuration's dqf block
def _config(**dqf):
    return dict(load_cell("sift128-zipf-sat").config, dqf=dqf)


def test_the_sift_config_builds_the_dqf_config_of_before():
    from repro.core import DQFConfig
    assert dqf_config(load_cell("sift128-zipf-sat").config) \
        == DQFConfig(k=10, full_pool=192)


def test_a_tier_block_becomes_a_tier_config():
    from repro.tiering import TierConfig
    c = dqf_config(_config(tier={"mode": "host", "block_rows": 128}))
    assert c.tier == TierConfig(mode="host", block_rows=128)


@pytest.mark.parametrize("dqf, names", [
    ({"full_pool": 192, "ful_pool": 64}, ("dqf", "DQFConfig", "'ful_pool'")),
    ({"quant": {"mode": "sq8", "rerank": 64}},
     ("dqf.quant", "QuantConfig", "'rerank'")),
], ids=["top_level", "nested"])
def test_an_unknown_key_fails_before_the_rows_are_made(dqf, names,
                                                       monkeypatch):
    def no_rows(*_a, **_k):
        raise AssertionError("make_rows reached")
    monkeypatch.setattr(hc, "make_rows", no_rows)
    cell = load_cell("sift128-zipf-sat")
    cell.config = dict(cell.config, dqf=dqf)
    with pytest.raises(ValueError) as e:
        hc.deploy(cell, lambda *_: None)
    for name in names:
        assert name in str(e.value)


@pytest.mark.parametrize("field, value", [("metric", "ip"),
                                          ("dtype", "uint8")])
def test_load_cell_refuses_semantics_the_reference_lacks(field, value,
                                                         tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "bench" / "configs" / "sift128-l2-50k.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **{field: value})))
    with pytest.raises(ValueError, match=field):
        load_cell("sift128-zipf-sat", str(tmp_path))


@pytest.mark.parametrize("dim, dqf, expected", [
    (128, {"full_pool": 192}, 512),
    (960, {"quant": {"mode": "sq8", "rerank_k": 64}}, 960),
    (128, {"quant": {"mode": "pq", "pq_m": 8}}, 8),
], ids=["sift128", "sq8_960", "pq_m8"])
def test_row_bytes_by_stored_code_width(dim, dqf, expected):
    assert roofline.row_bytes(dict(_config(**dqf), dim=dim)) == expected


def test_row_bytes_refuses_a_tiered_full_index():
    with pytest.raises(ValueError, match="host"):
        roofline.row_bytes(_config(tier={"mode": "host"}))


# ----------------------------------------------------------- refusing to run
def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift128-zipf-sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    lines = p.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and _no_result(p)
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and _no_result(p)


# --------------------------------------------------- trace-to-metrics reduction
def test_reduction_of_a_hand_made_trace():
    # device ops (start, dur, op, module) in ns; two overlap
    ops = [(100, 50, "a", "jit_tick"), (120, 60, "b", "jit_tick"),
           (300, 100, "c", "jit_admit_wave"), (900, 200, "d", "jit_tick")]
    assert trace.busy_ns(ops, 0, 1000) == 80 + 100 + 100
    mods = trace.module_seconds(ops, 0, 1000)
    assert mods["jit_tick"] == pytest.approx((80 + 100) * 1e-9)
    assert mods["jit_admit_wave"] == pytest.approx(100e-9)
    gaps = trace.idle_gaps(ops, 0, 1000)
    assert gaps[0] == (400, 900) and (0, 100) in gaps and (180, 300) in gaps
    spans = [(350, 950, "tick"), (500, 700, "tick.retire")]
    labels = trace.label_gaps(gaps[:2], spans, "out")
    assert [name for name, _ in labels] == ["tick.retire", "out"]
    assert [s for _, s in labels] == pytest.approx([500e-9, 120e-9])
    events = [{"ph": "X", "name": "tick", "ts": 0.0, "dur": 10.0},
              {"ph": "X", "name": "tick.jit", "ts": 2.0, "dur": 5.0},
              {"ph": "X", "name": "tick", "ts": 20.0, "dur": 4.0},
              {"ph": "X", "name": "tick.jit", "ts": 21.0, "dur": 1.0}]
    assert trace.span_self_ms(events, "tick", "tick.jit", 0, 100) \
        == pytest.approx((5 + 3) / 2 * 1e-3)
    assert trace.span_self_ms(events, "tick", "tick.jit", 50, 100) is None
    host = [(5000, 10, trace.SYNC)]
    assert trace.clock_offset_ns(host, 2.0) == 5000 - 2e9


def _union_by_sweep(ops, lo, hi):
    """Busy time by counting open intervals at every endpoint."""
    pts = sorted([(max(s, lo), 1) for s, d, *_ in ops if s + d > lo
                  and s < hi] + [(min(s + d, hi), -1) for s, d, *_ in ops
                                 if s + d > lo and s < hi])
    busy, depth, last = 0.0, 0, lo
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_reduction_of_a_trace_recorded_on_the_chip():
    with open(os.path.join(DATA, "tpu_trace.json")) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    lo, hi = rec["lo_ns"], rec["hi_ns"]
    assert rec["device_kind"] in json.load(
        open(os.path.join(BENCH, "peaks.json")))["devices"]
    busy = trace.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(_union_by_sweep(ops, lo, hi), rel=1e-9)
    assert 0 < busy <= hi - lo
    mods = trace.module_seconds(ops, lo, hi)
    assert "jit_tick" in mods
    assert sum(mods.values()) >= busy * 1e-9 * (1 - 1e-9)
    # the clock offset puts each device tick inside the host's tick.jit
    off = trace.clock_offset_ns([tuple(h) for h in rec["host"]],
                                rec["sync_perf_s"])
    jits = [(e["ts"] * 1e3 + off, (e["ts"] + e["dur"]) * 1e3 + off)
            for e in rec["timeline"] if e["name"] == "tick.jit"]
    ticks = [o for o in ops if o[3] == "jit_tick"]
    assert jits and ticks
    inside = sum(any(a - 1e5 <= s and s + d <= b + 1e5 for a, b in jits)
                 for s, d, *_ in ticks)
    assert inside == len(ticks)
    self_ms = trace.span_self_ms(rec["timeline"], "tick", "tick.jit",
                                 -1e18, 1e18)
    assert self_ms is not None and self_ms > 0


def test_extract_refuses_a_trace_without_a_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.SYNC):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no /device:TPU"):
        trace.extract(hc._xplane(str(tmp_path)))


def test_paced_readers_are_their_sat_twins_and_only_some_need_the_log():
    spec = _spec()
    for m in spec["per_layer"]:
        if m["name"].endswith(".paced"):
            twin = m["name"][:-len(".paced")] + ".sat"
            assert load_reader(m["name"]).__code__.co_filename == \
                load_reader(twin).__code__.co_filename
    logged = {m["name"] for m in spec["per_layer"]
              if uses_trace_log(m["name"])}
    assert logged == {"dist_per_query", "hop_roofline"}
    paced = load_cell("sift128-zipf-paced")
    assert not any(uses_trace_log(m["name"]) for m in paced.per_layer)


def test_hop_bytes_and_peaks():
    assert roofline.hop_bytes(10, 2, 128 * 4, 32) \
        == 10 * 128 * 4 + 2 * 32 * 4
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ------------------------------------------------------------- the generator
def test_seeds_of_any_size_give_the_same_inputs():
    for seed in (0, 2**31 + 5, 2**40, -3):
        a = make_rows(64, 8, 4, 4, 2.0, 0.1, seed_rng(seed, 0))
        b = make_rows(64, 8, 4, 4, 2.0, 0.1, seed_rng(seed, 0))
        assert np.array_equal(a, b)
    x = make_rows(500, 8, 4, 4, 2.0, 0.1, seed_rng(7, 0))
    wl = ZipfWorkload(x, 1.2, 0.05, seed_rng(7, 1))
    s1 = wl.stream(seed_rng(2**33 + 1, 1), chunk=16)
    s2 = wl.stream(seed_rng(2**33 + 1, 1), chunk=16)
    q1 = np.concatenate([s1.take(3)[0], s1.take(40)[0]])
    q2 = s2.take(43)[0]
    assert np.array_equal(q1, q2)


def test_every_query_of_a_stream_has_noise_of_its_own():
    # a Zipf head of 20% makes repeats of target likely; the queries
    # themselves never repeat, across chunk boundaries too
    x = make_rows(500, 8, 4, 4, 2.0, 0.1, seed_rng(3, 0))
    wl = ZipfWorkload(x, 1.2, 0.05, seed_rng(3, 1))
    st = wl.stream(seed_rng(5, 1), chunk=64)
    q, t = st.take(1000)
    assert len(np.unique(t)) < 500
    assert len(np.unique(q, axis=0)) == 1000
    # the seed draws the traffic; the popular rows are the deployment's
    t2 = wl.stream(seed_rng(6, 1), chunk=64).take(1000)[1]
    assert not np.array_equal(t, t2)
    assert np.bincount(t, minlength=500).argmax() \
        == np.bincount(t2, minlength=500).argmax()


class _FakeEngine:
    """Answers every queued request at each step after ``tick_s``, and
    stalls once for ``stall_s`` at step ``stall_at``; requests whose id is
    in ``never`` are never answered."""

    def __init__(self, tick_s=0.002, stall_at=None, stall_s=0.0,
                 never=()):
        self.q, self._results = [], {}
        self.tick_s, self.stall_at, self.stall_s = tick_s, stall_at, stall_s
        self.never, self.steps, self.next = set(never), 0, 0

    def submit(self, qs):
        rids = list(range(self.next, self.next + len(qs)))
        self.next += len(qs)
        self.q += rids
        return rids

    def step(self):
        time.sleep(self.stall_s if self.steps == self.stall_at
                   else self.tick_s)
        self.steps += 1
        for rid in self.q:
            if rid not in self.never:
                self._results[rid] = {"ids": np.arange(10),
                                      "dists": np.zeros(10), "status": "ok"}
        self.q = [r for r in self.q if r in self.never]


def _stream():
    x = make_rows(200, 8, 4, 4, 2.0, 0.1, seed_rng(1, 0))
    return ZipfWorkload(x, 1.2, 0.05, seed_rng(1, 1)).stream(
        seed_rng(2, 1), chunk=8)


def test_open_loop_counts_latency_from_the_due_time():
    eng = _FakeEngine(stall_at=5, stall_s=0.3)
    book = hc.Book(10, 8)
    t0 = hc.clock()
    dues = t0 + np.arange(0.0, 1.0, 0.01)          # 100 requests, 100/s
    hc._open_loop(eng, book, _stream(), dues, t0 + 1.0, None)
    hc._drain(eng, book, 5.0)
    n = book.n
    assert n == 100 and book.pending() == 0
    lat = book.done[:n] - book.due[:n]
    late = book.sub[:n] - book.due[:n]
    assert (lat >= late).all() and (late >= 0).all()
    # the stall starts at the sixth step; requests due while it lasts are
    # sent late and their latency counts the wait from their due time
    stalled = late > 0.1
    assert stalled.sum() >= 10
    assert (lat[stalled] >= late[stalled]).all()
    assert lat.max() >= 0.2


def test_requests_never_answered_are_counted():
    eng = _FakeEngine(never={3, 7})
    book = hc.Book(10, 8)
    t0 = hc.clock()
    hc._open_loop(eng, book, _stream(), t0 + np.arange(0, 0.2, 0.01),
                  t0 + 0.2, None)
    hc._drain(eng, book, 0.2)
    assert book.pending() == 2
    answered = np.isfinite(book.done[:book.n])
    checks = reference.judge(
        book.ids[:book.n], book.dists[:book.n], answered, book.ok[:book.n],
        book.ids[:book.n], book.dists[:book.n].astype(np.float64), n=200,
        eps=1e-6, limits={"unanswered": 0, "not_ok": 0, "malformed": 0,
                          "dist_gap": 1e-4, "recall_deficit": 0.1})
    assert checks["unanswered"]["value"] == 2


# ------------------------------------------- correct: the control and faults
def _tiny(name="sift128-zipf-sat"):
    cell = load_cell(name)
    cell.config = dict(cell.config, rows=2000)
    cell.traffic = dict(cell.traffic, history=256, tree=128)
    return cell


def _run(cell, **kw):
    return hc.run(cell, 20240607, 1.5, False, time.perf_counter(),
                  log=lambda *_: None, grace_s=3.0, **kw)


def test_program_passes_and_each_control_fails():
    out = _run(_tiny(), controls=("high", "bf16"))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 100 and out["failed"] == 0
    for precision, checks in out["controls"].items():
        failed = [k for k, c in checks.items() if c["value"] > c["limit"]]
        assert failed == ["dist_gap"], (precision, checks)


def test_a_quantized_deployment_runs_correct():
    from repro.core import QuantConfig
    cell = _tiny()
    cell.config = dict(cell.config, dim=960,
                       dqf={"full_pool": 192,
                            "quant": {"mode": "sq8", "rerank_k": 64}})
    dep = hc.deploy(cell, lambda *_: None)
    quant = dep.dqf.cfg.quant
    assert isinstance(quant, QuantConfig) and quant.mode == "sq8"
    out = _run(cell, deployment=dep)
    assert out["correct"], out["checks"]
    assert out["checks"]["dist_gap"]["value"] <= 1e-4
    assert out["attempted"] > 100 and out["failed"] == 0


def test_nothing_compiles_in_the_window_after_a_narrower_rebuild(
        monkeypatch):
    # the warm-up's own Alg-2 rebuild draws fewer entry points than the
    # index it replaces, as one rebuild in about thirty does, so the
    # stacked tables change width: the window still compiles nothing
    import repro.core.dqf as dqf_mod
    cell = _tiny()
    dep = hc.deploy(cell, lambda *_: None)
    before = dep.dqf.tenants.stacked(dep.dqf.store).entries.shape[1]
    build = dqf_mod.build_hot_index

    def narrower(*args, n_entry, **kw):
        return build(*args, n_entry=n_entry // 2, **kw)
    monkeypatch.setattr(dqf_mod, "build_hot_index", narrower)
    out = _run(cell, deployment=dep)
    assert dep.dqf.tenants.stacked(dep.dqf.store).entries.shape[1] < before
    assert out["correct"], out["checks"]
    assert out["window"]["compiles_in_window"] == 0


def _stuck(eng):
    def tick(ps, lanes, *_):
        return ps, (ps.active[lanes], ps.hops[lanes], ps.ids[lanes],
                    ps.dists[lanes])
    eng._tick_fn = tick


def _half_left_out(eng):
    retire = eng._retire

    def half(lanes_np, retiring, *args):
        before = set(eng._results)
        retire(lanes_np, retiring, *args)
        for rid in sorted(set(eng._results) - before)[::2]:
            del eng._results[rid]
    eng._retire = half


def _altered(monkeypatch):
    import repro.serving.paged_engine as pe
    orig = pe.retire_batch

    def altered(*args, **kw):
        ids, dists = orig(*args, **kw)
        ids = ids.copy()
        ids[:, -1] = (ids[:, -1] + 1) % 2000
        return ids, dists
    monkeypatch.setattr(pe, "retire_batch", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_each_fault_comes_out_not_correct(fault, monkeypatch):
    hook = {"state_unchanged": _stuck, "half_left_out": _half_left_out,
            "answer_altered": None}[fault]
    if fault == "answer_altered":
        _altered(monkeypatch)
    out = _run(_tiny(), engine_hook=hook)
    assert not out["correct"], out["checks"]


def _chip_trace(monkeypatch):
    """The CPU's trace has no device plane: hand the harness the trace
    recorded on the chip instead, its sync mark at the stretch's start,
    and the peaks of the chip it was recorded on."""
    with open(os.path.join(DATA, "tpu_trace.json")) as f:
        rec = json.load(f)
    sync = rec["lo_ns"]
    monkeypatch.setattr(hc.trace, "extract", lambda path: {
        "ops": [tuple(o) for o in rec["ops"]],
        "host": [(sync, 0.0, trace.SYNC)], "devices": 1})
    peaks = roofline.peaks(rec["device_kind"])
    monkeypatch.setattr(hc.roofline, "peaks", lambda _: peaks)


def test_traced_run_reports_each_per_layer_metric(monkeypatch):
    _chip_trace(monkeypatch)
    for name in ("sift128-zipf-sat", "sift128-zipf-paced"):
        cell = _tiny(name)
        if cell.traffic["loop"] == "open":
            cell.traffic["rate"] = 200.0
        out = hc.run(cell, 77, 2.0, True, time.perf_counter(),
                     log=lambda *_: None, grace_s=3.0)
        assert out["correct"], out["checks"]
        assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
        for m in out["metrics"].values():
            assert m["value"] > 0
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        for key in ("device_ops", "idle_gaps"):
            assert 0 < len(out["breakdown"][key]) <= 10
