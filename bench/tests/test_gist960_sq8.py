"""Tests of the GIST-960 ``sq8`` cell's files, on the CPU.

Run by hand from the checkout root, like the harness's own tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the cell's lookup (the program's config it builds, the bytes
a hop reads per scored row and the readers it reports), the rerank
reader and the hop roofline at the cell's row width on hand-made inputs,
and a traced run of the cell at a small size that reports every reader.
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import cell as hc  # noqa: E402
from harness import roofline, trace  # noqa: E402
from harness.spec import dqf_config, load_cell, load_reader  # noqa: E402

CELL = "gist960-sq8-zipf-sat"
DATA = os.path.join(BENCH, "tests", "data")


def test_the_cell_builds_an_sq8_config_of_960_byte_rows():
    from repro.core import QuantConfig

    cell = load_cell(CELL)
    cfg = dqf_config(cell.config)
    assert cfg.quant == QuantConfig(mode="sq8", rerank_k=64)
    assert cfg.full_pool == 192 and cfg.k == 10
    assert roofline.row_bytes(cell.config) == 960
    assert cell.config["generator"]["latent"] == 16
    assert {m["name"] for m in cell.end_to_end} == \
        {"qps", "recall_at_10", "setup_s"}
    # the SIFT sat cell's readers (the same engine loop, at 960-d) and
    # the rerank's own
    sift = {m["name"] for m in load_cell("sift128-zipf-sat").per_layer}
    assert {m["name"] for m in cell.per_layer} == sift | {"rerank_ms"}
    assert cell.traffic == load_cell("sift128-zipf-sat").traffic


def _x(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur),
            "args": args}


# Two ticks in the window (0 s to 1 s) and one after it.  Tick 1 retires
# three lanes, whose rerank takes 1,200 us; tick 2 retires none; the
# tick after the window reranks for 9,000 us and is not counted.
TICKS = [
    _x("tick", 0, 10_000),
    _x("tick.retire", 2_900, 2_500, retiring=3),
    _x("retire.results", 2_950, 1_500),
    _x("retire.rerank", 3_000, 1_200, rows=192),
    _x("tick", 20_000, 5_000),
    _x("tick", 2_000_000, 10_000),
    _x("retire.rerank", 2_000_100, 9_000, rows=640),
]


def test_rerank_ms_is_the_per_tick_mean_of_the_rerank_spans():
    read = load_reader("rerank_ms")
    run = types.SimpleNamespace(timeline=TICKS, window=(0.0, 1.0))
    assert read(run) == pytest.approx(1_200 / 2 * 1e-3, rel=1e-12)
    # a program without the span (as a float32 Full Index, or before
    # the span existed) reads None, not 0
    run.timeline = [e for e in TICKS if e["name"] != "retire.rerank"]
    assert read(run) is None


def test_hop_roofline_counts_the_cells_one_byte_codes():
    read = load_reader("hop_roofline")
    ops = [(0, 2_000, "a", "jit_tick"), (3_000, 1_000, "b", "jit_tick"),
           (5_000, 500, "c", "jit_seed_admit")]
    run = types.SimpleNamespace(
        cell=load_cell(CELL), degree=32,
        peaks=roofline.peaks("TPU v5 lite"), ops=ops, stretch=(0, 10_000),
        work={"dist_evals": 1_000, "hops": 40})
    need = 1_000 * 960 + 40 * 32 * 4
    want = 100.0 * need / (3_000e-9 * 819e9)
    assert read(run) == pytest.approx(want, rel=1e-12)
    run.ops = ops[2:]
    assert read(run) is None


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


def test_a_traced_run_of_the_cell_reports_both_readers(monkeypatch):
    _chip_trace(monkeypatch)
    cell = load_cell(CELL)
    cell.config = dict(cell.config, rows=2000)
    cell.traffic = dict(cell.traffic, history=256, tree=128)
    out = hc.run(cell, 2**31 + 77, 2.0, True, time.perf_counter(),
                 log=lambda *_: None, grace_s=3.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["dist_gap"]["value"] <= 1e-4
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    for m in out["metrics"].values():
        assert m["value"] > 0
