"""Tests of the readers of the serving engine's spans, on hand-made events.

Run by hand from the checkout root, like the harness's own tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each reader gets a timeline of Chrome trace events (``ts``/``dur`` in
microseconds) whose reading is known, and a timeline without its spans,
on which it reads None.
"""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cell as hc  # noqa: E402
from harness.spec import load_reader  # noqa: E402


def _x(name, ts, dur):
    return {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur)}


def _pair(name, rid, t0, t1):
    return [{"ph": "b", "name": name, "id": rid, "ts": float(t0),
             "args": {"tenant": "default"}},
            {"ph": "e", "name": name, "id": rid, "ts": float(t1)}]


# Two ticks inside the window (0 s to 1 s) and one after it.  Tick 1:
# two fetches (300 + 200 us), a retirement whose lanes loop (2,000 us)
# holds a rebuild (1,500 us), a refill with admission 700 us and lanes
# 400 us.  Tick 2: one fetch (100 us), refill lanes 600 us, admission
# 900 us.  The tick after the window has spans of every name.
TICKS = [
    _x("tick", 0, 10_000),
    _x("tick.jit", 100, 2_000),
    _x("tick.fetch", 2_200, 300),
    _x("tick.fetch", 2_600, 200),
    _x("tick.retire", 2_900, 2_500),
    _x("retire.results", 2_950, 300),
    _x("retire.lanes", 3_300, 2_000),
    _x("hot.rebuild", 3_400, 1_500),
    _x("tick.refill", 5_500, 1_200),
    _x("refill.queue", 5_510, 50),
    _x("refill.admit", 5_570, 700),
    _x("refill.lanes", 6_280, 400),
    _x("tick", 20_000, 5_000),
    _x("tick.jit", 20_100, 1_000),
    _x("tick.fetch", 21_200, 100),
    _x("tick.refill", 21_400, 1_600),
    _x("refill.admit", 21_450, 900),
    _x("refill.lanes", 22_400, 600),
    _x("tick", 2_000_000, 5_000),
    _x("tick.fetch", 2_000_100, 4_000),
    _x("retire.lanes", 2_000_100, 4_000),
    _x("refill.admit", 2_000_100, 4_000),
]


def _run(events, book=None):
    return types.SimpleNamespace(timeline=events, window=(0.0, 1.0),
                                 book=book)


@pytest.mark.parametrize("metric, expected_ms", [
    ("fetch_ms.sat", (300 + 200 + 100) / 2 * 1e-3),
    ("fetch_ms.paced", (300 + 200 + 100) / 2 * 1e-3),
    ("lane_host_ms.sat", (2_000 - 1_500 + 400 + 600) / 2 * 1e-3),
    ("lane_host_ms.paced", (2_000 - 1_500 + 400 + 600) / 2 * 1e-3),
    ("admit_host_ms.sat", (700 + 900) / 2 * 1e-3),
    ("admit_host_ms.paced", (700 + 900) / 2 * 1e-3),
])
def test_per_tick_reader_on_hand_made_spans(metric, expected_ms):
    read = load_reader(metric)
    assert read(_run(TICKS)) == pytest.approx(expected_ms, rel=1e-12)
    # a program without the engine's inner spans (only tick, tick.jit,
    # tick.retire and tick.refill) reads None, not 0
    parent = [e for e in TICKS if e["name"] in
              ("tick", "tick.jit", "tick.retire", "tick.refill")]
    assert read(_run(parent)) is None
    assert read(_run([])) is None


def _book(n):
    book = hc.Book(10, 4)
    book.n, book._rid0 = n, 100
    return book


@pytest.mark.parametrize("metric, name", [("queue_wait_ms", "req.queued"),
                                          ("lane_ms", "req.lane")])
def test_request_reader_joins_by_request_id(metric, name):
    read = load_reader(metric)
    # requests 100..104 are the book's; 99 and 105 are not and are
    # much longer, so a reading that took them would move the median
    events = list(TICKS)
    for rid, (t0, t1) in {99: (0, 90_000), 100: (10, 2_010),
                          101: (20, 4_020), 102: (30, 1_030),
                          103: (40, 8_040), 104: (50, 3_050),
                          105: (60, 90_060)}.items():
        events += _pair(name, rid, t0, t1)
    other = "req.lane" if name == "req.queued" else "req.queued"
    events += _pair(other, 102, 0, 500_000)
    assert read(_run(events, _book(5))) == pytest.approx(3.0, rel=1e-12)
    # an unfinished pair (no "e") is left out
    events = [e for e in events if not (e.get("id") == 103
                                        and e["name"] == name
                                        and e["ph"] == "e")]
    assert read(_run(events, _book(5))) == pytest.approx(
        (2.0 + 3.0) / 2, rel=1e-12)
    assert read(_run(TICKS, _book(5))) is None
    assert read(_run(events, _book(0))) is None
