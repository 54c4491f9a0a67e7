"""The reader of the engine's overlapped retirement, on hand-made events.

Run by hand from the checkout root, like the harness's own tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness.spec import load_reader  # noqa: E402


def _x(name, ts, dur):
    return {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur)}


# Three ticks inside the window (0 s to 1 s), one after it.  Tick 1
# retires 3,000 us with the next tick running, tick 2 retires before its
# refill (an Alg-2 rebuild fell due: no overlap), tick 3 overlaps 1,000 us.
TICKS = [
    _x("tick", 0, 10_000),
    _x("tick.refill", 1_000, 2_000),
    _x("tick.jit", 3_100, 100),
    _x("tick.overlap", 4_000, 3_000),
    _x("tick.retire", 4_000, 3_000),
    _x("tick", 20_000, 10_000),
    _x("tick.retire", 20_500, 5_000),
    _x("tick.refill", 26_000, 2_000),
    _x("tick", 40_000, 5_000),
    _x("tick.overlap", 41_000, 1_000),
    _x("tick", 2_000_000, 5_000),
    _x("tick.overlap", 2_000_100, 4_000),
]


@pytest.mark.parametrize("metric", ["overlap_ms.sat", "overlap_ms.paced"])
def test_overlap_reader_is_the_per_tick_mean(metric):
    read = load_reader(metric)
    run = types.SimpleNamespace(timeline=TICKS, window=(0.0, 1.0))
    assert read(run) == pytest.approx((3_000 + 1_000) / 3 * 1e-3,
                                      rel=1e-12)
    # the serial loop records no tick.overlap span: None, not 0
    serial = [e for e in TICKS if e["name"] != "tick.overlap"]
    assert read(types.SimpleNamespace(timeline=serial,
                                      window=(0.0, 1.0))) is None
