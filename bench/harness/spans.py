"""Per-tick and per-request readings of the engine's timeline.

The engine (``repro.serving.paged_engine``) records its phases as
``repro.obs.Timeline`` events on ``time.perf_counter`` microseconds:
complete ("X") spans nested inside each ``tick`` span, and one "b"/"e"
pair per request and phase of its life (``req.queued``, ``req.lane``),
keyed by the request id.  A program that records none of the spans asked
for reads None, not 0.
"""

from __future__ import annotations

import bisect
import statistics

__all__ = ["per_tick_ms", "request_ms", "median_request_ms"]


def _spans(events: list, names) -> list:
    return sorted((e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                  if e.get("ph") == "X" and e["name"] in names)


def per_tick_ms(events: list, names, lo_us: float, hi_us: float,
                less=()):
    """Mean over the ``tick`` spans that start in ``[lo_us, hi_us)`` of
    the summed duration of the ``names`` spans inside each, less the
    ``less`` spans inside those, in ms; None if the window has no tick or
    the timeline no ``names`` span at all."""
    ticks = [(s, e) for s, e, _ in _spans(events, ("tick",))
             if lo_us <= s < hi_us]
    parts = _spans(events, tuple(names))
    if not ticks or not parts:
        return None
    starts = [s for s, _ in ticks]

    def tick_of(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i if i >= 0 and e <= ticks[i][1] else None

    total = 0.0
    for s, e, _ in parts:
        if tick_of(s, e) is not None:
            total += e - s
    for s, e, _ in _spans(events, tuple(less)):
        if tick_of(s, e) is not None and any(
                ps <= s and e <= pe for ps, pe, _ in parts):
            total -= e - s
    return total / len(ticks) * 1e-3


def request_ms(events: list, name: str, rids) -> list:
    """Durations in ms of the ``name`` "b"/"e" pair of each request id in
    ``rids`` that has one."""
    begin, end = {}, {}
    for e in events:
        if e.get("name") == name:
            if e.get("ph") == "b":
                begin[e["id"]] = e["ts"]
            elif e.get("ph") == "e":
                end[e["id"]] = e["ts"]
    return [(end[r] - begin[r]) * 1e-3 for r in rids
            if r in begin and r in end]


def median_request_ms(run, name: str):
    """Median of :func:`request_ms` over every request of the run's book;
    None if no request has the pair."""
    book = run.book
    vals = request_ms(run.timeline, name,
                      [book.rid(i) for i in range(book.n)])
    return statistics.median(vals) if vals else None
