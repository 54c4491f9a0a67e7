"""Median time a request spends in a lane: its ``req.lane`` span, from
being seeded to retirement, over every request of the window
(``repro.obs.Timeline``, joined by request id)."""

from harness import spans


def read(run):
    return spans.median_request_ms(run, "req.lane")
