"""Median time a request waits in the engine's queue: its ``req.queued``
span, from ``submit`` to being seeded into a lane, over every request of
the window (``repro.obs.Timeline``, joined by request id)."""

from harness import spans


def read(run):
    return spans.median_request_ms(run, "req.queued")
