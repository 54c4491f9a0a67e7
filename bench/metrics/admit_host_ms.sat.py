"""Host time per engine tick in admission: the ``refill.admit`` span (the
stacked hot tables, the hot phase, its features, the seeding and the
admission dispatch), averaged over the ticks of the window
(``repro.obs.Timeline``)."""

from harness import spans


def read(run):
    t0, t1 = run.window
    return spans.per_tick_ms(run.timeline, ("refill.admit",), t0 * 1e6,
                             t1 * 1e6)
