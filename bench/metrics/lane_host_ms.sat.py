"""Host time per engine tick in the per-lane Python loops: the
``retire.lanes`` and ``refill.lanes`` spans, less the Alg-2 rebuilds
(``hot.rebuild``) inside them, summed per tick and averaged over the
ticks of the window (``repro.obs.Timeline``)."""

from harness import spans


def read(run):
    t0, t1 = run.window
    return spans.per_tick_ms(run.timeline, ("retire.lanes", "refill.lanes"),
                             t0 * 1e6, t1 * 1e6, less=("hot.rebuild",))
