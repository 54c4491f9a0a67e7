"""Host time per engine tick spent retiring lanes while the next tick runs
on the device: the ``tick.overlap`` spans (the retirement of a tick's
finished lanes, issued after the refill and the next tick's dispatch),
summed per tick and averaged over the ticks of the window
(``repro.obs.Timeline``).  A program whose retirement runs before the
refill records no such span and reads None."""

from harness import spans


def read(run):
    t0, t1 = run.window
    return spans.per_tick_ms(run.timeline, ("tick.overlap",), t0 * 1e6,
                             t1 * 1e6)
