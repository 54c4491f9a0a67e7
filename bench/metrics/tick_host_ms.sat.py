"""Host time per engine tick: the ``tick`` span minus its ``tick.jit``
child (``repro.obs.Timeline``), averaged over the ticks of the window.
In a traced run ``tick.jit`` waits for the device, so what is left is
the host's own work: admission, retirement, refill, housekeeping."""

from harness import trace


def read(run):
    t0, t1 = run.window
    return trace.span_self_ms(run.timeline, "tick", "tick.jit", t0 * 1e6,
                              t1 * 1e6)
