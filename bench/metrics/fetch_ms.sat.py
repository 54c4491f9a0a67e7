"""Host time per engine tick spent in blocking device-to-host reads: the
``tick.fetch`` spans inside each ``tick`` span, summed per tick and
averaged over the ticks of the window (``repro.obs.Timeline``).

With the trace log on (``trace_rate`` > 0, as in the sat cell's traced
run) two of a retiring tick's four reads are the log's own: the lanes'
``dist_count``/``terminated`` and the last refill's hot-phase counters,
the ``tick.fetch`` spans with ``args.arrays`` 2.  The untraced runs
that measure ``qps`` make neither, so that part cannot move it: on a TPU
v5e it read 1.78-1.87 of 3.82-3.96 ms a tick (PERF.md, section 3).
``fetch_ms.paced`` has no such part: the paced cell's traced run keeps
the log off."""

from harness import spans


def read(run):
    t0, t1 = run.window
    return spans.per_tick_ms(run.timeline, ("tick.fetch",), t0 * 1e6,
                             t1 * 1e6)
