"""Host time per engine tick in the float32 rerank of retiring lanes: the
``retire.rerank`` spans (the gather of each lane's candidate rows, their
float32 sums and the re-sort), summed per tick and averaged over the ticks
of the window (``repro.obs.Timeline``).  The engine records the span only
when the Full Index is quantized (``rerank_k`` > 0); a program without it
reads None."""

from harness import spans


def read(run):
    t0, t1 = run.window
    return spans.per_tick_ms(run.timeline, ("retire.rerank",), t0 * 1e6,
                             t1 * 1e6)
