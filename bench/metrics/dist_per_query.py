"""Distance evaluations per query, hot phase and full phase together.

Read from the engine's per-query trace log (``ObsConfig(trace_rate=1)``)
over every request of the window: the paper's hardware-independent
measure of search work.
"""

TRACE_LOG = True


def read(run):
    book = run.book
    vals = []
    for i in range(book.n):
        t = run.traces.get(book.rid(i))
        if t is not None:
            vals.append(t["hot_dist_evals"] + t["full_dist_evals"])
    return sum(vals) / len(vals) if vals else None
