"""Share of the traced stretch in which no operation ran on the device:
1 minus the union of the device op intervals over the stretch."""

from harness import trace


def read(run):
    lo, hi = run.stretch
    if not run.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - trace.busy_ns(run.ops, lo, hi) / (hi - lo))
