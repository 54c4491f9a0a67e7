"""Share of the HBM roofline reached by the hop program (``jit(tick)``).

Bytes the hops of the traced stretch need (``harness.roofline.hop_bytes``:
scored rows times the stored row width of ``harness.roofline.row_bytes``,
plus expanded nodes times their adjacency row, from the lanes' own
distance and hop counters) over the device time of the tick executable
in that stretch times the peak HBM bandwidth.
"""

from harness import roofline, trace

TRACE_LOG = True


def read(run):
    secs = trace.module_seconds(run.ops, *run.stretch).get("jit_tick")
    if not secs:
        return None
    need = roofline.hop_bytes(run.work["dist_evals"], run.work["hops"],
                              roofline.row_bytes(run.cell.config),
                              run.degree)
    return 100.0 * need / (secs * run.peaks["hbm_bytes_per_s"])
