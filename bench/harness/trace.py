"""From a profiler trace and host spans to per-layer numbers.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain tuples; everything after it works on those tuples alone, so
the reduction can be checked on a small recorded trace:

* device ops: ``(start_ns, dur_ns, op, module)`` for every op that ran
  on a device plane (``/device:TPU:<i>``), with ``module`` the
  executable it belongs to, e.g. ``jit_tick``.  A trace with no device
  plane is an error;
* host annotations: ``(start_ns, dur_ns, name)`` of the :data:`SYNC`
  ``jax.profiler.TraceAnnotation`` on the host plane.

Host spans of ``repro.obs.Timeline`` are on ``time.perf_counter``; one
annotation, :data:`SYNC`, taken at a known ``perf_counter`` reading,
gives the offset between the two clocks.
"""

from __future__ import annotations

import bisect
import re

__all__ = ["SYNC", "extract", "clock_offset_ns", "busy_ns", "module_seconds",
           "idle_gaps", "label_gaps", "span_self_ms"]

SYNC = "bench.sync"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def _module_name(name: str) -> str:
    return _MODULE_SUFFIX.sub("", name)


def _op_name(name: str) -> str:
    """``%fusion.103`` out of ``%fusion.103 = f32[...] fusion(...)``."""
    return name.split(" = ", 1)[0]


def extract(path: str) -> dict:
    """``{"ops": [...], "host": [...], "devices": int}`` from an xplane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, host, n_dev = [], [], 0
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            n_dev += 1
            modules, plane_ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         _module_name(e.name)) for e in line.events)
                elif line.name == "XLA Ops":
                    plane_ops = [(e.start_ns, e.duration_ns,
                                  _op_name(e.name)) for e in line.events]
            starts = [m[0] for m in modules]
            for s, d, name in plane_ops:
                i = bisect.bisect_right(starts, s) - 1
                mod = modules[i][2] if i >= 0 and s < modules[i][1] \
                    else "?"
                ops.append((s, d, name, mod))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC:
                        host.append((e.start_ns, e.duration_ns, e.name))
    if not n_dev:
        raise ValueError(f"no /device:TPU:<i> plane in {path}: planes "
                         f"{[p.name for p in pd.planes]}")
    ops.sort()
    host.sort()
    return {"ops": ops, "host": host, "devices": n_dev}


def clock_offset_ns(host: list, sync_perf_s: float) -> float:
    """Trace time minus ``perf_counter`` time, in nanoseconds."""
    for s, _, name in host:
        if name == SYNC:
            return s - sync_perf_s * 1e9
    raise ValueError(f"no {SYNC} annotation in the trace")


def _clipped(ops, lo: float, hi: float):
    for s, d, *rest in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b, rest


def busy_ns(ops: list, lo: float, hi: float) -> float:
    """Length of the union of the op intervals inside ``[lo, hi]``."""
    total, end = 0.0, lo
    for a, b, _ in sorted(_clipped(ops, lo, hi)):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def module_seconds(ops: list, lo: float, hi: float) -> dict:
    """Device seconds per executable inside ``[lo, hi]``: the union of
    its ops' intervals, since a loop's op encloses the ops of its body."""
    by_mod: dict = {}
    for op in ops:
        by_mod.setdefault(op[3], []).append(op)
    return {mod: busy_ns(mods, lo, hi) * 1e-9
            for mod, mods in by_mod.items()}


def idle_gaps(ops: list, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch of ``[lo, hi]`` with no op
    running, longest first."""
    gaps, end = [], lo
    for a, b, _ in sorted(_clipped(ops, lo, hi)):
        if a > end:
            gaps.append((end, a))
        end = max(end, b)
    if hi > end:
        gaps.append((end, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps


def label_gaps(gaps: list, spans: list, outside: str) -> list:
    """``[label, seconds]`` per gap: the innermost span ``(start, end,
    name)`` open at the gap's midpoint, else ``outside``."""
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        inner = [(e - s, name) for s, e, name in spans if s <= mid < e]
        out.append([min(inner)[1] if inner else outside, (b - a) * 1e-9])
    return out


def span_self_ms(events: list, parent: str, child: str, lo_us: float,
                 hi_us: float):
    """Mean over ``parent`` spans that start in ``[lo_us, hi_us)`` of the
    span's duration minus its ``child`` spans, in ms (Chrome trace events
    with ``ts``/``dur`` in microseconds); None if there is none."""
    parents = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                     if e.get("ph") == "X" and e["name"] == parent
                     and lo_us <= e["ts"] < hi_us)
    if not parents:
        return None
    kids = sorted((e["ts"], e["dur"]) for e in events
                  if e.get("ph") == "X" and e["name"] == child)
    starts = [k[0] for k in kids]
    total = 0.0
    for s, e in parents:
        inside = sum(d for ts, d in
                     kids[bisect.bisect_left(starts, s):
                          bisect.bisect_right(starts, e)])
        total += (e - s) - inside
    return total / len(parents) * 1e-3
