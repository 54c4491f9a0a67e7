"""Host-side span timeline → Chrome trace-event JSON (Perfetto-loadable).

``Timeline.span("tick.jit")`` wraps a region and records a complete
("X"-phase) trace event with microsecond ``time.perf_counter``
timestamps; ``export()`` writes the ``{"traceEvents": [...]}`` document
that chrome://tracing and https://ui.perfetto.dev open directly.  A
disabled timeline returns a shared no-op context manager, so
instrumented code costs one method call per span on the untraced path.

An enabled timeline mirrors every span into the device profile: the span
also opens a ``jax.profiler.TraceAnnotation`` of the same name, so a
``jax.profiler.trace`` capture shows the host phases on its host lane,
on the profiler's clock, beside the device ops.  ``async_span`` records a
"b"/"e" pair keyed by an id (one request's life, which overlaps other
requests and spans many ticks); it is never mirrored.

``device_annotation(name)`` returns that ``TraceAnnotation`` (or a null
context on jax builds without it); code that has no timeline uses it
alone.  Inside *traced* code use ``jax.named_scope`` instead — see
``repro.core.dynamic_search``.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Optional

__all__ = ["Timeline", "device_annotation"]


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span; ``args`` may still be added to before it closes."""

    __slots__ = ("_tl", "_name", "args", "_t0", "_ann")

    def __init__(self, tl: "Timeline", name: str, args: dict):
        self._tl = tl
        self._name = name
        self.args = args

    def __enter__(self):
        self._ann = device_annotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        ev = {"name": self._name, "ph": "X", "cat": "host",
              "ts": self._t0 * 1e6, "dur": (t1 - self._t0) * 1e6,
              "pid": self._tl.pid,
              "tid": threading.get_ident() & 0x7FFFFFFF}
        if self.args:
            ev["args"] = self.args
        self._tl._events.append(ev)
        return False


class Timeline:
    """Bounded span recorder emitting Chrome trace-event JSON."""

    def __init__(self, enabled: bool = True, capacity: int = 65536):
        self.enabled = bool(enabled)
        self.pid = os.getpid()
        self._events: collections.deque = collections.deque(
            maxlen=max(16, int(capacity)))

    def span(self, name: str, **args):
        """Context manager timing a region (no-op when disabled)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "cat": "host",
              "ts": time.perf_counter() * 1e6, "pid": self.pid,
              "tid": threading.get_ident() & 0x7FFFFFFF}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def async_span(self, name: str, id: int, t0: float, t1: float,
                   **args) -> None:
        """A "b"/"e" pair from ``t0`` to ``t1`` (``perf_counter``
        seconds) under ``id``, for a region that overlaps others of its
        name; ``args`` ride on the "b" event.  No-op when disabled."""
        if not self.enabled:
            return
        base = {"name": name, "cat": "request", "id": int(id),
                "pid": self.pid, "tid": threading.get_ident() & 0x7FFFFFFF}
        b = dict(base, ph="b", ts=t0 * 1e6)
        if args:
            b["args"] = args
        self._events.append(b)
        self._events.append(dict(base, ph="e", ts=t1 * 1e6))

    def events(self) -> list:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def export(self, path: Optional[str] = None):
        """The Chrome trace document; written to ``path`` when given."""
        doc = {"traceEvents": list(self._events),
               "displayTimeUnit": "ms"}
        if path is None:
            return doc
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def device_annotation(name: str):
    """``jax.profiler.TraceAnnotation`` or a null context (host-side only —
    wrap the *dispatch* of a jitted call, never code inside a trace)."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()
