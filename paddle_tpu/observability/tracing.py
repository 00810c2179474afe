"""Span tracer: nested host-side timing events -> Chrome-trace JSON.

``tracer().span("prefill", rid=3)`` is a context manager (and
decorator) that records one complete event — name, wall-clock begin,
duration, thread, its own ``id`` and the ``parent`` id of the span that
was open on the same thread when it began (0 = none) — into a bounded
ring buffer. ``parent`` is what gives a layer its SELF time: a span's
length less the part of it its children cover. The export is the Chrome
``traceEvents`` format (``chrome://tracing`` / Perfetto opens it
directly; both ignore the two extra keys), so a serving run under load
produces a per-request timeline with zero external dependencies.

Interop with the profiler facade: every span also enters a
``jax.profiler.TraceAnnotation`` (the primitive behind
``paddle_tpu.profiler.RecordEvent``), so when a ``jax.profiler`` device
capture is active the same spans land inside the XPlane trace alongside
the XLA events. The reverse direction holds too:
``profiler.RecordEvent`` scopes are mirrored into this ring buffer.

Host-side only, like the metrics registry — a span entered under trace
would time the TRACE, not the execution, and is flagged by tracecheck
rule TRC007.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["SpanTracer", "Span", "tracer"]

try:                                    # the annotation is optional:
    import jax                          # pure-host tools can trace spans
    _ANNOTATION = jax.profiler.TraceAnnotation
except Exception:                       # pragma: no cover - import guard
    _ANNOTATION = None


_IDS = itertools.count(1)       # span ids; next() is atomic under the GIL
_OPEN = threading.local()       # .stack: ids of this thread's open spans


def _open_stack() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


class Span:
    """One timed scope. Context manager; also usable as a decorator
    (``@tracer().span("load")``)."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_ann", "id", "parent")

    def __init__(self, tr: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]] = None):
        self._tracer = tr
        self.name = name
        self.args = args or {}
        self._t0 = 0.0
        self._ann = None
        self.id = self.parent = 0

    def __enter__(self) -> "Span":
        stack = _open_stack()
        self.id = next(_IDS)
        self.parent = stack[-1] if stack else 0
        stack.append(self.id)
        if _ANNOTATION is not None:
            try:
                self._ann = _ANNOTATION(self.name)
                self._ann.__enter__()
            except Exception:           # annotation is best-effort
                self._ann = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        stack = _open_stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        self._tracer._append(self.name, self._t0, t1, self.args,
                             self.id, self.parent)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with Span(self._tracer, self.name, self.args):
                return fn(*a, **kw)
        return wrapper


class SpanTracer:
    """Bounded ring buffer of complete events (Chrome-trace ``"X"``
    phase). Appends are deque ops under the GIL — no lock on the record
    path; ``events()``/exports copy."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from .. import flags
            capacity = int(flags.get_flag("telemetry_ring"))
        self._events: deque = deque(maxlen=max(1, capacity))
        self._pid = os.getpid()

    # ------------------------------------------------------------ record
    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def annotation(self, name: str):
        """A scope for the profiler alone: the ``TraceAnnotation`` a
        :class:`Span` enters, with no ring record. For a phase that
        recurs every step and is read only from a device capture (the
        decode dispatch's three parts): in the ring its records would
        crowd out the ones that are read there."""
        if _ANNOTATION is None:
            return contextlib.nullcontext()
        return _ANNOTATION(name)

    def event(self, name: str, t0: float, t1: float, parent: int = 0,
              **args) -> None:
        """Retroactive complete event from explicit ``perf_counter``
        begin/end stamps (request lifecycle phases whose boundaries were
        observed before the phase name was known). Ring-only: it never
        reaches a ``jax.profiler`` capture. It nests under no span by
        itself — a phase that began before the open span did is not its
        child — so ``parent`` is 0 unless the caller names the span the
        event lies inside."""
        self._append(name, t0, t1, args, next(_IDS), parent)

    def counter(self, name: str, t: float, **values) -> None:
        """Perfetto counter sample (Chrome-trace ``"C"`` phase): each
        key of ``values`` renders as its own counter track aligned with
        the span timeline — how pool bytes/pages-in-use line up against
        the serving steps in one view. One deque append, like spans."""
        self._events.append({
            "name": name, "ph": "C",
            "ts": t * 1e6,
            "pid": self._pid, "tid": threading.get_ident(),
            "args": {k: float(v) for k, v in values.items()},
        })

    def _append(self, name, t0, t1, args, id, parent) -> None:
        self._events.append({
            "name": name, "ph": "X",
            "ts": t0 * 1e6,                       # Chrome wants µs
            "dur": max(0.0, (t1 - t0)) * 1e6,
            "pid": self._pid, "tid": threading.get_ident(),
            "id": id, "parent": parent,
            "args": dict(args),
        })

    # ------------------------------------------------------------ export
    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def chrome_trace(self) -> Dict[str, Any]:
        """The ring as a Chrome-trace/Perfetto JSON object."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        import json
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)

    def clear(self) -> None:
        self._events.clear()

    @property
    def capacity(self) -> int:
        """Records the ring holds; a ring that is FULL may have dropped
        its oldest, so a reader that needs every record of an interval
        checks ``len(tracer) < tracer.capacity`` first."""
        return self._events.maxlen

    def __len__(self) -> int:
        return len(self._events)


_TRACER: Optional[SpanTracer] = None
_TRACER_LOCK = threading.Lock()


def tracer() -> SpanTracer:
    """The process-wide span tracer (ring size from
    ``FLAGS_telemetry_ring`` at first use)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                _TRACER = SpanTracer()
    return _TRACER
