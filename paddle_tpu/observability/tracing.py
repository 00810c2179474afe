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

Beside the ring, the STEP CLOCK: a phase that recurs every step is
accounted, not recorded. ``tracer().phase("engine.decode.dispatch")`` is
a scope that enters the same ``TraceAnnotation`` and, on exit, adds its
length to one :class:`Phase` of that name (calls, seconds, the longest,
and its SELF seconds inside the step now open) — no ring record. Every
:class:`Span` feeds the phase of its own name the same way, so
``tracer().phases()`` accounts a step whole. A stepping object (a
serving engine, a ``TrainStep``) brackets each step with a
:class:`StepClock`; a step much longer than the ones before it leaves
ONE record in ``tracer().slow_steps()`` — its phases, and what the host
thread was doing (CPU time, context switches, collections, builds, the
machine's load, the device's memory) — and one WARNING line.

Host-side only, like the metrics registry — a span entered under trace
would time the TRACE, not the execution, and is flagged by tracecheck
rule TRC007.
"""

from __future__ import annotations

import functools
import gc
import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

try:                                    # not on every platform
    import resource
except ImportError:                     # pragma: no cover - import guard
    resource = None

__all__ = ["SpanTracer", "Span", "Phase", "StepClock", "tracer",
           "SLOW_STEP_MIN_S", "SLOW_STEP_RATIO", "SLOW_STEP_HISTORY",
           "BASELINE_EVERY_S"]

try:                                    # the annotation is optional:
    import jax                          # pure-host tools can trace spans
    _ANNOTATION = jax.profiler.TraceAnnotation
except Exception:                       # pragma: no cover - import guard
    jax = None
    _ANNOTATION = None

_LOG = logging.getLogger(__name__)

# A step is SLOW when it is longer than SLOW_STEP_MIN_S and longer than
# SLOW_STEP_RATIO times the mean of the SLOW_STEP_HISTORY steps before
# it (PERF.md section 7 has the chip runs these were held against: no
# clean window of any cell flags a step, a step that compiles does)
SLOW_STEP_MIN_S = 0.1
SLOW_STEP_RATIO = 3.0
SLOW_STEP_HISTORY = 64
# The thread's CPU time and switch counts are system calls, and dear
# where the kernel is a sandbox's (13-15 us each on the chip tool's
# machine, PERF.md section 6 PR 36): a step's begin takes them anew only
# when the last reading is older than this, so a 5 ms step pays a tenth
# of them and a record says how old its baselines were
BASELINE_EVERY_S = 0.05
# names a Span may open a phase for by itself; past it a new span name
# is recorded in the ring only (a caller that formats names per request)
_MAX_SPAN_PHASES = 256

_now = time.perf_counter        # the clock of every scope and step


_IDS = itertools.count(1)       # span ids; next() is atomic under the GIL


class _Open(threading.local):
    """What this thread has open. ``ids``: the open spans' ids (a
    record's ``parent``). ``stack``: three entries a scope, span or
    phase, innermost last — the phase scope's annotation, the seconds
    its finished children took (what makes a phase's SELF time), its
    begin stamp."""

    def __init__(self):
        self.ids: list = []
        self.stack: list = []


_OPEN = _Open()


class Phase:
    """One phase's account, and the scope that feeds it: ``with
    tracer().phase(name):`` enters the ``TraceAnnotation`` of ``name``
    (a device capture holds it on the device's clock) and on exit adds
    its length here. No ring record, nothing kept per call.

    ``seconds`` and ``longest`` are whole lengths, children included;
    ``in_step`` is the phase's SELF seconds (its length less the scopes
    that closed inside it) in the step now open, so that the phases of
    a step add up to the step."""

    __slots__ = ("name", "calls", "seconds", "longest", "step",
                 "step_seconds", "_tracer")

    def __init__(self, tr: "SpanTracer", name: str):
        self._tracer = tr
        self.name = name
        self.calls = 0
        self.seconds = self.longest = 0.0
        # the step the phase last ran in (as the tracer numbers them),
        # and its self seconds in THAT step: ``in_step`` is the reading
        self.step = -1
        self.step_seconds = 0.0

    # the two methods below run a dozen times a serving step, and under
    # a profiler's Python tracer every call in them is paid again:
    # straight line, one thread-local read, as few calls as it takes
    def __enter__(self) -> "Phase":
        ann = None
        if _ANNOTATION is not None:
            try:
                ann = _ANNOTATION(self.name)
                ann.__enter__()
            except Exception:           # the annotation is best-effort
                ann = None
        stack = _OPEN.stack
        stack += (ann, 0.0, _now())
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _now()
        stack = _OPEN.stack
        ann, inside, t0 = stack[-3:]
        del stack[-3:]
        if ann is not None:
            ann.__exit__(None, None, None)
        self._add(t1 - t0, inside, stack)
        return False

    def _add(self, dt: float, inside: float, stack: list) -> None:
        """A scope of this name closed after ``dt`` seconds, ``inside``
        of them its children's; ``stack`` is what is still open."""
        if stack:
            stack[-2] += dt
        self.calls += 1
        self.seconds += dt
        if dt > self.longest:
            self.longest = dt
        step = self._tracer._step
        if self.step != step:
            self.step = step
            self.step_seconds = 0.0
        self.step_seconds += dt - inside

    @property
    def in_step(self) -> float:
        """Self seconds inside the step now open (0.0 where the phase
        has not run in it)."""
        return (self.step_seconds if self.step == self._tracer._step
                else 0.0)


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
        ids = _OPEN.ids
        self.id = next(_IDS)
        self.parent = ids[-1] if ids else 0
        ids.append(self.id)
        if _ANNOTATION is not None:
            try:
                self._ann = _ANNOTATION(self.name)
                self._ann.__enter__()
            except Exception:           # annotation is best-effort
                self._ann = None
        self._t0 = _now()
        stack = _OPEN.stack
        stack += (self.id, 0.0, self._t0)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _now()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        ids = _OPEN.ids
        if ids and ids[-1] == self.id:
            ids.pop()
        stack = _OPEN.stack
        inside = 0.0
        if stack and stack[-3] == self.id:
            inside = stack[-2]
            del stack[-3:]
        tr = self._tracer
        tr._append(self.name, self._t0, t1, self.args, self.id, self.parent)
        # the account a phase scope feeds: one code path for both
        phase = tr._phases.get(self.name) or tr._span_phase(self.name)
        if phase is not None:
            phase._add(t1 - self._t0, inside, stack)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with Span(self._tracer, self.name, self.args):
                return fn(*a, **kw)
        return wrapper


class _GcClock:
    """Seconds and count of the interpreter's garbage collections: the
    one ``gc.callbacks`` hook, installed with the first step clock."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.count += 1
            self._t0 = None


_GC = _GcClock()


def _thread_usage() -> Tuple[int, int, int]:
    """Voluntary and involuntary context switches and major faults of
    this thread so far (of the process where the platform has no
    per-thread reading; zeros where it has none)."""
    if resource is None:
        return (0, 0, 0)
    who = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
    u = resource.getrusage(who)
    return (u.ru_nvcsw, u.ru_nivcsw, u.ru_majflt)


def _device_memory() -> Tuple[Optional[int], Optional[int]]:
    """``bytes_in_use`` and ``largest_free_block_bytes`` of the first
    local device now (None where the backend reports none)."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:                   # no jax, no device, no stats
        stats = {}
    return stats.get("bytes_in_use"), stats.get("largest_free_block_bytes")


class StepClock:
    """The clock of one stepping object: ``begin()`` and ``end()``
    around each of its steps. ``begin`` opens the step for every
    phase's ``in_step`` and takes its baselines (the system calls among
    them at most every ``BASELINE_EVERY_S``); ``end`` holds
    the step's length against the mean of the ``SLOW_STEP_HISTORY``
    steps before it and, for a SLOW step only, keeps one record in the
    tracer's ``slow_steps()`` and logs it once at WARNING.

    One step is open per tracer at a time: clocks that step in turn on
    one thread (a fleet's replicas) each read their own step's phases;
    steps open on several threads at once share ``in_step``."""

    def __init__(self, tr: "SpanTracer", kind: str):
        self._tracer = tr
        self.kind = kind
        self._lengths: deque = deque(maxlen=SLOW_STEP_HISTORY)
        self._sum = 0.0
        self.open = False
        self.step = -1
        self._base_t = float("-inf")    # when the baselines were read
        if _GC not in gc.callbacks:
            gc.callbacks.append(_GC)

    def begin(self, builds: int = 0) -> None:
        """``builds``: the programs the owner has built so far (a slow
        step's record says how many it built)."""
        self._tracer._step += 1
        self.step = self._tracer._step  # what ``Phase.step`` reads then
        self._gc0 = (_GC.seconds, _GC.count)
        self._builds0 = builds
        self.open = True
        self._t0 = t0 = _now()
        if t0 - self._base_t >= BASELINE_EVERY_S:
            self._base_t = t0
            self._cpu0 = (time.thread_time(), time.process_time())
            self._usage0 = _thread_usage()

    def end(self, step: int, builds: int = 0) -> Tuple[float, float]:
        """Close the open step, numbered ``step`` by its owner, which
        has built ``builds`` programs by now: the step's length, and the
        seconds it ran over the mean it was held against (0.0 unless it
        was slow)."""
        length = _now() - self._t0
        self.open = False
        lengths = self._lengths
        mean = self._sum / len(lengths) if lengths else 0.0
        over = 0.0
        if length > SLOW_STEP_MIN_S and length > SLOW_STEP_RATIO * mean:
            over = length - mean
            self._record(step, length, mean, builds - self._builds0)
        if len(lengths) == lengths.maxlen:
            self._sum -= lengths[0]
        lengths.append(length)
        self._sum += length
        return length, over

    def _record(self, step: int, length: float, mean: float,
                built: int) -> None:
        thread_cpu, process_cpu = time.thread_time(), time.process_time()
        usage = _thread_usage()
        tr = self._tracer
        phases = {p.name: s for p in list(tr._phases.values())
                  if (s := p.in_step) > 0.0}
        in_use, free_block = _device_memory()
        rec = {
            "kind": self.kind, "step": step, "wall_time": time.time(),
            "length_s": length, "mean_s": mean,
            # self seconds: with ``outside_s``, the part of the step
            # under no phase, they add up to the length
            "phases": phases,
            "outside_s": max(0.0, length - sum(phases.values())),
            # the six readings below are over the step AND this many
            # seconds of the steps before it (0.0: read as it began)
            "baseline_age_s": self._t0 - self._base_t,
            "thread_cpu_s": thread_cpu - self._cpu0[0],
            "process_cpu_s": process_cpu - self._cpu0[1],
            "voluntary_switches": usage[0] - self._usage0[0],
            "involuntary_switches": usage[1] - self._usage0[1],
            "major_faults": usage[2] - self._usage0[2],
            "gc_s": _GC.seconds - self._gc0[0],
            "gc_count": _GC.count - self._gc0[1],
            "builds": max(0, built),   # under 0: the cache was dropped
            "loadavg": list(os.getloadavg()),
            "cpus": os.cpu_count(),
            "bytes_in_use": in_use,
            "largest_free_block_bytes": free_block,
        }
        tr._slow.append(rec)
        _LOG.warning("slow %s step %d: %.3f s against a mean of %.4f s "
                     "over %d steps: %s", self.kind, step, length, mean,
                     len(self._lengths), json.dumps(rec, sort_keys=True))


class SpanTracer:
    """Bounded ring buffer of complete events (Chrome-trace ``"X"``
    phase). Appends are deque ops under the GIL — no lock on the record
    path; ``events()``/exports copy. Beside the ring: the phases'
    accounts and the slow steps' records, which the ring's wrap and
    ``clear()`` leave alone."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            from .. import flags
            capacity = int(flags.get_flag("telemetry_ring"))
        self._events: deque = deque(maxlen=max(1, capacity))
        self._pid = os.getpid()
        self._phases: Dict[str, Phase] = {}
        self._step = 0          # the step now open, counted over clocks
        self._slow: deque = deque(maxlen=SLOW_STEP_HISTORY)

    # ------------------------------------------------------------ record
    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    def phase(self, name: str) -> Phase:
        """The account of the phase ``name``, which is also its scope
        (``with tracer().phase(name):``). For a phase that recurs every
        step: in the ring its records would crowd out the ones that are
        read there."""
        phase = self._phases.get(name)
        if phase is None:
            phase = self._phases.setdefault(name, Phase(self, name))
        return phase

    def _span_phase(self, name: str) -> Optional[Phase]:
        if len(self._phases) >= _MAX_SPAN_PHASES:
            return None
        return self.phase(name)

    def step_clock(self, kind: str) -> StepClock:
        """A clock for one stepping object of ``kind`` ("serving",
        "train")."""
        return StepClock(self, kind)

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

    def phases(self) -> Dict[str, Dict[str, float]]:
        """Every phase's account: ``calls``, ``seconds`` and ``longest``
        (whole lengths) since the process began, ``in_step`` (self
        seconds) in the step now open."""
        return {p.name: {"calls": p.calls, "seconds": p.seconds,
                         "longest": p.longest, "in_step": p.in_step}
                for p in list(self._phases.values())}

    def slow_steps(self) -> List[Dict[str, Any]]:
        """The records of the last ``SLOW_STEP_HISTORY`` slow steps,
        oldest first (:class:`StepClock` says what makes one)."""
        return list(self._slow)

    def chrome_trace(self) -> Dict[str, Any]:
        """The ring as a Chrome-trace/Perfetto JSON object."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        import json
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)

    def clear(self) -> None:
        """Empty the ring (the phases and slow steps stay)."""
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
