"""Runtime telemetry: unified metrics registry + span tracing.

The signal layer every serving/perf claim stands on: a process-wide
:mod:`metrics <paddle_tpu.observability.metrics>` registry (counters,
gauges, fixed-exponential-bucket histograms; JSON snapshot + Prometheus
text) and a :mod:`span tracer <paddle_tpu.observability.tracing>`
(nested host-side timing events -> Chrome-trace JSON, mirrored into
``jax.profiler`` captures) with its step clock (every phase of a step
accounted beside the ring; one record and one WARNING line for a step
much longer than the ones before it).

Instrumented subsystems: ``generation.serving.ServingEngine`` (one span
a phase of a step under the step's ``engine.step``, request lifecycle
records sharing a ``rid``, rows/slots/live-token/prefill-token counters
at the dispatch, the step clock's per-phase seconds and slow-step
counters, TTFT/inter-token histograms, queue/occupancy/KV-pool
gauges, prefix-cache counters), ``hapi.train_step.TrainStep`` (in-flight
window depth, sync/throttle/retrace counters, stage/dispatch/throttle/
pull/sync spans, the step clock over the interval from call to call),
``generation.program_cache`` (hit/miss counters, compile wall-time
histograms) and ``io.DevicePrefetcher``. ``tools/telemetry_dump.py``
renders snapshots; ``bench.py`` and the ``tools/*_bench.py`` drivers
embed a snapshot in their ``BENCH_*.json`` output.

There is one binding: every instrumented object binds the live
instruments at construction, always (the benchmark reads the spans and
counters, so "off" was a mode nothing could be measured in; what "on"
costs is inside the cells' run-to-run spread, PERF.md section 6). Only
memwatch's per-program ``memory_analysis()`` keeps a gate of its own,
``FLAGS_memwatch``. The
contract is HOST-SIDE ONLY: a telemetry write must never be reachable
under trace (it would fire once at trace time and freeze, or fail on a
tracer) — tracecheck rule TRC007 enforces this, and additionally
requires an explicit pragma + reason for writes in declared
``# tracecheck: hotpath`` code.

Usage::

    from paddle_tpu import observability as obs

    reqs = obs.registry().counter("my_requests", "requests seen")
    lat = obs.registry().histogram("my_latency_seconds")
    with obs.span("handle", rid=7):
        ...
        lat.observe(dt)
    obs.registry().snapshot()          # JSON-able dict
    obs.to_prometheus()                # text exposition format
    obs.tracer().save("trace.json")    # open in chrome://tracing
"""

from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, LATENCY_BUCKETS,
                      MetricsRegistry, exponential_buckets, registry,
                      series_quantile)
from .tracing import Phase, Span, SpanTracer, StepClock, tracer
from .export import to_prometheus
from . import memory

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "LATENCY_BUCKETS", "exponential_buckets", "registry",
    "series_quantile", "Phase", "Span", "SpanTracer", "StepClock", "tracer",
    "to_prometheus",
    "span", "snapshot", "memory",
]


def span(name: str, **args):
    """Convenience scoped span for warm paths (epoch boundaries,
    loaders). Hot paths pre-bind ``tracer().span`` instead."""
    return tracer().span(name, **args)


def snapshot():
    """The live registry snapshot (JSON-able)."""
    return registry().snapshot()
