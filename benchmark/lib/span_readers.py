"""Readers of the program's own spans and counters.

The program's spans (``engine.*``, ``request.*``, ``train.*``) enter a
``jax.profiler.TraceAnnotation``, so a traced run holds them in
``ctx["host_spans"]`` on the clock of ``ctx["device_ops"]``; what is
asked of them here is the device's busy time under a span and a span's
time outside its children. The profiler keeps a span's name and not its
arguments, so a step's phases are found by containment in time: the
spans that lie inside an ``engine.step`` span are that step's.

One record never reaches the profiler, ``request.first_token`` (it is
retroactive): that reader takes the program's span ring.

On a program that has no such span or counter every reader returns
None, and the line leaves the metric out.
"""

import bisect

from . import flops, stats, trace
from .registry import reader

PROGRAM_PREFIXES = ("engine.", "request.", "train.")


def _first_device_busy(ctx):
    """Merged busy intervals (ns) of the first device, as ``idle_gaps``
    takes it."""
    ops = ctx.get("device_ops")
    if not ops:
        return None
    return trace.union((s, s + d) for _, s, d in ops[sorted(ops)[0]])


def _named(ctx, name):
    """(start, end) in ns of the host spans called ``name``, by start."""
    return sorted((s, s + d) for n, s, d in ctx.get("host_spans") or ()
                  if n == name)


def _inside(spans, lo, hi):
    """Those of the sorted ``spans`` that lie wholly in [lo, hi]."""
    at = bisect.bisect_left(spans, (lo, lo))
    out = []
    while at < len(spans) and spans[at][0] <= hi:
        if spans[at][1] <= hi:
            out.append(spans[at])
        at += 1
    return out


def _busy_in(busy, lo, hi):
    """ns of the merged intervals ``busy`` that fall in [lo, hi]: an op
    that straddles an edge counts by the part inside."""
    at = max(bisect.bisect_right(busy, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while at < len(busy) and busy[at][0] < hi:
        total += max(0.0, min(busy[at][1], hi) - max(busy[at][0], lo))
        at += 1
    return total


def _median_ms(values_ns):
    return stats.percentile([v / 1e6 for v in values_ns], 50)


@reader("gap_attributed")
def gap_attributed(ctx):
    """Of the first device's idle seconds that have a cause (all gaps
    but ``short_gaps``), the share that ``idle_gaps`` gives to a span of
    the PROGRAM and not to one of the benchmark's or to none."""
    if not ctx.get("device_ops"):
        return None
    gaps = trace.idle_gaps(ctx["device_ops"], ctx.get("host_spans") or [])
    caused = sum(v for k, v in gaps.items() if k != "short_gaps")
    if not caused:
        return None
    mine = sum(v for k, v in gaps.items() if k.startswith(PROGRAM_PREFIXES))
    return 100.0 * mine / caused


@reader("host_per_step")
def host_per_step(ctx):
    """Per ``engine.step`` span, its length less the device's busy time
    inside it: what the host adds to a step. Median over the steps."""
    busy, steps = _first_device_busy(ctx), _named(ctx, "engine.step")
    if busy is None or not steps:
        return None
    return _median_ms([(b - a) - _busy_in(busy, a, b) for a, b in steps])


@reader("sched_per_step")
def sched_per_step(ctx):
    """Per ``engine.step`` span, the scheduler's own time: the length of
    ``engine.schedule`` plus the self time of ``engine.admit`` (its
    length less the ``request.prefill`` spans inside it). Median."""
    steps = _named(ctx, "engine.step")
    if not steps:
        return None
    schedule, admit, prefill = (_named(ctx, n) for n in (
        "engine.schedule", "engine.admit", "request.prefill"))
    per_step = []
    for a, b in steps:
        t = sum(e - s for s, e in _inside(schedule, a, b))
        for s, e in _inside(admit, a, b):
            t += (e - s) - sum(pe - ps for ps, pe in _inside(prefill, s, e))
        per_step.append(t)
    return _median_ms(per_step)


def _decode_busy(ctx):
    """Device busy ns inside each ``engine.decode_step`` span."""
    busy, spans = _first_device_busy(ctx), _named(ctx, "engine.decode_step")
    if busy is None or not spans:
        return None
    return [_busy_in(busy, a, b) for a, b in spans]


@reader("decode_device")
def decode_device(ctx):
    """Device busy time inside an ``engine.decode_step`` span, median."""
    per_step = _decode_busy(ctx)
    return None if per_step is None else _median_ms(per_step)


@reader("decode_floor_in_span")
def decode_floor_in_span(ctx):
    """The decode steps' byte floor over the device time that ran under
    a decode dispatch: each step must read the weights once and the
    cached tokens of its decoding rows once (the engine's own count), at
    the published HBM bandwidth. Prefill's device time is outside the
    spans, so it is not in the denominator."""
    per_step = _decode_busy(ctx)
    s = ctx["scalars"]
    steps, live = (s.get("serving_decode_steps"),
                   s.get("serving_decode_live_tokens"))
    if per_step is None or not steps or live is None:
        return None
    in_span_s = sum(per_step) / 1e9
    if not in_span_s:
        return None
    need = (steps * flops.weight_bytes(ctx["sizes"])
            + live * flops.kv_bytes_per_token(ctx["sizes"]))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / in_span_s


@reader("ring_span")
def ring_span(ctx, name, q):
    """Percentile ``q`` of the lengths (ms) of the ring's records called
    ``name`` that began in the window. The loops clear the ring as the
    window opens, and it ends at the ring's newest record. A ring that
    is full may have dropped its oldest records: then nothing is read."""
    from paddle_tpu import observability as obs
    tr = obs.tracer()
    events = [e for e in tr.events() if e["ph"] == "X"]
    if not events or len(tr) >= getattr(tr, "capacity", 0):
        return None
    w1 = max(e["ts"] + e["dur"] for e in events)
    w0 = w1 - ctx["scalars"]["window_s"] * 1e6
    return stats.percentile([e["dur"] / 1e3 for e in events
                             if e["name"] == name and e["ts"] >= w0], q)
