"""Profiler facade.

Reference: python/paddle/profiler/profiler.py (+ native CUPTI tracer in
paddle/fluid/platform/profiler/). On TPU both host and device tracing are
owned by jax.profiler (XPlane -> TensorBoard/Perfetto); this facade keeps the
reference's schedule(wait/warmup/active/repeat) + on_trace_ready + RecordEvent
API on top of it.
"""

from __future__ import annotations

import contextlib
import enum
import functools
import os
import time
from typing import Callable, Iterable, Optional

import jax


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference-shaped scheduler factory."""

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback: jax traces land as TensorBoard/Perfetto
    artifacts in ``dir_name``."""

    def handle(prof):
        prof._log_dir = dir_name

    return handle


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


class RecordEvent:
    """User-scope annotation -> jax.profiler.TraceAnnotation, mirrored
    into the observability span ring so RecordEvent
    scopes land in the exported Chrome-trace timeline alongside engine/
    train spans — and observability spans land in jax.profiler captures
    through the same TraceAnnotation primitive."""

    def __init__(self, name: str, event_type=None):
        from ..observability import tracer as _tracer

        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)
        # bound at construction like every other instrumented site
        self._mirror = _tracer().event
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def begin(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        self._ann.__exit__(None, None, None)
        if self._t0 is not None:
            self._mirror(self.name, self._t0, time.perf_counter())
            self._t0 = None


class Profiler:
    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(closed=0, ready=0, record=scheduler[1] - scheduler[0],
                           skip_first=scheduler[0]) if isinstance(scheduler, (tuple, list))
            else (lambda step: ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._log_dir = os.environ.get("PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
        self.timer_only = timer_only
        self._step = 0
        self._active = False
        self._step_times = []
        self._last_t = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        self._last_t = time.perf_counter()
        state = self._scheduler(self._step)
        if not self.timer_only and state in (ProfilerState.RECORD,
                                             ProfilerState.RECORD_AND_RETURN):
            self._start_trace()

    def _start_trace(self):
        if not self._active:
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
            os.makedirs(self._log_dir, exist_ok=True)
            jax.profiler.start_trace(self._log_dir)
            self._active = True

    def _stop_trace(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_t is not None:
            self._step_times.append((now - self._last_t, num_samples))
        self._last_t = now
        self._step += 1
        state = self._scheduler(self._step)
        if self.timer_only:
            return
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_trace()
        else:
            self._stop_trace()

    def step_info(self, unit: str = "samples") -> str:
        if not self._step_times:
            return ""
        dt, n = self._step_times[-1]
        ips = (n / dt) if (n and dt > 0) else (1.0 / dt if dt > 0 else 0.0)
        return f"batch_cost: {dt:.5f} s, ips: {ips:.3f} {unit}/s"

    def stop(self):
        self._stop_trace()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        times = [t for t, _ in self._step_times]
        if not times:
            print("no profiled steps")
            return
        import numpy as np
        arr = np.array(times) * 1000.0
        print("--------- step-time summary (host wall clock) ---------")
        print(f"steps: {len(arr)}  mean: {arr.mean():.3f}ms  p50: {np.percentile(arr, 50):.3f}ms  "
              f"p90: {np.percentile(arr, 90):.3f}ms  max: {arr.max():.3f}ms")
        print(f"device trace (if recorded): tensorboard --logdir {self._log_dir}")

    def export(self, path: str, format: str = "json"):
        print(f"traces are exported by jax.profiler to {self._log_dir}")

    def export_telemetry(self, path: str):
        """Write the observability span ring (engine/train/RecordEvent
        host spans) as Chrome-trace JSON — the host-side companion to
        the jax.profiler device capture in ``self._log_dir``."""
        from ..observability import tracer
        tracer().save(path)


def load_profiler_result(filename: str):
    raise NotImplementedError("load XPlane traces with TensorBoard instead")


import enum as _enum


class SortedKeys(_enum.Enum):
    """reference: paddle.profiler.SortedKeys — summary sort orders."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(_enum.Enum):
    """reference: paddle.profiler.SummaryView."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
