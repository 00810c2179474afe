"""The measured window: what is switched on when it opens and read when
it closes — the compile counter, the program's counters as deltas, the
profiler (traced runs only) and the device's peak memory."""

import shutil
import tempfile
import time

from . import trace as trace_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles = [0]
_listening = [False]


def _on_duration(event, duration, **_kw):
    if event == COMPILE_EVENT:
        _compiles[0] += 1


def listen_for_compiles():
    """A listener cannot be taken back, so add it once a process."""
    import jax.monitoring
    if not _listening[0]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening[0] = True


def program_counters() -> dict:
    """Every counter of the program's registry, summed over its label
    series. Histograms are NOT read: their bucket edges are no source
    for a percentile."""
    from paddle_tpu import observability as obs
    out = {}
    for name, fam in obs.registry().snapshot()["metrics"].items():
        if fam["type"] == "counter":
            out[name] = float(sum(s["value"] for s in fam["series"]))
    return out


def span(name: str):
    """A host span of the benchmark's own, visible in the device trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Window:
    """``open()`` ... ``close()`` around the measured loop. After
    ``close``: ``seconds`` (host clock), ``counters`` (deltas of the
    program's counters and ``compiles``), and for a traced run
    ``device_ops``/``host_spans`` of the profiler's trace."""

    def __init__(self, traced: bool):
        self.traced = bool(traced)
        self.t_open = self.t_close = None
        self.counters = {}
        self.device_ops, self.host_spans = {}, []
        self._dir = None

    def open(self):
        import jax
        listen_for_compiles()
        self._before = program_counters()
        self._compiles = _compiles[0]
        if self.traced:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._dir)
        self.t_open = time.perf_counter()
        return self.t_open

    def elapsed(self) -> float:
        return time.perf_counter() - self.t_open

    def close(self, at=None):
        """``at``: where the loop says the window ended (the end of its
        last whole step), if not now."""
        import jax
        self.t_close = time.perf_counter() if at is None else at
        if self.traced:
            jax.profiler.stop_trace()
            try:
                path = trace_lib.find_xplane(self._dir)
                if path is not None:
                    self.device_ops, self.host_spans = \
                        trace_lib.read_xplane(path)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        after = program_counters()
        self.counters = {k: v - self._before.get(k, 0.0)
                         for k, v in after.items()}
        self.counters["compiles"] = float(_compiles[0] - self._compiles)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open
