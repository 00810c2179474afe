"""Decode program cache: one compiled step per serving configuration.

The serving hot path dispatches the SAME program millions of times; what
varies between deployments is the model, the batch bucket, the page
budget, the dtype, and the flag settings. This module keys compiled
decode steps on exactly that tuple so:

  - a re-created :class:`~paddle_tpu.generation.serving.ServingEngine`
    over the same model re-uses the already-compiled step (no retrace on
    re-admission — jax.jit caches per *callable*, so a fresh engine
    building a fresh closure used to recompile from scratch);
  - ``fused_multi_transformer`` / ``masked_multihead_attention`` decode
    calls run one cached compiled program instead of dispatching their
    op chains eagerly per token;
  - flag resolution happens ONCE at program-build time (the flag tuple
    is part of the key), never per decode step.

Keys are structural — a model's signature is its class plus the
name/shape/dtype tree of its state — so two same-config model instances
share one program; the weights always travel as traced arguments, never
as baked-in constants.

Lifetime note: the cache never evicts. The fused decode step is a pure
function of its param dicts, but the GENERIC and PREFILL builders close
over the model object (functional_call needs the Layer structure), so a
cached generic program keeps that model — weights included — alive for
the process. A serving process that retires a model and loads a
replacement should call :func:`clear_decode_program_cache` (the
replacement re-compiles once and re-caches).

Every cached program carries a trace probe: the builder receives a
``note_trace`` callback to call INSIDE the traced python body, which
executes only when jax actually (re)traces. ``trace_count(key)`` is the
retrace regression test surface (the acceptance criterion "zero retraces
across repeated step() calls" asserts it stays at 1).

Build and run are separate steps. ``jax.jit`` traces, compiles and runs
a new argument signature inside one call, where a compiler refusal and a
device fault on the first run cannot be told apart; so ``get`` hands out
a :class:`_Program` that builds each new signature itself
(``jitted.lower(*args).compile()``) and then runs the executable. Only
the build step raises :class:`ProgramBuildError` (the tracer's or
compiler's own exception chained as its cause): that failure is
deterministic, so the serving recovery seams let it through instead of
replaying it. Whatever the executable raises when it runs — the first
time included — is a dispatch fault and propagates unchanged. Steady
state is one dict lookup in front of the executable's own dispatch.

Telemetry: hits/misses/traces mirror onto the process metrics registry,
and every build is charged its wall clock to a per-kind compile-time
histogram — a retrace regression shows up with a COST attached, not
just a count.

Memwatch (``FLAGS_memwatch``): every build banks the executable's
``CompiledMemoryStats`` into
``program_memory_bytes{kind,bucket,extra,section}`` — each cached
program carries a memory signature next to its compile-time counter
(see ``paddle_tpu/observability/memory.py``).
"""

from __future__ import annotations

import hashlib
import re
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

__all__ = ["DecodeKey", "DecodeProgramCache", "ProgramBuildError",
           "decode_program_cache", "clear_decode_program_cache",
           "model_signature"]


class DecodeKey(NamedTuple):
    """(model signature, batch bucket, page budget, dtype, flag tuple) —
    plus ``kind`` to separate the program families sharing the cache and
    ``extra`` for kind-specific geometry (the chunked-prefill programs
    key on their chunk length here; empty for the classic kinds)."""
    kind: str                 # decode_fused | decode_generic | prefill | ...
    model_sig: str
    batch_bucket: int
    page_budget: Tuple        # (num_pages, page_size, max_pages_per_seq)
    dtype: str
    flags: Tuple              # flags.snapshot(...).as_tuple()
    extra: Tuple = ()         # kind-specific, e.g. (chunk_len,)


class ProgramBuildError(RuntimeError):
    """A cached program failed while it was built — in its traced python
    body, its lowering or its compile (a Mosaic refusal, VMEM or HBM
    over budget). ``__cause__`` carries the original exception."""

    def __init__(self, key: DecodeKey):
        super().__init__(
            f"{key.kind} program (bucket {key.batch_bucket}, extra "
            f"{key.extra}) failed to trace/compile; see the chained cause")
        self.key = key


# default object.__repr__ embeds a memory address: "<X object at 0x7f..>"
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def model_signature(model) -> str:
    """Structural identity of a model: class + config + the full
    name/shape/dtype tree of params and buffers, digested. Captures
    everything that changes the traced program; weight VALUES are traced
    arguments and deliberately excluded.

    The config repr is canonicalized: a config member with a default
    ``object.__repr__`` embeds its memory address, which would mint a
    DISTINCT signature per instance — silently defeating cross-engine
    program sharing and splitting telemetry ``model`` labels. Addresses
    carry no structural identity, so they are zeroed out of the repr."""
    cfg_repr = _ADDR_RE.sub("0x0", repr(getattr(model, "config", None)))
    parts = [type(model).__name__, cfg_repr,
             f"training={getattr(model, 'training', False)}"]
    for name, t in sorted(model.named_parameters()):
        parts.append(f"{name}:{tuple(t.shape)}:{t.dtype}")
    for name, t in sorted(model.named_buffers()):
        if t is not None:
            parts.append(f"b:{name}:{tuple(t.shape)}:{t.dtype}")
    return hashlib.md5("|".join(parts).encode()).hexdigest()


def _key_tp(key: DecodeKey) -> str:
    """Tensor-parallel degree a key was built under, as a label value.
    The degree rides ``extra`` as a ``("tp", n)`` pair ONLY when the
    engine is armed (tp > 1), so every tp=1 key — and every pre-tp key —
    resolves to the default "1" without a schema change."""
    for item in key.extra:
        if isinstance(item, tuple) and len(item) == 2 and item[0] == "tp":
            return str(item[1])
    return "1"


class _Program:
    """One key's jitted step, as ``get`` hands it out: called like the
    jitted function, it builds an executable for each new argument
    signature and runs that.

    The key fixes every container argument (weights and pools ride
    ``model_sig`` / ``page_budget`` / ``dtype``), so the executable is
    chosen by the shapes of the top-level array arguments alone — a
    monolithic prefill's prompt length is the one thing that varies
    under a key. An executable checks its own avals: anything else that
    differs is refused with a TypeError, never silently retraced."""

    __slots__ = ("_cache", "key", "_jitted", "_exes")

    def __init__(self, cache: "DecodeProgramCache", key: DecodeKey, jitted):
        self._cache = cache
        self.key = key
        self._jitted = jitted
        self._exes: Dict[Tuple, Any] = {}

    def __call__(self, *args):
        sig = tuple([a.shape for a in args if hasattr(a, "shape")])
        exe = self._exes.get(sig)
        if exe is None:
            exe = self._exes[sig] = self._cache._build(
                self.key, self._jitted, args)
        return exe(*args)


class DecodeProgramCache:
    """Thread-safe keyed cache of compiled decode steps with per-key
    trace counting."""

    def __init__(self):
        from .. import observability as obs
        from ..testing import faults

        # build-path fault injection (FLAGS_fault_inject
        # 'program_build:...'): bound at cache construction; use
        # clear_decode_program_cache() to re-arm after a flag change
        self._f_build = faults.site("program_build")
        self._lock = threading.Lock()
        self._programs: Dict[DecodeKey, _Program] = {}
        self._trace_counts: Dict[DecodeKey, int] = {}
        self._compile_seconds: Dict[DecodeKey, float] = {}
        # key -> jax.stages.Lowered of its newest build
        self._lowered: Dict[DecodeKey, Any] = {}
        self.hits = 0
        self.misses = 0
        self.traces = 0     # every key's (re)traces, never reset
        # memwatch (FLAGS_memwatch): every build additionally banks
        # the executable's CompiledMemoryStats
        self._memwatch = obs.memory.enabled()
        r = obs.registry()
        self._m_hits = r.counter(
            "program_cache_hits",
            "decode program cache admissions served from cache")
        self._m_misses = r.counter(
            "program_cache_misses",
            "decode program cache admissions that built a program")
        self._m_traces = r.counter(
            "program_cache_traces",
            "jax (re)traces of cached programs (steady state: one "
            "per key); model = signature prefix, so two models' "
            "programs — or a fleet serving several — never share "
            "a series; tp = tensor-parallel degree from the key "
            "(\"1\" unless the engine sharded the program)",
            labels=("kind", "model", "tp"))
        self._m_compile = r.histogram(
            "program_cache_compile_seconds",
            "wall clock of program builds — trace + lower + "
            "compile cost per program kind, model and tp degree",
            labels=("kind", "model", "tp"))

    def get(self, key: DecodeKey,
            builder: Callable[[Callable[[], None]], Any]):
        """Return the step for ``key``, admitting it on first use.
        ``builder(note_trace)`` must return the jitted callable and
        arrange for ``note_trace()`` to run inside the traced body — it
        then fires exactly once per (re)trace. Nothing compiles here:
        the returned :class:`_Program` builds when it is first called."""
        with self._lock:
            fn = self._programs.get(key)
            if fn is not None:
                self.hits += 1
                self._m_hits.inc()
                return fn
        self._f_build.check(kind=key.kind)   # injected build failure
        # may be slow: build unlocked
        fn = _Program(self, key, builder(self._tracer(key)))
        with self._lock:
            cur = self._programs.setdefault(key, fn)
            if cur is fn:
                self.misses += 1
                self._m_misses.inc()
            else:
                self.hits += 1               # lost a benign build race
                self._m_hits.inc()
            return cur

    def _tracer(self, key: DecodeKey) -> Callable[[], None]:
        def note_trace():
            # runs INSIDE the traced python body, so it fires exactly
            # once per (re)trace — a host-side trace-TIME write, which
            # is the deliberate exception to "no telemetry under trace"
            with self._lock:
                self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
                self.traces += 1
            self._m_traces.labels(kind=key.kind,
                                  model=key.model_sig[:8],
                                  tp=_key_tp(key)).inc()
        return note_trace

    def _build(self, key: DecodeKey, jitted, args):
        """Trace, lower and compile ``jitted`` at ``args`` (donation is
        declared, nothing is consumed until the executable runs). A
        failure here is the one thing that is a
        :class:`ProgramBuildError`; the build is charged to the compile
        histogram and, with memwatch on, its CompiledMemoryStats are
        banked."""
        from .. import observability as obs

        t0 = time.perf_counter()
        try:
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        except Exception as exc:
            raise ProgramBuildError(key) from exc
        dt = time.perf_counter() - t0
        with self._lock:
            self._lowered[key] = lowered
            self._compile_seconds[key] = (
                self._compile_seconds.get(key, 0.0) + dt)
        model = key.model_sig[:8]
        self._m_compile.labels(kind=key.kind, model=model,
                               tp=_key_tp(key)).observe(dt)
        if self._memwatch:
            obs.memory.capture_compiled(key.kind, key.batch_bucket,
                                        key.extra, compiled, model=model)
        return compiled

    def lowered(self, key: DecodeKey):
        """``jax.stages.Lowered`` of ``key``'s newest build — the probe
        that shows WHICH path a compiled program took (``.as_text()``
        names a Pallas kernel as ``tpu_custom_call``; ``.compile()``
        gives memory and collectives)."""
        with self._lock:
            return self._lowered[key]

    def trace_count(self, key: DecodeKey) -> int:
        with self._lock:
            return self._trace_counts.get(key, 0)

    def compile_seconds(self, key: DecodeKey) -> float:
        """Accumulated build wall clock banked for ``key``."""
        with self._lock:
            return self._compile_seconds.get(key, 0.0)

    def keys(self) -> List[DecodeKey]:
        """Every key with a cached program (admission order) — the live
        census ``tools/telemetry_dump.py --programs`` renders."""
        with self._lock:
            return list(self._programs)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "programs": len(self._programs),
                    "traces": dict(self._trace_counts),
                    "compile_seconds": dict(self._compile_seconds)}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._trace_counts.clear()
            self._compile_seconds.clear()
            self._lowered.clear()
            self.hits = self.misses = 0


_GLOBAL: Optional[DecodeProgramCache] = None
_GLOBAL_LOCK = threading.Lock()


def decode_program_cache() -> DecodeProgramCache:
    """The process-wide decode program cache."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = DecodeProgramCache()
        return _GLOBAL


def traces_built() -> int:
    """(Re)traces of the process-wide cache's programs so far: one
    attribute read, no lock — a per-step baseline (the engine's step
    clock says how many programs a slow step built)."""
    cache = _GLOBAL
    return cache.traces if cache is not None else 0


def clear_decode_program_cache() -> None:
    """Drop every cached program AND the cache instance itself, so the
    next :func:`decode_program_cache` call rebinds its fault site and
    memwatch gate under the current flags."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.clear()
        _GLOBAL = None
