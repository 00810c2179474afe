"""memwatch: the HBM/memory observatory — third pillar beside
:mod:`metrics` and :mod:`tracing`.

Answers "where does *memory* go" the way r09 answered "where does time
go", with three instruments sharing one accounting vocabulary:

  1. **Compiled-program capture** — every program admitted by the decode
     program cache and every jitted ``TrainStep`` records its XLA
     ``CompiledMemoryStats`` (argument / output / temp / alias /
     generated-code bytes, plus the derived peak) into the registry as
     ``program_memory_bytes{kind,bucket,extra,section}`` gauges and a
     host-side row table (:func:`program_table`). XLA's buffer
     assignment is the only source of truth for temp/peak. The program
     cache builds its executables itself and hands them over
     (:func:`capture_compiled`, no extra compile); a ``TrainStep``
     dispatches through ``jax.jit``, which exposes no handle to the
     executable it built, so its capture costs ONE duplicate
     ``lower().compile()`` per (re)trace. ``FLAGS_memwatch=0`` drops
     both while keeping the rest of telemetry.
  2. **Live pool ledger** — the serving engine publishes its
     :class:`~paddle_tpu.kernels.paged_attention.PagedKVCache` ledger
     (pages/bytes used, free, shared, pinned; free-list fragmentation)
     as step-end gauges plus a Perfetto counter track, and
     :func:`sample_device_memory` banks backend watermarks
     (``device.memory_stats()`` where the PJRT backend supports it;
     host peak RSS always).
  3. **Analytic estimator / what-if planner** — :func:`estimate_program`
     and :func:`estimate_engine_memory` predict the same sections from
     avals + pool geometry + model dims WITHOUT compiling, for
     configurations too big to build locally ("does 7B int8 + page
     budget P + rung 32 fit in 16 GB?"). The sections that are
     arithmetic (argument, alias, output) are held to
     ``CompiledMemoryStats`` on tier-1-sized programs
     (tests/test_memwatch.py, within 2%); the fitted temp term is held
     by no test.

Everything is host-side (the capture itself runs at trace time, never
under trace). ``FLAGS_memwatch`` gates the compiled-program capture and
nothing else: the ledger and the watermarks are plain telemetry, bound
always. The flag is not in ``PROGRAM_FLAGS`` — toggling it never
recompiles a serving or train program.
"""

from __future__ import annotations

import sys
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "enabled", "stats_from_compiled", "capture_jitted", "capture_program",
    "capture_compiled", "record_program", "program_table",
    "clear_program_table",
    "sample_device_memory", "section",
    "estimate_program", "estimate_decode_program", "estimate_prefill_program",
    "estimate_engine_memory", "fits", "sharded_param_bytes",
    "compare_program_rows", "PoolGeometry", "ModelDims", "weight_bytes",
    "aval_bytes", "MEMWATCH_SCHEMA",
]

MEMWATCH_SCHEMA = 1

# the CompiledMemoryStats sections every surface (gauges, table rows,
# banked artifacts, estimator output) agrees on
SECTIONS = ("argument", "output", "temp", "alias", "generated_code", "peak")

_TABLE: Dict[Tuple[str, str, int, str], Dict[str, Any]] = {}
_TABLE_LOCK = threading.Lock()


def enabled() -> bool:
    """Memwatch capture gate: ``FLAGS_memwatch``. Resolve at
    CONSTRUCTION time (a program cache, a ``TrainStep``)."""
    from .. import flags
    return bool(flags.get_flag("memwatch"))


# --------------------------------------------------------------- capture
def stats_from_compiled(compiled) -> Dict[str, int]:
    """The section dict for one compiled executable (``jax.stages
    .Compiled`` or anything exposing ``memory_analysis()``). ``peak`` is
    derived: arguments + outputs - aliased (donation) + temp + code —
    the resident HBM high-water of one dispatch."""
    ma = compiled.memory_analysis() if hasattr(compiled, "memory_analysis") \
        else compiled
    out = {
        "argument": int(ma.argument_size_in_bytes),
        "output": int(ma.output_size_in_bytes),
        "temp": int(ma.temp_size_in_bytes),
        "alias": int(ma.alias_size_in_bytes),
        "generated_code": int(ma.generated_code_size_in_bytes),
    }
    out["peak"] = (out["argument"] + out["output"] - out["alias"]
                   + out["temp"] + out["generated_code"])
    return out


def capture_jitted(fn, args: Sequence[Any],
                   kwargs: Optional[Dict[str, Any]] = None
                   ) -> Optional[Dict[str, int]]:
    """AOT lower+compile ``fn`` (a jitted callable) at ``args``' avals
    and return the section dict, or None when the backend/lowering
    refuses (abstract avals survive donation, so this works even after
    the dispatch consumed the donated buffers)."""
    try:
        compiled = fn.lower(*args, **(kwargs or {})).compile()
        return stats_from_compiled(compiled)
    except Exception:
        return None


def record_program(kind: str, bucket: int, stats: Dict[str, int],
                   extra: Any = (), model: str = "") -> None:
    """Bank one program's section dict: registry gauges
    ``program_memory_bytes{model,kind,bucket,extra,section}`` (last
    write wins, the gauge contract) plus the host-side row table the
    benches and the regression gate read. ``model`` disambiguates
    same-shaped programs of different models sharing the process (the
    program cache passes a model-signature prefix, TrainStep the model
    class name)."""
    from .metrics import registry
    ex = _extra_str(extra)
    fam = registry().gauge(
        "program_memory_bytes",
        "XLA CompiledMemoryStats of cached compiled programs, by "
        "section (peak = argument + output - alias + temp + code)",
        labels=("model", "kind", "bucket", "extra", "section"))
    for sec in SECTIONS:
        fam.labels(model=model, kind=kind, bucket=str(bucket), extra=ex,
                   section=sec).set(float(stats.get(sec, 0)))
    with _TABLE_LOCK:
        row = _TABLE.setdefault((model, kind, int(bucket), ex), {
            "model": model, "kind": kind, "bucket": int(bucket),
            "extra": ex, "captures": 0})
        row.update({sec: int(stats.get(sec, 0)) for sec in SECTIONS})
        row["captures"] += 1


def capture_program(kind: str, bucket: int, extra: Any, fn,
                    args: Sequence[Any],
                    kwargs: Optional[Dict[str, Any]] = None,
                    model: str = "") -> bool:
    """Capture + record one jitted program (the TrainStep hook).
    Failures are counted, never raised — memory accounting must not
    take down a dispatch that already succeeded."""
    return _bank(kind, bucket, extra, capture_jitted(fn, args, kwargs),
                 model)


def capture_compiled(kind: str, bucket: int, extra: Any, compiled,
                     model: str = "") -> bool:
    """Record an executable the caller already holds (the program cache
    builds its programs explicitly, so nothing compiles twice). Same
    failure contract as :func:`capture_program`."""
    try:
        stats = stats_from_compiled(compiled)
    except Exception:
        stats = None
    return _bank(kind, bucket, extra, stats, model)


def _bank(kind, bucket, extra, stats, model) -> bool:
    if stats is None:
        from .metrics import registry
        registry().counter(
            "memwatch_capture_failures",
            "compiled-memory captures the backend refused",
            labels=("kind",)).labels(kind=kind).inc()
        return False
    record_program(kind, bucket, stats, extra, model=model)
    return True


def program_table() -> List[Dict[str, Any]]:
    """Every captured program's row (sorted, JSON-able) — the artifact
    the benches embed and ``MEMWATCH_*.json`` banks."""
    with _TABLE_LOCK:
        rows = [dict(r) for r in _TABLE.values()]
    return sorted(rows, key=lambda r: (r["model"], r["kind"], r["bucket"],
                                       r["extra"]))


def clear_program_table() -> None:
    with _TABLE_LOCK:
        _TABLE.clear()


TABLE_COLUMNS = ("model", "kind", "bucket", "extra", "argument", "output",
                 "temp", "alias", "peak")


def format_program_table(rows: Sequence[Dict[str, Any]]) -> str:
    """Render program rows as the fixed-width table every CLI view
    shares (``tools/memwatch.py view``, ``tools/telemetry_dump.py
    --memory``) — one renderer, like one accounting path."""
    lines = ["  ".join(f"{h:>14s}" for h in TABLE_COLUMNS)]
    for r in rows:
        lines.append("  ".join(f"{str(r.get(h, '')):>14s}"
                               for h in TABLE_COLUMNS))
    return "\n".join(lines)


def _extra_str(extra: Any) -> str:
    if extra in ((), None, ""):
        return ""
    if isinstance(extra, (tuple, list)):
        return ",".join(str(e) for e in extra)
    return str(extra)


# ---------------------------------------------------- device watermarks
def sample_device_memory(publish: bool = True) -> Dict[str, Any]:
    """Backend memory watermarks where the PJRT backend exposes them
    (``device.memory_stats()`` — TPU/GPU report bytes_in_use /
    peak_bytes_in_use / bytes_limit; CPU returns None), plus the host
    process peak RSS. Publishes ``device_memory_bytes{device,stat}`` /
    ``host_memory_bytes{stat}`` gauges (``publish``) and returns the
    raw JSON-able sample either way."""
    out: Dict[str, Any] = {"devices": {}, "host": {}}
    try:
        import jax
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats:
                out["devices"][str(d.id)] = {
                    k: int(v) for k, v in stats.items()
                    if isinstance(v, (int, float))}
    except Exception:
        pass
    try:
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        # linux reports ru_maxrss in KiB; darwin reports bytes
        scale = 1 if sys.platform == "darwin" else 1024
        out["host"]["peak_rss"] = int(ru.ru_maxrss) * scale
    except Exception:
        pass
    if publish:
        from .metrics import registry
        r = registry()
        if out["devices"]:
            fam = r.gauge("device_memory_bytes",
                          "PJRT device memory watermarks "
                          "(device.memory_stats())",
                          labels=("device", "stat"))
            for dev, stats in out["devices"].items():
                for k, v in stats.items():
                    fam.labels(device=dev, stat=k).set(float(v))
        if out["host"]:
            fam = r.gauge("host_memory_bytes",
                          "host process memory watermarks",
                          labels=("stat",))
            for k, v in out["host"].items():
                fam.labels(stat=k).set(float(v))
    return out


def section() -> Dict[str, Any]:
    """The ``"memory"`` section benches embed next to ``"telemetry"``:
    the captured program table + device/host watermarks. (The live pool
    ledger and the per-program gauges already ride the telemetry
    snapshot itself.)"""
    return {"schema": MEMWATCH_SCHEMA,
            "programs": program_table(),
            "watermarks": sample_device_memory()}


# ------------------------------------------------------------ estimator
# The analytic twin of stats_from_compiled: predict the same sections
# from avals + geometry WITHOUT compiling. Exact for arguments/outputs/
# alias (those are just the avals); temp is a calibrated working-set
# model (XLA's buffer assignment reuses aggressively, so temp is a
# max-live, not a sum of intermediates). Calibration constants below
# were fit against CompiledMemoryStats on the tier-1 CPU programs of one
# jax and no test holds them to anything under another (they read two
# thirds under this one's; ROADMAP Queue 3 item 7).

_DECODE_TEMP_K = 1.25     # decode: full working-set chain stays live-ish
_PREFILL_TEMP_K = 1.0     # prefill/chunk: two largest stage buffers


def aval_bytes(x) -> int:
    """Bytes of one array-like / ShapeDtypeStruct / (shape, dtype)."""
    if isinstance(x, tuple) and len(x) == 2:
        shape, dtype = x
    else:
        shape, dtype = x.shape, x.dtype
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def estimate_program(arg_avals: Sequence[Any], out_avals: Sequence[Any],
                     donated: Sequence[int] = (),
                     temp: int = 0, generated_code: int = 0
                     ) -> Dict[str, int]:
    """Generic donation-aware section estimate from flat aval lists:
    ``donated`` indexes into ``arg_avals``; those bytes alias outputs
    instead of doubling the peak."""
    arg = sum(aval_bytes(a) for a in arg_avals)
    out = sum(aval_bytes(a) for a in out_avals)
    alias = sum(aval_bytes(arg_avals[i]) for i in donated)
    est = {"argument": arg, "output": out, "temp": int(temp),
           "alias": alias, "generated_code": int(generated_code)}
    est["peak"] = arg + out - alias + est["temp"] + est["generated_code"]
    return est


class PoolGeometry:
    """The KV pool shape vocabulary every estimate walks: mirrors
    :class:`PagedKVCache`'s constructor args."""

    __slots__ = ("num_layers", "num_pages", "page_size", "num_kv_heads",
                 "head_dim", "max_pages_per_seq", "dtype", "kv_quant")

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, max_pages_per_seq: int,
                 dtype: Any = "float32", kv_quant: bool = False):
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.dtype = np.dtype(dtype) if not hasattr(dtype, "itemsize") \
            else dtype
        # int8-quantized pool: int8 payload + one f32 amax scale per
        # (head, page, token) row alongside (r18)
        self.kv_quant = bool(kv_quant)

    @classmethod
    def of_pool(cls, pool) -> "PoolGeometry":
        """Geometry of a live :class:`PagedKVCache`."""
        from ..kernels.paged_attention import QuantizedPages
        k0 = pool.k_pages[0]
        quant = isinstance(k0, QuantizedPages)
        hkv, num_pages, page, d = k0.shape
        return cls(len(pool.k_pages), num_pages, page, hkv, d,
                   pool.max_pages_per_seq,
                   k0.q.dtype if quant else k0.dtype, kv_quant=quant)

    def pool_bytes(self) -> int:
        """Both pools, all layers — the donated/aliased block. A
        quantized pool bills the int8 payload plus the f32 per-token
        scale rows (head_dim + 4 bytes per token-head)."""
        per_elem = (self.head_dim * np.dtype(self.dtype).itemsize
                    + (4 if self.kv_quant else 0))
        return (self.num_layers * 2 * self.num_kv_heads * self.num_pages
                * self.page_size * per_elem)

    def tables_bytes(self, batch: int) -> int:
        """block table + seq_lens for one dispatch (int32)."""
        return batch * (self.max_pages_per_seq + 1) * 4

    @property
    def max_seq(self) -> int:
        return self.max_pages_per_seq * self.page_size


class ModelDims:
    """The model dims the temp model needs — constructable from any
    config exposing the Llama/GPT field names, or from explicit kwargs
    (the planner's too-big-to-build path)."""

    __slots__ = ("hidden", "layers", "heads", "kv_heads", "intermediate",
                 "vocab", "param_count")

    def __init__(self, hidden: int, layers: int, heads: int,
                 kv_heads: Optional[int], intermediate: int, vocab: int,
                 param_count: Optional[int] = None):
        self.hidden = int(hidden)
        self.layers = int(layers)
        self.heads = int(heads)
        self.kv_heads = int(kv_heads if kv_heads else heads)
        self.intermediate = int(intermediate)
        self.vocab = int(vocab)
        self.param_count = param_count

    @classmethod
    def of_config(cls, cfg) -> "ModelDims":
        inter = getattr(cfg, "intermediate_size", None)
        if inter is None:                      # GPT publishes a 4x MLP
            inter = 4 * cfg.hidden_size
        n = cfg.num_params() if hasattr(cfg, "num_params") else None
        return cls(cfg.hidden_size, cfg.num_hidden_layers,
                   cfg.num_attention_heads,
                   getattr(cfg, "num_key_value_heads", None),
                   inter, cfg.vocab_size, n)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def _decode_temp(dims: ModelDims, geom: PoolGeometry, batch: int) -> int:
    """Decode-step temp model: per-row working set of one layer chain
    (x/qkv round-trips, attention scores over the gathered width, FFN)
    summed over layers, plus the logits row — all f32 (kernels
    accumulate in f32), scaled by the calibrated live-set factor."""
    per_layer = (4 * dims.hidden            # x, q, attn-out, residual
                 + 2 * dims.kv_dim          # k, v new-token rows
                 + dims.heads * geom.max_seq   # attention scores
                 + 2 * dims.intermediate)   # gate/up FFN halves
    elems = batch * (dims.layers * per_layer + dims.vocab)
    return int(_DECODE_TEMP_K * elems * 4)


def _prefill_temp(dims: ModelDims, geom: PoolGeometry, s: int,
                  chunked: bool = False) -> int:
    """Prefill/chunk temp model (b=1, S query tokens): XLA's buffer
    reuse keeps roughly the two largest stage buffers live at the
    worst program point — scores, the FFN intermediate, the logits
    block, or the QKV block.

    ``chunked`` selects the r17 copy-free chunk path: attention reads
    K/V pages through the block table (a fixed-size page-GROUP block in
    flight — ~128 keys on the XLA twin, one page on the pallas kernel —
    online softmax), so the gathered full-context K/V view and the full
    S x max_seq score matrix never materialize — their stages are
    replaced by the page-group score/K/V blocks and the softmax carry."""
    if chunked:
        # mirrors _CHUNK_GROUP_KEYS in kernels/paged_attention.py: the
        # XLA twin batches pages into ~128-key groups per loop step
        pages = -(-geom.max_seq // geom.page_size)
        gk = min(pages, max(1, 128 // geom.page_size)) * geom.page_size
        stages = [
            dims.heads * s * gk,                 # page-group score block
            2 * gk * dims.kv_dim,                # gathered K+V group block
            2 * dims.heads * s * dims.head_dim,  # online-softmax acc carry
            2 * s * dims.intermediate,           # gate/up FFN halves
            s * dims.vocab,                      # logits
            s * 4 * dims.hidden,                 # q/k/v/x block
        ]
    else:
        stages = [
            dims.heads * s * geom.max_seq,      # attention scores
            2 * geom.max_seq * dims.kv_dim,     # gathered k+v view
            2 * s * dims.intermediate,          # gate/up FFN halves
            s * dims.vocab,                     # logits
            s * 4 * dims.hidden,                # q/k/v/x block
        ]
    top2 = sum(sorted(stages)[-2:])
    return int(_PREFILL_TEMP_K * top2 * 4)


def _nlayer_slice_temp(dims: ModelDims, batch: int) -> int:
    """Temp floor of the N-layer fused decode program on the CPU ref
    path (r17). The grouped program receives STACKED per-group weights
    and slices one layer per iteration; CPU XLA materializes the sliced
    merged weight feeding each dot instead of fusing the slice, so one
    largest-merged-slice buffer (reused across layers — hence no N
    term) plus the merged activations stays live. Measured fit across
    hidden/intermediate/N sweeps: within 0.7% of compiled temp. The
    Pallas path streams weight tiles through VMEM and never sees this
    buffer; see :func:`plan_fused_layers` for its VMEM pricing."""
    slice_elems = dims.hidden * max(2 * dims.intermediate,
                                    dims.heads * dims.head_dim
                                    + 2 * dims.kv_dim)
    act_elems = batch * (2 * dims.intermediate + 2 * dims.hidden)
    return 4 * (slice_elems + act_elems)


def _kv_dequant_temp(dims: ModelDims, geom: PoolGeometry,
                     batch: int) -> int:
    """int8-KV decode adder (r18): the XLA pool readers gather the
    page payload and materialize ONE f32 dequantized K view of the
    gathered context (the V dequant fuses into the PV dot, and the
    buffer is reused across layers, so there is no per-layer term).
    Fit against CompiledMemoryStats on the tier-1 quantized rows:
    +5.0% on decode_fused int8 at the capture geometry."""
    pages = -(-geom.max_seq // geom.page_size)
    return 4 * dims.kv_heads * batch * pages * geom.page_size \
        * dims.head_dim


def _int4_unpack_temp(dims: ModelDims, group_layers: int) -> int:
    """int4 stacked-weight adder (r18): the CPU/XLA ref path of the
    N-layer program dequantizes the group's packed matrices up front,
    so the group's merged f32 weights land in the temp section — all
    but ``wd``, whose unpack XLA fuses into its consuming dot (the fit
    that lands the banked fully-quantized row at -5.8%). The Pallas
    path unpacks tile-wise in VMEM and never sees these buffers."""
    merged = (dims.hidden * (dims.heads * dims.head_dim
                             + 2 * dims.kv_dim)       # wqkv
              + dims.heads * dims.head_dim * dims.hidden   # wo
              + dims.hidden * 2 * dims.intermediate)       # gate|up
    return 4 * group_layers * merged


def estimate_decode_program(dims: ModelDims, geom: PoolGeometry,
                            batch: int, param_bytes: int,
                            fused_layers: int = 1,
                            int4_weights: bool = False) -> Dict[str, int]:
    """Predicted sections of one decode-step program (fused, generic, or
    the r17 N-layer grouped program — the calibrated model covers all
    three): params + pools + tables in, donated pools + token ids out.

    ``fused_layers`` > 1 prices the ``decode_fused_nlayer`` program.
    Its ARGUMENT section is unchanged: the stacked per-group weight
    copies add exactly the element count of the per-layer block params
    XLA elides as unused, so ``param_bytes`` (all params + buffers)
    still lands on the compiled number. Its temp floor is the stacked
    slice working set (:func:`_nlayer_slice_temp`)."""
    pool = geom.pool_bytes()
    tables = geom.tables_bytes(batch)
    arg = param_bytes + pool + tables + batch * 4         # toks (B,1)
    out = pool + tables + batch * 4                       # argmax ids
    temp = _decode_temp(dims, geom, batch)
    if int(fused_layers) > 1:
        temp = max(temp, _nlayer_slice_temp(dims, batch))
    if geom.kv_quant:
        temp += _kv_dequant_temp(dims, geom, batch)
    if int4_weights:
        temp += _int4_unpack_temp(dims, int(fused_layers))
    return {
        "argument": arg, "output": out,
        "temp": temp,
        "alias": pool, "generated_code": 0,
        "peak": arg + out - pool + temp,
    }


def estimate_prefill_program(dims: ModelDims, geom: PoolGeometry,
                             s: int, param_bytes: int,
                             chunked: bool = False) -> Dict[str, int]:
    """Predicted sections of a b=1 prefill (monolithic length ``s``) or
    chunked-prefill (``s`` = chunk, ``chunked=True`` — the r17
    copy-free block-table path) program."""
    pool = geom.pool_bytes()
    tables = geom.tables_bytes(1)
    arg = param_bytes + pool + tables + s * 4             # ids (1, S)
    out = pool + tables + 4                               # argmax id
    temp = _prefill_temp(dims, geom, s, chunked=chunked)
    return {"argument": arg, "output": out, "temp": temp,
            "alias": pool, "generated_code": 0,
            "peak": arg + out - pool + temp}


# ------------------------------------------------------ what-if planner
_WEIGHT_BYTES = {"float32": 4.0, "f32": 4.0, "bfloat16": 2.0, "bf16": 2.0,
                 "float16": 2.0, "int8": 1.0, "int4": 0.5}


def weight_bytes(param_count: int, dtype: str,
                 scale_group: int = 128) -> int:
    """Model weight bytes for a storage dtype. Quantized dtypes carry
    per-group f32 scales (``scale_group`` weights per scale — the
    streaming-int8 path stores per-channel scales, which this bounds)."""
    per = _WEIGHT_BYTES[str(dtype)]
    total = param_count * per
    if per < 2.0:                       # quantized: add the scales
        total += param_count / scale_group * 4
    return int(total)


class _ShardedDims(ModelDims):
    """Per-shard view of a tensor-parallel serving engine (r19): heads,
    kv-heads and the MLP width divide by ``tp`` while ``head_dim`` stays
    the FULL model's ``hidden // heads`` — the residual stream (and so
    ``hidden``) is replicated, only the head and channel axes shard."""

    __slots__ = ("_head_dim",)

    def __init__(self, dims: ModelDims, tp: int):
        super().__init__(dims.hidden, dims.layers, dims.heads // tp,
                         dims.kv_heads // tp, dims.intermediate // tp,
                         dims.vocab, dims.param_count)
        self._head_dim = dims.head_dim

    @property
    def head_dim(self) -> int:
        return self._head_dim


def estimate_engine_memory(dims: ModelDims, *,
                           page_size: int = 64,
                           page_budget: Optional[int] = None,
                           max_batch: int = 8,
                           max_seq_len: int = 1024,
                           chunk: int = 0,
                           weight_dtype: str = "bfloat16",
                           kv_dtype: str = "bfloat16",
                           host_tier_pages: int = 0,
                           param_count: Optional[int] = None,
                           draft_dims: Optional[ModelDims] = None,
                           spec_gamma: int = 0,
                           draft_param_count: Optional[int] = None,
                           draft_weight_dtype: Optional[str] = None,
                           tp: int = 1
                           ) -> Dict[str, Any]:
    """The what-if planner: predicted steady-state serving HBM for a
    configuration that may be too big to compile locally. Returns the
    transparent breakdown ``tools/memwatch.py plan`` renders; compare
    ``total`` against the chip's HBM. ``page_budget`` = USABLE pages
    (the FLAGS_serving_page_budget contract: +1 null page rides on
    top); None = the worst-case formula. ``host_tier_pages`` (r14)
    prices the host-RAM KV tier alongside: its bytes land under
    ``host_tier`` — host RAM, NOT HBM — so device and host are planned
    jointly but never summed into one number.

    ``draft_dims`` (r16) prices speculative decoding alongside: the
    draft model's weights, its ALWAYS-worst-case KV pool (the engine
    sizes it ``1 + max_batch * pages_per_seq`` regardless of
    ``page_budget`` — draft sync must never fail allocate), and the
    (1, gamma+1) verify chunk's workspace through the TARGET (the
    verify is a chunk program, so it prices exactly like a prefill of
    ``spec_gamma + 1`` positions).

    ``tp`` (r19) prices ONE SHARD of a tensor-parallel engine: the
    stacked block weights split head-/column-/row-wise (embedding and
    lm_head stay replicated, exactly as the sharder leaves them), the
    KV pool partitions over kv-heads — the int8 per-token scale band
    divides with its payload — and the workspaces are re-derived on the
    per-shard dims. Refuses (ValueError) any degree that does not
    divide heads, kv-heads and the MLP width: the engine refuses the
    same configs, and a planner that silently rounded would under-bill.
    Draft-model terms stay replicated — the r16 draft chain runs
    un-sharded on every rank, its pool partitioning is future work."""
    n_params = param_count or dims.param_count
    if n_params is None:
        raise ValueError("need param_count (config.num_params() or "
                         "explicit)")
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1 and (dims.heads % tp or dims.kv_heads % tp
                   or dims.intermediate % tp):
        raise ValueError(
            f"tp={tp} must divide heads ({dims.heads}), kv_heads "
            f"({dims.kv_heads}) and intermediate ({dims.intermediate}) "
            f"— the engine refuses this config too")
    if tp > 1 and str(weight_dtype) == "int4":
        raise ValueError(
            "int4 weight tiles cannot be sharded: two-nibble row-pairing "
            "does not commute with the head-shard permutation — the "
            "engine refuses this config too (serve int8 or bf16 under tp)")
    sdims = _ShardedDims(dims, tp) if tp > 1 else dims
    pages_per_seq = -(-max_seq_len // page_size)
    usable = (int(page_budget) if page_budget
              else max_batch * pages_per_seq)
    geom = PoolGeometry(sdims.layers, usable + 1, page_size,
                        sdims.kv_heads,
                        sdims.head_dim, pages_per_seq, np.dtype(
                            "int8" if str(kv_dtype) == "int8"
                            else "float16"),  # 2B stand-in for bf16
                        kv_quant=str(kv_dtype) == "int8")
    if str(kv_dtype) in ("bfloat16", "bf16", "float16"):
        kv_item = 2
    elif str(kv_dtype) == "int8":
        kv_item = 1
    else:
        kv_item = np.dtype(kv_dtype).itemsize
    pool = (sdims.layers * 2 * sdims.kv_heads * (usable + 1) * page_size
            * sdims.head_dim * kv_item)
    if str(kv_dtype) == "int8":
        # per-TOKEN f32 amax scales stored alongside the pool (k and v:
        # one scale per head-token row — write-order-independent, so
        # fault replay stays bit-identical)
        pool += (sdims.layers * 2 * sdims.kv_heads * (usable + 1)
                 * page_size * 4)
    if tp > 1:
        # embedding + lm_head replicate on every shard (the sharder
        # never touches them); every block weight splits exactly /tp.
        # int4/int8 per-group scale tiles ride weight_bytes' per-group
        # scale term, so they divide with their payload.
        replicated = min(int(n_params), 2 * dims.vocab * dims.hidden)
        shard_params = replicated + (int(n_params) - replicated) // tp
        weights = weight_bytes(shard_params, weight_dtype)
    else:
        weights = weight_bytes(n_params, weight_dtype)
    decode_tmp = _decode_temp(sdims, geom, max_batch)
    # chunked prefill is the copy-free block-table path (r17): no
    # gathered full-context K/V view, no full S x max_seq score matrix
    chunk_tmp = (_prefill_temp(sdims, geom, chunk, chunked=True)
                 if chunk else 0)
    tables = geom.tables_bytes(max_batch)
    # ---- speculative decoding (r16): draft weights + worst-case draft
    # pool are resident; the verify chunk and the draft's own programs
    # only add workspace (dispatches never overlap, so max not sum)
    draft_weights = draft_pool = verify_tmp = draft_tmp = 0
    if draft_dims is not None:
        gamma = max(1, int(spec_gamma))
        dn = draft_param_count or draft_dims.param_count
        if dn is None:
            raise ValueError("need draft_param_count "
                             "(config.num_params() or explicit)")
        draft_weights = weight_bytes(
            dn, draft_weight_dtype or weight_dtype)
        dgeom = PoolGeometry(
            draft_dims.layers, 1 + max_batch * pages_per_seq, page_size,
            draft_dims.kv_heads, draft_dims.head_dim, pages_per_seq,
            geom.dtype, kv_quant=geom.kv_quant)
        draft_pool = dgeom.pool_bytes()
        # the verify IS a chunk program — priced on the copy-free path,
        # through the (possibly sharded) TARGET dims
        verify_tmp = _prefill_temp(sdims, geom, gamma + 1, chunked=True)
        draft_tmp = max(_decode_temp(draft_dims, dgeom, 1),
                        _prefill_temp(draft_dims, dgeom, gamma + 1))
    # XLA program text + runtime allocations scale with model size; a
    # visible margin line, not silent slack
    margin = max(64 << 20, int(0.05 * (weights + draft_weights)))
    workspace = max(decode_tmp, chunk_tmp, verify_tmp, draft_tmp)
    total = (weights + draft_weights + pool + draft_pool + workspace
             + tables + margin)
    # host-RAM tier: same per-page geometry as the device pool (spill
    # copies pages verbatim, scales included), priced against HOST
    # memory — derived from the pool term so the two can never drift
    bytes_per_page = pool // (usable + 1)
    host_tier = int(host_tier_pages) * bytes_per_page
    return {
        "dims": {"hidden": dims.hidden, "layers": dims.layers,
                 "heads": dims.heads, "kv_heads": dims.kv_heads,
                 "intermediate": dims.intermediate, "vocab": dims.vocab,
                 "params": int(n_params)},
        "config": {"page_size": page_size, "usable_pages": usable,
                   "max_batch": max_batch, "max_seq_len": max_seq_len,
                   "chunk": chunk, "weight_dtype": str(weight_dtype),
                   "kv_dtype": str(kv_dtype),
                   "host_tier_pages": int(host_tier_pages),
                   "spec_gamma": (max(1, int(spec_gamma))
                                  if draft_dims is not None else 0),
                   "tp": tp},
        "breakdown": {
            "weights": weights, "kv_pool": pool,
            **({"draft_weights": draft_weights,
                "draft_kv_pool": draft_pool,
                "spec_verify_workspace": verify_tmp,
                "draft_workspace": draft_tmp}
               if draft_dims is not None else {}),
            "decode_workspace": decode_tmp,
            "chunk_prefill_workspace": chunk_tmp,
            "block_tables": tables,
            "xla_code_and_runtime_margin": margin,
        },
        "total": int(total),
        "host_tier": {"pages": int(host_tier_pages),
                      "bytes": int(host_tier),
                      "bytes_per_page": int(bytes_per_page)},
    }


def plan_fused_layers(dims: ModelDims, *, fused_layers: int,
                      batch: int = 8, page_size: int = 64,
                      io_dtype_bytes: int = 2,
                      vmem_limit: int = 16 << 20) -> Dict[str, Any]:
    """Price the N-layer fused decode kernel's VMEM working set (r17)
    and say whether ``fused_layers`` fits the per-core VMEM budget.

    Walks the exact tile/scratch shapes ``fused_multi_block_decode_pallas``
    allocates: every block operand is double-buffered by Mosaic (weight
    tiles, the per-layer page blocks — 2 per grouped layer, so the pool
    term is the only one that grows with N), activations/carries are
    persistent f32 VMEM scratch. ``io_dtype_bytes`` is the streamed
    weight/activation storage width (2 = bf16 serving, 4 = f32).
    Returns the transparent breakdown + a ``fits`` verdict against
    ``vmem_limit`` — the ``tools/memwatch.py plan --fused-layers``
    refusal reads it.

    The tile/scratch geometry itself lives in ONE place —
    ``paddle_tpu.analysis.tile_geometry`` — which the kernel imports
    its tiling from and the kernelcheck lint (KRN002) checks the
    kernel source against, so this plan can never silently disagree
    with what the kernel actually allocates (r18)."""
    from ..analysis.tile_geometry import fused_decode_env, price_fused_decode

    n = int(fused_layers)
    if n < 1:
        raise ValueError(f"fused_layers must be >= 1, got {n}")
    env = fused_decode_env(
        hidden=dims.hidden, intermediate=dims.intermediate,
        heads=dims.heads, kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        batch=batch, page_size=page_size)
    priced = price_fused_decode(env, fused_layers=n,
                                io_dtype_bytes=io_dtype_bytes,
                                vmem_limit=vmem_limit)
    return {
        "fused_layers": n, "batch": int(batch), "b_pad": env["b_pad"],
        "page_size": int(page_size), "io_dtype_bytes": int(io_dtype_bytes),
        "breakdown": {
            "weight_stream_buffers": priced["weight_stream_buffers"],
            "activation_io_buffers": priced["activation_io_buffers"],
            "kv_page_buffers": priced["kv_page_buffers"],
            "scratch": priced["scratch"],
        },
        "total": priced["total"],
        "vmem_limit": priced["vmem_limit"],
        "fits": priced["fits"],
        "headroom_bytes": priced["headroom_bytes"],
    }


def fits(plan: Dict[str, Any], hbm_bytes: int) -> Dict[str, Any]:
    """Verdict + headroom for one planner breakdown against a chip."""
    total = plan["total"]
    return {"hbm_bytes": int(hbm_bytes), "total": int(total),
            "fits": total <= hbm_bytes,
            "headroom_bytes": int(hbm_bytes - total)}


# --------------------------------------------- sharded-state accounting
def sharded_param_bytes(shape: Sequence[int], dtype: Any, spec,
                        mesh_shape: Dict[str, int]) -> int:
    """Per-device bytes of one sharded array: per-dim CEIL division (a
    dim not divisible by its mesh axes pads up on device, so flat
    ``total // prod`` would undercount and let a topology pass the fit
    check yet OOM on hardware). The one shard-accounting code path —
    ``PipelineTrainStep.per_device_state_bytes`` and
    ``tools/memory_70b.py`` both call through here."""
    n = 1
    entries = tuple(spec) if spec is not None else ()
    for i, dim in enumerate(shape):
        denom = 1
        if i < len(entries) and entries[i] is not None:
            entry = entries[i]
            for name in ((entry,) if isinstance(entry, str) else entry):
                denom *= int(mesh_shape[name])
        n *= -(-int(dim) // denom)
    return n * np.dtype(dtype).itemsize


# -------------------------------------------------------- regression gate
def compare_program_rows(banked: List[Dict[str, Any]],
                         current: List[Dict[str, Any]],
                         tolerance: float = 0.10) -> List[Dict[str, Any]]:
    """The memory analogue of the zero-retrace gate: flag every program
    whose ``temp`` or ``peak`` grew beyond ``tolerance`` vs the banked
    artifact. Programs only in one table are reported informationally
    (``"missing"``/``"new"``) and do not fail the gate — a config drift
    shows up as growth on the programs both runs share."""
    key = lambda r: (r.get("model", ""), r["kind"], r["bucket"],
                     r.get("extra", ""))
    cur = {key(r): r for r in current}
    findings: List[Dict[str, Any]] = []
    seen = set()
    for row in banked:
        k = key(row)
        seen.add(k)
        now = cur.get(k)
        if now is None:
            findings.append({"model": row.get("model", ""),
                             "kind": row["kind"], "bucket": row["bucket"],
                             "extra": row.get("extra", ""),
                             "verdict": "missing"})
            continue
        for sec in ("temp", "peak"):
            old_v, new_v = int(row.get(sec, 0)), int(now.get(sec, 0))
            # a zero banked value is NOT a free pass: byte sizes are
            # deterministic per backend, so 0 -> anything is real growth
            if new_v > old_v * (1.0 + tolerance) and new_v > old_v:
                findings.append({
                    "model": row.get("model", ""),
                    "kind": row["kind"], "bucket": row["bucket"],
                    "extra": row.get("extra", ""), "section": sec,
                    "banked": old_v, "current": new_v,
                    "growth": (round(new_v / old_v - 1.0, 4)
                               if old_v else None),
                    "verdict": "grew"})
    for k, row in cur.items():
        if k not in seen:
            findings.append({"model": row.get("model", ""),
                             "kind": row["kind"], "bucket": row["bucket"],
                             "extra": row.get("extra", ""),
                             "verdict": "new"})
    return findings
