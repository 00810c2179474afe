"""Runtime flag registry.

TPU-native equivalent of the reference's gflags-style registry
(paddle/phi/core/flags.cc, paddle/utils/flags.h): typed, documented,
env-overridable flags, settable at runtime via ``set_flags`` and readable
via ``get_flags`` — same user API as ``paddle.set_flags``.

Flags are read from the environment (``FLAGS_<name>=...``) at first access,
so launchers can configure workers without code changes.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


_PARSERS: Dict[type, Callable[[str], Any]] = {
    bool: _parse_bool,
    int: int,
    float: float,
    str: str,
}


@dataclass
class _Flag:
    name: str
    default: Any
    type: type
    help: str
    value: Any = None
    is_set: bool = False  # explicitly set (env or set_flags)

    def current(self) -> Any:
        if self.is_set:
            return self.value
        env = os.environ.get("FLAGS_" + self.name)
        if env is not None:
            return _PARSERS[self.type](env)
        return self.default


class _Registry:
    def __init__(self) -> None:
        self._flags: Dict[str, _Flag] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help: str = "") -> None:
        with self._lock:
            if name in self._flags:
                return
            self._flags[name] = _Flag(name, default, type(default), help)

    def get(self, name: str) -> Any:
        f = self._flags.get(self._norm(name))
        if f is None:
            raise KeyError(f"Unknown flag: {name!r}. See paddle_tpu.flags.list_flags().")
        return f.current()

    def set(self, name: str, value: Any) -> None:
        key = self._norm(name)
        f = self._flags.get(key)
        if f is None:
            raise KeyError(f"Unknown flag: {name!r}. See paddle_tpu.flags.list_flags().")
        if isinstance(value, str) and f.type is not str:
            value = _PARSERS[f.type](value)
        f.value = f.type(value)
        f.is_set = True

    @staticmethod
    def _norm(name: str) -> str:
        return name[6:] if name.startswith("FLAGS_") else name

    def snapshot(self, names=None) -> "FlagSnapshot":
        """Resolve ``names`` (all flags when None) ONCE: one lock
        acquisition and one env read per flag, returning an immutable
        view. Hot paths (kernel dispatch) read the snapshot instead of
        hitting the registry per call."""
        with self._lock:
            if names is None:
                flags = list(self._flags.values())
            else:
                flags = [self._flags[self._norm(n)] for n in names]
        return FlagSnapshot({f.name: f.current() for f in flags})

    def all(self) -> Dict[str, Any]:
        return {n: f.current() for n, f in sorted(self._flags.items())}

    def describe(self) -> List[str]:
        return [
            f"FLAGS_{n} (default={f.default!r}): {f.help}"
            for n, f in sorted(self._flags.items())
        ]


class FlagSnapshot:
    """Immutable point-in-time flag view with mapping and attribute
    access. Kernels resolve ONE snapshot per trace (`flags.snapshot`)
    and thread it through their helpers instead of re-importing the
    registry and re-parsing the environment on every call — the decode
    hot path dispatches thousands of kernel calls per second and the
    per-call registry/env round-trips were measurable host overhead."""

    __slots__ = ("_values",)

    def __init__(self, values: Dict[str, Any]):
        object.__setattr__(self, "_values", dict(values))

    def __getattr__(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"flag {name!r} not in snapshot "
                                 f"(have {sorted(self._values)})") from None

    def __getitem__(self, name: str) -> Any:
        return self._values[name[6:] if name.startswith("FLAGS_") else name]

    def __contains__(self, name: str) -> bool:
        return (name[6:] if name.startswith("FLAGS_") else name) in self._values

    def __setattr__(self, name, value):
        raise TypeError("FlagSnapshot is immutable")

    def as_tuple(self) -> tuple:
        """Hashable (name, value) tuple — the ``flag tuple`` component of
        decode program cache keys."""
        return tuple(sorted(self._values.items()))

    def __repr__(self) -> str:
        return f"FlagSnapshot({self._values!r})"


_registry = _Registry()
define_flag = _registry.define


def snapshot(names=None) -> FlagSnapshot:
    """Resolve a set of flags once into an immutable :class:`FlagSnapshot`.
    ``names`` may be any iterable of flag names (with or without the
    ``FLAGS_`` prefix); None snapshots every registered flag."""
    return _registry.snapshot(names)


def set_flags(flags: Dict[str, Any]) -> None:
    """Set runtime flags. Mirrors ``paddle.set_flags``."""
    for k, v in flags.items():
        _registry.set(k, v)


def get_flags(names) -> Dict[str, Any]:
    """Read runtime flags. Mirrors ``paddle.get_flags``."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        out[key] = _registry.get(n)
    return out


def get_flag(name: str) -> Any:
    return _registry.get(name)


def list_flags() -> List[str]:
    return _registry.describe()


# ---------------------------------------------------------------------------
# Core flag definitions (load-bearing set mirrored from the reference's
# paddle/phi/core/flags.cc; TPU-specific ones added).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "Check every op output for NaN/Inf (debug).")
define_flag("check_nan_inf_level", 0, "0: abort on nan/inf; >=1: report only.")
define_flag("benchmark", False, "Synchronize after each op and log timings.")
define_flag("deterministic", False, "Force deterministic kernels where possible.")
define_flag("use_pallas", True, "Use Pallas fused kernels where available (vs pure-XLA fallbacks).")
define_flag("flash_attn_min_seqlen", 1024,
            "Dispatch sdpa to the Pallas flash kernel only at seq >= this; "
            "0 = always flash. Lowered 2048 -> 1024 on r05 on-chip "
            "evidence: (a) ATTN_BENCH_r05 block sweep: 512x512 blocks cut "
            "flash fwd+bwd 108.6 -> 76.0 ms at seq 4096 (dense: 100.6), "
            "and flash already matched dense at 1024 with the OLD slow "
            "blocks; (b) PROFILE_r05: the dense path's materialized mask "
            "+ f32 score temps put copy/layout at 67% of accumulated "
            "device time on GPT-345M seq 1024; (c) the r05 batch tuning run: dense "
            "bf16[16,16,1024,1024] score temps (512 MB/layer) OOM the "
            "batch-16 345M step that flash runs fine.")
define_flag("embedding_matmul_grad", "auto",
            "Embedding-lookup weight gradient as a one-hot matmul "
            "instead of jnp.take's scatter-add vjp: 'auto' = on TPU "
            "backends only (XLA lowers big scatter-adds to serialized "
            "while loops there — PROFILE_r05 top ops), 'on'/'off' = "
            "force. The matmul accumulates in f32 on the MXU; the "
            "transient one-hot is [tokens, vocab] in the grad dtype.")
define_flag("flash_compact_stats", True,
            "Flash-attention stats stay compact (BH, S) at the kernel "
            "boundary: fwd keeps softmax stats in VMEM scratch and emits "
            "lse via an in-kernel (1, bq) write; bwd loads lse/delta/seg "
            "as (1, bq) lane rows transposed in-kernel — kills the "
            "128x-replicated HBM transients (advisor r2). Numerics are "
            "parity-tested in interpret mode; that the layouts compile "
            "for the chip is pinned by tests/test_chip_compile.py.")
define_flag("fused_block_decode", True,
            "Serve steady-state decode through the fused transformer-block "
            "kernel (kernels/fused_block_decode.py): one program per layer "
            "computes rms_norm -> QKV -> RoPE -> paged attention -> "
            "out-proj -> rms_norm -> SwiGLU FFN with the per-slot "
            "activations VMEM-resident, instead of the op chain that "
            "round-trips HBM between every op. Applies to models exposing "
            "block_decode_spec() (the Llama family); others keep the "
            "generic compiled step. Env-overridable "
            "(FLAGS_fused_block_decode=0).")
define_flag("fused_block_layers", 1,
            "How many transformer blocks one fused decode kernel runs "
            "(kernels/fused_block_decode.py multi-layer mode): N > 1 "
            "groups the model's layers into ceil(L/N) stacked-weight "
            "groups, each dispatched as ONE pallas_call whose activations "
            "stay VMEM-resident across the group's layers and whose "
            "q/k/v and gate/up projections run as merged wider matmuls. "
            "1 (default) keeps the r06 one-kernel-per-layer step. Price "
            "an N before flipping it: "
            "`python tools/memwatch.py plan --fused-layers N` refuses an "
            "N whose VMEM working set cannot fit. Requires the model's "
            "block_decode_spec() to publish layer_groups; models that "
            "fall back to the generic step ignore this flag.")
define_flag("flash_dispatch_table", "0:flash;2048:dense;4096:flash",
            "Per-shape flash-attention dispatch table: ';'-separated "
            "'<min_seqlen>:<entry>' buckets, entry 'flash' (the kernels, "
            "at the blocks flash_tiling derives from the call's shapes) "
            "or 'dense' (XLA dense sdpa). A query length resolves to the "
            "bucket with the largest min_seqlen <= it; lengths below "
            "every bucket use 'flash'. Seeded from the r05 on-chip A/B "
            "(ATTN_BENCH_r05.json): flash matches dense at 1024 (1.01x), "
            "LOSES at 2048 (0.86x -> dense fallback so the fused path "
            "never loses to XLA dense), and wins at 4096+ (76.0 ms vs "
            "100.6 dense). Applies where sdpa already cleared "
            "FLAGS_flash_attn_min_seqlen; set to '' to disable the table "
            "(always flash).")
define_flag("train_max_in_flight", 32,
            "Hard cap on dispatched-but-unsynced train steps. The async "
            "TrainStep window never blocks on the loss; this bound is the "
            "HBM safety net for callers that never pull metrics (each "
            "in-flight step holds its input batch buffers until it "
            "retires). Normal loops sync far earlier via "
            "metrics_every/sync().")
define_flag("allocator_strategy", "auto_growth", "Kept for API parity; PJRT owns memory on TPU.")
define_flag("fraction_of_gpu_memory_to_use", 0.92, "API parity; PJRT owns memory on TPU.")
define_flag("log_level", 1, "Framework log verbosity (GLOG_v analogue).")
define_flag("eager_delete_tensor_gb", 0.0, "API parity; JAX GC owns tensor lifetime.")
define_flag("tpu_matmul_precision", "default", "jax matmul precision: default|high|highest.")
define_flag("memwatch", True,
            "Compiled-program memory capture (observability.memory): "
            "every program admitted by the decode program cache and "
            "every jitted TrainStep banks its XLA CompiledMemoryStats "
            "(argument/output/temp/alias/code bytes) as "
            "program_memory_bytes gauges + the memwatch program table. "
            "A TrainStep capture costs ONE duplicate lower()+compile() "
            "per (re)trace (the program cache hands over the "
            "executable it built) and nothing per steady-state "
            "step. Eager-only by design, NOT in PROGRAM_FLAGS: "
            "toggling never recompiles a serving or train program.")
define_flag("telemetry_ring", 16384,
            "Span-tracer ring-buffer capacity in events; the oldest events "
            "drop first, so a long-lived server keeps a bounded, recent "
            "timeline window.")
define_flag("embedding_deterministic", 0, "API parity with reference embedding determinism flag.")
define_flag("cudnn_deterministic", False, "API parity alias of FLAGS_deterministic.")
define_flag("fault_inject", "",
            "Deterministic fault-injection spec (paddle_tpu.testing."
            "faults): ';'-separated '<site>:every=N' / '<site>:p=F"
            "[:seed=N][:times=N][:after=N]' entries arming named "
            "injection sites (prefill, decode_dispatch, preempt, "
            "kv_spill, router_dispatch, spec_draft, spec_verify, "
            "program_build, train_dispatch, "
            "train_sync, dataloader_worker, "
            "checkpoint_save). Empty (default) = disabled: components "
            "bind no-op stubs at construction, zero hot-path cost. "
            "Eager-only by design — injection never changes a traced "
            "program, so it is NOT part of PROGRAM_FLAGS.")
define_flag("serving_max_retries", 3,
            "ServingEngine replay-recovery budget: how many consecutive "
            "NO-PROGRESS replays a request survives before it is "
            "terminated FAILED. A replay after new tokens were emitted "
            "resets the count — the budget guards wedged requests, not "
            "long ones under a flaky backend.")
define_flag("serving_retry_backoff", 0.05,
            "Base seconds of the serving recovery backoff; doubles per "
            "consecutive no-progress recovery (capped at 2 s), resets "
            "once any request makes progress.")
define_flag("serving_prefill_chunk", 256,
            "ServingEngine chunked-prefill granularity in tokens: a "
            "prompt longer than this prefills in fixed-size chunks "
            "interleaved with decode steps (ONE cached b=1 program per "
            "chunk length — the final partial chunk pads, so prompt "
            "length never forces a retrace), bounding the decode stall "
            "a long-prompt arrival can cause to one chunk instead of "
            "the whole prompt. Prompts at or under the chunk keep the "
            "exact monolithic prefill program. 0 = chunking off "
            "(monolithic prefill, the pre-r12 behavior). Eager-only: "
            "the chunk size reaches compiled programs through the "
            "program-cache key, never through a traced flag read.")
define_flag("serving_bucket_ladder", "4,8,16,32",
            "ServingEngine batch-bucket ladder: ','-separated decode "
            "batch sizes. The engine runs its decode step at the "
            "smallest rung covering current demand and migrates "
            "between rungs as occupancy changes (grow immediately on "
            "queue pressure, shrink after FLAGS_serving_bucket_patience "
            "idle steps); each rung's program compiles once and is "
            "cached. Rungs above the engine's max_batch are dropped and "
            "max_batch itself is always a rung, so max_batch=4 serves "
            "exactly the pre-r12 fixed-shape behavior.")
define_flag("serving_bucket_patience", 8,
            "Steps a lower bucket rung must stay sufficient before the "
            "serving engine shrinks its decode batch to it (hysteresis "
            "against occupancy flapping; growth is immediate).")
define_flag("serving_page_budget", 0,
            "USABLE KV page-pool pages for ServingEngine when "
            "num_pages is not passed, decoupling pool memory from the "
            "bucket ladder's top rung. 0 (default) keeps the "
            "worst-case formula 1 + max_batch * "
            "ceil(max_seq_len / page_size); a positive value N "
            "allocates N + 1 pages (one reserved null scribble page, "
            "like the formula's +1) and lets admission control "
            "(page-pressure queueing + prefix-cache eviction) absorb "
            "the difference.")
define_flag("serving_preempt", True,
            "SLO-aware preemption inside ServingEngine: when a "
            "tight-deadline arrival cannot admit (no free slot, or "
            "page-blocked after prefix-cache eviction), the SLACKEST "
            "running request may be unseated and re-queued for "
            "replay-from-host-state (the r10 recovery path IS the "
            "preemption mechanism, so the victim's resumed greedy "
            "continuation is bit-identical). Bounded per victim by "
            "FLAGS_serving_preempt_budget; a victim is only unseated "
            "for an arrival whose deadline slack is smaller by at "
            "least FLAGS_serving_preempt_margin seconds. Eager-only: "
            "scheduling policy, never part of a traced program.")
define_flag("serving_preempt_budget", 2,
            "How many times one request may be preempted (unseated and "
            "re-queued for replay) before it becomes untouchable — the "
            "starvation bound on SLO preemption. Preemptions never "
            "count against the replay-recovery retry budget: a "
            "preempted request is healthy, just displaced.")
define_flag("serving_preempt_horizon", 1.0,
            "Only preempt for an arrival whose deadline slack is "
            "already below this many seconds — a head with comfortable "
            "slack waits like everyone else (preemption is for "
            "endangered SLOs, not queue-jumping). Raise for slower "
            "backends; 0 disables preemption as surely as "
            "FLAGS_serving_preempt=0.")
define_flag("serving_preempt_margin", 0.0,
            "Minimum seconds of deadline-slack difference (victim "
            "slack minus arrival slack) before preemption triggers; "
            "no-deadline victims have infinite slack and always clear "
            "the margin. 0 = any tighter deadline may preempt.")
define_flag("serving_kv_host_tier_pages", 0,
            "Host-RAM KV tier capacity in pages (0 = tiering off). "
            "With a positive budget, prefix-cache eviction SPILLS cold "
            "shared pages (cache-only reference, unpinned) to host RAM "
            "instead of dropping them, and pages them back on prefix "
            "adoption — the shared-prefix working set scales past the "
            "device page budget at the cost of one host round-trip per "
            "re-adopted page. Beyond the host budget the coldest "
            "spilled pages drop entirely (classic eviction). Eager-"
            "only: pure pool bookkeeping, never traced.")
define_flag("serving_spec_gamma", 4,
            "Initial speculative-decoding draft length γ for a "
            "ServingEngine built with draft_model= — how many draft "
            "tokens one target verify checks. Snapped down to the "
            "nearest FLAGS_serving_spec_rungs rung; per-request "
            "adaptation (FLAGS_serving_spec_adaptive) takes over from "
            "there. Eager-only: γ reaches compiled programs through "
            "the program-cache key (DecodeKey.extra), never through a "
            "traced flag read.")
define_flag("serving_spec_rungs", "2,4,8",
            "','-separated γ rung set for speculative serving. Each "
            "rung compiles one draft-propose and one verify program "
            "(cached, like bucket-ladder rungs), and adaptive γ moves "
            "between rungs instead of retracing per value — steady "
            "state is zero-retrace by construction. Eager-only; part "
            "of program identity via DecodeKey.extra.")
define_flag("serving_spec_adaptive", True,
            "Per-request adaptive γ: an accept-rate EMA (the "
            "serving_spec_accept_rate signal) moves each request up a "
            "γ rung when the draft keeps agreeing and down when it "
            "keeps missing, so a hard request stops wasting draft "
            "forwards. Off = every round uses the "
            "FLAGS_serving_spec_gamma rung. Eager-only scheduling "
            "policy.")
define_flag("serving_spec_max_slots", 0,
            "Decode-slot budget speculation may bill: a speculating "
            "request prices as γ+1 decode slots (its verify covers γ+1 "
            "positions), and a step's rows only take speculation "
            "rounds when n_rows * (γ+1) fits the budget — as "
            "occupancy rises γ is capped down and finally priced out "
            "entirely (plain batched decode is the better schedule "
            "there). 0 (default) = max(max_batch, smallest rung + 1), "
            "so a lone decode row always affords the smallest rung. "
            "Eager-only.")
define_flag("serving_spec_sync_chunk", 64,
            "Chunk width (tokens) of the draft-KV catch-up sync: when "
            "a request enters speculation with its draft cache behind "
            "the target's accepted length (admission prefilled the "
            "target only, or plain decode ran while speculation was "
            "priced out), the gap teacher-forces through the draft's "
            "chunked-prefill program in fixed (1, C) chunks — one "
            "cached program, any gap length. Eager-only; the width "
            "reaches the program via the cache key.")
define_flag("serving_kv_dtype", "native",
            "KV pool storage dtype for ServingEngine pools: 'native' "
            "stores K/V at the compute dtype, 'int8' stores per-page "
            "int8 payload with per-token f32 amax scales alongside "
            "(≈2x the page budget at fixed memory). Dequantization is "
            "fused into every consuming kernel — the bf16 pool view "
            "is never materialized in HBM. Eager-only: the dtype "
            "reaches compiled programs through the program-cache key "
            "(DecodeKey.extra), never through a traced flag read.")
define_flag("fused_weight_dtype", "native",
            "Stacked-weight storage dtype for the fused N-layer "
            "decode kernel: 'native' keeps the r17 layout, 'int4' "
            "packs the merged q|k|v / gate|up / o / down matmuls two "
            "nibbles per byte with per-tile f32 scales, unpacked "
            "MXU-friendly inside the kernel's VMEM stream (2x weight "
            "memory headroom on top of int8 streaming). LayerNorm "
            "params stay native. Eager-only; part of program "
            "identity via DecodeKey.extra.")
define_flag("serving_tp_degree", 1,
            "Tensor-parallel degree of ServingEngine decode: > 1 "
            "shards the fused stacked weights column/row-wise (the "
            "shard_block_weights Megatron layout) and the paged KV "
            "pool over kv-heads across the mp axis, running the block "
            "chain under shard_map with two psums per layer. The mp "
            "process group (fleet.init) names the axis and devices "
            "when its world size matches; otherwise the first N local "
            "devices under 'mp'. Eager-only: the degree reaches "
            "compiled programs through the program-cache key "
            "(DecodeKey.extra), never a traced flag read.")
define_flag("train_max_retries", 2,
            "Model.fit step-recovery budget: retries of a failed "
            "dispatch (sync to last-good state, emergency checkpoint, "
            "backoff, re-dispatch) before the original exception "
            "propagates.")
define_flag("train_retry_backoff", 0.05,
            "Base seconds of the fit recovery backoff; doubles per "
            "attempt (capped at 2 s).")
define_flag("dataloader_max_worker_restarts", 2,
            "Per-worker restart budget for process DataLoader workers "
            "that die mid-epoch (total budget = this * num_workers); "
            "beyond it the epoch fails with the restart ledger in the "
            "message.")

# The flags a TRACED program can read (kernel dispatch, block tuning,
# matmul precision, nan checks, embedding grad mode) — the flag-tuple
# component of decode program cache keys snapshots exactly this set, so
# changing an eager-only flag (log_level, benchmark, allocator parity
# shims) never invalidates a compiled serving program.
PROGRAM_FLAGS = (
    "fused_block_decode", "fused_block_layers", "use_pallas",
    "flash_attn_min_seqlen", "flash_compact_stats", "flash_dispatch_table",
    "tpu_matmul_precision", "embedding_matmul_grad", "deterministic",
    "check_nan_inf", "check_nan_inf_level",
)


def is_tpu_backend() -> bool:
    """The one definition of "on a TPU": the default backend is the
    native ``tpu`` platform. Gates Pallas-kernel dispatch."""
    import jax
    return jax.default_backend() == "tpu"
