"""Continuous-batching serving engine over the paged KV cache.

Reference parity target: the reference ecosystem's block-attention
serving runtime (PaddleNLP llm serving over block_multihead_attention /
the vLLM scheduler design): requests ADMIT into free batch slots the
moment one opens, every decode step runs the whole fixed-shape batch with
per-slot ragged lengths, and finished sequences return their pages to the
shared pool for the next request.

TPU-native structure: exactly TWO compiled programs serve steady state —
a b=1 prefill per distinct prompt length (bucketable) and ONE fixed-shape
decode step over max_batch slots. Ragged per-slot positions ride the
paged kernel's seq_lens; idle slots write into the reserved null page and
their outputs are ignored. The host loop between tokens is where the
scheduler lives — admission, eviction, and result collection are plain
Python on block tables. That loop runs BESIDE the device, not between
its steps: decode step N+1 is dispatched while step N's tokens are still
on the device (the program takes its input tokens from the last step's
output), and the host reads step N's tokens after that dispatch, one
step late. Whatever needs the values, or moves a seated row, reads the
step in flight first (``ServingEngine._settle``).

Greedy decoding (the deterministic serving mode); sampling composes the
same way via the logits hook.

A model that publishes ``block_spec()`` generates by DIFFUSION OVER
BLOCKS (``models/sdar_moe.py``): its batched step is the BLOCK step
(``ServingEngine._block_step``). A row's unit is a block of B positions
at its cursor; a denoising forward reveals the most confident masked
position of the block, and once none is masked a commit forward stores
the block's K/V, advances the cursor by B and hands the block's tokens
to the host — B + 1 forwards for B tokens a row. The block's token ids
stay on the device between steps and the host knows every row's phase
from counts, so the step rides the same one-step-late read.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as obs
from ..analysis import key_vocab
# unwraps a paddle Tensor and ONLY that: duck-typing on ``._value`` also
# matches jax.Array, whose ``_value`` property is the array copied to the
# host — on the chip that pulled the whole KV pool back every dispatch
from ..core.tensor import _val
from ..kernels.paged_attention import (PagedBlockState, PagedDecodeState,
                                       PagedKVCache, chunk_tile_pairs,
                                       chunk_tiling)
from ..testing import faults
from .cache_manager import (CacheManager, cache_entries,
                            has_recurrent_layers, has_window_layers,
                            kv_heads)
from .program_cache import ProgramBuildError, traces_built

__all__ = ["ServingEngine", "Request"]

# terminal request statuses (Request.status / ServingEngine.status)
OK, FAILED, TIMEOUT = "OK", "FAILED", "TIMEOUT"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    # prompt-suffix tokens still to be teacher-forced through the decode
    # step (prefix-cache admission skipped their prefill)
    pending: List[int] = field(default_factory=list)
    # prefix-cache pages this request adopted (pinned until it finishes)
    pinned: List[int] = field(default_factory=list)
    # telemetry lifecycle stamps (perf_counter): submit time and the
    # last generated-token time (inter-token latency baseline)
    t_submit: float = 0.0
    t_last: float = 0.0
    # absolute perf_counter cutoff (submit(deadline=...)); enforced at
    # step boundaries — None = no deadline
    deadline: Optional[float] = None
    # terminal status ("PENDING" while queued/in flight)
    status: str = "PENDING"
    error: Optional[str] = None
    # replay-recovery bookkeeping: consecutive no-progress replays, and
    # the (tokens, prefill-cursor) high-water mark at the last failure
    # (progress on EITHER axis resets the budget — a long prompt's
    # chunks are progress before any token exists)
    retries: int = 0
    progress_mark: Tuple[int, int] = (-1, -1)
    # chunked-prefill cursor: tokens of ``feed`` already written to the
    # KV pool (None = not mid-prefill); ``feed`` is the teacher-forced
    # token stream (prompt, plus emitted tokens on replay)
    prefill_pos: Optional[int] = None
    feed: Optional[np.ndarray] = None
    # streaming callbacks deliberately do NOT live on the request: they
    # are engine-local state (``ServingEngine._callbacks``, rid ->
    # on_token), stripped at every export seam and re-bound on
    # inject/adopt — a bound callable inside a handoff bundle cannot
    # cross the process boundary the fleet transport serializes over
    # prefix-aware admission bookkeeping: how many cached-prefix
    # requests bypassed THIS request while it was the page-blocked head
    bypassed: int = 0
    # SLO preemption bookkeeping: times this request was unseated for a
    # tighter-deadline arrival (bounded by FLAGS_serving_preempt_budget;
    # never counts against the replay-recovery retry budget)
    preempts: int = 0
    # ---- speculative decoding (r16) ---------------------------------
    # sampling law (temperature 0 = greedy); temperature > 0 requires a
    # draft-model engine — the spec verify program is the only sampler
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    # per-request adaptive draft length: current γ rung (0 = none yet)
    # and the accept-rate EMA that moves it. Both SURVIVE replay — the
    # draft's observed agreement is a property of the request's text,
    # not of the admission that learned it
    gamma: int = 0
    spec_ema: float = 0.5
    # transient: the draft pool holds this slot's allocation (the draft
    # KV cursor itself is the draft pool's seq_lens row)
    spec_ready: bool = False
    # transient: tokens of this request that dispatched decode steps
    # will give and the host has not read yet (0 or 1 between steps; a
    # block model's commit forward puts a block's new tokens in flight)
    in_flight: int = 0
    # ---- block diffusion (a model with ``block_spec()``) --------------
    # transient: positions of the row's current block still masked (0:
    # the next forward commits the block), and how many of its leading
    # positions are prompt tokens (the prompt's remainder, first block)
    masks: int = 0
    known: int = 0


class _Flight(NamedTuple):
    """A batched decode step that was dispatched and not read yet. A
    block step's ``toks`` are the rows' blocks after the forward,
    (rung, B), and its ``rows`` only those that COMMIT in it, each with
    the count of leading block positions that are prompt tokens."""
    toks: Any                   # (rung,) int32 on the device
    rows: List[tuple]           # (slot, the request it decodes[, known])
    t0: float                   # when its dispatch began


class _ReadFirst(Exception):
    """The scheduler is about to move or end a seated row while a
    decode step is in flight: :meth:`ServingEngine._step_inner` reads
    that step (``reason`` names why) and schedules again."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


_POOL_STATES = ("used", "free", "shared", "pinned", "spilled")

# schema version of the harvest_request/adopt_request handoff bundle:
# bumped whenever the bundle's field set changes, and validated at
# adopt — a disaggregated pair built from different revisions must
# refuse loudly instead of mis-seating pages. 2: ``state``, the
# recurrent layers' rows (None for a model that has none)
HANDOFF_SCHEMA_VERSION = 2


class _EngineTelemetry:
    """Pre-bound instrument handles for the serving hot path: resolved
    once per engine, one attribute read per write inside ``step()`` —
    no registry lookups, no flag reads per token.

    Every family carries a ``replica`` label (r14): two engines in one
    process — the fleet case — used to collide on one series, so one
    replica's TTFT polluted another's and the KV gauges flapped between
    pools. The label is threaded from the engine's ``replica`` id and
    each engine binds its own child instruments here, once.

    Every family also carries a ``tp`` label (r19, the tensor-parallel
    degree, "1" for a solo engine): one FLT005-clean schema per family
    everywhere it is registered, so a tp=2 engine's series never merge
    with a solo replica's in a mixed fleet."""

    def __init__(self, replica: str = "0", tp: str = "1",
                 block: bool = False, experts: bool = False):
        r = obs.registry()
        t = obs.tracer()
        rl = ("replica", "tp")

        def c(name, help):
            return r.counter(name, help,
                             labels=rl).labels(replica=replica, tp=tp)

        def g(name, help):
            return r.gauge(name, help,
                           labels=rl).labels(replica=replica, tp=tp)

        def h(name, help):
            return r.histogram(name, help,
                               labels=rl).labels(replica=replica, tp=tp)

        self.span = t.span
        self.phase = t.phase
        self.event = t.event
        self.submitted = c(
            "serving_requests_submitted", "requests accepted by submit()")
        self.finished = c(
            "serving_requests_finished", "requests that completed")
        self.prefills = c(
            "serving_prefills", "b=1 prefill programs dispatched")
        self.shared_admits = c(
            "serving_shared_admissions",
            "admissions that adopted cached prefix pages (prefill skipped)")
        self.decode_steps = c(
            "serving_decode_steps", "full-batch decode steps dispatched")
        # ---- what the scheduler's batching decided, counted where the
        # dispatch is made: rows over slots is the share of the paid-for
        # batch that did work
        self.decode_rows = c(
            "serving_decode_rows",
            "rows of the dispatched decode steps that held a decoding "
            "request")
        self.decode_slots = c(
            "serving_decode_slots",
            "rows the dispatched decode programs computed (each step's "
            "ladder rung)")
        self.decode_live_tokens = c(
            "serving_decode_live_tokens",
            "cached tokens of the decoding rows, summed over the decode "
            "steps — the KV each step had to read")
        self.decode_window_tokens = c(
            "serving_decode_window_tokens",
            "positions a WINDOW layer reads for the decoding rows, "
            "min(len + 1, window) each, summed over the decode steps "
            "(written only for a model with window layers)")
        self.chunk_attn_pairs = c(
            "serving_chunk_attn_pairs",
            "query-key pairs a layer that keeps the whole cache had to "
            "compute in the chunk dispatches: a real query at position p "
            "sees p + 1 keys (pad rows of a final chunk are not counted)")
        self.chunk_window_pairs = c(
            "serving_chunk_window_pairs",
            "the same in a WINDOW layer, min(p + 1, window) keys a query "
            "(written only for a model with window layers)")
        self.chunk_attn_tile_pairs = c(
            "serving_chunk_attn_tile_pairs",
            "query-key pairs of the (query tile, key block) pairs "
            "paged_chunk_attention computed for those chunks in such a "
            "layer, masked pairs and pad rows included: "
            "serving_chunk_attn_pairs over this is the fill of what was "
            "computed")
        self.chunk_window_tile_pairs = c(
            "serving_chunk_window_tile_pairs",
            "the same in a WINDOW layer, beside "
            "serving_chunk_window_pairs")
        self.window_pages_released = c(
            "serving_window_pages_released",
            "pages the window layers' pool took back from rows whose "
            "window slid past them")
        self.decode_read_pages = c(
            "serving_decode_read_pages",
            "pool pages the decode steps' paged attention reads: over ALL "
            "rows of the rung, the pages a row holds with the step's token")
        self.decode_table_pages = c(
            "serving_decode_table_pages",
            "page slots of the dispatched decode steps' block tables "
            "(rung x pages a row): what a grid over the table visits")
        # ---- the overlap of host and device: a decode step is either
        # dispatched behind one that is still in flight, or the step
        # before it was read first, for a reason. With the steps dropped
        # unread the two add up to serving_decode_steps
        self.decode_overlapped = c(
            "serving_decode_overlapped",
            "decode steps dispatched while the step before them was "
            "still in flight, its tokens unread: the host's work for "
            "the step ran beside the device")
        settles = r.counter(
            "serving_decode_settles",
            "decode steps whose tokens were read (or dropped) with no "
            "step dispatched behind them, by what needed them: a rung "
            "migration, a preemption, a deadline, a handoff, "
            "speculation, the last decoding row gone, or a recovery, an "
            "export or the watchdog, which drop the step",
            labels=rl + ("reason",))
        self.decode_settles = lambda reason: settles.labels(
            replica=replica, tp=tp, reason=reason)
        self.prefill_tokens = c(
            "serving_prefill_tokens",
            "prompt tokens put through prefill compute (a monolithic "
            "prompt whole, a chunk by its real tokens — pad excluded)")
        self.ttft = h(
            "serving_ttft_seconds",
            "time to first generated token, submit() to host-visible")
        self.itl = h(
            "serving_inter_token_seconds",
            "per-request latency between consecutive generated tokens")
        self.queue_depth = g(
            "serving_queue_depth", "requests waiting for a batch slot")
        self.occupancy = g(
            "serving_batch_occupancy",
            "active slots in the fixed-shape decode batch")
        self.kv_pages_in_use = g(
            "serving_kv_pages_in_use",
            "KV pool pages held by sequences or the prefix cache "
            "(excludes the reserved null page)")
        self.prefix_pinned = g(
            "serving_prefix_pinned_pages",
            "prefix-cache pages pinned by in-flight requests — the "
            "pressure that caps evict() reclaim")
        self.evict_short = c(
            "serving_prefix_evict_shortfall_pages",
            "pages evict() was asked for but could not free "
            "(pinned/shared)")
        # ---- fault-tolerance instruments (replay recovery, r10)
        self.retries = c(
            "serving_retries_total",
            "in-flight request replays re-queued by recovery after a "
            "failed dispatch")
        self.recoveries = c(
            "serving_recoveries",
            "replay-recovery events: failed dispatch -> fresh pools + "
            "re-queue of all in-flight requests")
        self.requests_failed = c(
            "serving_requests_failed",
            "requests terminated FAILED (no-progress retry budget "
            "exhausted)")
        self.requests_timeout = c(
            "serving_requests_timeout",
            "requests terminated TIMEOUT (per-request deadline or the "
            "run(max_wall=...) watchdog)")
        self.recovery_seconds = h(
            "serving_recovery_seconds",
            "wall clock of one replay recovery (fresh pools + requeue, "
            "excluding backoff sleep)")
        self.page_pressure = g(
            "serving_page_pressure",
            "KV pages short at the last page-blocked admission (0 = "
            "admission is not page-blocked)")
        # ---- continuous-batching instruments (chunked prefill +
        # bucket ladder, r12)
        self.prefill_chunk_s = h(
            "serving_prefill_chunk_seconds",
            "wall clock of one chunked-prefill chunk dispatch — the "
            "bound on how long a long-prompt arrival can stall decode")
        self.decode_stall_s = h(
            "serving_decode_stall_seconds",
            "per-step wall clock decoding slots spent waiting on "
            "scheduler + prefill work before the decode dispatch "
            "(observed only on steps that ran prefill work while "
            "decode-ready requests were waiting)")
        self.bucket = g(
            "serving_bucket",
            "current decode batch-bucket rung of the bucket ladder")
        self.migrations = c(
            "serving_bucket_migrations",
            "bucket-ladder migrations (grow or shrink) — each rung's "
            "program compiles once, so steady state stops migrating "
            "or cycles between already-compiled rungs")
        # ---- SLO-aware preemption (r14)
        self.preemptions = c(
            "serving_preemptions",
            "running requests unseated for a tighter-deadline arrival "
            "and re-queued for bit-identical replay from host state")
        self.preempted_tokens = c(
            "serving_preempted_tokens_replayed",
            "decode tokens preemption victims will regenerate on "
            "replay — the compute a preemption trades for deadline "
            "slack")
        # ---- speculative decoding (r16)
        self.spec_rounds_c = c(
            "serving_spec_rounds",
            "speculation rounds retired (one draft-propose scan + one "
            "target-verify chunk per round)")
        self.spec_accept = h(
            "serving_spec_accept_rate",
            "per-round fraction of draft proposals the target verify "
            "accepted — the signal per-request adaptive γ follows")
        self.spec_accepted = c(
            "serving_spec_tokens_accepted",
            "draft-proposed tokens the target verify accepted")
        self.spec_rejected = c(
            "serving_spec_tokens_rejected",
            "draft-proposed tokens the target verify rejected — their "
            "KV positions rolled back to the accepted length and the "
            "next dispatch overwrites them")
        self.spec_gamma = g(
            "serving_spec_gamma",
            "γ (draft tokens per round) of the most recent speculation "
            "round: per-request adaptive within the "
            "FLAGS_serving_spec_rungs set, capped down as batch "
            "occupancy prices speculation out")
        # ---- tensor-parallel decode (r19)
        self.collective_s = h(
            "serving_collective_seconds",
            "wall clock of one tensor-parallel sharded decode dispatch "
            "(per-layer psum pair + compute), observed host-side at the "
            "dispatch boundary — only tp > 1 engines write it")
        # ---- memwatch pool ledger (r13): step-end gauges over the
        # PagedKVCache ledger, pre-resolved per state label; "spilled"
        # (r14) is the host-RAM tier
        pages = r.gauge(
            "kv_pool_pages",
            "KV page-pool ledger by state: used (held by sequences or "
            "the prefix cache), free, shared (refcount > 1), pinned "
            "(prefix pages an in-flight request's block table holds), "
            "spilled (prefix pages resident only in the host-RAM tier)",
            labels=("replica", "tp", "state"))
        pbytes = r.gauge(
            "kv_pool_bytes",
            "KV page-pool ledger in bytes (all layers, k+v)",
            labels=("replica", "tp", "state"))
        self.pool_pages = {s: pages.labels(replica=replica, tp=tp, state=s)
                           for s in _POOL_STATES}
        self.pool_bytes = {s: pbytes.labels(replica=replica, tp=tp,
                                            state=s)
                           for s in _POOL_STATES}
        # ---- a model with window layers keeps a second pool: each by
        # the pages in use, written only by an engine that has both
        by_pool = r.gauge(
            "serving_kv_pool_bytes",
            "bytes of the KV pages in use, by pool: global (the layers "
            "that keep every page of a request) and window (the layers "
            "whose pages are given back as the window slides)",
            labels=("replica", "tp", "pool"))
        self.kv_pool_bytes = {p: by_pool.labels(replica=replica, tp=tp,
                                                pool=p)
                              for p in ("global", "window")}
        self.pool_frag = g(
            "kv_pool_fragmentation",
            "free-list fragmentation: 1 - largest contiguous free run "
            "/ free pages (0 = clean; recomputed only when the free "
            "list changed)")
        self.host_tier_peak = g(
            "kv_host_tier_peak_pages",
            "high-water mark of pages resident in the host-RAM KV "
            "tier — the tier watermark memwatch prices against host "
            "memory")
        # ---- the recurrent-state store (a model with state-space
        # layers): written only by an engine that has one
        self.state_bytes = g(
            "serving_state_bytes",
            "device bytes of the recurrent-state store: every slot's "
            "row of every recurrent layer (SSM state float32 + "
            "convolution window), resident whether or not the slot is "
            "taken")
        self.state_slots_live = g(
            "serving_state_slots_live",
            "slots whose recurrent-state rows belong to a seated "
            "request")
        self.state_resets = c(
            "serving_state_resets",
            "recurrent-state rows zeroed at admission (one per "
            "admission that runs prefill compute)")
        self.state_moves = c(
            "serving_state_moves",
            "recurrent-state rows copied slot to slot on the device by "
            "a bucket-ladder migration")
        self.state_exports = c(
            "serving_state_exports",
            "recurrent-state rows snapshotted to the host for a "
            "harvest_request handoff")
        self.counter_track = t.counter
        # ---- the step clock: one round of ``step()`` and the host's
        # phases inside it, published as the round ends. Each phase a
        # family of its own (a reader sums a family over its labels)
        self.clock = t.step_clock("serving")
        self.steps = c(
            "serving_steps", "rounds of step(), idle ones too")
        self.step_seconds = c(
            "serving_step_seconds", "wall clock of those rounds")
        self.slow_steps = c(
            "serving_slow_steps",
            "rounds over 0.1 s and over 3 times the mean of the 64 "
            "before them: each left a record in tracer().slow_steps()")
        self.slow_step_seconds = c(
            "serving_slow_step_seconds",
            "what those rounds ran over the mean they were held against")
        # counter -> the phases whose self seconds in the round it takes
        self.phase_seconds = [(c(name, help), [t.phase(n) for n in phases])
                           for name, help, phases in (
            ("serving_decode_dispatch_seconds",
             "the host inside the call of the compiled decode (or "
             "block) program",
             ("engine.decode.dispatch", "engine.block.dispatch")),
            ("serving_decode_stage_inputs_seconds",
             "staging a decode step: the feed and the cache manager's "
             "host tables",
             ("engine.decode.stage.inputs", "engine.block.stage.inputs")),
            ("serving_decode_stage_put_seconds",
             "staging a decode step: the one device_put of its inputs",
             ("engine.decode.stage.put", "engine.block.stage.put")),
            ("serving_decode_stage_caches_seconds",
             "staging a decode step: taking the pools' arrays",
             ("engine.decode.stage.caches", "engine.block.stage.caches")),
            ("serving_prefill_chunk_host_seconds",
             "a prefill chunk's uploads and dispatch, its token's "
             "wait left out",
             ("engine.prefill_chunk",)),
            ("serving_wait_seconds",
             "the host waiting for the device: the read of a decode "
             "step's tokens, of a prefill's or a final chunk's token",
             ("engine.decode.pull", "engine.prefill.pull")))]
        if block:
            # ---- block diffusion: a step, a row and a token are three
            # things there. Bound only by an engine whose model
            # generates by blocks, so no other engine has the series
            self.block_forwards = c(
                "serving_block_forwards",
                "row-forwards of the dispatched block steps: one a row "
                "that ran a denoising or a commit forward")
            self.block_commits = c(
                "serving_block_commits",
                "commit forwards: rows whose block was stored and whose "
                "cursor advanced by the block length")
            self.block_tokens = c(
                "serving_block_tokens",
                "tokens the commit forwards committed (a block less the "
                "prompt tokens it began with)")
        if experts:
            # ---- expert layers: bound only by an engine whose model
            # returns its expert layers' counts
            self.moe_assignments = c(
                "moe_assignments",
                "token-to-expert assignments the expert layers computed "
                "(every step and prefill, pad tokens too), from the "
                "counts kept on the device and read by "
                "ServingEngine.expert_histogram")
            self.moe_experts_touched = c(
                "moe_experts_touched",
                "(call, layer, expert) triples that got at least one "
                "assignment: how many experts' weights the grouped "
                "matmuls had to read")


class _PrefixTelemetry:
    def __init__(self, replica: str = "0"):
        r = obs.registry()
        rl = ("replica",)

        def c(name, help):
            return r.counter(name, help, labels=rl).labels(replica=replica)

        self.hits = c(
            "prefix_cache_hits", "lookups that matched >= 1 cached page")
        self.misses = c(
            "prefix_cache_misses", "lookups that matched nothing")
        self.hit_pages = c(
            "prefix_cache_hit_pages", "cached pages returned by lookups")
        self.registered_pages = c(
            "prefix_cache_registered_pages",
            "new prompt pages registered into the trie")
        self.evicted_pages = c(
            "prefix_cache_evicted_pages",
            "pages actually returned to the free list by evict()")
        # ---- host-RAM tiering (r14)
        self.spilled_pages = c(
            "prefix_cache_spilled_pages",
            "cold prefix pages spilled to the host-RAM tier (device "
            "page freed, KV bytes retained host-side)")
        self.restored_pages = c(
            "prefix_cache_restored_pages",
            "spilled prefix pages paged back onto the device on "
            "prefix adoption")
        self.dropped_spilled = c(
            "prefix_cache_dropped_spilled_pages",
            "spilled pages evicted from the host tier entirely "
            "(host-tier budget pressure)")


class PrefixCache:
    """Page-aligned prompt-prefix trie over a :class:`PagedKVCache`
    (reference parity target: the vLLM-style automatic prefix caching in
    the reference's serving ecosystem).

    Each node maps one FULL page of prompt tokens (keyed by its parent
    chain, so equal chunks under different prefixes never collide) to the
    page id holding that chunk's KV. Registered pages carry a cache
    reference, so they survive their creating request and later requests
    with the same prefix adopt them read-only instead of re-running
    prefill. Causality makes this sound: KV at position i depends only on
    tokens 0..i, so equal page-aligned prefixes have bitwise-equal pages.
    Eviction drops least-recently-used LEAF nodes only (an interior node
    must outlive its children or their chains become unreachable).

    Host-RAM tiering (r14, ``host_tier_pages`` > 0): eviction pressure
    first SPILLS cold nodes — device page copied to host RAM
    (:meth:`PagedKVCache.spill_page`) and returned to the free list,
    trie node kept with the host copy — and ``lookup`` pages spilled
    chain nodes back in on adoption (one restore write beats re-running
    the chunk's prefill compute). Spill candidates come straight from
    the r13 ledger states: only pages the cache alone references
    (rc == 1, i.e. not ``shared`` with a live sequence) and that no
    in-flight request pins; when free-list fragmentation is high the
    policy prefers spilling pages adjacent to free runs, so spills heal
    the free list instead of shredding it further. Past the host-tier
    budget the coldest spilled LEAF nodes drop entirely (classic
    eviction)."""

    _ROOT = ("root",)

    def __init__(self, pool: PagedKVCache, replica: str = "0",
                 host_tier_pages: int = 0):
        self.pool = pool
        self.page_size = pool.page_size
        self.host_tier_pages = int(host_tier_pages)
        # key -> {"page": int|None, "parent": key, "children": int,
        #         "tick": int, "pins": int, "host": HostPage|None}
        # (page is None exactly while the node is spilled)
        self._nodes: Dict[tuple, dict] = {}
        self._by_page: Dict[int, tuple] = {}    # page id -> node key
        self._tick = 0
        self._pinned_nodes = 0      # nodes with pins > 0 (O(1) gauge)
        self._spilled_nodes = 0     # nodes in the host tier (O(1))
        self._f_spill = faults.site("kv_spill")
        self._m = _PrefixTelemetry(replica)

    def _chunks(self, prompt: np.ndarray):
        key = self._ROOT
        for i in range(0, (len(prompt) // self.page_size) * self.page_size,
                       self.page_size):
            chunk = prompt[i:i + self.page_size].tobytes()
            key = (key, chunk)
            yield key

    def lookup(self, prompt: np.ndarray, max_cover: Optional[int] = None):
        """Longest cached page-aligned prefix: (page_ids, n_tokens).
        Spilled chain nodes are paged back in from the host tier when a
        free device page exists (restore is one pool write; the
        alternative is re-running the chunk's prefill compute); the hit
        ends at the first spilled node that cannot be restored.
        ``max_cover`` caps the returned coverage in tokens — the engine
        passes ``len(prompt) - 1`` because it can never adopt a
        whole-prompt hit (the first generated token's logits are not
        cached), and a restore spent on a page the caller then discards
        would consume a free page for nothing."""
        self._tick += 1
        pages: List[int] = []
        for key in self._chunks(prompt):
            if max_cover is not None and \
                    (len(pages) + 1) * self.page_size > max_cover:
                break           # the caller could not adopt this page
            node = self._nodes.get(key)
            if node is None:
                break
            if node["host"] is not None:
                if self.pool.free_page_count() == 0:
                    break       # no room to page in: hit ends here
                self._restore_node(key, node)
            node["tick"] = self._tick
            pages.append(node["page"])
        if pages:
            self._m.hits.inc()
            self._m.hit_pages.inc(len(pages))
        else:
            self._m.misses.inc()
        return pages, len(pages) * self.page_size

    def _restore_node(self, key: tuple, node: dict) -> None:
        """Page one spilled node back onto the device: fresh page off
        the free list, host bytes written back, cache reference
        restored. The fault check runs BEFORE any mutation, so an
        injected restore failure leaves the tier consistent and simply
        propagates into replay recovery."""
        self._f_spill.check(op="restore")
        pid = self.pool.take_free_page()
        self.pool.restore_page(node["host"], pid)
        node["host"] = None
        node["page"] = pid
        self._by_page[pid] = key
        self._spilled_nodes -= 1
        self._m.restored_pages.inc()

    def register(self, prompt: np.ndarray, block_row) -> None:
        """Pin the full prompt pages of a just-prefilled sequence."""
        self._tick += 1
        for i, key in enumerate(self._chunks(prompt)):
            node = self._nodes.get(key)
            if node is not None:        # dedup: keep the existing page
                node["tick"] = self._tick
                if node["host"] is not None:
                    # the just-prefilled sequence re-materialized this
                    # chunk's KV on device (equal page-aligned prefixes
                    # are bitwise-equal): flip the node back to
                    # resident on the sequence's page and drop the
                    # host copy — a free re-adoption
                    self.pool.forget_spilled(node["host"])
                    node["host"] = None
                    node["page"] = int(block_row[i])
                    self._by_page[int(block_row[i])] = key
                    self._spilled_nodes -= 1
                    self.pool.ref_page(int(block_row[i]))
                continue
            parent = key[0] if key[0] in self._nodes else None
            self._nodes[key] = {"page": int(block_row[i]), "parent": parent,
                                "children": 0, "tick": self._tick,
                                "pins": 0, "host": None}
            self._by_page[int(block_row[i])] = key
            if parent is not None:
                self._nodes[parent]["children"] += 1
            self.pool.ref_page(int(block_row[i]))
            self._m.registered_pages.inc()

    def pin(self, pages) -> None:
        """Mark cached pages as adopted by an in-flight request: a pinned
        node is untouchable by ``evict`` until ``unpin``, independent of
        what the pool's reference counts happen to say. Call on
        adoption; ``unpin`` when the adopting request finishes."""
        for pid in pages:
            key = self._by_page.get(int(pid))
            if key is not None:
                node = self._nodes[key]
                node["pins"] += 1
                if node["pins"] == 1:
                    self._pinned_nodes += 1

    def unpin(self, pages) -> None:
        for pid in pages:
            key = self._by_page.get(int(pid))
            if key is not None and self._nodes[key]["pins"] > 0:
                node = self._nodes[key]
                node["pins"] -= 1
                if node["pins"] == 0:
                    self._pinned_nodes -= 1

    def evict(self, n_pages: int) -> int:
        """Free up to ``n_pages`` device pages, REFUSING any node that
        is pinned by an in-flight request's block table (pin count from
        adoption) or whose page anyone besides the cache still
        references (rc > 1). With a host tier armed, cold nodes SPILL
        first (device page freed, KV retained host-side for later
        restore); whatever spilling cannot cover falls back to dropping
        LRU leaf nodes outright. Returns the number of pages actually
        returned to the free list — callers size retry loops on real
        capacity, so unrefs that free nothing don't count."""
        freed = self.spill(n_pages) if self.host_tier_pages > 0 else 0
        dropped = 0
        while freed + dropped < n_pages:
            leaves = [(node["tick"], key) for key, node in
                      self._nodes.items()
                      if node["children"] == 0 and node["pins"] == 0
                      and node["host"] is None
                      and self.pool._page_rc[node["page"]] == 1]
            if not leaves:
                break
            _, key = min(leaves, key=lambda t: t[0])
            if self._drop_node(key):
                dropped += 1
        if dropped:
            self._m.evicted_pages.inc(dropped)
        return freed + dropped

    def _drop_node(self, key: tuple) -> bool:
        """Remove one trie node entirely. Returns True when a DEVICE
        page actually returned to the free list (a spilled node's drop
        frees host RAM, not device pages)."""
        node = self._nodes.pop(key)
        if node["parent"] is not None:
            self._nodes[node["parent"]]["children"] -= 1
        if node["host"] is not None:
            self.pool.forget_spilled(node["host"])
            self._spilled_nodes -= 1
            return False
        self._by_page.pop(node["page"], None)
        return self.pool.unref_page(node["page"])

    def spill(self, n_pages: int) -> int:
        """Move up to ``n_pages`` cold resident nodes to the host tier,
        freeing their device pages. Candidates are exactly what the
        r13 ledger calls cache-only pages: unpinned, rc == 1 (a shared
        or adopted page never spills under a live reader). LRU order;
        under high free-list fragmentation the policy prefers, among
        the colder half, pages adjacent to the current free list so
        each spill extends a contiguous run. Past the host budget the
        coldest spilled leaves drop entirely."""
        freed = 0
        # one sort per spill() call (the per-page state this loop
        # mutates never re-ranks the survivors; re-sorting per page
        # made a blocked admission quadratic in the spill batch)
        cands = sorted(
            ((node["tick"], key) for key, node in self._nodes.items()
             if node["host"] is None and node["pins"] == 0
             and self.pool._page_rc[node["page"]] == 1),
            key=lambda t: t[0])     # trie keys are not comparable
        frag = (len(cands) > 1
                and self.pool.free_list_fragmentation() > 0.5)
        free = set(self.pool._free) if frag else None
        while freed < n_pages and cands:
            # the host tier is a HARD budget (operators size it
            # against real host RAM): make room by dropping the
            # coldest spilled leaves BEFORE spilling in, and stop
            # spilling entirely when nothing is droppable (all
            # spilled nodes interior with live children)
            if self._spilled_nodes >= self.host_tier_pages:
                self._drop_spilled_until(self.host_tier_pages - 1)
                if self._spilled_nodes >= self.host_tier_pages:
                    break
            idx = 0
            if frag:
                # fragmentation-aware tie-break: among the colder half,
                # spill a page that extends an existing free run
                for j in range(max(1, len(cands) // 2)):
                    pid = self._nodes[cands[j][1]]["page"]
                    if pid + 1 in free or pid - 1 in free:
                        idx = j
                        break
            _, key = cands.pop(idx)
            node = self._nodes[key]
            pid = node["page"]
            # fault check BEFORE mutation: an injected spill failure
            # leaves the node resident and propagates into replay
            self._f_spill.check(op="spill", page=pid)
            node["host"] = self.pool.spill_page(pid)
            node["page"] = None
            self._by_page.pop(pid, None)
            self._spilled_nodes += 1
            if self.pool.unref_page(pid):
                freed += 1
                if free is not None:
                    free.add(pid)
            self._m.spilled_pages.inc()
        return freed

    def _drop_spilled_until(self, limit: int) -> None:
        """Drop the coldest spilled LEAF nodes until the host tier
        holds at most ``limit`` pages (an interior spilled node waits
        for its children — dropping it would orphan their chains).
        ``spill`` calls this before every page it moves in, so the
        spilled census never exceeds ``host_tier_pages``."""
        while self._spilled_nodes > max(0, limit):
            spilled_leaves = [(node["tick"], key) for key, node in
                              self._nodes.items()
                              if node["host"] is not None
                              and node["children"] == 0
                              and node["pins"] == 0]
            if not spilled_leaves:
                break
            _, key = min(spilled_leaves, key=lambda t: t[0])
            self._drop_node(key)
            self._m.dropped_spilled.inc()

    def spilled_page_count(self) -> int:
        """Pages currently resident only in the host tier (O(1))."""
        return self._spilled_nodes

    def evictable_page_count(self) -> int:
        """Device pages ``evict``/``spill`` could free right now —
        resident, unpinned, cache-only (rc == 1). The preemption
        trigger consults this so a tight-deadline arrival never
        preempts a victim while plain eviction could still pay its
        page bill. With a host tier armed, any such node spills
        regardless of trie position; without one, ``evict`` drops
        LEAVES only, so a pinned/shared/spilled descendant blocks
        every ancestor from the cascade — counting those would make
        the preemption trigger skip a victim for pages eviction can
        never actually free."""
        free_ok = (lambda node: node["host"] is None
                   and node["pins"] == 0
                   and self.pool._page_rc[node["page"]] == 1)
        blocked: set = set()
        for node in self._nodes.values():
            if free_ok(node):
                continue
            k = node["parent"]
            while k is not None and k not in blocked:
                blocked.add(k)
                parent = self._nodes.get(k)
                k = parent["parent"] if parent is not None else None
        droppable = sum(1 for key, node in self._nodes.items()
                        if key not in blocked and free_ok(node))
        if self.host_tier_pages <= 0:
            return droppable
        # tier armed: nodes beyond the leaf-drop cascade free via
        # SPILL, but only as far as the HARD tier budget has room —
        # current headroom plus droppable spilled leaves (each drop
        # opens one slot; no cascade credit, so this under- rather
        # than over-estimates and the preemption trigger errs toward
        # protecting the deadline)
        flat = sum(1 for node in self._nodes.values() if free_ok(node))
        room = max(0, self.host_tier_pages - self._spilled_nodes)
        room += sum(1 for node in self._nodes.values()
                    if node["host"] is not None
                    and node["children"] == 0 and node["pins"] == 0)
        return droppable + min(room, max(0, flat - droppable))

    def pinned_page_count(self) -> int:
        """Pages untouchable by ``evict`` because an in-flight request's
        block table still points at them — the pinned-page pressure a
        shortfalling evict() reports instead of silently under-freeing.
        O(1): maintained on pin/unpin transitions (evict only ever drops
        pins==0 nodes), so the per-step gauge refresh costs nothing."""
        return self._pinned_nodes

    def peek(self, prompt: np.ndarray,
             include_spilled: bool = False) -> int:
        """Length (tokens) of the cached page-aligned prefix WITHOUT
        touching LRU ticks or hit/miss telemetry — the scheduler's
        prefix-aware admission probe (``lookup`` is the real,
        stats-bearing read at admission time). By default the probe
        counts DEVICE-resident pages only, so admission pricing stays
        honest (restoring a spilled page consumes a free page, exactly
        like fresh allocation); the fleet router's affinity probe passes
        ``include_spilled=True`` because a host-tier hit still beats
        re-running prefill on a cold replica."""
        n = 0
        for key in self._chunks(prompt):
            node = self._nodes.get(key)
            if node is None:
                break
            if node["host"] is not None and not include_spilled:
                break
            n += self.page_size
        return n


class ServingEngine:
    """Drive ``model`` (a GenerationMixin Layer) as a continuous-batching
    server. ``submit`` enqueues (deadline-slack-ordered, prefix-cache-
    aware admission); each ``step`` admits waiting requests, runs at
    most ONE prefill chunk (long prompts interleave with decode instead
    of stalling it), migrates the decode batch between bucket-ladder
    rungs as occupancy changes, and decodes one token for every active
    slot. ``run`` steps until drained and returns {rid: tokens}; the
    non-blocking surface is ``run_step``/``poll`` plus per-token
    ``submit(on_token=...)`` streaming callbacks.

    With ``draft_model=`` the engine decodes SPECULATIVELY (r16): the
    draft proposes γ tokens in one scanned dispatch, the target checks
    all of them (plus the bonus position) in one (1, γ+1) chunk through
    the r12 chunked-prefill machinery, and the KV cursors of both pools
    roll to exactly the accepted length. Greedy output is bit-identical
    to the non-speculative engine by construction; ``submit`` requests
    with ``temperature > 0`` sample losslessly through the rejection
    test. γ adapts per request from the observed accept rate, and a
    speculating request bills γ+1 decode slots against the
    FLAGS_serving_spec_max_slots budget, so rising batch occupancy caps
    γ down and finally prices speculation out in favor of the plain
    batched decode step."""

    def __init__(self, model, max_batch: int = 4, page_size: int = 64,
                 num_pages: Optional[int] = None, max_seq_len: int = 1024,
                 prefix_cache: bool = False,
                 bucket_ladder: Optional[Tuple[int, ...]] = None,
                 prefill_chunk: Optional[int] = None,
                 replica: str = "0",
                 host_tier_pages: Optional[int] = None,
                 draft_model=None,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 tp_degree: Optional[int] = None):
        from .. import flags as _flags
        from ..jit import ensure_live

        self.model = model
        # identity of this engine in a multi-engine (fleet) process:
        # threaded as the `replica` label through every metric family,
        # so per-replica series never collide
        self.replica = str(replica)
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        if num_pages is None:
            # the pool budget decouples from the ladder's top rung:
            # FLAGS_serving_page_budget caps memory and lets admission
            # control absorb the difference; 0 keeps the worst-case
            # formula
            budget = int(_flags.get_flag("serving_page_budget"))
            # +1 pays for the reserved null page in BOTH modes, so a
            # budget of N means N USABLE pages (the formula's explicit
            # +1 already did)
            num_pages = (budget + 1 if budget > 0 else
                         1 + max_batch * (-(-max_seq_len // page_size)))
        # ---- chunked prefill: prompts longer than ``chunk`` prefill in
        # fixed-size chunks interleaved with decode steps (0 = off)
        self.chunk = int(_flags.get_flag("serving_prefill_chunk")
                         if prefill_chunk is None else prefill_chunk)
        if self.chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {self.chunk}")
        # ---- batch-bucket ladder: decode runs at the smallest rung
        # covering demand; rungs above max_batch drop, max_batch is
        # always the top rung (so max_batch=4 == the fixed pre-r12 shape)
        if bucket_ladder is None:
            raw = str(_flags.get_flag("serving_bucket_ladder"))
            rungs = [int(r) for r in raw.replace(";", ",").split(",")
                     if r.strip()]
        else:
            rungs = [int(r) for r in bucket_ladder]
        if any(r < 1 for r in rungs):
            raise ValueError(f"bucket ladder rungs must be >= 1: {rungs}")
        self.ladder: Tuple[int, ...] = tuple(sorted(
            {r for r in rungs if r <= max_batch} | {max_batch}))
        self.bucket = self.ladder[0]
        self.bucket_patience = int(
            _flags.get_flag("serving_bucket_patience"))
        self._shrink_wait = 0
        # prefill-unit fairness flip-flop (chunks' turn when True)
        self._chunk_turn = False
        # host-side probes (test/bench surface)
        self.bucket_migrations = 0
        self.chunk_dispatches = 0
        self.max_decode_stall = 0.0
        params, buffers = model.raw_state()
        ensure_live(params, "call step.sync_to_model() first.")
        self._params, self._buffers = params, buffers
        dtype = jnp.result_type(next(iter(params.values())))
        # ---- quantized serving (r18): KV pool storage dtype and the
        # fused N-layer stacked-weight dtype are engine identity — both
        # reach compiled programs only through DecodeKey.extra
        self.kv_dtype = str(_flags.get_flag("serving_kv_dtype")
                            if kv_dtype is None else kv_dtype)
        if self.kv_dtype not in ("native", "int8"):
            raise ValueError(
                f"kv_dtype must be 'native' or 'int8', got {self.kv_dtype!r}")
        self.weight_dtype = str(_flags.get_flag("fused_weight_dtype")
                                if weight_dtype is None else weight_dtype)
        if self.weight_dtype not in ("native", "int4"):
            raise ValueError(f"weight_dtype must be 'native' or 'int4', "
                             f"got {self.weight_dtype!r}")
        # ---- tensor-parallel decode (r19): shard the stacked fused
        # weights column/row-wise and the paged KV pool over kv-heads
        # across the mp axis. Engine identity like the dtypes above —
        # it reaches compiled programs only through DecodeKey.extra
        self.tp_degree = int(_flags.get_flag("serving_tp_degree")
                             if tp_degree is None else tp_degree)
        if self.tp_degree < 1:
            raise ValueError(
                f"tp_degree must be >= 1, got {self.tp_degree}")
        if has_recurrent_layers(model) or (
                draft_model is not None
                and has_recurrent_layers(draft_model)):
            # each would need something the state store does not have;
            # none falls back to a path that gives other tokens
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a recurrent model: a shared "
                    "prefix would need a snapshot of the recurrent state "
                    "at the prefix boundary, which the store does not keep "
                    "(pages are addressable by position, a recurrence is "
                    "not)")
            if draft_model is not None:
                raise ValueError(
                    "draft_model= with a recurrent model: a rejected "
                    "draft token cannot be rolled back out of a "
                    "recurrence (the KV cursor rolls back by length; the "
                    "state has already absorbed the token)")
            if self.tp_degree > 1:
                raise ValueError(
                    f"tp_degree={self.tp_degree} with a recurrent model: "
                    "the recurrent-state store and the state-update kernel "
                    "are not sharded over heads")
        if has_window_layers(model) or (
                draft_model is not None and has_window_layers(draft_model)):
            # a window layer's pages are given back as its window slides;
            # each of these counts on pages that stay
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a model that has window "
                    "layers: a page that fell out of a row's window is "
                    "given back to the pool, so a cached prefix's window "
                    "pages are not there to share")
            if draft_model is not None:
                raise ValueError(
                    "draft_model= with a model that has window layers: "
                    "the speculation programs address one pool through "
                    "one block table, and a rejected draft token would "
                    "roll the cursor back over pages already given back")
            if self.tp_degree > 1:
                raise ValueError(
                    f"tp_degree={self.tp_degree} with a model that has "
                    "window layers: the window pool has no sharded "
                    "placement (the sharded decode program is built over "
                    "one pool)")
        # ---- expert layers: the width of the counts a model that
        # publishes ``expert_counts_width()`` returns from every
        # program, else 0 (``expert_histogram``)
        width = getattr(model, "expert_counts_width", None)
        self._counts_width = 0 if width is None else int(width())
        # ---- generation by diffusion over blocks: (block length, mask
        # token id) of a model that publishes ``block_spec()``, else None
        spec = getattr(model, "block_spec", None)
        self._block: Optional[Tuple[int, int]] = None
        if spec is not None:
            spec = spec()
            self._block = (int(spec["block_length"]),
                           int(spec["mask_token_id"]))
            blen = self._block[0]
            if page_size % blen:
                raise ValueError(
                    f"page_size {page_size} is no multiple of the model's "
                    f"block length {blen}: a block must not straddle a page")
            # none of these falls back to a path that gives other tokens
            if draft_model is not None:
                raise ValueError(
                    "draft_model= with a block-diffusion model: a step "
                    "there commits a block of tokens every few forwards; "
                    "a draft's token-by-token proposals have no verify "
                    "program to go through")
            if prefix_cache and not self.chunk:
                raise ValueError(
                    "prefix_cache=True with a block-diffusion model needs "
                    "chunked prefill (prefill_chunk > 0): a shared prefix "
                    "ends on a page boundary, which is a block boundary, "
                    "and the rest of the prompt's whole blocks prefill "
                    "from that cursor in chunks; the token-at-a-time "
                    "suffix path of the decode step does not exist here")
            if self.tp_degree > 1:
                raise ValueError(
                    f"tp_degree={self.tp_degree} with a block-diffusion "
                    "model: the block step has no sharded program (its "
                    "expert layer is told which experts it holds, and the "
                    "exchange between holders is not built)")
        if self._counts_width and draft_model is not None:
            raise ValueError(
                "draft_model= with a model that counts its expert "
                "layers' assignments: the speculation programs do not "
                "return them, so moe_assignments and moe_experts_touched "
                "would undercount")
        self._tp_mesh = None
        self._tp_axis = "mp"
        pool_sharding = None
        if self.tp_degree > 1:
            if self.weight_dtype == "int4":
                raise ValueError(
                    "tp_degree > 1 with weight_dtype='int4' is not "
                    "supported: Int4Tiles nibble packing does not commute "
                    "with the head-shard permutation (pack after sharding "
                    "is a chip-window follow-up)")
            heads = kv_heads(model)
            if heads % self.tp_degree:
                raise ValueError(
                    f"tp_degree={self.tp_degree} must divide the model's "
                    f"kv-head count ({heads}) so the paged pool "
                    "partitions evenly over kv-heads")
            from jax.sharding import Mesh as _Mesh
            from jax.sharding import NamedSharding as _NS
            from jax.sharding import PartitionSpec as _P
            from ..distributed.communication.group import resolve_group_axis
            from ..distributed.fleet.base_topology import (
                try_get_hybrid_communicate_group,
            )
            # the mp process group (when fleet.init built one) names the
            # axis and the member devices; a bare runtime falls back to
            # the first tp devices under the canonical "mp" axis name
            hcg = try_get_hybrid_communicate_group()
            group = None
            if (hcg is not None and
                    hcg.get_model_parallel_world_size() == self.tp_degree):
                group = hcg.get_model_parallel_group()
            self._tp_axis = resolve_group_axis(group, "mp")
            devs = jax.devices()
            if group is not None:
                members = [devs[r % len(devs)] for r in group.ranks]
            elif len(devs) >= self.tp_degree:
                members = devs[:self.tp_degree]
            else:
                raise ValueError(
                    f"tp_degree={self.tp_degree} needs that many devices; "
                    f"the runtime has {len(devs)}")
            self._tp_mesh = _Mesh(np.array(members), (self._tp_axis,))
            # canonical partition of every per-layer pool leaf: kv-heads
            # lead on the payload AND the int8 scale band, so one spec
            # shards both together
            pool_sharding = _NS(self._tp_mesh,
                                _P(self._tp_axis, None, None, None))
        # ---- what a request keeps, per layer kind (pages; for a model
        # with recurrent layers also a row of the state store): one
        # owner, generation/cache_manager.py
        geom = dict(max_batch=max_batch, page_size=page_size,
                    max_seq_len=max_seq_len, kv_dtype=self.kv_dtype,
                    pool_sharding=pool_sharding, tp_degree=self.tp_degree,
                    step_tokens=self.chunk)
        self._caches = CacheManager(model, num_pages=num_pages, dtype=dtype,
                                    **geom)
        # how the kernel cuts the chunk program's attention reads, per
        # layer kind (here, where a model it cannot be said of is refused
        # before a step's recovery could swallow the refusal)
        self._chunk_cut = self._chunk_tilings() if self.chunk else ()
        maxpos = getattr(getattr(model, "config", None),
                         "max_position_embeddings", None)
        if maxpos is not None and max_seq_len > maxpos:
            raise ValueError(
                f"engine max_seq_len ({max_seq_len}) exceeds the model's "
                f"max_position_embeddings ({maxpos})")
        # ---- host-RAM KV tier (r14): prefix-cache eviction spills to
        # host RAM up to this many pages instead of dropping (0 = off)
        self.host_tier_pages = int(
            _flags.get_flag("serving_kv_host_tier_pages")
            if host_tier_pages is None else host_tier_pages)
        # ---- SLO-aware preemption (r14): a tight-deadline arrival may
        # unseat the slackest running request (bounded per victim),
        # which replays later from host state bit-identically
        self.preempt_enabled = bool(_flags.get_flag("serving_preempt"))
        self.preempt_budget = int(_flags.get_flag("serving_preempt_budget"))
        self.preempt_margin = float(
            _flags.get_flag("serving_preempt_margin"))
        self.preempt_horizon = float(
            _flags.get_flag("serving_preempt_horizon"))
        self.preemptions = 0        # host probe
        self._host_tier_peak = 0
        # ---- speculative decoding (r16): a draft model turns decode
        # into propose-γ/verify-once rounds. The draft keeps its OWN
        # paged pool in slot lockstep with the target's; the draft
        # pool's seq_lens row IS the draft-KV cursor, so falling behind
        # (admission prefilled the target only, or plain decode ran
        # while speculation was priced out) is detected by comparing
        # the two cursors — no separate bookkeeping to drift
        self.draft_model = draft_model
        self._draft: Optional[CacheManager] = None
        if draft_model is not None:
            dparams, dbuffers = draft_model.raw_state()
            ensure_live(dparams, "call step.sync_to_model() first.")
            self._draft_params, self._draft_buffers = dparams, dbuffers
            dmax = getattr(getattr(draft_model, "config", None),
                           "max_position_embeddings", None)
            if dmax is not None and max_seq_len > dmax:
                raise ValueError(
                    f"engine max_seq_len ({max_seq_len}) exceeds the "
                    f"draft model's max_position_embeddings ({dmax})")
            # ALWAYS worst-case pages (the serving_page_budget cap does
            # not apply): the target pool admits against its budget —
            # possibly on adopted shared-prefix pages — and the draft
            # sync must then never fail an allocate of the same span.
            # Draft KV is a fraction of target KV, so the safety margin
            # is cheap where it matters
            self._draft = CacheManager(
                draft_model,
                num_pages=1 + max_batch * (-(-max_seq_len // page_size)),
                dtype=jnp.result_type(next(iter(dparams.values()))),
                **geom)
            raw = str(_flags.get_flag("serving_spec_rungs"))
            srungs = sorted({int(r) for r in raw.replace(";", ",").split(",")
                             if r.strip()})
            if not srungs or srungs[0] < 1:
                raise ValueError(
                    f"serving_spec_rungs must name rungs >= 1: {raw!r}")
            self.spec_rungs: Tuple[int, ...] = tuple(srungs)
            g0 = int(_flags.get_flag("serving_spec_gamma"))
            self.spec_gamma_default = max(
                r for r in self.spec_rungs if r <= max(g0, srungs[0]))
            self.spec_adaptive = bool(
                _flags.get_flag("serving_spec_adaptive"))
            # slot budget for γ+1 pricing; the floor keeps a lone
            # decode row affordable at the smallest rung even on tiny
            # engines (batch-1 speculation is the headline win)
            self.spec_slots = (int(_flags.get_flag("serving_spec_max_slots"))
                               or max(max_batch, srungs[0] + 1))
            self.spec_sync_chunk = max(
                1, int(_flags.get_flag("serving_spec_sync_chunk")))
            self._f_spec_draft = faults.site("spec_draft")
            self._f_spec_verify = faults.site("spec_verify")
            self._spec_fns: Dict[tuple, object] = {}
            self._spec_keys: Dict[tuple, object] = {}
            self.spec_draft_key = None      # test probes: last-used keys
            self.spec_verify_key = None
            # host probes (bench/test surface)
            self.spec_rounds = 0
            self.spec_tokens_accepted = 0
            self.spec_tokens_rejected = 0
            self.spec_last_gamma = 0
        self._slots: List[Optional[Request]] = [None] * max_batch
        self._queue: List[Request] = []
        self._results: Dict[int, List[int]] = {}
        self._status: Dict[int, str] = {}
        # the newest token of each slot that the HOST knows: the next
        # decode step's input unless the step in flight holds a newer one
        self._last_tok = np.zeros((max_batch,), np.int32)
        # block model: the newest BLOCK of each slot that the host knows,
        # or -1 throughout where the step in flight holds a newer one
        self._blk = (None if self._block is None else np.full(
            (max_batch, self._block[0]), self._block[1], np.int32))
        self._block_fns: Dict[int, object] = {}     # bucket rung -> fn
        # per-expert assignment counts of the block steps, kept on the
        # device and read by expert_histogram(), never in the step
        self._expert_hist = None
        # set-up probe (record_blocks): rid -> the row's forwards
        self._block_records: Optional[Dict[int, list]] = None
        # the decode step dispatched and not read yet (None: none)
        self._flying: Optional[_Flight] = None
        self._next_rid = 0
        self._prefill_fn = None
        self._chunk_fn = None
        self._decode_fns: Dict[int, object] = {}    # bucket rung -> fn
        self._decode_keys: Dict[int, object] = {}
        self.decode_key = None      # key of the current rung (test probe)
        # FLAGS_fused_block_layers > 1: per-group MultiBlockDecodeWeights
        # (q|k|v and gate|up merged into stacked wider matmuls), built
        # ONCE on first N-layer program build and passed to every decode
        # step as traced args. One extra HBM copy of the layer weights —
        # the originals still serve prefill/chunk/spec programs. None
        # whenever the N-layer path doesn't apply (N=1, generic model,
        # int8 fallback), which is also the dispatch-site discriminant.
        self._stacked: Optional[tuple] = None
        # streaming: (callback, rid, token|None, done) events buffered
        # during a step and drained AFTER dispatch/recovery, so a user
        # callback that raises never masquerades as a dispatch failure
        self._events: List[tuple] = []
        # telemetry's view of the step: a running number every span and
        # lifecycle record of the step carries, the root span's id, and
        # rid -> when a first token became known on the host (closed as
        # request.first_token once its callback has run)
        self._step_no = 0
        self._step_span = 0
        self._first_known: Dict[int, float] = {}
        # streaming-callback registry: rid -> on_token. Engine-LOCAL by
        # design — callbacks never ride the Request objects the export/
        # harvest seams detach (a bound callable cannot serialize across
        # a process boundary); take_callbacks() strips the registry at
        # export and inject_request/adopt_request re-bind on the far side
        self._callbacks: Dict[int, Callable] = {}
        self._prefix_enabled = bool(prefix_cache)
        self._prefix = (PrefixCache(self.pool, replica=self.replica,
                                    host_tier_pages=self.host_tier_pages)
                        if prefix_cache else None)
        # ---- fault tolerance: injection sites bind at construction
        # (NULL stubs when FLAGS_fault_inject is unset — zero hot-path
        # cost, the telemetry idiom) and the replay-recovery budget
        self._f_prefill = faults.site("prefill")
        self._f_chunk = faults.site("chunk_prefill")
        self._f_decode = faults.site("decode_dispatch")
        self._f_migrate = faults.site("bucket_migrate")
        self._f_preempt = faults.site("preempt")
        self.max_retries = int(_flags.get_flag("serving_max_retries"))
        self.retry_backoff = float(
            _flags.get_flag("serving_retry_backoff"))
        self._consec_failures = 0   # engine-wide no-progress failures
        self._failed_admission: Optional[Request] = None
        self._head_blocked = False  # last _next_admission left the
        # slack head page-blocked (bypass admits must not clear gauges)
        # per-step memo of _shared_adopt_pages by rid: the scheduler
        # probes the same requests several times per step (migration
        # demand, head bill per free slot, bypass scan, unit routing)
        # and each probe re-walks the prefix trie over the full prompt
        self._probe_memo: Dict[int, int] = {}
        # flag resolution happens ONCE per engine; the PROGRAM_FLAGS
        # snapshot (every flag a traced program can read — kernel
        # dispatch, flash blocks, compact stats, matmul precision) is
        # part of the program-cache key, so engines built under
        # different flag settings compile and cache distinct steps
        # instead of silently serving a program compiled under stale
        # flags, while eager-only flags (log_level, benchmark) never
        # force a spurious recompile
        from .program_cache import model_signature
        self._flags = _flags.snapshot(_flags.PROGRAM_FLAGS)
        self._model_sig = model_signature(model)
        self._draft_sig = (model_signature(draft_model)
                           if draft_model is not None else None)
        # telemetry binding is per-engine and resolved once here; the
        # replica id labels every series so fleet engines coexist
        self._m = _EngineTelemetry(self.replica, str(self.tp_degree),
                                   block=self._block is not None,
                                   experts=bool(self._counts_width))
        # pool-ledger fragmentation memo: recompute only when the pool's
        # free-list epoch moved (steady-state decode never moves it)
        self._pool_frag_epoch = -1
        self._pool_frag = 0.0
        # the window pool's released pages already counted
        self._window_released_seen = 0
        self._observe_bucket()

    # ------------------------------------------------------------ frontend
    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               deadline: Optional[float] = None,
               on_token: Optional[Callable] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None) -> int:
        """Enqueue one request. ``deadline`` (seconds from now) bounds
        its total latency: a request past its deadline — queued or in
        flight — is terminated ``TIMEOUT`` at the next step boundary
        with whatever tokens it produced. The scheduler admits by
        deadline SLACK (tightest first; no-deadline requests keep FIFO
        order among themselves). ``on_token(rid, token, done)`` streams
        tokens as they are generated: one call per token with
        ``done=False``, then one final ``(rid, None, True)`` when the
        request reaches a terminal status — callbacks fire on the
        caller's thread at step boundaries, after dispatch/recovery, so
        a raising callback surfaces to the caller instead of tripping
        replay recovery.

        ``temperature``/``top_k``/``top_p`` select the sampling law
        (0 = greedy, the default). Sampling requires a speculative
        engine (``draft_model=``): the verify program's rejection
        sampler is the only sampler — it draws the exact
        temperature/top-k/top-p-filtered target distribution. ``seed``
        keys the request's sampling stream (default: its rid), and the
        stream is position-keyed, so replay recovery and preemption
        reproduce sampled continuations bit-identically."""
        if temperature is not None and float(temperature) < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if float(temperature or 0.0) > 0.0 and self.draft_model is None:
            raise ValueError(
                "temperature > 0 requires a speculative engine "
                "(ServingEngine(..., draft_model=...)): the spec verify "
                "program is the engine's sampler")
        prompt = np.asarray(_val(prompt), np.int32).reshape(-1)
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds engine max_seq_len "
                f"({self.max_seq_len})")
        # a request that can never fit would deadlock FIFO admission
        need = -(-(len(prompt) + max_new_tokens) // self.pool.page_size)
        usable = self.pool.num_pages - 1        # null page reserved
        if need > min(usable, self.pool.max_pages_per_seq):
            raise ValueError(
                f"request needs {need} pages but the pool can ever offer "
                f"{min(usable, self.pool.max_pages_per_seq)}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, int(max_new_tokens), eos_token_id)
        if on_token is not None:
            self._callbacks[rid] = on_token
        req.temperature = float(temperature or 0.0)
        req.top_k = int(top_k)
        req.top_p = float(top_p)
        req.seed = int(seed) if seed is not None else rid
        req.t_submit = time.perf_counter()
        if deadline is not None:
            req.deadline = req.t_submit + float(deadline)
        self._queue.append(req)
        self._m.submitted.inc()
        return rid

    def has_work(self) -> bool:
        # a step in flight is work: its tokens are read by the next step()
        return (bool(self._queue) or self._flying is not None
                or any(s is not None for s in self._slots))

    def load(self) -> Tuple[int, int]:
        """``(deadline_bearing, total)`` live request counts (queued +
        in flight) — the fleet router's deadline-aware load-balance
        probe. Cheap: two list scans over host bookkeeping."""
        live = [r for r in self._slots if r is not None] + self._queue
        return (sum(1 for r in live if r.deadline is not None), len(live))

    def run_step(self) -> bool:
        """The non-blocking pump: one scheduler round (admission, at
        most one prefill chunk, one decode dispatch), then returns
        whether work remains — callers interleave ``run_step`` with
        ``poll``/``results`` to drain tokens while the engine runs,
        instead of blocking in :meth:`run`."""
        self.step()
        return self.has_work()

    def poll(self, rid: int) -> Dict[str, object]:
        """Non-blocking progress probe for one request: ``{"status",
        "tokens", "done"}`` with the tokens emitted SO FAR (a snapshot —
        safe to mutate). Completed requests report their terminal
        status until :meth:`run`'s next drain prunes them."""
        if rid in self._results:
            return {"status": self._status.get(rid, OK),
                    "tokens": list(self._results[rid]), "done": True}
        for req in list(self._slots) + self._queue:
            if req is not None and req.rid == rid:
                return {"status": "PENDING", "tokens": list(req.tokens),
                        "done": False}
        raise KeyError(f"unknown or already-drained request id {rid}")

    def run(self, max_wall: Optional[float] = None) -> Dict[int, List[int]]:
        """Step until drained and return ``{rid: tokens}`` (partial
        tokens for FAILED/TIMEOUT requests — check :meth:`status`).
        ``max_wall`` is the watchdog: past it, everything still queued
        or in flight is terminated ``TIMEOUT`` and ``run`` returns
        instead of spinning on a wedged backend."""
        t0 = time.perf_counter()
        while self.has_work():
            if max_wall is not None and \
                    time.perf_counter() - t0 > max_wall:
                self._expire_all("run(max_wall=%.3f) watchdog" % max_wall)
                self._drain_events()
                break
            self.step()
        out, self._results = self._results, {}
        # statuses are retained for exactly the requests this drain
        # returned: a long-lived engine must not accumulate one status
        # entry per request forever
        self._status = {rid: self._status[rid] for rid in out
                        if rid in self._status}
        return out

    def results(self) -> Dict[int, List[int]]:
        """Completed results accumulated so far, WITHOUT draining them —
        the exception-safety accessor: after a mid-``run`` raise, every
        request that finished before the failure is retrievable here
        (``run`` only hands over-and-clears on a clean drain)."""
        return {rid: list(toks) for rid, toks in self._results.items()}

    def take_results(self) -> Dict[int, List[int]]:
        """Drain completed results (and their statuses): the
        ``run_step()`` loop's collection surface. A long-lived server
        pumping ``run_step`` must drain through here (or through
        ``run``) — ``results()``/``poll()`` deliberately never free the
        per-request entries, so without a drain they grow one entry per
        completed request forever. Check :meth:`status`/:meth:`statuses`
        BEFORE draining; drained rids poll as unknown afterwards."""
        out, self._results = self._results, {}
        for rid in out:
            self._status.pop(rid, None)
        return out

    def status(self, rid: int) -> str:
        """Terminal status for ``rid``: ``OK`` / ``FAILED`` / ``TIMEOUT``
        (``PENDING`` while queued or in flight). Statuses survive until
        the NEXT completed ``run`` drain, then prune with its results."""
        return self._status.get(rid, "PENDING")

    def statuses(self) -> Dict[int, str]:
        return dict(self._status)

    # ---------------------------------------------- fleet router surface
    def export_requests(self) -> List[Request]:
        """Detach every live request — in flight and queued — as pure
        host state, in submission order: the fleet router's
        replica-loss harvest. In-flight requests reset to replay form
        (prompt + emitted tokens; pins, slots, cursors dropped), so
        re-routing them through another replica's admission produces
        the bit-identical greedy continuation. The engine is left with
        no pending work; completed results stay until drained. Pages
        release when the pool is still alive (a lost replica's pool may
        be detached — its device state is gone either way). Streaming
        callbacks do NOT ride the exported requests (host bundles stay
        transportable): grab them with :meth:`take_callbacks` and
        re-bind each via ``inject_request(req, on_token=...)``."""
        # the replica may be lost: the step in flight is dropped, not
        # read, and its rows replay that token elsewhere
        self._settle("export", read=False)
        live = [r for r in self._slots if r is not None]
        pool_alive = not self._caches.detached
        out = sorted(live + self._queue, key=lambda r: r.rid)
        for req in live:
            if pool_alive and req.slot is not None:
                self._caches.free(req.slot)
        for req in out:
            self._to_replay_form(req)
        self._slots = [None] * self.max_batch
        self._queue = []
        self._last_tok[:] = 0
        return out

    def take_callbacks(self) -> Dict[int, Callable]:
        """Detach the rid -> streaming-callback registry — the
        strip-at-export half of the callback discipline. Callbacks are
        engine-local and never ride the ``Request`` bundles the export/
        harvest seams detach (a bound callable cannot serialize across
        a process boundary); the caller re-binds each one on the far
        side via ``inject_request(..., on_token=)`` /
        ``adopt_request(..., on_token=)``."""
        out, self._callbacks = self._callbacks, {}
        return out

    def inject_request(self, req: Request,
                       on_token: Optional[Callable] = None) -> int:
        """Enqueue an EXISTING request object under a fresh local rid —
        the fleet router's re-route half of :meth:`export_requests`.
        Prompt, emitted tokens, deadline and budgets ride along, so
        admission treats a token-bearing injection exactly like a
        replay (prefill from prompt + tokens, bit-identical greedy
        continuation). ``on_token`` re-binds the request's streaming
        callback under its fresh rid (the re-bind-on-adopt half of
        :meth:`take_callbacks`)."""
        req.rid = self._next_rid
        self._next_rid += 1
        req.status = "PENDING"
        req.error = None
        if on_token is not None:
            self._callbacks[req.rid] = on_token
        self._queue.append(req)
        # NOT counted as a submission: the request was submitted once,
        # on its original replica — fleet_rerouted_requests is the
        # re-route count, and double-counting here would inflate every
        # fleet-wide sum over serving_requests_submitted{replica}
        return req.rid

    # ----------------------------------- disaggregated handoff (r19)
    def harvest_request(self, rid: int) -> dict:
        """Detach ONE live greedy request WITH its written KV pages —
        the prefill-replica half of prefill→decode disaggregation. The
        pages spill verbatim (int8 payload + scale band included) and
        leave with the request, so the decode replica resumes WITHOUT
        re-running prefill and the greedy continuation stays
        bit-identical: the pool bits move, nothing is recomputed.
        Returns the bundle :meth:`adopt_request` seats — pure host
        state (``HANDOFF_SCHEMA_VERSION``-tagged, pickle-transportable;
        the streaming callback is stripped, re-bind it via
        ``adopt_request(..., on_token=)``); transfer it however the
        deployment likes (the dryrun harness rides the deterministic
        p2p mailbox)."""
        self._settle("handoff")     # the cursor and last token leave
        req = next((r for r in self._slots
                    if r is not None and r.rid == rid), None)
        if req is None or req.slot is None:
            raise ValueError(
                f"harvest_request: rid {rid} is not seated in a slot "
                "(queued/completed requests re-route through "
                "export_requests/inject_request instead)")
        if req.prefill_pos is not None or req.pending:
            raise ValueError(
                "harvest_request: request is mid-prefill (chunk cursor "
                "or teacher-forced suffix pending) — hand off after its "
                "first generated token")
        if req.temperature > 0.0:
            raise ValueError(
                "harvest_request: sampled requests park their KV cursor "
                "in the spec verify program; only greedy requests hand "
                "off with pages")
        slot = req.slot
        pages, seq_len, state = self._caches.export_slot(slot)
        self._m.state_exports.inc(self._caches.state_rows)
        last_tok = int(self._last_tok[slot])
        self._to_replay_form(req)
        self._slots[slot] = None
        self._last_tok[slot] = 0
        # strip-at-export: the callback is engine-local state, never
        # part of the transportable bundle (the adopter re-binds one)
        self._callbacks.pop(rid, None)
        return {"v": HANDOFF_SCHEMA_VERSION, "request": req,
                "pages": pages, "seq_len": seq_len,
                "last_token": last_tok, "state": state}

    def adopt_request(self, bundle: dict,
                      on_token: Optional[Callable] = None) -> int:
        """Seat a harvested request mid-stream — the decode-replica
        half of :meth:`harvest_request`: allocate the span, write the
        transferred pages into the fresh block table
        (:meth:`PagedKVCache.adopt_page`), restore the KV cursor and
        the last emitted token, and resume decoding under a fresh local
        rid. Pool geometry must match byte-for-byte (same page layout =
        same compiled programs serve the adopted row). ``on_token``
        re-binds a streaming callback under the fresh rid (callbacks
        never ride the bundle — the re-bind-on-adopt half of the
        callback discipline)."""
        v = bundle.get("v")
        if v != HANDOFF_SCHEMA_VERSION:
            raise ValueError(
                f"adopt_request: bundle schema version {v!r} != this "
                f"engine's {HANDOFF_SCHEMA_VERSION} — the disaggregated "
                "pair must run the same handoff revision (re-harvest on "
                "a matching build instead of mis-seating pages)")
        req: Request = bundle["request"]
        pages = bundle["pages"]
        state = bundle["state"]
        self._settle("handoff")     # a slot the step in flight frees
        slot = self._slots.index(None) if None in self._slots else None
        self._caches.adopt_slot(
            slot, len(req.prompt) + int(req.max_new_tokens), pages,
            bundle["seq_len"], state)
        req.rid = self._next_rid
        self._next_rid += 1
        req.slot = slot
        req.status = "PENDING"
        req.error = None
        now = time.perf_counter()
        req.t_submit = req.t_submit or now
        req.t_last = now
        if on_token is not None:
            self._callbacks[req.rid] = on_token
        self._slots[slot] = req
        self._last_tok[slot] = int(bundle["last_token"])
        if self._block is not None:
            # the block it was denoising starts again from masks (the
            # cursor counts committed blocks only)
            self._seat_block(req, slot)
        return req.rid

    # ------------------------------------------------- compiled programs
    def _key(self, kind: str, bucket: Optional[int] = None,
             extra: Tuple = ()):
        from .program_cache import DecodeKey
        # the kv/weight storage dtypes are program identity (r18): a
        # dtype flip must never re-serve a stale cached program, so the
        # discriminant rides every key's extra (the pool dtype string
        # below also flips to "int8" for quantized pools, but the extra
        # covers the weight dtype and keys built before pools exist)
        extra = tuple(extra) + ((key_vocab.TAG_KV, self.kv_dtype),
                                (key_vocab.TAG_WT, self.weight_dtype))
        # tp rides the extra ONLY when armed, so every tp=1 key (and the
        # banked artifacts keyed on it) stays byte-identical to r18
        if self.tp_degree > 1:
            extra = extra + ((key_vocab.TAG_TP, self.tp_degree),)
        return DecodeKey(
            kind=kind, model_sig=self._model_sig,
            batch_bucket=self.max_batch if bucket is None else bucket,
            page_budget=self._caches.page_budget,
            dtype=str(self.pool.k_pages[0].dtype),
            flags=self._flags.as_tuple(), extra=extra)

    def _fused_spec(self, draft: bool = False):
        """The model's fused-block layout when the fused path applies:
        FLAGS_fused_block_decode on, the model publishes
        ``block_decode_spec()``, and every named weight is live in the
        param/buffer dicts (a weight-quantized model restructures its
        Linears into int8 buffers and falls back to the generic step).
        ``draft=True`` probes the speculative DRAFT model instead — the
        draft-propose scan fuses per-layer exactly like the batched
        decode step when its model qualifies (the draft always stays
        per-layer: its scan carries one layer's pools at a time, and
        γ-token proposal latency is not where N-layer fusion pays)."""
        if not self._flags.fused_block_decode:
            return None
        model = self.draft_model if draft else self.model
        get_spec = getattr(model, "block_decode_spec", None)
        if get_spec is None:
            return None
        n = int(self._flags.fused_block_layers)
        if n > 1 and not draft:
            try:
                spec = get_spec(fused_layers=n)
            except TypeError:
                # model predates the stacked layout (no fused_layers
                # kwarg): serve it per-layer rather than refuse
                spec = get_spec()
        else:
            spec = get_spec()
        if spec is None:
            return None
        allp = ({**self._draft_buffers, **self._draft_params} if draft
                else {**self._buffers, **self._params})
        names = [spec["embed"], spec["final_norm"]]
        if spec["lm_head"]:
            names.append(spec["lm_head"])
        for lw in spec["layers"]:
            names.extend(lw.values())
        if not all(allp.get(n) is not None for n in names):
            return None
        return spec

    def _prefill_program(self):
        if self._prefill_fn is None:
            from .program_cache import decode_program_cache
            self._prefill_fn = decode_program_cache().get(
                self._key("prefill"),
                functools.partial(_build_prefill, model=self.model))  # keycheck: disable=KEY002 — the documented model-object closure (model_sig rides the key)
        return self._prefill_fn

    def _chunk_program(self):
        """The chunked-prefill program: ONE cached compiled step per
        (chunk length, model/pool config) — every chunk of every prompt
        dispatches the same fixed (1, chunk) shape (the final partial
        chunk pads), so prompt length never retraces."""
        if self._chunk_fn is None:
            from .program_cache import decode_program_cache
            self._chunk_fn = decode_program_cache().get(
                self._key("prefill_chunk", bucket=1,
                          extra=(self.chunk,)),
                functools.partial(_build_chunk_prefill, model=self.model))  # keycheck: disable=KEY002 — the documented model-object closure (model_sig rides the key)
        return self._chunk_fn

    def _chunk_tilings(self) -> tuple:
        """How ``paged_chunk_attention`` cuts the chunk program's reads
        (``chunk_tiling`` of the shapes that program hands the kernel:
        the chunk, the pool's page, the table's width, the model's
        query heads over the pool's KV heads): in a global layer and, behind
        it, in a window layer of a model that has them. What
        ``serving_chunk_*_tile_pairs`` count by
        (``tests/test_afmoe.py`` holds them to what the kernel's wrapper
        computes while the program is traced)."""
        pool = self.pool
        heads = getattr(getattr(self.model, "config", None),
                        "num_attention_heads", None)
        if heads is None or int(heads) % pool.num_kv_heads:
            raise ValueError(
                "chunked prefill (prefill_chunk > 0) needs the model's "
                "config.num_attention_heads, a multiple of its "
                f"{pool.num_kv_heads} KV heads: the chunk kernel tiles its "
                f"query rows by their ratio; got {heads!r}")
        heads = int(heads)
        shapes = dict(
            s=self.chunk, rep=heads // pool.num_kv_heads,
            page_size=pool.page_size,
            max_pages=pool.block_tables.shape[1],
            block=1 if self._block is None else self._block[0])
        cut = (chunk_tiling(**shapes),)
        if self._caches.window is not None:
            cut += (chunk_tiling(window=self._caches.window_len, **shapes),)
        return cut

    def _stacked_weights(self, spec) -> tuple:
        """Build (once) the per-group MultiBlockDecodeWeights the N-layer
        decode programs take as traced args: each group's
        BlockDecodeWeights stacked along a leading layer axis, q|k|v and
        gate|up concatenated into single wider matmul operands.

        Under tp > 1 the stacks are additionally permuted into the
        shard-major Megatron layout (``shard_block_weights``) and
        committed to the tp mesh with the canonical per-field shardings
        — column-parallel wqkv/wgu split their LAST axis, row-parallel
        wo/wd their middle (contraction) axis, norms replicate — so
        every decode dispatch reuses one stable placement and never
        retraces on a sharding flip."""
        if self._stacked is None:
            from ..kernels.fused_block_decode import (BlockDecodeWeights,
                                                      stack_block_weights)
            allp = {**self._buffers, **self._params}
            self._stacked = tuple(
                stack_block_weights([
                    BlockDecodeWeights(
                        **{f: allp[n]
                           for f, n in spec["layers"][i].items()})
                    for i in group], weight_dtype=self.weight_dtype)
                for group in spec["layer_groups"])
            if self.tp_degree > 1:
                from jax.sharding import NamedSharding as _NS
                from jax.sharding import PartitionSpec as _P
                from ..kernels.fused_block_decode import (
                    MultiBlockDecodeWeights, shard_block_weights)
                ax = self._tp_axis
                shardings = MultiBlockDecodeWeights(
                    ln1=_NS(self._tp_mesh, _P()),
                    wqkv=_NS(self._tp_mesh, _P(None, None, ax)),
                    wo=_NS(self._tp_mesh, _P(None, ax, None)),
                    ln2=_NS(self._tp_mesh, _P()),
                    wgu=_NS(self._tp_mesh, _P(None, None, ax)),
                    wd=_NS(self._tp_mesh, _P(None, ax, None)))
                self._stacked = tuple(
                    jax.device_put(
                        shard_block_weights(
                            g, self.tp_degree,
                            num_heads=spec["num_heads"],
                            num_kv_heads=spec["num_kv_heads"]),
                        shardings)
                    for g in self._stacked)
        return self._stacked

    def _decode_program(self, bucket: int):
        """The decode step for one bucket rung, compiled once per rung
        and cached — bucket migration swaps between already-compiled
        programs instead of retracing. With FLAGS_fused_block_layers=N
        and a model that publishes ``layer_groups``, the rung's program
        is the N-layer kernel step (DecodeKey.extra carries the
        layer-group shape so same-model engines under a different N
        never share a program)."""
        fn = self._decode_fns.get(bucket)
        if fn is None:
            from .program_cache import decode_program_cache
            spec = self._fused_spec()
            groups = spec.get("layer_groups") if spec else None
            if spec and self.tp_degree > 1:
                # tensor-parallel rung: every fused arm (N=1 included)
                # consumes stacked weights through ONE shard_map body —
                # a per-layer group chain IS the N=1 stacked layout
                if not groups:
                    spec = dict(spec)
                    groups = [[i] for i in range(len(spec["layers"]))]
                    spec["layer_groups"] = groups
                self._stacked_weights(spec)
                if any(len(g) > 1 for g in groups):
                    key = self._key(
                        "decode_fused_nlayer", bucket=bucket,
                        extra=(key_vocab.TAG_NLAYER,
                               tuple(len(g) for g in groups)))
                else:
                    # all-singleton groups ARE the N=1 stacked layout:
                    # model_sig pins the layer count, so a (1,)*L shape
                    # tag adds nothing — key it as plain decode_fused
                    # (the ("tp", N) pair still separates it from the
                    # single-device program) so the kind keeps ONE
                    # extra schema package-wide (KEY006)
                    key = self._key("decode_fused", bucket=bucket)
                builder = functools.partial(
                    _build_fused_nlayer_decode_tp, spec=spec,
                    snap=self._flags, mesh=self._tp_mesh,
                    axis=self._tp_axis, tp=self.tp_degree)
            elif groups:
                self._stacked_weights(spec)
                key = self._key(
                    "decode_fused_nlayer", bucket=bucket,
                    extra=(key_vocab.TAG_NLAYER,
                           tuple(len(g) for g in groups)))
                builder = functools.partial(_build_fused_nlayer_decode,
                                            spec=spec, snap=self._flags)
            elif spec:
                key = self._key("decode_fused", bucket=bucket)
                builder = functools.partial(_build_fused_decode, spec=spec,
                                            snap=self._flags)
            else:
                key = self._key("decode_generic", bucket=bucket)
                builder = functools.partial(_build_generic_decode,
                                            model=self.model)  # keycheck: disable=KEY002 — the documented model-object closure (model_sig rides the key)
            fn = decode_program_cache().get(key, builder)
            self._decode_fns[bucket] = fn
            self._decode_keys[bucket] = key
        self.decode_key = self._decode_keys.get(bucket, self.decode_key)
        return fn

    def _block_program(self, bucket: int):
        """The block step for one bucket rung (a block-diffusion
        model's batched step), compiled once per rung and cached like
        the decode step's."""
        fn = self._block_fns.get(bucket)
        if fn is None:
            from .program_cache import decode_program_cache
            key = self._key("block_step", bucket=bucket, extra=self._block)
            fn = decode_program_cache().get(key, functools.partial(
                _build_block_step, model=self.model,  # keycheck: disable=KEY002 — the documented model-object closure (model_sig rides the key)
                mask_id=self._block[1]))
            self._block_fns[bucket] = fn
            self._decode_keys[bucket] = key
        self.decode_key = self._decode_keys.get(bucket, self.decode_key)
        return fn

    # ----------------------------------------------------------- internals
    # What a request keeps per layer kind lives behind ``self._caches``
    # (and, for the draft model, ``self._draft``): the dispatch sites
    # pass ``take_caches()`` to the donating programs and hand their
    # returned entries to ``install_caches`` (generation/cache_manager.py,
    # "dispatch"). These three are read-only views for tests, tools and
    # the benchmark.

    @property
    def pool(self) -> PagedKVCache:
        return self._caches.pool

    @property
    def _state(self):
        return self._caches.state

    @property
    def _draft_pool(self) -> Optional[PagedKVCache]:
        return None if self._draft is None else self._draft.pool

    def _reset_state(self, slot: int) -> None:
        """Admission that runs prefill compute: what the slot's last
        request left in its fixed-size state goes."""
        self._caches.reset(slot)
        self._m.state_resets.inc(self._caches.state_rows)

    def _adopt_prefix(self, req: Request, slot: int, pages: List[int],
                      n_cached: int) -> None:
        """Seat ``slot`` on the cached prompt pages, read-only, with its
        cursor behind them."""
        self.pool.adopt_shared(slot, pages)
        if self._prefix is not None:
            # pin count on adoption: evict() must never free pages an
            # in-flight request's block table still points at
            self._prefix.pin(pages)
            req.pinned = [int(p) for p in pages]
        self.pool.seq_lens[slot] = n_cached
        self._m.shared_admits.inc()

    def _admit_shared(self, req: Request, slot: int, pages: List[int],
                      n_cached: int) -> None:
        """Prefix-cache admission: adopt the cached prompt pages
        read-only — the cached portion's prefill compute is skipped
        entirely. A SHORT remaining suffix teacher-forces through the
        ordinary decode step (one token per engine step, no extra
        program: the model output while suffix tokens are pending is a
        prompt-position logit and is discarded; the step that feeds the
        LAST suffix token emits the first generated token). A LONG
        suffix, with chunking enabled, prefills from the adopted-prefix
        cursor in chunks instead — the chunk program natively starts at
        a nonzero position."""
        self._adopt_prefix(req, slot, pages, n_cached)
        suffix = req.prompt[n_cached:]
        self._caches.allocate(slot, len(suffix) + req.max_new_tokens)
        if self.chunk and len(suffix) > 2 * self.pool.page_size:
            req.feed = req.prompt
            req.prefill_pos = n_cached
        else:
            self._last_tok[slot] = int(suffix[0])
            req.pending = [int(t) for t in suffix[1:]]
        req.slot = slot
        self._slots[slot] = req

    def _covers_enough(self, req: Request, n_cached: int) -> bool:
        """The monolithic-mode coverage threshold: the suffix replays
        one token per decode step, so a barely-covered long prompt
        would trade one b=1 prefill for hundreds of full-batch steps.
        With chunking on, long suffixes prefill in chunks from the
        adopted cursor instead, so ANY hit is worth taking (callers
        short-circuit on ``self.chunk``)."""
        return (len(req.prompt) - n_cached
                <= max(2 * self.pool.page_size, n_cached))

    def _hit_worth_taking(self, req: Request) -> bool:
        """Would ``_admit`` accept this request's prefix hit? Mirrored
        on POTENTIAL coverage (spilled pages included) BEFORE lookup
        runs: with chunking off, a hit the coverage threshold refuses
        must be detected up front, or lookup's restores would consume
        free pages ``_next_admission`` never priced — the subsequent
        full-span allocate could exhaust the pool mid-step."""
        if self.chunk:
            return True
        n = self._prefix.peek(req.prompt, include_spilled=True)
        while n >= len(req.prompt):
            n -= self.pool.page_size
        return n > 0 and self._covers_enough(req, n)

    def _admission_feed(self, req: Request) -> np.ndarray:
        """What prefill teacher-forces for this admission. First
        admission: the prompt. Replay admission (recovery re-queued an
        in-flight request): prompt + every already-emitted token — all
        host-side state — so the b=1 prefill reconstructs the KV cache
        and its argmax IS the next greedy token. Greedy decoding makes
        the replayed continuation identical to the uninterrupted one."""
        if not req.tokens:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])

    def _admit(self, req: Request, slot: int) -> bool:
        """Route one admission: prefix-cache shared adoption when the
        prompt's pages already live in the pool, the chunked-prefill
        cursor for long prompts, the classic monolithic b=1 prefill
        otherwise."""
        # queued phase closes at admission: submit() -> here (once per
        # REQUEST, not per token)  # tracecheck: disable=TRC007
        self._m.event("request.queued", req.t_submit, time.perf_counter(),
                      rid=req.rid, step=self._step_no)
        if self._block is not None:
            return self._admit_block(req, slot)
        replay = bool(req.tokens)
        if self._prefix is not None and not replay \
                and self._hit_worth_taking(req):
            # max_cover never covers the WHOLE prompt: the first
            # generated token's logits are not cached, so at least one
            # prompt token must go through compute — and lookup must
            # not restore a spilled page an over-cover would discard
            pages, n_cached = self._prefix.lookup(
                req.prompt, max_cover=len(req.prompt) - 1)
            if pages and (self.chunk or self._covers_enough(
                    req, n_cached)):
                self._admit_shared(req, slot, pages, n_cached)
                return False    # no prefill compute dispatched
        feed = self._admission_feed(req)
        if self.chunk and len(feed) > self.chunk:
            # chunked admission: allocate the full page span now, then
            # prefill one chunk per step() so decode never stalls for
            # more than one chunk
            remaining = req.max_new_tokens - len(req.tokens)
            self._caches.allocate(slot, len(feed) + remaining)
            self._reset_state(slot)
            req.feed = feed
            req.prefill_pos = 0
            req.slot = slot
            self._slots[slot] = req
            return False    # chunks dispatch one per step, not here
        self._prefill(req, slot, feed)
        return True         # monolithic prefill compute ran this step

    def _admit_block(self, req: Request, slot: int) -> bool:
        """Admission of a block-diffusion request. Prefill covers the
        WHOLE BLOCKS of the feed (the prompt; on replay prompt + emitted
        tokens, which is whole blocks by then) under the block-causal
        mask and gives no token; what is left of the prompt, fewer than
        a block, becomes the known part of the first generated block
        (``_seat_block``). Shared prefix pages cover whole blocks (a
        page is a multiple of a block), and the rest prefills from that
        cursor in chunks."""
        blen = self._block[0]
        feed = self._admission_feed(req)
        whole = len(feed) - len(feed) % blen
        remaining = req.max_new_tokens - len(req.tokens)
        n_cached = 0
        if self._prefix is not None and not req.tokens:
            pages, n_cached = self._prefix.lookup(
                req.prompt, max_cover=len(req.prompt) - 1)
            if pages:
                self._adopt_prefix(req, slot, pages, n_cached)
        # a page is whole blocks, so the span rounded up to whole blocks
        # (the last block's surplus is dropped at emission) needs no
        # page more than the span itself
        self._caches.allocate(slot, len(feed) + remaining - n_cached)
        if whole == n_cached:               # nothing left to prefill
            self._seat_block(req, slot)
            return False
        if n_cached or (self.chunk and whole > self.chunk):
            req.feed = feed[:whole]
            req.prefill_pos = n_cached
            req.slot = slot
            self._slots[slot] = req
            return False    # chunks dispatch one per step, not here
        self._prefill(req, slot, feed[:whole])
        return True

    def _seat_block(self, req: Request, slot: int,
                    register: bool = False) -> None:
        """The row's whole blocks are cached: seat it at that cursor
        with its first block to denoise, the feed's remainder known and
        the rest masks. ``register``: a first prefill just wrote the
        prompt's pages, which the prefix cache may now share."""
        if register and self._prefix is not None:
            self._prefix.register(req.prompt, self.pool.block_tables[slot])
        blen, mask = self._block
        feed = self._admission_feed(req)
        whole = len(feed) - len(feed) % blen
        self.pool.seq_lens[slot] = whole
        self._blk[slot] = mask
        self._blk[slot, :len(feed) - whole] = feed[whole:]
        req.known = len(feed) - whole
        req.masks = blen - req.known
        req.prefill_pos = None
        req.feed = None
        req.slot = slot
        self._slots[slot] = req

    def _prefill(self, req: Request, slot: int,
                 feed: Optional[np.ndarray] = None) -> None:
        """Monolithic b=1 whole-prompt prefill (prompts at or under the
        chunk size, and every prompt when chunking is off)."""
        replay = bool(req.tokens)
        if feed is None:
            feed = self._admission_feed(req)
        p = len(feed)
        # the cached prefill program: jit itself caches one compilation
        # per prompt length (bucket/pad prompts in production to bound
        # that set); the program-cache layer shares those compilations
        # across engine instances over the same model
        fn = self._prefill_program()

        if self._block is None:     # else _admit_block allocated the span
            remaining = req.max_new_tokens - len(req.tokens)
            self._caches.allocate(slot, p + remaining)
        self._reset_state(slot)
        bt = self._caches.tables(slot, p)
        # per-request prefill timeline span  # tracecheck: disable=TRC007
        with self._m.span("request.prefill", rid=req.rid, prompt_len=p,
                          step=self._step_no):
            pools = self._caches.take_caches()
            self._f_prefill.check()
            tok, states, *counts = fn(
                self._params, self._buffers, jnp.asarray(feed[None]),
                pools, bt, jnp.zeros((1,), jnp.int32),
                *self._caches.slot_args(slot))
            self._note_expert_counts(counts)
            # b=1 prefill wrote THROUGH slot's block table into the
            # shared pool arrays; adopt them and the slot's bookkeeping
            self._caches.install_caches(states)
            if self._block is None:
                # the wait for the device, apart from the span's own
                # host work  # tracecheck: disable=TRC007
                with self._m.phase("engine.prefill.pull"):
                    tok = int(tok)
        # once per admitted request  # tracecheck: disable=TRC007
        self._m.prefills.inc()
        # tracecheck: disable=TRC007
        self._m.prefill_tokens.inc(p)
        if self._block is not None:
            # the prompt's whole blocks are cached; no token comes of a
            # prefill here (never pulled: the dispatch stays async)
            self._seat_block(req, slot, register=not replay)
            return
        if req.temperature > 0.0:
            # a sampled request never takes the prefill's greedy argmax:
            # park the cursor ONE position short with the last fed token
            # as the pending feed — exactly the spec-round entry
            # invariant, so the verify program samples the position the
            # prefill would have decided (and a replayed admission
            # resumes at the SAME position key, redrawing identically)
            self.pool.seq_lens[slot] = p - 1
            self._last_tok[slot] = int(feed[-1])
            req.slot = slot
            self._slots[slot] = req
            if self._prefix is not None and not replay:
                self._prefix.register(req.prompt,
                                      self.pool.block_tables[slot])
            return
        self.pool.seq_lens[slot] = p
        self._last_tok[slot] = tok
        self._observe_token(req, time.perf_counter())
        req.tokens.append(tok)
        self._emit(req, tok)
        req.slot = slot
        self._slots[slot] = req
        if self._prefix is not None and not replay:
            # pin this prompt's full pages for future shared admissions
            # (they are immutable: later writes land at seq_len and up)
            self._prefix.register(req.prompt, self.pool.block_tables[slot])
        self._finish_if_done(req)

    def _prefill_chunk(self, req: Request) -> None:
        """One chunk of one mid-prefill request: write ``chunk`` tokens
        of its feed into the KV pool at the cursor and advance it. Every
        chunk dispatches the SAME cached (1, chunk) program — the final
        partial chunk pads (pad KV is causally masked and pad positions
        past the block table drop), and only the final chunk's argmax is
        pulled to the host: it is the request's first generated token
        (or, on replay, the next greedy continuation token)."""
        feed, pos, c = req.feed, req.prefill_pos, self.chunk
        end = min(pos + c, len(feed))
        last = end == len(feed)
        ids = np.zeros((c,), np.int32)
        ids[:end - pos] = feed[pos:end]
        fn = self._chunk_program()
        slot = req.slot
        # uploads, dispatch and (last chunk only) the token pull: one
        # span a chunk  # tracecheck: disable=TRC007
        with self._m.span("engine.prefill_chunk", step=self._step_no,
                          rid=req.rid, pos=pos, last=last):
            bt = self._caches.tables(slot, c)
            sl = jnp.asarray(np.full((1,), pos, np.int32))
            t0 = time.perf_counter()
            pools = self._caches.take_caches()
            self._f_chunk.check()
            tok, states, *counts = fn(
                self._params, self._buffers, jnp.asarray(ids[None]), pools,
                bt, sl, jnp.int32(end - pos - 1),
                *self._caches.slot_args(slot))
            self._note_expert_counts(counts)
            self._caches.install_caches(states)
            self.pool.seq_lens[slot] = end
            req.prefill_pos = end
            self.chunk_dispatches += 1
            if last and self._block is None:
                # designed sync: the first generated token. A non-final
                # argmax is garbage-padded and never pulled, so that
                # dispatch stays async  # tracecheck: disable=TRC007
                with self._m.phase("engine.prefill.pull"):
                    tok = int(tok)
        tnow = time.perf_counter()
        self._observe_chunk(tnow - t0, pos, end - pos, final=last)
        if not last:
            return
        replay = bool(req.tokens)
        if self._block is not None:
            self._seat_block(req, slot, register=not replay)
            return
        if req.temperature > 0.0:
            # sampled request: discard the final chunk's greedy argmax
            # and park the cursor one short (see _prefill) — the spec
            # verify program is the only sampler
            self.pool.seq_lens[slot] = len(feed) - 1
            self._last_tok[slot] = int(feed[-1])
            req.prefill_pos = None
            req.feed = None
            if self._prefix is not None and not replay:
                self._prefix.register(req.prompt,
                                      self.pool.block_tables[slot])
            return
        self._observe_token(req, tnow)
        req.tokens.append(tok)
        self._emit(req, tok)
        self._last_tok[slot] = tok
        req.prefill_pos = None
        req.feed = None
        if self._prefix is not None and not replay:
            # the whole prompt's KV is now written (adopted prefix +
            # chunked suffix): register its full pages — repeats of
            # this prompt deepen the cache
            self._prefix.register(req.prompt, self.pool.block_tables[slot])
        self._finish_if_done(req)

    def _chunk_step(self) -> bool:
        """At most ONE prefill chunk per engine step — the stall a
        long-prompt arrival can impose on decoding requests is bounded
        by one chunk, never a whole prompt. Among mid-prefill requests
        the scheduler order (deadline slack, then FIFO) picks."""
        cands = [r for r in self._slots
                 if r is not None and r.prefill_pos is not None]
        if not cands:
            return False
        now = time.perf_counter()
        req = min(cands, key=lambda r: self._slack_key(r, now))
        self._prefill_chunk(req)
        return True

    def _to_replay_form(self, req: Request, unpin: bool = True) -> None:
        """Reset a request's per-admission transient state to pure
        replay form (prompt + emitted tokens drive any re-admission).
        Every path that detaches a live request funnels through here —
        terminal finalize, replay recovery, SLO preemption, fleet
        export — so a new transient field added to ``Request`` gets its
        reset in ONE place instead of four. ``unpin=False`` when the
        pool the pins indexed is already dead (recovery rebuilt pool
        AND prefix cache; the fresh cache never saw those pages)."""
        if unpin and req.pinned and self._prefix is not None:
            self._prefix.unpin(req.pinned)
        if req.spec_ready:
            # release the draft pool's mirror allocation when that pool
            # is still alive (recovery rebuilds it fresh, so a freshly
            # rebuilt or detached pool has nothing of ours to free);
            # gamma/spec_ema deliberately survive — the draft's observed
            # agreement is the request's property, not the admission's
            if (req.slot is not None and self._draft is not None
                    and not self._draft.detached):
                self._draft.free(req.slot)
            req.spec_ready = False
        req.pinned = []
        req.pending = []
        req.prefill_pos = None
        req.feed = None
        req.slot = None
        req.bypassed = 0
        req.in_flight = 0
        req.masks = req.known = 0

    def _emit(self, req: Request, tok: Optional[int],
              done: bool = False) -> None:
        """Buffer one streaming event; :meth:`step` drains the buffer
        to the callbacks after dispatch/recovery completes."""
        cb = self._callbacks.get(req.rid)
        if cb is not None:
            self._events.append((cb, req.rid, tok, done))

    def _drain_events(self) -> None:
        first = self._first_known
        # once per step  # tracecheck: disable=TRC007
        with self._m.span("engine.callbacks", step=self._step_no,
                          n=len(self._events)):
            while self._events:
                cb, rid, tok, done = self._events.pop(0)
                cb(rid, tok, done)
                if first and rid in first:
                    self._observe_first_token(rid, first.pop(rid))
            # no callback bound: the token is the caller's to read when
            # step() returns, which is now
            while first:
                self._observe_first_token(*first.popitem())

    def _observe_first_token(self, rid: int, t_known: float) -> None:
        """How long a request's first token was held on the host: from
        the moment it was known (the prefill's pull, the last chunk's,
        or a shared admission's first decode pull) to the moment its
        callback had run."""
        self._m.event("request.first_token", t_known, time.perf_counter(),
                      rid=rid, step=self._step_no)

    def _finalize(self, req: Request, status: str,
                  error: Optional[str] = None) -> None:
        """Terminal bookkeeping shared by every way a request ends:
        release its slot/pages/pins, bank its tokens (partial for
        FAILED/TIMEOUT) and record the status. Pure host state — no
        telemetry here (callers observe through ``_observe_*``)."""
        if req.slot is not None:
            self._caches.free(req.slot)
            self._slots[req.slot] = None
        self._to_replay_form(req)
        req.status = status
        req.error = error
        self._results[req.rid] = req.tokens
        self._status[req.rid] = status
        self._emit(req, None, done=True)
        # the terminal event is buffered above with the callback object
        # in hand; the registry entry is dead weight from here on
        self._callbacks.pop(req.rid, None)

    def _finish_if_done(self, req: Request) -> None:
        done = len(req.tokens) >= req.max_new_tokens or (
            req.eos_token_id is not None
            and req.tokens and req.tokens[-1] == req.eos_token_id)
        if done and req.slot is not None:
            self._finalize(req, OK)
            # once per finished request  # tracecheck: disable=TRC007
            self._m.finished.inc()
            # lifecycle close event  # tracecheck: disable=TRC007
            self._m.event("request.complete", req.t_submit,
                          time.perf_counter(), rid=req.rid,
                          tokens=len(req.tokens), step=self._step_no)

    def _sweep_deadlines(self) -> None:
        """Step-boundary deadline enforcement: terminate every queued or
        in-flight request past its ``submit(deadline=...)`` cutoff with
        status TIMEOUT and its partial tokens banked."""
        now = time.perf_counter()
        expired = [r for r in self._slots
                   if r is not None and r.deadline is not None
                   and now > r.deadline]
        expired += [r for r in self._queue
                    if r.deadline is not None and now > r.deadline]
        if not expired:
            return
        if any(r.slot is not None for r in expired):
            # a seated row ends with the tokens it has, the one in
            # flight too
            self._values_first("deadline")
        rids = {r.rid for r in expired}
        self._queue = [r for r in self._queue if r.rid not in rids]
        for req in expired:
            self._finalize(req, TIMEOUT, "deadline exceeded")
        self._observe_timeouts(len(expired))

    def _expire_all(self, why: str) -> None:
        """The ``run(max_wall=...)`` watchdog tripped: terminate every
        remaining request TIMEOUT instead of spinning forever."""
        # a wedged backend is what the watchdog is for: the step in
        # flight is dropped, not waited for
        self._settle("watchdog", read=False)
        remaining = [r for r in self._slots if r is not None]
        remaining += list(self._queue)
        self._queue = []
        for req in remaining:
            self._finalize(req, TIMEOUT, why)
        if remaining:
            self._observe_timeouts(len(remaining))
        self._observe_step_end()

    def step(self) -> None:  # tracecheck: hotpath
        """One scheduler round: deadline sweep, bucket migration,
        admission, at most one prefill chunk, one decode dispatch. A
        failed dispatch does NOT propagate — replay recovery (fresh
        pools, re-queue of all in-flight requests, bounded retries with
        exponential backoff) runs instead, and requests only ever end
        in a terminal OK/FAILED/TIMEOUT status. A program that fails to
        trace or compile DOES propagate: the refusal is deterministic,
        so replaying it would only end every request FAILED a few
        back-offs later with the compiler's words buried in a status.
        Streaming callbacks drain LAST, outside the recovery boundary:
        a raising callback surfaces to the caller, never as a fake
        dispatch failure."""
        self._step_no += 1
        self._m.clock.begin(traces_built())
        try:
            # the step's root span: every phase below nests in it
            # tracecheck: disable=TRC007
            with self._m.span("engine.step", step=self._step_no,
                              bucket=self.bucket) as root:
                self._step_span = root.id
                try:
                    self._step_inner()
                    self._consec_failures = 0
                except ProgramBuildError:
                    raise
                except Exception as exc:
                    self._recover_dispatch(exc)
                finally:
                    self._drain_events()
        finally:
            self._observe_step_clock()

    def _recover_dispatch(self, exc: Exception) -> None:
        """Replay recovery. The donated dispatch died, so the pool is
        already detached (r08 discipline) and its device buffers are
        unrecoverable — but every request's prompt AND emitted tokens
        are host-side state. Allocate fresh pools, terminate requests
        whose no-progress retry budget is exhausted, re-queue the rest
        for re-prefill from prompt + emitted tokens (greedy decoding
        makes the replayed continuation bit-identical), and back off
        exponentially while nothing progresses."""
        t0 = time.perf_counter()
        # the step in flight is dropped with the pools it wrote: replay
        # starts from the tokens the host has
        self._settle("recovery", read=False)
        live = [r for r in self._slots if r is not None]
        failed_adm = self._failed_admission
        self._failed_admission = None
        # a failed admission was rolled back before the raise, so it is
        # never also in a slot
        victims = live + ([failed_adm] if failed_adm is not None else [])
        if not victims:
            if self._queue and self._consec_failures < self.max_retries:
                # nothing in flight died but work is queued — e.g. a
                # bucket-migration fault BEFORE admission. No request
                # state was lost, so back off and press on; the
                # engine-wide no-progress budget still bounds this, so
                # a real scheduler bookkeeping bug surfaces loudly
                # after max_retries consecutive failures instead of
                # spinning forever.
                if self._caches.detached or (
                        self._draft is not None and self._draft.detached):
                    self._rebuild_pool()    # a detached pool stays dead
                self._consec_failures += 1
                self._observe_recovery(0, 0, time.perf_counter() - t0)
                time.sleep(min(
                    self.retry_backoff * (2 ** (self._consec_failures - 1)),
                    2.0))
                return
            # nothing was in flight and nothing is queued (or the
            # budget is spent): this is not a dispatch failure the
            # replay machinery can absorb — a bookkeeping error must
            # stay loud (results so far remain retrievable, see
            # ``results()``)
            raise exc
        self._rebuild_pool()
        survivors: List[Request] = []
        failed: List[Request] = []
        any_progress = False
        for req in victims:
            # progress is (tokens, prefill cursor): a long prompt's
            # chunks count as progress before any token exists, so a
            # transient mid-prefill fault doesn't eat the retry budget.
            # The mark is a HIGH-WATER mark — it never moves backwards:
            # the cursor resets to 0 on every replay, and an oscillating
            # failure point below the best attempt must not read as
            # fresh progress or a persistently flaky backend could
            # reset the retry budget forever.
            progress = (len(req.tokens), req.prefill_pos or 0)
            # unpin=False: the pinned pages died with the old pool and
            # the rebuilt prefix cache never saw them; replay
            # re-prefills from host state (prompt + tokens)
            self._to_replay_form(req, unpin=False)
            if progress > req.progress_mark:
                any_progress = True
                req.retries = 1
                req.progress_mark = progress
            else:
                req.retries += 1
            if req.retries > self.max_retries:
                failed.append(req)
            else:
                survivors.append(req)
        self._slots = [None] * self.max_batch
        self._last_tok[:] = 0
        for req in failed:
            self._finalize(req, FAILED, repr(exc))
        # replays keep their submission order relative to the queue
        self._queue = sorted(survivors + self._queue,
                             key=lambda r: r.rid)
        self._consec_failures = (1 if any_progress
                                 else self._consec_failures + 1)
        self._observe_recovery(len(survivors), len(failed),
                               time.perf_counter() - t0)
        if self._queue:
            time.sleep(min(
                self.retry_backoff * (2 ** (self._consec_failures - 1)),
                2.0))

    def _rebuild_pool(self) -> None:
        """Fresh pools with the identical geometry, so the already-
        compiled prefill/decode programs (keyed on that geometry) serve
        the replays without a retrace. The prefix cache indexed pages of
        the dead pool and restarts empty."""
        self._caches.rebuild()
        if self._draft is not None:
            # the draft pool dies with the target's (a spec fault leaves
            # one detached, and a rebuilt target invalidates the draft's
            # cursor lockstep either way); replay re-syncs from host
            # state through the draft chunk program
            self._draft.rebuild()
        self._prefix = (PrefixCache(self.pool, replica=self.replica,
                                    host_tier_pages=self.host_tier_pages)
                        if self._prefix_enabled else None)
        self._pool_frag_epoch = -1      # fresh pool: re-publish ledger

    def _rollback_admission(self, req: Request, slot: int) -> None:
        """Undo a partial admission (page exhaustion mid-``allocate``):
        return the slot's pages, drop adopted pins, clear teacher-forced
        state — the request goes back to the queue head intact."""
        self._caches.free(slot)
        if req.pinned and self._prefix is not None:
            self._prefix.unpin(req.pinned)
        req.pinned = []
        req.pending = []
        req.prefill_pos = None
        req.feed = None
        req.slot = None
        self._slots[slot] = None

    # ---------------------------------------------------- the scheduler
    _BYPASS_BUDGET = 4   # cached-prefix bypasses one blocked head allows
    _BYPASS_SCAN = 8     # queue depth scanned for a bypass candidate

    @staticmethod
    def _slack_key(req: Request, now: float):
        """Scheduler order: deadline slack ascending (tightest budget
        first); every no-deadline request ties at +inf, so among
        themselves they keep classic FIFO arrival order by rid."""
        slack = (req.deadline - now) if req.deadline is not None \
            else float("inf")
        return (slack, req.rid)

    def _pages_needed(self, req: Request) -> int:
        return -(-(len(req.prompt) + req.max_new_tokens)
                 // self.pool.page_size)

    def _admission_order(self) -> List[Request]:
        """This step's admission order, computed ONCE per step (slack
        depends only on the clock, not on pages, so the order is stable
        across the step's slot loop): deadline slack ascending, FIFO by
        rid among no-deadline ties."""
        now = time.perf_counter()
        return sorted(self._queue, key=lambda r: self._slack_key(r, now))

    def _shared_adopt_pages(self, req: Request) -> int:
        """Pages an admission of ``req`` would adopt read-only from the
        prefix cache (0 = it would NOT take the shared route). The one
        probe that mirrors ``_admit``'s actual routing — a probe that
        disagrees with ``_admit`` would misprice admissions: replays
        never share, a whole-prompt hit trims, and chunking-off mode
        applies the coverage threshold."""
        if self._prefix is None or req.tokens:
            return 0
        memo = self._probe_memo.get(req.rid)
        if memo is not None:
            return memo
        n = self._prefix.peek(req.prompt)
        while n >= len(req.prompt):
            n -= self.pool.page_size
        if n <= 0 or (not self.chunk
                      and not self._covers_enough(req, n)):
            n = 0           # miss, or _admit's monolithic coverage
                            # threshold would refuse the hit
        pages = n // self.pool.page_size
        self._probe_memo[req.rid] = pages
        return pages

    def _fresh_pages_needed(self, req: Request) -> int:
        """Fresh (free-list) pages admitting ``req`` costs right now —
        total span minus whatever its cached prefix supplies."""
        return self._pages_needed(req) - self._shared_adopt_pages(req)

    def _needs_prefill_unit(self, req: Request) -> bool:
        """Would admitting ``req`` dispatch a monolithic prefill — the
        step's single prefill-compute unit? Shared adoptions and
        chunked admissions are cursor-only host bookkeeping."""
        if self._shared_adopt_pages(req):
            return False
        n = len(req.prompt) + len(req.tokens)
        if self._block is not None:
            n -= n % self._block[0]         # whole blocks prefill
            if not n:
                return False
        if self.chunk and n > self.chunk:
            return False
        return True

    def _next_admission(self, order: List[Request]) -> Optional[Request]:
        """The next request to admit from this step's ``order``, or
        None when admission must wait. The slack head goes first; a
        page-blocked head first reclaims cached-but-unshared pages
        (evict), then may be BYPASSED — boundedly, so it never starves
        — by a request whose prompt prefix already lives in the prefix
        cache: that request admits onto pages it shares instead of
        fresh ones, so it lands where its pages already live without
        consuming the head's."""
        head = order[0]
        self._head_blocked = False
        # the head's page bill is its FRESH need: a head whose prompt
        # prefix already lives in the cache admits onto shared pages
        # and only pays for the suffix — gating it on the full span
        # would declare an admittable head blocked (and eviction could
        # even cannibalize its own cached prefix)
        need = self._fresh_pages_needed(head)
        if need > self.pool.free_page_count() and self._prefix:
            # cached-but-unshared pages are reclaimable capacity;
            # a shortfall (pinned/shared pages refusing eviction)
            # is banked as pressure, not silently swallowed
            want = need - self.pool.free_page_count()
            freed = self._prefix.evict(want)
            if freed < want:
                self._observe_evict_shortfall(want - freed)
            # eviction mutates the trie — LRU may even have dropped
            # part of the HEAD's own cached prefix — so its bill must
            # be repriced, not tested against the stale estimate
            self._probe_memo.clear()
            need = self._fresh_pages_needed(head)
        if need <= self.pool.free_page_count():
            return head
        # graceful degradation: the head WAITS in the queue (no
        # starvation) and the shortfall is published as pressure
        self._head_blocked = True
        self._observe_page_pressure(need - self.pool.free_page_count())
        if self._prefix is not None and head.bypassed < self._BYPASS_BUDGET:
            for req in order[1:1 + self._BYPASS_SCAN]:
                adopt = self._shared_adopt_pages(req)
                if adopt and (self._pages_needed(req) - adopt
                              <= self.pool.free_page_count()):
                    head.bypassed += 1
                    return req
        return None

    def _maybe_migrate(self, order: List[Request]) -> None:
        """Bucket-ladder control: pick the smallest rung covering
        current demand, capped at the top rung. Demand counts only
        queued work the page pool could actually admit, scanned in the
        SAME deadline-slack order admission uses (head-of-line on that
        order) — a page-BLOCKED queue must not inflate the bucket to
        rungs whose slots can never fill, where every decode step would
        pay for idle rows. Growth is immediate — admittable work is
        waiting; shrink waits out
        ``FLAGS_serving_bucket_patience`` steps of sustained lower
        demand so occupancy flapping never thrashes programs."""
        if len(self.ladder) == 1:
            return
        active = sum(1 for r in self._slots if r is not None)
        free = self.pool.free_page_count()
        admittable = 0
        for req in order[:self.max_batch]:
            need = self._fresh_pages_needed(req)
            if need > free:
                break
            free -= need
            admittable += 1
        demand = max(1, min(active + admittable, self.max_batch))
        target = next(r for r in self.ladder if r >= demand)
        if target < self.bucket \
                and self._shrink_wait + 1 < self.bucket_patience:
            self._shrink_wait += 1
            return
        if target != self.bucket:
            # rows change slots and the next step's program its rung
            self._values_first("migrate")
            self._migrate(target)
        self._shrink_wait = 0

    def _migrate(self, target: int) -> None:
        """Move the decode batch to rung ``target``: shrinking compacts
        the active sequences into the low slots (host-side block-table
        row moves — KV pages never copy; a recurrent model's state rows
        are indexed by slot, so those DO move, one device row copy a
        layer), growing just widens the next dispatch. Each rung's
        program compiles once and stays cached, so steady-state
        migration is retrace-free."""
        with self._m.span("engine.migrate", step=self._step_no,
                          **{"from": self.bucket, "to": target}):
            self._f_migrate.check(phase="begin", frm=self.bucket,
                                  to=target)
            if target < self.bucket:
                dst = 0
                for s in range(target, self.max_batch):
                    req = self._slots[s]
                    if req is None:
                        continue
                    while self._slots[dst] is not None:
                        dst += 1    # always < target: target covers active
                    self._caches.move(s, dst)
                    # once per moved request, not per token
                    # tracecheck: disable=TRC007
                    self._m.state_moves.inc(self._caches.state_rows)
                    if req.spec_ready:
                        # the draft pool mirrors the target's slot layout
                        self._draft.move(s, dst)
                    self._last_tok[dst] = self._last_tok[s]
                    if self._blk is not None:
                        self._blk[dst] = self._blk[s]
                    self._slots[dst] = req
                    self._slots[s] = None
                    req.slot = dst
                    # deliberately MID-mutation: every=N drills must land
                    # between row moves, and recovery replays the whole
                    # batch from host state so no half-compacted table
                    # survives  # faultcheck: disable=FLT002
                    self._f_migrate.check(phase="move", rid=req.rid)
            self.bucket = target
            self.bucket_migrations += 1
            # post-commit schedule point, same full-replay argument
            # faultcheck: disable=FLT002
            self._f_migrate.check(phase="commit")
            self._observe_bucket(migrated=True)

    # ------------------------------------------------ SLO preemption
    def _preempt_for(self, order: List[Request]) -> None:
        """Bounded eviction of running work for an ENDANGERED deadline:
        when the tightest-slack waiting request (a) has a deadline with
        slack already inside ``FLAGS_serving_preempt_horizon``, and (b)
        cannot admit — every slot is occupied, or its fresh-page bill
        exceeds free + evictable pages — unseat the SLACKEST running
        request whose slack exceeds the head's by at least the margin.
        The victim goes back to the queue intact (prompt + emitted
        tokens are host state) and its later re-admission replays the
        r10 recovery path, so the resumed greedy continuation is
        bit-identical; each victim is preemptible at most
        ``FLAGS_serving_preempt_budget`` times, and preemptions never
        touch the replay-recovery retry budget."""
        if not self.preempt_enabled or not order:
            return
        head = order[0]
        if head.deadline is None:
            return                  # only deadline pressure preempts
        now = time.perf_counter()
        head_slack = head.deadline - now
        if head_slack > self.preempt_horizon:
            return                  # comfortable slack: wait in line
        while True:
            # free slots within the CURRENT bucket rung: the fill loop
            # only admits into slots below self.bucket, and a ladder's
            # out-of-rung slots are always None — counting those would
            # read a saturated rung as admittable and never preempt
            # (migration can't grow the rung either: a page-blocked
            # head is not "admittable demand")
            free_slots = self._slots[:self.bucket].count(None)
            need = self._fresh_pages_needed(head)
            reclaimable = (self.pool.free_page_count()
                           + (self._prefix.evictable_page_count()
                              if self._prefix is not None else 0))
            if free_slots and need <= reclaimable:
                return              # admittable without a victim
            cands = [r for r in self._slots
                     if r is not None and r.rid != head.rid
                     and r.preempts < self.preempt_budget]
            victim = None
            best = (-1.0, -1)
            # STRICTLY slacker than head + margin: an equal-slack pair
            # must never swap seats (each swap replays a healthy
            # request's whole prefill for zero deadline benefit)
            for r in cands:
                slack = ((r.deadline - now) if r.deadline is not None
                         else float("inf"))
                if slack <= head_slack + self.preempt_margin:
                    continue
                if (slack, r.rid) > best:
                    best = (slack, r.rid)
                    victim = r
            if victim is None:
                return              # nobody meaningfully slacker
            # the victim replays from its tokens: all of them
            self._values_first("preempt")
            # fault check BEFORE any mutation: an injected preemption
            # failure propagates into replay recovery cleanly
            self._f_preempt.check(rid=victim.rid)
            self._unseat(victim)
            # pages moved: reprice the head's bill before looping
            self._probe_memo.clear()

    def _unseat(self, req: Request) -> None:
        """Return one RUNNING request to the queue as pure host state —
        the preemption primitive. Slot, pages and pins release; tokens
        and the deadline stay; admission later replays it from prompt +
        emitted tokens (greedy => bit-identical continuation)."""
        slot = req.slot
        self._caches.free(slot)
        self._slots[slot] = None
        self._last_tok[slot] = 0
        self._to_replay_form(req)
        req.preempts += 1
        self.preemptions += 1
        self._queue.append(req)
        self._observe_preemption(req)

    # ------------------------------------------- speculative decoding
    # One round = one draft-propose dispatch (γ+1 draft forwards inside
    # a lax.scan) + one target-verify dispatch (a (1, γ+1) chunk of the
    # r12 chunked-prefill machinery). Losslessness rests ONLY on the
    # verify: draft writes past the accepted length — even past the
    # allocated span, where unallocated block-table entries route to
    # the reserved null scribble page — are garbage a later dispatch
    # overwrites before any real row attends to it, so γ needs no
    # tail-fitting constraint (new tokens just truncate to the budget).

    def _spec_occupancy_cap(self, n_rows: int) -> int:
        """Largest γ rung the decode-slot budget affords with
        ``n_rows`` speculating rows, each billed γ+1 slots (its verify
        covers γ+1 positions — the bucket-ladder admission price of a
        speculating request). 0 = priced out: at this occupancy the
        plain batched decode step is the cheaper schedule."""
        for g in reversed(self.spec_rungs):
            if n_rows * (g + 1) <= self.spec_slots:
                return g
        return 0

    def _spec_gamma(self, req: Request, cap: int) -> int:
        """This round's γ for one request: its adaptive rung, capped by
        occupancy and snapped DOWN to a compiled rung (never retrace),
        then trimmed toward the tail of the token budget so the last
        round doesn't draft far past ``max_new_tokens`` (truncation
        keeps correctness either way; this keeps the draft cheap)."""
        g = req.gamma or self.spec_gamma_default
        if cap:
            g = min(g, cap)
        remaining = req.max_new_tokens - len(req.tokens)
        fit = [r for r in self.spec_rungs
               if r <= min(g, max(1, remaining - 1))]
        return fit[-1] if fit else self.spec_rungs[0]

    def _spec_step(self, rows: List[Request]) -> bool:
        """Serve this step's decode-ready rows through speculation
        rounds, or decline (return False) and let the plain batched
        decode run. All-or-nothing per step: a row still teacher-
        forcing a prompt suffix (``pending``) keeps the whole step on
        the plain path (the suffix feed IS the plain step), and a step
        whose occupancy prices speculation out declines too — UNLESS a
        sampled request is present: sampling only exists through the
        verify program's rejection sampler, so sampled rows force
        speculation (at the smallest rung when over the budget)."""
        if any(r.pending for r in rows):
            return False
        sampled = any(r.temperature > 0.0 for r in rows)
        cap = self._spec_occupancy_cap(len(rows))
        if cap == 0 and not sampled:
            return False
        for req in list(rows):
            self._spec_round(req, self._spec_gamma(req, cap))
        return True

    def _spec_sync(self, req: Request) -> None:
        """Bring the draft pool's KV for this slot up to the target's
        accepted length L. First entry allocates the slot's full span
        (the worst-case draft pool makes that infallible); any cursor
        gap — admission prefilled the target only, or plain decode
        advanced it while speculation was priced out — teacher-forces
        through the draft's chunked-prefill program in fixed (1, C)
        chunks whose argmax is never pulled, so sync never retraces
        and never blocks on a device value."""
        slot = req.slot
        L = int(self.pool.seq_lens[slot])
        if not req.spec_ready:
            self._draft.allocate(
                slot, L + 1 + req.max_new_tokens - len(req.tokens))
            req.spec_ready = True
        cur = int(self._draft.pool.seq_lens[slot])
        if cur >= L:
            return
        feed = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        width = self.spec_sync_chunk
        fn = self._spec_sync_program()
        while cur < L:
            end = min(cur + width, L)
            ids = np.zeros((width,), np.int32)
            ids[:end - cur] = feed[cur:end]
            bt = jnp.asarray(
                self._draft.pool.block_tables[slot:slot + 1])
            sl = jnp.asarray(np.full((1,), cur, np.int32))
            dpools = self._draft.take_caches()
            self._f_spec_draft.check(rid=req.rid, op="sync")
            _tok, states = fn(self._draft_params, self._draft_buffers,
                              jnp.asarray(ids[None]), dpools, bt, sl,
                              jnp.int32(end - cur - 1))
            self._draft.install_caches(states)
            cur = end
        self._draft.pool.seq_lens[slot] = L

    def _spec_round(self, req: Request, gamma: int) -> None:
        """One propose/verify round for one decode-ready request.
        Round invariant (both pools, entering and leaving): the KV
        holds ids[:L] and ``_last_tok`` is ids[L], the newest not-yet-
        written token. The draft scan runs γ+1 forwards — the extra
        one writes the last proposal's KV — so a fully-accepted round
        leaves the draft cache gap-free and the next round needs no
        catch-up dispatch. Both fault sites fire BEFORE the accepted-
        length cursor roll (FLT002): an injected fault replays the
        round from host state bit-identically."""
        slot = req.slot
        sample = req.temperature > 0.0
        self._spec_sync(req)
        L = int(self.pool.seq_lens[slot])
        t0 = time.perf_counter()
        # --- draft: γ proposals in ONE dispatch
        dfn = self._spec_draft_program(gamma, sample, req.top_k)
        dbt = jnp.asarray(self._draft.pool.block_tables[slot:slot + 1])
        dsl = jnp.asarray(self._draft.pool.seq_lens[slot:slot + 1])
        tok = jnp.asarray(self._last_tok[slot:slot + 1][:, None])
        dpools = self._draft.take_caches()
        self._f_spec_draft.check(rid=req.rid, op="draft")
        if sample:
            key = jax.random.PRNGKey(
                (req.seed * 1000003 + L) & 0x7FFFFFFF)
            props, qrows, dstates = dfn(
                self._draft_params, self._draft_buffers, tok, dpools,
                dbt, dsl, key, jnp.float32(req.temperature),
                jnp.float32(req.top_p))
        else:
            qrows = None
            props, dstates = dfn(self._draft_params,
                                 self._draft_buffers, tok, dpools,
                                 dbt, dsl)
        self._draft.install_caches(dstates)
        # the verify chunk's ids need the concrete proposals — the
        # round's one designed draft->host sync point
        props_np = np.asarray(props).astype(np.int32).reshape(-1)
        # --- verify: ONE (1, γ+1) chunk through the TARGET
        ids = np.empty((gamma + 1,), np.int32)
        ids[0] = self._last_tok[slot]
        ids[1:] = props_np[:gamma]
        vfn = self._spec_verify_program(gamma, sample, req.top_k)
        bt = jnp.asarray(self.pool.block_tables[slot:slot + 1])
        sl = jnp.asarray(self.pool.seq_lens[slot:slot + 1])
        pools = self._caches.take_caches()
        self._f_spec_verify.check(rid=req.rid)
        if sample:
            greedy, prows, states = vfn(
                self._params, self._buffers, jnp.asarray(ids[None]),
                pools, bt, sl, jnp.float32(req.temperature),
                jnp.float32(req.top_p))
        else:
            prows = None
            greedy, states = vfn(self._params, self._buffers,
                                 jnp.asarray(ids[None]), pools, bt, sl)
        self._caches.install_caches(states)
        # --- acceptance (host): longest agreeing prefix + correction
        if sample:
            new_toks, accepted = self._spec_accept_sample(
                req, L, gamma, props_np, np.asarray(qrows),
                np.asarray(prows))
        else:
            greedy_np = np.asarray(greedy).reshape(-1)
            accepted = 0
            while accepted < gamma and \
                    int(props_np[accepted]) == int(greedy_np[accepted]):
                accepted += 1
            new_toks = [int(t) for t in props_np[:accepted]]
            new_toks.append(int(greedy_np[accepted]))
        # clip to the token budget, and to the first EOS — the plain
        # engine would have stopped there, so later positions of this
        # round must never exist
        new_toks = new_toks[:req.max_new_tokens - len(req.tokens)]
        if req.eos_token_id is not None and req.eos_token_id in new_toks:
            new_toks = new_toks[:new_toks.index(req.eos_token_id) + 1]
        # --- cursor roll (the rollback contract): both pools advance
        # to EXACTLY the accepted length; the rejected tail's KV
        # positions hold stale writes the next dispatch overwrites
        # before anything attends to them
        self.pool.seq_lens[slot] = L + len(new_toks)
        self._draft.pool.seq_lens[slot] = L + len(new_toks)
        now = time.perf_counter()
        if self._prefix is not None and not req.tokens:
            # first generated token of a shared admission: the verify
            # chunk just wrote the last prompt position — register the
            # full pages so repeats of this prompt deepen the cache
            self._prefix.register(req.prompt,
                                  self.pool.block_tables[slot])
        # ONE latency sample per round: a round delivers its tokens as
        # a burst, so the host-visible gap is the round gap
        self._observe_token(req, now)
        for t in new_toks:
            req.tokens.append(int(t))
            self._emit(req, int(t))
        self._last_tok[slot] = int(new_toks[-1])
        # --- adaptive γ: accept-rate EMA moves the rung
        rate = accepted / gamma
        req.spec_ema = 0.7 * req.spec_ema + 0.3 * rate
        if self.spec_adaptive:
            idx = max(i for i, r in enumerate(self.spec_rungs)
                      if r <= max(gamma, self.spec_rungs[0]))
            if accepted == gamma and req.spec_ema >= self._SPEC_GROW:
                idx = min(idx + 1, len(self.spec_rungs) - 1)
            elif req.spec_ema < self._SPEC_SHRINK:
                idx = max(idx - 1, 0)
            req.gamma = self.spec_rungs[idx]
        else:
            req.gamma = gamma
        self.spec_rounds += 1
        self.spec_tokens_accepted += accepted
        self.spec_tokens_rejected += gamma - accepted
        self.spec_last_gamma = gamma
        self._observe_spec(gamma, accepted, rate, t0, now)
        self._finish_if_done(req)

    # accept-rate EMA thresholds of the adaptive-γ rung walk: grow only
    # on a sustained-high EMA *and* a clean round, shrink on sustained
    # low — the gap is the hysteresis band that stops rung flapping
    _SPEC_GROW = 0.75
    _SPEC_SHRINK = 0.35

    def _spec_accept_sample(self, req: Request, L: int, gamma: int,
                            props: np.ndarray, qrows: np.ndarray,
                            prows: np.ndarray):
        """Rejection sampling (the speculative-sampling identity):
        accept draft token d_i with probability min(1, p_i(d_i) /
        q_i(d_i)); on the first rejection draw the correction from the
        residual normalize(max(p_i - q_i, 0)); after a full accept
        draw the bonus token from the target's last row. p and q are
        the FILTERED (temperature/top-k/top-p) distributions the
        programs return, so the emitted law is exactly the target's
        sampling law. Uniforms come from default_rng((seed, L)) —
        position-keyed, so a replayed round at the same accepted
        length redraws identically and sampled recovery/preemption
        stays bit-identical. Returns (new_tokens, accepted_count)."""
        rng = np.random.default_rng((req.seed, L))
        out: List[int] = []
        for i in range(gamma):
            d = int(props[i])
            q = float(qrows[i, d])
            p = float(prows[i, d])
            if q <= 0.0 or rng.random() * q <= p:
                out.append(d)
                continue
            resid = np.maximum(
                prows[i].astype(np.float64) - qrows[i], 0.0)
            s = float(resid.sum())
            if s <= 0.0:        # q >= p everywhere (numerically): the
                resid = prows[i].astype(np.float64)     # target row
                s = float(resid.sum())                  # itself
            out.append(int(rng.choice(resid.shape[0], p=resid / s)))
            return out, i
        last = prows[gamma].astype(np.float64)
        out.append(int(rng.choice(last.shape[0], p=last / last.sum())))
        return out, gamma

    # ---- speculative program getters: one compiled program per
    # (kind, γ rung, sampling mode, top_k) via DecodeKey.extra — the
    # rung set is small and each entry compiles once, so steady state
    # swaps between compiled programs with ZERO retraces (the bench's
    # retrace ledger asserts it)

    def _spec_program(self, kind: str, extra: Tuple, builder,
                      draft: bool):
        from .program_cache import DecodeKey, decode_program_cache
        memo = (kind,) + tuple(extra)
        fn = self._spec_fns.get(memo)
        if fn is None:
            pool = (self._draft if draft else self._caches).pool
            key = DecodeKey(
                kind=kind,
                model_sig=self._draft_sig if draft else self._model_sig,
                batch_bucket=1,
                page_budget=(pool.num_pages, pool.page_size,
                             pool.max_pages_per_seq),
                dtype=str(pool.k_pages[0].dtype),
                flags=self._flags.as_tuple(),
                extra=tuple(extra) + ((key_vocab.TAG_KV, self.kv_dtype),
                                      (key_vocab.TAG_WT,
                                       self.weight_dtype)))
            fn = decode_program_cache().get(key, builder)
            self._spec_fns[memo] = fn
            self._spec_keys[memo] = key
        if kind == "spec_draft":
            self.spec_draft_key = self._spec_keys[memo]
        elif kind == "spec_verify":
            self.spec_verify_key = self._spec_keys[memo]
        return fn

    def _spec_sync_program(self):
        """The DRAFT model's chunked-prefill program — the same r12
        builder the target's chunk path uses, keyed on the draft's
        signature and the sync chunk width."""
        return self._spec_program(
            "prefill_chunk", (self.spec_sync_chunk,),
            functools.partial(_build_chunk_prefill,
                              model=self.draft_model), draft=True)  # keycheck: disable=KEY002 — the documented model-object closure (draft model_sig rides the key)

    def _spec_draft_program(self, gamma: int, sample: bool,
                            top_k: int):
        fspec = self._fused_spec(draft=True)
        mode = ((key_vocab.ATOM_SAMPLE, int(top_k)) if sample
                else (key_vocab.ATOM_GREEDY,))
        path = ((key_vocab.ATOM_FUSED,) if fspec
                else (key_vocab.ATOM_GENERIC,))
        return self._spec_program(
            "spec_draft", (gamma,) + path + mode,
            functools.partial(_build_spec_draft, model=self.draft_model,  # keycheck: disable=KEY002 — the documented model-object closure (draft model_sig rides the key)
                              gamma=gamma, sample=sample,
                              top_k=int(top_k), fspec=fspec,
                              snap=self._flags if fspec else None),
            draft=True)

    def _spec_verify_program(self, gamma: int, sample: bool,
                             top_k: int):
        mode = ((key_vocab.ATOM_SAMPLE, int(top_k)) if sample
                else (key_vocab.ATOM_GREEDY,))
        return self._spec_program(
            "spec_verify", (gamma + 1,) + mode,
            functools.partial(_build_spec_verify, model=self.model,  # keycheck: disable=KEY002 — the documented model-object closure (model_sig rides the key)
                              sample=sample, top_k=int(top_k)),
            draft=False)

    def _step_inner(self) -> None:  # tracecheck: hotpath
        n = self._step_no
        while True:
            try:
                # one span a phase a step  # tracecheck: disable=TRC007
                with self._m.span("engine.schedule", step=n,
                                  queued=len(self._queue)):
                    self._sweep_deadlines()
                    self._probe_memo.clear()    # prefix probes are per-step
                    # decode-ready requests present BEFORE this step's
                    # scheduler + prefill work: the population that work
                    # below is stalling
                    waiting = any(r is not None and r.prefill_pos is None
                                  for r in self._slots)
                    t_sched = time.perf_counter()
                    # the step's admission order, sorted once and shared
                    # by the migration demand estimate and the slot-fill
                    # loop below
                    order = self._admission_order() if self._queue else []
                    self._maybe_migrate(order)
                    # SLO preemption runs BEFORE the slot fill: an
                    # unseated victim's slot admits the endangered head
                    # in this very step
                    self._preempt_for(order)
                break
            except _ReadFirst as first:
                # outside the span, so the wait for the device is not
                # the scheduler's; then the same schedule, from its top
                self._settle(first.reason)
        # the step's ONE prefill-compute unit alternates between new
        # monolithic admissions and in-flight chunks under contention:
        # admissions always winning would starve a mid-prefill long
        # prompt forever under a stream of short arrivals; chunks are
        # finite per request, and a unit-needing head stops admission
        # (head-of-line), so neither side starves
        chunk_pending = any(r is not None and r.prefill_pos is not None
                            for r in self._slots)
        did_prefill = False
        chunk_ran_first = False
        if chunk_pending and self._chunk_turn:
            chunk_ran_first = self._chunk_step()
            did_prefill = chunk_ran_first
        # request.prefill nests in this span, so its SELF time is the
        # scheduler's  # tracecheck: disable=TRC007
        with self._m.span("engine.admit", step=n) as fill:
            admitted = 0
            for slot in range(self.bucket):
                if self._slots[slot] is not None or not order:
                    continue
                req = self._next_admission(order)
                if req is None:
                    break       # head page-blocked: wait, keep order
                if did_prefill and self._needs_prefill_unit(req):
                    # the unit is spent: a monolithic-prefill head admits
                    # next step (head-of-line — nothing jumps it); cursor-
                    # only admissions behind a served head keep filling
                    break
                order.remove(req)
                self._queue.remove(req)
                try:
                    did_prefill |= self._admit(req, slot)
                except Exception as e:
                    if isinstance(e, RuntimeError) and \
                            "page pool exhausted" in str(e):
                        # allocate came up short mid-step (pinned pages
                        # under-counted by the pre-check): back off to
                        # the queue instead of killing the step
                        self._rollback_admission(req, slot)
                        self._queue.insert(0, req)
                        self._observe_page_pressure(max(
                            1, self._pages_needed(req)
                            - self.pool.free_page_count()))
                        break
                    # dispatch failure: hand the request to recovery
                    # (it holds no slot state after the rollback)
                    self._rollback_admission(req, slot)
                    self._failed_admission = req
                    raise
                admitted += 1
                if not self._head_blocked:
                    # a BYPASS admission must not clear the pressure the
                    # still-blocked head just published
                    self._observe_page_pressure(0)
            fill.args["admitted"] = admitted
        # ONE prefill-compute unit per step (one monolithic prefill OR
        # one chunk — admitting several prefills back to back would
        # stack their stalls on every decoding request; the load bench
        # measured admission bursts, not long prompts, as the worst
        # stall): if admission spent it, chunks wait for their turn
        admission_used_unit = did_prefill and not chunk_ran_first
        if not did_prefill:
            did_prefill = self._chunk_step()
        # fairness flip: when chunks were pending but an admission took
        # the unit, the next contended step is the chunks'
        self._chunk_turn = chunk_pending and admission_used_unit
        if waiting and did_prefill:
            self._observe_stall(time.perf_counter() - t_sched)

        # the rows this step decodes, from COUNTS: a row whose budget
        # the step in flight fills is not dispatched again (it ends when
        # that step is read); an end by EOS is a value, seen one step
        # late, and costs the row one more step whose token is dropped
        decode_rows = [r for r in self._slots
                       if r is not None and r.prefill_pos is None
                       and len(r.tokens) + r.in_flight < r.max_new_tokens]
        if not decode_rows:
            self._settle("drained")     # no step goes out behind it
            self._observe_step_begin(0)
            return
        self._observe_step_begin(len(decode_rows))

        if self._draft is not None and self._spec_step(decode_rows):
            # the rows were served by speculation rounds (draft scan +
            # verify chunk per row); the batched decode must not run
            # again this step, and nothing is ever left in flight
            self._m.decode_settles("speculation").inc()
            self._observe_step_end()
            return

        if self._block is not None:
            self._block_step(decode_rows)
            self._observe_step_end()
            return

        b = self.bucket
        fn = self._decode_program(b)
        self._observe_decode(decode_rows)
        flying = self._flying
        # the decode dispatch, from its uploads to the read and emit of
        # the step BEFORE it, and its parts (phase scopes: a device
        # capture can say which device time ran under a decode dispatch
        # and which part of the host's share is which, the step clock
        # accounts each; the ring keeps only the whole)
        # tracecheck: disable=TRC007
        with self._m.span("engine.decode_step", step=n,
                          active=len(decode_rows), bucket=b,
                          overlapped=flying is not None):
            # tracecheck: disable=TRC007
            with self._m.phase("engine.decode.stage"):
                # tracecheck: disable=TRC007
                with self._m.phase("engine.decode.stage.inputs"):
                    # a row takes its token from the step in flight (-1:
                    # the program reads that step's output, still on the
                    # device) unless the host knows a newer one: a
                    # prefill's or a final chunk's of this step, a
                    # forced prompt token
                    feed = self._last_tok[:b].copy()
                    for req in decode_rows:
                        if req.in_flight:
                            feed[req.slot] = -1
                    inputs = self._caches.decode_inputs(
                        b, [r.slot for r in decode_rows]) + [feed]
                # ONE transfer call for the step's small inputs: each
                # ``jnp.asarray`` is a dispatch of its own, a third of a
                # millisecond of the host's share of every step
                # tracecheck: disable=TRC007
                with self._m.phase("engine.decode.stage.put"):
                    bt, sl, *extra, feed = jax.device_put(inputs)
                if self._stacked is not None:
                    # N-layer program signature: the stacked per-group
                    # weight structs ride as traced args (never baked
                    # constants)
                    extra = [self._stacked]
                t0 = time.perf_counter()
                # tracecheck: disable=TRC007
                with self._m.phase("engine.decode.stage.caches"):
                    pools = self._caches.take_caches()
                    self._f_decode.check()
            # tracecheck: disable=TRC007
            with self._m.phase("engine.decode.dispatch"):
                # with nothing in flight every row's token is in
                # ``feed``, which then stands in for the last output
                toks, states, *counts = fn(
                    self._params, self._buffers,
                    (feed if flying is None else flying.toks, feed),
                    pools, bt, sl, *extra)
                self._note_expert_counts(counts)
                self._caches.install_caches(states)
            # the host's state advances from counts, at the dispatch:
            # every cursor by one, a forced prompt suffix by one token
            rows = []
            for req in decode_rows:
                if req.temperature > 0.0 and not req.pending:
                    # a sampled request never takes a token from the
                    # greedy batch step — the spec verify program is its
                    # sampler. The row's KV write at the cursor was a
                    # correct (and repeatable) prefix write, but the
                    # cursor must NOT advance: the next speculation
                    # round re-feeds this position through its verify
                    # chunk
                    continue
                self.pool.seq_lens[req.slot] += 1
                if req.pending:
                    # still teacher-forcing the prompt suffix (prefix-
                    # cache admission): the model output is a prompt-
                    # position logit, not a generated token — feed the
                    # next suffix token
                    self._last_tok[req.slot] = req.pending.pop(0)
                    continue
                req.in_flight += 1
                rows.append((req.slot, req))
            self._flying = _Flight(toks, rows, t0)
            if flying is not None:
                # the late read: step N's tokens, behind step N+1's
                # dispatch. Where the device is the longer side the host
                # waits here for N with N+1 queued behind it
                self._read(flying)
            if self._draft is not None:
                # a speculation round works from the values
                self._settle("speculation")
        self._observe_step_end()

    # --------------------------------------------------- the block step
    def _block_step(self, rows: List[Request]) -> None:  # tracecheck: hotpath
        """One forward of every row's current block (a block-diffusion
        model's batched step). A row with masked positions left runs a
        DENOISING forward: on the device, per masked position the argmax
        token and its softmax probability, the most confident revealed.
        A row with none left runs the COMMIT forward: its K/V stay, its
        cursor advances by the block and the block's tokens go to the
        host one step late, behind the next dispatch. Which it is the
        host knows from counts, without reading a token."""
        n = self._step_no
        blen, mask = self._block
        b = self.bucket
        fn = self._block_program(b)
        self._observe_decode(rows)
        flying = self._flying
        # tracecheck: disable=TRC007
        with self._m.span("engine.block_step", step=n, active=len(rows),
                          bucket=b, overlapped=flying is not None):
            # the parts of the staging as the decode step's
            # tracecheck: disable=TRC007
            with self._m.phase("engine.block.stage"):
                # tracecheck: disable=TRC007
                with self._m.phase("engine.block.stage.inputs"):
                    # a row's block is the host's word on it, or -1s for
                    # "what the step in flight leaves" (still on the
                    # device)
                    feed = self._blk[:b].copy()
                    commit = np.zeros((b,), np.int32)
                    for req in rows:
                        if not req.masks:
                            commit[req.slot] = 1
                    tables, cursors = self._caches.decode_inputs(b, ())
                # tracecheck: disable=TRC007
                with self._m.phase("engine.block.stage.put"):
                    bt, sl, commit_d, feed_d = jax.device_put(
                        [tables, cursors, commit, feed])
                hist = self._expert_counts()
                t0 = time.perf_counter()
                # tracecheck: disable=TRC007
                with self._m.phase("engine.block.stage.caches"):
                    pools = self._caches.take_caches()
                    self._f_decode.check()
            # tracecheck: disable=TRC007
            with self._m.phase("engine.block.dispatch"):
                blocks, conf, states, *hist = fn(
                    self._params, self._buffers,
                    (feed_d if flying is None else flying.toks, feed_d),
                    pools, bt, sl, commit_d, *hist)
                self._caches.install_caches(states)
                if hist:
                    self._expert_hist = hist[0]
            if self._block_records is not None:
                self._record_blocks(rows, feed, cursors, blocks, conf,
                                    flying)
            # the host's state advances from counts, at the dispatch
            done, new_tokens = [], 0
            for req in rows:
                slot = req.slot
                if req.masks:
                    req.masks -= 1
                    self._blk[slot] = -1        # the device's is newer
                    continue
                self.pool.seq_lens[slot] += blen
                req.in_flight += blen - req.known
                new_tokens += blen - req.known
                done.append((slot, req, req.known))
                # the next block starts as masks, which the host knows
                req.known, req.masks = 0, blen
                self._blk[slot] = mask
            # three counter writes a step: what the step, its rows and
            # their tokens were, where the dispatch is made
            m = self._m
            m.block_forwards.inc(len(rows))  # tracecheck: disable=TRC007
            if done:
                m.block_commits.inc(len(done))  # tracecheck: disable=TRC007
                m.block_tokens.inc(new_tokens)  # tracecheck: disable=TRC007
            self._flying = _Flight(blocks, done, t0)
            if flying is not None:
                self._read(flying)

    def _emit_blocks(self, flight: _Flight, blocks: np.ndarray, now: float,
                     level: bool) -> None:
        """A read block step: emit the committed blocks' tokens (less
        the prompt tokens a first block began with, and less the
        surplus over a request's budget), and with nothing dispatched
        behind it (``level``) take every block the device held newer
        than the host back into the host's copy."""
        if level:
            stale = self._blk[:len(blocks), 0] < 0
            self._blk[:len(blocks)][stale] = blocks[stale]
        for slot, req, known in flight.rows:
            if req.slot != slot:
                continue            # ended by EOS a commit earlier
            req.in_flight -= blocks.shape[1] - known
            if self._prefix is not None and not req.tokens:
                self._prefix.register(req.prompt,
                                      self.pool.block_tables[slot])
            n0 = len(req.tokens)
            for tok in blocks[slot, known:]:
                if len(req.tokens) >= req.max_new_tokens:
                    break           # the last block's surplus
                self._observe_token(req, now)
                req.tokens.append(int(tok))
                self._emit(req, int(tok))
                if tok == req.eos_token_id:
                    break
            # tracecheck: disable=TRC007
            self._m.event("request.block_commit", flight.t0, now,
                          parent=self._step_span, rid=req.rid,
                          tokens=len(req.tokens) - n0, step=self._step_no)
            self._finish_if_done(req)

    def record_blocks(self, rids) -> None:
        """Set-up probe: from now on keep, for the requests ``rids``,
        every forward of their blocks: the cursor, the block's ids
        before the forward and after it, the log-confidence the step
        computed for every position of the block (the reveal takes the
        largest among the masked), and whether it committed
        (``block_records``). Each recorded step is read at once, so
        this is for checks, not for a timed window; ``rids`` None ends
        it."""
        self._block_records = (None if rids is None
                               else {int(r): [] for r in rids})

    def block_records(self) -> Dict[int, list]:
        """What :meth:`record_blocks` kept so far: rid -> a list of
        ``dict(cursor, before, after, log_conf, commit)`` in forward
        order."""
        return dict(self._block_records or {})

    def _record_blocks(self, rows, feed, cursors, blocks, conf,
                       flying) -> None:
        last = None if flying is None else np.asarray(flying.toks)
        after, conf = np.asarray(blocks), np.asarray(conf)
        for req in rows:
            rec = self._block_records.get(req.rid)
            if rec is None:
                continue
            before = feed[req.slot]
            if before[0] < 0:
                before = last[req.slot]
            rec.append(dict(cursor=int(cursors[req.slot]),
                            before=before.copy(),
                            after=after[req.slot].copy(),
                            log_conf=conf[req.slot].copy(),
                            commit=not req.masks))

    def _expert_counts(self) -> tuple:
        """``(hist,)``: the device array a step's expert counts
        accumulate in (``model.expert_counts_width()`` wide), made on
        first use; ``()`` for a model without expert layers."""
        if not self._counts_width:
            return ()
        if self._expert_hist is None:
            self._expert_hist = jnp.zeros((self._counts_width,), jnp.int32)
        return (self._expert_hist,)

    def _note_expert_counts(self, counts) -> None:
        """A prefill or decode program of a model with expert layers
        returned its counts: add them on the device (one small dispatch
        a call; the block step accumulates inside its own program)."""
        if counts:
            self._expert_hist = (counts[0] if self._expert_hist is None
                                 else self._expert_hist + counts[0])

    def expert_histogram(self) -> Optional[np.ndarray]:
        """The assignments each held expert got since the last call,
        (experts held,), from every program that ran the expert layers
        — read from the device HERE (a sync), never in the step — then
        zeroed. The counters move by what was read: ``moe_assignments``
        by their sum, ``moe_experts_touched`` by the (call, layer,
        expert) triples that got at least one assignment, which is how
        many experts' weights the grouped matmuls had to read. None
        where nothing was counted (always, for a model without expert
        layers)."""
        if self._expert_hist is None:
            return None
        hist = np.asarray(self._expert_hist)
        self._expert_hist = None
        self._m.moe_assignments.inc(int(hist[:-1].sum()))
        self._m.moe_experts_touched.inc(int(hist[-1]))
        return hist[:-1]

    # --------------------------------------------- the step in flight
    def _values_first(self, reason: str) -> None:
        """The scheduler is about to move or end a seated row: not
        with a decode step in flight (see :class:`_ReadFirst`)."""
        if self._flying is not None:
            raise _ReadFirst(reason)

    def _settle(self, reason: str, read: bool = True) -> None:
        """Bring the host level with the device: read the decode step
        in flight, if there is one, with nothing dispatched behind it.
        Whatever needs the token VALUES or moves seated rows calls this
        first; ``reason`` labels the count. ``read=False`` drops the
        step unread (its pools or its replica are lost, or may be): the
        rows replay that token from the tokens the host has."""
        flying, self._flying = self._flying, None
        if flying is None:
            return
        self._m.decode_settles(reason).inc()
        if read:
            self._read(flying, level=True)
        else:
            for row in flying.rows:
                row[1].in_flight = 0

    def _read(self, flight: _Flight, level: bool = False) -> None:  # tracecheck: hotpath
        """Read one dispatched decode step's tokens and emit them: the
        scheduler's one sync with the device. ``level``: nothing was
        dispatched behind it, so the host's copies come level with the
        device (a block model's blocks)."""
        # tracecheck: disable=TRC007
        with self._m.phase("engine.decode.pull"):
            # admission/eviction need the concrete token ids
            # tracecheck: disable=TRC002
            toks = np.asarray(flight.toks)
        now = time.perf_counter()
        if self._block is not None:
            # tracecheck: disable=TRC007
            with self._m.span("engine.emit", step=self._step_no):
                self._emit_blocks(flight, toks, now, level)
            return
        if self.tp_degree > 1:
            # sharded dispatch envelope: compute + the per-layer psum
            # pair, observed host-side OUTSIDE the shard_map body
            # (meshcheck MSH006 keeps telemetry off the traced path)
            self._observe_collective(now - flight.t0)
        # tracecheck: disable=TRC007
        with self._m.span("engine.emit", step=self._step_no):
            for slot, req in flight.rows:
                if req.slot != slot:
                    # ended by EOS when the step before this one was
                    # read, with this step already dispatched: the extra
                    # token is dropped (its KV write landed in a page
                    # the row still held; a page freed since is reused
                    # only by programs dispatched later)
                    continue
                req.in_flight -= 1
                tok = int(toks[slot])
                if self._prefix is not None and not req.tokens:
                    # first generated token of a shared admission: the whole
                    # prompt's KV is now written — register the suffix's full
                    # pages so repeats of THIS prompt deepen the cache too
                    self._prefix.register(req.prompt,
                                          self.pool.block_tables[slot])
                if req.tokens:
                    # per-token host-side latency write, bench-gated <2%
                    # tracecheck: disable=TRC007
                    self._m.itl.observe(now - req.t_last)
                else:
                    # first token of a shared admission: TTFT closes here
                    # tracecheck: disable=TRC007
                    self._m.ttft.observe(now - req.t_submit)
                    self._first_known[req.rid] = now
                req.t_last = now
                req.tokens.append(tok)
                self._emit(req, tok)
                self._last_tok[slot] = tok
                self._finish_if_done(req)

    # ------------------------------------------------- telemetry helpers
    # NOT hotpath-marked: plain host bookkeeping called once per step()
    # (the per-token writes stay inline above under pragma'd lines).

    def _observe_step_clock(self) -> None:
        """The round is over, its root span closed: publish its length
        and its phases' seconds as counters; a slow round has left its
        record with the tracer."""
        m = self._m
        length, over = m.clock.end(self._step_no, traces_built())
        m.steps.inc()
        m.step_seconds.inc(length)
        step = m.clock.step
        for counter, phases in m.phase_seconds:
            for phase in phases:
                if phase.step == step:      # it ran in this round
                    counter.inc(phase.step_seconds)
        if over:
            m.slow_steps.inc()
            m.slow_step_seconds.inc(over)

    def _observe_step_begin(self, n_active: int) -> None:
        if n_active:
            self._m.decode_steps.inc()
        else:
            # idle step: nothing decoded, but keep the gauges honest
            self._observe_step_end()

    def _observe_step_end(self) -> None:
        """One gauge refresh per step, AFTER finishes freed their
        slots/pages (and unpinned prefix pages), so a drained engine
        reads 0 everywhere instead of freezing at shortfall-time or
        pre-free values."""
        m = self._m
        # telemetry's own cost, under its own name
        with m.span("engine.ledger", step=self._step_no):
            m.queue_depth.set(len(self._queue))
            m.occupancy.set(self.max_batch - self._slots.count(None))
            if not self._queue:
                m.page_pressure.set(0)  # an empty queue has no pressure
            self._observe_pool_ledger()

    def _observe_token(self, req: Request, now: float) -> None:
        """A prefill's token or a speculation round's burst became known
        on the host at ``now`` (call BEFORE appending it): the first
        token of a request closes its TTFT; a later one — a replayed
        prefill's too, it continues the sequence — is an inter-token
        gap, not a second TTFT."""
        if req.tokens:
            self._m.itl.observe(now - req.t_last)
        else:
            self._m.ttft.observe(now - req.t_submit)
            self._first_known[req.rid] = now
        req.t_last = now

    def _observe_decode(self, rows: List[Request]) -> None:
        """One batched decode dispatch is about to be made: the rows
        that decode, the rows the rung's program computes, and the
        cached tokens those rows must read."""
        m = self._m
        if self._flying is not None:
            m.decode_overlapped.inc()   # it goes out behind that step
        m.decode_rows.inc(len(rows))
        m.decode_slots.inc(self.bucket)
        m.decode_live_tokens.inc(
            int(sum(self.pool.seq_lens[r.slot] for r in rows)))
        windowed = self._caches.window_read_tokens(r.slot for r in rows)
        if windowed is not None:
            m.decode_window_tokens.inc(windowed)
        # every row of the rung rides the kernel, decoding or not (an
        # idle slot reads the null page, a mid-prefill row its cursor);
        # with the step's own token, or block of them
        b, page = self.bucket, self.pool.page_size
        own = 1 if self._block is None else self._block[0]
        held = -(-(self.pool.seq_lens[:b] + own) // page)
        m.decode_read_pages.inc(int(held.sum()))
        m.decode_table_pages.inc(b * self.pool.block_tables.shape[1])

    def _observe_pool_ledger(self) -> None:
        """memwatch pool ledger (r13): the PagedKVCache ledger as
        step-end gauges plus one Perfetto counter sample, so memory
        watermarks line up with the serving timeline. All O(1) reads;
        fragmentation (a numpy sort over the free list) recomputes only
        when the free-list epoch moved — steady-state decode steps
        never touch the list and pay nothing for it."""
        m = self._m
        led = self._caches.ledger()
        pinned = (self._prefix.pinned_page_count()
                  if self._prefix is not None else 0)
        # the r09 gauges read the same pool state: set them from the
        # ledger rather than recomputing (serving pools always reserve
        # the null page, so pages_in_use == num_pages - 1 - free)
        m.kv_pages_in_use.set(led["pages_in_use"])
        if self._prefix is not None:
            m.prefix_pinned.set(pinned)
        m.pool_pages["used"].set(led["pages_in_use"])
        m.pool_pages["free"].set(led["pages_free"])
        m.pool_pages["shared"].set(led["pages_shared"])
        m.pool_pages["pinned"].set(pinned)
        m.pool_pages["spilled"].set(led["pages_spilled"])
        m.pool_bytes["used"].set(led["bytes_in_use"])
        m.pool_bytes["free"].set(led["bytes_free"])
        m.pool_bytes["shared"].set(
            led["pages_shared"] * led["bytes_per_page"])
        m.pool_bytes["pinned"].set(pinned * led["bytes_per_page"])
        m.pool_bytes["spilled"].set(led["bytes_spilled"])
        if led["pages_spilled"] > self._host_tier_peak:
            # tier watermark: the host-RAM bytes memwatch prices
            self._host_tier_peak = led["pages_spilled"]
            m.host_tier_peak.set(self._host_tier_peak)
        if led["epoch"] != self._pool_frag_epoch:
            self._pool_frag_epoch = led["epoch"]
            self._pool_frag = self.pool.free_list_fragmentation()
            m.pool_frag.set(self._pool_frag)
        m.counter_track(
            "kv_pool", time.perf_counter(),
            pages_in_use=led["pages_in_use"],
            bytes_in_use=led["bytes_in_use"],
            pages_shared=led["pages_shared"], pages_pinned=pinned,
            pages_spilled=led["pages_spilled"])
        if "window_bytes_in_use" in led:
            # the window layers' pool is billed beside the global one,
            # each by the pages in use
            m.kv_pool_bytes["global"].set(led["bytes_in_use"])
            m.kv_pool_bytes["window"].set(led["window_bytes_in_use"])
            m.window_pages_released.inc(
                led["window_pages_released"] - self._window_released_seen)
            self._window_released_seen = led["window_pages_released"]
        if led["state_bytes"]:
            # the state store is billed beside the pages: all of it is
            # resident, the seated slots' share is what is in use
            seated = self.max_batch - self._slots.count(None)
            m.state_bytes.set(led["state_bytes"])
            m.state_slots_live.set(seated)
            m.counter_track(
                "recurrent_state", time.perf_counter(),
                bytes_resident=led["state_bytes"],
                bytes_in_use=seated * led["state_bytes_per_slot"])

    def _observe_page_pressure(self, short: int) -> None:
        """Admission is (or stopped being) page-blocked: publish how
        many pages short the queue head is."""
        self._m.page_pressure.set(short)

    def _observe_timeouts(self, n: int) -> None:
        self._m.requests_timeout.inc(n)

    def _observe_recovery(self, n_replayed: int, n_failed: int,
                          dt: float) -> None:
        """One replay-recovery event: how many requests were re-queued,
        how many were terminated FAILED, and the recovery wall clock."""
        m = self._m
        m.recoveries.inc()
        if n_replayed:
            m.retries.inc(n_replayed)
        if n_failed:
            m.requests_failed.inc(n_failed)
        m.recovery_seconds.observe(dt)
        # the ledger must reflect the FRESH pool immediately (the step
        # that died never reached its step-end refresh)
        self._observe_pool_ledger()

    def _observe_evict_shortfall(self, short: int) -> None:
        """``evict()`` freed fewer pages than the admission asked for:
        record how many, and the pinned-page pressure that explains it."""
        self._m.evict_short.inc(short)
        self._m.prefix_pinned.set(self._prefix.pinned_page_count())

    def _observe_preemption(self, req: Request) -> None:
        """One victim unseated for a tighter deadline: count it and the
        decode tokens its replay will regenerate."""
        m = self._m
        m.preemptions.inc()
        if req.tokens:
            m.preempted_tokens.inc(len(req.tokens))

    def _observe_spec(self, gamma: int, accepted: int, rate: float,
                      t0: float, t1: float) -> None:
        """One speculation round retired: the accept-rate histogram
        (the adaptive-γ signal), accepted/rejected token counters, the
        γ gauge and a timeline event."""
        m = self._m
        m.spec_rounds_c.inc()
        m.spec_accept.observe(rate)
        if accepted:
            m.spec_accepted.inc(accepted)
        if gamma - accepted:
            m.spec_rejected.inc(gamma - accepted)
        m.spec_gamma.set(gamma)
        # retroactive, but wholly inside the step: its root is the parent
        m.event("engine.spec_round", t0, t1, parent=self._step_span,
                step=self._step_no, gamma=gamma, accepted=accepted)

    def _observe_chunk(self, dt: float, pos: int, tokens: int,
                       final: bool = False) -> None:
        """One chunked-prefill dispatch retired: bank its wall clock —
        the unit a long-prompt arrival can stall decode by — the real
        tokens it computed, from cursor ``pos``, and the query-key
        pairs its attention had to compute in a layer of each kind,
        beside the pairs the kernel's tiles made of them.
        The final chunk also closes the per-request prefill counter."""
        self._m.prefill_chunk_s.observe(dt)
        self._m.prefill_tokens.inc(tokens)
        self._m.chunk_attn_pairs.inc(
            tokens * pos + tokens * (tokens + 1) // 2)
        windowed = self._caches.window_read_pairs(pos, tokens)
        if windowed is not None:
            self._m.chunk_window_pairs.inc(windowed)
        # what the kernel's tiling made of them, per layer kind
        for counter, tl in zip((self._m.chunk_attn_tile_pairs,
                                self._m.chunk_window_tile_pairs),
                               self._chunk_cut):
            counter.inc(chunk_tile_pairs(tl, pos))
        if final:
            self._m.prefills.inc()

    def _observe_collective(self, dt: float) -> None:
        """One tensor-parallel decode dispatch retired: bank the wall
        clock of the sharded envelope (per-layer psum pair + compute).
        Host-side only — the shard_map body itself never writes
        telemetry (MSH006); a tp=1 engine never reaches here."""
        self._m.collective_s.observe(dt)

    def _observe_stall(self, dt: float) -> None:
        """Scheduler + prefill work ran this step while decode-ready
        requests waited: that wall clock is the decode stall (the load
        bench asserts the bound of the ``max_decode_stall`` probe)."""
        if dt > self.max_decode_stall:
            self.max_decode_stall = dt
        self._m.decode_stall_s.observe(dt)

    def _observe_bucket(self, migrated: bool = False) -> None:
        """The bucket gauge only moves on migration (plus once at
        construction), so it refreshes there instead of per step."""
        self._m.bucket.set(self.bucket)
        if migrated:
            self._m.migrations.inc()


# ------------------------------------------------------ program builders
# Module-level (not engine methods) so the decode program cache can hand
# one compiled step to every engine over the same model. All three donate
# ONLY the pools (each buffer appears once there; bt/sl are shared by
# every layer's state and must not be donated), so a page write may
# reuse the pool's memory. Donation alone did not make the write free:
# XLA's scatter was done in place but in a layout of its own, and a pool
# with a head dim under 128 crossed the jit boundary in a page-minor
# layout, so every program transposed each pool two or three times. On
# the TPU the write is an aliased Pallas page-write kernel and the pool
# is allocated lane-padded (kernels/paged_attention.py, "pool
# management", ``padded_head_dim``): parameter, kernels and result all
# have the row-major layout, and the compiled programs hold no
# pool-shaped copy
# (tests/test_chip_compile.py::test_serving_program_copies_no_pool).

def _forward_with_cache(model, params, buffers, ids, states, offset, **kw):
    """``model.forward_with_cache`` traced functionally: ``(logits,
    states, counts)``. ``counts`` is ``()``, or for a model that
    publishes ``expert_counts_width()`` the 1-tuple of what its expert
    layers counted in this call: the program returns it as one value
    more, and the engine accumulates it on the device. ``kw``: the
    model's own keywords (:func:`_prefill_row`)."""
    from ..jit import functional_call
    if not hasattr(model, "expert_counts_width"):
        return functional_call(
            model, params, ids, states, offset, buffers=buffers,
            method="forward_with_cache", **kw) + ((),)
    logits, states, counts = functional_call(
        model, params, ids, states, offset, buffers=buffers,
        method="forward_with_cache", expert_counts=True, **kw)
    return logits, states, (counts,)


def _prefill_row(model, params, buffers, ids, states, offset, at):
    """A b=1 prefill's forward: ``(row, states, counts)``, ``row`` the
    ``(V,)`` logits of position ``at``, the ONE position a prefill
    program reads. A model that publishes ``logits_at_position`` is
    handed the position and runs its head on that row alone (at a
    vocabulary of 200k and a chunk of 1,024 the whole ``(S, V)`` is
    0.8 GB of logits and 4 ms of head); any other computes them all and
    the row is indexed here, as it always was."""
    if getattr(model, "logits_at_position", False):
        logits, states, counts = _forward_with_cache(
            model, params, buffers, ids, states, offset, logits_at=at)
        return logits[0, 0], states, counts
    logits, states, counts = _forward_with_cache(
        model, params, buffers, ids, states, offset)
    return logits[0, at], states, counts


def _build_prefill(note_trace, model):
    def serving_prefill(params, buffers, ids, pools, bt, sl, *slot):
        # ``slot``: only for a recurrent model, the state row to use
        note_trace()
        states = cache_entries(model, pools, PagedDecodeState, bt, sl,
                               **({"slot": slot[0]} if slot else {}))
        row, states, counts = _prefill_row(
            model, params, buffers, ids, states, jnp.int32(0),
            ids.shape[1] - 1)
        return jnp.argmax(row.astype(jnp.float32)), states, *counts

    return jax.jit(serving_prefill, donate_argnums=(3,))


def _build_chunk_prefill(note_trace, model):
    """The chunked-prefill step: one fixed-size b=1 chunk of prompt
    through the model against the PAGED pool. ``PagedChunkState`` routes
    attention onto the cache-READING prefill path — the chunk writes its
    KV at positions ``sl .. sl+C-1`` and attends to the already-written
    prefix plus itself causally — and ``sl[0]`` is the rotary/positional
    offset, so ONE compiled program serves every chunk of every prompt
    (the final partial chunk pads; pad rows are causally invisible to
    real rows and ``last_idx`` picks the real tail's logits). The argmax
    return is meaningful only on the final chunk — earlier dispatches
    never pull it, so they stay async."""
    from ..kernels.paged_attention import PagedChunkState

    def serving_prefill_chunk(params, buffers, ids, pools, bt, sl, last_idx,
                              *slot):
        # a recurrent layer starts from what the last chunk left in row
        # ``slot`` and must not see the pad: pad rows are causally
        # invisible to attention, but a recurrence would absorb them
        note_trace()
        states = cache_entries(
            model, pools, PagedChunkState, bt, sl,
            **({"slot": slot[0], "n_valid": last_idx + 1} if slot else {}))
        row, states, counts = _prefill_row(
            model, params, buffers, ids, states, sl[0], last_idx)
        return jnp.argmax(row.astype(jnp.float32)), states, *counts

    return jax.jit(serving_prefill_chunk, donate_argnums=(3,))


def _step_tokens(toks):
    """The prologue every batched decode program shares: the step's
    ``(b, 1)`` input tokens from ``toks = (last, feed)``. ``last`` is
    the ``(b,)`` output of the decode step dispatched before this one,
    which the host may not have read yet; ``feed`` is the host's word on
    each row, a token (>= 0) that overrides it or -1 for "the
    device's". So a step's input never waits for the host to read the
    last step's output."""
    last, feed = toks
    return jnp.where(feed >= 0, feed, last)[:, None]


def _build_generic_decode(note_trace, model):
    """The unfused decode step: one functional_call through the model's
    forward_with_cache (every layer an op chain XLA schedules)."""
    def serving_decode_generic(params, buffers, toks, pools, bt, sl, *live):
        # ``live``: only for a recurrent model, the rows that advance
        note_trace()
        toks = _step_tokens(toks)
        states = cache_entries(model, pools, PagedDecodeState, bt, sl,
                               **({"live": live[0]} if live else {}))
        # offset=None -> per-slot positions from states.seq_lens
        logits, states, counts = _forward_with_cache(
            model, params, buffers, toks, states, None)
        return (jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1),
                states, *counts)

    return jax.jit(serving_decode_generic, donate_argnums=(3,))


def _build_block_step(note_trace, model, mask_id):
    """The block step of a block-diffusion model: every row's block of
    B token ids through ``forward_with_cache`` over ``PagedBlockState``
    (the block's K/V written at the row's cursor, all B queries
    attending cursor + B positions), then the published reveal on the
    device: per masked position the argmax token and the log of its
    softmax probability, the most confident masked position of each row
    takes its token. A row with no masked position (a commit forward)
    comes back as it went in; beside the blocks the program returns the
    (b, B) log-confidences it chose by, which only the set-up probe
    reads (``record_blocks``). ``blocks = (last, feed)`` as the decode
    step's ``toks``: ``feed`` is the host's word on a row's block, -1s
    for "the last step's output", which the host may not have read.
    ``hist``: only for a model with expert layers, what their counts
    accumulate in; it comes back as one value more."""
    def serving_block_step(params, buffers, blocks, pools, bt, sl, commit,
                           *hist):
        note_trace()
        last, feed = blocks
        ids = jnp.where(feed >= 0, feed, last)              # (b, B)
        states = cache_entries(
            model, pools,
            lambda k, v, bt_, sl_: PagedBlockState(k, v, bt_, sl_, commit),
            bt, sl)
        # offset=None -> per-row positions from states.seq_lens
        logits, states, counts = _forward_with_cache(
            model, params, buffers, ids, states, None)
        logits = logits.astype(jnp.float32)                 # (b, B, V)
        token = jnp.argmax(logits, axis=-1).astype(ids.dtype)
        log_conf = (jnp.max(logits, axis=-1)
                    - jax.nn.logsumexp(logits, axis=-1))
        masked = ids == mask_id
        pick = jnp.argmax(jnp.where(masked, log_conf, -jnp.inf), axis=1)
        here = masked & (jnp.arange(ids.shape[1])[None, :] == pick[:, None])
        return (jnp.where(here, token, ids), log_conf, states,
                *(h + c for h, c in zip(hist, counts)))

    return jax.jit(serving_block_step, donate_argnums=(3,))


def _spec_filtered_probs(rows, temperature, top_k, top_p):
    """The sampling law as a distribution: temperature scale, static
    top-k, traced top-p nucleus, softmax — the same filter chain
    generation's offline sampler applies, so the engine's rejection
    sampler and ``model.generate(do_sample=True)`` share one law.
    ``rows`` is (..., V) f32 logits; ``top_k`` is static (part of the
    program key), temperature/top_p are traced scalars."""
    from . import _top_k_filter, _top_p_filter
    lg = rows / jnp.maximum(temperature, jnp.float32(1e-6))
    if top_k and top_k > 0:
        lg = _top_k_filter(lg, top_k)
    lg = _top_p_filter(lg, top_p)
    return jax.nn.softmax(lg, axis=-1)


def _build_spec_draft(note_trace, model, gamma, sample, top_k,
                      fspec=None, snap=None):
    """The draft-propose program: γ draft forwards in ONE dispatch — a
    ``lax.scan`` over the draft's paged decode step with the scanned
    seq_lens advancing per iteration, so a speculation round costs two
    dispatches total (this + the verify chunk) instead of γ+1. The
    scan deliberately runs γ+1 iterations: the extra forward writes
    the last proposal's KV, so a fully-accepted round leaves the draft
    cache gap-free and the next round needs no catch-up sync (its
    output is discarded — only the first γ proposals return). In
    sample mode each iteration draws from the FILTERED draft
    distribution and the program also returns the γ q-rows the
    rejection test divides by. With ``fspec`` (the draft qualifies for
    the fused path) each scanned forward runs the per-layer fused
    block-decode kernel instead of the generic functional_call — the
    same fusion the batched decode step uses."""
    from ..jit import functional_call
    if fspec is not None:
        from ..kernels.fused_block_decode import (BlockDecodeWeights,
                                                  _rms,
                                                  fused_block_decode)
        nh, nkv = fspec["num_heads"], fspec["num_kv_heads"]
        theta, eps = fspec["rope_theta"], fspec["epsilon"]

    def serving_spec_draft(params, buffers, tok, pools, bt, sl, *rest):
        note_trace()
        if sample:
            key, temperature, top_p = rest
        else:
            key = jnp.zeros((2,), jnp.uint32)

        def one(carry, _x):
            t, cpools, csl, k = carry
            if fspec is not None:
                allp = {**buffers, **params}
                x = jnp.take(allp[fspec["embed"]], t[:, 0], axis=0)
                nxt_pools = []
                for i, lw in enumerate(fspec["layers"]):
                    w = BlockDecodeWeights(
                        **{f: allp[n] for f, n in lw.items()})
                    kp, vp = cpools[i]
                    x, kp, vp = fused_block_decode(
                        x, w, kp, vp, bt, csl, num_heads=nh,
                        num_kv_heads=nkv, rope_theta=theta,
                        epsilon=eps, snap=snap)
                    nxt_pools.append((kp, vp))
                x = _rms(x, allp[fspec["final_norm"]], eps)
                if fspec["lm_head"]:
                    logits = x @ allp[fspec["lm_head"]]
                else:                           # tied embeddings
                    logits = x @ allp[fspec["embed"]].T
                row = logits[0].astype(jnp.float32)
            else:
                states = [PagedDecodeState(kp, vp, bt, csl)
                          for kp, vp in cpools]
                logits, states = functional_call(
                    model, params, t, states, None,
                    buffers=buffers, method="forward_with_cache")
                row = _val(logits)[0, -1].astype(jnp.float32)
                nxt_pools = [(_val(st.k_pages), _val(st.v_pages))
                             for st in states]
            if sample:
                k, sub = jax.random.split(k)
                q = _spec_filtered_probs(row, temperature, top_k, top_p)
                nxt = jax.random.categorical(
                    sub, jnp.log(q + 1e-30)).astype(jnp.int32)
                out = (nxt, q)
            else:
                nxt = jnp.argmax(row).astype(jnp.int32)
                out = nxt
            return (nxt[None, None], nxt_pools, csl + 1, k), out

        init = (tok, [(k, v) for k, v in pools], sl, key)
        (_, out_pools, _, _), outs = jax.lax.scan(
            one, init, None, length=gamma + 1)
        states = [PagedDecodeState(k, v, bt, sl)
                  for k, v in out_pools]
        if sample:
            props, qrows = outs
            return props[:gamma], qrows[:gamma], states
        return outs[:gamma], states

    return jax.jit(serving_spec_draft, donate_argnums=(3,))


def _build_spec_verify(note_trace, model, sample, top_k):
    """The verify program IS a (1, γ+1) chunk of the r12 chunked-
    prefill machinery: ``PagedChunkState`` statically routes the S>1
    paged attention through the cache-reading path, the chunk writes
    the proposal positions' KV at ``sl .. sl+γ`` (so accepted tokens
    are already cached when the cursor rolls forward), and the
    returned per-position argmax (greedy) or filtered distributions
    (sample) drive host-side acceptance. No bespoke kernel — see
    KERNEL_DECISIONS round 16."""
    from ..jit import functional_call
    from ..kernels.paged_attention import PagedChunkState

    def serving_spec_verify(params, buffers, ids, pools, bt, sl, *rest):
        note_trace()
        states = [PagedChunkState(k, v, bt, sl) for k, v in pools]
        logits, states = functional_call(
            model, params, ids, states, sl[0],
            buffers=buffers, method="forward_with_cache")
        rows = _val(logits)[0].astype(jnp.float32)      # (γ+1, V)
        greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)
        if not sample:
            return greedy, states
        temperature, top_p = rest
        return (greedy,
                _spec_filtered_probs(rows, temperature, top_k, top_p),
                states)

    return jax.jit(serving_spec_verify, donate_argnums=(3,))


def _build_fused_decode(note_trace, spec, snap):
    """The fused decode step: embedding lookup, then ONE fused block
    kernel per layer (kernels/fused_block_decode.py — activations stay
    VMEM-resident across the block), final norm + lm head. Pure function
    of the param/buffer dicts — no model closure, so any same-config
    model shares the compiled program."""
    from ..kernels.fused_block_decode import (BlockDecodeWeights, _rms,
                                              fused_block_decode)

    nh, nkv = spec["num_heads"], spec["num_kv_heads"]
    theta, eps = spec["rope_theta"], spec["epsilon"]

    def serving_decode_fused(params, buffers, toks, pools, bt, sl):
        note_trace()
        toks = _step_tokens(toks)
        allp = {**buffers, **params}
        x = jnp.take(allp[spec["embed"]], toks[:, 0], axis=0)   # (B, H)
        states = []
        for i, lw in enumerate(spec["layers"]):
            w = BlockDecodeWeights(**{f: allp[n] for f, n in lw.items()})
            kp, vp = pools[i]
            x, kp, vp = fused_block_decode(
                x, w, kp, vp, bt, sl, num_heads=nh, num_kv_heads=nkv,
                rope_theta=theta, epsilon=eps, snap=snap)
            states.append(PagedDecodeState(kp, vp, bt, sl))
        x = _rms(x, allp[spec["final_norm"]], eps)
        if spec["lm_head"]:
            logits = x @ allp[spec["lm_head"]]
        else:                                   # tied embeddings
            logits = x @ allp[spec["embed"]].T
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), states

    return jax.jit(serving_decode_fused, donate_argnums=(3,))


def _build_fused_nlayer_decode(note_trace, spec, snap):
    """The N-layer fused decode step (FLAGS_fused_block_layers > 1):
    embedding lookup, then ONE multi-layer fused kernel per LAYER GROUP
    — activations stay VMEM-resident across all N blocks of a group and
    the per-layer weights stream through VMEM double-buffers inside a
    single pallas_call. ``stacked`` is the engine-built tuple of
    per-group MultiBlockDecodeWeights (one per spec["layer_groups"]
    entry, traced args so any same-config model shares the program —
    riding LAST so ``pools`` keeps the decode-step convention of
    position 3, the one donated slot every builder shares)."""
    from ..kernels.fused_block_decode import (_rms,
                                              fused_multi_block_decode)

    nh, nkv = spec["num_heads"], spec["num_kv_heads"]
    theta, eps = spec["rope_theta"], spec["epsilon"]
    groups = spec["layer_groups"]

    def serving_decode_fused_nlayer(params, buffers, toks, pools, bt, sl, stacked):
        note_trace()
        toks = _step_tokens(toks)
        allp = {**buffers, **params}
        x = jnp.take(allp[spec["embed"]], toks[:, 0], axis=0)   # (B, H)
        states = []
        for gi, group in enumerate(groups):
            kps = [pools[i][0] for i in group]
            vps = [pools[i][1] for i in group]
            x, kps, vps = fused_multi_block_decode(
                x, stacked[gi], kps, vps, bt, sl, num_heads=nh,
                num_kv_heads=nkv, rope_theta=theta, epsilon=eps,
                snap=snap)
            states.extend(PagedDecodeState(kp, vp, bt, sl)
                          for kp, vp in zip(kps, vps))
        x = _rms(x, allp[spec["final_norm"]], eps)
        if spec["lm_head"]:
            logits = x @ allp[spec["lm_head"]]
        else:                                   # tied embeddings
            logits = x @ allp[spec["embed"]].T
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), states

    return jax.jit(serving_decode_fused_nlayer, donate_argnums=(3,))


def _build_fused_nlayer_decode_tp(note_trace, spec, snap, mesh, axis, tp):
    """Tensor-parallel fused decode step (r19): the layer-group chain
    runs under ``shard_map`` over the mp axis — stacked weights
    column/row-sharded in the ``shard_block_weights`` layout, pools
    kv-head-sharded — while embedding lookup, the final norm and the lm
    head stay on the replicated residual outside the manual region.
    Exactly two collectives per layer (the row-parallel exits of wo and
    wd) through ``mp_ops._mp_allreduce``; the body holds NO telemetry
    and no host work (meshcheck MSH006/MSH001-clean). Same call
    signature and donation slot as the tp=1 N-layer builder, so the
    dispatch site does not fork."""
    from jax.sharding import PartitionSpec
    from ..kernels.fused_block_decode import (MultiBlockDecodeWeights,
                                              _rms,
                                              fused_multi_block_decode_tp)

    nh, nkv = spec["num_heads"], spec["num_kv_heads"]
    theta, eps = spec["rope_theta"], spec["epsilon"]
    groups = spec["layer_groups"]
    nh_s, nkv_s = nh // tp, nkv // tp
    rep = PartitionSpec()
    pool_spec = PartitionSpec(axis, None, None, None)
    w_spec = MultiBlockDecodeWeights(
        ln1=rep,
        wqkv=PartitionSpec(None, None, axis),
        wo=PartitionSpec(None, axis, None),
        ln2=rep,
        wgu=PartitionSpec(None, None, axis),
        wd=PartitionSpec(None, axis, None))

    def tp_block_chain(x, pools, bt, sl, stacked):
        # per-shard body: local head counts, local weight shards, local
        # kv-head pool partition; the residual x stays replicated
        out_pools = list(pools)
        for gi, group in enumerate(groups):
            kps = [pools[i][0] for i in group]
            vps = [pools[i][1] for i in group]
            x, kps, vps = fused_multi_block_decode_tp(
                x, stacked[gi], kps, vps, bt, sl, num_heads=nh_s,
                num_kv_heads=nkv_s, rope_theta=theta, epsilon=eps,
                axis_name=axis)
            for j, i in enumerate(group):
                out_pools[i] = (kps[j], vps[j])
        return x, out_pools

    sharded = jax.shard_map(
        tp_block_chain, mesh=mesh,
        in_specs=(rep, pool_spec, rep, rep,
                  tuple(w_spec for _ in groups)),
        out_specs=(rep, pool_spec),
        check_vma=False)

    def serving_decode_fused_tp(params, buffers, toks, pools, bt, sl, stacked):
        note_trace()
        toks = _step_tokens(toks)
        allp = {**buffers, **params}
        x = jnp.take(allp[spec["embed"]], toks[:, 0], axis=0)   # (B, H)
        x, out_pools = sharded(x, list(pools), bt, sl, stacked)
        states = [PagedDecodeState(kp, vp, bt, sl)
                  for kp, vp in out_pools]
        x = _rms(x, allp[spec["final_norm"]], eps)
        if spec["lm_head"]:
            logits = x @ allp[spec["lm_head"]]
        else:                                   # tied embeddings
            logits = x @ allp[spec["embed"]].T
        return jnp.argmax(logits.astype(jnp.float32), axis=-1), states

    return jax.jit(serving_decode_fused_tp, donate_argnums=(3,))
