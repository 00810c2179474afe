"""What a sequence keeps on one engine, per layer kind, and how it travels.

A model's ``cache_spec()`` says per layer which kind of state a request
owns there: ``(kv_heads, head_dim)`` is KV **pages** of the shared pool
(:class:`~paddle_tpu.kernels.paged_attention.PagedKVCache`, addressed
through a block table, shareable, grown a page at a time), a
:class:`~paddle_tpu.kernels.recurrent_state.RecurrentSpec` is one **row**
of the recurrent-state store
(:class:`~paddle_tpu.kernels.recurrent_state.RecurrentStateCache`,
indexed by slot, fixed in size). This module is the only code that knows
that there are two kinds. :class:`CacheManager` owns both stores of one
model on one engine: their geometry and placement, a slot's life
(allocate / reset / move / free), the handoff of a seated request to
another engine, the donation handoff around every dispatch, and the
ledger. :func:`cache_entries` is the traced half of the same decision:
which entry a layer of which kind is handed inside a compiled program,
and :meth:`CacheManager.install_caches` is its inverse. A new layer kind
adds a store, an entry and their unpacking here, and nowhere else.

A speculative engine builds a second instance for its draft model.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# unwraps a paddle Tensor and ONLY that (see serving.py's import)
from ..core.tensor import _val
from ..kernels.paged_attention import (HostPage, PagedKVCache,
                                       padded_head_dim)
from ..kernels.recurrent_state import (RecurrentSpec, RecurrentState,
                                       RecurrentStateCache,
                                       is_recurrent_state, recurrent_layout)

__all__ = ["CacheManager", "cache_entries", "has_recurrent_layers",
           "kv_heads", "pool_head_dim"]


def _split_spec(model) -> Tuple[list, List[RecurrentSpec]]:
    """``model.cache_spec()`` by kind: the paged layers' ``(kv_heads,
    head_dim)`` and the recurrent layers' specs. A plain list is "all
    pages"."""
    full = model.cache_spec()
    return ([e for e in full if not isinstance(e, RecurrentSpec)],
            [e for e in full if isinstance(e, RecurrentSpec)])


def has_recurrent_layers(model) -> bool:
    """Does ``model`` keep per-slot recurrent state? What an engine asks
    before any store exists (its refusals)."""
    return recurrent_layout(model.cache_spec()) is not None


def kv_heads(model) -> int:
    """The KV-head count of ``model``'s paged layers: what a
    tensor-parallel pool is partitioned over."""
    return _split_spec(model)[0][0][0]


def pool_head_dim(model, head_dim: int, kv_dtype: str) -> int:
    """The row width of ``model``'s KV pool. A model that only ever runs
    the generic path (``forward_with_cache``, which pads to the pool's
    width) gets the lane-padded width that keeps a plain pool's default
    layout row-major on the TPU (``padded_head_dim``); one that
    publishes a fused block-decode layout keeps its head's own width,
    which those kernels address the pool by, and so does an int8 pool
    (written by the scatter either way)."""
    if (kv_dtype == "native"
            and getattr(model, "block_decode_spec", None) is None):
        return padded_head_dim(head_dim)
    return head_dim


def cache_entries(model, pools, paged_cls, bt, sl, **recurrent):
    """The per-layer cache entries a program hands ``model``, by its
    ``cache_spec()`` (read while tracing). No recurrent layer: every
    layer is paged and ``pools`` is the list of ``(k, v)``. Else
    ``pools`` is ``(pairs, rows)`` (:meth:`CacheManager.take_caches`) and
    the recurrent layers take a ``RecurrentState`` over their rows,
    with the call's ``slot`` / ``n_valid`` / ``live``. ALL of ``pools``
    is donated, so the state-update kernel and the row write-backs work
    in place like the page writes."""
    layout = recurrent_layout(model.cache_spec())
    if layout is None:
        return [paged_cls(k, v, bt, sl) for k, v in pools]
    pairs, rows = (iter(p) for p in pools)
    return [RecurrentState(*next(rows), **recurrent) if rec
            else paged_cls(*next(pairs), bt, sl) for rec in layout]


class CacheManager:
    """One model's per-request layer state on one engine: the page pool
    (``pool``) and, where ``cache_spec()`` names recurrent layers, the
    state store (``state``, else None).

    ``pool_sharding`` / ``tp_degree``: the canonical kv-head
    ``NamedSharding`` of a tensor-parallel engine; a pool whose kv-head
    count ``tp_degree`` does not divide stays replicated (a narrow
    draft model)."""

    def __init__(self, model, *, max_batch: int, page_size: int,
                 num_pages: int, max_seq_len: int, kv_dtype: str, dtype,
                 pool_sharding=None, tp_degree: int = 1):
        paged, recurrent = _split_spec(model)
        # the geometry is kept so that rebuild() allocates FRESH stores
        # of the identical shape (the same compiled programs apply)
        self._pool_geom = dict(
            num_layers=len(paged), num_pages=num_pages, page_size=page_size,
            num_kv_heads=paged[0][0],
            head_dim=pool_head_dim(model, paged[0][1], kv_dtype),
            max_batch=max_batch, max_seq_len=max_seq_len, dtype=dtype,
            reserve_null_page=True, kv_dtype=kv_dtype)
        # one row a slot a recurrent layer, NOT addressed through the
        # block table
        self._state_geom = (dict(specs=recurrent, max_batch=max_batch,
                                 dtype=dtype) if recurrent else None)
        self._sharding = (pool_sharding
                          if paged[0][0] % tp_degree == 0 else None)
        self.rebuild()

    def rebuild(self) -> None:
        """Fresh, empty stores of the identical geometry (replay
        recovery: the donated arrays died with the failed dispatch, and
        every request replays from its prompt, so zeros are right)."""
        self.pool = PagedKVCache(**self._pool_geom)
        if self._sharding is not None:
            # every per-layer pool leaf onto the canonical kv-head
            # sharding (the int8 payload and its per-token-row scale band
            # both lead with the kv-head axis, so one spec shards both).
            # All host bookkeeping — ledger, spill/restore, replay — is
            # kv-head-count-invariant, so it needs no per-shard twin
            self.pool.install_pools(self._canonical(self.pool.take_pools()))
        self.state: Optional[RecurrentStateCache] = (
            RecurrentStateCache(**self._state_geom)
            if self._state_geom else None)

    def _canonical(self, pairs):
        """Re-pin pool pairs to the canonical sharding before they
        (re-)enter the cache: the sharded decode step already returns
        them committed there (free), while prefill/chunk/spec outputs
        carry whatever placement GSPMD inferred and reshard once here —
        so the next decode dispatch always sees one stable input
        sharding and never retraces."""
        if self._sharding is None:
            return pairs
        return [(jax.device_put(k, self._sharding),
                 jax.device_put(v, self._sharding)) for k, v in pairs]

    # ------------------------------------------------------ what it holds
    @property
    def state_rows(self) -> int:
        """Recurrent-state rows a slot owns (0: no recurrent layer):
        what one reset / move / export adds to the ``serving_state_*``
        counters."""
        return 0 if self.state is None else 1

    @property
    def detached(self) -> bool:
        """A donating dispatch holds a store's arrays, or died holding
        them (only :meth:`rebuild` brings those back)."""
        return (bool(self.pool.k_pages) and self.pool.k_pages[0] is None) \
            or (self.state is not None and self.state.detached)

    def ledger(self) -> dict:
        """The pool's ledger (``PagedKVCache.ledger``, fragmentation
        left to its epoch memo) beside the state store's bill: all of it
        is resident whether or not a slot is taken."""
        led = self.pool.ledger(fragmentation=False)
        led["state_bytes"] = 0 if self.state is None else self.state.nbytes
        led["state_bytes_per_slot"] = (
            0 if self.state is None else self.state.bytes_per_slot)
        return led

    # -------------------------------------------------------- a slot's life
    def allocate(self, slot: int, n_tokens: int) -> None:
        """Pages for ``n_tokens`` more tokens of ``slot``'s sequence
        (``PagedKVCache.allocate``: RuntimeError when the pool is
        exhausted, what was popped so far recorded and freeable)."""
        self.pool.allocate(slot, n_tokens)

    def reset(self, slot: int) -> None:
        """Admission: the slot's recurrent rows start from zero (they
        hold what the slot's last request left). Pages need nothing:
        they are addressed by position and overwritten."""
        if self.state is not None:
            self.state.reset(slot)

    def move(self, src: int, dst: int) -> None:
        """Relocate a sequence to the empty slot ``dst`` (the ladder
        compacting): pages never copy (a host-side block-table row
        move), rows are indexed by slot, so those DO move, one device
        row copy a layer."""
        self.pool.move_sequence(src, dst)
        if self.state is not None:
            self.state.move(src, dst)

    def free(self, slot: int) -> None:
        """Return the slot's pages (rows are not freed: the next
        admission resets them)."""
        self.pool.free_sequence(slot)

    # ------------------------------------------------------------- handoff
    def export_slot(self, slot: int):
        """Detach what ``slot``'s sequence has written, as host state:
        ``(pages, seq_len, state)`` — its pages verbatim (``HostPage``,
        int8 payload and scale band included), its KV cursor, and its
        recurrent rows (None for a model that has none): they are as
        much the sequence's written state, and as little recomputed.
        The slot's pages return to the pool."""
        if self.detached:
            raise RuntimeError("harvest_request: pool is detached")
        seq_len = int(self.pool.seq_lens[slot])
        state = None if self.state is None else self.state.export(slot)
        pages: List[HostPage] = []
        for pid in self.pool.sequence_pages(slot):
            hp = self.pool.spill_page(int(pid))
            # the copy leaves with the request — it was never this
            # pool's host-tier resident, so retire it from the census
            self.pool.forget_spilled(hp)
            pages.append(hp)
        self.pool.free_sequence(slot)
        return pages, seq_len, state

    def adopt_slot(self, slot: Optional[int], n_tokens: int,
                   pages: List[HostPage], seq_len: int, state) -> None:
        """Seat what :meth:`export_slot` of another manager detached
        into ``slot`` with a span of ``n_tokens``: the both-or-neither
        check on recurrent state, the page-layout check, allocate, the
        page-count check, the page and row writes, the cursor. Every
        refusal leaves no page allocated. ``slot`` None is the engine
        having no free slot: refused here, after the bundle's own
        checks, where that refusal has always come."""
        if self.detached:
            raise RuntimeError("adopt_request: pool is detached")
        if (state is None) != (self.state is None):
            raise ValueError(
                "adopt_request: the bundle "
                + ("carries no recurrent state but this engine's model "
                   "has recurrent layers" if state is None else
                   "carries recurrent state but this engine's model has "
                   "no recurrent layers")
                + " (the disaggregated pair must serve the same model)")
        if pages and pages[0].nbytes != self.pool.bytes_per_page:
            raise ValueError(
                f"adopt_request: page layout mismatch — bundle pages "
                f"are {pages[0].nbytes} bytes, this pool's are "
                f"{self.pool.bytes_per_page} (layers/kv-heads/page_size/"
                "kv_dtype must agree across the disaggregated pair)")
        if slot is None:
            raise RuntimeError(
                "adopt_request: no free slot (drain or grow max_batch)")
        try:
            self.pool.allocate(slot, n_tokens)
        except RuntimeError:
            # partial allocation is recorded by the pool — return it
            self.pool.free_sequence(slot)
            raise
        held = self.pool.sequence_pages(slot)
        if len(held) < len(pages):
            self.pool.free_sequence(slot)
            raise ValueError(
                f"adopt_request: bundle carries {len(pages)} pages but "
                f"the span only needs {len(held)}")
        for hp, pid in zip(pages, held):
            self.pool.adopt_page(hp, int(pid))
        if state is not None:
            try:
                self.state.import_(slot, state)
            except ValueError:
                self.pool.free_sequence(slot)
                raise
        self.pool.seq_lens[slot] = int(seq_len)

    # ------------------------------------------------------------ dispatch
    # Donation discipline (tracecheck TRC003): the compiled programs
    # donate their pools argument, so the dispatch sites pass
    # ``take_caches()`` — the stores' references are detached BEFORE the
    # buffers are invalidated by donation, and ``install_caches``
    # installs the step's returned arrays. A dispatch that raises leaves
    # the stores explicitly empty (a second take refuses) rather than
    # silently aliasing deleted device buffers.

    def take_caches(self):
        """What a donating serving program is handed as its ``pools``:
        the per-layer ``(k, v)`` pairs, and for a model with recurrent
        layers ``(pairs, [(ssm, conv) a recurrent layer])``. Every store
        is detached until :meth:`install_caches`."""
        pairs = self.pool.take_pools()
        if self.state is None:
            return pairs
        return pairs, self.state.take_arrays()

    def install_caches(self, states) -> None:
        """Take a program's returned per-layer entries apart again (the
        inverse of :func:`cache_entries`) and install each store's
        arrays."""
        if self.state is not None:
            self.state.install_arrays(
                [(_val(st.ssm), _val(st.conv)) for st in states
                 if is_recurrent_state(st)])
            states = [st for st in states if not is_recurrent_state(st)]
        self.pool.install_pools(self._canonical(
            [(_val(st.k_pages), _val(st.v_pages)) for st in states]))

    def slot_args(self, slot: int) -> tuple:
        """The extra argument of a b=1 program over a recurrent model:
        the row of the state store it works on."""
        return () if self.state is None else (jnp.int32(slot),)

    def decode_inputs(self, b: int, live_slots) -> list:
        """The host arrays of a decode step over rung ``b``: the block
        tables, the KV cursors and, only for a recurrent model, the
        rows whose recurrence advances (``live_slots``): an idle or
        mid-prefill row's pages take a garbage write that is
        overwritten later, its STATE must not move. COPIES, not views:
        the engine advances the cursors as soon as the step is
        dispatched, while the transfer may still be reading these."""
        host = [self.pool.block_tables[:b].copy(),
                self.pool.seq_lens[:b].copy()]
        if self.state is not None:
            live = np.zeros((b,), np.int32)
            live[live_slots] = 1
            host.append(live)
        return host
