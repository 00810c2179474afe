"""What a sequence keeps on one engine, per layer kind, and how it travels.

A model's ``cache_spec()`` says per layer which kind of state a request
owns there. There are three kinds:

- ``(kv_heads, head_dim)``: KV **pages** of the shared pool
  (:class:`~paddle_tpu.kernels.paged_attention.PagedKVCache`, addressed
  through a block table, shareable, grown a page at a time and kept for
  the life of the request): a GLOBAL attention layer;
- :class:`~paddle_tpu.kernels.paged_attention.WindowKV`: pages of a
  SECOND pool of the same class, with a block table and a free list of
  its own, for the WINDOW attention layers. A row there holds the pages
  of its last ``window`` positions and of the step being written, and no
  more: before every dispatch the pages that fell wholly out of the
  row's window go back to the free list (their table slots to the null
  page, which the windowed kernels never visit) and the pages the step
  writes are taken. The pool is SIZED so that taking them cannot fail:
  a row holds at most ``min(span pages, ceil((window + step) / page) +
  1)``, and the pool has that bound for every slot, or as many pages as
  the global pool, whose admission then covers both;
- :class:`~paddle_tpu.kernels.recurrent_state.RecurrentSpec`: one **row**
  of the recurrent-state store
  (:class:`~paddle_tpu.kernels.recurrent_state.RecurrentStateCache`,
  indexed by slot, fixed in size).

This module is the only code that knows that there is more than one
kind. :class:`CacheManager` owns the stores of one model on one engine:
their geometry and placement, a slot's life (allocate / reset / move /
free), what a dispatch is handed (block tables, cursors, the donated
arrays), the handoff of a seated request to another engine, and the
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
from ..kernels.paged_attention import (HostPage, PagedKVCache, WindowKV,
                                       padded_head_dim)
from ..kernels.recurrent_state import (RecurrentSpec, RecurrentState,
                                       RecurrentStateCache,
                                       is_recurrent_state, recurrent_layout)

__all__ = ["CacheManager", "cache_entries", "has_recurrent_layers",
           "has_window_layers", "kv_heads", "pool_head_dim"]


def _split_spec(model) -> Tuple[list, List[WindowKV], List[RecurrentSpec]]:
    """``model.cache_spec()`` by kind: the global layers' ``(kv_heads,
    head_dim)``, the window layers' and the recurrent layers' specs. A
    plain list is "all pages, kept whole"."""
    full = model.cache_spec()
    return ([e for e in full
             if not isinstance(e, (WindowKV, RecurrentSpec))],
            [e for e in full if isinstance(e, WindowKV)],
            [e for e in full if isinstance(e, RecurrentSpec)])


def has_recurrent_layers(model) -> bool:
    """Does ``model`` keep per-slot recurrent state? What an engine asks
    before any store exists (its refusals)."""
    return recurrent_layout(model.cache_spec()) is not None


def has_window_layers(model) -> bool:
    """Does ``model`` have window attention layers, whose pages are given
    back as the window slides? Asked like :func:`has_recurrent_layers`."""
    return any(isinstance(e, WindowKV) for e in model.cache_spec())


def kv_heads(model) -> int:
    """The KV-head count of ``model``'s paged layers: what a
    tensor-parallel pool is partitioned over."""
    paged, window, _ = _split_spec(model)
    return (paged or window)[0][0]


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
    ``cache_spec()`` (read while tracing). Every layer global: ``pools``
    is the list of ``(k, v)`` and ``bt`` the block tables. Else
    ``pools`` is one list a store the model has, in the order global
    pairs, window pairs, recurrent rows
    (:meth:`CacheManager.take_caches`); a model with window layers is
    handed ``bt`` as the pair ``(global tables, window tables)``
    (:meth:`CacheManager.tables`), and its window layers' entries are
    over the window pool and its tables; the recurrent layers take a
    ``RecurrentState`` over their rows, with the call's ``slot`` /
    ``n_valid`` / ``live``. ALL of ``pools`` is donated, so the
    state-update kernel and the row write-backs work in place like the
    page writes."""
    spec = model.cache_spec()
    kinds = [WindowKV if isinstance(e, WindowKV)
             else RecurrentSpec if isinstance(e, RecurrentSpec) else tuple
             for e in spec]
    if all(k is tuple for k in kinds):
        return [paged_cls(k, v, bt, sl) for k, v in pools]
    present = [k for k in (tuple, WindowKV, RecurrentSpec) if k in kinds]
    store = {k: iter(p) for k, p in zip(present, pools)}
    tables = {tuple: bt}
    if WindowKV in store:
        tables[tuple], tables[WindowKV] = bt
    return [RecurrentState(*next(store[k]), **recurrent)
            if k is RecurrentSpec
            else paged_cls(*next(store[k]), tables[k], sl) for k in kinds]


class CacheManager:
    """One model's per-request layer state on one engine: the page pool
    of its global layers (``pool``); where ``cache_spec()`` names window
    layers, their pool (``window``, else None); where it names recurrent
    layers, the state store (``state``, else None).

    ``pool_sharding`` / ``tp_degree``: the canonical kv-head
    ``NamedSharding`` of a tensor-parallel engine; a pool whose kv-head
    count ``tp_degree`` does not divide stays replicated (a narrow
    draft model). ``step_tokens``: the most tokens ONE dispatch writes
    into a row (the prefill chunk; 0: a whole prompt, up to
    ``max_seq_len``), which with the window bounds what a window row
    holds."""

    def __init__(self, model, *, max_batch: int, page_size: int,
                 num_pages: int, max_seq_len: int, kv_dtype: str, dtype,
                 pool_sharding=None, tp_degree: int = 1,
                 step_tokens: int = 0):
        paged, window, recurrent = _split_spec(model)
        some = (paged or window)[0]
        # of the paged layers in layer order, which are window layers
        # (install_caches)
        self._is_window = [isinstance(e, WindowKV)
                           for e in model.cache_spec()
                           if not isinstance(e, RecurrentSpec)]
        # the geometry is kept so that rebuild() allocates FRESH stores
        # of the identical shape (the same compiled programs apply)
        self._pool_geom = dict(
            num_layers=len(paged), num_pages=num_pages, page_size=page_size,
            num_kv_heads=some[0],
            head_dim=pool_head_dim(model, some[1], kv_dtype),
            max_batch=max_batch, max_seq_len=max_seq_len, dtype=dtype,
            reserve_null_page=True, kv_dtype=kv_dtype)
        self._window_geom = None
        # pages the window pool took back, over the manager's life
        self.window_pages_released = 0
        if window:
            if not paged:
                raise NotImplementedError(
                    "a model of window layers only: the engine keeps its "
                    "cursors and its admission in the global pool's "
                    "books, which such a model would leave empty")
            if len({tuple(e) for e in window}) != 1 \
                    or tuple(window[0][:2]) != tuple(some[:2]):
                raise NotImplementedError(
                    "window layers of different windows or head "
                    f"geometries in one model: {sorted(set(window))}")
            self.window_len = int(window[0].window)
            # the most pages a window row holds: its window and the step
            # being written, and one more where neither starts on a page
            step = step_tokens or max_seq_len
            self._row_bound = min(
                -(-max_seq_len // page_size),
                -(-(self.window_len + step) // page_size) + 1)
            # every slot at its bound, and never more than the global
            # pool has: either way the rows the global pool admitted
            # find their pages here, so admission prices that pool alone
            self._window_geom = dict(
                self._pool_geom, num_layers=len(window),
                num_pages=min(num_pages, 1 + max_batch * self._row_bound))
        # one row a slot a recurrent layer, NOT addressed through the
        # block table
        self._state_geom = (dict(specs=recurrent, max_batch=max_batch,
                                 dtype=dtype) if recurrent else None)
        self._sharding = (pool_sharding
                          if some[0] % tp_degree == 0 else None)
        self.rebuild()

    def rebuild(self) -> None:
        """Fresh, empty stores of the identical geometry (replay
        recovery: the donated arrays died with the failed dispatch, and
        every request replays from its prompt, so zeros are right)."""
        self.pool = PagedKVCache(**self._pool_geom)
        self.window: Optional[PagedKVCache] = None
        if self._window_geom is not None:
            self.window = PagedKVCache(**self._window_geom)
            # per slot: the tokens its request spans
            self._span = np.zeros((self.pool.block_tables.shape[0],),
                                  np.int64)
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
    def page_budget(self) -> tuple:
        """The page geometry a compiled program is specialised to, part
        of its cache key: ``(num_pages, page_size, max_pages_per_seq)``
        of the pool, and for a model with window layers their pool's
        page count behind them (it follows from the engine's chunk)."""
        pool = self.pool
        budget = (pool.num_pages, pool.page_size, pool.max_pages_per_seq)
        if self.window is not None:
            budget += (self.window.num_pages,)
        return budget

    @property
    def detached(self) -> bool:
        """A donating dispatch holds a store's arrays, or died holding
        them (only :meth:`rebuild` brings those back)."""
        return any(bool(p.k_pages) and p.k_pages[0] is None
                   for p in (self.pool, self.window) if p is not None) \
            or (self.state is not None and self.state.detached)

    def ledger(self) -> dict:
        """The pool's ledger (``PagedKVCache.ledger``, fragmentation
        left to its epoch memo) beside the state store's bill: all of it
        is resident whether or not a slot is taken. A model with window
        layers adds their pool's bill under ``window_*``: the pages and
        bytes in use, a page's bytes, and the pages given back so far."""
        led = self.pool.ledger(fragmentation=False)
        led["state_bytes"] = 0 if self.state is None else self.state.nbytes
        led["state_bytes_per_slot"] = (
            0 if self.state is None else self.state.bytes_per_slot)
        if self.window is not None:
            win = self.window.ledger(fragmentation=False)
            led.update(window_pages_in_use=win["pages_in_use"],
                       window_bytes_in_use=win["bytes_in_use"],
                       window_pages_released=self.window_pages_released)
        return led

    def window_read_tokens(self, slots) -> Optional[int]:
        """The positions a window layer reads for the rows ``slots`` in
        the decode step about to go out, ``min(len + 1, window)`` each;
        None for a model without window layers."""
        if self.window is None:
            return None
        return int(np.minimum(self.pool.seq_lens[list(slots)] + 1,
                              self.window_len).sum())

    def window_read_pairs(self, pos: int, n: int) -> Optional[int]:
        """The query-key pairs a window layer computes for ``n`` queries
        at positions ``pos ..``, ``min(p + 1, window)`` each; None for a
        model without window layers."""
        if self.window is None:
            return None
        return int(np.minimum(np.arange(pos + 1, pos + n + 1),
                              self.window_len).sum())

    # -------------------------------------------------------- a slot's life
    def allocate(self, slot: int, n_tokens: int) -> None:
        """Pages for ``n_tokens`` more tokens of ``slot``'s sequence
        (``PagedKVCache.allocate``: RuntimeError when the pool is
        exhausted, what was popped so far recorded and freeable). The
        window pool only notes the span: its pages are taken a dispatch
        at a time (:meth:`tables`, :meth:`decode_inputs`)."""
        self.pool.allocate(slot, n_tokens)
        if self.window is not None:
            self._span[slot] = int(self.pool.seq_lens[slot]) + int(n_tokens)

    def _slide(self, slot: int, ahead: int) -> None:
        """Before a dispatch that writes ``ahead`` tokens at ``slot``'s
        cursor: the window pages wholly before the first position the
        step's first query sees go back, and the pages the step writes
        are taken (inside the request's span: a padded chunk's tail
        falls on the null page, as in the global pool). The pool has
        every slot's bound (``__init__``), so the ``allocate`` cannot
        run out while every row stays inside its own."""
        win = self.window
        cursor = int(self.pool.seq_lens[slot])
        win.seq_lens[slot] = cursor
        self.window_pages_released += win.release_before(
            slot, cursor + 1 - self.window_len)
        win.allocate(slot, max(0, min(ahead, int(self._span[slot]) - cursor)))
        assert (win._pages_used[slot] - win._pages_first[slot]
                <= self._row_bound), "a window row past its bound"

    def tables(self, slot: int, n_tokens: int):
        """The block tables of ``slot`` alone, for a b=1 program that
        writes ``n_tokens`` at its cursor: an array, or for a model with
        window layers the pair :func:`cache_entries` takes apart."""
        # COPIES, as in :meth:`decode_inputs`: the CPU backend may alias
        # an aligned host view instead of copying it, and a non-final
        # chunk is not waited for, so the program can still be reading
        # its tables when a move, a free or the next slide rewrites the
        # rows
        bt = jnp.asarray(self.pool.block_tables[slot:slot + 1].copy())
        if self.window is None:
            return bt
        self._slide(slot, n_tokens)
        return bt, jnp.asarray(self.window.block_tables[slot:slot + 1].copy())

    def reset(self, slot: int) -> None:
        """Admission: the slot's recurrent rows start from zero (they
        hold what the slot's last request left). Pages need nothing:
        they are addressed by position and overwritten."""
        if self.state is not None:
            self.state.reset(slot)

    def move(self, src: int, dst: int) -> None:
        """Relocate a sequence to the empty slot ``dst`` (the ladder
        compacting): pages never copy (a host-side block-table row
        move, in each pool), rows are indexed by slot, so those DO
        move, one device row copy a layer."""
        self.pool.move_sequence(src, dst)
        if self.window is not None:
            self.window.move_sequence(src, dst)
            self._span[dst], self._span[src] = self._span[src], 0
        if self.state is not None:
            self.state.move(src, dst)

    def free(self, slot: int) -> None:
        """Return the slot's pages, of both pools (rows are not freed:
        the next admission resets them)."""
        self.pool.free_sequence(slot)
        if self.window is not None:
            self.window.free_sequence(slot)
            self._span[slot] = 0

    # ------------------------------------------------------------- handoff
    def _no_window_handoff(self, what: str) -> None:
        if self.window is not None:
            raise NotImplementedError(
                f"{what}: a row of a model with window layers does not "
                "travel with its pages yet (its window pool holds a "
                "moving part of the sequence); hand it over as tokens "
                "(export_requests / inject_request), which replays it")

    def export_slot(self, slot: int):
        """Detach what ``slot``'s sequence has written, as host state:
        ``(pages, seq_len, state)`` — its pages verbatim (``HostPage``,
        int8 payload and scale band included), its KV cursor, and its
        recurrent rows (None for a model that has none): they are as
        much the sequence's written state, and as little recomputed.
        The slot's pages return to the pool."""
        self._no_window_handoff("harvest_request")
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
        self._no_window_handoff("adopt_request")
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
        the per-layer ``(k, v)`` pairs; for a model with more than one
        store, one list a store in the order :func:`cache_entries`
        reads: global pairs, window pairs, ``[(ssm, conv) a recurrent
        layer]``. Every store is detached until
        :meth:`install_caches`."""
        pairs = self.pool.take_pools()
        if self.window is None and self.state is None:
            return pairs
        return ((pairs,)
                + (() if self.window is None
                   else (self.window.take_pools(),))
                + (() if self.state is None
                   else (self.state.take_arrays(),)))

    def install_caches(self, states) -> None:
        """Take a program's returned per-layer entries apart again (the
        inverse of :func:`cache_entries`) and install each store's
        arrays."""
        if self.state is not None:
            self.state.install_arrays(
                [(_val(st.ssm), _val(st.conv)) for st in states
                 if is_recurrent_state(st)])
            states = [st for st in states if not is_recurrent_state(st)]
        if self.window is not None:
            self.window.install_pools(
                [(_val(st.k_pages), _val(st.v_pages))
                 for st, w in zip(states, self._is_window) if w])
            states = [st for st, w in zip(states, self._is_window) if not w]
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
        tables = self.pool.block_tables[:b].copy()
        if self.window is not None:
            # the decoding rows write one token at their cursors (a
            # mid-prefill row's garbage write lands on whatever its
            # window table holds there: its next chunk's page, or the
            # null page)
            for slot in live_slots:
                self._slide(slot, 1)
            tables = (tables, self.window.block_tables[:b].copy())
        host = [tables, self.pool.seq_lens[:b].copy()]
        if self.state is not None:
            live = np.zeros((b,), np.int32)
            live[live_slots] = 1
            host.append(live)
        return host
