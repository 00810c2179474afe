"""Paged KV-cache attention (block tables) for serving.

Reference parity target: the reference's block-attention serving op
``paddle.incubate.nn.functional.block_multihead_attention``
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu) —
the vLLM-style PagedAttention design: the KV cache lives in fixed-size
PAGES drawn from a shared pool, and each sequence owns a block table of
page indices. Sequences grow without reallocation, freed pages recycle
across requests, and HBM holds exactly ceil(len/page) pages per sequence
instead of a max-length ring buffer.

TPU-native pieces:
  - ``paged_attention`` — Pallas decode kernel: a grid step is several
    pages of one batch row with ALL its KV heads; the page index maps
    read a SCALAR-PREFETCHED table made from the block table, so each
    step streams its pages of the pool straight from HBM (no gather
    materialization of a contiguous per-sequence view) and a page past
    a row's length is neither fetched nor computed. Online softmax
    accumulates across pages in VMEM; GQA reads the unexpanded pool at
    Hkv bandwidth (q heads ride the products' sublane dim). With a
    static ``window`` a row reads only the pages that hold its last
    ``window`` positions: the kernel is handed the row's table from the
    first of them on, ``ceil(window / page) + 1`` slots wide, so the
    pages before them cost neither a fetch nor a slot of the grid.
  - ``paged_chunk_attention`` — Pallas chunked-prefill kernel: a grid
    step is a tile of the chunk's query tokens (with all the query heads
    of a KV head) against a key block of several pages fetched the same
    way; a tile walks only the blocks between the first and the last key
    it can see, and masks only those on the edges (``chunk_tiling``).
  - ``paged_attention_xla`` — gather-based reference (CPU tests, and the
    fallback wherever pallas is off). Materializes the gathered view —
    correct, but pays the copy the kernel avoids.
  - ``PagedKVCache`` — the pool + block-table manager (allocate/append/
    free; page reuse through a free list), with device-side page writes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NN, _NT, _dot, _lanes, _softmax_update

_NEG_INF = -1e30
_LANES = 128


def paged_position_ids(s: int, offset, state, dtype: str):
    """Decode position ids for a paged cache entry: a scalar ``offset``
    (lockstep batch) broadcasts; ``offset=None`` gives each row ITS
    written length (continuous batching — slots decode at different
    positions). Shared by every model wired for paged serving."""
    from .. import ops
    from ..core.tensor import Tensor

    base = ops.arange(s, dtype=dtype).unsqueeze(0)
    if offset is not None:
        return base + offset
    sl = state.seq_lens
    if not isinstance(sl, Tensor):
        sl = Tensor(sl, stop_gradient=True)
    return base + sl.astype(dtype).unsqueeze(1)


class PagedDecodeState(NamedTuple):
    """One layer's paged cache as it rides a jitted decode step: the pool
    pair, the block tables, and the per-sequence written-token counts.
    A NamedTuple (= pytree) so it threads through jit/functional_call the
    same way the ring-buffer (k_cache, v_cache) tuples do."""
    k_pages: Any
    v_pages: Any
    block_tables: Any
    seq_lens: Any


class PagedChunkState(NamedTuple):
    """The chunked-prefill twin of :class:`PagedDecodeState`: same pytree
    shape, but its TYPE statically routes S > 1 attention onto the
    cache-READING prefill path — the query chunk lands at positions
    ``seq_lens .. seq_lens+S-1`` and attends to the already-written
    prefix plus itself causally, instead of requiring empty sequences.
    The serving engine's chunk programs trace with this type so one
    compiled program serves every chunk of every prompt; decode (S == 1)
    behaves identically to PagedDecodeState.

    Length contract: the returned state's ``seq_lens`` advance by the
    FULL chunk width S — S is a static shape, so a padded final chunk
    overcounts by its pad tail. The DRIVER owns the true lengths (it
    knows how many fed tokens were real) and must carry them host-side,
    as ``ServingEngine`` does; never feed a padded chunk's returned
    ``seq_lens`` back as ground truth."""
    k_pages: Any
    v_pages: Any
    block_tables: Any
    seq_lens: Any


class PagedBlockState(NamedTuple):
    """The block-diffusion step's twin of :class:`PagedDecodeState`: every
    row of the batch runs a BLOCK of S query tokens at positions
    ``seq_lens .. seq_lens+S-1``. The block's K/V are written there (a
    later forward of the same block overwrites them) and all S queries
    attend every cached token and the whole block, ``seq_lens + S``
    positions: within a block attention is full, so the decode kernel
    serves it with ``S`` times as many query rows a KV head. ``commit``
    (B,) int32 says which rows the step finishes: the returned
    ``seq_lens`` advance by S there and stay elsewhere (a row that is
    still denoising runs its block again from the same cursor).

    ``seq_lens`` are multiples of S and the page size is too, so a
    block never straddles a page."""
    k_pages: Any
    v_pages: Any
    block_tables: Any
    seq_lens: Any
    commit: Any


class WindowKV(NamedTuple):
    """A WINDOW attention layer's entry in a model's ``cache_spec()``
    (a global layer's is the plain ``(kv_heads, head_dim)``): its pages
    live in a pool of their own whose rows hold the last ``window``
    positions and the step being written, and give the pages before
    them back (``generation/cache_manager.py``)."""
    kv_heads: int
    head_dim: int
    window: int


def is_paged_state(entry) -> bool:
    """Static (trace-time) test for any paged-cache state flavor —
    the dispatch models use to route attention onto the paged path."""
    return isinstance(entry, (PagedDecodeState, PagedChunkState,
                              PagedBlockState))


def _block_bits(block: int) -> int:
    """``block - 1`` for a power-of-two block length: a query at ``q``
    sees keys up to ``q | (block - 1)``, the end of its own block (1 is
    plain causal attention)."""
    if block < 1 or block & (block - 1):
        raise ValueError(f"block length must be a power of two, got {block}")
    return block - 1


def _interpret() -> bool:
    from ..flags import is_tpu_backend
    return not is_tpu_backend()


# ------------------------------------------------------- quantized pools
class QuantizedPages(NamedTuple):
    """One pool half stored int8 with per-token f32 amax scales riding
    alongside: ``q`` is the payload, ``scale[h, p, t, 0]`` dequantizes
    token ``t`` of page ``p`` for kv head ``h`` (``q.astype(f32) *
    scale``). Scales are per TOKEN ROW, not per page: quantization is
    then a pure function of each token's own k/v vector, so the pool's
    bits never depend on WRITE ORDER (chunked prefill vs token-at-a-time
    replay) — the property greedy fault-replay's bit-identical contract
    rests on. A NamedTuple (= pytree) so it rides jit/scan/donation like
    a plain pool array; ``shape``/``dtype`` delegate to the payload so
    geometry probes (``k_pages.shape[2]``, ``str(dtype)``) keep working.
    """
    q: Any       # int8 (Hkv, num_pages, page_size, D)
    scale: Any   # f32  (Hkv, num_pages, page_size, 1)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def quantize_kv_rows(x):
    """Symmetric per-row int8 quantization over the trailing (head_dim)
    axis: returns ``(q, scale)`` with ``q*scale`` the dequantized value.
    Deterministic and order-free — the write-time half of the int8 KV
    contract (readers dequantize in-kernel)."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x32 / safe), -127, 127).astype(jnp.int8)
    return q, scale


def _gathered_pool(pages, idx):
    """Gather pool pages by an int32 index array and hand back the f32
    (or storage-dtype) view with batch leading: the XLA twins' common
    gather, dequantizing on the spot for quantized pools so no reader
    ever branches on storage dtype again."""
    if isinstance(pages, QuantizedPages):
        g = jnp.moveaxis(pages.q[:, idx], 1, 0).astype(jnp.float32)
        return g * jnp.moveaxis(pages.scale[:, idx], 1, 0)
    return jnp.moveaxis(pages[:, idx], 1, 0)


# ------------------------------------------------------------ the kernel
# One grid step is ``ppb`` pages of one batch row, all of its KV heads: the
# pool rides in ``ppb`` times over, operand ``i`` fetching page
# ``j * ppb + i`` of the row as a ``(Hkv, 1, page_size, D)`` block, and
# heads are a batch dimension of the two products. A slot whose page lies
# past the row's length is not computed (``pl.when``) and not fetched
# either: its index map repeats the page the slot already holds
# (``_slot_pages``), and Pallas moves no block whose index did not change.
# So a call pays for the pages its rows hold, plus the pipeline's
# bookkeeping of a slot (about 0.15 us on a v5e, live or dead).
# (The pool left in ``pl.ANY`` with hand-made copies bounded by the row's
# own page count — how ``jax.experimental.pallas.ops.tpu.paged_attention``
# does it — would drop that too, but Mosaic slices no copy out of an array
# whose minor dim is under the 128 lanes: a head-width pool of 64 and the
# int8 pools' one-lane scale columns would need a second body.)

# VMEM the page blocks may take (K and V, and a quantized pool's scale
# columns; ``ppb`` of each, double-buffered by the pipeline). v5e has
# 128 MiB of VMEM, of which Mosaic scopes a kernel to 16 MiB unless told
# otherwise; the kernel asks for this plus the products' temporaries.
_PAGE_BLOCK_VMEM_BYTES = 8 << 20
_TEMP_VMEM_BYTES = 8 << 20
# Keys one product spans: a step's pages are multiplied in groups of this
# many keys (pages side by side as one operand), which costs a short row
# at most the masked tail of its last group and saves the long ones a
# product and a softmax update a page. On the chip 256 beat 128 and 64 at
# every length tried (KERNEL_DECISIONS.md "Decode paged attention").
_GROUP_KEYS = 256
# Rows a query tile of the chunk kernel is cut to whole multiples of: the
# sublanes of a packed (bf16) tile.
_ROW_ALIGN = 16


def _pages_per_step(hkv: int, page_size: int, d: int, max_pages: int,
                    pool_itemsize: int, quant: bool) -> Tuple[int, int]:
    """``(ppb, grp)`` from the call's own shapes: the pages one grid step
    holds — the table's width split into the fewest equal steps whose
    page blocks, K and V double-buffered, fit ``_PAGE_BLOCK_VMEM_BYTES``
    — and the pages one product spans (``_GROUP_KEYS`` keys)."""
    # in VMEM a row narrower than the 128 lanes pads up to them
    page_bytes = 2 * hkv * page_size * max(d, _LANES) * pool_itemsize
    if quant:
        # the (page_size, 1) float32 scale column pads to 128 lanes
        page_bytes += 2 * hkv * page_size * _LANES * 4
    fit = max(1, _PAGE_BLOCK_VMEM_BYTES // (2 * page_bytes))
    n_blk = -(-max_pages // fit)
    ppb = -(-max_pages // n_blk)
    return ppb, max(1, min(_GROUP_KEYS // page_size, ppb))


def _parked(pages, live):
    """``pages`` (steps, slots) in grid order with the dead entries
    replaced by what the slot fetched at its last live step — of an
    earlier row, if need be — so that a dead step moves nothing; before
    a slot's first live step it waits on that step's page, so the step
    itself moves nothing either (the parking rule of ``_page_write``).
    Flat, as the index maps read it."""
    pages = jnp.where(live, pages, 0)
    step = jnp.arange(pages.shape[0], dtype=jnp.int32)[:, None]
    prev = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jnp.argmax(live, axis=0).astype(jnp.int32)[None, :]
    held = jnp.where(prev >= 0, prev, first)
    return jnp.take_along_axis(pages, held, axis=0).reshape(-1)


def _slot_pages(bt, n_pages, ppb: int):
    """The pool page each slot of each grid step of the decode kernel
    fetches, ``(B * n_blk * ppb,)`` int32 in grid order: a live slot
    (page ``j * ppb + i`` of a row that holds it) fetches its page, a
    dead one is parked (``_parked``)."""
    b, max_pages = bt.shape
    n_blk = -(-max_pages // ppb)
    bt = jnp.pad(bt, ((0, 0), (0, n_blk * ppb - max_pages)))
    live = (jnp.arange(n_blk * ppb, dtype=jnp.int32)[None, :]
            < n_pages[:, None]).reshape(b * n_blk, ppb)
    return _parked(bt.reshape(b * n_blk, ppb), live)


def _paged_decode_kernel(pg_ref, sl_ref, q_ref, *rest, sm_scale: float,
                         page_size: int, ppb: int, max_pages: int,
                         quant: bool, grp: int, window: Optional[int]):
    # per slot: a K and a V page block, then (quantized pools) their
    # scale columns in the same order
    k_refs, v_refs = rest[:ppb], rest[ppb:2 * ppb]
    rest = rest[2 * ppb:]
    if quant:
        ks_refs, vs_refs = rest[:ppb], rest[ppb:2 * ppb]
        rest = rest[2 * ppb:]
    out_ref, acc_ref, m_ref, l_ref = rest

    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = sl_ref[b]
    n_pages = jnp.clip((seq_len + page_size - 1) // page_size, 1, max_pages)
    q = q_ref[0]                                       # (hkv, rep_pad, d)

    for i in range(0, ppb, grp):
        pg = j * ppb + i

        # a group is computed if the row holds its first page; pages of
        # it past the row's length hold a page fetched earlier (finite,
        # whatever it is) and are masked by position
        @pl.when(pg < n_pages)
        def _accumulate():
            def group(refs):
                pages = [ref[:, 0] for ref in refs[i:i + grp]]
                return (pages[0] if len(pages) == 1
                        else jnp.concatenate(pages, axis=1))
            k, v = group(k_refs), group(v_refs)        # (hkv, keys, d)
            if quant:
                # dequantize in VMEM: (keys, d) * (keys, 1) per head
                k = k.astype(jnp.float32) * group(ks_refs)
                v = v.astype(jnp.float32) * group(vs_refs)
            # operands as they are stored: bf16 x bf16 is exact in
            # float32, so a bf16 pool under a bf16 query needs no upcast
            # (Mosaic takes no float32-precision request on bf16
            # operands: DEFAULT is pinned against a process-wide
            # ``jax_default_matmul_precision``); otherwise float32, at
            # the precision the process asks for, as before
            if q.dtype == k.dtype == jnp.bfloat16:
                qk, kk, precision = q, k, jax.lax.Precision.DEFAULT
            else:
                qk, kk, precision = (q.astype(jnp.float32),
                                     k.astype(jnp.float32), None)
            s = jax.lax.dot_general(
                qk, kk, (((2,), (2,)), ((0,), (0,))), precision=precision,
                preferred_element_type=jnp.float32) * sm_scale
            pos = pg * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)                 # (hkv, rep_pad, keys)
            seen = pos < seq_len
            if window is not None:
                # the query, at seq_len - 1, sees the last ``window``
                # positions (the table starts at the page of the first)
                seen &= pos >= seq_len - window
            s = jnp.where(seen, s, _NEG_INF)

            # the running max and sum are lane-broadcast in their scratch
            m_prev, l_prev = m_ref[:, :, 0:1], l_ref[:, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            m_new = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=2, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            # p stays float32 (three bf16 parts of it in one product
            # were no faster on the chip: the step is not bound here)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # (hkv, rep_pad, d)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        # an idle row (seq_len 0) masked every key: l is 0, emit zeros
        l = l_ref[:, :, 0:1]
        out_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(out_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array,
                    sm_scale: Optional[float] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Single-token decode attention against a paged pool.

    q:            (B, H, D) — one query token per sequence
    k/v_pages:    (Hkv, num_pages, page_size, D) — the shared pool
    block_tables: (B, max_pages) int32 — page i of sequence b is pool page
                  ``block_tables[b, i]`` (entries past the used count are
                  never fetched)
    seq_lens:     (B,) int32 — valid tokens per sequence
    window:       static; the query (at ``seq_lens - 1``) sees only the
                  last ``window`` positions, ``j >= seq_lens - window``.
                  Pages wholly before them are never addressed, so their
                  block-table entries may be anything (a window pool
                  gives them back: :meth:`PagedKVCache.release_before`)
    Returns (B, H, D) in q's dtype.
    """
    h, d = q.shape[1:]
    hkv = k_pages.shape[0]
    if h % hkv:
        raise ValueError(f"query heads {h} not divisible by kv heads {hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return _paged_decode(q, k_pages, v_pages,
                         jnp.asarray(block_tables, jnp.int32),
                         jnp.asarray(seq_lens, jnp.int32),
                         sm_scale=float(sm_scale), interpret=_interpret(),
                         **_window_kw(window))


def _window_kw(window) -> dict:
    """``window`` as a keyword of a jitted kernel wrapper, left out where
    there is none: a call without a window is the call it always was,
    cache key and all."""
    if window is None:
        return {}
    if int(window) < 1:
        raise ValueError(f"window must be a positive length, got {window}")
    return {"window": int(window)}


def _table_from(bt, first_pos, span: int, page_size: int):
    """What a windowed reader is handed: each row's block table from the
    page of its first visible position ``first_pos`` (B,) on, as many
    slots as ``span`` positions can lie on, and the positions that come
    before that page (B,). Lengths and cursors counted less that shift
    address the narrow table as the whole ones address the wide one
    (queries and keys shift alike), and no slot before the window is
    ever addressed."""
    width = min(bt.shape[1], -(-span // page_size) + 1)
    first = jnp.maximum(first_pos, 0) // page_size
    slots = first[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    return (jnp.take_along_axis(bt, jnp.minimum(slots, bt.shape[1] - 1),
                                axis=1), first * page_size)


# jitted like the writers below: the layers of one program share ONE
# trace and ONE Mosaic lowering of the kernel
@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "window"))
def _paged_decode(q, k_pages, v_pages, bt, sl, *, sm_scale, interpret,
                  window=None):
    b, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    rep = h // hkv
    max_pages = bt.shape[1]
    quant = isinstance(k_pages, QuantizedPages)
    if window is not None:
        # the query, at sl - 1, sees from sl - window on: a window's
        # slots a row, whatever the table's width (a slot costs its
        # bookkeeping, live or dead)
        bt, shift = _table_from(bt, sl - window, window, page_size)
        sl, max_pages = sl - shift, bt.shape[1]
    ppb, grp = _pages_per_step(hkv, page_size, d, max_pages,
                               jnp.dtype(k_pages.dtype).itemsize, quant)
    n_blk = -(-max_pages // ppb)
    n_pages = jnp.clip((sl + page_size - 1) // page_size, 1, max_pages)

    # query heads ride the sublanes of their KV head's product; pad them
    # to a whole tile so every block is aligned
    rep_pad = -(-rep // 8) * 8
    qg = q.reshape(b, hkv, rep, d)
    if rep_pad != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))

    def page_spec(i, width):
        return pl.BlockSpec(
            (hkv, 1, page_size, width),
            lambda b_, j, pg_ref, sl_ref: (
                0, pg_ref[(b_ * n_blk + j) * ppb + i], 0, 0))

    q_spec = pl.BlockSpec((1, hkv, rep_pad, d),
                          lambda b_, j, *_: (b_, 0, 0, 0))
    pools = [k_pages, v_pages]
    if quant:
        # per-token scale columns: their own operands, fetched by the
        # same page; the 1-wide lane is the int8-scale contract
        # kernelcheck: disable=KRN001
        pools = [k_pages.q, v_pages.q, k_pages.scale, v_pages.scale]
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                          page_size=page_size, ppb=ppb, max_pages=max_pages,
                          quant=quant, grp=grp, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blk),
            in_specs=[q_spec] + [page_spec(i, x.shape[-1])
                                 for x in pools for i in range(ppb)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, rep_pad, d), jnp.float32),      # acc
                pltpu.VMEM((hkv, rep_pad, _LANES), jnp.float32),  # m
                pltpu.VMEM((hkv, rep_pad, _LANES), jnp.float32),  # l
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PAGE_BLOCK_VMEM_BYTES + _TEMP_VMEM_BYTES),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep_pad, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(_slot_pages(bt, n_pages, ppb), sl, qg,
      *(x for x in pools for _ in range(ppb)))
    return out[:, :, :rep].reshape(b, h, d)


def paged_attention_xla(q, k_pages, v_pages, block_tables, seq_lens,
                        sm_scale=None, window=None):
    """Gather-based reference: materializes each sequence's contiguous
    view (the copy the Pallas kernel avoids), then masked attention
    (under ``window`` over the last ``window`` positions only)."""
    b, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    rep = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    # (Hkv, B, max_pages, page, D) -> (B, Hkv, T, D), dequantized on the
    # gathered (not pool-sized) view for quantized pools
    k = _gathered_pool(k_pages, bt)
    v = _gathered_pool(v_pages, bt)
    t = k.shape[2] * page_size
    k = k.reshape(b, hkv, t, d)
    v = v.reshape(b, hkv, t, d)
    qg = q.reshape(b, hkv, rep, d).astype(jnp.float32)
    s = jnp.einsum("bhrd,bhtd->bhrt", qg, k.astype(jnp.float32)) * sm_scale
    mask = jnp.arange(t)[None, :] < sl[:, None]
    if window is not None:
        mask &= jnp.arange(t)[None, :] >= sl[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrt,bhtd->bhrd", p, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# -------------------------------------- chunk-native prefill attention
# One grid step is a TILE of query tokens against a KEY BLOCK of ``ppb``
# pages (about ``_GROUP_KEYS`` keys, the pages side by side as one
# operand, fetched like the decode kernel's: the pool rides in ``ppb``
# times over and a slot that has nothing to fetch repeats the page it
# holds). A tile is a contiguous run of chunk tokens with all the ``rep``
# query heads of the KV head (row = token * rep + head), so its first and
# last position bound the keys it can see: the grid walks a tile's key
# blocks from the first it sees on, a (tile, block) pair past the last is
# not computed, a pair wholly inside them takes no mask, and only the
# pairs on the causal diagonal, at the window's edge and at the table's
# end pay for one. The statistics are lane-replicated and touched once a
# block (``flash_attention._softmax_update``). Every size comes from the
# call's shapes (``chunk_tiling``); KERNEL_DECISIONS.md "Chunk paged
# attention" has the chip timings that set the rule.

class ChunkTiling(NamedTuple):
    """How ``paged_chunk_attention`` cuts a call of its shapes, and what
    the serving engine's ``serving_chunk_*_tile_pairs`` counters count
    by (``chunk_tile_pairs``)."""
    tile: int          # query tokens a tile
    n_tiles: int       # tiles the (padded) chunk makes
    ppb: int           # pages a key block
    n_blk: int         # key-block steps of the grid: the most a tile sees
    page_size: int
    max_pages: int     # the block table's width
    window: Optional[int]
    block_bits: int    # ``block - 1`` of a block-causal call, else 0

    @property
    def keys(self) -> int:
        return self.ppb * self.page_size


def chunk_tiling(s: int, rep: int, page_size: int, max_pages: int,
                 window: Optional[int] = None, block: int = 1) -> ChunkTiling:
    """The tiling of a chunk call, from its own shapes: ``s`` query
    tokens of ``rep`` query heads a KV head, a table ``max_pages`` wide.

    - a key block is ``_GROUP_KEYS`` keys, so the score tile's lanes are
      full and the statistics are touched once per block;
    - a tile is as many tokens as keep the float32 score temporaries of
      one (tile, block) pair inside ``_TEMP_VMEM_BYTES`` (four copies:
      the scores, the mask, ``p`` and the ``exp``'s argument), the chunk
      cut into the fewest equal tiles of whole sublane tiles of rows.

    A grid step holds ONE KV head, whatever their number: several a step
    where a head's whole chunk is under a tile (gpt3-345m: eight) were
    10-24 us a call faster alone and did not separate end to end
    (KERNEL_DECISIONS.md "Chunk paged attention")."""
    ppb = max(1, min(_GROUP_KEYS // page_size, max_pages))
    keys = ppb * page_size
    rows = max(_ROW_ALIGN, _TEMP_VMEM_BYTES // (4 * 4 * keys))
    align = _ROW_ALIGN // math.gcd(_ROW_ALIGN, rep)
    n_tiles = -(-s // max(align, rows // rep // align * align))
    tile = -(-s // (n_tiles * align)) * align
    n_blk = -(-max_pages // ppb)
    if window is not None:
        # a tile's first query sees ``window - 1`` positions back, its
        # last sits ``tile - 1`` further on
        n_blk = min(n_blk, -(-(window + tile - 1) // keys) + 1)
    return ChunkTiling(tile=tile, n_tiles=n_tiles, ppb=ppb,
                       n_blk=n_blk, page_size=page_size, max_pages=max_pages,
                       window=None if window is None else int(window),
                       block_bits=_block_bits(block))


def _chunk_tile_keys(tl: ChunkTiling, start, xp=np):
    """``(lo, hi)``: the first and the last key position each tile of a
    chunk at cursor ``start`` (B,) can see, ``(B, n_tiles)`` each.
    Causal: nothing after the tile's last query (block-causal: the end
    of its block), nothing past the table (a padded final chunk may
    point there); windowed: nothing before the first query's window.
    ``xp`` is numpy on the host and ``jax.numpy`` in the kernel's
    wrapper: one rule for the kernel and for the counter."""
    p0 = start[:, None] + xp.arange(tl.n_tiles, dtype=start.dtype)[None, :] \
        * tl.tile
    hi = xp.minimum((p0 + tl.tile - 1) | tl.block_bits,
                    tl.max_pages * tl.page_size - 1)
    lo = xp.zeros_like(p0) if tl.window is None \
        else xp.maximum(p0 + 1 - tl.window, 0)
    # a tile that lies past the table still takes the table's last block
    return xp.minimum(lo, hi), hi


def chunk_tile_pairs(tl: ChunkTiling, start: int) -> int:
    """Query-key pairs (a query token against a key, per KV head and
    query head one) of the (tile, key block) pairs the kernel COMPUTES
    for a chunk at cursor ``start``, masked pairs and pad rows included:
    what ``serving_chunk_*_tile_pairs`` add a chunk."""
    lo, hi = _chunk_tile_keys(tl, np.full((1,), start, np.int64))
    return int((hi // tl.keys + 1 - lo // tl.keys).sum()) * tl.tile * tl.keys


def _chunk_slots(tl: ChunkTiling, bt, start):
    """What the kernel's index maps and body read: the pool page of
    every slot of every grid step, ``(B * n_tiles * n_blk * ppb,)`` in
    grid order, and per tile its first key block and the block after its
    last (``(B * n_tiles,)`` each). A slot is live where its page holds
    a key some query of the tile can see."""
    lo, hi = _chunk_tile_keys(tl, start, jnp)
    idx = ((lo // tl.keys)[:, :, None, None]
           + jnp.arange(tl.n_blk, dtype=jnp.int32)[None, None, :, None]) \
        * tl.ppb + jnp.arange(tl.ppb, dtype=jnp.int32)[None, None, None, :]
    live = ((idx >= (lo // tl.page_size)[:, :, None, None])
            & (idx <= (hi // tl.page_size)[:, :, None, None]))
    idx = jnp.minimum(idx, tl.max_pages - 1).reshape(bt.shape[0], -1)
    pages = jnp.take_along_axis(bt, idx, axis=1)
    pg = _parked(pages.reshape(-1, tl.ppb), live.reshape(-1, tl.ppb))
    return pg, (lo // tl.keys).reshape(-1), (hi // tl.keys + 1).reshape(-1)


def _paged_chunk_kernel(pg_ref, lo_ref, hi_ref, st_ref, q_ref, *rest,
                        sm_scale: float, tl: ChunkTiling, rep: int,
                        quant: bool):
    # per slot: a K and a V page block, then (quantized pools) their
    # scale columns in the same order
    ppb = tl.ppb
    k_refs, v_refs = rest[:ppb], rest[ppb:2 * ppb]
    rest = rest[2 * ppb:]
    if quant:
        ks_refs, vs_refs = rest[:ppb], rest[ppb:2 * ppb]
        rest = rest[2 * ppb:]
    out_ref, acc_ref, m_ref, l_ref = rest

    b, t, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    keys, d = tl.keys, acc_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    tile = b * tl.n_tiles + t
    blk = lo_ref[tile] + j
    k_lo = blk * keys
    p0 = st_ref[b] + t * tl.tile                       # the first query's

    def block_of(refs):
        pages = [ref[0, 0] for ref in refs]
        return pages[0] if ppb == 1 else jnp.concatenate(pages, axis=0)

    def pair(masked):
        """The step's tile against its key block."""
        k, v = block_of(k_refs), block_of(v_refs)         # (keys, d)
        if quant:
            # dequantize in VMEM: (keys, d) * (keys, 1)
            k = k.astype(jnp.float32) * block_of(ks_refs)
            v = v.astype(jnp.float32) * block_of(vs_refs)
        # operands as stored: bf16 x bf16 is exact in float32 (``_dot``)
        s = _dot(q_ref[0, 0], k, _NT) * sm_scale          # (rows, keys)
        if masked:
            # row r of the tile holds query head r % rep of token
            # r // rep, at position p0 + r // rep: it sees every key up
            # to itself (block-causal: up to the end of its block) and,
            # windowed, none at ``window`` or more behind it
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            tok = (row >> (rep.bit_length() - 1) if rep & (rep - 1) == 0
                   else jax.lax.div(row, rep))
            q_pos = p0 + tok
            kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = kv_pos <= (q_pos | tl.block_bits)
            if tl.window is not None:
                seen &= kv_pos > q_pos - tl.window
            s = jnp.where(seen, s, _NEG_INF)
        m_ref[...], l_ref[...], p, alpha = _softmax_update(
            s, m_ref[...], l_ref[...])
        # p stays float32, as in the decode kernel
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + _dot(p, v, _NN)

    live = blk < hi_ref[tile]
    # no mask where every query of the tile sees every key of the block:
    # the block ends by the first query's last key and, windowed, starts
    # inside the last query's window
    whole = k_lo + keys - 1 <= (p0 | tl.block_bits)
    if tl.window is not None:
        whole &= k_lo > p0 + tl.tile - 1 - tl.window
    pl.when(live & whole)(functools.partial(pair, False))
    pl.when(live & jnp.logical_not(whole))(functools.partial(pair, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _emit():
        # a row that saw nothing: l is 0, emit zeros
        l = _lanes(l_ref[...], d)
        out_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                         ).astype(out_ref.dtype)


def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          start: jax.Array,
                          sm_scale: Optional[float] = None,
                          block: int = 1,
                          window: Optional[int] = None) -> jax.Array:
    """Chunked-prefill attention read straight through the block table —
    the copy-free replacement for ``gather_paged_view`` +
    ``cached_attention`` on the chunk hot path (the r12 leftover).

    The S-token query chunk sits at absolute positions ``start ..
    start+S-1`` and attends causally to the pool's already-written
    prefix PLUS its own tokens, which the caller must have written
    (``write_paged_prompt_at``) before calling — write-then-attend, the
    same ordering the gather path used. A grid step is a tile of query
    tokens against a key block of several pool pages (block-table page
    indices scalar-prefetched, :func:`chunk_tiling`), online softmax
    across the blocks a tile can see; nothing ever materializes the
    ``(B, T, Hkv, D)`` per-sequence view.

    q:     (B, S, H, D) — the chunk's queries
    start: (B,) int32   — written length BEFORE this chunk (the cursor)
    block: 1 is causal; a power of two B is BLOCK-causal (block
           diffusion): a query sees every key up to the end of its own
           block of B positions, ``k <= q | (B - 1)``. ``start`` and S
           are then multiples of B, so the chunk holds whole blocks.
    window: static; query ``i`` of the chunk also sees no key before
           ``start + i + 1 - window`` (``q - k < window``). Pages wholly
           before the first query's window are never addressed, so their
           block-table entries may be anything (causal only).
    Returns (B, S, H, D) in q's dtype. Rows past the real prompt tail
    (final-chunk padding) emit garbage the caller discards.
    """
    h, d = q.shape[2:]
    hkv = k_pages.shape[0]
    if h % hkv:
        raise ValueError(f"query heads {h} not divisible by kv heads {hkv}")
    if window is not None and block != 1:
        raise ValueError("a window is a positive length over the causal "
                         f"mask; got window={window}, block={block}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return _paged_chunk(q, k_pages, v_pages,
                        jnp.asarray(block_tables, jnp.int32),
                        jnp.asarray(start, jnp.int32),
                        sm_scale=float(sm_scale), block=int(block),
                        interpret=_interpret(), **_window_kw(window))


# jitted like ``_paged_decode``: one trace and one Mosaic lowering for
# the layers of a program, the slot table built on the device inside it
@functools.partial(jax.jit, static_argnames=("sm_scale", "block",
                                             "interpret", "window"))
def _paged_chunk(q, k_pages, v_pages, bt, st, *, sm_scale, block, interpret,
                 window=None):
    b, s, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    rep = h // hkv
    quant = isinstance(k_pages, QuantizedPages)
    tl = chunk_tiling(s, rep, page_size, bt.shape[1], window=window,
                      block=block)
    rows, s_pad = tl.tile * rep, tl.n_tiles * tl.tile

    # (B, S, H, D) -> (B, Hkv, S * rep, D): row = token * rep + rep head,
    # so a run of rows is a run of tokens with all their heads
    qg = q.reshape(b, s, hkv, rep, d).transpose(0, 2, 1, 3, 4)
    if s_pad != s:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    qg = qg.reshape(b, hkv, s_pad * rep, d)

    def page_spec(i, width):
        return pl.BlockSpec(
            (1, 1, page_size, width),
            lambda b_, h_, t, j, pg_ref, *_: (
                h_, pg_ref[((b_ * tl.n_tiles + t) * tl.n_blk + j) * tl.ppb
                           + i], 0, 0))

    q_spec = pl.BlockSpec((1, 1, rows, d),
                          lambda b_, h_, t, j, *_: (b_, h_, t, 0))
    pools = [k_pages, v_pages]
    if quant:
        # per-token int8 scale rows: 1-wide lane by contract
        # kernelcheck: disable=KRN001
        pools = [k_pages.q, v_pages.q, k_pages.scale, v_pages.scale]

    # what a step keeps in VMEM: the query and output blocks
    # (double-buffered), the accumulator and the two lane-wide
    # statistics, the page blocks (double-buffered, rows under 128 lanes
    # padded to them) and the score temporaries. Past Mosaic's default
    # scope of 16 MiB the call says what it needs (v5e has 128 MiB); a
    # call under it passes nothing
    vmem = (rows * (4 * d * q.dtype.itemsize + (d + 2 * _LANES) * 4)
            + sum(4 * tl.ppb * page_size
                  * max(x.shape[-1], _LANES) * x.dtype.itemsize
                  for x in pools)
            + 4 * rows * tl.keys * 4)
    call_kw = {}
    if vmem > (12 << 20):
        call_kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20))

    out = pl.pallas_call(
        functools.partial(_paged_chunk_kernel, sm_scale=sm_scale, tl=tl,
                          rep=rep, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hkv, tl.n_tiles, tl.n_blk),
            in_specs=[q_spec] + [page_spec(i, x.shape[-1])
                                 for x in pools for i in range(tl.ppb)],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, d), jnp.float32),         # acc
                pltpu.VMEM((rows, _LANES), jnp.float32),    # m
                pltpu.VMEM((rows, _LANES), jnp.float32),    # l
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
        name="paged_chunk_attention",
        **call_kw,
    )(*_chunk_slots(tl, bt, st), st, qg,
      *(x for x in pools for _ in range(tl.ppb)))
    out = out.reshape(b, hkv, s_pad, rep, d)[:, :, :s]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)


# XLA-twin page grouping: pages per fori_loop step are batched so each
# iteration runs one ~GROUP_KEYS-wide matmul instead of max_pages tiny
# page-wide ones (64 sequential dispatches of 8-key dots halve CPU
# prefill throughput). The live workspace stays a FIXED-size page-group
# block — O(GROUP_KEYS), independent of sequence length — so the
# copy-free contract (never the (B, T, Hkv, D) gathered view) holds.
_CHUNK_GROUP_KEYS = 128


def paged_chunk_attention_xla(q, k_pages, v_pages, block_tables, start,
                              sm_scale=None, block: int = 1, window=None):
    """Copy-free XLA twin of :func:`paged_chunk_attention` (CPU tests,
    and the fallback wherever pallas is off): ``lax.fori_loop`` over
    page GROUPS with online softmax, so the live workspace is one
    ``(B, Hkv, ~_CHUNK_GROUP_KEYS, D)`` page-group block — fixed-size,
    O(1) in sequence length — instead of the gathered ``(B, T, Hkv, D)``
    view the old chunk path materialized. Pages past a sequence's
    written count are read (their block-table entries are 0 by contract)
    but fully masked by position."""
    b, s, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    if h % hkv:
        raise ValueError(f"query heads {h} not divisible by kv heads {hkv}")
    rep = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    bt = jnp.asarray(block_tables, jnp.int32)
    st = jnp.asarray(start, jnp.int32)
    max_pages = bt.shape[1]
    grp = min(max_pages, max(1, _CHUNK_GROUP_KEYS // page_size))
    n_groups = -(-max_pages // grp)
    if n_groups * grp != max_pages:
        # pad with page 0: its kv_pos >= max_pages*page_size > any q_pos,
        # so the position mask kills every padded lane
        bt = jnp.pad(bt, ((0, 0), (0, n_groups * grp - max_pages)))
    qg = (q.astype(jnp.float32) * sm_scale).transpose(0, 2, 1, 3)
    qg = qg.reshape(b, hkv, rep, s, d)
    q_pos = st[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # (B, S)
    q_pos = q_pos | _block_bits(block)      # block-causal: the block's end
    if window is not None and block != 1:
        raise ValueError("a window is over the causal mask (block=1)")

    def body(j, carry):
        acc, m, l = carry
        pages = jax.lax.dynamic_slice_in_dim(bt, j * grp, grp, 1)  # (B, G)
        kb = _gathered_pool(k_pages, pages).astype(jnp.float32)
        vb = _gathered_pool(v_pages, pages).astype(jnp.float32)
        kb = kb.reshape(b, hkv, grp * page_size, d)
        vb = vb.reshape(b, hkv, grp * page_size, d)
        sc = jnp.einsum("bhrsd,bhpd->bhrsp", qg, kb)
        kv_pos = (j * grp * page_size
                  + jnp.arange(grp * page_size, dtype=jnp.int32))
        vis = kv_pos[None, None, :] <= q_pos[:, :, None]           # (B,S,Gp)
        if window is not None:
            vis &= kv_pos[None, None, :] > q_pos[:, :, None] - window
        sc = jnp.where(vis[:, None, None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        m_new = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhrsp,bhpd->bhrsd",
                                                  p, vb)
        return acc, m_new, l

    acc = jnp.zeros((b, hkv, rep, s, d), jnp.float32)
    m = jnp.full((b, hkv, rep, s), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, rep, s), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_groups, body, (acc, m, l))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, s, h, d).astype(q.dtype)


# ------------------------------------------------------- pool management
# A pool is written in the layout its readers stream it in. The obvious
# write, ``pool.at[:, page, off].set(...)``, IS done in place under
# donation, but XLA's TPU layout assignment gives a scatter over dims 1
# and 2 the layout {3,0,2,1} (scattered dims major), while the Pallas
# readers are row-major {3,2,1,0}: every program that scattered into a
# pool transposed the whole pool into the scatter's layout and on into
# the readers' (and, where the parameter's own layout was a third one,
# back into that: ``padded_head_dim``) — 83% of a gpt3-345m decode
# step's device time. ``with_layout_constraint`` on the scatter's result
# and a ``dynamic_update_slice`` loop both kept the copies. A Pallas
# call is opaque to layout assignment, so on the TPU plain pools are
# written by one aliased read-modify-write-by-page kernel; the scatter
# (``*_xla``) stays as the reference everywhere else (CPU tier-1,
# ``use_pallas`` off) and for ``QuantizedPages``.

def _page_write_kernel(blk_ref, lo_ref, hi_ref, kn_ref, vn_ref, kin_ref,
                       vin_ref, kout_ref, vout_ref, *, n_pg: int):
    """One grid step = one pool page of one batch row: rows ``[lo, hi)``
    of the page take the new tokens, the rest keep the pool's bits.

    The pools alias input to output, and Pallas neither re-fetches an
    input block nor writes an output block back while consecutive steps
    stay on the same block — so a step that selected from the (stale)
    input would undo the step before it. The output block is therefore
    the accumulator: it is seeded from the input on the first step of a
    run of equal blocks and only ever updated in place after that
    (``lo >= hi`` updates nothing: how the wrapper parks a step that has
    nothing to write on the block of the step before it)."""
    i = pl.program_id(0) * n_pg + pl.program_id(1)
    fresh = jnp.logical_or(
        i == 0, blk_ref[i] != blk_ref[jnp.maximum(i - 1, 0)])
    lo, hi = lo_ref[i], hi_ref[i]

    def one_pool(new_ref, pin_ref, pout_ref):
        @pl.when(fresh)
        def _seed():
            pout_ref[...] = pin_ref[...]

        @pl.when(hi > lo)
        def _write():
            page = pout_ref[:, 0]                    # (Hkv, page_size, D)
            row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
            # decode hands one token (Hkv, 1, D), broadcast over the
            # page's rows; a prompt hands the page's own rows
            new = jnp.broadcast_to(new_ref[0], page.shape)
            pout_ref[:, 0] = jnp.where((row >= lo) & (row < hi), new, page)

    one_pool(kn_ref, kin_ref, kout_ref)
    one_pool(vn_ref, vin_ref, vout_ref)


def _page_write(k_pages, v_pages, k_new, v_new, pages, lo, hi, *, name,
                interpret):
    """Run :func:`_page_write_kernel` over a ``(B, n_pg)`` grid.
    ``pages``/``lo``/``hi`` are (B, n_pg) int32: the pool page each step
    works on and the half-open row range it writes there (``lo >= hi``:
    nothing; such a step — and one whose page lies outside the pool —
    is parked on the nearest earlier writing step's page, so it moves no
    block and can never sit between two steps of a page that is written
    for real). ``k_new``/``v_new``: (B, Hkv, 1, D) for one token a row,
    else (B, Hkv, n_pg * page_size, D) with row ``r`` of step ``j`` at
    ``j * page_size + r``."""
    hkv, num_pages, page_size, d = k_pages.shape
    b, n_pg = pages.shape
    rows = k_new.shape[2] // n_pg
    pages, lo, hi = (x.reshape(-1) for x in (pages, lo, hi))
    # an out-of-pool page never reaches an index map (a DMA past the
    # pool faults the chip where the scatter silently dropped the write)
    writes = (hi > lo) & (pages >= 0) & (pages < num_pages)
    step = jnp.arange(b * n_pg, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(writes, step, -1))
    # before the first writing step: ITS page (seeded there, untouched
    # until it arrives); no writing step at all: page 0, copied onto itself
    # (argmax of all-False is step 0, whose masked page is 0)
    park = jnp.where(prev >= 0, prev, jnp.argmax(writes).astype(jnp.int32))
    blk = jnp.where(writes, pages, 0)[park]
    hi = jnp.where(writes, hi, lo)

    new_spec = pl.BlockSpec((1, hkv, rows, d),
                            lambda b_, j, *_: (b_, 0, j, 0))
    pool_spec = pl.BlockSpec(
        (hkv, 1, page_size, d),
        lambda b_, j, blk_ref, *_: (0, blk_ref[b_ * n_pg + j], 0, 0))
    pool_shape = jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype)
    return pl.pallas_call(
        functools.partial(_page_write_kernel, n_pg=n_pg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_pg),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[pool_shape, pool_shape],
        # operands count the scalar-prefetch arguments: 5, 6 = the pools
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name=name,
    )(blk, lo, hi, k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
      k_pages, v_pages)


def _table_pages(bt, page_idx):
    """Pool page of each ``page_idx`` (B, n) through the block tables;
    a position past the table's width gets the out-of-pool page -1."""
    width = bt.shape[1]
    pages = jnp.take_along_axis(bt, jnp.clip(page_idx, 0, width - 1), axis=1)
    return jnp.where(page_idx < width, pages, -1)


# The two Pallas writers are jitted: the layers of one program call them
# with the same shapes, so they share ONE trace and ONE Mosaic lowering
# where each layer paid its own (most of a second a program of set-up at
# 24 layers). ``interpret`` is static because a trace is cached by its
# arguments, not by the backend a test may have steered it to.

def write_paged_kv_pallas(k_pages, v_pages, k_new, v_new, block_tables,
                          positions):
    """Pallas twin of :func:`write_paged_kv_xla` for plain pools:
    grid over batch rows, each row rewrites the one page its token lands
    on. Inactive rows (all-zero block tables) write the engine's null
    page 0 one after another, which the kernel's accumulation makes as
    well-defined as their scatter was."""
    return _kv_write(k_pages, v_pages, k_new, v_new,
                     jnp.asarray(block_tables, jnp.int32),
                     jnp.asarray(positions, jnp.int32),
                     interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write(k_pages, v_pages, k_new, v_new, bt, pos, *, interpret):
    page_size = k_pages.shape[2]
    off = (pos % page_size)[:, None]
    return _page_write(
        k_pages, v_pages, k_new[:, :, None], v_new[:, :, None],
        _table_pages(bt, (pos // page_size)[:, None]), off, off + 1,
        name="paged_kv_write", interpret=interpret)


def write_paged_prompt_at_pallas(k_pages, v_pages, k_new, v_new,
                                 block_tables, start):
    """Pallas twin of :func:`write_paged_prompt_at_xla` for plain pools:
    grid over batch rows x the ``ceil((S - 1) / page) + 1`` pages S
    tokens can touch from an arbitrary ``start``. The tokens are staged
    page-aligned first (token ``t`` at row ``start % page + t`` — a small
    XLA gather; in-kernel the shift would be an unaligned dynamic sublane
    slice of a packed dtype), so every step selects whole aligned rows.
    Pages past the block table's width are dropped, as the scatter's
    ``mode="drop"`` drops them."""
    return _prompt_write(k_pages, v_pages, k_new, v_new,
                         jnp.asarray(block_tables, jnp.int32),
                         jnp.asarray(start, jnp.int32),
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _prompt_write(k_pages, v_pages, k_new, v_new, bt, st, *, interpret):
    b, s, hkv, d = k_new.shape
    page_size = k_pages.shape[2]
    n_pg = (s + page_size - 2) // page_size + 1
    first = st // page_size                                   # (B,)
    page_idx = first[:, None] + jnp.arange(n_pg, dtype=jnp.int32)[None, :]
    base = page_idx * page_size                               # (B, n_pg)
    lo = jnp.clip(st[:, None] - base, 0, page_size)
    hi = jnp.clip(st[:, None] + s - base, 0, page_size)
    # staged row i of batch row b holds token i - start_b % page
    tok = (jnp.arange(n_pg * page_size, dtype=jnp.int32)[None, :]
           - (st % page_size)[:, None])                       # (B, L)
    tok = jnp.clip(tok, 0, s - 1)[:, None, :, None]

    def staged(x):
        return jnp.take_along_axis(jnp.swapaxes(x, 1, 2), tok, axis=2,
                                   mode="promise_in_bounds")

    return _page_write(k_pages, v_pages, staged(k_new), staged(v_new),
                       _table_pages(bt, page_idx), lo, hi,
                       name="paged_prompt_write", interpret=interpret)


# its jnp twin is write_paged_prompt_at_xla: a block is a short prompt at
# an offset  # kernelcheck: disable=KRN006
def write_paged_block(k_pages, v_pages, k_new, v_new, block_tables, start):
    """The block step's write: k_new/v_new (B, S, Hkv, D) land at
    positions [start, start+S) of each row, S a power of two that
    divides the page size and ``start`` (B,) a multiple of it, so each
    row writes ONE page. On the TPU (plain pools) the page-write kernel
    over a one-page grid, the block tiled over the page's rows (row
    ``r`` takes token ``r % S``, which is the token it holds where it
    lies in ``[start % page, start % page + S)``); elsewhere, and for
    ``QuantizedPages`` pools, the scatter: a block is a short prompt at
    an offset."""
    if not _write_kernel_applies(k_pages):
        return write_paged_prompt_at_xla(k_pages, v_pages, k_new, v_new,
                                         block_tables, start)
    return _block_write(k_pages, v_pages, k_new, v_new,
                        jnp.asarray(block_tables, jnp.int32),
                        jnp.asarray(start, jnp.int32),
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _block_write(k_pages, v_pages, k_new, v_new, bt, st, *, interpret):
    b, s, hkv, d = k_new.shape
    page_size = k_pages.shape[2]
    if page_size % s:
        raise ValueError(f"block of {s} does not divide the page size "
                         f"{page_size}")
    off = (st % page_size)[:, None]

    def tiled(x):
        return jnp.tile(jnp.swapaxes(x, 1, 2), (1, 1, page_size // s, 1))

    return _page_write(k_pages, v_pages, tiled(k_new), tiled(v_new),
                       _table_pages(bt, (st // page_size)[:, None]),
                       off, off + s, name="paged_block_write",
                       interpret=interpret)


def padded_head_dim(head_dim: int) -> int:
    """The row width to ALLOCATE a plain pool with for heads of
    ``head_dim``: on the TPU a head narrower than the 128 lanes pads up
    to them, everywhere else it is ``head_dim``.

    Why: the layout an array crosses a ``jit`` boundary in is its
    shape's default, and for ``(Hkv, pages, page_size, 64)`` the TPU
    runtime's default is PAGE-MINOR (``major_to_minor=(0, 2, 3, 1)``,
    1.25x the bytes; row-major would leave half of every 128-lane row
    empty, 2x). One page is then one lane of every tile, no page-indexed
    kernel can address it, and XLA transposed the whole pool into
    row-major in front of the first kernel of every program and back
    behind the last — whatever wrote the page. (Pinning the layout on
    the ``jit`` instead works until the executable comes back from the
    persistent compile cache: jax 0.9 loads it expecting the default.)
    A 128-wide row has the row-major default, so a pool allocated that
    wide is never copied; it costs the lane padding, resident, which the
    row-major copies cost in passing. The caller pads q/k/v to the
    pool's width and slices the output
    (``nn.functional.paged_scaled_dot_product_attention``); code that
    addresses the pool by the head's own width (the fused block-decode
    kernels) must be given an unpadded pool."""
    from ..flags import is_tpu_backend
    return max(head_dim, _LANES) if is_tpu_backend() else head_dim


def _write_kernel_applies(k_pages) -> bool:
    """The page-write kernels run where the attention kernels run (a TPU
    backend with ``FLAGS_use_pallas``) on plain pools; ``QuantizedPages``
    (int8 payload + a 1-lane scale array) keep the scatter."""
    from ..flags import is_tpu_backend, snapshot
    return (not isinstance(k_pages, QuantizedPages)
            and snapshot(("use_pallas",)).use_pallas and is_tpu_backend())


def write_paged_kv(k_pages, v_pages, k_new, v_new, block_tables, positions):
    """Write one token per sequence into the pool at absolute sequence
    ``positions`` ((B,) int32). k_new/v_new: (B, Hkv, D). Returns the
    updated pools: the page-write kernel on the TPU (plain pools), the
    scatter elsewhere."""
    write = (write_paged_kv_pallas if _write_kernel_applies(k_pages)
             else write_paged_kv_xla)
    return write(k_pages, v_pages, k_new, v_new, block_tables, positions)


def write_paged_kv_xla(k_pages, v_pages, k_new, v_new, block_tables,
                       positions):
    """:func:`write_paged_kv` as a device-side scatter via the block
    tables: the reference (CPU tests, ``use_pallas`` off) and the write
    of ``QuantizedPages`` pools."""
    bt = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    b = pos.shape[0]
    page_size = k_pages.shape[2]
    page_of = jnp.take_along_axis(bt, (pos // page_size)[:, None],
                                  axis=1)[:, 0]            # (B,)
    off = pos % page_size
    if isinstance(k_pages, QuantizedPages):
        # amax-quantize at write time: each token's (payload, scale) row
        # pair is a pure function of its own k/v vector
        kq, ks = quantize_kv_rows(k_new)
        vq, vs = quantize_kv_rows(v_new)
        k_pages = QuantizedPages(
            k_pages.q.at[:, page_of, off].set(jnp.moveaxis(kq, 0, 1)),
            k_pages.scale.at[:, page_of, off].set(jnp.moveaxis(ks, 0, 1)))
        v_pages = QuantizedPages(
            v_pages.q.at[:, page_of, off].set(jnp.moveaxis(vq, 0, 1)),
            v_pages.scale.at[:, page_of, off].set(jnp.moveaxis(vs, 0, 1)))
        return k_pages, v_pages
    kt = jnp.moveaxis(k_new.astype(k_pages.dtype), 0, 1)   # (Hkv, B, D)
    vt = jnp.moveaxis(v_new.astype(v_pages.dtype), 0, 1)
    k_pages = k_pages.at[:, page_of, off].set(kt)
    v_pages = v_pages.at[:, page_of, off].set(vt)
    return k_pages, v_pages


def write_paged_prompt(k_pages, v_pages, k_new, v_new, block_tables):
    """Prefill write: k_new/v_new (B, S, Hkv, D) go to positions [0, S)
    of each sequence. Returns the updated pools."""
    b = k_new.shape[0]
    return write_paged_prompt_at(k_pages, v_pages, k_new, v_new,
                                 block_tables, jnp.zeros((b,), jnp.int32))


def write_paged_prompt_at(k_pages, v_pages, k_new, v_new, block_tables,
                          start):
    """Prefill write at an offset: k_new/v_new (B, S, Hkv, D) land at
    positions [start, start+S) of each sequence (``start`` (B,) int32 —
    the chunked-prefill cursor; :func:`write_paged_prompt` is the
    start=0 case). Positions past the block table's width are DROPPED
    (scatter mode="drop"): the final chunk of a prompt pads to the fixed
    chunk length, and its pad tail must never clamp onto a live page.
    The page-write kernel on the TPU (plain pools), the scatter
    elsewhere."""
    write = (write_paged_prompt_at_pallas if _write_kernel_applies(k_pages)
             else write_paged_prompt_at_xla)
    return write(k_pages, v_pages, k_new, v_new, block_tables, start)


def write_paged_prompt_at_xla(k_pages, v_pages, k_new, v_new, block_tables,
                              start):
    """:func:`write_paged_prompt_at` as a ``mode="drop"`` scatter: the
    reference (CPU tests, ``use_pallas`` off) and the write of
    ``QuantizedPages`` pools."""
    bt = jnp.asarray(block_tables, jnp.int32)
    b, s, hkv, d = k_new.shape
    page_size = k_pages.shape[2]
    pos = (jnp.asarray(start, jnp.int32)[:, None]
           + jnp.arange(s, dtype=jnp.int32)[None, :])        # (B, S)
    page_idx = pos // page_size
    in_range = page_idx < bt.shape[1]
    pages = jnp.take_along_axis(
        bt, jnp.minimum(page_idx, bt.shape[1] - 1), axis=1)  # (B, S)
    # out-of-range positions get an out-of-range POOL page so the
    # mode="drop" scatter discards them
    pages = jnp.where(in_range, pages, k_pages.shape[1])
    off = pos % page_size
    if isinstance(k_pages, QuantizedPages):
        kq, ks = quantize_kv_rows(k_new)               # (B, S, Hkv, *)
        vq, vs = quantize_kv_rows(v_new)
        k_pages = QuantizedPages(
            k_pages.q.at[:, pages, off].set(
                jnp.moveaxis(kq, 2, 0), mode="drop"),
            k_pages.scale.at[:, pages, off].set(
                jnp.moveaxis(ks, 2, 0), mode="drop"))
        v_pages = QuantizedPages(
            v_pages.q.at[:, pages, off].set(
                jnp.moveaxis(vq, 2, 0), mode="drop"),
            v_pages.scale.at[:, pages, off].set(
                jnp.moveaxis(vs, 2, 0), mode="drop"))
        return k_pages, v_pages
    kt = jnp.moveaxis(k_new.astype(k_pages.dtype), 2, 0)   # (Hkv, B, S, D)
    vt = jnp.moveaxis(v_new.astype(v_pages.dtype), 2, 0)
    k_pages = k_pages.at[:, pages, off].set(kt, mode="drop")
    v_pages = v_pages.at[:, pages, off].set(vt, mode="drop")
    return k_pages, v_pages


def gather_paged_view(k_pages, v_pages, block_tables):
    """Materialize each sequence's contiguous ``(B, T, Hkv, D)`` cache
    view from its pages (T = max_pages * page_size) — the gather the
    decode kernel avoids. Chunked prefill used to amortize this copy
    over its whole query chunk; it now reads the pool through the block
    table directly (``paged_chunk_attention`` /
    ``paged_chunk_attention_xla``), so this helper survives only as the
    parity oracle those paths are tested against and for offline cache
    inspection."""
    bt = jnp.asarray(block_tables, jnp.int32)
    hkv, _, page_size, d = k_pages.shape
    b, max_pages = bt.shape
    t = max_pages * page_size
    # quantized pools dequantize here: the oracle view is f32
    k = _gathered_pool(k_pages, bt).reshape(b, hkv, t, d)
    v = _gathered_pool(v_pages, bt).reshape(b, hkv, t, d)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)     # (B, T, Hkv, D)


class HostPage:
    """One KV page spilled to host RAM: the per-layer ``(k, v)`` numpy
    copies of a pool page, ready to be written back into any free
    device page by :meth:`PagedKVCache.restore_page`. Owned by whoever
    orchestrates tiering (the serving PrefixCache) — the pool only
    counts it so the ledger's ``spilled`` state stays honest."""

    __slots__ = ("k", "v", "nbytes")

    def __init__(self, k: List[np.ndarray], v: List[np.ndarray],
                 nbytes: int):
        self.k = k
        self.v = v
        self.nbytes = nbytes


class PagedKVCache:
    """Host-side page-pool manager: one pool per transformer layer, a
    block table per live sequence, and a free list that recycles pages
    across requests (the continuous-batching substrate)."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, max_batch: int,
                 max_seq_len: int, dtype=jnp.bfloat16,
                 reserve_null_page: bool = False,
                 kv_dtype: str = "native"):
        """``reserve_null_page``: keep page 0 out of the free list so it
        only ever holds writes from INACTIVE batch slots (whose block
        tables are all-zero) — a continuous-batching engine decodes full
        fixed-shape batches, and idle rows must scribble somewhere that
        no live sequence owns.

        ``kv_dtype``: the pool STORAGE dtype — ``"native"`` keeps plain
        ``dtype`` arrays; ``"int8"`` stores :class:`QuantizedPages`
        (int8 payload + per-token f32 scale rows, amax-quantized at
        write time, dequantized in-kernel by every reader).
        ``bytes_per_page`` bills the actual quantized footprint."""
        if page_size % 8:
            raise ValueError("page_size must be a multiple of 8 (TPU "
                             "sublane tile)")
        if kv_dtype not in ("native", "int8"):
            raise ValueError(f"kv_dtype must be 'native' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.page_size = page_size
        self.num_pages = num_pages
        # pool geometry the tp=2 sharder (r19) and memwatch both need:
        # kv-head partitioning is legal only when this divides evenly
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.max_pages_per_seq = -(-max_seq_len // page_size)
        self.reserved_null_page = bool(reserve_null_page)
        # memwatch ledger bookkeeping, all O(1)-maintained (the r09
        # pin-transition idiom): pages shared across >1 reference, and
        # a free-list mutation epoch so fragmentation recomputes only
        # when allocate/free actually changed the list
        self._shared_pages = 0
        self._free_epoch = 0
        # host-RAM tier census: pages currently spilled via spill_page
        # (decremented on restore_page / forget_spilled) — the ledger's
        # "spilled" state. The HostPage objects themselves live with
        # the tiering orchestrator (the serving PrefixCache).
        self._spilled_pages = 0
        if kv_dtype == "int8":
            # int8 payload + one f32 scale per token row per head: the
            # ACTUAL quantized footprint (ledger honesty contract)
            self.bytes_per_page = (num_layers * 2 * num_kv_heads
                                   * page_size * (head_dim + 4))

            def _pool():
                return QuantizedPages(
                    jnp.zeros((num_kv_heads, num_pages, page_size,
                               head_dim), jnp.int8),
                    jnp.zeros((num_kv_heads, num_pages, page_size, 1),
                              jnp.float32))
        else:
            self.bytes_per_page = (num_layers * 2 * num_kv_heads
                                   * page_size * head_dim
                                   * jnp.dtype(dtype).itemsize)

            def _pool():
                return jnp.zeros(
                    (num_kv_heads, num_pages, page_size, head_dim), dtype)
        self.k_pages: List[Any] = [_pool() for _ in range(num_layers)]
        self.v_pages: List[Any] = [_pool() for _ in range(num_layers)]
        self.block_tables = np.zeros((max_batch, self.max_pages_per_seq),
                                     np.int32)
        self.seq_lens = np.zeros((max_batch,), np.int32)
        self._pages_used = np.zeros((max_batch,), np.int32)
        # a row's table slots before this one were given back
        # (``release_before``): it holds slots [first, used)
        self._pages_first = np.zeros((max_batch,), np.int32)
        # per-page reference counts: a page may be owned by one sequence
        # (rc=1), shared read-only across sequences with a common prompt
        # prefix, and/or pinned by a prefix cache — it returns to the
        # free list only when the last reference drops
        self._page_rc = np.zeros((num_pages,), np.int32)
        first = 1 if reserve_null_page else 0
        if reserve_null_page:
            self._page_rc[0] = np.int32(1 << 30)   # immortal scratch page
        self._free = list(range(num_pages - 1, first - 1, -1))

    # ------------------------------------------------------------- admin
    def free_page_count(self) -> int:
        return len(self._free)

    def sequence_pages(self, seq_idx: int) -> np.ndarray:
        """The page ids sequence ``seq_idx`` holds, in position order (a
        view of its block-table row: read it before freeing)."""
        return self.block_tables[seq_idx, int(self._pages_first[seq_idx]):
                                 int(self._pages_used[seq_idx])]

    def release_before(self, seq_idx: int, position: int) -> int:
        """Give back, from the front, the pages of ``seq_idx`` that lie
        WHOLLY before ``position`` (a window layer's reader never visits
        them again); their table slots go to page 0. Returns how many
        pages went."""
        first = int(self._pages_first[seq_idx])
        upto = min(max(int(position), 0) // self.page_size,
                   int(self._pages_used[seq_idx]))
        for i in range(first, upto):
            self.unref_page(int(self.block_tables[seq_idx, i]))
        if upto > first:
            self.block_tables[seq_idx, first:upto] = 0
            self._pages_first[seq_idx] = upto
        return max(0, upto - first)

    def ledger(self, fragmentation: bool = True) -> dict:
        """The memwatch pool ledger: pages/bytes in use, free, and
        shared (rc > 1, O(1)-maintained on ref transitions like the r09
        pin counter — never a pool scan), plus free-list fragmentation
        (1 - largest contiguous free run / free pages: 0 = one clean
        run, ->1 = free capacity shredded into single pages; paged
        attention itself is immune, but contiguity is what any future
        defrag/compaction or contiguous-gather fast path would buy).
        ``epoch`` increments on every free-list mutation so per-step
        publishers skip the fragmentation recompute on steady-state
        decode steps (which never touch the list)."""
        usable = self.num_pages - (1 if self.reserved_null_page else 0)
        free = len(self._free)
        out = {
            "usable_pages": usable,
            "pages_in_use": usable - free,
            "pages_free": free,
            "pages_shared": self._shared_pages,
            "pages_spilled": self._spilled_pages,
            "bytes_per_page": self.bytes_per_page,
            "bytes_in_use": (usable - free) * self.bytes_per_page,
            "bytes_free": free * self.bytes_per_page,
            "bytes_spilled": self._spilled_pages * self.bytes_per_page,
            "epoch": self._free_epoch,
        }
        if fragmentation:
            out["fragmentation"] = self.free_list_fragmentation()
        return out

    def free_list_fragmentation(self) -> float:
        """1 - (largest contiguous page-id run / free pages); 0.0 when
        the free list is empty or one contiguous block. One numpy sort
        over the free list — call on epoch change, not per step."""
        n = len(self._free)
        if n <= 1:
            return 0.0
        # host-only ledger probe over the python free list — never
        # reachable from a traced body  # tracecheck: disable=TRC002
        ids = np.sort(np.asarray(self._free, np.int64))
        breaks = np.flatnonzero(np.diff(ids) != 1)
        runs = np.diff(np.concatenate(([-1], breaks, [n - 1])))
        return float(1.0 - int(runs.max()) / n)

    def ref_page(self, page_id: int) -> None:
        self._page_rc[page_id] += 1
        if self._page_rc[page_id] == 2:     # 1 -> 2: became shared
            self._shared_pages += 1

    def unref_page(self, page_id: int) -> bool:
        """Drop one reference; returns True when the page actually
        returned to the free list (last reference gone) so callers
        reclaiming capacity can count REAL frees, not unrefs."""
        self._page_rc[page_id] -= 1
        if self._page_rc[page_id] == 1:     # 2 -> 1: stopped sharing
            self._shared_pages -= 1
        if self._page_rc[page_id] == 0:
            self._free.append(int(page_id))
            self._free_epoch += 1
            return True
        return False

    def adopt_shared(self, seq_idx: int, page_ids) -> None:
        """Install already-written pages (a cached prompt prefix) at the
        FRONT of ``seq_idx``'s block table, sharing them read-only (+1 ref
        each). The sequence's writes land beyond them — sharing is
        full-page-aligned, so shared pages are immutable by construction.
        Call before ``allocate``; the caller sets ``seq_lens``."""
        assert self._pages_used[seq_idx] == 0, "adopt into a fresh slot"
        for i, pid in enumerate(page_ids):
            self.block_tables[seq_idx, i] = pid
            self.ref_page(pid)
        self._pages_used[seq_idx] = len(page_ids)

    # ------------------------------------------------ host-RAM tiering
    # Scheduler-time only: spill/restore read and write the live pool
    # arrays, so they must never run while a donating dispatch holds
    # the pools detached (take_pools raises through the read if so).

    def spill_page(self, page_id: int) -> HostPage:
        """Copy one pool page to host RAM (every layer's k and v rows)
        and return the :class:`HostPage`. The caller still owns the
        page's reference — drop it via ``unref_page`` to actually free
        the device page (the spill-then-free split keeps a failed spill
        from losing the page)."""
        pid = int(page_id)
        # deliberate host pulls: spilling IS the device->host copy, and
        # it only ever runs at scheduler time between dispatched steps.
        # np.array (not asarray): numpy-backed pools would hand back a
        # VIEW of a buffer whose page id gets recycled
        if isinstance(self.k_pages[0], QuantizedPages):
            # quantized payload + scales move VERBATIM — the host tier
            # holds the pool bits, never a dequantized copy
            # tracecheck: disable=TRC002
            ks = [(np.array(p.q[:, pid]), np.array(p.scale[:, pid]))
                  for p in self.k_pages]
            # tracecheck: disable=TRC002
            vs = [(np.array(p.q[:, pid]), np.array(p.scale[:, pid]))
                  for p in self.v_pages]
        else:
            # tracecheck: disable=TRC002
            ks = [np.array(self.k_pages[i][:, pid])
                  for i in range(len(self.k_pages))]
            # tracecheck: disable=TRC002
            vs = [np.array(self.v_pages[i][:, pid])
                  for i in range(len(self.v_pages))]
        self._spilled_pages += 1
        return HostPage(ks, vs, self.bytes_per_page)

    def restore_page(self, host: HostPage, page_id: int) -> None:
        """Write a spilled page back into device page ``page_id`` (a
        page the caller just took from the free list) and retire the
        host copy from the spilled census. The pool arrays may be
        numpy-backed between dispatches (a donating step's returned
        tensors unwrap to read-only host views on CPU backends), so
        both flavors route through a functional ``jnp .at[].set`` —
        one pool-copy-sized write per layer, the price of a restore
        (still far cheaper than re-running the chunk's prefill)."""
        self.adopt_page(host, page_id)
        self._spilled_pages -= 1

    def adopt_page(self, host: HostPage, page_id: int) -> None:
        """Write a :class:`HostPage` spilled from ANOTHER pool into
        device page ``page_id`` — the prefill→decode disaggregation
        transfer (r19): the page was never in THIS pool's spilled
        census, so unlike :meth:`restore_page` nothing is retired from
        it. Functional per-layer ``.at[].set`` writes, so a committed
        (tensor-parallel) pool sharding is preserved — under tp the
        caller moves the full-head HostPage and each shard keeps its
        kv-head slice."""
        pid = int(page_id)
        for i in range(len(self.k_pages)):
            kp, vp = self.k_pages[i], self.v_pages[i]
            if isinstance(kp, QuantizedPages):
                # asarray each FIELD — never the NamedTuple itself
                # (that would try to stack payload and scale)
                self.k_pages[i] = QuantizedPages(
                    jnp.asarray(kp.q).at[:, pid].set(host.k[i][0]),
                    jnp.asarray(kp.scale).at[:, pid].set(host.k[i][1]))
                self.v_pages[i] = QuantizedPages(
                    jnp.asarray(vp.q).at[:, pid].set(host.v[i][0]),
                    jnp.asarray(vp.scale).at[:, pid].set(host.v[i][1]))
            else:
                k = jnp.asarray(kp)
                v = jnp.asarray(vp)
                self.k_pages[i] = k.at[:, pid].set(host.k[i])
                self.v_pages[i] = v.at[:, pid].set(host.v[i])

    def forget_spilled(self, host: HostPage) -> None:
        """A spilled page is being dropped entirely (host-tier budget
        eviction): retire it from the spilled census without a device
        write."""
        self._spilled_pages -= 1

    def take_free_page(self) -> int:
        """Pop one page from the free list with reference count 1 —
        the restore path's single-page allocation (sequence-shaped
        ``allocate`` sizes whole block tables). Raises like
        ``allocate`` when the pool is exhausted."""
        if not self._free:
            raise RuntimeError("page pool exhausted")
        pid = self._free.pop()
        self._free_epoch += 1
        self._page_rc[pid] = 1
        return pid

    def allocate(self, seq_idx: int, n_tokens: int) -> None:
        """Ensure sequence ``seq_idx`` has pages for ``n_tokens`` more
        tokens; raises RuntimeError when the pool is exhausted (the
        caller's scheduler decides eviction — same contract as the
        reference's block manager)."""
        need = -(-(int(self.seq_lens[seq_idx]) + n_tokens)
                 // self.page_size)
        have = int(self._pages_used[seq_idx])
        if need > self.block_tables.shape[1]:
            raise RuntimeError(
                f"sequence {seq_idx} needs {need} pages > max_pages_per_seq "
                f"{self.block_tables.shape[1]}")
        for i in range(have, need):
            if not self._free:
                # pages popped so far are already recorded in _pages_used
                # below, so an evict-and-retry caller cannot leak them
                raise RuntimeError("page pool exhausted")
            pid = self._free.pop()
            self._free_epoch += 1
            self.block_tables[seq_idx, i] = pid
            self._page_rc[pid] = 1
            self._pages_used[seq_idx] = i + 1

    def move_sequence(self, src: int, dst: int) -> None:
        """Relocate sequence ``src``'s bookkeeping row to the empty slot
        ``dst`` (bucket-shrink compaction): pure host-side index moves —
        the pool arrays, page contents and reference counts are
        untouched, only the block-table row changes slots."""
        if self._pages_used[dst] or self.seq_lens[dst]:
            raise RuntimeError(
                f"move_sequence: destination slot {dst} is not empty")
        n = int(self._pages_used[src])
        self.block_tables[dst, :n] = self.block_tables[src, :n]
        self.block_tables[dst, n:] = 0
        self.seq_lens[dst] = self.seq_lens[src]
        self._pages_used[dst] = self._pages_used[src]
        self._pages_first[dst] = self._pages_first[src]
        self.block_tables[src, :n] = 0
        self.seq_lens[src] = 0
        self._pages_used[src] = 0
        self._pages_first[src] = 0

    def free_sequence(self, seq_idx: int) -> None:
        n = int(self._pages_used[seq_idx])
        for i in range(int(self._pages_first[seq_idx]), n):
            self.unref_page(int(self.block_tables[seq_idx, i]))
        self.block_tables[seq_idx, :n] = 0
        self._pages_used[seq_idx] = 0
        self._pages_first[seq_idx] = 0
        self.seq_lens[seq_idx] = 0

    # ----------------------------------------------------------- writing
    def prefill(self, layer: int, seq_ids, k_new, v_new) -> None:
        """Write prompts for the (sub)batch ``seq_ids``; call
        ``allocate`` first. On layer 0 the seq_lens advance."""
        bt = jnp.asarray(self.block_tables[seq_ids])
        self.k_pages[layer], self.v_pages[layer] = write_paged_prompt(
            self.k_pages[layer], self.v_pages[layer], k_new, v_new, bt)
        if layer == 0:
            self.seq_lens[seq_ids] = k_new.shape[1]

    def append(self, layer: int, seq_ids, k_new, v_new) -> None:
        """Write one decode token per sequence of ``seq_ids`` at position
        ``seq_lens`` (call ``advance`` once per token AFTER all layers)."""
        bt = jnp.asarray(self.block_tables[seq_ids])
        pos = jnp.asarray(self.seq_lens[seq_ids])
        self.k_pages[layer], self.v_pages[layer] = write_paged_kv(
            self.k_pages[layer], self.v_pages[layer], k_new, v_new, bt, pos)

    def advance(self, seq_ids) -> None:
        self.seq_lens[seq_ids] += 1

    # ------------------------------------------------- donation handoff
    def take_pools(self) -> List[Tuple[jax.Array, jax.Array]]:
        """Detach and return the per-layer ``(k, v)`` pool pairs for a
        donating dispatch (``jax.jit(..., donate_argnums=...)``): the
        cache's own references are cleared, so nothing can read the
        donated — hence invalidated — buffers through this object while
        the step is in flight.  The dispatcher MUST hand the step's
        returned pools back via :meth:`install_pools`; until then the
        cache is deliberately unusable (a failed dispatch leaves it
        empty and loudly broken instead of silently aliasing dead
        buffers).  tracecheck rule TRC003 recognizes the ``take_*``
        naming as the sanctioned ownership-transfer idiom."""
        if self.k_pages[0] is None:
            raise RuntimeError(
                "take_pools: pools already detached (a donating dispatch "
                "is in flight or failed without install_pools)")
        pairs = [(self.k_pages[i], self.v_pages[i])
                 for i in range(len(self.k_pages))]
        for i in range(len(self.k_pages)):
            self.k_pages[i] = None
            self.v_pages[i] = None
        return pairs

    def install_pools(self, pairs) -> None:
        """Install the pool pairs a donating step returned (the other
        half of :meth:`take_pools`)."""
        for i, (k, v) in enumerate(pairs):
            self.k_pages[i] = k
            self.v_pages[i] = v

    # ---------------------------------------------------------- attention
    def attend(self, layer: int, q, seq_ids) -> jax.Array:
        """Decode attention of q (B, H, D) for ``seq_ids`` against this
        layer's pool (lengths INCLUDE any token just appended)."""
        from ..flags import snapshot
        snap = snapshot(("use_pallas",))
        bt = jnp.asarray(self.block_tables[seq_ids])
        sl = jnp.asarray(self.seq_lens[seq_ids] + 1)
        fn = paged_attention if snap.use_pallas else paged_attention_xla
        return fn(q, self.k_pages[layer], self.v_pages[layer], bt, sl)
