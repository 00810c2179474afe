"""Flash attention for TPU in Pallas.

Reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu (vendored
FlashAttention-2) + python/paddle/nn/functional/flash_attention.py. The TPU
design is the standard online-softmax block algorithm laid out for the
MXU/VMEM hierarchy:

  - Grids iterate (batch*heads, q_blocks, kv_blocks) with the kv dimension
    innermost: TPU grid steps run sequentially per core, so the f32
    accumulators (out-sum, running max m, denominator l) live in REVISITED
    output blocks that stay VMEM-resident across the kv sweep — only one
    (block_q, d) + (block_k, d) tile pair is resident at a time, so max
    sequence length is bounded by HBM, not VMEM (long-context ready).
  - Causal kv blocks strictly above the diagonal are predicated off with
    pl.when (no MXU work issued).
  - bwd: two kernels recomputing P from (q, k, saved logsumexp) — dq sweeps
    kv blocks, dk/dv sweeps q blocks on TRANSPOSED score blocks (k.q^T),
    so P^T and dS^T are made in place — FlashAttention-2's backward with
    D_i = rowsum(dO * O) precomputed outside.
  - varlen (flash_attn_unpadded / segment masking): optional int32 segment
    ids mask cross-segment attention, the TPU-idiomatic replacement for
    ragged varlen batches (static shapes). Padding rows should carry a
    dedicated segment id; they then only attend to other padding rows, and
    their loss contribution is masked out by the caller. Rows whose segment
    matches NO kv position emit zeros (fwd) and zero grads (bwd).

Every product accumulates in float32 and takes its operands in the dtype
q, k, v and dO arrive in: bf16 inputs go to the MXU as stored, with P and dS
rounded to bf16 right before their products (FlashAttention-2's bf16
arithmetic; softmax statistics, exp, lse, delta and every accumulator stay
float32); float32 inputs keep float32 products, which Mosaic, at the default
precision, computes in one bf16 pass all the same. ``sm_scale`` goes on the
float32 scores, never on an operand, and on dq and dk once each outside the
kernels. See ``_dot``; KERNEL_DECISIONS.md "Flash attention operands" has
the chip's timings, and what a block step does wait for.
Layout at this level is (BH, S, D); the (B, S, H, D) paddle-convention
wrapper is ``flash_attention_bshd``.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu registers TPU lowerings — unavailable on CPU-only test envs
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover - CPU CI path (interpret mode)
    pltpu = None

# the flag set the flash entry points resolve ONCE per call via
# flags.snapshot (one lock acquisition + env parse), then thread through
# _compact — the decode/serving hot path calls these thousands of times a
# second and per-helper registry round-trips were host overhead
_FLASH_FLAGS = ("use_pallas", "flash_compact_stats", "flash_dispatch_table")


def _flash_snapshot():
    from ..flags import snapshot
    return snapshot(_FLASH_FLAGS)


def resolve_dispatch(seq_len: int, snap=None) -> str:
    """Per-shape dispatch (FLAGS_flash_dispatch_table): resolve a query
    length against the ';'-separated ``min_seqlen:entry`` buckets and
    return ``"flash"`` (the kernels, at ``flash_tiling``'s blocks) or
    ``"dense"`` (the benched-slower shapes: the r05 on-chip A/B has flash
    LOSING to XLA dense at seq 2048, 0.86x, so that bucket must fall back
    — a fused path that loses to the unfused one has no reason to exist).
    A length resolves to the bucket with the largest min_seqlen <= it;
    lengths below every bucket — and any malformed entry — resolve to
    flash, and an empty table disables per-shape dispatch entirely."""
    if snap is None:
        snap = _flash_snapshot()
    table = (snap.flash_dispatch_table or "").strip()
    best_min, best = -1, None
    for entry in table.split(";"):
        min_s, _, kind = entry.strip().partition(":")
        try:
            lo = int(min_s)
        except ValueError:
            continue
        if lo <= seq_len and lo > best_min:
            best_min, best = lo, kind.strip().lower()
    return "dense" if best == "dense" else "flash"


def _snap(block: int, n: int) -> int:
    """Largest usable block for a length-n axis: block itself when it
    divides n, else the largest multiple-of-128 divisor of n that is
    < block. Returns 0 when none exists (caller raises). Keeps a block
    wider than a shape allows from demoting it to the dense path: seq
    1664 under a 1,024 block snaps to 128 instead of losing the kernel."""
    block = min(block, n)
    if block and n % block == 0:
        return block
    for cand in range(block - block % 128, 0, -128):
        if n % cand == 0:
            return cand
    return 0


_NEG_INF = -1e30
_LANES = 128  # stat rows replicate across one lane tile inside kernels


# ================================================================== tiling
# What one grid step of the three training kernels spans comes from the
# call's shapes (``flash_tiling``), as ``paged_chunk_attention``'s does
# (``paged_attention.chunk_tiling``): per-step costs (the statistics, the
# pipeline's prologue) outweigh a 512 x 512 block's products at a head of
# 64, so a step takes as much of the sequence as VMEM allows.
# KERNEL_DECISIONS.md "Flash attention tiling" has the chip sweep that set
# the two constants.
_MAX_BLOCK = 1024
# VMEM one step may hold by ``_step_vmem``'s count. v5e has 128 MiB, of
# which Mosaic scopes a kernel to 16 MiB unless the call asks for more
# (``_compiler_params``).
_STEP_VMEM_BYTES = 24 << 20


def _step_vmem(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM of the heaviest kernel's step (dk/dv's): four float32
    ``(bq, bk)`` temporaries (scores, ``p``, ``dp``, ``ds``), the q, dO,
    k and v blocks double-buffered, and the two float32 accumulators
    (dk and dv; the forward's output and accumulator are no wider),
    double-buffered, a head under the 128 lanes padded to them."""
    lanes = max(d, _LANES)
    return (4 * 4 * block_q * block_k
            + 2 * 2 * (block_q + block_k) * lanes * itemsize
            + 2 * 2 * max(block_q, block_k) * lanes * 4)


def flash_tiling(s_q: int, s_k: int, d: int,
                 itemsize: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` of a call over ``s_q`` queries and ``s_k``
    keys of head ``d`` in a dtype of ``itemsize`` bytes: the widest side,
    from ``_MAX_BLOCK`` down in steps of 128, whose blocks (``_snap``'d to
    each axis) keep a step inside ``_STEP_VMEM_BYTES``; 0 for an axis with
    no usable block (the kernels raise)."""
    for side in range(_MAX_BLOCK, 0, -128):
        bq, bk = _snap(side, s_q), _snap(side, s_k)
        fits = _step_vmem(bq, bk, d, itemsize) <= _STEP_VMEM_BYTES
        if fits or not (bq and bk):
            return bq, bk
    return bq, bk


def _compiler_params(block_q: int, block_k: int, d: int, itemsize: int):
    """The VMEM scope a step of these blocks asks Mosaic for, where
    ``_step_vmem`` counts more than 12 MiB of the default 16; else none."""
    vmem = _step_vmem(block_q, block_k, d, itemsize)
    if vmem <= (12 << 20) or pltpu is None:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20))}


def _blocks(block_q, block_k, q, k):
    """Blocks passed explicitly as given; a side left None takes
    ``flash_tiling``'s for the call's shapes."""
    tq, tk = flash_tiling(q.shape[1], k.shape[1], q.shape[2],
                          q.dtype.itemsize)
    return (tq if block_q is None else block_q,
            tk if block_k is None else block_k)


# ==================================================================== band
# Under a window (key ``j`` seen by query ``i`` iff ``i - W < j <= i``) a
# query block sees only the key blocks that overlap its band, and a key
# block is seen only by the query blocks whose band reaches it. The grid's
# inner axis runs over those alone: its step ``j`` is the band's ``j``-th
# block, counted from the first one the outer block sees, and a step past
# the last (the band is narrower at the sequence's start) re-reads the last
# block (no new copy) with its work predicated off.
def _kv_first(i, bq, bk, window):
    """First key block query block ``i`` sees (a Python int or a traced
    scalar; no negative operand reaches the division)."""
    return jnp.maximum(i * bq - window + 1, 0) // bk


def _kv_last(i, bq, bk):
    return (i * bq + bq - 1) // bk


def _q_first(j, bq, bk):
    """First query block that sees key block ``j``."""
    return (j * bk) // bq


def _q_last(j, bq, bk, window, n_q):
    return jnp.minimum((j * bk + bk + window - 2) // bq, n_q - 1)


def band_steps(s: int, bq: int, bk: int, window: int) -> Tuple[int, int]:
    """``(key steps a query block, query steps a key block)``: the inner
    extents of the windowed grids, the widest band over the sequence."""
    n_q, n_k = s // bq, s // bk     # Python ints: these size the grid
    kv = max((i * bq + bq - 1) // bk - max(i * bq - window + 1, 0) // bk + 1
             for i in range(n_q))
    q = max(min((j * bk + bk + window - 2) // bq, n_q - 1) - (j * bk) // bq
            + 1 for j in range(n_k))
    return kv, q


def _kv_block(i, j, bq, bk, window):
    """The key block step ``j`` of query block ``i`` reads, clamped to the
    band's last; and whether the step computes."""
    first = _kv_first(i, bq, bk, window)
    last = _kv_last(i, bq, bk)
    return jnp.minimum(first + j, last), first + j <= last


def _q_block(j, i, bq, bk, window, n_q):
    """The query block step ``i`` of key block ``j`` reads, clamped; and
    whether the step computes."""
    first = _q_first(j, bq, bk)
    last = _q_last(j, bq, bk, window, n_q)
    return jnp.minimum(first + i, last), first + i <= last


def _named(name: str, window) -> str:
    """A windowed call's name in a device trace is its own."""
    return name if window is None else name + "_window"


def _rep(x):
    """(BH, S) -> (BH, S, 128) lane-replicated: Mosaic needs the last two
    block dims (8, 128)-aligned, and a trailing singleton would PAD to 128
    lanes in HBM anyway — replicating transiently at the kernel boundary
    keeps the persistent arrays compact (the residuals saved across layers
    are the 2-D forms).

    Known cost (advisor r2): such transients feed dq's pallas_call
    (~128 MB each at BH=256, S=4096; dk/dv's takes the compact rows in
    either layout). The fix — compact
    (BH, S) stats loaded as (1, block_q) lane rows and transposed
    in-kernel, plus a scratch-stat forward — is implemented behind
    FLAGS_flash_compact_stats (parity-tested in interpret mode, compiled
    for the chip by tests/test_chip_compile.py)."""
    return jnp.broadcast_to(x[..., None], (*x.shape, _LANES))


def _interpret() -> bool:
    from ..flags import is_tpu_backend
    return not is_tpu_backend()


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes: inside a
    check_vma=True shard_map (e.g. the ring-attention sep region) pallas
    outputs must declare their vma explicitly."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _compact(snap=None) -> bool:
    """FLAGS_flash_compact_stats: keep softmax stats compact (BH, S) at
    the kernel boundary — no 128x lane-replicated HBM transients. Numerics
    are identical (parity-tested); only Mosaic layouts differ."""
    if snap is None:
        snap = _flash_snapshot()
    return bool(snap.flash_compact_stats)


def _dims(ref_shape):
    return ref_shape[1], ref_shape[2]


# =================================================== the products' operands
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b


def _dot(a, b, dims):
    """``a . b`` accumulated in float32. bf16 operands go to the MXU as
    stored, one pass (bf16 x bf16 is exact in float32); DEFAULT is pinned
    there because Mosaic takes no float32-precision request on bf16
    operands and a process may set ``jax_default_matmul_precision``. Any
    other pairing multiplies in float32 at the precision the process
    asks for."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims,
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, preferred_element_type=jnp.float32)


def _beside(x, stored):
    """A float32 block (P, dS) as the operand of a product with
    ``stored``: rounded to bf16 where that one is bf16 — here, after all
    the block's float32 element-wise work — else as it is."""
    return x.astype(jnp.bfloat16) if stored.dtype == jnp.bfloat16 else x


# ============================================================ forward kernel
def _masked_scores(q, k, seg_q, seg_kv, q_blk, kv_blk, causal, sm_scale,
                   transposed=False, window=None):
    """Scaled score block with causal + segment masking — the shared core
    of all four kernels: the ``(bq, d)`` and ``(bk, d)`` blocks as
    stored, ``sm_scale`` on the float32 product, which rounds no operand.
    ``(bq, bk)``, or ``(bk, bq)`` with ``transposed`` (``k . q^T``: what
    dk/dv's products take on the left with nothing to transpose). ``seg_q`` / ``seg_kv``: the segment ids
    laid along their own axis of the block — ``(bq, 1)`` and ``(1, bk)``,
    or ``(1, bq)`` and ``(bk, 1)`` transposed — or None. ``window``: a
    causal band, key ``j`` seen by query ``i`` iff ``i - window < j <= i``
    (``causal`` is then implied)."""
    block_q, block_k = q.shape[0], k.shape[0]
    s = (_dot(k, q, _NT) if transposed else _dot(q, k, _NT)) * sm_scale
    if causal or window is not None:
        q_axis = 1 if transposed else 0
        q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, q_axis)
        kv_pos = kv_blk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - q_axis)
        seen = q_pos >= kv_pos
        if window is not None:
            seen &= q_pos - kv_pos < window
        s = jnp.where(seen, s, _NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q == seg_kv, s, _NEG_INF)
    return s


def _lanes(x, n):
    """A lane-replicated ``(rows, 128)`` stat as ``(rows, n)``: a prefix
    of its lanes, or its lane tile repeated — the same vregs, no relayout
    — where ``n`` allows; else column 0 broadcast."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _softmax_update(s, m_prev, l_prev):
    """One online-softmax step: returns (m_new, l_new, p, alpha) for a
    score block against the running stats, which are lane-replicated
    ``(bq, 128)`` and stay so through every per-row operation (alpha
    too): as ``(bq, 1)`` columns, one number a vreg, each of them and
    each broadcast over the block was a relayout a block step, and that,
    not the products, was what the forward waited for (KERNEL_DECISIONS.md
    "Flash attention operands").

    Also the serving chunk read's step (``paged_attention.
    _paged_chunk_kernel``): the clamp below is what makes a chunk row
    that sees no key emit zeros there, so a change to it changes both."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # clamp for fully-masked rows: with m_new == -inf, exp(s - m_new)
    # would be exp(0) = 1 for every masked score — clamping to 0 makes
    # p = exp(-1e30) = 0 so masked rows emit zeros, and the saved
    # lse = 0 + log(1) keeps the backward's p = exp(-1e30 - 0) = 0 too
    m_new = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - _lanes(m_new, s.shape[1]))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l_new, p, alpha


def _fwd_kernel(q_ref, k_ref, v_ref, seg_q_ref, seg_kv_ref,
                acc_ref, m_ref, l_ref, *, causal: bool, sm_scale: float,
                window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_q, _ = _dims(q_ref.shape)
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[0] = jnp.zeros_like(acc_ref[0])
        m_ref[0] = jnp.full_like(m_ref[0], _NEG_INF)
        l_ref[0] = jnp.zeros_like(l_ref[0])

    if window is not None:
        # the band's step: kj counts from the first block this row sees
        kv_blk, run = _kv_block(qi, kj, block_q, block_k, window)
    elif causal:
        # skip kv blocks strictly above the causal diagonal
        kv_blk, run = kj, kj * block_k <= qi * block_q + block_q - 1
    else:
        kv_blk, run = kj, True

    @pl.when(run)
    def _step():
        seg_q = seg_kv = None
        if seg_q_ref is not None:
            seg_q, seg_kv = seg_q_ref[0][:, :1], seg_kv_ref[0]
        s = _masked_scores(q_ref[0], k_ref[0], seg_q, seg_kv, qi, kv_blk,
                           causal, sm_scale, window=window)
        m_ref[0], l_ref[0], p, alpha = _softmax_update(s, m_ref[0], l_ref[0])
        v = v_ref[0]
        acc_ref[0] = (_lanes(alpha, acc_ref.shape[2]) * acc_ref[0]
                      + _dot(_beside(p, v), v, _NN))


def _fwd_kernel_compact(q_ref, k_ref, v_ref, seg_q_ref, seg_kv_ref,
                        out_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                        causal: bool, sm_scale: float, n_k: int,
                        window=None):
    """Compact-stat forward: acc/m/l live in VMEM scratch across the
    sequential kv sweep (same structure as decode_attention._prefill_kernel);
    the normalized output and the compact (1, block_q) lse row are emitted
    on the LAST kv block each q row-block runs — no lane-replicated stat
    arrays ever reach HBM."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_q, d = _dims(q_ref.shape)
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if window is not None:
        first = _kv_first(qi, block_q, block_k, window)
        last = _kv_last(qi, block_q, block_k)
        kv_blk, run = first + kj, first + kj <= last
    else:
        kv_blk = kj
        run = ((kj * block_k <= qi * block_q + block_q - 1) if causal
               else True)

    @pl.when(run)
    def _step():
        # seg block is (1, 1, bq) — Mosaic needs the sublane dim of every
        # compact stat block to equal the (size-1) array dim, so compact
        # stats ride (BH, 1, S) through every pallas boundary
        seg_q = seg_kv = None
        if seg_q_ref is not None:
            seg_q, seg_kv = jnp.transpose(seg_q_ref[0]), seg_kv_ref[0]
        s = _masked_scores(q_ref[0], k_ref[0], seg_q, seg_kv, qi, kv_blk,
                           causal, sm_scale, window=window)
        m_ref[...], l_ref[...], p, alpha = _softmax_update(
            s, m_ref[...], l_ref[...])
        v = v_ref[0]
        acc_ref[...] = (_lanes(alpha, d) * acc_ref[...]
                        + _dot(_beside(p, v), v, _NN))

    if window is not None:
        final_kj = last - first         # the band's last step of this row
    elif causal:
        final_kj = jnp.minimum((qi * block_q + block_q - 1) // block_k,
                               n_k - 1)
    else:
        final_kj = n_k - 1

    @pl.when(kj == final_kj)
    def _emit():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[...] / l_safe).astype(out_ref.dtype)
        lse_ref[0] = jnp.transpose(m + jnp.log(l_safe))      # (1, bq)


def _fwd_setup(q, k, block_q, block_k, h, hkv, window=None):
    """Shared fwd-path setup for both stat layouts: block clamping, the
    divisibility contract (NotImplementedError so the sdpa dispatch can
    fall back to dense), grid, and the GQA kv index map reading the
    UNEXPANDED kv at Hkv bandwidth. ``window``: the grid's kv axis runs
    over the band's blocks alone (``band_steps``)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_q = _snap(block_q, sq)
    block_k = _snap(block_k, skv)
    if not block_q or not block_k:
        raise NotImplementedError(
            f"flash_attention needs seq lens ({sq}, {skv}) with a "
            f"multiple-of-128 divisor <= the block sizes; pad or use "
            f"the dense path")
    n_k = skv // block_k
    grid = (bh, sq // block_q, n_k)
    rep = h // hkv
    if window is not None:
        if sq != skv:
            raise NotImplementedError(
                "a windowed flash call needs as many keys as queries")
        grid = (bh, sq // block_q,
                band_steps(sq, block_q, block_k, window)[0])

    def kv_at(i, j):
        if window is None:
            return j
        return _kv_block(i, j, block_q, block_k, window)[0]

    def kv_index(b, i, j):
        # GQA: query head -> its kv head (identity when hkv == h)
        return ((b // h) * hkv + (b % h) // rep, kv_at(i, j), 0)

    def kv_seg_index(b, i, j):
        return ((b // h) * hkv + (b % h) // rep, 0, kv_at(i, j))

    qkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    return (bh, sq, d, block_q, block_k, n_k, grid, qkv_specs,
            kv_seg_index)


def _fwd_compact(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                 block_k, h, hkv, window=None):
    if pltpu is None:
        raise NotImplementedError(
            "FLAGS_flash_compact_stats needs pallas TPU scratch support")
    (bh, sq, d, block_q, block_k, n_k, grid, qkv_specs,
     kv_seg_index) = _fwd_setup(q, k, block_q, block_k, h, hkv, window)
    win = {} if window is None else {"window": window}

    in_specs = list(qkv_specs)
    args = [q, k, v]
    if seg_q is not None:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_k), kv_seg_index),
        ]
        args += [seg_q[:, None, :], seg_kv[:, None, :]]
        kernel = functools.partial(_fwd_kernel_compact, causal=causal,
                                   sm_scale=sm_scale, n_k=n_k, **win)
    else:
        kernel = functools.partial(
            lambda qr, kr, vr, o, ls, a, m, l, **kw: _fwd_kernel_compact(
                qr, kr, vr, None, None, o, ls, a, m, l, **kw),
            causal=causal, sm_scale=sm_scale, n_k=n_k, **win)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _sds((bh, sq, d), q.dtype, q),
            _sds((bh, 1, sq), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name=_named("flash_fwd", window),
        **_compiler_params(block_q, block_k, d, q.dtype.itemsize),
    )(*args)
    return out, lse[:, 0, :]


def _fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q, block_k,
         h=1, hkv=1, compact=False, window=None):
    if window is not None and seg_q is not None:
        raise NotImplementedError("a window with segment ids")
    if compact:
        return _fwd_compact(q, k, v, seg_q, seg_kv, causal, sm_scale,
                            block_q, block_k, h, hkv, window)
    (bh, sq, d, block_q, block_k, n_k, grid, qkv_specs,
     kv_seg_index) = _fwd_setup(q, k, block_q, block_k, h, hkv, window)
    win = {} if window is None else {"window": window}

    in_specs = list(qkv_specs)
    args = [q, k, v]
    if seg_q is not None:
        # q-side ids lane-replicated (column orientation, no transpose);
        # kv-side ids compact (BH, 1, S) row vectors
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), kv_seg_index),
        ]
        args += [_rep(seg_q), seg_kv[:, None, :]]
        kernel = functools.partial(_fwd_kernel, causal=causal,
                                   sm_scale=sm_scale, **win)
    else:
        kernel = functools.partial(
            lambda qr, kr, vr, a, m, l, **kw: _fwd_kernel(
                qr, kr, vr, None, None, a, m, l, **kw),
            causal=causal, sm_scale=sm_scale, **win)

    # accumulators are revisited output blocks: index maps ignore the kv
    # grid dim, so the block stays VMEM-resident across the kv sweep
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, sq, d), jnp.float32, q),
            _sds((bh, sq, _LANES), jnp.float32, q),
            _sds((bh, sq, _LANES), jnp.float32, q),
        ],
        interpret=_interpret(),
        name=_named("flash_fwd_stats", window),
        **_compiler_params(block_q, block_k, d, q.dtype.itemsize),
    )(*args)

    # reduce the lane-replicated stats to compact (BH, S) residuals —
    # these persist per layer until the backward, so layout matters
    m, l = m[..., 0], l[..., 0]
    # fully-masked rows (e.g. padding segments) have l == 0 — emit zeros
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)                                # (bh, sq)
    return out, lse


# =========================================================== backward kernels
def _col(ref, compact):
    """Read a per-q-row stat as a (block_q, 1) column (dq's kernel; dk/dv
    take the rows as they are). Replicated layout:
    ref block (1, bq, 128), column 0. Compact layout: ref block (1, 1, bq)
    lane row (stats ride (BH, 1, S) — the size-1 sublane dim satisfies
    Mosaic's block-shape rule), transposed in-kernel (the relayout the
    flag gates)."""
    if compact:
        return jnp.transpose(ref[0])
    return ref[0][:, :1]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seg_q_ref, seg_kv_ref, dq_ref, *, causal, sm_scale,
                   compact=False, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    block_q, d = _dims(q_ref.shape)
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    if window is not None:
        kv_blk, run = _kv_block(qi, kj, block_q, block_k, window)
    else:
        kv_blk = kj
        run = ((kj * block_k <= qi * block_q + block_q - 1) if causal
               else True)

    @pl.when(run)
    def _step():
        k = k_ref[0]
        lse = _col(lse_ref, compact)                         # (bq, 1)
        delta = _col(delta_ref, compact)                     # (bq, 1)
        seg_q = seg_kv = None
        if seg_q_ref is not None:
            seg_q, seg_kv = _col(seg_q_ref, compact), seg_kv_ref[0]
        s = _masked_scores(q_ref[0], k, seg_q, seg_kv, qi, kv_blk, causal,
                           sm_scale, window=window)
        p = jnp.exp(s - lse)                                 # (bq, bk)
        dp = _dot(do_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta)
        dq_ref[0] = dq_ref[0] + _dot(_beside(ds, k), k, _NN)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    seg_q_ref, seg_kv_ref, dk_ref, dv_ref, *, causal,
                    sm_scale, window=None, n_q=0):
    # grid: (b_kv, ki, rep, qj) — dk/dv blocks are revisited across the
    # (rep, qj) sweep (GQA: every query head in the group accumulates
    # into its kv head's gradient). Everything here is TRANSPOSED,
    # (bk, bq): P^T and dS^T are what dv's and dk's products take on the
    # left, so neither block is ever transposed, and the per-q-row stats
    # are wanted as the (1, bq) lane rows they are stored as, in either
    # stat layout.
    ki = pl.program_id(1)
    r = pl.program_id(2)
    qj = pl.program_id(3)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when((qj == 0) & (r == 0))
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    if window is not None:
        # the band's step: qj counts from the first q block seeing ki
        q_blk, run = _q_block(ki, qj, block_q, block_k, window, n_q)
    else:
        # causal: q blocks whose END is before this kv block's start
        # never see it
        q_blk = qj
        run = ((qj * block_q + block_q - 1 >= ki * block_k) if causal
               else True)

    @pl.when(run)
    def _step():
        q, do = q_ref[0], do_ref[0]
        seg_q = seg_kv = None
        if seg_q_ref is not None:
            seg_q, seg_kv = seg_q_ref[0], jnp.transpose(seg_kv_ref[0])
        st = _masked_scores(q, k_ref[0], seg_q, seg_kv, q_blk, ki, causal,
                            sm_scale, transposed=True, window=window)
        pt = jnp.exp(st - lse_ref[0])                        # (bk, bq)
        dv_ref[0] = dv_ref[0] + _dot(_beside(pt, do), do, _NN)
        dpt = _dot(v_ref[0], do, _NT)
        dst = pt * (dpt - delta_ref[0])
        # against q as stored: dk takes sm_scale once, outside the kernel
        dk_ref[0] = dk_ref[0] + _dot(_beside(dst, q), q, _NN)


def _bwd(causal, sm_scale, block_q, block_k, h, hkv, compact, window, res,
         g):
    do = g[0] if isinstance(g, (tuple, list)) else g
    return _bwd_impl(causal, sm_scale, block_q, block_k, h, hkv, compact,
                     res, do, None, window)


def _bwd_with_lse(causal, sm_scale, block_q, block_k, h, hkv, compact,
                  window, res, g):
    do, dlse = g
    dq, dk, dv, _, _ = _bwd_impl(causal, sm_scale, block_q, block_k, h,
                                 hkv, compact, res, do, dlse, window)
    return dq, dk, dv, None, None


def _bwd_impl(causal, sm_scale, block_q, block_k, h, hkv, compact, res,
              do, dlse, window=None):
    q, k, v, seg_q, seg_kv, out, lse = res
    rep = h // hkv
    bh, sq, d = q.shape
    skv = k.shape[1]
    # same snap as the forward (whose guard already rejected impossible
    # shapes) so fwd and bwd tile identically
    bq = _snap(block_q, sq)
    bk = _snap(block_k, skv)
    n_q, n_kv = pl.cdiv(sq, bq), pl.cdiv(skv, bk)
    call_kw = _compiler_params(bq, bk, d, q.dtype.itemsize)
    win = {} if window is None else {"window": window}
    if window is not None:
        # the two grids' inner axes run over the band alone
        n_kv, n_q_inner = band_steps(sq, bq, bk, window)
    else:
        n_q_inner = n_q

    def kv_at(i, j):
        if window is None:
            return j
        return _kv_block(i, j, bq, bk, window)[0]

    def q_at(i, j):
        # dkv's grid: step j of kv block i
        if window is None:
            return j
        return _q_block(i, j, bq, bk, window, n_q)[0]

    def kv_index(b, i, j):
        return ((b // h) * hkv + (b % h) // rep, kv_at(i, j), 0)

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                               # (bh, sq)
    if dlse is not None:
        # lse cotangent folds into the kernels for free: ds = p*(dp -
        # delta) becomes p*(dp - delta + dlse) since d lse/d s = p —
        # i.e. the SAME kernels with delta := delta - dlse
        delta = delta - dlse.astype(jnp.float32)

    has_seg = seg_q is not None
    # stats + ids compact, (BH, 1, S): (1, 1, bq) lane rows at a kernel's
    # boundary (no replicated HBM transients at all). dkv takes them so
    # in either layout: rows are what it wants
    rows = [lse[:, None, :], delta[:, None, :]]
    if has_seg:
        rows += [seg_q[:, None, :], seg_kv[:, None, :]]
    if compact:
        # dq transposes them to columns in-kernel
        stat_spec_dq = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
        stats_dq = rows
    else:
        # q-side rows lane-replicated transiently for the kernel boundary;
        # kv-side ids ride compact as (BH, 1, S) row vectors
        stat_spec_dq = pl.BlockSpec((1, bq, _LANES),
                                    lambda b, i, j: (b, i, 0))
        stats_dq = [_rep(lse), _rep(delta)]
        if has_seg:
            stats_dq += [_rep(seg_q), seg_kv[:, None, :]]

    in_specs_dq = [
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, bk, d), kv_index),                    # k
        pl.BlockSpec((1, bk, d), kv_index),                    # v
        pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # do
        stat_spec_dq,                                          # lse
        stat_spec_dq,                                          # delta
    ]
    if has_seg:
        in_specs_dq += [
            stat_spec_dq,
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: ((b // h) * hkv + (b % h) // rep,
                                          0, kv_at(i, j)))]
        dq_kernel = functools.partial(_bwd_dq_kernel, causal=causal,
                                      sm_scale=sm_scale, compact=compact,
                                      **win)
    else:
        dq_kernel = functools.partial(
            lambda qr, kr, vr, dor, lr, der, dqr, **kw: _bwd_dq_kernel(
                qr, kr, vr, dor, lr, der, None, None, dqr, **kw),
            causal=causal, sm_scale=sm_scale, compact=compact, **win)

    dq = pl.pallas_call(
        dq_kernel, grid=(bh, n_q, n_kv),
        in_specs=in_specs_dq,
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, sq, d), jnp.float32, q),
        interpret=_interpret(),
        name=_named("flash_bwd_dq", window),
        **call_kw,
    )(q, k, v, do, *stats_dq)
    dq = (dq * sm_scale).astype(q.dtype)

    # dkv grid: (b_kv, kv block, group member, q sweep) — dk/dv blocks are
    # revisited across BOTH trailing dims; every query head of the GQA
    # group accumulates into its kv head's gradient
    def q_index(b, i, r, j):
        return ((b // hkv) * h + (b % hkv) * rep + r, q_at(i, j), 0)

    stat_spec_dkv = pl.BlockSpec(
        (1, 1, bq), lambda b, i, r, j: (q_index(b, i, r, j)[0], 0,
                                        q_at(i, j)))

    in_specs_dkv = [
        pl.BlockSpec((1, bq, d), q_index),                     # q
        pl.BlockSpec((1, bk, d), lambda b, i, r, j: (b, i, 0)),  # k
        pl.BlockSpec((1, bk, d), lambda b, i, r, j: (b, i, 0)),  # v
        pl.BlockSpec((1, bq, d), q_index),                     # do
        stat_spec_dkv,                                         # lse
        stat_spec_dkv,                                         # delta
    ]
    if has_seg:
        in_specs_dkv += [
            stat_spec_dkv,
            pl.BlockSpec((1, 1, bk), lambda b, i, r, j: (b, 0, i))]
        dkv_kernel = functools.partial(_bwd_dkv_kernel, causal=causal,
                                       sm_scale=sm_scale, **win)
    else:
        dkv_kernel = functools.partial(
            lambda qr, kr, vr, dor, lr, der, dkr, dvr, **kw: _bwd_dkv_kernel(
                qr, kr, vr, dor, lr, der, None, None, dkr, dvr, **kw),
            causal=causal, sm_scale=sm_scale, **win)
    if window is not None:
        dkv_kernel = functools.partial(dkv_kernel, n_q=n_q)

    bh_kv = k.shape[0]
    dk, dv = pl.pallas_call(
        dkv_kernel, grid=(bh_kv, pl.cdiv(skv, bk), rep, n_q_inner),
        in_specs=in_specs_dkv,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, i, r, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, r, j: (b, i, 0))],
        out_shape=[_sds((bh_kv, skv, d), jnp.float32, q),
                   _sds((bh_kv, skv, d), jnp.float32, q)],
        interpret=_interpret(),
        name=_named("flash_bwd_dkv", window),
        **call_kw,
    )(q, k, v, do, *rows)
    dk = (dk * sm_scale).astype(k.dtype)
    return dq, dk, dv.astype(v.dtype), None, None


# ============================================================== public entry
# ``compact`` is a STATIC custom_vjp argument, not read from the flag
# inside _fwd/_bwd: jax caches custom_vjp traces process-wide keyed on the
# static args, so a trace-time flag read would make whichever layout
# traced first sticky for every later call with the same shapes.
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_attention(q, k, v, seg_q, seg_kv, causal, sm_scale,
                     block_q, block_k, h, hkv, compact, window=None):
    out, _ = _fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                  block_k, h, hkv, compact, window)
    return out


def _flash_fwd_rule(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                    block_k, h, hkv, compact, window=None):
    out, lse = _fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                    block_k, h, hkv, compact, window)
    return out, (q, k, v, seg_q, seg_kv, out, lse)


_flash_attention.defvjp(_flash_fwd_rule, _bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_attention_lse(q, k, v, seg_q, seg_kv, causal, sm_scale,
                         block_q, block_k, h, hkv, compact, window=None):
    return _fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                block_k, h, hkv, compact, window)


def _flash_lse_fwd_rule(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                        block_k, h, hkv, compact, window=None):
    out, lse = _fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                    block_k, h, hkv, compact, window)
    return (out, lse), (q, k, v, seg_q, seg_kv, out, lse)


_flash_attention_lse.defvjp(_flash_lse_fwd_rule, _bwd_with_lse)


def flash_attention_ref(q, k, v, segment_ids=None, kv_segment_ids=None,
                        causal: bool = True,
                        sm_scale: Optional[float] = None,
                        n_heads: int = 1,
                        n_kv_heads: Optional[int] = None,
                        window: Optional[int] = None):
    """Pure-jnp dense twin of :func:`flash_attention` — the parity
    oracle. Same (BH, S, D) layout and GQA convention (query heads of
    one group are consecutive rows per kv head); matches the kernels'
    fully-masked-row semantics (such rows emit zeros, not NaN)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    h = n_heads
    hkv = h if n_kv_heads is None else n_kv_heads
    rep = h // hkv
    bh, sq, d = q.shape
    b = bh // h
    skv = k.shape[1]
    qf = q.reshape(b, hkv, rep, sq, d).astype(jnp.float32) * sm_scale
    kf = k.reshape(b, hkv, skv, d).astype(jnp.float32)
    vf = v.reshape(b, hkv, skv, d).astype(jnp.float32)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qf, kf)
    if causal or window is not None:
        q_pos = jnp.arange(sq)[:, None]
        kv_pos = jnp.arange(skv)[None, :]
        seen = kv_pos <= q_pos
        if window is not None:
            seen &= q_pos - kv_pos < window
        s = jnp.where(seen, s, _NEG_INF)
    if segment_ids is not None:
        kv_ids = (segment_ids if kv_segment_ids is None
                  else kv_segment_ids)
        same = (segment_ids.reshape(b, hkv, rep, sq)[..., :, None]
                == kv_ids.reshape(b, hkv, skv)[:, :, None, None, :])
        s = jnp.where(same, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p / l_safe, vf)
    return out.reshape(bh, sq, d).astype(q.dtype)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             sm_scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             n_heads: int = 1,
                             n_kv_heads: Optional[int] = None):
    """(BH, S, D) flash attention returning ``(out, lse)`` — the mergeable
    form ring attention needs (two partial results combine in log-space).
    Differentiable in BOTH outputs: the lse cotangent folds into the
    standard FA2 backward as ``delta - dlse`` (d lse/d s = p). GQA as in
    ``flash_attention``."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    snap = _flash_snapshot()
    block_q, block_k = _blocks(block_q, block_k, q, k)
    if n_kv_heads is None:
        n_kv_heads = n_heads
    if n_heads % n_kv_heads:
        raise ValueError(f"n_heads {n_heads} not divisible by n_kv_heads "
                         f"{n_kv_heads}")
    if q.shape[0] * n_kv_heads != k.shape[0] * n_heads:
        raise ValueError(
            f"q rows {q.shape[0]} / k rows {k.shape[0]} inconsistent with "
            f"n_heads={n_heads}, n_kv_heads={n_kv_heads} — pass the head "
            f"counts for GQA inputs")
    return _flash_attention_lse(q, k, v, None, None, causal, sm_scale,
                                block_q, block_k, n_heads, n_kv_heads,
                                _compact(snap), None)


def flash_attention(q, k, v, segment_ids: Optional[jax.Array] = None,
                    kv_segment_ids: Optional[jax.Array] = None,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    n_heads: int = 1, n_kv_heads: Optional[int] = None,
                    snap=None, window: Optional[int] = None):
    """(BH, S, D)-layout flash attention. segment_ids: (BH, S) int32 — rows
    attend only within their segment (varlen batches packed statically).
    ``window``: a causal band, key ``j`` seen by query ``i`` iff
    ``i - window < j <= i``; the grids run over the band's blocks alone
    (None: the plain causal or full kernels, unchanged).
    GQA: pass q as (B*n_heads, S, D) and k/v as (B*n_kv_heads, Skv, D) —
    the kernels read the UNEXPANDED kv via index maps (Hkv bandwidth) and
    accumulate dk/dv over each group's query heads. ``block_q`` /
    ``block_k`` left None take ``flash_tiling``'s. ``snap``: the
    caller's trace-boundary flags snapshot (must cover _FLASH_FLAGS);
    resolved here only when the caller didn't already."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if snap is None:
        snap = _flash_snapshot()
    block_q, block_k = _blocks(block_q, block_k, q, k)
    if n_kv_heads is None:
        n_kv_heads = n_heads
    if n_heads % n_kv_heads:
        raise ValueError(f"n_heads {n_heads} not divisible by n_kv_heads "
                         f"{n_kv_heads}")
    if q.shape[0] * n_kv_heads != k.shape[0] * n_heads:
        raise ValueError(
            f"q rows {q.shape[0]} / k rows {k.shape[0]} inconsistent with "
            f"n_heads={n_heads}, n_kv_heads={n_kv_heads} — pass the head "
            f"counts for GQA inputs")
    if segment_ids is not None and kv_segment_ids is None:
        if n_kv_heads != n_heads:
            # a (B*H, Skv) default would be read with (B*Hkv)-space rows
            raise ValueError(
                "GQA flash_attention needs an explicit (B*n_kv_heads, Skv) "
                "kv_segment_ids (the q-side ids have a different leading "
                "dim)")
        kv_segment_ids = segment_ids
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be positive, got {window}")
        if window >= q.shape[1]:
            window = None               # the band holds the whole triangle
        else:
            causal = True
    return _flash_attention(q, k, v, segment_ids, kv_segment_ids,
                            causal, sm_scale, block_q, block_k,
                            n_heads, n_kv_heads, _compact(snap), window)


class FlashPartitionError(ValueError):
    """The declared activation layout does not let the kernel be split
    over the context mesh (a busy axis nobody declared, or a dim an axis
    does not divide). Not a NotImplementedError: callers fall back to
    the dense path on those, and this must be seen."""


# (batch axes, head axis) of the (B, S, H, D) activations traced inside
# ``activation_layout`` — None outside one
_LAYOUT: contextvars.ContextVar = contextvars.ContextVar(
    "flash_activation_layout", default=None)


@contextlib.contextmanager
def activation_layout(mesh, batch_axes: Tuple[str, ...] = (),
                      head_axis: Optional[str] = None):
    """Trace the body with ``mesh`` as the context mesh and (B, S, H, D)
    activations declared split by batch row over ``batch_axes`` and by
    head over ``head_axis``.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so inside this context
    ``flash_attention_bshd`` splits the call by hand — attention is
    independent per batch row and per head. The names come from the
    layer that laid the activations out (``hapi.TrainStep``: its data
    axes, and the axis its parameters are split over); this module knows
    none. Outside a declared layout the kernel runs unsplit."""
    token = _LAYOUT.set((tuple(batch_axes), head_axis))
    try:
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            yield
    finally:
        _LAYOUT.reset(token)


def _mesh_split(b: int, h: int, hkv: int):
    """``(batch axes, head axis, manual axes)`` to run the kernel per
    shard of the declared layout, or None where there is nothing to
    split: no declared layout, one device, or a region another engine is
    already manual over (ring attention's sep region, a pipeline stage).
    Mosaic wants the region manual over EVERY mesh axis, unit ones too."""
    layout = _LAYOUT.get()
    mesh = jax.sharding.get_abstract_mesh()
    if layout is None or mesh.empty or mesh.manual_axes:
        return None
    busy = {a for a, n in mesh.shape.items() if n > 1}
    if not busy:
        return None
    batch = tuple(a for a in layout[0] if a in busy)
    head = layout[1] if layout[1] in busy else None
    loose = busy - {*batch, head}
    if loose:
        raise FlashPartitionError(
            f"flash attention under mesh {dict(mesh.shape)}: axes "
            f"{sorted(loose)} hold more than one device but the declared "
            f"layout (batch over {layout[0]}, heads over {layout[1]!r}) "
            f"does not say how activations lie on them")
    nb = math.prod(mesh.shape[a] for a in batch)
    if b % nb:
        raise FlashPartitionError(
            f"flash attention: batch {b} is not divisible by the "
            f"{nb} shards of its axes {batch}")
    if head and (h % mesh.shape[head] or hkv % mesh.shape[head]):
        raise FlashPartitionError(
            f"flash attention: {h} heads / {hkv} kv heads are not "
            f"divisible by the {mesh.shape[head]} shards of {head!r}")
    return batch or None, head, frozenset(mesh.axis_names)


def flash_attention_bshd(q, k, v, segment_ids=None, kv_segment_ids=None,
                         causal: bool = True,
                         sm_scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         snap=None, window: Optional[int] = None):
    """Paddle-convention (B, S, H, D) wrapper (reference:
    python/paddle/nn/functional/flash_attention.py uses [batch, seq, heads,
    dim]). ``segment_ids``: (B, S_q); ``kv_segment_ids``: (B, S_kv),
    defaulting to ``segment_ids`` when the lengths match. GQA: k/v may
    carry fewer heads (Hkv | H) — never expanded in HBM. Traced inside
    :func:`activation_layout` (``hapi.TrainStep(mesh=...)`` declares
    one) the kernel runs per (batch, head) shard of the declared axes.
    ``window``: as :func:`flash_attention`'s."""
    if segment_ids is not None and kv_segment_ids is None:
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                "kv_segment_ids required when q and kv lengths differ")
        kv_segment_ids = segment_ids

    def local(q, k, v, segment_ids, kv_segment_ids):
        b, s, h, d = q.shape
        skv = k.shape[1]
        hkv = k.shape[2]

        def to_bhsd(t, sl, nh):
            return jnp.swapaxes(t, 1, 2).reshape(b * nh, sl, d)

        seg_q = seg_kv = None
        if segment_ids is not None:
            seg_q = jnp.repeat(segment_ids, h, axis=0)
            seg_kv = jnp.repeat(kv_segment_ids, hkv, axis=0)
        out = flash_attention(to_bhsd(q, s, h), to_bhsd(k, skv, hkv),
                              to_bhsd(v, skv, hkv), seg_q, seg_kv, causal,
                              sm_scale, block_q, block_k, n_heads=h,
                              n_kv_heads=hkv, snap=snap, window=window)
        return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)

    split = _mesh_split(q.shape[0], q.shape[2], k.shape[2])
    if split is None:
        return local(q, k, v, segment_ids, kv_segment_ids)
    batch, head, manual = split
    from jax.sharding import PartitionSpec as P
    bshd, bs = P(batch, None, head, None), P(batch, None)
    seg_spec = None if segment_ids is None else bs
    return jax.shard_map(
        local, in_specs=(bshd, bshd, bshd, seg_spec, seg_spec),
        out_specs=bshd, axis_names=manual, check_vma=False)(
            q, k, v, segment_ids, kv_segment_ids)
