"""Fused transformer-block decode: ONE kernel per layer for the serving
hot path.

Reference parity target: the decode phase of the reference's whole-stack
fused op (paddle/fluid/operators/fused/fused_multi_transformer_op.cu) and
its block-attention successor (block_multihead_attention), generalized the
way ClusterFusion-style decode fusion papers argue for: fuse the FULL
block step — ``residual + attn(rms_norm(x))`` then
``residual + ffn(rms_norm(x))`` — not just the attention core.

Why: steady-state decode moves one token per sequence through L layers.
Every op boundary in the unfused chain (rms_norm -> q/k/v matmuls -> RoPE
-> paged attention -> out-proj -> rms_norm -> SwiGLU FFN) parks the
(B, hidden) activation back in HBM and re-loads it, and each op pays its
own dispatch. The activations are tiny (a few hundred KB); the weights
are the real traffic. The right TPU program therefore streams each
weight matrix through VMEM exactly once per step while the activations
NEVER leave VMEM.

TPU-native design — one ``pallas_call`` with a flat 1-D grid of
sequential phases (TPU grid steps run in order on a core, so VMEM
scratch persists across phases):

  Q | K | V   tiled matmuls of the rms-normed activation against the
              projection weights (contraction x output tiling, f32
              accumulation in a revisited scratch accumulator);
  R           in-VMEM RoPE of q/k at each slot's own position
              (``seq_lens`` rides scalar prefetch) + emit of the new
              token's k/v for the pool append;
  A           paged attention: the block-table index map streams one
              pool page per step straight from HBM (scalar-prefetched
              block tables, exactly like kernels/paged_attention.py);
              the just-computed k/v token is folded from VMEM into the
              online softmax at each row's last valid page — attention
              covers position ``seq_lens`` WITHOUT the pool write having
              happened yet;
  O           out-projection tiles + first residual add into VMEM;
  F           SwiGLU: gate and up tiles in one pass (two accumulators),
              silu(g) * u into a VMEM scratch;
  D           down-projection tiles + second residual add, emitted as
              the kernel output.

The ONLY HBM round-trip the step still makes for activations is the
(B, Hkv, D) new-token k/v append, which ``write_paged_kv`` puts into the
pool inside the same compiled program: on the TPU an aliased page-write
kernel that rewrites the ONE page a row's token lands on (B pages a
layer; folding the write into this kernel would stream every visited
page back out for one written row), elsewhere a scatter. The new token
is a few KB, but the scatter was not: its layout made XLA copy the whole
pool at least twice per array per program, which is why plain pools no
longer take it on the chip.

A pure-jnp reference (``fused_block_decode_ref``) is bit-compatible with
the UNFUSED op chain the models execute (same primitive composition and
dtypes) — it is the CPU-CI path and the parity oracle for the kernel.
Interpret mode proves numerics; that Mosaic compiles both kernels at
Llama-2-7B and GQA 32/8 widths across the batch ladder is pinned by
tests/test_chip_compile.py (see "Mosaic-provable indexing" below for the
layout rule that makes it so).
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.tile_geometry import LANES as _LANES
from ..analysis.tile_geometry import tile as _tile
from .paged_attention import (QuantizedPages, paged_attention_xla,
                              write_paged_kv)

_NEG_INF = -1e30
_SUB = 8    # f32 sublane tile: the batch-row window phase A works on

__all__ = ["BlockDecodeWeights", "Int4Tiles", "MultiBlockDecodeWeights",
           "fused_block_decode", "fused_block_decode_pallas",
           "fused_block_decode_ref", "fused_multi_block_decode",
           "fused_multi_block_decode_pallas", "fused_multi_block_decode_ref",
           "fused_multi_block_decode_tp", "pack_int4_tiles",
           "shard_block_weights", "stack_block_weights",
           "unpack_int4_tiles"]


class BlockDecodeWeights(NamedTuple):
    """One decoder layer's weights in the (in, out) Linear layout the
    models use. A NamedTuple (= pytree) so a whole layer threads through
    jit as one argument."""
    ln1: Any        # (H,)       input rms_norm weight
    wq: Any         # (H, nh*d)
    wk: Any         # (H, nkv*d)
    wv: Any         # (H, nkv*d)
    wo: Any         # (nh*d, H)
    ln2: Any        # (H,)       post-attention rms_norm weight
    wg: Any         # (H, I)     SwiGLU gate
    wu: Any         # (H, I)     SwiGLU up
    wd: Any         # (I, H)     SwiGLU down


def _rope_tables(seq_lens: jax.Array, d: int, theta: float):
    """Per-slot decode rotary tables at positions ``seq_lens`` — the
    direct compute of incubate's fused_rotary_position_embedding
    (position_ids branch): (sin, cos), each (B, d) float32."""
    pos = jnp.asarray(seq_lens, jnp.int32).astype(jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos[:, None] * inv                       # (B, d/2)
    emb = jnp.concatenate([freqs, freqs], axis=-1)   # (B, d)
    return jnp.sin(emb), jnp.cos(emb)


def _rms(x, w, eps):
    """F.rms_norm's exact composition (f32 moments, cast, then scale in
    the activation dtype) so the fused path matches the unfused chain."""
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    out = (h * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return out * w.astype(x.dtype)


def _rope_heads(t, sin, cos):
    """Neox rotate-half at per-row angles; sin/cos (B, d) f32, applied in
    the activation dtype (the unfused chain's cast point)."""
    c = cos[:, None, :].astype(t.dtype)
    s = sin[:, None, :].astype(t.dtype)
    t1, t2 = jnp.split(t, 2, axis=-1)
    rot = jnp.concatenate([-t2, t1], axis=-1)
    return t * c + rot * s


def fused_block_decode_ref(x, weights: BlockDecodeWeights, k_pages, v_pages,
                           block_tables, seq_lens, *, num_heads: int,
                           num_kv_heads: int, rope_theta: float = 10000.0,
                           epsilon: float = 1e-6,
                           sm_scale: Optional[float] = None):
    """Pure-jnp fused block step — primitive-for-primitive the unfused
    chain (LlamaDecoderLayer over the paged cache), composed in one
    function so XLA fuses what it can. CPU-CI path and parity oracle."""
    b, hidden = x.shape
    d = weights.wq.shape[1] // num_heads
    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)

    h = _rms(x, weights.ln1, epsilon)
    q = (h @ weights.wq).reshape(b, num_heads, d)
    k = (h @ weights.wk).reshape(b, num_kv_heads, d)
    v = (h @ weights.wv).reshape(b, num_kv_heads, d)
    sin, cos = _rope_tables(sl, d, rope_theta)
    q = _rope_heads(q, sin, cos)
    k = _rope_heads(k, sin, cos)

    k_pages, v_pages = write_paged_kv(k_pages, v_pages, k, v, bt, sl)
    attn = paged_attention_xla(q, k_pages, v_pages, bt, sl + 1, sm_scale)

    x2 = x + attn.reshape(b, num_heads * d) @ weights.wo
    h2 = _rms(x2, weights.ln2, epsilon)
    f = jax.nn.silu(h2 @ weights.wg) * (h2 @ weights.wu)
    out = x2 + f @ weights.wd
    return out, k_pages, v_pages


# --------------------------------------------------------------- tiling
# Block tiling (``_tile``) and the lane constant come from the shared
# geometry module (analysis/tile_geometry.py) — the memwatch planner
# and the kernelcheck lint derive VMEM pricing from the same source.


def _f32_dot(a, b):
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fake_quant_rows(u):
    """In-VMEM int8 fake-quantize of the new token's k/v fold (per-row
    amax, the same math as ``quantize_kv_rows``): the ref path WRITES
    the quantized token then attends, so the kernel's fold must attend
    to exactly the value a pool re-read would dequantize to."""
    amax = jnp.max(jnp.abs(u), axis=1, keepdims=True)
    sc = amax / 127.0
    safe = jnp.where(sc > 0, sc, 1.0)
    return jnp.clip(jnp.round(u / safe), -127.0, 127.0) * sc


# ------------------------------------------------------ int4 weight tiles
class Int4Tiles(NamedTuple):
    """A stacked weight matrix packed two int4 values per byte with
    per-tile f32 amax scales. Packing is ROW-paired within each
    (tr, tc) tile: payload row ``r*tr/2 + i`` of row-band ``r`` holds
    tile rows ``i`` (low nibble) and ``i + tr/2`` (high nibble), so a
    kernel block of ``(1, tr/2, tc)`` packed rows unpacks to exactly one
    ``(tr, tc)`` weight tile by a sublane concat — MXU-friendly, no
    cross-block shuffles. A NamedTuple (= pytree) so it rides jit as one
    argument like the bf16 stacks; tiling is DERIVED from the q/scale
    shapes (never stored — stored ints would become traced pytree
    leaves). ``shape`` reports the logical unpacked (n, R, C)."""
    q: Any      # uint8 (n, R/2, C)
    scale: Any  # f32   (n, R/tr, C/tc)

    @property
    def shape(self):
        return (self.q.shape[0], 2 * self.q.shape[1], self.q.shape[2])


def pack_int4_tiles(w, tr: int, tc: int) -> Int4Tiles:
    """Quantize ``w`` (n, R, C) to symmetric int4 ([-7, 7]) with one
    amax scale per (tr, tc) tile, nibble-packing each tile's row halves
    (see :class:`Int4Tiles` for the layout)."""
    n, rows, cols = w.shape
    if tr % 2 or rows % tr or cols % tc:
        raise ValueError(f"int4 tile ({tr}, {tc}) must be even-rowed and "
                         f"divide ({rows}, {cols})")
    nr, nc = rows // tr, cols // tc
    t = w.astype(jnp.float32).reshape(n, nr, tr, nc, tc)
    amax = jnp.max(jnp.abs(t), axis=(2, 4), keepdims=True)
    scale = amax / 7.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(t / safe), -7, 7).astype(jnp.int8)
    lo, hi = q[:, :, :tr // 2], q[:, :, tr // 2:]
    packed = ((lo & 0xF).astype(jnp.uint8)
              | ((hi & 0xF).astype(jnp.uint8) << 4))
    return Int4Tiles(packed.reshape(n, rows // 2, cols),
                     scale.reshape(n, nr, nc))


def unpack_int4_tiles(t: Int4Tiles):
    """Dequantize back to f32 (n, R, C) — the pure-jnp reference the
    in-kernel unpack is exactness-tested against, and the up-front
    dequant the N-layer REF path runs (elementwise identical to the
    kernel's tile-wise dequant, so ref/kernel parity is unaffected)."""
    q, scale = t.q, t.scale
    n, half_rows, cols = q.shape
    nr, nc = scale.shape[1], scale.shape[2]
    tr2, tc = half_rows // nr, cols // nc
    p = q.reshape(n, nr, tr2, nc, tc).astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo < 8, lo, lo - 16)
    hi = jnp.where(hi < 8, hi, hi - 16)
    full = jnp.concatenate([lo, hi], axis=2).astype(jnp.float32)
    full = full * scale[:, :, None, :, None]
    return full.reshape(n, 2 * half_rows, cols)


def _int4_plan(hidden: int, qw: int, kvw: int, inter: int) -> dict:
    """The (tr, tc) tile per stacked matrix — the SAME ``_tile`` calls
    :func:`fused_multi_block_decode_pallas` makes, shared so pack time
    and kernel time can never disagree on tiling."""
    plan = {
        "wqkv": (_tile(hidden, 512), _tile(qw + 2 * kvw, 256)),
        "wo": (_tile(qw, 512), _tile(hidden, 256)),
        # wgu packs as ONE (n, H, 2I) matrix tiled tc_f: tc_f divides I,
        # so no tile straddles the gate|up column boundary and the
        # kernel's two col-offset views stay tile-aligned
        "wgu": (_tile(hidden, 512), _tile(inter, 256)),
        "wd": (_tile(inter, 512), _tile(hidden, 256)),
    }
    for name, (tr, _tc) in plan.items():
        if tr % 2:
            raise ValueError(f"int4 weights need an even contraction "
                             f"tile; {name} got tr={tr}")
    return plan


# ------------------------------------------------ Mosaic-provable indexing
# Mosaic refuses a dynamic index on a TILED dim (sublane or lane) unless
# it can prove the index tile-aligned; a dynamic index on a LEADING dim
# is always fine. So per-head tensors live HEAD-MAJOR in VMEM —
# (heads, b_pad, d): the head picked at run time is a leading index —
# the batch row is reached through its aligned 8-row sublane window plus
# a row mask, and lane windows of the 2-D carries are declared aligned.


def _check_head_tiles(d: int, **tiles: int) -> None:
    """Matmul tiles over a head axis scatter to / gather from the
    head-major scratch, so each must hold whole heads (true whenever
    ``head_dim`` divides the 128-lane tile or the tile itself)."""
    bad = {k: v for k, v in tiles.items() if v % d}
    if bad:
        raise ValueError(
            f"fused block decode: head_dim {d} does not divide the "
            f"head-axis tiles {bad}; this shape needs the unfused path")


def _cols(idx, size: int):
    """The ``idx``-th ``size``-wide lane window of a 2-D carry. Phase
    tiles are lane-tile multiples wherever the dim allows
    (``tile_geometry.tile``), which Mosaic has to be told."""
    start = idx * size
    if size % _LANES == 0:
        start = pl.multiple_of(start, size)
    return pl.ds(start, size)


def _scatter_heads(ref, c, acc, d: int):
    """Emit matmul column tile ``c`` (b_pad, k*d) into the head-major
    scratch ``ref`` (heads, b_pad, d), one static lane slice per head."""
    per = acc.shape[1] // d
    for i in range(per):
        ref[c * per + i] = acc[:, i * d:(i + 1) * d]


def _gather_heads(ref, r, per: int):
    """Contraction tile ``r`` of a head-major scratch as (b_pad, per*d)."""
    return jnp.concatenate([ref[r * per + i] for i in range(per)], axis=1)


def _rope_heads_inplace(ref, n_heads: int, sin, cos):
    """Neox rotate-half on ``ref[head]`` (b_pad, d), heads [0, n_heads)."""
    half = sin.shape[1] // 2
    for head in range(n_heads):
        u = ref[head]
        rot = jnp.concatenate([-u[:, half:], u[:, :half]], axis=1)
        ref[head] = u * cos + rot * sin


def _online_softmax(s, pv, am_ref, mm_ref, ll_ref):
    """One online-softmax update of the (rows, d) accumulator with scores
    ``s`` (rows, n); ``pv(p)`` is the weighted-value term for ``p``."""
    m_prev = mm_ref[:, 0:1]
    l_prev = ll_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_new = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    ll_ref[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), ll_ref.shape)
    mm_ref[...] = jnp.broadcast_to(m_new, mm_ref.shape)
    am_ref[...] = alpha * am_ref[...] + pv(p)


def _attn_phase(D, local_a, in_a, sl_ref, q_ref, kn_ref, vn_ref, q0: int,
                kn0: int, vn0: int, gates, pools, ao_ref, am_ref, mm_ref,
                ll_ref, new_dtype):
    """Phase A of both fused kernels: paged attention of one (slot,
    kv-head) over one pool page per grid step, this step's own token
    folded from VMEM at the slot's last valid page, the result emitted
    into the head-major ``ao_ref``.

    ``q_ref[q0 + head]``, ``kn_ref[kn0 + kv_head]`` and
    ``vn_ref[vn0 + kv_head]`` are (b_pad, d). The softmax state covers
    the slot's whole 8-row sublane window x ``rep`` query heads (row
    ``r*8 + i`` = head ``r``, window row ``i``); rows of the other slots
    in the window are computed and discarded — the MXU pads to 8 rows
    regardless, and every index stays provably aligned. ``pools[m]`` is
    layer ``m``'s operand group, read while ``gates[m]`` holds."""
    nkv, d, rep = D["nkv"], D["d"], D["rep"]
    page, mp, scale = D["page"], D["mp"], D["scale"]
    j = local_a % mp
    bh = local_a // mp
    h_i = bh % nkv
    b_i = bh // nkv
    win = pl.ds(pl.multiple_of((b_i // _SUB) * _SUB, _SUB), _SUB)
    seq = sl_ref[b_i]
    n_pages = jnp.maximum((seq + page - 1) // page, 1)
    rows = rep * _SUB

    @pl.when(in_a & (j == 0))
    def _attn_init():
        am_ref[...] = jnp.zeros_like(am_ref)
        mm_ref[...] = jnp.full_like(mm_ref, _NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    def _page(kp_ref, vp_ref, kps_ref=None, vps_ref=None):
        q = q_ref[pl.ds(q0 + h_i * rep, rep), win, :].reshape(rows, d)
        k = kp_ref[0, 0].astype(jnp.float32)           # (page, d)
        v = vp_ref[0, 0].astype(jnp.float32)
        if kps_ref is not None:
            k = k * kps_ref[0, 0]                      # (page, d)*(page, 1)
            v = v * vps_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        _online_softmax(
            jnp.where(pos < seq, s, _NEG_INF),
            lambda p: jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
            am_ref, mm_ref, ll_ref)

        # the token computed THIS step attends too: fold its k/v straight
        # from VMEM at the row's last valid page — the pool append happens
        # after the kernel, off the critical path
        @pl.when(j == n_pages - 1)
        def _attn_new_token():
            kn = kn_ref[kn0 + h_i, win, :]                  # (8, d)
            vn = vn_ref[vn0 + h_i, win, :]
            if kps_ref is not None:
                # match the post-kernel quantized pool write: round-trip
                # through the emit dtype (what write_paged_kv will see),
                # then fake-quantize to the value a re-read dequantizes to
                kn = _fake_quant_rows(
                    kn.astype(new_dtype).astype(jnp.float32))
                vn = _fake_quant_rows(
                    vn.astype(new_dtype).astype(jnp.float32))
            # row r*8+i meets window row i's own new token
            kn = jnp.concatenate([kn] * rep, axis=0)
            vn = jnp.concatenate([vn] * rep, axis=0)
            s_new = jnp.sum(q * kn, axis=1, keepdims=True) * scale
            _online_softmax(s_new, lambda p: p * vn,
                            am_ref, mm_ref, ll_ref)

    for gate, refs in zip(gates, pools):
        @pl.when(in_a & gate & (j < n_pages))
        def _attn_page(refs=refs):
            _page(*refs)

    @pl.when(in_a & (j == mp - 1))
    def _attn_emit():
        l = ll_ref[:, 0:1]
        o = am_ref[...] / jnp.where(l == 0.0, 1.0, l)
        mine = jax.lax.broadcasted_iota(
            jnp.int32, (_SUB, 1), 0) == b_i % _SUB
        for r in range(rep):
            head = h_i * rep + r
            ao_ref[head, win, :] = jnp.where(
                mine, o[r * _SUB:(r + 1) * _SUB], ao_ref[head, win, :])


def _fused_block_kernel(
        bt_ref, sl_ref,                                   # scalar prefetch
        x_ref, ln1_ref, ln2_ref, wq_ref, wk_ref, wv_ref, sin_ref, cos_ref,
        wo_ref, wg_ref, wu_ref, wd_ref, *rest,            # pools/outs/scratch
        dims: dict):
    D = dims
    # quantized pools ride as (payload, payload, scale, scale) operands;
    # everything after is (out, knew, vnew) then the 12 scratch refs
    n_pool = 4 if D["kv_quant"] else 2
    pool_refs, rest = rest[:n_pool], rest[n_pool:]
    out_ref, knew_ref, vnew_ref = rest[:3]
    (h_ref, q_ref, k_ref, v_ref, ao_ref, x2_ref, fs_ref,
     acc_a, acc_b, am_ref, mm_ref, ll_ref) = rest[3:]
    nh, nkv, d = D["nh"], D["nkv"], D["d"]
    eps = D["eps"]
    t = pl.program_id(0)

    # ---------------------------------------------- t == 0: pre-attn norm
    @pl.when(t == 0)
    def _init():
        xv = x_ref[:].astype(jnp.float32)
        var = jnp.mean(xv * xv, axis=-1, keepdims=True)
        h_ref[:] = (xv * jax.lax.rsqrt(var + eps)
                    * ln1_ref[:].astype(jnp.float32))
        ao_ref[...] = jnp.zeros_like(ao_ref)

    # ------------------------------------------------ shared matmul phase
    def _mm(local, n_r, tc, src, w_ref, emit):
        c = local // n_r
        r = local % n_r

        @pl.when(r == 0)
        def _zero():
            acc_a[:, :tc] = jnp.zeros_like(acc_a[:, :tc])

        acc_a[:, :tc] += _f32_dot(src(r), w_ref[:])

        @pl.when(r == n_r - 1)
        def _emit():
            emit(c, acc_a[:, :tc])

    def _h_tile(r):
        return h_ref[:, _cols(r, D["tr_h"])]

    # Q / K / V projections out of the VMEM-resident normed activation
    @pl.when((t >= D["off_q"]) & (t < D["off_k"]))
    def _q():
        _mm(t - D["off_q"], D["nr_h"], D["tc_q"], _h_tile, wq_ref,
            lambda c, acc: _scatter_heads(q_ref, c, acc, d))

    @pl.when((t >= D["off_k"]) & (t < D["off_v"]))
    def _k():
        _mm(t - D["off_k"], D["nr_h"], D["tc_kv"], _h_tile, wk_ref,
            lambda c, acc: _scatter_heads(k_ref, c, acc, d))

    @pl.when((t >= D["off_v"]) & (t < D["off_r"]))
    def _v():
        _mm(t - D["off_v"], D["nr_h"], D["tc_kv"], _h_tile, wv_ref,
            lambda c, acc: _scatter_heads(v_ref, c, acc, d))

    # ------------------------------------- R: in-VMEM rope + k/v emission
    @pl.when(t == D["off_r"])
    def _rope():
        sin = sin_ref[:]
        cos = cos_ref[:]
        _rope_heads_inplace(q_ref, nh, sin, cos)
        _rope_heads_inplace(k_ref, nkv, sin, cos)
        knew_ref[...] = k_ref[...].astype(knew_ref.dtype)
        vnew_ref[...] = v_ref[...].astype(vnew_ref.dtype)

    # --------------------------------------- A: paged attention, by page
    _attn_phase(D, jnp.clip(t - D["off_a"], 0, D["steps_a"] - 1),
                (t >= D["off_a"]) & (t < D["off_o"]), sl_ref,
                q_ref, k_ref, v_ref, 0, 0, 0, [True], [pool_refs],
                ao_ref, am_ref, mm_ref, ll_ref, knew_ref.dtype)

    # ------------------------------- O: out-projection + first residual
    @pl.when((t >= D["off_o"]) & (t < D["off_f"]))
    def _o():
        def emit(c, acc):
            cols = _cols(c, D["tc_o"])
            x2_ref[:, cols] = x_ref[:, cols].astype(jnp.float32) + acc

        _mm(t - D["off_o"], D["nr_o"], D["tc_o"],
            lambda r: _gather_heads(ao_ref, r, D["tr_o"] // d),
            wo_ref, emit)

    # ------------------------------------- F: ffn norm + SwiGLU gate/up
    in_f = (t >= D["off_f"]) & (t < D["off_d"])
    local_f = jnp.clip(t - D["off_f"], 0, D["steps_f"] - 1)

    @pl.when(in_f & (local_f == 0))
    def _ffn_norm():
        xv = x2_ref[:]
        var = jnp.mean(xv * xv, axis=-1, keepdims=True)
        h_ref[:] = (xv * jax.lax.rsqrt(var + eps)
                    * ln2_ref[:].astype(jnp.float32))

    @pl.when(in_f)
    def _f():
        tc = D["tc_f"]
        c = local_f // D["nr_h"]
        r = local_f % D["nr_h"]

        @pl.when(r == 0)
        def _zero():
            acc_a[:, :tc] = jnp.zeros_like(acc_a[:, :tc])
            acc_b[:, :tc] = jnp.zeros_like(acc_b[:, :tc])

        src = _h_tile(r)
        acc_a[:, :tc] += _f32_dot(src, wg_ref[:])
        acc_b[:, :tc] += _f32_dot(src, wu_ref[:])

        @pl.when(r == D["nr_h"] - 1)
        def _emit():
            g = acc_a[:, :tc]
            fs_ref[:, _cols(c, tc)] = jax.nn.silu(g) * acc_b[:, :tc]

    # ---------------------------- D: down-projection + second residual
    @pl.when(t >= D["off_d"])
    def _d():
        def emit(c, acc):
            x2 = x2_ref[:, _cols(c, D["tc_d"])]
            out_ref[:, :] = (x2 + acc).astype(out_ref.dtype)

        _mm(t - D["off_d"], D["nr_i"], D["tc_d"],
            lambda r: fs_ref[:, _cols(r, D["tr_i"])], wd_ref, emit)


def fused_block_decode_pallas(x, weights: BlockDecodeWeights, k_pages,
                              v_pages, block_tables, seq_lens, *,
                              num_heads: int, num_kv_heads: int,
                              rope_theta: float = 10000.0,
                              epsilon: float = 1e-6,
                              sm_scale: Optional[float] = None,
                              interpret: Optional[bool] = None):
    """One-kernel block decode step (see module docstring).

    x:            (B, H) — one token's hidden state per slot
    k/v_pages:    (Hkv, num_pages, page, D) shared pools
    block_tables: (B, max_pages) int32; seq_lens: (B,) int32
    Returns ``(out, k_pages, v_pages)`` with the new token appended.
    """
    if interpret is None:
        from ..flags import is_tpu_backend
        interpret = not is_tpu_backend()
    b, hidden = x.shape
    nh, nkv = num_heads, num_kv_heads
    if nh % nkv:
        raise ValueError(f"query heads {nh} not divisible by kv heads {nkv}")
    d = weights.wq.shape[1] // nh
    rep = nh // nkv
    page = k_pages.shape[2]
    mp = block_tables.shape[1]
    inter = weights.wg.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    b_pad = -(-b // _SUB) * _SUB
    rep_rows = rep * _SUB

    sin, cos = _rope_tables(sl, d, rope_theta)
    if b_pad != b:
        pad = [(0, b_pad - b), (0, 0)]
        x_p = jnp.pad(x, pad)
        sin, cos = jnp.pad(sin, pad), jnp.pad(cos, pad)
        bt_p = jnp.pad(bt, pad)
        sl_p = jnp.pad(sl, (0, b_pad - b))
    else:
        x_p, bt_p, sl_p = x, bt, sl

    # tile sizes: contraction x output tiling keeps any one weight block
    # (plus its double buffer) a small slice of VMEM while activations
    # stay resident; divisor snapping keeps odd dims correct
    tr_h = _tile(hidden, 512)       # H-contraction rows (Q/K/V/F)
    tr_o = _tile(nh * d, 512)       # attn-out contraction rows (O)
    tr_i = _tile(inter, 512)        # FFN contraction rows (D)
    tc_q = _tile(nh * d, 256)
    tc_kv = _tile(nkv * d, 256)
    _check_head_tiles(d, tr_o=tr_o, tc_q=tc_q, tc_kv=tc_kv)
    tc_o = _tile(hidden, 256)
    tc_f = _tile(inter, 256)
    tc_d = _tile(hidden, 256)
    tc_max = max(tc_q, tc_kv, tc_o, tc_f, tc_d)

    nr_h = hidden // tr_h
    nr_o = (nh * d) // tr_o
    nr_i = inter // tr_i
    steps_q = nr_h * ((nh * d) // tc_q)
    steps_kv = nr_h * ((nkv * d) // tc_kv)
    steps_a = b_pad * nkv * mp
    steps_o = nr_o * (hidden // tc_o)
    steps_f = nr_h * (inter // tc_f)
    steps_d = nr_i * (hidden // tc_d)

    off_q = 0
    off_k = off_q + steps_q
    off_v = off_k + steps_kv
    off_r = off_v + steps_kv
    off_a = off_r + 1
    off_o = off_a + steps_a
    off_f = off_o + steps_o
    off_d = off_f + steps_f
    total = off_d + steps_d

    kv_quant = isinstance(k_pages, QuantizedPages)
    dims = dict(nh=nh, nkv=nkv, d=d, rep=rep, page=page, mp=mp,
                eps=float(epsilon), scale=float(sm_scale),
                tr_h=tr_h, tr_o=tr_o, tr_i=tr_i, tc_q=tc_q, tc_kv=tc_kv,
                tc_o=tc_o, tc_f=tc_f, tc_d=tc_d, nr_h=nr_h, nr_o=nr_o,
                nr_i=nr_i, steps_a=steps_a, steps_f=steps_f,
                off_q=off_q, off_k=off_k, off_v=off_v, off_r=off_r,
                off_a=off_a, off_o=off_o, off_f=off_f, off_d=off_d,
                kv_quant=kv_quant)

    def _const(*_args):
        return (0, 0)

    def _const3(*_args):
        return (0, 0, 0)

    def _phase_map(off, steps, n_r):
        def index(t, bt_ref, sl_ref):
            local = jnp.clip(t - off, 0, steps - 1)
            return (local % n_r, local // n_r)
        return index

    def _kp_map(t, bt_ref, sl_ref):
        local = jnp.clip(t - off_a, 0, steps_a - 1)
        jj = local % mp
        bh = local // mp
        return (bh % nkv, bt_ref[bh // nkv, jj], 0, 0)

    def _out_map(t, bt_ref, sl_ref):
        local = jnp.clip(t - off_d, 0, steps_d - 1)
        return (0, local // nr_i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(total,),
        in_specs=[
            pl.BlockSpec((b_pad, hidden), _const),                  # x
            pl.BlockSpec((1, hidden), _const),                      # ln1
            pl.BlockSpec((1, hidden), _const),                      # ln2
            pl.BlockSpec((tr_h, tc_q),
                         _phase_map(off_q, steps_q, nr_h)),         # wq
            pl.BlockSpec((tr_h, tc_kv),
                         _phase_map(off_k, steps_kv, nr_h)),        # wk
            pl.BlockSpec((tr_h, tc_kv),
                         _phase_map(off_v, steps_kv, nr_h)),        # wv
            pl.BlockSpec((b_pad, d), _const),                       # sin
            pl.BlockSpec((b_pad, d), _const),                       # cos
            pl.BlockSpec((tr_o, tc_o),
                         _phase_map(off_o, steps_o, nr_o)),         # wo
            pl.BlockSpec((tr_h, tc_f),
                         _phase_map(off_f, steps_f, nr_h)),         # wg
            pl.BlockSpec((tr_h, tc_f),
                         _phase_map(off_f, steps_f, nr_h)),         # wu
            pl.BlockSpec((tr_i, tc_d),
                         _phase_map(off_d, steps_d, nr_i)),         # wd
        ] + [
            pl.BlockSpec((1, 1, page, d), _kp_map),                 # k_pages
            pl.BlockSpec((1, 1, page, d), _kp_map),                 # v_pages
        ] + ([
            # int8 KV scale: ONE value per token row is the quant
            # contract; a 128-wide block would DMA 127 dead lanes
            # kernelcheck: disable=KRN001
            pl.BlockSpec((1, 1, page, 1), _kp_map),                 # k scale
            # kernelcheck: disable=KRN001
            pl.BlockSpec((1, 1, page, 1), _kp_map),                 # v scale
        ] if kv_quant else []),
        out_specs=[
            pl.BlockSpec((b_pad, tc_d), _out_map),                  # out
            pl.BlockSpec((nkv, b_pad, d), _const3),                 # k_new
            pl.BlockSpec((nkv, b_pad, d), _const3),                 # v_new
        ],
        scratch_shapes=[
            pltpu.VMEM((b_pad, hidden), jnp.float32),     # h (normed)
            pltpu.VMEM((nh, b_pad, d), jnp.float32),      # q
            pltpu.VMEM((nkv, b_pad, d), jnp.float32),     # k_new
            pltpu.VMEM((nkv, b_pad, d), jnp.float32),     # v_new
            pltpu.VMEM((nh, b_pad, d), jnp.float32),      # attn out
            pltpu.VMEM((b_pad, hidden), jnp.float32),     # x2 (residual)
            pltpu.VMEM((b_pad, inter), jnp.float32),      # silu(g)*u
            pltpu.VMEM((b_pad, tc_max), jnp.float32),     # acc a
            pltpu.VMEM((b_pad, tc_max), jnp.float32),     # acc b
            pltpu.VMEM((rep_rows, d), jnp.float32),       # attn acc
            pltpu.VMEM((rep_rows, _LANES), jnp.float32),  # attn m
            pltpu.VMEM((rep_rows, _LANES), jnp.float32),  # attn l
        ],
    )

    pool_ops = ([k_pages.q, v_pages.q, k_pages.scale, v_pages.scale]
                if kv_quant else [k_pages, v_pages])
    out, k_new, v_new = pl.pallas_call(
        functools.partial(_fused_block_kernel, dims=dims),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, hidden), x.dtype),
            jax.ShapeDtypeStruct((nkv, b_pad, d), x.dtype),
            jax.ShapeDtypeStruct((nkv, b_pad, d), x.dtype),
        ],
        interpret=interpret,
        name="fused_block_decode",
    )(bt_p, sl_p, x_p, weights.ln1.reshape(1, hidden),
      weights.ln2.reshape(1, hidden), weights.wq, weights.wk, weights.wv,
      sin, cos, weights.wo, weights.wg, weights.wu, weights.wd,
      *pool_ops)

    k_pages, v_pages = write_paged_kv(
        k_pages, v_pages, k_new[:, :b].swapaxes(0, 1),
        v_new[:, :b].swapaxes(0, 1), bt, sl)
    return out[:b], k_pages, v_pages


# ===================================================== multi-layer fusion
# r17: N transformer blocks per pallas_call (ClusterFusion++ / FlashFuser
# direction). The grid becomes ``n_layers x per_layer_phases``; the
# stacked weight arrays stream through VMEM with a LAYER-aware index map
# (Pallas double-buffers the next block automatically), the activation
# carries across layers in a VMEM scratch that never touches HBM, and
# the q/k/v (resp. gate/up) projections of each layer are ONE merged
# wider matmul over a concatenated weight (FFN-Fusion's observation:
# sequential same-input matmuls are width-parallel).


class MultiBlockDecodeWeights(NamedTuple):
    """A GROUP of ``n`` decoder layers' weights, stacked on a leading
    layer axis with the width-parallel projections pre-merged:

      ln1   (n, H)
      wqkv  (n, H, (nh + 2*nkv) * d)    q|k|v concatenated on columns
      wo    (n, nh*d, H)
      ln2   (n, H)
      wgu   (n, H, 2*I)                 gate|up concatenated on columns
      wd    (n, I, H)

    Built ONCE per engine by :func:`stack_block_weights` (a host-side
    copy of the layer weights — the per-layer originals keep serving
    prefill/chunk programs) and threaded through jit as a traced
    argument, so the compiled step never bakes weights as constants."""
    ln1: Any
    wqkv: Any
    wo: Any
    ln2: Any
    wgu: Any
    wd: Any

    @property
    def n_layers(self) -> int:
        return int(self.ln1.shape[0])


def stack_block_weights(layers,
                        weight_dtype: str = "native"
                        ) -> MultiBlockDecodeWeights:
    """Stack per-layer :class:`BlockDecodeWeights` into one
    :class:`MultiBlockDecodeWeights` group (merging q|k|v and gate|up on
    the output axis). One-time cost: a device copy of the group's layer
    weights. ``weight_dtype="int4"`` packs the four stacked matmul
    weights as :class:`Int4Tiles` (per-tile amax scales on the kernel's
    own ``_int4_plan`` tiling — halving the group's weight-stream
    traffic); the rms-norm vectors stay native."""
    ws = list(layers)
    out = MultiBlockDecodeWeights(
        ln1=jnp.stack([w.ln1 for w in ws]),
        wqkv=jnp.stack([jnp.concatenate([w.wq, w.wk, w.wv], axis=1)
                        for w in ws]),
        wo=jnp.stack([w.wo for w in ws]),
        ln2=jnp.stack([w.ln2 for w in ws]),
        wgu=jnp.stack([jnp.concatenate([w.wg, w.wu], axis=1)
                       for w in ws]),
        wd=jnp.stack([w.wd for w in ws]))
    if weight_dtype == "native":
        return out
    if weight_dtype != "int4":
        raise ValueError(f"weight_dtype must be 'native' or 'int4', "
                         f"got {weight_dtype!r}")
    hidden = out.ln1.shape[1]
    qw = out.wo.shape[1]
    kvw = (out.wqkv.shape[2] - qw) // 2
    inter = out.wd.shape[1]
    plan = _int4_plan(hidden, qw, kvw, inter)
    return MultiBlockDecodeWeights(
        ln1=out.ln1,
        wqkv=pack_int4_tiles(out.wqkv, *plan["wqkv"]),
        wo=pack_int4_tiles(out.wo, *plan["wo"]),
        ln2=out.ln2,
        wgu=pack_int4_tiles(out.wgu, *plan["wgu"]),
        wd=pack_int4_tiles(out.wd, *plan["wd"]))


def fused_multi_block_decode_ref(x, weights: MultiBlockDecodeWeights,
                                 k_pages, v_pages, block_tables, seq_lens,
                                 *, num_heads: int, num_kv_heads: int,
                                 rope_theta: float = 10000.0,
                                 epsilon: float = 1e-6,
                                 sm_scale: Optional[float] = None):
    """Pure-jnp N-layer fused step over a stacked weight group.
    ``k_pages``/``v_pages`` are SEQUENCES of the group's per-layer pools.
    The layer loop is the per-layer chain of :func:`fused_block_decode_ref`
    except the q/k/v and gate/up projections run as the merged matmuls
    (same contraction per output column, so the split results match the
    separate matmuls bitwise on every backend we test). CPU-CI path and
    the parity oracle for the N-layer kernel."""
    n = int(weights.ln1.shape[0])
    if len(k_pages) != n or len(v_pages) != n:
        raise ValueError(f"expected {n} per-layer pools, got "
                         f"{len(k_pages)}/{len(v_pages)}")
    b, hidden = x.shape
    d = weights.wqkv.shape[2] // (num_heads + 2 * num_kv_heads)
    qw = num_heads * d
    kvw = num_kv_heads * d
    inter = weights.wd.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    sin, cos = _rope_tables(sl, d, rope_theta)

    # int4 groups dequantize up front: unpack is elementwise, so the
    # whole-matrix dequant here equals the kernel's tile-wise dequant
    # value-for-value (the parity contract)
    w_qkv, w_o, w_gu, w_d = (
        unpack_int4_tiles(m) if isinstance(m, Int4Tiles) else m
        for m in (weights.wqkv, weights.wo, weights.wgu, weights.wd))

    kps, vps = list(k_pages), list(v_pages)
    for i in range(n):
        h = _rms(x, weights.ln1[i], epsilon)
        qkv = h @ w_qkv[i]
        q = _rope_heads(qkv[:, :qw].reshape(b, num_heads, d), sin, cos)
        k = _rope_heads(qkv[:, qw:qw + kvw].reshape(b, num_kv_heads, d),
                        sin, cos)
        v = qkv[:, qw + kvw:].reshape(b, num_kv_heads, d)
        kps[i], vps[i] = write_paged_kv(kps[i], vps[i], k, v, bt, sl)
        attn = paged_attention_xla(q, kps[i], vps[i], bt, sl + 1, sm_scale)
        x2 = x + attn.reshape(b, qw) @ w_o[i]
        h2 = _rms(x2, weights.ln2[i], epsilon)
        gu = h2 @ w_gu[i]
        f = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        x = x2 + f @ w_d[i]
    return x, kps, vps


def shard_block_weights(weights: MultiBlockDecodeWeights, tp: int, *,
                        num_heads: int, num_kv_heads: int
                        ) -> MultiBlockDecodeWeights:
    """Permute a stacked group into the tensor-parallel (Megatron) shard
    layout: each of the ``tp`` shards owns a contiguous slice of heads
    and of the FFN intermediate, so a plain even split of the LAST axis
    of wqkv/wgu (and of the MIDDLE axis of wo/wd) hands every shard its
    own locally-merged q|k|v and gate|up blocks.

    The merged matmuls concatenate q|k|v (and gate|up) on columns, so
    shard s's columns are NOT contiguous in the stacked layout — this
    host-side one-time permutation reorders columns shard-major:

      wqkv  [q | k | v]        ->  [q_0|k_0|v_0 | q_1|k_1|v_1 | ...]
      wgu   [gate | up]        ->  [g_0|u_0 | g_1|u_1 | ...]

    wo (rows = nh*d, head-major) and wd (rows = I) are already
    shard-contiguous on their contraction axis, and the rms-norm vectors
    replicate. Int4-packed stacks are refused: the nibble row-pairing and
    per-tile scales of :class:`Int4Tiles` do not commute with the column
    permutation (the planner still prices int4-per-shard analytically)."""
    if tp <= 1:
        return weights
    for name in ("wqkv", "wo", "wgu", "wd"):
        if isinstance(getattr(weights, name), Int4Tiles):
            raise ValueError(
                "shard_block_weights: int4-packed stacks cannot be "
                "resharded (pack after sharding instead); got Int4Tiles "
                f"for {name}")
    d = weights.wqkv.shape[2] // (num_heads + 2 * num_kv_heads)
    inter = weights.wd.shape[1]
    if num_heads % tp or num_kv_heads % tp or inter % tp:
        raise ValueError(
            f"shard_block_weights: heads/kv-heads/intermediate "
            f"({num_heads}/{num_kv_heads}/{inter}) must all divide "
            f"tp={tp}")
    qw = num_heads * d
    kvw = num_kv_heads * d
    cols = np.arange(qw + 2 * kvw)
    q_cols = cols[:qw].reshape(tp, -1)
    k_cols = cols[qw:qw + kvw].reshape(tp, -1)
    v_cols = cols[qw + kvw:].reshape(tp, -1)
    qkv_perm = np.concatenate(
        [np.concatenate([q_cols[s], k_cols[s], v_cols[s]])
         for s in range(tp)])
    gu_cols = np.arange(2 * inter)
    g_cols = gu_cols[:inter].reshape(tp, -1)
    u_cols = gu_cols[inter:].reshape(tp, -1)
    gu_perm = np.concatenate(
        [np.concatenate([g_cols[s], u_cols[s]]) for s in range(tp)])
    return MultiBlockDecodeWeights(
        ln1=weights.ln1,
        wqkv=weights.wqkv[:, :, qkv_perm],
        wo=weights.wo,
        ln2=weights.ln2,
        wgu=weights.wgu[:, :, gu_perm],
        wd=weights.wd)


def fused_multi_block_decode_tp(x, weights: MultiBlockDecodeWeights,
                                k_pages, v_pages, block_tables, seq_lens,
                                *, num_heads: int, num_kv_heads: int,
                                rope_theta: float = 10000.0,
                                epsilon: float = 1e-6,
                                axis_name: str = "mp",
                                sm_scale: Optional[float] = None):
    """Per-SHARD N-layer fused step for the ``shard_map`` decode body.

    ``num_heads``/``num_kv_heads`` are the LOCAL (per-shard) head
    counts; ``weights`` is the local column/row shard produced by
    :func:`shard_block_weights` + an even split, and the pools are the
    local kv-head partition. The chain is exactly
    :func:`fused_multi_block_decode_ref` per shard except the two
    row-parallel exits (wo and wd) each finish with ONE ``psum`` over
    ``axis_name`` — the Megatron minimum of two collectives per layer.
    The residual stream ``x`` stays replicated across shards, so rms
    moments and rope tables are computed identically everywhere."""
    # lazy import: mp_ops pulls the distributed package; the kernel
    # module must stay importable on a bare single-chip runtime
    from ..distributed.fleet.layers.mpu.mp_ops import _mp_allreduce

    n = int(weights.ln1.shape[0])
    if len(k_pages) != n or len(v_pages) != n:
        raise ValueError(f"expected {n} per-layer pools, got "
                         f"{len(k_pages)}/{len(v_pages)}")
    b, hidden = x.shape
    d = weights.wqkv.shape[2] // (num_heads + 2 * num_kv_heads)
    qw = num_heads * d
    kvw = num_kv_heads * d
    inter = weights.wd.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    sin, cos = _rope_tables(sl, d, rope_theta)

    kps, vps = list(k_pages), list(v_pages)
    for i in range(n):
        h = _rms(x, weights.ln1[i], epsilon)
        qkv = h @ weights.wqkv[i]
        q = _rope_heads(qkv[:, :qw].reshape(b, num_heads, d), sin, cos)
        k = _rope_heads(qkv[:, qw:qw + kvw].reshape(b, num_kv_heads, d),
                        sin, cos)
        v = qkv[:, qw + kvw:].reshape(b, num_kv_heads, d)
        kps[i], vps[i] = write_paged_kv(kps[i], vps[i], k, v, bt, sl)
        attn = paged_attention_xla(q, kps[i], vps[i], bt, sl + 1, sm_scale)
        x2 = x + _mp_allreduce(attn.reshape(b, qw) @ weights.wo[i],
                               axis_name)
        h2 = _rms(x2, weights.ln2[i], epsilon)
        gu = h2 @ weights.wgu[i]
        f = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        x = x2 + _mp_allreduce(f @ weights.wd[i], axis_name)
    return x, kps, vps


def _fused_multi_block_kernel(bt_ref, sl_ref,                 # scalar prefetch
                              *ops, dims: dict):
    D = dims
    n_layers = D["n_layers"]
    wt = D["wt_quant"]
    # operand order (int4 weights interleave a per-tile scale ref right
    # after their packed payload; quantized pools ride 4 refs per layer
    # instead of 2): x, ln1, ln2, wqkv[, sc], sin, cos, wo[, sc],
    # wg[, sc], wu[, sc], wd[, sc], pools..., outs..., scratch...
    it = iter(ops)
    x_ref, ln1_ref, ln2_ref = next(it), next(it), next(it)
    wqkv_ref = next(it)
    wqkv_sc = next(it) if wt else None
    sin_ref, cos_ref = next(it), next(it)
    wo_ref = next(it)
    wo_sc = next(it) if wt else None
    wg_ref = next(it)
    wg_sc = next(it) if wt else None
    wu_ref = next(it)
    wu_sc = next(it) if wt else None
    wd_ref = next(it)
    wd_sc = next(it) if wt else None
    rest = list(it)
    stride = 4 if D["kv_quant"] else 2
    pool_refs = rest[:stride * n_layers]
    out_ref, knew_ref, vnew_ref = \
        rest[stride * n_layers:stride * n_layers + 3]
    (xc_ref, h_ref, qkv_ref, ao_ref, x2_ref, fs_ref,
     acc_a, acc_b, am_ref, mm_ref, ll_ref) = rest[stride * n_layers + 3:]

    def _load(w_ref, w_sc):
        # packed int4 blocks carry HALF the weight tile's rows; the
        # sublane concat of the two nibble planes rebuilds the (tr, tc)
        # tile in VMEM, scaled by its one per-tile f32 scale — the MXU
        # sees a plain f32 operand, HBM only ever saw 4 bits/weight
        w = w_ref[0]
        if w_sc is None:
            return w
        p = w.astype(jnp.int32)
        lo = p & 0xF
        hi = (p >> 4) & 0xF
        lo = jnp.where(lo < 8, lo, lo - 16)
        hi = jnp.where(hi < 8, hi, hi - 16)
        full = jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
        return full * w_sc[0, 0, 0]

    nh, nkv, d = D["nh"], D["nkv"], D["d"]
    eps = D["eps"]
    per = D["per_layer"]
    t = pl.program_id(0)
    layer = t // per
    lt = t % per

    # -------------------------------- layer start: pre-attn norm of the
    # VMEM-resident activation (layer 0 seeds it from the kernel input)
    @pl.when(lt == 0)
    def _layer_init():
        @pl.when(layer == 0)
        def _seed():
            xc_ref[:] = x_ref[:].astype(jnp.float32)

        xv = xc_ref[:]
        var = jnp.mean(xv * xv, axis=-1, keepdims=True)
        h_ref[:] = (xv * jax.lax.rsqrt(var + eps)
                    * ln1_ref[0].astype(jnp.float32))
        ao_ref[...] = jnp.zeros_like(ao_ref)

    # ------------------------------------------------ shared matmul phase
    def _mm(local, n_r, tc, src, w_ref, emit, w_sc=None):
        c = local // n_r
        r = local % n_r

        @pl.when(r == 0)
        def _zero():
            acc_a[:, :tc] = jnp.zeros_like(acc_a[:, :tc])

        acc_a[:, :tc] += _f32_dot(src(r), _load(w_ref, w_sc))

        @pl.when(r == n_r - 1)
        def _emit():
            emit(c, acc_a[:, :tc])

    def _h_tile(r):
        return h_ref[:, _cols(r, D["tr_h"])]

    # ------- QKV: ONE merged matmul into the head-major q|k|v scratch
    # (heads [0, nh) are q, [nh, nh+nkv) are k, the rest v)
    @pl.when((lt >= D["off_qkv"]) & (lt < D["off_r"]))
    def _qkv():
        _mm(lt - D["off_qkv"], D["nr_h"], D["tc_qkv"], _h_tile, wqkv_ref,
            lambda c, acc: _scatter_heads(qkv_ref, c, acc, d),
            w_sc=wqkv_sc)

    # ------------------------------------- R: in-VMEM rope + k/v emission
    @pl.when(lt == D["off_r"])
    def _rope():
        _rope_heads_inplace(qkv_ref, nh + nkv, sin_ref[:], cos_ref[:])
        knew_ref[0] = qkv_ref[nh:nh + nkv].astype(knew_ref.dtype)
        vnew_ref[0] = qkv_ref[nh + nkv:].astype(vnew_ref.dtype)

    # --------------------------------------- A: paged attention, by page
    # each layer reads ITS pool operand group: the layer gate is unrolled
    # over the static group size so the body indexes a python list, and
    # the operands' index maps freeze inactive layers at page 0 (no
    # spurious refetch mid-phase)
    _attn_phase(D, jnp.clip(lt - D["off_a"], 0, D["steps_a"] - 1),
                (lt >= D["off_a"]) & (lt < D["off_o"]), sl_ref,
                qkv_ref, qkv_ref, qkv_ref, 0, nh, nh + nkv,
                [layer == m for m in range(n_layers)],
                [pool_refs[stride * m:stride * (m + 1)]
                 for m in range(n_layers)],
                ao_ref, am_ref, mm_ref, ll_ref, knew_ref.dtype)

    # ------------------------------- O: out-projection + first residual
    @pl.when((lt >= D["off_o"]) & (lt < D["off_f"]))
    def _o():
        def emit(c, acc):
            cols = _cols(c, D["tc_o"])
            x2_ref[:, cols] = xc_ref[:, cols] + acc

        _mm(lt - D["off_o"], D["nr_o"], D["tc_o"],
            lambda r: _gather_heads(ao_ref, r, D["tr_o"] // d),
            wo_ref, emit, w_sc=wo_sc)

    # --------------------- F: ffn norm + merged gate|up (two col-offset
    # views of the SAME stacked wgu operand feed the paired accumulators)
    in_f = (lt >= D["off_f"]) & (lt < D["off_d"])
    local_f = jnp.clip(lt - D["off_f"], 0, D["steps_f"] - 1)

    @pl.when(in_f & (local_f == 0))
    def _ffn_norm():
        xv = x2_ref[:]
        var = jnp.mean(xv * xv, axis=-1, keepdims=True)
        h_ref[:] = (xv * jax.lax.rsqrt(var + eps)
                    * ln2_ref[0].astype(jnp.float32))

    @pl.when(in_f)
    def _f():
        tc = D["tc_f"]
        c = local_f // D["nr_h"]
        r = local_f % D["nr_h"]

        @pl.when(r == 0)
        def _zero():
            acc_a[:, :tc] = jnp.zeros_like(acc_a[:, :tc])
            acc_b[:, :tc] = jnp.zeros_like(acc_b[:, :tc])

        src = _h_tile(r)
        acc_a[:, :tc] += _f32_dot(src, _load(wg_ref, wg_sc))
        acc_b[:, :tc] += _f32_dot(src, _load(wu_ref, wu_sc))

        @pl.when(r == D["nr_h"] - 1)
        def _emit():
            g = acc_a[:, :tc]
            fs_ref[:, _cols(c, tc)] = jax.nn.silu(g) * acc_b[:, :tc]

    # --------- D: down-projection + second residual. The next layer's
    # activation rounds through the activation dtype (matching the
    # unfused chain's inter-layer cast) back into the VMEM carry; the
    # same tile lands in the kernel output, so the LAST layer's write is
    # the result
    @pl.when(lt >= D["off_d"])
    def _d():
        def emit(c, acc):
            cols = _cols(c, D["tc_d"])
            nxt = (x2_ref[:, cols] + acc).astype(out_ref.dtype)
            out_ref[:, cols] = nxt
            xc_ref[:, cols] = nxt.astype(jnp.float32)

        _mm(lt - D["off_d"], D["nr_i"], D["tc_d"],
            lambda r: fs_ref[:, _cols(r, D["tr_i"])], wd_ref, emit,
            w_sc=wd_sc)


def fused_multi_block_decode_pallas(x, weights: MultiBlockDecodeWeights,
                                    k_pages, v_pages, block_tables,
                                    seq_lens, *, num_heads: int,
                                    num_kv_heads: int,
                                    rope_theta: float = 10000.0,
                                    epsilon: float = 1e-6,
                                    sm_scale: Optional[float] = None,
                                    interpret: Optional[bool] = None):
    """N layers in ONE ``pallas_call`` (see the multi-layer section of
    the module docstring). ``k_pages``/``v_pages`` are sequences of the
    group's per-layer pools; each is its own kernel operand whose index
    map streams pages only while its layer is active. Returns
    ``(out, k_pages_list, v_pages_list)``."""
    if interpret is None:
        from ..flags import is_tpu_backend
        interpret = not is_tpu_backend()
    n_layers = int(weights.ln1.shape[0])
    b, hidden = x.shape
    nh, nkv = num_heads, num_kv_heads
    if nh % nkv:
        raise ValueError(f"query heads {nh} not divisible by kv heads {nkv}")
    d = weights.wqkv.shape[2] // (nh + 2 * nkv)
    rep = nh // nkv
    qw = nh * d
    kvw = nkv * d
    wq_cols = qw + 2 * kvw
    page = k_pages[0].shape[2]
    mp = block_tables.shape[1]
    inter = weights.wd.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    b_pad = -(-b // _SUB) * _SUB
    rep_rows = rep * _SUB

    sin, cos = _rope_tables(sl, d, rope_theta)
    if b_pad != b:
        pad = [(0, b_pad - b), (0, 0)]
        x_p = jnp.pad(x, pad)
        sin, cos = jnp.pad(sin, pad), jnp.pad(cos, pad)
        bt_p = jnp.pad(bt, pad)
        sl_p = jnp.pad(sl, (0, b_pad - b))
    else:
        x_p, bt_p, sl_p = x, bt, sl

    tr_h = _tile(hidden, 512)
    tr_o = _tile(qw, 512)
    tr_i = _tile(inter, 512)
    tc_qkv = _tile(wq_cols, 256)
    _check_head_tiles(d, tr_o=tr_o, tc_qkv=tc_qkv)
    tc_o = _tile(hidden, 256)
    tc_f = _tile(inter, 256)
    tc_d = _tile(hidden, 256)
    tc_max = max(tc_qkv, tc_o, tc_f, tc_d)

    nr_h = hidden // tr_h
    nr_o = qw // tr_o
    nr_i = inter // tr_i
    n_cf = inter // tc_f
    steps_qkv = nr_h * (wq_cols // tc_qkv)
    steps_a = b_pad * nkv * mp
    steps_o = nr_o * (hidden // tc_o)
    steps_f = nr_h * n_cf
    steps_d = nr_i * (hidden // tc_d)

    off_qkv = 0
    off_r = off_qkv + steps_qkv
    off_a = off_r + 1
    off_o = off_a + steps_a
    off_f = off_o + steps_o
    off_d = off_f + steps_f
    per = off_d + steps_d

    kv_quant = isinstance(k_pages[0], QuantizedPages)
    wt_quant = isinstance(weights.wqkv, Int4Tiles)
    dims = dict(n_layers=n_layers, per_layer=per, nh=nh, nkv=nkv, d=d,
                rep=rep, page=page, mp=mp, eps=float(epsilon),
                scale=float(sm_scale), tr_h=tr_h, tr_o=tr_o, tr_i=tr_i,
                tc_qkv=tc_qkv, tc_o=tc_o, tc_f=tc_f, tc_d=tc_d,
                nr_h=nr_h, nr_o=nr_o, nr_i=nr_i, steps_a=steps_a,
                steps_f=steps_f, off_qkv=off_qkv, off_r=off_r,
                off_a=off_a, off_o=off_o, off_f=off_f, off_d=off_d,
                kv_quant=kv_quant, wt_quant=wt_quant)

    def _const(*_args):
        return (0, 0)

    def _ln_map(t, bt_ref, sl_ref):
        return (t // per, 0, 0)

    def _phase_map(off, steps, n_r):
        def index(t, bt_ref, sl_ref):
            local = jnp.clip(t % per - off, 0, steps - 1)
            return (t // per, local % n_r, local // n_r)
        return index

    def _up_map(t, bt_ref, sl_ref):
        local = jnp.clip(t % per - off_f, 0, steps_f - 1)
        return (t // per, local % nr_h, n_cf + local // nr_h)

    def _kp_map(m):
        def index(t, bt_ref, sl_ref):
            active = (t // per) == m
            local = jnp.clip(t % per - off_a, 0, steps_a - 1)
            jj = local % mp
            bh = local // mp
            return (jnp.where(active, bh % nkv, 0),
                    jnp.where(active, bt_ref[bh // nkv, jj], 0), 0, 0)
        return index

    def _kv_out_map(t, bt_ref, sl_ref):
        return (t // per, 0, 0, 0)

    # int4 weights stream HALF-row packed payload blocks, each chased by
    # its (1, 1, 1) per-tile scale under the SAME index map (block index
    # == scale element index); the map itself never changes, so the
    # phase schedule is identical to the native-dtype program's
    def _wrows(tr):
        return tr // 2 if wt_quant else tr

    # the stacked (n, H) norm weights ride a unit middle axis: a
    # (1, H) block of an (n, H) array breaks Mosaic's second-minor rule,
    # a (1, 1, H) block of (n, 1, H) spans the tiled dims whole
    in_specs = [
        pl.BlockSpec((b_pad, hidden), _const),                      # x
        pl.BlockSpec((1, 1, hidden), _ln_map),                      # ln1
        pl.BlockSpec((1, 1, hidden), _ln_map),                      # ln2
    ]
    operands = [bt_p, sl_p, x_p, weights.ln1[:, None, :],
                weights.ln2[:, None, :]]

    def _weight(w, spec, imap):
        in_specs.append(spec)
        if wt_quant:
            operands.append(w.q)
            # int4 tile scale: one scalar per (row, col) weight tile by
            # design, given unit tiled dims for the same block rule
            # kernelcheck: disable=KRN001
            sc_spec = pl.BlockSpec((1, 1, 1, 1, 1),
                                   lambda *a: imap(*a) + (0, 0))
            in_specs.append(sc_spec)
            operands.append(w.scale[..., None, None])
        else:
            operands.append(w)

    qkv_map = _phase_map(off_qkv, steps_qkv, nr_h)
    _weight(weights.wqkv,
            pl.BlockSpec((1, _wrows(tr_h), tc_qkv), qkv_map), qkv_map)
    in_specs += [
        pl.BlockSpec((b_pad, d), _const),                           # sin
        pl.BlockSpec((b_pad, d), _const),                           # cos
    ]
    operands += [sin, cos]
    o_map = _phase_map(off_o, steps_o, nr_o)
    _weight(weights.wo, pl.BlockSpec((1, _wrows(tr_o), tc_o), o_map),
            o_map)
    g_map = _phase_map(off_f, steps_f, nr_h)
    _weight(weights.wgu, pl.BlockSpec((1, _wrows(tr_h), tc_f), g_map),
            g_map)                                                  # gate
    _weight(weights.wgu, pl.BlockSpec((1, _wrows(tr_h), tc_f), _up_map),
            _up_map)                                                # up
    d_map = _phase_map(off_d, steps_d, nr_i)
    _weight(weights.wd, pl.BlockSpec((1, _wrows(tr_i), tc_d), d_map),
            d_map)

    for kp, vp in zip(k_pages, v_pages):
        if kv_quant:
            operands += [kp.q, vp.q, kp.scale, vp.scale]
        else:
            operands += [kp, vp]
    for m in range(n_layers):
        in_specs += [pl.BlockSpec((1, 1, page, d), _kp_map(m))] * 2
        if kv_quant:
            # int8 KV scale rows: one value per token row by contract
            # kernelcheck: disable=KRN001
            in_specs += [pl.BlockSpec((1, 1, page, 1), _kp_map(m))] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_layers * per,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b_pad, hidden), _const),                  # out
            pl.BlockSpec((1, nkv, b_pad, d), _kv_out_map),          # k_new
            pl.BlockSpec((1, nkv, b_pad, d), _kv_out_map),          # v_new
        ],
        scratch_shapes=[
            pltpu.VMEM((b_pad, hidden), jnp.float32),     # x carry
            pltpu.VMEM((b_pad, hidden), jnp.float32),     # h (normed)
            pltpu.VMEM((nh + 2 * nkv, b_pad, d), jnp.float32),  # q|k|v
            pltpu.VMEM((nh, b_pad, d), jnp.float32),      # attn out
            pltpu.VMEM((b_pad, hidden), jnp.float32),     # x2 (residual)
            pltpu.VMEM((b_pad, inter), jnp.float32),      # silu(g)*u
            pltpu.VMEM((b_pad, tc_max), jnp.float32),     # acc a
            pltpu.VMEM((b_pad, tc_max), jnp.float32),     # acc b
            pltpu.VMEM((rep_rows, d), jnp.float32),       # attn acc
            pltpu.VMEM((rep_rows, _LANES), jnp.float32),  # attn m
            pltpu.VMEM((rep_rows, _LANES), jnp.float32),  # attn l
        ],
    )

    out, k_new, v_new = pl.pallas_call(
        functools.partial(_fused_multi_block_kernel, dims=dims),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, hidden), x.dtype),
            jax.ShapeDtypeStruct((n_layers, nkv, b_pad, d), x.dtype),
            jax.ShapeDtypeStruct((n_layers, nkv, b_pad, d), x.dtype),
        ],
        interpret=interpret,
        name="fused_block_decode_nlayer",
    )(*operands)

    kps, vps = list(k_pages), list(v_pages)
    for i in range(n_layers):
        kps[i], vps[i] = write_paged_kv(
            kps[i], vps[i], k_new[i, :, :b].swapaxes(0, 1),
            v_new[i, :, :b].swapaxes(0, 1), bt, sl)
    return out[:b], kps, vps


def fused_multi_block_decode(x, weights: MultiBlockDecodeWeights, k_pages,
                             v_pages, block_tables, seq_lens, *,
                             num_heads: int, num_kv_heads: int,
                             rope_theta: float = 10000.0,
                             epsilon: float = 1e-6,
                             sm_scale: Optional[float] = None, snap=None):
    """Dispatch one N-layer fused decode step: the multi-layer Pallas
    kernel on a real TPU backend, the merged-matmul jnp composition
    elsewhere. ``snap`` as in :func:`fused_block_decode`."""
    from ..flags import is_tpu_backend, snapshot
    if snap is None:
        snap = snapshot(("use_pallas",))
    kwargs = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                  rope_theta=rope_theta, epsilon=epsilon, sm_scale=sm_scale)
    if snap.use_pallas and is_tpu_backend():
        return fused_multi_block_decode_pallas(
            x, weights, k_pages, v_pages, block_tables, seq_lens, **kwargs)
    return fused_multi_block_decode_ref(
        x, weights, k_pages, v_pages, block_tables, seq_lens, **kwargs)


def fused_block_decode(x, weights: BlockDecodeWeights, k_pages, v_pages,
                       block_tables, seq_lens, *, num_heads: int,
                       num_kv_heads: int, rope_theta: float = 10000.0,
                       epsilon: float = 1e-6,
                       sm_scale: Optional[float] = None, snap=None):
    """Dispatch one fused block-decode step: the Pallas kernel on a real
    TPU backend (``FLAGS_use_pallas``), the jnp composition elsewhere.
    ``snap`` is an optional :func:`paddle_tpu.flags.snapshot` so a caller
    building a multi-layer program resolves flags ONCE per trace."""
    from ..flags import is_tpu_backend, snapshot
    if snap is None:
        snap = snapshot(("use_pallas",))
    kwargs = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                  rope_theta=rope_theta, epsilon=epsilon, sm_scale=sm_scale)
    if snap.use_pallas and is_tpu_backend():
        return fused_block_decode_pallas(x, weights, k_pages, v_pages,
                                         block_tables, seq_lens, **kwargs)
    return fused_block_decode_ref(x, weights, k_pages, v_pages,
                                  block_tables, seq_lens, **kwargs)
