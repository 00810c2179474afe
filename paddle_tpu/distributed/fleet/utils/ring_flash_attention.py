"""Long-context attention: ring attention + Ulysses (sep) attention.

Reference: the reference ecosystem's balanced ring flash attention
(paddlenlp/transformers/ring_flash_attention.py (approx., out-of-tree)) and
the ``sep_degree`` Ulysses axis wired through
python/paddle/distributed/fleet/base/topology.py — SURVEY.md §5.7.

TPU-native design (this is where the rebuild can exceed the reference —
SURVEY.md §5.7 "TPU equivalent"):

  - **Ring attention** rides the ICI torus: each sep shard holds a Q/K/V
    sequence chunk; ``axis_size`` scan steps each compute one block of the
    online-softmax update and rotate the K/V chunk to the next neighbour
    with ``lax.ppermute`` — XLA overlaps the permute with the block matmul,
    so the sequence length per chip is bounded by HBM while communication
    stays nearest-neighbour. Backward is jax autodiff: the transpose of
    ppermute is the reverse-direction ppermute, giving the reverse ring
    without hand-written comm.
  - **Ulysses attention**: one ``lax.all_to_all`` turns seq-sharded
    activations into head-sharded ones (each shard sees the FULL sequence
    for H/P heads), runs ordinary attention, and the inverse all_to_all
    restores seq sharding. Two collectives total, both on ICI.

Both functions are PER-SHARD code (inside ``jax.shard_map`` over the sep
axis); ``sep_scaled_dot_product_attention`` is the jit-level wrapper that
builds the shard_map over the current mesh. Layout: (B, S, H, D) — the
paddle sdpa convention; S is the GLOBAL length, S/P per shard.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


# ------------------------------------------------------------ ring attention
def _chunk_attn(q, k, v, causal, sm_scale, h, hkv):
    """One ring step's inner attention: (B, Cq, H, D) x (B, Ck, Hkv, D)
    -> (out (B, Cq, H, D), lse (B, H, Cq)), the mergeable pair. Runs the
    Pallas flash kernel (O(block) temps, unexpanded GQA kv) whenever the
    chunk shapes fit its tiling on the current backend; falls back to a
    dense-with-lse computation otherwise (small test chunks)."""
    from ....flags import is_tpu_backend, snapshot
    snap = snapshot(("use_pallas",))
    b, cq, _, d = q.shape
    ck = k.shape[1]
    if is_tpu_backend():
        # Mosaic tiling wants full lane-aligned chunks
        ok = cq % 128 == 0 and ck % 128 == 0
    else:
        # pallas INTERPRET mode cannot run inside a check_vma=True
        # shard_map (jax hlo_interpreter limitation) — only use it when
        # the values carry no vma (sep-only meshes run check_vma=False)
        ok = not jax.typeof(q).vma
    if snap.use_pallas and ok:
        from ....kernels.flash_attention import flash_attention_with_lse
        try:
            qf = jnp.swapaxes(q, 1, 2).reshape(b * h, cq, d)
            kf = jnp.swapaxes(k, 1, 2).reshape(b * hkv, ck, d)
            vf = jnp.swapaxes(v, 1, 2).reshape(b * hkv, ck, d)
            # pin 128x128 tiles: flash_tiling's are swept on monolithic
            # multi-k seqs; ring steps see small per-rank chunks where a
            # full-chunk block would re-materialize the quadratic (C, C)
            # scores the ring exists to avoid
            out, lse = flash_attention_with_lse(
                qf, kf, vf, causal=causal, sm_scale=sm_scale,
                block_q=128, block_k=128,
                n_heads=h, n_kv_heads=hkv)
            return (jnp.swapaxes(out.reshape(b, h, cq, d), 1, 2),
                    lse.reshape(b, h, cq))
        except NotImplementedError:
            pass
    rep = h // hkv
    kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * sm_scale
    kf = jnp.swapaxes(kx, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(vx, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal:
        mask = lax.broadcasted_iota(jnp.int32, (cq, ck), 0) >= \
            lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        s = jnp.where(mask, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)            # (B, H, Cq)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


def _merge(o1, lse1, o2, lse2):
    """Combine two partial softmax results in log-space: out (B, C, H, D)
    returned in FLOAT32 (the ring accumulator dtype — per-step casts back
    to bf16 would compound rounding across the P merges; callers cast
    once after the scan), lse (B, H, C). Empty partials carry
    lse = -1e30 and contribute 0."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    den = w1 + w2
    den_safe = jnp.where(den == 0.0, 1.0, den)
    lse = jnp.where(den == 0.0, _NEG_INF, m_safe + jnp.log(den_safe))
    wt = lambda w: jnp.swapaxes(w / den_safe, 1, 2)[..., None]
    return (o1.astype(jnp.float32) * wt(w1)
            + o2.astype(jnp.float32) * wt(w2)), lse


def _empty_partial(b, c, h, d):
    return (jnp.zeros((b, c, h, d), jnp.float32),
            jnp.full((b, h, c), _NEG_INF, jnp.float32))


def ring_flash_attention(q, k, v, axis_name: str = "sep",
                         causal: bool = True,
                         sm_scale: Optional[float] = None,
                         zigzag: bool = False):
    """Per-shard ring attention. q/k/v: (B, C, H(kv), D) local chunks of
    the (B, S, H, D) global arrays, C = S / axis_size; GQA kv (Hkv < H)
    rides the ring UNEXPANDED. Returns (B, C, H, D).

    Each of the ``axis_size`` ring steps computes one chunk-vs-chunk
    attention through the Pallas flash kernel (mergeable (out, lse) form
    — per-shard temps O(C*D + block^2), never the (C, C) score matrix)
    and rotates the kv chunk to the neighbour with ``lax.ppermute``; XLA
    overlaps the permute with the step's matmuls, and the backward ring
    is the transposed ppermute via autodiff.

    ``zigzag`` (opt-in — the data must actually BE in zigzag order; the
    function cannot reorder it): the caller feeds chunks where rank r
    holds sequence pieces r and 2P-1-r (half a chunk each;
    ``sep_scaled_dot_product_attention`` does the reorder and sets this).
    Causal work then balances EXACTLY: per rank over a full rotation,
    qa-vs-ka runs r full half-blocks, qb-vs-ka runs P (piece(qb) =
    2P-1-r exceeds every ka piece, so it is a full half-block on all P
    steps), qb-vs-kb runs P-1-r — a constant 2P-1 halves plus the
    diagonal contributions (qa-vs-ka and qb-vs-kb at src == r), vs the
    contiguous layout's r-proportional skew (rank P-1 does P times rank
    0's work). Work units are gated by ``lax.switch`` on the piece
    comparison, so skipped blocks cost nothing; the branches are pure
    local compute (no collectives), so per-rank divergence is sound."""
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, c, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    perm = [(j, (j + 1) % p) for j in range(p)]

    def rotate(t):
        return lax.ppermute(t, axis_name, perm)

    def _vary(x):
        # fresh accumulators start unvarying and need the varying tag for
        # the scan carry; never applied to k/v (already varying — under
        # check_vma=False their typeof may not even report it)
        if axis_name in jax.typeof(x).vma:
            return x
        return lax.pcast(x, axis_name, to="varying")

    def unit(mode, qx, kx, vx):
        """mode 0: skip, 1: full, 2: causal (same-piece, aligned). The o
        partial comes back f32 (switch branches must agree with the skip
        branch's f32 accumulator dtype)."""

        def attn(causal_):
            def run(a, b_, c_):
                o, lse = _chunk_attn(a, b_, c_, causal_, sm_scale, h, hkv)
                return o.astype(jnp.float32), lse
            return run

        return lax.switch(
            mode,
            [lambda a, b_, c_: jax.tree.map(_vary, _empty_partial(
                b, a.shape[1], h, d)),
             attn(False), attn(True)],
            qx, kx, vx)

    if not zigzag:
        # one accumulator over the whole chunk. Non-causal: every chunk
        # pair runs full. Causal contiguous: rank r's chunk attends
        # chunks src < r fully, its own causally, later ones not at all
        # (work skewed by r — the zigzag layout fixes that).
        def step(carry, i):
            o, lse, k_cur, v_cur = carry
            src = (idx - i) % p
            if causal:
                mode = jnp.where(src == idx, 2,
                                 jnp.where(src < idx, 1, 0))
            else:
                mode = jnp.ones((), jnp.int32)
            oi, lsei = unit(mode.astype(jnp.int32), q, k_cur, v_cur)
            o, lse = _merge(o, lse, oi, lsei)
            return (o, lse, rotate(k_cur), rotate(v_cur)), None

        o0, l0 = _empty_partial(b, c, h, d)
        carry = (_vary(o0), _vary(l0), k, v)
        (o, _, _, _), _ = lax.scan(step, carry, jnp.arange(p))
        return o.astype(q.dtype)

    # zigzag: local chunk = [piece idx, piece 2P-1-idx], half each
    if not causal:
        raise ValueError("zigzag layout only applies to causal attention")
    if c % 2:
        raise ValueError(f"zigzag ring needs an even local chunk, got {c}")
    half = c // 2
    qa, qb = q[:, :half], q[:, half:]

    def step(carry, i):
        oa, la, ob, lb, k_cur, v_cur = carry
        src = (idx - i) % p
        ka, kb = k_cur[:, :half], k_cur[:, half:]
        va, vb = v_cur[:, :half], v_cur[:, half:]
        # piece indices: qa=idx, qb=2P-1-idx, ka=src, kb=2P-1-src
        mode_aa = jnp.where(src == idx, 2,
                            jnp.where(src < idx, 1, 0)).astype(jnp.int32)
        # piece(ka)=src <= P-1 < P <= 2P-1-idx = piece(qb): always full
        o1, l1 = unit(mode_aa, qa, ka, va)
        o2, l2 = _chunk_attn(qb, ka, va, False, sm_scale, h, hkv)
        mode_bb = jnp.where(src == idx, 2,
                            jnp.where(src > idx, 1, 0)).astype(jnp.int32)
        o3, l3 = unit(mode_bb, qb, kb, vb)
        oa, la = _merge(oa, la, o1, l1)
        ob, lb = _merge(ob, lb, o2, l2)
        ob, lb = _merge(ob, lb, o3, l3)
        return (oa, la, ob, lb, rotate(k_cur), rotate(v_cur)), None

    oa0, la0 = _empty_partial(b, half, h, d)
    ob0, lb0 = _empty_partial(b, half, h, d)
    carry = (_vary(oa0), _vary(la0), _vary(ob0), _vary(lb0), k, v)
    (oa, _, ob, _, _, _), _ = lax.scan(step, carry, jnp.arange(p))
    return jnp.concatenate([oa, ob], axis=1).astype(q.dtype)


def zigzag_order(S: int, p: int):
    """Global sequence permutation for the balanced ring: rank r's chunk
    is [piece r, piece 2P-1-r] of 2P equal pieces. Returns (order,
    inverse) index arrays, or None when S doesn't split into 2P pieces."""
    if S % (2 * p):
        return None
    piece = S // (2 * p)
    order = np.concatenate([
        np.r_[r * piece:(r + 1) * piece,
              (2 * p - 1 - r) * piece:(2 * p - r) * piece]
        for r in range(p)])
    inv = np.argsort(order)
    return order, inv


# --------------------------------------------------------- ulysses attention
def _dense_sdpa(q, k, v, causal, sm_scale):
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * sm_scale
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = lax.broadcasted_iota(jnp.int32, (sq, sk), 0) >= \
            lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(mask, s, _NEG_INF)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vf)
    return jnp.swapaxes(o.astype(q.dtype), 1, 2)


def ulysses_attention(q, k, v, axis_name: str = "sep", causal: bool = True,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      attn_fn_gqa: bool = False):
    """Per-shard Ulysses attention (reference: the sep_degree axis /
    head-scatter seq-gather all-to-alls). q/k/v: (B, C, H, D) seq-sharded;
    requires H % axis_size == 0. Each shard computes FULL-sequence attention
    for H/P heads, so any single-device attention impl (the Pallas flash
    kernel included) drops in via ``attn_fn``.

    GQA (k/v with Hkv < H heads): when Hkv is divisible by the sep degree
    the kv all-to-alls split kv heads like q heads. When it is NOT
    (Hkv < P, the 70B-style layout), plain Ulysses cannot shard kv by
    head — instead the (few) kv heads are ALL-GATHERED in sequence and
    each shard selects the kv heads its q-head slice attends to
    (comm: 2 q all-to-alls + one kv all-gather of B*S*Hkv*D — cheaper
    than ring's (P-1) kv rotations whenever Hkv <= 2H/P).

    ``attn_fn_gqa``: declare that ``attn_fn`` handles grouped-query inputs
    natively (fewer kv heads than q heads, e.g. the Pallas flash kernel) —
    the unexpanded kv then reaches it at Hkv bandwidth instead of being
    jnp.repeat-expanded first (advisor r3)."""
    p = lax.axis_size(axis_name)
    b, c, h, d = q.shape
    hkv = k.shape[2]
    if h % p:
        raise ValueError(f"num heads {h} not divisible by sep degree {p}")
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def seq_gather(t):   # (B, C, Hx, D) -> (B, C*P, Hx/P, D)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def seq_scatter(t):  # (B, C*P, H/P, D) -> (B, C, H, D)
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg = seq_gather(q)
    fn = attn_fn or functools.partial(_dense_sdpa, causal=causal,
                                      sm_scale=sm_scale)
    gqa_fn = attn_fn is not None and attn_fn_gqa
    if hkv == h or hkv % p == 0:
        kg, vg = seq_gather(k), seq_gather(v)
        if hkv != h and not gqa_fn:
            # per-shard GQA: expand the local kv head slice to match
            # (dense fallback only — a GQA-aware attn_fn reads the
            # unexpanded slice at Hkv bandwidth)
            rep = (h // p) // (hkv // p)
            kg = jnp.repeat(kg, rep, axis=2)
            vg = jnp.repeat(vg, rep, axis=2)
        out = fn(qg, kg, vg)
    else:
        # GQA-Ulysses: kv heads are too few to split — gather full-seq kv
        # and select this shard's group heads (q head g = r*(H/P)+j maps
        # to kv head g // (H/Hkv))
        kg = lax.all_gather(k, axis_name, axis=1, tiled=True)
        vg = lax.all_gather(v, axis_name, axis=1, tiled=True)
        r = lax.axis_index(axis_name)
        rep = h // hkv
        hq_l = h // p
        # here hkv % p != 0 (else-branch), which rules out hq_l % rep == 0
        # (they are equivalent) — the only unexpanded-kv case left is the
        # whole local q slice sharing ONE kv group:
        if gqa_fn and rep % hq_l == 0:
            # the whole local q slice lives inside ONE kv group (slice
            # start r*hq_l is a multiple of hq_l and rep % hq_l == 0, so
            # the slice never crosses a group boundary): one kv head
            kv_heads = jnp.reshape(r * hq_l // rep, (1,))
            out = fn(qg, jnp.take(kg, kv_heads, axis=2),
                     jnp.take(vg, kv_heads, axis=2))
        else:
            heads = r * (h // p) + jnp.arange(h // p)
            k_sel = jnp.take(kg, heads // rep, axis=2)
            v_sel = jnp.take(vg, heads // rep, axis=2)
            out = fn(qg, k_sel, v_sel)
    return seq_scatter(out)


# ------------------------------------------------------------- jit-level API
def sep_scaled_dot_product_attention(
        q, k, v, mesh: Optional[Mesh] = None, sep_axis: str = "sep",
        method: str = "ring", causal: bool = True,
        sm_scale: Optional[float] = None):
    """Context-parallel sdpa at the jit level: shard_maps the per-shard
    implementation over ``sep_axis`` (other mesh axes stay under GSPMD).
    q/k/v: GLOBAL (B, S, H, D); S must divide by the sep degree."""
    if mesh is None:
        from ..base_topology import get_hybrid_communicate_group
        mesh = get_hybrid_communicate_group().get_mesh()
    if sep_axis not in mesh.shape or mesh.shape[sep_axis] <= 1:
        if k.shape[2] != q.shape[2]:      # GQA: the dense path expands
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return _dense_sdpa(q, k, v, causal,
                           sm_scale or 1.0 / math.sqrt(q.shape[-1]))

    p = mesh.shape[sep_axis]
    impl = {"ring": ring_flash_attention, "ulysses": ulysses_attention}[method]
    kw = {}
    zig = None
    if method == "ring" and causal:
        # balanced causal ring: permute the sequence into zigzag order
        # (rank r holds pieces r and 2P-1-r) so per-rank causal work is
        # uniform; the inverse permute restores order on the way out.
        # GSPMD turns the takes on the seq-sharded operands into the
        # half-chunk exchange.
        zig = zigzag_order(q.shape[1], p)
        kw["zigzag"] = zig is not None
    fn = functools.partial(impl, axis_name=sep_axis, causal=causal,
                           sm_scale=sm_scale, **kw)
    spec = P(None, sep_axis, None, None)
    if set(mesh.axis_names) == {sep_axis}:
        # full-manual mesh: check_vma=False — pallas interpret mode can
        # then serve the inner flash kernel on CPU test meshes
        mapped = jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
    else:
        # manual over sep only; other axes stay GSPMD. check_vma must be
        # True: this jax version's check_vma=False path re-enters
        # shard_map with out_specs over ALL mesh axes, which
        # partial-manual mode rejects
        mapped = jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=frozenset({sep_axis}))
    if zig is not None:
        order, inv = zig
        out = mapped(jnp.take(q, order, axis=1),
                     jnp.take(k, order, axis=1),
                     jnp.take(v, order, axis=1))
        return jnp.take(out, inv, axis=1)
    return mapped(q, k, v)
