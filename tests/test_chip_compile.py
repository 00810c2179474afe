"""Compile every Pallas kernel entry point for a DESCRIBED TPU v5e.

The TPU compiler ships with jaxlib/libtpu and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so these cases
raise here, on the CPU, exactly what Mosaic would raise on the chip —
what interpret mode cannot show (tile alignment the compiler cannot
prove, VMEM over budget, block-shape rules). Nothing runs: a case that
passes says the kernel COMPILES at that width, never that it is right
(the interpret-mode parity tests say that) or fast (only a chip run does).

Tier-1 keeps one case per kernel entry point at its widest shape; the
full matrix the chip bring-up compiled before its first chip call rides
``-m slow``. A kernel that stops compiling is a finding for the PR that
broke it: fix the kernel, or mark the case ``xfail(strict=True)`` with
the compiler's own words — never route around it with a fallback.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from paddle_tpu.kernels import fused_block_decode as fbd
from paddle_tpu.kernels.decode_attention import flash_prefill
from paddle_tpu.kernels.flash_attention import (activation_layout,
                                                flash_attention,
                                                flash_attention_bshd)
from paddle_tpu.kernels.grouped_matmul import (grouped_matmul_tpu,
                                               padded_rows)
from paddle_tpu.kernels.paged_attention import (QuantizedPages,
                                                paged_attention,
                                                paged_chunk_attention,
                                                write_paged_kv_pallas,
                                                write_paged_prompt_at_pallas)
from paddle_tpu.kernels.rms_norm import rms_norm_pallas
from paddle_tpu.kernels.ssm_update import ssm_decode_update_pallas

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def chips():
    """The four described chips of a v5e 2x2; the whole file skips where
    the topology cannot be described (no libtpu). The persistent compile
    cache is off around the module: a described-device executable is
    written to it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure means "no libtpu"
        pytest.skip(f"cannot describe a v5e topology here: {exc!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _on_tpu(monkeypatch):
    """The kernels pick interpret mode from ``is_tpu_backend()``, which
    sees the CPU here: steer them to the real lowering from the test."""
    monkeypatch.setattr("paddle_tpu.flags.is_tpu_backend", lambda: True)


# ------------------------------------------------------------- the cases
# Each builder returns (fn, abstract args); ``S(shape, dtype)`` is bound to
# ONE described chip by the test (``S.chips`` is all four, for the case
# that spans them).

def _flash(bh, s, d, *, bkv=None, skv=None, causal=True, segments=False,
           heads=1, kv_heads=None, grad=False, window=None):
    skv = skv or s
    bkv = bkv or bh

    def build(S):
        args = [S((bh, s, d), BF16), S((bkv, skv, d), BF16),
                S((bkv, skv, d), BF16)]
        if segments:
            args.append(S((bh, s), I32))

        def fwd(q, k, v, seg=None):
            return flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                   n_heads=heads, n_kv_heads=kv_heads,
                                   window=window)

        if not grad:
            return fwd, args

        def loss(q, k, v, seg=None):
            return fwd(q, k, v, seg).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), args
    return build


def _flash_dp2_mp2(b, s, h, hkv, d):
    """Flash fwd+bwd inside a GSPMD program over a dp2 x mp2 mesh — what
    ``hapi.TrainStep(mesh=...)`` compiles. GSPMD cannot partition a
    Mosaic kernel; ``flash_attention_bshd`` splits it per shard of the
    layout the step declares."""
    def build(S):
        mesh = Mesh(np.array(S.chips).reshape(2, 2), ("dp", "mp"))
        on = NamedSharding(mesh, PartitionSpec("dp", None, "mp", None))
        args = [jax.ShapeDtypeStruct((b, s, nh, d), BF16, sharding=on)
                for nh in (h, hkv, hkv)]

        def loss(q, k, v):
            with activation_layout(mesh, ("dp",), "mp"):
                return flash_attention_bshd(q, k, v).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), args
    return build


def _prefill(b, s, h, hkv, d, t):
    def build(S):
        fn = lambda q, k, v, n: flash_prefill(q, k, v, n)  # noqa: E731
        return fn, [S((b, s, h, d), BF16), S((b, t, hkv, d), BF16),
                    S((b, t, hkv, d), BF16), S((), I32)]
    return build


def _rms(n, h, grad=False):
    def build(S):
        args = [S((n, h), BF16), S((h,), BF16)]
        if not grad:
            return rms_norm_pallas, args
        return jax.grad(lambda x, w: rms_norm_pallas(x, w).astype(F32).sum(),
                        argnums=(0, 1)), args
    return build


def _pool(S, hkv, d, page, n_pages, int8):
    if int8:
        return QuantizedPages(S((hkv, n_pages, page, d), I8),
                              S((hkv, n_pages, page, 1), F32))
    return S((hkv, n_pages, page, d), BF16)


def _paged(h, hkv, d, *, int8=False, chunk=0, b=8, page=64, max_pages=16,
           block=1, window=None, q_dtype=BF16):
    def build(S):
        pool = _pool(S, hkv, d, page, 256, int8)
        if chunk:
            attend = (paged_chunk_attention if block == 1 and not window
                      else lambda *a: paged_chunk_attention(
                          *a, block=block, window=window))
            return attend, [
                S((b, chunk, h, d), q_dtype), pool, pool,
                S((b, max_pages), I32), S((b,), I32)]
        attend = (paged_attention if not window else
                  lambda *a: paged_attention(*a, window=window))
        return attend, [S((b, h, d), BF16), pool, pool,
                        S((b, max_pages), I32), S((b,), I32)]
    return build


def _page_write(hkv, d, *, chunk=0, b=32, page=64, max_pages=16):
    """The aliased page-write kernels at a configuration's pool shape
    (``max_batch`` 32 x 16 pages + the null page): one token a row, or a
    ``chunk`` of one prompt."""
    def build(S):
        pool = _pool(S, hkv, d, page, b * max_pages + 1, False)
        if chunk:
            new = S((1, chunk, hkv, d), BF16)
            return write_paged_prompt_at_pallas, [
                pool, pool, new, new, S((1, max_pages), I32), S((1,), I32)]
        new = S((b, hkv, d), BF16)
        return write_paged_kv_pallas, [pool, pool, new, new,
                                       S((b, max_pages), I32), S((b,), I32)]
    return build


def _grouped(k, n, *, experts=128, assignments=2048):
    """The grouped expert matmul (upstream's ``gmm`` at this repo's
    tiling) at SDAR-30B-A3B's widths: 128 stacked experts, the 2,048
    assignments of a block step (64 rows x 4 positions x top 8)."""
    def build(S):
        return grouped_matmul_tpu, [
            S((padded_rows(assignments), k), BF16),
            S((experts, k, n), BF16), S((experts,), I32)]
    return build


def _grouped_train(tokens, h, f, *, experts=16, router=64, top_k=8):
    """The differentiable dropless layer's grouped products, forward
    (``gmm``) and backward (``gmm`` for the rows, ``tgmm`` for the
    weights), at Mellum 2's widths: one chunk of ``tokens`` through the
    16 experts of 64 a chip holds."""
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        dropless_moe_train)

    def build(S):
        def loss(x, rw, gu, dn):
            y, _, bal = dropless_moe_train(x, rw, gu, dn, top_k=top_k)
            return y.astype(F32).sum() + bal
        return jax.grad(loss, argnums=(0, 1, 2, 3)), [
            S((tokens, h), BF16), S((h, router), BF16),
            S((experts, h, 2 * f), BF16), S((experts, f, h), BF16)]
    return build


def _block_write(hkv, d, *, b=64, block=4, page=64, max_pages=20):
    """The block step's page write at SDAR's pool shape (64 rows x 20
    pages + the null page): a block of 4 positions a row, one page."""
    from paddle_tpu.kernels.paged_attention import _block_write as write

    def build(S):
        pool = _pool(S, hkv, d, page, b * max_pages + 1, False)
        new = S((b, block, hkv, d), BF16)

        def fn(kp, vp, kn, vn, bt, st):
            return write(kp, vp, kn, vn, bt, st, interpret=False)
        return fn, [pool, pool, new, new, S((b, max_pages), I32),
                    S((b,), I32)]
    return build


def _ssm_update(b, *, slots=32, n_heads=64, d_head=64, d_state=128):
    """The aliased Mamba-2 state-update kernel at granite-4.0-h-micro's
    widths: the packed store of one layer (two heads of 64 to a 128-lane
    row), the first ``b`` of its ``slots`` rows stepped."""
    def build(S):
        rows = n_heads * d_head // 128
        return ssm_decode_update_pallas, [
            S((slots, rows, d_state, 128), F32), S((b, n_heads, d_head), BF16),
            S((b, n_heads), F32), S((n_heads,), F32),
            S((b, 1, d_state), BF16), S((b, 1, d_state), BF16),
            S((n_heads,), F32)]
    return build


def _fused(layers, b, *, h=4096, nh=32, nkv=32, d=128, inter=11008,
           int8=False, int4=False, page=64, max_pages=16):
    """``layers == 0``: the single-layer kernel; else the N-layer one."""
    qw, kvw = nh * d, nkv * d

    def build(S):
        tail = [S((b, max_pages), I32), S((b,), I32)]
        kw = dict(num_heads=nh, num_kv_heads=nkv, interpret=False)
        if layers == 0:
            w = fbd.BlockDecodeWeights(
                S((h,), BF16), S((h, qw), BF16), S((h, kvw), BF16),
                S((h, kvw), BF16), S((qw, h), BF16), S((h,), BF16),
                S((h, inter), BF16), S((h, inter), BF16),
                S((inter, h), BF16))
            pool = _pool(S, nkv, d, page, 256, int8)

            def fn(x, w, kp, vp, bt, sl):
                return fbd.fused_block_decode_pallas(x, w, kp, vp, bt, sl,
                                                     **kw)
            return fn, [S((b, h), BF16), w, pool, pool] + tail

        def mat(rows, cols, key):
            if not int4:
                return S((layers, rows, cols), BF16)
            tr, tc = fbd._int4_plan(h, qw, kvw, inter)[key]
            return fbd.Int4Tiles(S((layers, rows // 2, cols), jnp.uint8),
                                 S((layers, rows // tr, cols // tc), F32))

        w = fbd.MultiBlockDecodeWeights(
            S((layers, h), BF16), mat(h, qw + 2 * kvw, "wqkv"),
            mat(qw, h, "wo"), S((layers, h), BF16),
            mat(h, 2 * inter, "wgu"), mat(inter, h, "wd"))
        pools = [_pool(S, nkv, d, page, 256, int8) for _ in range(layers)]

        def fn(x, w, kps, vps, bt, sl):
            return fbd.fused_multi_block_decode_pallas(x, w, kps, vps, bt,
                                                       sl, **kw)
        return fn, [S((b, h), BF16), w, pools, list(pools)] + tail
    return build


_GQA = dict(nkv=8, inter=14336)           # H 4096, 32/8 heads, I 14336

# one per kernel entry point, widest shape: the tier-1 guard
_TIER1 = {
    "flash_fwd-gqa32x8-d128-s2048": _flash(
        8 * 32, 2048, 128, bkv=8 * 8, heads=32, kv_heads=8),
    "flash_bwd-gqa32x8-d128-s2048": _flash(
        8 * 32, 2048, 128, bkv=8 * 8, heads=32, kv_heads=8, grad=True),
    "flash_bwd-dp2xmp2-gqa32x8-d128-s1024": _flash_dp2_mp2(4, 1024, 32, 8,
                                                          128),
    # the two train cells' calls at their exact shapes, no blocks passed:
    # ``flash_tiling``'s 1,024 x 1,024 steps fit VMEM and keep their names
    # (gpt3-345m.train: BH 256 x 1,024 x 64; mistral-7b.train-dp2mp2, a
    # chip's share: 16 query heads over 4 KV heads, 8 KV rows, 4,096 x 128)
    "flash_bwd-gpt3-train-d64-s1024": _flash(256, 1024, 64, grad=True),
    "flash_bwd-mistral-train-gqa16x4-d128-s4096": _flash(
        32, 4096, 128, bkv=8, heads=16, kv_heads=4, grad=True),
    # mellum2-12b-a2.5b-instruct.train-8k's window layers: 4 rows x 32
    # query heads over 4 KV heads, 8,192 x 128, the band of 1,024 alone
    "flash_window_bwd-mellum-train-gqa32x4-d128-s8192": _flash(
        4 * 32, 8192, 128, bkv=4 * 4, heads=32, kv_heads=4, grad=True,
        window=1024),
    "tgmm-mellum-train-e16-t4096": _grouped_train(4096, 2304, 896),
    "flash_fwd-varlen-d64-s1024": _flash(8 * 16, 1024, 64, segments=True),
    "flash_fwd-noncausal-d40-s4096": _flash(2 * 8, 4096, 40, causal=False),
    "flash_prefill-d128": _prefill(1, 512, 32, 8, 128, 1024),
    "rms_norm_fwd-8192x4096": _rms(8192, 4096),
    "rms_norm_bwd-8192x4096": _rms(8192, 4096, grad=True),
    "paged_attention-gqa32x8-d128": _paged(32, 8, 128),
    "paged_attention-int8-gqa32x8-d128": _paged(32, 8, 128, int8=True),
    # the serving cells' own decode calls: gpt3-345m's lane-padded pool on
    # rungs 4 and 32 (16 pages a row), granite-4.0-h-micro's attention
    # layers (20 pages a row: not a multiple of the pages a grid step holds)
    "paged_attention-gpt3-b4": _paged(16, 16, 128, b=4),
    "paged_attention-gpt3-b32": _paged(16, 16, 128, b=32),
    "paged_attention-granite-b32": _paged(32, 8, 128, b=32, max_pages=20),
    "paged_attention-int8-gpt3-b4": _paged(16, 16, 128, int8=True, b=4),
    "paged_attention-int8-gpt3-b32": _paged(16, 16, 128, int8=True, b=32),
    "paged_attention-int8-granite-b32": _paged(32, 8, 128, int8=True, b=32,
                                               max_pages=20),
    "paged_chunk-gqa32x8-d128": _paged(32, 8, 128, chunk=256, b=1),
    "paged_chunk-int8-gqa32x8-d128": _paged(32, 8, 128, int8=True,
                                           chunk=256, b=1),
    # gpt3-345m's 16 KV heads on a head-width pool (what a fused-decode
    # model of that width gets), and mistral-7b's; gpt3-345m's own pool is
    # lane-padded to 128 (test_serving_program_copies_no_pool)
    "paged_kv_write-mha16-d64-b32": _page_write(16, 64),
    "paged_kv_write-gqa8-d128-b32": _page_write(8, 128),
    "paged_prompt_write-mha16-d64-c256": _page_write(16, 64, chunk=256),
    "paged_prompt_write-gqa8-d128-c256": _page_write(8, 128, chunk=256),
    "ssm_decode_update-h64-d64-n128-b32": _ssm_update(32),
    # sdar-30b-a3b-chat: the block step's attention (4 positions x 8
    # query heads a KV head = 32 query rows), its one-page block write,
    # the block-causal chunk and the two grouped expert matmuls
    "paged_attention-sdar-block4-b64": _paged(4 * 32, 4, 128, b=64,
                                              max_pages=20),
    "paged_block_write-gqa4-d128-b64": _block_write(4, 128),
    "paged_chunk-sdar-blockcausal4-c256": _paged(32, 4, 128, chunk=256, b=1,
                                                 max_pages=20, block=4),
    # trinity-mini: the windowed reads at the cell's table width (384
    # pages a row) and chunk (1,024 queries: four tiles of 2,048 query
    # rows a KV head), and the global layer's chunk
    "paged_attention-trinity-window2048-b32": _paged(
        32, 4, 128, b=32, max_pages=384, window=2048),
    "paged_chunk-trinity-window2048-c1024": _paged(
        32, 4, 128, chunk=1024, b=1, max_pages=384, window=2048),
    "paged_chunk-trinity-global-c1024": _paged(
        32, 4, 128, chunk=1024, b=1, max_pages=384),
    "gmm-gate_up-e128": _grouped(2048, 1536),
    "gmm-down-e128": _grouped(768, 2048),
    "fused_block-int8kv-gqa-b32": _fused(0, 32, int8=True, **_GQA),
    "fused_nlayer2-int8kv-gqa-b32": _fused(2, 32, int8=True, **_GQA),
    "fused_nlayer2-int4-7b-b8": _fused(2, 8, int4=True),
}

# the rest of the bring-up matrix (ISSUE 21 A): every listed width
_MATRIX = {
    "flash_fwd-d64-s1024": _flash(8 * 16, 1024, 64),
    "flash_bwd-d64-s1024": _flash(8 * 16, 1024, 64, grad=True),
    "flash_bwd-varlen-d64-s1024": _flash(8 * 16, 1024, 64, segments=True,
                                         grad=True),
    "flash_bwd-noncausal-d40-s4096": _flash(2 * 8, 4096, 40, causal=False,
                                            grad=True),
    "flash_fwd-noncausal-d80-s1024": _flash(2 * 8, 1024, 80, causal=False),
    "flash_bwd-noncausal-d80-s1024": _flash(2 * 8, 1024, 80, causal=False,
                                            grad=True),
    "flash_bwd-d192-s1024": _flash(2 * 8, 1024, 192, grad=True),
    "flash_prefill-d64": _prefill(1, 512, 16, 16, 64, 1024),
    "rms_norm_fwd-8192x1024": _rms(8192, 1024),
    "rms_norm_bwd-8192x1024": _rms(8192, 1024, grad=True),
    "paged_attention-mha16-d64": _paged(16, 16, 64),
    "paged_attention-int8-mha16-d64": _paged(16, 16, 64, int8=True),
    "paged_chunk-mha16-d64": _paged(16, 16, 64, chunk=256, b=1),
    "paged_chunk-int8-mha16-d64": _paged(16, 16, 64, int8=True, chunk=256,
                                         b=1),
    # trinity-mini's chunk at the tile ``chunk_tiling`` never exceeds
    # (2,048 query rows a step: 256 tokens x 8 heads; a chunk of 4,096 is
    # 16 of them) where a step holds the most VMEM: an int8 pool's scale
    # columns and float32 dequantized blocks beside the score
    # temporaries, and float32 queries
    "paged_chunk-int8-trinity-window2048-c1024": _paged(
        32, 4, 128, int8=True, chunk=1024, b=1, max_pages=384, window=2048),
    "paged_chunk-int8-trinity-global-c4096": _paged(
        32, 4, 128, int8=True, chunk=4096, b=1, max_pages=384),
    "paged_chunk-f32-trinity-global-c1024": _paged(
        32, 4, 128, chunk=1024, b=1, max_pages=384, q_dtype=F32),
    # the shape the old on-chip sprint checked: d 32 exercises the
    # sub-lane-tile head path of the head-major scratch
    "ssm_decode_update-h64-d64-n128-b1": _ssm_update(1),
    "ssm_decode_update-h64-d64-n128-b16": _ssm_update(16),
    "fused_block-small-d32": _fused(0, 8, h=256, nh=8, nkv=2, d=32,
                                    inter=512, page=16, max_pages=4),
    "fused_nlayer2-small-d32": _fused(2, 8, h=256, nh=8, nkv=2, d=32,
                                      inter=512, page=16, max_pages=4),
}
for _name, _shape in (("7b", {}), ("gqa", _GQA)):
    for _b in (4, 8, 16, 32):
        for _int8 in (False, True):
            for _layers in (0, 1, 2):
                _kind = ("fused_block" if _layers == 0
                         else f"fused_nlayer{_layers}")
                _case = (f"{_kind}{'-int8kv' if _int8 else ''}"
                         f"-{_name}-b{_b}")
                if _case not in _TIER1:
                    _MATRIX[_case] = _fused(_layers, _b, int8=_int8,
                                            **_shape)

# the names the kernels give their ``pl.pallas_call``: what a device trace
# shows in place of ``run`` / ``jvp__`` (the HLO instruction of a Mosaic
# custom call is named after the innermost scope of JAX's name stack,
# which ``name=`` opens)
_NAMES = {
    "flash_fwd": ("flash_fwd",),
    "flash_bwd": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "flash_window_bwd": ("flash_fwd_window", "flash_bwd_dq_window",
                         "flash_bwd_dkv_window"),
    "flash_prefill": ("flash_prefill",),
    "rms_norm_fwd": ("rms_norm",),
    "rms_norm_bwd": ("rms_norm", "rms_norm_bwd"),
    "paged_attention": ("paged_attention",),
    "paged_chunk": ("paged_chunk_attention",),
    "paged_kv_write": ("paged_kv_write",),
    "paged_prompt_write": ("paged_prompt_write",),
    "paged_block_write": ("paged_block_write",),
    "gmm": ("gmm",),
    "tgmm": ("gmm", "tgmm"),
    "ssm_decode_update": ("ssm_decode_update",),
    "fused_block": ("fused_block_decode",),
    "fused_nlayer": ("fused_block_decode_nlayer",),
}


def _names_of(case_id: str):
    return _NAMES[case_id.split("-")[0].rstrip("0123456789")]


_CASES = [pytest.param(name, build, id=name)
          for name, build in _TIER1.items()]
_CASES += [pytest.param(name, build, id=name, marks=pytest.mark.slow)
           for name, build in _MATRIX.items()]


@pytest.mark.parametrize("case_id,build", _CASES)
def test_compiles_for_v5e(chips, case_id, build):
    one_chip = SingleDeviceSharding(chips[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    S.chips = chips
    fn, args = build(S)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, \
        "compiled, but no Pallas kernel in the program: a fallback ran"
    # the kernels' instructions carry the given names; under autodiff
    # JAX's name stack wraps them (``jvp_flash_fwd_``,
    # ``transpose_jvp_flash_bwd_dq__``), so the name is looked for inside
    called = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    for name in _names_of(case_id):
        assert any(name in inst for inst in called), (name, called)


# ------------------------------------------- no serving program copies a pool
@pytest.fixture(scope="module")
def gpt3_345m_serving(chips):
    """gpt3-345m at its published widths and its cell's pool geometry
    (16 KV heads of 64, 513 pages of 64 tokens), cut to two layers: every
    layer writes its pools the same way. The pool has the row width the
    engine allocates it with on the chip, which ``is_tpu_backend`` (the
    autouse fixture) makes ``padded_head_dim`` give here. Layouts are
    left open, so each array has its shape's default on the chip, as a
    concrete array under ``jit`` has. Returns the model, its abstract
    state, and a function giving the three program builders' abstract
    arguments."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.generation import cache_manager, serving

    layers, hkv, d, page, max_pages, max_batch = 2, 16, 64, 64, 16, 32
    one_chip = SingleDeviceSharding(chips[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    cfg = models.GPTConfig(
        vocab_size=50304, hidden_size=1024, num_hidden_layers=layers,
        num_attention_heads=16, intermediate_size=4096,
        max_position_embeddings=1024, layer_norm_epsilon=1e-5,
        tie_word_embeddings=True)
    with paddle.LazyGuard():
        model = models.GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    params, buffers = ({k: S(v.shape, v.dtype) for k, v in state.items()}
                       for state in model.raw_state())
    params = serving.PackedParams.pack(params)

    def programs():
        # called from a test: ``_on_tpu`` is function-scoped
        width = cache_manager.pool_head_dim(model, d, "native")
        pool = S((hkv, max_batch * max_pages + 1, page, width), BF16)
        pools = [(pool, pool)] * layers

        def tail(b):
            return [pools, S((b, max_pages), I32), S((b,), I32)]
        return pool.shape, {
            "serving_decode_generic": (serving._build_generic_decode,
                                       [(S((16,), I32), S((16,), I32))]
                                       + tail(16)),
            "serving_prefill_chunk": (serving._build_chunk_prefill,
                                      [S((1, 256), I32)] + tail(1)
                                      + [S((), I32)]),
            "serving_prefill": (serving._build_prefill,
                                [S((1, 128), I32)] + tail(1)),
        }
    return model, params, buffers, programs


@pytest.mark.parametrize("program", ["serving_decode_generic",
                                     "serving_prefill_chunk",
                                     "serving_prefill"])
def test_serving_program_copies_no_pool(chips, gpt3_345m_serving, program):
    """The pool is written in the layout its readers read and it crosses
    the jit boundary in: the program the chip's compiler emits holds NO
    copy with a pool's shape (the scatter on a head-width pool cost up
    to three per array: into its preferred {3,0,2,1}, on into the
    kernels' row-major, back into the parameter's page-minor default),
    aliases every pool input to output, and writes each layer's pair
    with one page-write kernel."""
    import re
    model, params, buffers, programs = gpt3_345m_serving
    pool_shape, programs = programs()
    assert pool_shape[-1] == 128
    build, args = programs[program]
    text = build(lambda: None, model).lower(
        params, buffers, *args).compile().as_text()
    assert f"HloModule jit_{program}" in text
    shape = ",".join(map(str, pool_shape))
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= bf16\[{shape}\]\S* copy\(", ln)]
    assert not copies, copies
    n_pools = 2 * len(args[1])
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == n_pools, header[:400]
    writes = re.findall(r"%paged_(?:kv|prompt)_write\S* = ", text)
    assert len(writes) == len(args[1]), writes


# ------------------------- nor does one copy a recurrent layer's state
@pytest.fixture(scope="module")
def granite_hybrid_serving(chips):
    """granite-4.0-h-micro at its published widths and its cell's
    geometry (32 slots; 641 pages of 64 tokens for the attention
    layers), cut to one period's three kinds of neighbour: Mamba-2,
    attention, Mamba-2. Returns the model, its abstract state, the
    state's shapes and a function giving the three builders' abstract
    arguments."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.generation import cache_manager, serving
    from paddle_tpu.kernels.recurrent_state import RecurrentSpec

    slots, page, max_pages = 32, 64, 20
    one_chip = SingleDeviceSharding(chips[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    cfg = models.GraniteHybridConfig(
        num_hidden_layers=3, layer_types=("mamba", "attention", "mamba"))
    with paddle.LazyGuard():
        model = models.GraniteHybridForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    params, buffers = ({k: S(v.shape, v.dtype) for k, v in state.items()}
                       for state in model.raw_state())
    params = serving.PackedParams.pack(params)
    spec = model.cache_spec()
    rec = next(e for e in spec if isinstance(e, RecurrentSpec))
    shapes = ((slots,) + rec.ssm_shape, (slots,) + rec.conv_shape)

    def programs():
        width = cache_manager.pool_head_dim(model, 64, "native")
        pool = S((8, slots * max_pages + 1, page, width), BF16)
        pools = ([(pool, pool)], [(S(shapes[0], F32), S(shapes[1], BF16))] * 2)

        def tail(b):
            return [pools, S((b, max_pages), I32), S((b,), I32)]
        return {
            "serving_decode_generic": (serving._build_generic_decode,
                                       [(S((32,), I32), S((32,), I32))]
                                       + tail(32)
                                       + [S((32,), I32)]),
            "serving_prefill_chunk": (serving._build_chunk_prefill,
                                      [S((1, 256), I32)] + tail(1)
                                      + [S((), I32), S((), I32)]),
            "serving_prefill": (serving._build_prefill,
                                [S((1, 160), I32)] + tail(1) + [S((), I32)]),
        }
    return model, params, buffers, shapes, programs


@pytest.mark.parametrize("program", ["serving_decode_generic",
                                     "serving_prefill_chunk",
                                     "serving_prefill"])
def test_serving_program_copies_no_state(chips, granite_hybrid_serving,
                                         program):
    """The recurrent state is changed in place: the program the chip's
    compiler emits holds NO copy with the SSM state's shape, aliases
    every pool and state input to its output, and steps each Mamba-2
    layer of the decode program with one ``ssm_decode_update`` call.
    The convolution's window (0.8 MB a layer) may be staged into fast
    memory, in the layout it has: a copy of it that changes the layout
    is a finding."""
    import re
    model, params, buffers, (ssm_shape, conv_shape), programs = \
        granite_hybrid_serving
    build, args = programs()[program]
    text = build(lambda: None, model).lower(
        params, buffers, *args).compile().as_text()
    assert f"HloModule jit_{program}" in text
    ssm = ",".join(map(str, ssm_shape))
    assert ssm == "32,32,128,128"
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= f32\[{ssm}\]\S* copy\(", ln)]
    assert not copies, copies
    conv = ",".join(map(str, conv_shape))
    for ln in text.splitlines():
        m = re.search(rf"= bf16\[{conv}\](\{{[^}}]*\}}) copy\(", ln)
        if m:
            layout = re.sub(r"S\(\d+\)", "", m.group(1))
            src = re.search(rf"%(\S+)\)", ln.split(" copy(", 1)[1]).group(1)
            src_line = next(x for x in text.splitlines()
                            if x.strip().startswith(f"%{src} = "))
            assert re.sub(r"S\(\d+\)", "", re.search(
                rf"bf16\[{conv}\](\{{[^}}]*\}})", src_line).group(1)) \
                == layout, ln.strip()[:200]
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 2 + 4, \
        header[:400]
    updates = re.findall(r"%ssm_decode_update\S* = ", text)
    assert len(updates) == (2 if program == "serving_decode_generic" else 0)
    writes = re.findall(r"%paged_(?:kv|prompt)_write\S* = ", text)
    assert len(writes) == 1, writes


# ------------------------- the block step of a block-diffusion expert model
@pytest.mark.parametrize("program", ["serving_block_step",
                                     "serving_prefill_chunk"])
def test_sdar_serving_programs_compile_and_copy_no_pool(chips, program):
    """sdar-30b-a3b-chat at its published widths and its cell's geometry
    (64 rows; 1,281 pages of 64 tokens; 128 experts), cut to two layers:
    the block step and the block-causal chunk compile for the chip, hold
    each layer's kernels under their names (the decode paged attention
    with 32 query rows a KV head, the one-page block write or the prompt
    write, two grouped expert matmuls under upstream's name), alias
    every pool input to its
    output and copy none."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.generation import serving

    layers, rows, page, max_pages = 2, 64, 64, 20
    one_chip = SingleDeviceSharding(chips[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    cfg = models.SDARMoEConfig(num_hidden_layers=layers)
    with paddle.LazyGuard():
        model = models.SDARMoEForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    params, buffers = ({k: S(v.shape, v.dtype) for k, v in state.items()}
                       for state in model.raw_state())
    params = serving.PackedParams.pack(params)
    pool = S((4, rows * max_pages + 1, page, 128), BF16)
    pools = [(pool, pool)] * layers
    if program == "serving_block_step":
        fn = serving._build_block_step(lambda: None, model,
                                       cfg.mask_token_id)
        args = [(S((rows, 4), I32), S((rows, 4), I32)), pools,
                S((rows, max_pages), I32), S((rows,), I32),
                S((rows,), I32), S((129,), I32)]
        kernels = {"paged_attention": layers, "paged_block_write": layers,
                   "gmm": 2 * layers}
    else:
        fn = serving._build_chunk_prefill(lambda: None, model)
        args = [S((1, 256), I32), pools, S((1, max_pages), I32),
                S((1,), I32), S((), I32)]
        kernels = {"paged_chunk_attention": layers,
                   "paged_prompt_write": layers,
                   "gmm": 2 * layers}
    text = fn.lower(params, buffers, *args).compile().as_text()
    assert f"HloModule jit_{program}" in text
    for name, count in kernels.items():
        found = re.findall(rf"%{name}\S* = ", text)
        assert len(found) == count, (name, found)
    shape = ",".join(map(str, pool.shape))
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(rf"= bf16\[{shape}\]\S* copy\(", ln)]
    assert not copies, copies
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == 2 * layers, header[:400]


# ------------------- window and global layers over two pools (trinity-mini)
@pytest.mark.parametrize("program", ["serving_decode_generic",
                                     "serving_prefill_chunk"])
def test_afmoe_serving_programs_compile_over_two_pools(chips, program):
    """trinity-mini as its cell runs it (one dense and four sparse
    layers, of which three window layers and one global; 32 rows; a
    global pool of 12,289 pages and a window pool of 1,569; a chunk of
    1,024): the decode step and the chunk compile for the chip, hold
    each layer's kernels under their names (the windowed reads keep
    the names ``paged_attention`` and ``paged_chunk_attention``), alias
    every pool of BOTH stores to its output and copy none."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.generation import serving

    rows, page, max_pages, window_pages = 32, 64, 384, 49
    one_chip = SingleDeviceSharding(chips[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    types = models.AfmoeConfig().layer_types[:5]
    cfg = models.AfmoeConfig(num_hidden_layers=5, num_dense_layers=1,
                             layer_types=types)
    with paddle.LazyGuard():
        model = models.AfmoeForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    params, buffers = ({k: S(v.shape, v.dtype) for k, v in state.items()}
                       for state in model.raw_state())
    params = serving.PackedParams.pack(params)
    whole = S((4, rows * max_pages + 1, page, 128), BF16)
    sliding = S((4, rows * window_pages + 1, page, 128), BF16)
    pools = ([(whole, whole)], [(sliding, sliding)] * 4)
    if program == "serving_decode_generic":
        fn = serving._build_generic_decode(lambda: None, model)
        tables = (S((rows, max_pages), I32), S((rows, max_pages), I32))
        args = [(S((rows,), I32), S((rows,), I32)), pools, tables,
                S((rows,), I32)]
        kernels = {"paged_attention": 5, "paged_kv_write": 5, "gmm": 8}
    else:
        fn = serving._build_chunk_prefill(lambda: None, model)
        tables = (S((1, max_pages), I32), S((1, max_pages), I32))
        args = [S((1, 1024), I32), pools, tables, S((1,), I32), S((), I32)]
        kernels = {"paged_chunk_attention": 5, "paged_prompt_write": 5,
                   "gmm": 8}
    text = fn.lower(params, buffers, *args).compile().as_text()
    assert f"HloModule jit_{program}" in text
    for name, count in kernels.items():
        found = re.findall(rf"%{name}\S* = ", text)
        assert len(found) == count, (name, found)
    # the chunk's head runs on the ONE position the program reads
    assert "1024,200192]" not in text
    for pool in (whole, sliding):
        shape = ",".join(map(str, pool.shape))
        copies = [ln.strip()[:120] for ln in text.splitlines()
                  if re.search(rf"= bf16\[{shape}\]\S* copy\(", ln)]
        assert not copies, copies
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == 2 * 5, header[:400]


def _abstract(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _stats_variant(S):
    """The forward with lane-replicated stats (FLAGS_flash_compact_stats
    off): the one ``pallas_call`` the default flags never reach."""
    from paddle_tpu import flags
    fn, args = _flash(8 * 16, 1024, 64)(S)

    def fwd(*a):
        was = flags.get_flag("flash_compact_stats")
        flags.set_flags({"flash_compact_stats": False})
        try:
            return fn(*a)
        finally:
            flags.set_flags({"flash_compact_stats": was})
    return fwd, args


@pytest.mark.parametrize("case,names", [
    pytest.param(build, _names_of(name), id=name)
    for name, build in _TIER1.items()
    # upstream's kernel is no call site of this repo: it has its jit's name
    if "dp2xmp2" not in name and not name.startswith(("gmm-", "tgmm-"))
] + [pytest.param(_stats_variant, ("flash_fwd_stats",),
                  id="flash_fwd-stats-d64-s1024")])
def test_pallas_call_carries_its_name(case, names):
    """Every ``pl.pallas_call`` site passes ``name=``: traced here on the
    CPU (nothing lowers, nothing runs), the call's equation holds it."""
    fn, args = case(_abstract)
    text = str(jax.make_jaxpr(fn)(*args))
    for name in names:
        assert f"name={name}\n" in text or f"name={name} " in text, name
