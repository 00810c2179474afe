"""Flash-attention Pallas kernel vs dense reference (OpTest pattern:
numpy/jnp reference + gradient check — SURVEY.md §4 fixture 1).

Runs in Pallas interpret mode on CPU; the same code compiles for TPU.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_bshd, flash_tiling,
)


def dense_ref(q, k, v, causal=True, seg_q=None, seg_kv=None):
    """O(S^2) reference in f32."""
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(q.shape[-1])
    mask = jnp.ones(s.shape, bool)
    if causal:
        mask &= jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))[None]
    if seg_q is not None:
        mask &= seg_q[:, :, None] == seg_kv[:, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible kv: zero output (kernel contract)
    any_visible = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(any_visible, p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, vf).astype(q.dtype)


def make_qkv(bh=2, s=256, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shape = (bh, s, d)
    return tuple(jnp.asarray(rng.standard_normal(shape) * 0.5, dtype)
                 for _ in range(3))


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        out = flash_attention(q, k, v, causal=causal)
        ref = dense_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_segment_ids(self):
        q, k, v = make_qkv(bh=2, s=256)
        # two packed sequences per row + a padding segment
        seg = jnp.concatenate([
            jnp.zeros((2, 96), jnp.int32),
            jnp.ones((2, 96), jnp.int32),
            jnp.full((2, 64), 7, jnp.int32),
        ], axis=1)
        out = flash_attention(q, k, v, segment_ids=seg, causal=True)
        ref = dense_ref(q, k, v, causal=True, seg_q=seg, seg_kv=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_fully_masked_rows_emit_zeros(self):
        q, k, v = make_qkv(bh=1, s=128)
        seg_q = jnp.full((1, 128), 3, jnp.int32)
        seg_kv = jnp.full((1, 128), 5, jnp.int32)   # never matches
        out = flash_attention(q, k, v, segment_ids=seg_q,
                              kv_segment_ids=seg_kv, causal=False)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

    def test_non_divisible_seq_raises_not_implemented(self):
        # no multiple-of-128 block <= the 1,024 cap divides 1,100, and
        # 1,100 itself exceeds the cap -> no usable block
        q, k, v = make_qkv(s=1100)
        with pytest.raises(NotImplementedError):
            flash_attention(q, k, v)

    def test_short_non_divisible_seq_runs_single_block(self):
        # seqs <= the block cap snap to one full-length block (Mosaic
        # allows block == overall dim), so 300 now takes the kernel path
        q, k, v = make_qkv(s=300)
        out = flash_attention(q, k, v, causal=True)
        ref = dense_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)

    def test_bshd_layout(self):
        rng = np.random.default_rng(3)
        b, s, h, d = 2, 128, 4, 32
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
                   for _ in range(3))
        out = flash_attention_bshd(q, k, v, causal=True)
        # reference on flattened heads
        qf = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
        kf = jnp.swapaxes(k, 1, 2).reshape(b * h, s, d)
        vf = jnp.swapaxes(v, 1, 2).reshape(b * h, s, d)
        ref = dense_ref(qf, kf, vf, causal=True)
        ref = jnp.swapaxes(ref.reshape(b, h, s, d), 1, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, causal):
        q, k, v = make_qkv(bh=2, s=256, d=64, seed=5)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_ref(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name}")

    def test_grads_with_segments(self):
        q, k, v = make_qkv(bh=1, s=256, seed=9)
        seg = jnp.concatenate([jnp.zeros((1, 128), jnp.int32),
                               jnp.ones((1, 128), jnp.int32)], axis=1)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, segment_ids=seg) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_ref(q, k, v, True, seg, seg) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name}")

    def test_fully_masked_rows_zero_grads(self):
        q, k, v = make_qkv(bh=1, s=128, seed=2)
        seg_q = jnp.full((1, 128), 3, jnp.int32)
        seg_kv = jnp.full((1, 128), 5, jnp.int32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, segment_ids=seg_q, kv_segment_ids=seg_kv,
                causal=False) ** 2)

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), 0.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gk), 0.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gv), 0.0, atol=1e-6)

    def test_head_of_one_and_a_half_lane_tiles(self):
        """A head of 192: wider than the 128 lanes the running stats are
        kept in and no multiple of them, so alpha reaches the accumulator
        by ``_lanes``' column broadcast."""
        q, k, v = make_qkv(bh=2, s=256, d=192, seed=6)
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True)),
            np.asarray(dense_ref(q, k, v, causal=True)),
            atol=2e-5, rtol=2e-5)
        gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda *a: jnp.sum(dense_ref(*a, causal=True) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name}")

    def test_bf16_close(self):
        q, k, v = make_qkv(bh=1, s=128, d=64, seed=4, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        ref = dense_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2)


def _kernel_eqns(jaxpr, kernel=None, found=None):
    """{kernel name: [equation, ...]} of everything inside each
    ``pallas_call`` of ``jaxpr``, however deep (``pl.when`` bodies are
    ``cond`` branches)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        inside = kernel
        if eqn.primitive.name == "pallas_call":
            inside = eqn.params["name"]
            found.setdefault(inside, [])
        elif kernel is not None:
            found[kernel].append(eqn)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)      # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    _kernel_eqns(sub, inside, found)
    return found


def _grad_kernels(h, hkv, d, dtype, segments, compact, block=128):
    """The equations of the three kernels in the gradient of a causal
    call at ``2 x heads`` rows of 256 tokens, under one stat layout."""
    import paddle_tpu
    q = jax.ShapeDtypeStruct((2 * h, 256, d), dtype)
    kv = jax.ShapeDtypeStruct((2 * hkv, 256, d), dtype)
    seg = (jnp.zeros((2 * h, 256), jnp.int32) if segments else None)

    def loss(q_, k_, v_):
        return flash_attention(
            q_, k_, v_, segment_ids=seg, causal=True, block_q=block,
            block_k=block, n_heads=h,
            n_kv_heads=hkv).astype(jnp.float32).sum()

    was = paddle_tpu.get_flags("flash_compact_stats")
    paddle_tpu.set_flags({"flash_compact_stats": compact})
    try:
        return _kernel_eqns(jax.make_jaxpr(
            jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr)
    finally:
        paddle_tpu.set_flags(
            {"flash_compact_stats": was["FLAGS_flash_compact_stats"]})


# what the call looks like: (heads, kv heads, head dim, segment ids)
_OPERAND_CASES = {
    "causal-d64": (1, 1, 64, False),
    "gqa-d128": (4, 2, 128, False),
    "segments-d64": (1, 1, 64, True),
}


@pytest.mark.parametrize("compact", [True, False],
                         ids=["compact", "replicated"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(_OPERAND_CASES))
def test_products_take_operands_in_the_input_dtype(case, dtype, compact):
    """Every product of the three kernels multiplies in the dtype q, k, v
    (and so dO) arrive in and accumulates in float32: bf16 inputs reach
    the MXU as stored, with P and dS rounded right before their products;
    float32 inputs keep float32 products. Fails the day someone puts an
    ``astype(jnp.float32)`` back in front of a product."""
    h, hkv, d, segments = _OPERAND_CASES[case]
    dots = {
        kernel: [tuple(str(v.aval.dtype) for v in (*e.invars, *e.outvars))
                 for e in eqns if e.primitive.name == "dot_general"]
        for kernel, eqns in _grad_kernels(h, hkv, d, dtype, segments,
                                          compact).items()}
    fwd = "flash_fwd" if compact else "flash_fwd_stats"
    name = jnp.dtype(dtype).name
    assert {k: len(v) for k, v in dots.items()} == {
        fwd: 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}, dots
    for kernel, products in dots.items():
        for product in products:
            assert product == (name, name, "float32"), (kernel, products)


@pytest.mark.parametrize("compact", [True, False],
                         ids=["compact", "replicated"])
@pytest.mark.parametrize("case", list(_OPERAND_CASES))
def test_block_step_keeps_stats_lane_wide_and_transposes_no_block(
        case, compact):
    """What the block step waited for on the chip, pinned where the CPU
    can see it (KERNEL_DECISIONS.md "Flash attention operands"): the
    forward's running statistics stay a lane tile wide (its two ``exp``
    are of the (bq, bk) block and of a (bq, 128) stat, never of a
    (bq, 1) column), and dk/dv work on transposed scores, so no product
    contracts its left operand's rows and nothing two-dimensional is
    transposed there."""
    h, hkv, d, segments = _OPERAND_CASES[case]
    kernels = _grad_kernels(h, hkv, d, jnp.bfloat16, segments, compact,
                            block=256)
    fwd = kernels["flash_fwd" if compact else "flash_fwd_stats"]
    exps = [e.invars[0].aval.shape for e in fwd if e.primitive.name == "exp"]
    assert sorted(exps) == [(256, 128), (256, 256)], exps
    dkv = kernels["flash_bwd_dkv"]
    for e in dkv:
        if e.primitive.name == "dot_general":
            (lhs_contract, _), _ = e.params["dimension_numbers"]
            assert lhs_contract == (1,), e
        if e.primitive.name == "transpose":
            assert 1 in e.invars[0].aval.shape, e    # an id row at most


# bf16 gradients against the float32 dense twin on the same values:
# (heads, kv heads, head dim, causal)
_BF16_GRAD_CASES = {
    "causal-d64": (1, 1, 64, True),
    "causal-d128": (1, 1, 128, True),
    "gqa-d64": (4, 2, 64, True),
    "gqa-d128": (4, 2, 128, True),
    "full-d64": (1, 1, 64, False),
}


@pytest.mark.parametrize("case", list(_BF16_GRAD_CASES))
def test_bf16_grads_match_float32_ref(case):
    """dq, dk, dv of the kernels on bf16 inputs (bf16 products, P and dS
    rounded to bf16) against ``flash_attention_ref`` in float32 on the
    same values, at this file's bf16 tolerance."""
    from paddle_tpu.kernels.flash_attention import flash_attention_ref
    h, hkv, d, causal = _BF16_GRAD_CASES[case]
    rng = np.random.default_rng(17)
    q = jnp.asarray(rng.standard_normal((2 * h, 256, d)) * 0.5, jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((2 * hkv, 256, d)) * 0.5,
                        jnp.bfloat16) for _ in range(2))
    w = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss(fn, **kw):
        return lambda q_, k_, v_: (fn(
            q_, k_, v_, causal=causal, n_heads=h, n_kv_heads=hkv,
            **kw).astype(jnp.float32) * w).sum()

    got = jax.grad(loss(flash_attention, block_q=128, block_k=128),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(flash_attention_ref), argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v)))
    for a, b, name in zip(got, want, "qkv"):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=3e-2, rtol=3e-2, err_msg=f"d{name}")


@pytest.mark.parametrize("d", [64, 128])
def test_bf16_fully_masked_rows_zero_grads(d):
    """Rows whose segment matches no kv position: zeros stay zeros
    through the bf16 products, forward and backward."""
    q, k, v = make_qkv(bh=1, s=256, d=d, seed=2, dtype=jnp.bfloat16)
    seg_q = jnp.full((1, 256), 3, jnp.int32)
    seg_kv = jnp.full((1, 256), 5, jnp.int32)

    def loss(q_, k_, v_):
        return jnp.sum(flash_attention(
            q_, k_, v_, segment_ids=seg_q, kv_segment_ids=seg_kv,
            causal=False, block_q=128, block_k=128).astype(jnp.float32) ** 2)

    out = flash_attention(q, k, v, segment_ids=seg_q, kv_segment_ids=seg_kv,
                          causal=False, block_q=128, block_k=128)
    assert not np.asarray(out, np.float32).any()
    for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v):
        assert not np.asarray(g, np.float32).any()


class TestGQA:
    """GQA path: unexpanded kv via BlockSpec index maps — fwd/bwd must
    equal the repeat_interleave + MHA reference exactly."""

    def _data(self, b=2, s=64, h=8, hkv=2, d=32, seed=9):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
        return q, k, v, h // hkv

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_expanded(self, causal):
        from paddle_tpu.kernels.flash_attention import flash_attention_bshd
        q, k, v, rep = self._data()
        out = flash_attention_bshd(q, k, v, causal=causal, block_q=32,
                                   block_k=32)
        ref = flash_attention_bshd(q, jnp.repeat(k, rep, axis=2),
                                   jnp.repeat(v, rep, axis=2),
                                   causal=causal, block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_expanded(self):
        from paddle_tpu.kernels.flash_attention import flash_attention_bshd
        q, k, v, rep = self._data(s=32)

        def loss_gqa(q, k, v):
            return flash_attention_bshd(q, k, v, causal=True, block_q=16,
                                        block_k=16).sum()

        def loss_ref(q, k, v):
            return flash_attention_bshd(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                causal=True, block_q=16, block_k=16).sum()

        gq, gk, gv = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
        # jnp.repeat's transpose already sums the group back to Hkv heads
        rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                                   rtol=2e-4, atol=2e-4)

    def test_segment_ids_with_gqa(self):
        from paddle_tpu.kernels.flash_attention import flash_attention_bshd
        q, k, v, rep = self._data(b=1, s=32)
        seg = jnp.asarray(
            np.repeat(np.arange(2), 16)[None, :], jnp.int32)
        out = flash_attention_bshd(q, k, v, segment_ids=seg, causal=True,
                                   block_q=16, block_k=16)
        ref = flash_attention_bshd(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            segment_ids=seg, causal=True, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestCompactStats:
    """FLAGS_flash_compact_stats: the compact stat layout (scratch-stat
    fwd + in-kernel transposed (1, bq) bwd loads) must be numerically
    identical to the replicated layout on every path — causal/full,
    segments, GQA, fwd and bwd (VERDICT r3 item 4)."""

    @pytest.fixture(autouse=True)
    def _flag(self):
        import paddle_tpu
        paddle_tpu.set_flags({"flash_compact_stats": True})
        yield
        paddle_tpu.set_flags({"flash_compact_stats": False})

    def _grads(self, fn, *args, wrt=(0, 1, 2)):
        loss = lambda *a: fn(*a).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=wrt)(*args)

    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_bwd_matches_replicated(self, causal):
        import paddle_tpu
        q, k, v = make_qkv(s=256)
        fn = functools.partial(flash_attention, causal=causal)
        out_c = fn(q, k, v)
        g_c = self._grads(fn, q, k, v)
        paddle_tpu.set_flags({"flash_compact_stats": False})
        out_r = fn(q, k, v)
        g_r = self._grads(fn, q, k, v)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                                   atol=1e-6, rtol=1e-6)
        for a, b, n in zip(g_c, g_r, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"d{n}")

    def test_segments_match_dense(self):
        q, k, v = make_qkv(bh=2, s=256, seed=3)
        seg = jnp.concatenate([
            jnp.zeros((2, 128), jnp.int32), jnp.ones((2, 128), jnp.int32),
        ], axis=1)
        out = flash_attention(q, k, v, segment_ids=seg, causal=True)
        ref = dense_ref(q, k, v, causal=True, seg_q=seg, seg_kv=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gf = self._grads(functools.partial(
            flash_attention, segment_ids=seg, causal=True), q, k, v)
        gd = self._grads(functools.partial(
            dense_ref, causal=True, seg_q=seg, seg_kv=seg), q, k, v)
        for a, b, n in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{n}")

    def test_gqa_matches_dense(self):
        from paddle_tpu.kernels.flash_attention import flash_attention_bshd
        rng = np.random.default_rng(9)
        b, s, h, hkv, d = 1, 256, 4, 2, 32
        q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5,
                        jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((b, s, hkv, d)) * 0.5,
                            jnp.float32) for _ in range(2))

        def dense_bshd(q, k, v):
            rep = q.shape[2] // k.shape[2]
            kr = jnp.repeat(k, rep, axis=2)
            vr = jnp.repeat(v, rep, axis=2)
            bh = q.shape[0] * q.shape[2]
            to = lambda t: jnp.swapaxes(t, 1, 2).reshape(bh, s, d)
            out = dense_ref(to(q), to(kr), to(vr), causal=True)
            return jnp.swapaxes(out.reshape(q.shape[0], q.shape[2], s, d),
                                1, 2)

        out = flash_attention_bshd(q, k, v, causal=True)
        ref = dense_bshd(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gf = self._grads(functools.partial(flash_attention_bshd,
                                           causal=True), q, k, v)
        gd = self._grads(dense_bshd, q, k, v)
        for a, b_, n in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{n}")


def test_compact_stats_kill_replicated_transients():
    """The compact layout must remove the lane-replicated (BH, S, 128)
    stat arrays from the bwd program. Those broadcasts live in XLA
    (outside the pallas calls), so the lowered HLO shows them as
    f32[BH,S,128] operands on any backend; the compact program must
    carry none."""
    import paddle_tpu

    bh, s, d = 8, 2048, 64
    q = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16)

    def loss(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True).astype(
            jnp.float32).sum()

    rep_sig = f"8x{s}x128xf32"

    # NB: fresh function objects per lowering — jit's trace cache keys on
    # function identity + avals, so reusing one grad object would hand the
    # second lowering the first layout's cached trace (the flag, like any
    # trace-time flag, must be set before tracing).
    paddle_tpu.set_flags({"flash_compact_stats": True})
    try:
        compact_hlo = jax.jit(
            jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()
    finally:
        paddle_tpu.set_flags({"flash_compact_stats": False})
    rep_hlo = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()

    assert rep_sig in rep_hlo          # the replicated transients exist
    assert rep_sig not in compact_hlo  # and the compact layout sheds them


class TestReferenceFlashAPI:
    """The reference's user-facing names (python/paddle/nn/functional/
    flash_attention.py): flash_attention and the varlen packed form."""

    def test_flash_attention_matches_sdpa(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(20)
        q = paddle.to_tensor(rng.standard_normal((2, 16, 4, 32))
                             .astype(np.float32))
        out, sm = F.flash_attention(q, q, q, causal=True)
        assert sm is None
        ref = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-6)

    def test_flash_attn_unpadded_varlen_causal(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(21)
        lens = [5, 7, 3]
        tot, h, d = sum(lens), 4, 32
        q = paddle.to_tensor(rng.standard_normal((tot, h, d))
                             .astype(np.float32))
        k = paddle.to_tensor(rng.standard_normal((tot, h, d))
                             .astype(np.float32))
        v = paddle.to_tensor(rng.standard_normal((tot, h, d))
                             .astype(np.float32))
        cu = np.cumsum([0] + lens).astype(np.int32)   # reference style
        out, _ = F.flash_attn_unpadded(q, k, v, cu, cu, max(lens),
                                       max(lens), causal=True)
        o = out.numpy()
        start = 0
        for L in lens:
            qs = q.numpy()[start:start + L]
            ks = k.numpy()[start:start + L]
            vs = v.numpy()[start:start + L]
            s = np.einsum("qhd,khd->hqk", qs, ks) / np.sqrt(d)
            m = np.tril(np.ones((L, L), bool))
            s = np.where(m[None], s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hqk,khd->qhd", p, vs)
            np.testing.assert_allclose(o[start:start + L], ref,
                                       rtol=2e-4, atol=2e-4)
            start += L

    def test_unpadded_grads_flow(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(22)
        q = paddle.to_tensor(rng.standard_normal((8, 2, 16))
                             .astype(np.float32), stop_gradient=False)
        cu = np.array([0, 3, 8], np.int32)
        out, _ = F.flash_attn_unpadded(q, q, q, cu, cu, 5, 5, causal=True)
        out.sum().backward()
        assert q.grad is not None
        assert float(np.abs(q.grad.numpy()).sum()) > 0

    def test_unpadded_cross_attention_causal_uses_local_positions(self):
        """cu_seqlens_q != cu_seqlens_k with causal=True: masking is by
        LOCAL per-sequence positions (top-left alignment), not global
        packed indices (code-review r05: global indices would mask whole
        rows to zero)."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        rng = np.random.default_rng(23)
        lens_q, lens_k = [2, 3], [4, 5]
        tq, tk, h, d = sum(lens_q), sum(lens_k), 2, 16
        q = paddle.to_tensor(rng.standard_normal((tq, h, d))
                             .astype(np.float32))
        k = paddle.to_tensor(rng.standard_normal((tk, h, d))
                             .astype(np.float32))
        v = paddle.to_tensor(rng.standard_normal((tk, h, d))
                             .astype(np.float32))
        cu_q = np.cumsum([0] + lens_q).astype(np.int32)
        cu_k = np.cumsum([0] + lens_k).astype(np.int32)
        out, _ = F.flash_attn_unpadded(q, k, v, cu_q, cu_k, max(lens_q),
                                       max(lens_k), causal=True)
        o = out.numpy()
        assert np.abs(o).sum() > 0            # not masked to nothing
        sq = sk = 0
        for Lq, Lk in zip(lens_q, lens_k):
            qs = q.numpy()[sq:sq + Lq]
            ks = k.numpy()[sk:sk + Lk]
            vs = v.numpy()[sk:sk + Lk]
            s = np.einsum("qhd,khd->hqk", qs, ks) / np.sqrt(d)
            m = np.arange(Lq)[:, None] >= np.arange(Lk)[None, :]
            s = np.where(m[None], s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum("hqk,khd->qhd", p, vs)
            np.testing.assert_allclose(o[sq:sq + Lq], ref,
                                       rtol=2e-4, atol=2e-4)
            sq += Lq
            sk += Lk

    def test_reference_trailing_kwargs_accepted(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        q = paddle.to_tensor(np.ones((6, 2, 16), np.float32))
        cu = np.array([0, 3, 6], np.int32)
        out, _ = F.flash_attn_unpadded(
            q, q, q, cu, cu, 3, 3, None, 0.1, True, False,
            fixed_seed_offset=None, rng_name="", training=False)
        assert out.shape == [6, 2, 16]        # eval dropout is a no-op


class TestMeshPartitioned:
    """GSPMD cannot partition a Mosaic kernel, so inside a declared
    ``activation_layout`` the (B, S, H, D) entry runs the kernel per
    (batch, head) shard (tests/test_chip_compile.py compiles that for
    the chip; here: same numbers as the unsplit call, forward and
    backward, GQA + segments). The axis names are the declarer's."""

    def test_declared_layout_matches_unsplit(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.kernels.flash_attention import activation_layout
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("rows", "cols"))
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((4, 128, 4, 32)) * 0.5,
                        jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((4, 128, 2, 32)) * 0.5,
                            jnp.float32) for _ in range(2))
        seg = jnp.asarray(np.repeat([[0, 1]], 64, axis=1).repeat(4, 0),
                          jnp.int32)

        def loss(q, k, v):
            out = flash_attention_bshd(q, k, v, segment_ids=seg,
                                       block_q=64, block_k=64)
            return (out * out).sum(), out

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
        want, want_out = grad(q, k, v)
        put = functools.partial(
            jax.device_put,
            device=NamedSharding(mesh, P("rows", None, "cols", None)))
        with activation_layout(mesh, ("rows",), "cols"):
            got, got_out = grad(put(q), put(k), put(v))
        assert len(got_out.sharding.device_set) == 4
        np.testing.assert_allclose(got_out, want_out, rtol=2e-5, atol=2e-5)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)

    def test_split_follows_the_declaration_or_says_why_not(self):
        from jax.sharding import AbstractMesh
        from paddle_tpu.kernels.flash_attention import (
            FlashPartitionError, _mesh_split, activation_layout)

        class mesh:     # what activation_layout needs of a Mesh
            def __init__(self, sizes, names):
                self.abstract_mesh = AbstractMesh(sizes, names)

        assert _mesh_split(4, 4, 2) is None          # nothing declared
        with jax.sharding.use_abstract_mesh(
                AbstractMesh((2, 2), ("dp", "mp"))):
            assert _mesh_split(4, 4, 2) is None      # a mesh, no layout
        with activation_layout(mesh((2, 1, 2), ("x", "pp", "y")),
                               ("x",), "y"):
            assert _mesh_split(4, 4, 2) == (
                ("x",), "y", frozenset({"x", "pp", "y"}))
            with pytest.raises(FlashPartitionError, match="batch 3"):
                _mesh_split(3, 4, 2)
            with pytest.raises(FlashPartitionError, match="3 kv heads"):
                _mesh_split(4, 6, 3)
        with activation_layout(mesh((2, 2), ("dp", "sep")), ("dp",), "mp"):
            with pytest.raises(FlashPartitionError, match=r"\['sep'\]"):
                _mesh_split(4, 4, 2)
        assert _mesh_split(4, 4, 2) is None          # context restored

    def test_train_step_declares_data_and_tensor_parallel_axes(self):
        """The layout comes from the layer that owns it: TrainStep's data
        axes, and the one other axis its parameter specs name."""
        import paddle_tpu as paddle
        from jax.sharding import Mesh
        from paddle_tpu.hapi import TrainStep
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama import annotate_llama_tp
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("data", "tensor"))
        model = LlamaForCausalLM(LlamaConfig.tiny())
        annotate_llama_tp(model, "tensor")
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = TrainStep(model, opt, mesh=mesh, data_axes=("data",))
        assert step._layout == (("data",), "tensor")


class TestTiling:
    """``flash_tiling``: the blocks a call takes from its own shapes —
    the widest side up to the cap that divides each axis and keeps a
    step inside the VMEM budget (KERNEL_DECISIONS.md "Flash attention
    tiling")."""

    @pytest.mark.parametrize("s_q,s_k,d,itemsize,want", [
        (1024, 1024, 64, 2, (1024, 1024)),   # gpt3-345m.train: the whole seq
        (4096, 4096, 128, 2, (1024, 1024)),  # mistral-7b, a chip's share
        (256, 256, 64, 4, (256, 256)),       # under the cap: one block
        (640, 640, 64, 2, (640, 640)),
        (1664, 1664, 64, 2, (128, 128)),     # 13 x 128: snaps to 128
        (3072, 3072, 64, 2, (1024, 1024)),
        (2560, 2560, 64, 2, (640, 640)),     # 1,024 does not divide it
        (4096, 4096, 40, 4, (1024, 1024)),   # head 40 (non-causal cases)
        (256, 4096, 64, 2, (256, 1024)),     # queries shorter than keys
        (4096, 4096, 256, 4, (512, 512)),    # wide float32 head: VMEM binds
        (1100, 1100, 64, 2, (0, 0)),         # no usable block: kernels raise
    ], ids=["gpt3", "mistral", "short", "short-odd", "snap-1664", "s3072",
            "s2560", "d40", "cross", "d256-f32", "none"])
    def test_rule(self, s_q, s_k, d, itemsize, want):
        got = flash_tiling(s_q, s_k, d, itemsize)
        assert got == want
        if all(got):
            assert s_q % got[0] == 0 and s_k % got[1] == 0
            assert fa._step_vmem(*got, d, itemsize) <= fa._STEP_VMEM_BYTES

    @pytest.mark.parametrize("d", [64, 128, 192])
    def test_head_128_inside_the_budget_and_wider_blocks_not(self, d):
        """At the cells' heads the tiling's blocks fit the budget and the
        next wider block of either side would not: the budget, not the
        cap alone, is what stops 2,048 at a head of 128."""
        bq, bk = flash_tiling(4096, 4096, d, 2)
        assert (bq, bk) == (1024, 1024)
        assert fa._step_vmem(bq, bk, d, 2) <= fa._STEP_VMEM_BYTES
        assert fa._step_vmem(2 * bq, bk, d, 2) > fa._STEP_VMEM_BYTES

    def test_large_steps_ask_for_their_vmem(self):
        """A step counted past 12 MiB asks Mosaic for its VMEM (the
        default scope is 16 MiB); a 512 x 512 step asks for nothing."""
        assert fa._compiler_params(512, 512, 64, 2) == {}
        params = fa._compiler_params(1024, 1024, 128, 2)["compiler_params"]
        assert params.vmem_limit_bytes == \
            fa._step_vmem(1024, 1024, 128, 2) + (16 << 20)

    @pytest.mark.parametrize("case", ["causal", "noncausal", "segments",
                                      "gqa"])
    def test_parity_at_the_derived_tiles(self, case, monkeypatch):
        """Two 1,024 blocks a side — the derived tiles of a 2,048-token
        call, the causal diagonal skipping a whole block — forward and
        backward against the dense reference, no blocks passed."""
        from paddle_tpu.kernels.flash_attention import flash_attention_ref
        seen = []
        real = fa._fwd_setup
        monkeypatch.setattr(fa, "_fwd_setup", lambda q, k, bq, bk, *a: (
            seen.append((bq, bk)) or real(q, k, bq, bk, *a)))
        h, hkv = (4, 2) if case == "gqa" else (1, 1)
        rng = np.random.default_rng(17)
        s, d = 2048, 32
        q = jnp.asarray(rng.standard_normal((h, s, d)) * 0.5, jnp.float32)
        k, v = (jnp.asarray(rng.standard_normal((hkv, s, d)) * 0.5,
                            jnp.float32) for _ in range(2))
        causal = case != "noncausal"
        seg = None
        if case == "segments":
            seg = jnp.concatenate([jnp.zeros((1, 1536), jnp.int32),
                                   jnp.ones((1, 512), jnp.int32)], axis=1)
        kw = dict(causal=causal, n_heads=h, n_kv_heads=hkv)

        def loss(fn, **extra):
            return lambda *a: jnp.sum(fn(*a, **kw, **extra) ** 2)

        got = jax.value_and_grad(loss(flash_attention, segment_ids=seg),
                                 argnums=(0, 1, 2))(q, k, v)
        want = jax.value_and_grad(loss(flash_attention_ref,
                                       segment_ids=seg),
                                  argnums=(0, 1, 2))(q, k, v)
        assert seen and set(seen) == {(1024, 1024)}
        np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-4)
        for a, b, name in zip(got[1], want[1], "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4,
                                       err_msg=f"d{name}")


class TestDispatchTable:
    """Per-shape dispatch (FLAGS_flash_dispatch_table): benched-slower
    shape buckets must resolve to the dense path, benched-faster ones to
    the kernel at the blocks its shapes give — VERDICT r05: a fused
    path that loses to the unfused one has no reason to exist."""

    def _resolve(self, seq, table):
        import paddle_tpu
        from paddle_tpu.kernels.flash_attention import resolve_dispatch
        prior = paddle_tpu.get_flags("flash_dispatch_table")
        paddle_tpu.set_flags({"flash_dispatch_table": table})
        try:
            return resolve_dispatch(seq)
        finally:
            paddle_tpu.set_flags(
                {"flash_dispatch_table": prior["FLAGS_flash_dispatch_table"]})

    def test_default_table_buckets(self):
        """The shipped default encodes the ATTN_BENCH_r05 A/B: flash at
        1024 (1.01x), dense at 2048 (0.86x — the losing row), flash
        again at 4096+ (76.0ms vs 100.6 dense): mistral-7b.train-dp2mp2's
        4,096-token attention stays on the kernels."""
        from paddle_tpu.kernels.flash_attention import resolve_dispatch
        assert resolve_dispatch(1024) == "flash"
        assert resolve_dispatch(2048) == "dense"
        assert resolve_dispatch(3072) == "dense"
        assert resolve_dispatch(4096) == "flash"
        assert resolve_dispatch(8192) == "flash"
        # below every bucket: flash
        assert resolve_dispatch(128) == "flash"

    def test_override_and_disable(self):
        assert self._resolve(2048, "") == "flash"   # table off
        assert self._resolve(2048, "0:dense") == "dense"
        assert self._resolve(512, "0:flash;1024:dense") == "flash"
        assert self._resolve(1024, "0:flash;1024:dense") == "dense"
        # malformed entries never take the kernel down — default to flash;
        # a block-size entry (the removed 'BQxBK' form) is one of them
        assert self._resolve(2048, "0:flash;bogus;2048:99xx") == "flash"
        assert self._resolve(4096, "0:dense;4096:512x512") == "flash"

    def test_parity_across_dispatch_outcomes(self):
        """Both outcomes of a bucketed table agree numerically with the
        dense reference: the 'flash with block override' bucket via the
        kernel, the 'dense' bucket via sdpa's XLA path."""
        q, k, v = make_qkv(bh=2, s=256, d=64)
        ref = dense_ref(q, k, v, causal=True)
        # bucket -> the kernel, at the tiling's blocks and at explicit ones
        for blocks in ({}, {"block_q": 128, "block_k": 128}):
            out = flash_attention(q, k, v, causal=True, **blocks)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)
        # bucket -> dense: sdpa on CPU takes the dense path; same numbers
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        qb = paddle.to_tensor(np.asarray(q).reshape(2, 1, 256, 64)
                              .transpose(0, 2, 1, 3))
        kb = paddle.to_tensor(np.asarray(k).reshape(2, 1, 256, 64)
                              .transpose(0, 2, 1, 3))
        vb = paddle.to_tensor(np.asarray(v).reshape(2, 1, 256, 64)
                              .transpose(0, 2, 1, 3))
        dense = F.scaled_dot_product_attention(qb, kb, vb, is_causal=True)
        got = np.asarray(dense.value).transpose(0, 2, 1, 3).reshape(2, 256, 64)
        np.testing.assert_allclose(got, np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_sdpa_dispatch_consults_table(self, monkeypatch):
        """On a TPU backend sdpa must route benched-slower buckets to
        dense: with the table pinning every shape to dense, the flash
        kernel is never entered (probed via an import-time hook)."""
        import paddle_tpu
        import paddle_tpu.nn.functional as F
        from paddle_tpu import flags as flags_mod
        from paddle_tpu.kernels import flash_attention as fa

        calls = []
        monkeypatch.setattr(
            fa, "flash_attention_bshd",
            lambda *a, **kw: calls.append(1) or (_ for _ in ()).throw(
                NotImplementedError()))
        monkeypatch.setattr(flags_mod, "is_tpu_backend", lambda: True)
        prior = paddle_tpu.get_flags("flash_dispatch_table")
        q = paddle_tpu.to_tensor(
            np.random.default_rng(0).standard_normal(
                (1, 1024, 2, 16)).astype(np.float32))
        try:
            paddle_tpu.set_flags({"flash_dispatch_table": "0:dense"})
            F.scaled_dot_product_attention(q, q, q, is_causal=True)
            assert not calls, "dense bucket must not enter the kernel"
            paddle_tpu.set_flags({"flash_dispatch_table": "0:flash"})
            F.scaled_dot_product_attention(q, q, q, is_causal=True)
            assert calls, "flash bucket must reach the kernel"
        finally:
            paddle_tpu.set_flags(
                {"flash_dispatch_table": prior["FLAGS_flash_dispatch_table"]})


class TestRefTwin:
    """flash_attention_ref: the pure-jnp twin the kernelcheck ref-twin
    census (KRN006) names as the parity oracle — it must agree with the
    kernel on every path it claims to mirror."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_kernel(self, causal):
        from paddle_tpu.kernels.flash_attention import flash_attention_ref
        q, k, v = make_qkv()
        out = flash_attention(q, k, v, causal=causal)
        ref = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_matches_kernel(self):
        from paddle_tpu.kernels.flash_attention import flash_attention_ref
        rng = np.random.default_rng(11)
        b, s, h, hkv, d = 2, 64, 4, 2, 32
        q = jnp.asarray(rng.standard_normal((b * h, s, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b * hkv, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b * hkv, s, d)), jnp.float32)
        out = flash_attention(q, k, v, causal=True, n_heads=h,
                              n_kv_heads=hkv, block_q=32, block_k=32)
        ref = flash_attention_ref(q, k, v, causal=True, n_heads=h,
                                  n_kv_heads=hkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_segments_and_masked_rows(self):
        from paddle_tpu.kernels.flash_attention import flash_attention_ref
        q, k, v = make_qkv(bh=2, s=256)
        seg = jnp.concatenate([
            jnp.zeros((2, 96), jnp.int32),
            jnp.ones((2, 96), jnp.int32),
            jnp.full((2, 64), 7, jnp.int32),
        ], axis=1)
        out = flash_attention(q, k, v, segment_ids=seg, causal=True)
        ref = flash_attention_ref(q, k, v, segment_ids=seg, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # fully-masked rows: the ref mirrors the kernel's zeros contract
        seg_q = jnp.full((2, 256), 3, jnp.int32)
        seg_kv = jnp.full((2, 256), 5, jnp.int32)
        ref = flash_attention_ref(q, k, v, segment_ids=seg_q,
                                  kv_segment_ids=seg_kv, causal=False)
        np.testing.assert_allclose(np.asarray(ref), 0.0, atol=1e-6)
