"""Paged KV-cache attention (paddle_tpu/kernels/paged_attention.py).

Reference parity target: block_multihead_attention, the reference's
vLLM-style block-attention serving op. Invariants under test:

  - the Pallas kernel (interpret mode on the CPU mesh) == the gather-based
    XLA reference == a dense einsum over the logically-contiguous cache,
    for ragged lengths, shuffled page tables, and GQA;
  - the pool manager allocates exactly ceil(len/page) pages, recycles
    freed pages, and reproduces ring-buffer attention end-to-end through
    a prefill + decode loop.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.paged_attention import (PagedKVCache,
                                                QuantizedPages,
                                                paged_attention,
                                                paged_attention_xla,
                                                quantize_kv_rows,
                                                write_paged_kv,
                                                write_paged_prompt)


def make_pool(rng, hkv=2, num_pages=16, page=8, d=32, dtype=jnp.float32):
    k = jnp.asarray(rng.standard_normal((hkv, num_pages, page, d)) * 0.5,
                    dtype)
    v = jnp.asarray(rng.standard_normal((hkv, num_pages, page, d)) * 0.5,
                    dtype)
    return k, v


def dense_ref(q, k_pages, v_pages, bt, sl):
    """Gather to contiguous, then plain masked attention in f64-ish f32."""
    b, h, d = q.shape
    hkv, _, page, _ = k_pages.shape
    rep = h // hkv
    out = np.zeros((b, h, d), np.float32)
    kp = np.asarray(k_pages, np.float32)
    vp = np.asarray(v_pages, np.float32)
    for r in range(b):
        t = int(sl[r])
        n_pages = -(-t // page)
        k = np.concatenate([kp[:, bt[r, i]] for i in range(n_pages)],
                           axis=1)[:, :t]          # (hkv, t, d)
        v = np.concatenate([vp[:, bt[r, i]] for i in range(n_pages)],
                           axis=1)[:, :t]
        for head in range(h):
            kv = head // rep
            s = (np.asarray(q, np.float32)[r, head] @ k[kv].T) / np.sqrt(d)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[r, head] = p @ v[kv]
    return out


class TestPagedKernelParity:
    @pytest.mark.parametrize("h,hkv", [(2, 2), (8, 2)])  # MHA and GQA
    def test_kernel_matches_dense_ragged(self, h, hkv):
        rng = np.random.default_rng(0)
        b, d, page, num_pages = 3, 32, 8, 16
        k_pages, v_pages = make_pool(rng, hkv, num_pages, page, d)
        q = jnp.asarray(rng.standard_normal((b, h, d)) * 0.5, jnp.float32)
        # shuffled, non-contiguous page assignment + ragged lengths
        bt = np.zeros((b, 4), np.int32)
        perm = rng.permutation(num_pages)
        bt[0, :2] = perm[:2]
        bt[1, :4] = perm[2:6]
        bt[2, :1] = perm[6:7]
        sl = np.array([13, 29, 5], np.int32)      # partial last pages

        out_k = paged_attention(q, k_pages, v_pages, bt, sl)
        out_x = paged_attention_xla(q, k_pages, v_pages, bt, sl)
        ref = dense_ref(q, k_pages, v_pages, bt, sl)
        np.testing.assert_allclose(np.asarray(out_k), ref, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_x), ref, rtol=2e-5,
                                   atol=2e-5)

    def test_single_page_and_exact_page_boundary(self):
        rng = np.random.default_rng(1)
        hkv, page, d = 2, 8, 32
        k_pages, v_pages = make_pool(rng, hkv, 8, page, d)
        q = jnp.asarray(rng.standard_normal((2, 4, d)) * 0.5, jnp.float32)
        bt = np.array([[3, 0], [5, 1]], np.int32)
        sl = np.array([8, 16], np.int32)          # exactly 1 and 2 pages
        out = paged_attention(q, k_pages, v_pages, bt, sl)
        ref = dense_ref(q, k_pages, v_pages, bt, sl)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)

    def test_bf16_pool(self):
        rng = np.random.default_rng(2)
        k_pages, v_pages = make_pool(rng, 2, 8, 8, 32, jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((2, 4, 32)) * 0.5, jnp.bfloat16)
        bt = np.array([[1, 2], [4, 0]], np.int32)
        sl = np.array([11, 8], np.int32)
        out = paged_attention(q, k_pages, v_pages, bt, sl)
        ref = dense_ref(q, k_pages, v_pages, bt, sl)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, rtol=3e-2, atol=3e-2)


# The serving cells' decode shapes (page 64, a 128-lane pool row): gpt3-345m
# (16 KV heads, one query head each, 16 pages a row, the default scale) and
# granite-4.0-h-micro's attention layers (8 KV heads x 4, 20 pages a row —
# not a multiple of the pages a grid step holds — and its own softmax scale).
_CELL_SHAPES = {"gpt3": (16, 1, 16, None), "granite": (8, 4, 20, 1.0 / 64)}
_POISON = 100.0


def _cell_lengths(b, max_pages, page=64):
    """Row lengths for a rung of ``b``: an idle slot (0), 1, the table's
    full width and one that ends mid-page; a wider rung adds a page's
    edge, one token short of the full width, and seeded others."""
    full = max_pages * page
    lens = [0, 1, full, 3 * page + 17, page, full - 1, 5 * page + 63]
    more = np.random.default_rng(b).integers(1, full + 1, size=b)
    return np.array((lens + list(more))[:b], np.int32)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("b", [4, 32])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_kernel_at_the_cells_shapes(cell, b, kv_dtype):
    """The decode kernel against the gather reference at the shapes the
    benchmark's cells run it at. Everything a row does not hold is
    poison: the keys past its length inside its last page, and the null
    page 0 that every unused block-table entry names — a key that leaks
    moves the output by far more than the tolerance."""
    hkv, rep, max_pages, sm_scale = _CELL_SHAPES[cell]
    page, d = 64, 128
    rng = np.random.default_rng(100 * len(cell) + b)
    sl = _cell_lengths(b, max_pages)
    n_pages = -(-sl // page)
    num_pages = int(n_pages.sum()) + 1
    k = rng.standard_normal((hkv, num_pages, page, d)) * 0.5
    v = rng.standard_normal((hkv, num_pages, page, d)) * 0.5
    k[:, 0], v[:, 0] = 4.0, _POISON
    bt = np.zeros((b, max_pages), np.int32)
    perm = rng.permutation(np.arange(1, num_pages))
    at = 0
    for r in range(b):
        bt[r, :n_pages[r]] = perm[at:at + n_pages[r]]
        at += n_pages[r]
        if sl[r] % page:
            last = bt[r, n_pages[r] - 1]
            k[:, last, sl[r] % page:] = 4.0
            v[:, last, sl[r] % page:] = _POISON
    q = jnp.asarray(rng.standard_normal((b, hkv * rep, d)) * 0.5)
    if kv_dtype == "int8":
        # float32 queries, as tests/test_kv_quant.py: the kernel's
        # arithmetic alone, at its tolerance
        q = q.astype(jnp.float32)
        k_pages, v_pages = (QuantizedPages(*quantize_kv_rows(jnp.asarray(
            x, jnp.float32))) for x in (k, v))
        tol = dict(rtol=2e-5, atol=2e-5)
    else:
        # what the cells run: bf16 queries on a bf16 pool; against the
        # reference's float32 result, half a bf16 ulp of rounding
        q = q.astype(jnp.bfloat16)
        k_pages, v_pages = (jnp.asarray(x, jnp.bfloat16) for x in (k, v))
        tol = dict(rtol=2.0 ** -8, atol=1e-5)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, sl,
                                     sm_scale=sm_scale), np.float32)
    ref = np.asarray(paged_attention_xla(q.astype(jnp.float32), k_pages,
                                         v_pages, bt, sl,
                                         sm_scale=sm_scale))
    assert np.isfinite(out).all()
    live = sl > 0
    np.testing.assert_allclose(out[live], ref[live], **tol)
    # an idle slot (seq_len 0) emits zeros, not the null page's mean
    assert not out[~live].any() and (~live).any()


class TestWrites:
    def test_decode_write_lands_in_right_page_slot(self):
        rng = np.random.default_rng(3)
        hkv, page, d = 2, 8, 16
        k_pages = jnp.zeros((hkv, 6, page, d), jnp.float32)
        v_pages = jnp.zeros_like(k_pages)
        bt = np.array([[2, 4], [5, 0]], np.int32)
        pos = np.array([9, 3], np.int32)          # page 1 slot 1 / page 0 slot 3
        k_new = jnp.asarray(rng.standard_normal((2, hkv, d)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((2, hkv, d)), jnp.float32)
        k_pages, v_pages = write_paged_kv(k_pages, v_pages, k_new, v_new,
                                          bt, pos)
        np.testing.assert_allclose(np.asarray(k_pages)[:, 4, 1],
                                   np.asarray(k_new)[0].reshape(hkv, d))
        np.testing.assert_allclose(np.asarray(k_pages)[:, 5, 3],
                                   np.asarray(k_new)[1].reshape(hkv, d))
        assert float(jnp.abs(k_pages).sum()) == pytest.approx(
            float(jnp.abs(k_new).sum()), rel=1e-6)

    def test_prompt_write_spans_pages(self):
        rng = np.random.default_rng(4)
        hkv, page, d, s = 2, 8, 16, 13
        k_pages = jnp.zeros((hkv, 6, page, d), jnp.float32)
        v_pages = jnp.zeros_like(k_pages)
        bt = np.array([[1, 3]], np.int32)
        k_new = jnp.asarray(rng.standard_normal((1, s, hkv, d)), jnp.float32)
        k_pages, v_pages = write_paged_prompt(k_pages, v_pages, k_new,
                                              jnp.zeros_like(k_new), bt)
        got = np.concatenate([np.asarray(k_pages)[:, 1],
                              np.asarray(k_pages)[:, 3]], axis=1)[:, :s]
        want = np.moveaxis(np.asarray(k_new)[0], 1, 0)   # (hkv, s, d)
        np.testing.assert_allclose(got, want)


class TestManager:
    def test_alloc_free_recycles_pages(self):
        c = PagedKVCache(num_layers=1, num_pages=8, page_size=8,
                         num_kv_heads=2, head_dim=16, max_batch=4,
                         max_seq_len=32, dtype=jnp.float32)
        assert c.free_page_count() == 8
        c.allocate(0, 20)                 # 3 pages
        c.allocate(1, 8)                  # 1 page
        assert c.free_page_count() == 4
        used = set(c.block_tables[0, :3]) | set(c.block_tables[1, :1])
        assert len(used) == 4             # distinct pages
        c.free_sequence(0)
        assert c.free_page_count() == 7
        c.allocate(2, 24)                 # reuses the freed pages
        assert c.free_page_count() == 4

    def test_pool_exhaustion_raises(self):
        c = PagedKVCache(num_layers=1, num_pages=2, page_size=8,
                         num_kv_heads=1, head_dim=16, max_batch=2,
                         max_seq_len=64, dtype=jnp.float32)
        c.allocate(0, 16)
        with pytest.raises(RuntimeError, match="exhausted"):
            c.allocate(1, 8)

    def test_end_to_end_prefill_decode_matches_ring_buffer(self):
        """The full serving flow — prefill a prompt, append decode tokens,
        attend — reproduces plain contiguous-cache attention."""
        from paddle_tpu.kernels.decode_attention import (cached_attention,
                                                         update_kv_cache)
        rng = np.random.default_rng(5)
        b, hkv, h, d, page = 2, 2, 4, 16, 8
        p_len, n_decode = 9, 3
        cache = PagedKVCache(num_layers=1, num_pages=12, page_size=page,
                             num_kv_heads=hkv, head_dim=d, max_batch=b,
                             max_seq_len=32, dtype=jnp.float32)
        seq_ids = np.arange(b)
        k_prompt = jnp.asarray(rng.standard_normal((b, p_len, hkv, d)) * 0.5,
                               jnp.float32)
        v_prompt = jnp.asarray(rng.standard_normal((b, p_len, hkv, d)) * 0.5,
                               jnp.float32)
        cache.allocate(0, p_len)
        cache.allocate(1, p_len)
        cache.prefill(0, seq_ids, k_prompt, v_prompt)

        # ring-buffer shadow
        kc = jnp.zeros((b, 32, hkv, d), jnp.float32)
        vc = jnp.zeros_like(kc)
        kc, vc = update_kv_cache(kc, vc, k_prompt, v_prompt, 0)

        cur = p_len
        for step in range(n_decode):
            k_new = jnp.asarray(rng.standard_normal((b, hkv, d)) * 0.5,
                                jnp.float32)
            v_new = jnp.asarray(rng.standard_normal((b, hkv, d)) * 0.5,
                                jnp.float32)
            q = jnp.asarray(rng.standard_normal((b, h, d)) * 0.5,
                            jnp.float32)
            for s in seq_ids:
                cache.allocate(int(s), 1)
            cache.append(0, seq_ids, k_new, v_new)
            out_paged = cache.attend(0, q, seq_ids)
            cache.advance(seq_ids)

            kc, vc = update_kv_cache(kc, vc, k_new[:, None], v_new[:, None],
                                     cur)
            cur += 1
            out_ring = cached_attention(q[:, None], kc, vc, cur)[:, 0]
            np.testing.assert_allclose(np.asarray(out_paged),
                                       np.asarray(out_ring),
                                       rtol=2e-5, atol=2e-5)

    def test_partial_allocation_failure_leaks_no_pages(self):
        """Exhaustion mid-allocate must leave popped pages reclaimable
        (code-review r05: evict-and-retry schedulers would leak)."""
        c = PagedKVCache(num_layers=1, num_pages=4, page_size=8,
                         num_kv_heads=1, head_dim=16, max_batch=2,
                         max_seq_len=64, dtype=jnp.float32)
        c.allocate(0, 16)                      # 2 pages
        with pytest.raises(RuntimeError, match="exhausted"):
            c.allocate(1, 32)                  # needs 4, only 2 free
        assert c.free_page_count() == 0        # 2 partially granted
        c.free_sequence(1)                     # must reclaim them
        assert c.free_page_count() == 2
        c.free_sequence(0)
        assert c.free_page_count() == 4


class TestGeneratePaged:
    """generate_paged (host-loop serving flow over the paged pool) must
    reproduce generate's greedy ring-buffer decode token-for-token."""

    def test_gpt_matches_ring_generate(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(51)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        prompt = paddle.to_tensor(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 7)).astype(np.int32))
        ring = model.generate(prompt, max_new_tokens=6,
                              do_sample=False).numpy()
        paged = model.generate_paged(prompt, max_new_tokens=6,
                                     page_size=8).numpy()
        np.testing.assert_array_equal(ring, paged)

    def test_llama_gqa_matches_ring_generate(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(52)
        cfg = LlamaConfig.tiny()          # 4 q heads, 2 kv heads
        model = LlamaForCausalLM(cfg)
        prompt = paddle.to_tensor(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (2, 7)).astype(np.int32))
        ring = model.generate(prompt, max_new_tokens=5,
                              do_sample=False).numpy()
        paged = model.generate_paged(prompt, max_new_tokens=5,
                                     page_size=8).numpy()
        np.testing.assert_array_equal(ring, paged)

    def test_eos_padding(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(53)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        prompt = paddle.to_tensor(np.random.default_rng(2).integers(
            0, cfg.vocab_size, (2, 5)).astype(np.int32))
        free = model.generate_paged(prompt, max_new_tokens=4,
                                    page_size=8).numpy()
        eos = int(free[0, 5])             # first generated token of row 0
        out = model.generate_paged(prompt, max_new_tokens=4, page_size=8,
                                   eos_token_id=eos,
                                   pad_token_id=0).numpy()
        row = out[0, 5:]
        hits = np.where(row == eos)[0]
        assert hits.size
        assert np.all((row[hits[0] + 1:] == 0) | (row[hits[0] + 1:] == eos))


class TestBlockMultiheadAttention:
    """The reference-named wrapper (incubate.nn.functional.
    block_multihead_attention) over the paged machinery."""

    def test_decode_phase_matches_paged_attention(self):
        import paddle_tpu.incubate.nn.functional as FF

        rng = np.random.default_rng(9)
        b, h, d, page = 2, 2, 16, 8
        k_pages, v_pages = make_pool(rng, h, 8, page, d)
        bt = np.array([[1, 3], [5, 0]], np.int32)
        dec_lens = np.array([9, 4], np.int32)
        qkv = jnp.asarray(rng.standard_normal((b, 1, 3, h, d)) * 0.5,
                          jnp.float32)

        out, k2, v2 = FF.block_multihead_attention(
            qkv, k_pages, v_pages,
            seq_lens_encoder=np.zeros(b, np.int32),
            seq_lens_decoder=dec_lens,
            seq_lens_this_time=np.ones(b, np.int32),
            block_tables=bt)
        # reference: write then attend with the standalone pieces
        kw, vw = write_paged_kv(k_pages, v_pages,
                                jnp.asarray(qkv[:, 0, 1]),
                                jnp.asarray(qkv[:, 0, 2]), bt, dec_lens)
        ref = paged_attention_xla(jnp.asarray(qkv[:, 0, 0]), kw, vw, bt,
                                  dec_lens + 1)
        np.testing.assert_allclose(
            np.asarray(out.numpy()).reshape(b, h * d),
            np.asarray(ref).reshape(b, h * d), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(k2.numpy()), np.asarray(kw))

    def test_prefill_phase_writes_pages(self):
        import paddle_tpu.incubate.nn.functional as FF

        rng = np.random.default_rng(10)
        b, s, h, d, page = 1, 13, 2, 16, 8
        k_pages = jnp.zeros((h, 6, page, d), jnp.float32)
        v_pages = jnp.zeros_like(k_pages)
        bt = np.array([[2, 4]], np.int32)
        qkv = jnp.asarray(rng.standard_normal((b, s, 3, h, d)) * 0.5,
                          jnp.float32)
        out, k2, v2 = FF.block_multihead_attention(
            qkv, k_pages, v_pages,
            seq_lens_encoder=np.full(b, s, np.int32),
            seq_lens_decoder=np.zeros(b, np.int32),
            seq_lens_this_time=np.full(b, s, np.int32),
            block_tables=bt)
        assert out.shape == [b, s, h * d]
        got = np.concatenate([np.asarray(k2.numpy())[:, 2],
                              np.asarray(k2.numpy())[:, 4]], axis=1)[:, :s]
        want = np.moveaxis(np.asarray(qkv[0, :, 1]), 1, 0)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_unsupported_options_raise(self):
        import paddle_tpu.incubate.nn.functional as FF

        with pytest.raises(NotImplementedError, match="rope"):
            FF.block_multihead_attention(
                jnp.zeros((1, 1, 3, 2, 16), jnp.float32),
                jnp.zeros((2, 4, 8, 16), jnp.float32),
                jnp.zeros((2, 4, 8, 16), jnp.float32),
                np.zeros(1, np.int32), np.ones(1, np.int32),
                np.ones(1, np.int32), np.zeros((1, 2), np.int32),
                rotary_embs=object())

    def test_reference_default_kwargs_accepted(self):
        import paddle_tpu.incubate.nn.functional as FF

        rng = np.random.default_rng(11)
        b, h, d, page = 1, 2, 16, 8
        k_pages, v_pages = make_pool(rng, h, 6, page, d)
        qkv = jnp.asarray(rng.standard_normal((b, 1, 3, h, d)), jnp.float32)
        out, _, _ = FF.block_multihead_attention(
            qkv, k_pages, v_pages, np.zeros(b, np.int32),
            np.array([5], np.int32), np.ones(b, np.int32),
            np.array([[1, 2]], np.int32),
            max_seq_len=-1, use_neox_style=False, quant_round_type=1,
            quant_max_bound=127.0, quant_min_bound=-127.0,
            compute_dtype="default")
        assert out.shape == [b, 1, h * d]

    def test_mixed_or_inactive_batches_refused(self):
        import paddle_tpu.incubate.nn.functional as FF

        rng = np.random.default_rng(12)
        k_pages, v_pages = make_pool(rng, 2, 6, 8, 16)
        qkv = jnp.asarray(rng.standard_normal((2, 1, 3, 2, 16)), jnp.float32)
        with pytest.raises(NotImplementedError, match="uniform"):
            FF.block_multihead_attention(
                qkv, k_pages, v_pages, np.zeros(2, np.int32),
                np.array([5, 0], np.int32),
                np.array([1, 0], np.int32),       # inactive row
                np.array([[1, 2], [3, 4]], np.int32))


# ------------------------------------------------------- a window (PR 34)
def _dense_window(q_rows, q_pos, k, v, window):
    """Plain masked attention of query rows at absolute positions
    ``q_pos`` over contiguous k/v (hkv, t, d): ``j <= i`` and, under a
    window, ``i - j < window``."""
    h, d = q_rows.shape[1:]
    rep = h // k.shape[0]
    out = np.zeros(q_rows.shape, np.float32)
    j = np.arange(k.shape[1])
    for r, i in enumerate(q_pos):
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        for head in range(h):
            s = (q_rows[r, head] @ k[head // rep].T) / np.sqrt(d)
            s = np.where(seen, s, -np.inf)
            p = np.exp(s - s.max())
            out[r, head] = (p / p.sum()) @ v[head // rep]
    return out


def _contiguous(pages, bt_row, t):
    arr = np.asarray(pages, np.float32)
    return np.concatenate([arr[:, p] for p in bt_row], axis=1)[:, :t]


class TestWindow:
    """``window=`` in the decode and the chunk kernels (interpret mode)
    and their XLA twins against the dense mask, at lengths under, at and
    over the window and across a page edge; pages before the window are
    never read, so their table entries may point anywhere."""

    PAGE, WINDOW = 8, 20

    def _pool(self, rng, n_pages=24):
        return make_pool(rng, hkv=2, num_pages=n_pages, page=self.PAGE, d=32)

    # under the window, at it, one over, at a page edge of the window's
    # start (28 - 20 = 8), past it, and far past
    @pytest.mark.parametrize("lens", [(5, 19, 20, 21), (28, 29, 44, 64)])
    def test_decode_matches_dense_mask(self, lens):
        rng = np.random.default_rng(3)
        kp, vp = self._pool(rng, n_pages=40)
        b, h = len(lens), 4
        bt = rng.permutation(np.arange(1, 40))[:b * 8].reshape(b, 8)
        bt = bt.astype(np.int32)
        sl = np.asarray(lens, np.int32)
        q = jnp.asarray(rng.standard_normal((b, h, 32)), jnp.float32)
        want = np.stack([
            _dense_window(np.asarray(q)[r:r + 1], [sl[r] - 1],
                          _contiguous(kp, bt[r], sl[r]),
                          _contiguous(vp, bt[r], sl[r]), self.WINDOW)[0]
            for r in range(b)])
        # what a window pool does to the pages before the window: the
        # table slot goes to the null page, whose content is not the row's
        freed = bt.copy()
        for r in range(b):
            freed[r, :max(0, sl[r] - self.WINDOW) // self.PAGE] = 0
        for attend in (paged_attention, paged_attention_xla):
            for table in (bt, freed):
                got = attend(q, kp, vp, jnp.asarray(table), jnp.asarray(sl),
                             window=self.WINDOW)
                np.testing.assert_allclose(np.asarray(got), want,
                                           rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("start", [0, 8, 13, 24, 40])
    def test_chunk_matches_dense_mask(self, start):
        from paddle_tpu.kernels.paged_attention import (
            paged_chunk_attention, paged_chunk_attention_xla)
        rng = np.random.default_rng(4)
        kp, vp = self._pool(rng)
        s, h = 16, 4
        bt = rng.permutation(np.arange(1, 24))[:8].astype(np.int32)[None]
        q = jnp.asarray(rng.standard_normal((1, s, h, 32)), jnp.float32)
        t = start + s
        want = _dense_window(np.asarray(q)[0], start + np.arange(s),
                             _contiguous(kp, bt[0], t),
                             _contiguous(vp, bt[0], t), self.WINDOW)
        freed = bt.copy()
        freed[0, :max(0, start + 1 - self.WINDOW) // self.PAGE] = 0
        for attend in (paged_chunk_attention, paged_chunk_attention_xla):
            for table in (bt, freed):
                got = attend(q, kp, vp, jnp.asarray(table),
                             jnp.asarray([start], jnp.int32),
                             window=self.WINDOW)
                np.testing.assert_allclose(np.asarray(got)[0], want,
                                           rtol=2e-5, atol=2e-5)

    def test_the_window_bites(self):
        """Past the window the windowed read differs from the full one
        (else the cases above would show nothing)."""
        rng = np.random.default_rng(5)
        kp, vp = self._pool(rng)
        bt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
        q = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
        sl = jnp.asarray([44], jnp.int32)
        full = paged_attention(q, kp, vp, bt, sl)
        cut = paged_attention(q, kp, vp, bt, sl, window=self.WINDOW)
        assert float(jnp.max(jnp.abs(full - cut))) > 1e-3

    @pytest.mark.parametrize("kernel", ["decode", "decode_xla", "chunk",
                                        "chunk_xla"])
    def test_no_window_is_todays_output(self, kernel):
        """``window=None`` is the call without the keyword, to the bit,
        and so is a window no row reaches."""
        from paddle_tpu.kernels.paged_attention import (
            paged_chunk_attention, paged_chunk_attention_xla)
        rng = np.random.default_rng(6)
        kp, vp = self._pool(rng)
        bt = jnp.asarray(rng.permutation(np.arange(1, 17)).reshape(2, 8)
                         .astype(np.int32))
        if kernel.startswith("decode"):
            fn = paged_attention if kernel == "decode" else paged_attention_xla
            args = (jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32),
                    kp, vp, bt, jnp.asarray([37, 64], jnp.int32))
        else:
            fn = (paged_chunk_attention if kernel == "chunk"
                  else paged_chunk_attention_xla)
            args = (jnp.asarray(rng.standard_normal((1, 16, 4, 32)),
                                jnp.float32),
                    kp, vp, bt[:1], jnp.asarray([21], jnp.int32))
        base = np.asarray(fn(*args))
        np.testing.assert_array_equal(np.asarray(fn(*args, window=None)),
                                      base)
        np.testing.assert_array_equal(np.asarray(fn(*args, window=4096)),
                                      base)

    def test_bad_window_refused(self):
        rng = np.random.default_rng(7)
        kp, vp = self._pool(rng)
        q = jnp.zeros((1, 4, 32), jnp.float32)
        with pytest.raises(ValueError, match="window"):
            paged_attention(q, kp, vp, jnp.zeros((1, 8), jnp.int32),
                            jnp.asarray([3], jnp.int32), window=0)


# ------------------------------- the chunk kernel's tiling (PR 35)
def _dense_chunk(q, k, v, start, window=None, block=1):
    """Plain masked attention of the chunk ``q`` (s, h, d) at positions
    ``start ..`` over contiguous k/v (hkv, t, d): ``j <= i | (block -
    1)`` and, under a window, ``i - j < window``."""
    s, h, d = q.shape
    hkv = k.shape[0]
    i = start + np.arange(s)[:, None]
    j = np.arange(k.shape[1])[None, :]
    seen = j <= (i | (block - 1))
    if window is not None:
        seen &= i - j < window
    qh = np.asarray(q, np.float32).reshape(s, hkv, h // hkv, d)
    sc = np.einsum("skrd,ktd->krst", qh, k) / np.sqrt(d)
    sc = np.where(seen[None, None], sc, -np.inf)
    p = np.exp(sc - sc.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("krst,ktd->skrd", p, v).reshape(s, h, d)


class TestChunkTiling:
    """``paged_chunk_attention`` cut into query tiles by tokens and key
    blocks of several pages (``chunk_tiling``): against the dense mask
    where the tiling can go wrong, and the tiling's own arithmetic with
    no kernel."""

    PAGE, WIDTH, D = 16, 70, 32

    def _case(self, s, h, hkv, seed=11, dtype=jnp.float32, width=None):
        rng = np.random.default_rng(seed)
        width = width or self.WIDTH
        kp, vp = make_pool(rng, hkv=hkv, num_pages=width + 1,
                           page=self.PAGE, d=self.D, dtype=dtype)
        bt = rng.permutation(np.arange(1, width + 1)).astype(np.int32)[None]
        q = jnp.asarray(rng.standard_normal((1, s, h, self.D)) * 0.5, dtype)
        return q, kp, vp, bt

    def _check(self, q, kp, vp, bt, start, *, pools=None, tol=2e-5, **kw):
        """The kernel over ``bt`` (and, windowed, over the table with the
        slots before the window given back) against the dense mask; rows
        past the table (a padded final chunk) are the caller's to drop."""
        from paddle_tpu.kernels.paged_attention import paged_chunk_attention
        s = q.shape[1]
        end = bt.shape[1] * self.PAGE
        t, real = min(start + s, end), min(s, end - start)
        want = _dense_chunk(np.asarray(q, np.float32)[0],
                            _contiguous(kp, bt[0], t),
                            _contiguous(vp, bt[0], t), start, **kw)
        tables = [bt]
        if kw.get("window"):
            freed = bt.copy()
            freed[0, :max(0, start + 1 - kw["window"]) // self.PAGE] = 0
            tables.append(freed)
        for table in tables:
            got = paged_chunk_attention(
                q, *(pools or (kp, vp)), jnp.asarray(table),
                jnp.asarray([start], jnp.int32), **kw)
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[0, :real], want[:real],
                rtol=tol, atol=tol)

    def test_the_cases_cut_as_they_say(self):
        """600 tokens of 8 query heads a KV head are three tiles, the
        70-page table five key blocks of 16 pages, the last one short;
        64 tokens of one head a KV head are one tile and one block."""
        from paddle_tpu.kernels.paged_attention import chunk_tiling
        tl = chunk_tiling(600, 8, self.PAGE, self.WIDTH)
        assert (tl.tile, tl.n_tiles, tl.ppb, tl.n_blk) == (200, 3, 16, 5)
        tl = chunk_tiling(600, 8, self.PAGE, self.WIDTH, window=50)
        assert tl.n_blk == 2          # 249 positions lie on two blocks
        tl = chunk_tiling(64, 1, self.PAGE, 10)
        assert (tl.tile, tl.n_tiles, tl.n_blk) == (64, 1, 1)

    # cursor 0; inside a page; the last visible block partly dead; a
    # padded final chunk that points past the table
    @pytest.mark.parametrize("start", [0, 37, 300, 700])
    def test_causal_tiles_and_blocks(self, start):
        self._check(*self._case(600, 8, 1), start)

    # a window smaller than a tile (200 tokens), between a tile and the
    # chunk, and larger than everything written; the second table of
    # each has the slots before the window on the null page
    @pytest.mark.parametrize("window,start", [(50, 300), (320, 437),
                                              (4096, 100)])
    def test_windowed_tiles(self, window, start):
        self._check(*self._case(600, 8, 1), start, window=window)

    def test_block_causal_tiles(self):
        """Two KV heads here (one in the other cases): the tiles of the
        second head read its half of the pool."""
        self._check(*self._case(600, 16, 2), 296, block=4)

    def test_one_query_head_a_kv_head(self):
        """``rep`` 1: a row is a token; four KV heads, the whole chunk
        of each under one tile, a grid step each."""
        self._check(*self._case(64, 4, 4, width=10), 21)

    def test_quantized_pool(self):
        q, kp, vp, bt = self._case(600, 8, 1)
        kq, vq = (QuantizedPages(*quantize_kv_rows(x)) for x in (kp, vp))
        deq = [np.asarray(x.q, np.float32) * np.asarray(x.scale)
               for x in (kq, vq)]
        self._check(q, *deq, bt, 300, pools=(kq, vq))

    def test_bf16_operands_as_stored(self):
        """A bf16 query on a bf16 pool goes to the MXU as stored: the
        products are exact in float32, the output rounds to bf16."""
        self._check(*self._case(600, 8, 1, dtype=jnp.bfloat16), 300,
                    tol=1e-2)

    @pytest.mark.parametrize("window,block", [(None, 1), (50, 1), (320, 1),
                                              (2048, 1), (None, 4)])
    @pytest.mark.parametrize("s,rep,page,width", [
        (600, 8, 16, 70), (64, 1, 16, 10), (1024, 8, 64, 384),
        (256, 1, 64, 16), (256, 8, 64, 20), (100, 3, 8, 37)])
    def test_tiling_covers_every_visible_pair(self, s, rep, page, width,
                                              window, block):
        """No kernel: for cursors all over the table, the live (tile,
        key block) pairs of ``_chunk_tile_keys`` cover every query-key
        pair a brute-force mask makes visible, ``chunk_tile_pairs`` is
        the count of what they cover, and a tile's blocks fit the
        grid's key-block axis."""
        from paddle_tpu.kernels.paged_attention import (
            _chunk_tile_keys, chunk_tile_pairs, chunk_tiling)
        tl = chunk_tiling(s, rep, page, width, window=window,
                          block=block)
        assert tl.n_tiles * tl.tile >= s
        end = width * page
        n_blocks = -(-width // tl.ppb)
        starts = sorted({0, block * 9, page * 3, end // 2 // block * block,
                         max(0, end - s) // block * block,
                         (end - s // 2) // block * block})
        for start in starts:
            lo, hi = _chunk_tile_keys(tl, np.asarray([start], np.int64))
            lo_b, hi_b = lo[0] // tl.keys, hi[0] // tl.keys + 1
            assert (hi_b > lo_b).all() and (hi_b <= n_blocks).all()
            covered = np.zeros((tl.n_tiles * tl.tile, n_blocks * tl.keys),
                               bool)
            for t in range(tl.n_tiles):
                covered[t * tl.tile:(t + 1) * tl.tile,
                        lo_b[t] * tl.keys:hi_b[t] * tl.keys] = True
            i = start + np.arange(s)[:, None]
            j = np.arange(end)[None, :]
            seen = (j <= (i | (block - 1))) & (i < end)
            if window is not None:
                seen &= i - j < window
            assert not (seen & ~covered[:s, :end]).any(), start
            assert chunk_tile_pairs(tl, start) == covered.sum()
            assert (hi_b - lo_b <= tl.n_blk).all()
