"""The page-write kernels against the scatter they replace on the TPU
(paddle_tpu/kernels/paged_attention.py, "pool management").

``write_paged_kv_pallas`` / ``write_paged_prompt_at_pallas`` run here in
interpret mode; the ``*_xla`` scatters are the reference. The contract is
the scatter's pool BIT FOR BIT on every page a live sequence owns. The
engine's null page 0 is compared only where its rows come one after
another (the kernel accumulates a run of steps on one block; rows that
return to page 0 after a live row are left to the hardware's pipeline,
as duplicate scatter indices are left to XLA). That these kernels
compile for the chip, and that no serving program copies a pool, is
``tests/test_chip_compile.py``'s to say.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.paged_attention import (PagedKVCache, QuantizedPages,
                                                write_paged_kv,
                                                write_paged_kv_pallas,
                                                write_paged_kv_xla,
                                                write_paged_prompt_at,
                                                write_paged_prompt_at_pallas,
                                                write_paged_prompt_at_xla)

PAGE = 8
# (Hkv, D, dtype): MHA and GQA pool widths of gpt3-345m and mistral-7b
# (the head ratio never reaches a pool write: only Hkv heads are stored)
WIDTHS = [pytest.param(16, 64, jnp.bfloat16, id="mha16-d64-bf16"),
          pytest.param(8, 128, jnp.bfloat16, id="gqa8-d128-bf16"),
          pytest.param(2, 64, jnp.float32, id="kv2-d64-f32")]


def _pools(rng, hkv, d, dtype, num_pages=14):
    shape = (hkv, num_pages, PAGE, d)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _new(rng, shape, dtype):
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _same_bits(got, ref, pages=slice(None)):
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32))[:, pages],
            np.asarray(r.astype(jnp.float32))[:, pages])


class TestDecodeWrite:
    @pytest.mark.parametrize("hkv,d,dtype", WIDTHS)
    def test_matches_scatter(self, hkv, d, dtype):
        rng = np.random.default_rng(0)
        kp, vp = _pools(rng, hkv, d, dtype)
        bt = np.array([[3, 4, 5], [6, 7, 8], [9, 1, 2]], np.int32)
        pos = np.array([17, 0, 23], np.int32)    # mid page, first, last row
        kn, vn = _new(rng, (3, hkv, d), dtype)
        _same_bits(write_paged_kv_pallas(kp, vp, kn, vn, bt, pos),
                   write_paged_kv_xla(kp, vp, kn, vn, bt, pos))

    def test_inactive_rows_share_the_null_page(self):
        """Inactive slots (all-zero block tables) all land on page 0, in
        a run at the batch's tail and between live rows; every live page
        still holds the scatter's bits, and a run of inactive rows at
        different offsets leaves each of its rows on page 0."""
        rng = np.random.default_rng(1)
        kp, vp = _pools(rng, 4, 64, jnp.bfloat16)
        bt = np.zeros((6, 3), np.int32)
        bt[0], bt[2] = [3, 4, 5], [6, 7, 8]
        pos = np.array([9, 0, 20, 1, 2, 3], np.int32)
        kn, vn = _new(rng, (6, 4, 64), jnp.bfloat16)
        got = write_paged_kv_pallas(kp, vp, kn, vn, bt, pos)
        _same_bits(got, write_paged_kv_xla(kp, vp, kn, vn, bt, pos),
                   pages=slice(1, None))
        # rows 3, 4, 5 are consecutive grid steps on page 0: all three kept
        for r in (3, 4, 5):
            np.testing.assert_array_equal(
                np.asarray(got[0][:, 0, pos[r]].astype(jnp.float32)),
                np.asarray(kn[r].astype(jnp.float32)))

    def test_position_past_the_table_is_dropped(self):
        rng = np.random.default_rng(2)
        kp, vp = _pools(rng, 2, 64, jnp.float32)
        bt = np.array([[3, 4], [5, 6]], np.int32)
        pos = np.array([2 * PAGE, 5], np.int32)      # row 0: one past the end
        kn, vn = _new(rng, (2, 2, 64), jnp.float32)
        _same_bits(write_paged_kv_pallas(kp, vp, kn, vn, bt, pos),
                   write_paged_kv_xla(kp, vp, kn, vn, bt, pos))


class TestPromptWrite:
    @pytest.mark.parametrize("hkv,d,dtype", WIDTHS)
    @pytest.mark.parametrize("s,start", [
        (24, 0),        # whole pages: the spare last step has nothing to do
        (16, 8),        # a page-aligned chunk behind a written prefix
        (11, 5),        # unaligned start (the speculative verify chunk)
        (1, 7),         # one token, last row of a page
        (20, 3),        # unaligned at both ends
    ])
    def test_matches_scatter(self, hkv, d, dtype, s, start):
        rng = np.random.default_rng(3)
        kp, vp = _pools(rng, hkv, d, dtype)
        bt = np.array([[3, 9, 5, 2]], np.int32)
        st = np.array([start], np.int32)
        kn, vn = _new(rng, (1, s, hkv, d), dtype)
        _same_bits(write_paged_prompt_at_pallas(kp, vp, kn, vn, bt, st),
                   write_paged_prompt_at_xla(kp, vp, kn, vn, bt, st))

    def test_batched_prompts_each_at_its_own_start(self):
        """B > 1, every row on its own pages and its own alignment; one
        row lies wholly past its table (nothing of it may land) and comes
        FIRST, so the grid opens on steps that have nothing to write."""
        rng = np.random.default_rng(4)
        kp, vp = _pools(rng, 4, 64, jnp.bfloat16)
        bt = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 12]],
                      np.int32)
        st = np.array([3 * PAGE, 0, 5, 13], np.int32)
        kn, vn = _new(rng, (4, 10, 4, 64), jnp.bfloat16)
        _same_bits(write_paged_prompt_at_pallas(kp, vp, kn, vn, bt, st),
                   write_paged_prompt_at_xla(kp, vp, kn, vn, bt, st))

    @pytest.mark.parametrize("reserve_null_page", [True, False])
    def test_padded_final_chunk_runs_past_the_block_table(
            self, reserve_null_page):
        """The last chunk of a prompt pads to the fixed chunk length and
        its pad runs past the table's width: those positions are dropped,
        never parked on a page that is written for real — not on page 0
        either, which without ``reserve_null_page`` is a live page (here:
        the other sequence's first)."""
        rng = np.random.default_rng(5)
        cache = PagedKVCache(num_layers=1, num_pages=10, page_size=PAGE,
                             num_kv_heads=2, head_dim=64, max_batch=2,
                             max_seq_len=4 * PAGE, dtype=jnp.bfloat16,
                             reserve_null_page=reserve_null_page)
        cache.allocate(0, 2 * PAGE)          # owns page 0 unless reserved
        cache.allocate(1, 4 * PAGE)
        assert (0 in cache.block_tables[0, :2]) != reserve_null_page
        kp, vp = _pools(rng, 2, 64, jnp.bfloat16, num_pages=10)
        bt = cache.block_tables[1:2]
        chunk = 2 * PAGE
        for start in (3 * PAGE, 3 * PAGE - 3):       # aligned; verify-like
            st = np.array([start], np.int32)
            kn, vn = _new(rng, (1, chunk, 2, 64), jnp.bfloat16)
            _same_bits(write_paged_prompt_at_pallas(kp, vp, kn, vn, bt, st),
                       write_paged_prompt_at_xla(kp, vp, kn, vn, bt, st))

    def test_out_of_pool_page_is_dropped(self):
        """A block-table entry outside the pool is the scatter's "drop"
        and must never become a DMA address."""
        rng = np.random.default_rng(6)
        kp, vp = _pools(rng, 2, 64, jnp.float32)
        bt = np.array([[3, 99, 5]], np.int32)
        st = np.array([4], np.int32)
        kn, vn = _new(rng, (1, 18, 2, 64), jnp.float32)
        _same_bits(write_paged_prompt_at_pallas(kp, vp, kn, vn, bt, st),
                   write_paged_prompt_at_xla(kp, vp, kn, vn, bt, st))


class TestDispatch:
    """The kernel is chosen from what the code can see — backend,
    ``FLAGS_use_pallas``, plain array or ``QuantizedPages`` — no flag of
    its own."""

    @pytest.fixture
    def calls(self, monkeypatch):
        seen = []
        for name in ("write_paged_kv_pallas", "write_paged_kv_xla",
                     "write_paged_prompt_at_pallas",
                     "write_paged_prompt_at_xla"):
            monkeypatch.setattr(
                pa, name,
                lambda kp, vp, *a, _n=name: (seen.append(_n), (kp, vp))[1])
        return seen

    @staticmethod
    def _write_both(pool):
        bt = np.array([[1, 2]], np.int32)
        one = np.zeros((1,), np.int32)
        write_paged_kv(pool, pool, jnp.zeros((1, 2, 64)),
                       jnp.zeros((1, 2, 64)), bt, one)
        write_paged_prompt_at(pool, pool, jnp.zeros((1, 4, 2, 64)),
                              jnp.zeros((1, 4, 2, 64)), bt, one)

    @pytest.mark.parametrize("on_tpu,use_pallas,quantized,suffix", [
        (True, True, False, "_pallas"),
        (False, True, False, "_xla"),       # CPU tier-1
        (True, False, False, "_xla"),       # use_pallas off
        (True, True, True, "_xla"),         # int8 pools keep the scatter
    ])
    def test_selection(self, monkeypatch, calls, on_tpu, use_pallas,
                       quantized, suffix):
        from paddle_tpu import flags
        monkeypatch.setattr(flags, "is_tpu_backend", lambda: on_tpu)
        was = flags.get_flag("use_pallas")
        flags.set_flags({"use_pallas": use_pallas})
        try:
            pool = jnp.zeros((2, 4, PAGE, 64), jnp.bfloat16)
            if quantized:
                pool = QuantizedPages(pool.astype(jnp.int8),
                                      jnp.zeros((2, 4, PAGE, 1)))
            self._write_both(pool)
        finally:
            flags.set_flags({"use_pallas": was})
        assert calls == ["write_paged_kv" + suffix,
                         "write_paged_prompt_at" + suffix]


class TestLanePaddedPool:
    """A pool allocated wider than the head (``padded_head_dim``: on the
    TPU a head under 128 lanes pads up to them, so the pool's default
    layout is the row-major one the kernels read and no program copies
    it). The generic attention path pads q/k/v to the pool's width and
    slices back: same outputs, same bits in the head's own lanes, zeros
    beside them."""

    @staticmethod
    def _run(width, phase):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.kernels.paged_attention import (PagedChunkState,
                                                        PagedDecodeState)
        rng = np.random.default_rng(11)
        hkv, h, d, b = 2, 4, 64, 2
        bt = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
        seeded = rng.standard_normal((2, hkv, 7, PAGE, d)).astype(np.float32)
        kp, vp = (jnp.zeros((hkv, 7, PAGE, width), jnp.float32)
                  .at[..., :d].set(x) for x in seeded)
        t = paddle.to_tensor
        s, state_cls, sl = {
            "decode": (1, PagedDecodeState, [9, 14]),
            "chunk": (6, PagedChunkState, [5]),
            "prefill": (11, PagedDecodeState, [0, 0]),
        }[phase]
        if phase == "chunk":
            b, bt = 1, bt[:1]
        q, k, v = (t(rng.standard_normal((b, s, heads, d))
                     .astype(np.float32)) for heads in (h, hkv, hkv))
        out, state = F.paged_scaled_dot_product_attention(
            q, k, v, state_cls(kp, vp, bt, np.array(sl, np.int32)))
        return (np.asarray(out.numpy()), np.asarray(state.k_pages),
                np.asarray(state.v_pages))

    @pytest.mark.parametrize("phase", ["decode", "chunk", "prefill"])
    def test_same_attention_and_pool_bits_as_the_unpadded_pool(self, phase):
        out, kp, vp = self._run(64, phase)
        out_p, kp_p, vp_p = self._run(128, phase)
        assert out_p.shape == out.shape
        np.testing.assert_allclose(out_p, out, rtol=1e-6, atol=1e-6)
        for padded, plain in ((kp_p, kp), (vp_p, vp)):
            np.testing.assert_array_equal(padded[..., :64], plain)
            assert not padded[..., 64:].any()

    def test_width_is_chosen_from_backend_model_and_pool_dtype(
            self, monkeypatch):
        from paddle_tpu import flags
        from paddle_tpu.generation.cache_manager import pool_head_dim

        class Generic:                  # forward_with_cache only
            pass

        class Fused:                    # addresses the pool by head width
            def block_decode_spec(self):
                return None

        assert pa.padded_head_dim(64) == 64             # the CPU: as is
        assert pool_head_dim(Generic(), 64, "native") == 64
        monkeypatch.setattr(flags, "is_tpu_backend", lambda: True)
        assert [pa.padded_head_dim(d) for d in (64, 80, 128, 256)] \
            == [128, 128, 128, 256]
        assert pool_head_dim(Generic(), 64, "native") == 128
        assert pool_head_dim(Generic(), 64, "int8") == 64
        assert pool_head_dim(Fused(), 64, "native") == 64
