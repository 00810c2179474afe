"""Fused transformer-block decode (kernels/fused_block_decode.py), the
decode program cache (generation/program_cache.py), and the prefix-cache
pin/evict contract.

Invariants:
  - the fused block step (jnp composition AND the Pallas kernel in
    interpret mode) is numerically the unfused op chain the models run
    (F.rms_norm -> linears -> fused rope -> paged sdpa -> swiglu), at
    fp32 and bf16 tolerances;
  - the decode program cache hands the SAME compiled object to every
    engine over a same-signature model and never retraces at a fixed
    batch bucket (the trace-count probe stays flat across step() calls);
  - PrefixCache.evict refuses pages pinned by in-flight adoptions and
    reports the number of pages actually freed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.generation.program_cache import decode_program_cache
from paddle_tpu.generation.serving import PrefixCache, ServingEngine
from paddle_tpu.kernels.fused_block_decode import (BlockDecodeWeights,
                                                   fused_block_decode_pallas,
                                                   fused_block_decode_ref)
from paddle_tpu.kernels.paged_attention import PagedKVCache
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _mk_case(rng, b=3, hidden=64, nh=4, nkv=2, inter=128, page=8,
             num_pages=16, mp=4, dtype=jnp.float32,
             seq_lens=(5, 8, 11)):
    d = hidden // nh
    mk = lambda *s: jnp.asarray(
        (rng.standard_normal(s) * 0.1).astype(np.float32), dtype)
    w = BlockDecodeWeights(
        ln1=jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden).astype(
            np.float32), dtype),
        wq=mk(hidden, nh * d), wk=mk(hidden, nkv * d), wv=mk(hidden, nkv * d),
        wo=mk(nh * d, hidden),
        ln2=jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden).astype(
            np.float32), dtype),
        wg=mk(hidden, inter), wu=mk(hidden, inter), wd=mk(inter, hidden))
    x = mk(b, hidden)
    kp = mk(nkv, num_pages, page, d)
    vp = mk(nkv, num_pages, page, d)
    # shuffled non-trivial block tables, page 0 reserved as null
    perm = rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp) + 1
    bt = jnp.asarray(perm, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    return x, w, kp, vp, bt, sl, dict(num_heads=nh, num_kv_heads=nkv,
                                      rope_theta=10000.0, epsilon=1e-5)


def _unfused_chain(x, w, kp, vp, bt, sl, num_heads, num_kv_heads,
                   rope_theta, epsilon):
    """The op-by-op chain LlamaDecoderLayer actually runs over the paged
    cache — composed from the SAME public surface (F.rms_norm, matmul,
    fused rope, paged sdpa, swiglu), not a private re-derivation."""
    import paddle_tpu.incubate.nn.functional as FF
    import paddle_tpu.nn.functional as F
    from paddle_tpu import ops
    from paddle_tpu.kernels.paged_attention import PagedDecodeState

    b, hidden = x.shape
    d = hidden // num_heads
    t = lambda a: paddle.to_tensor(a)
    xt = t(x)[:, None]                                   # (B, 1, H)
    h = F.rms_norm(xt, t(w.ln1), epsilon)
    q = ops.matmul(h, t(w.wq)).reshape([b, 1, num_heads, d])
    k = ops.matmul(h, t(w.wk)).reshape([b, 1, num_kv_heads, d])
    v = ops.matmul(h, t(w.wv)).reshape([b, 1, num_kv_heads, d])
    pos = t(np.asarray(sl)[:, None].astype(np.int32))
    q, k, _ = FF.fused_rotary_position_embedding(
        q, k, None, position_ids=pos, rotary_emb_base=rope_theta)
    state = PagedDecodeState(kp, vp, bt, sl)
    out, state = F.paged_scaled_dot_product_attention(q, k, v, state)
    attn = out.reshape([b, 1, num_heads * d])
    x2 = xt + ops.matmul(attn, t(w.wo))
    h2 = F.rms_norm(x2, t(w.ln2), epsilon)
    f = F.swiglu(ops.matmul(h2, t(w.wg)), ops.matmul(h2, t(w.wu)))
    y = x2 + ops.matmul(f, t(w.wd))
    return (np.asarray(y.numpy())[:, 0], np.asarray(state.k_pages),
            np.asarray(state.v_pages))


class TestFusedBlockParity:
    def test_ref_matches_unfused_chain_fp32(self):
        rng = np.random.default_rng(0)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng)
        out, kp2, vp2 = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        ref, kpr, vpr = _unfused_chain(x, w, kp, vp, bt, sl, **kw)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(kp2), kpr, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(vp2), vpr, rtol=1e-6,
                                   atol=1e-6)

    def test_ref_matches_unfused_chain_bf16(self):
        rng = np.random.default_rng(1)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng, dtype=jnp.bfloat16)
        out, _, _ = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        ref, _, _ = _unfused_chain(x, w, kp, vp, bt, sl, **kw)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2)

    @pytest.mark.pallas_interpret
    def test_kernel_matches_ref_fp32(self):
        rng = np.random.default_rng(2)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng)
        o_ref, kpr, vpr = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        o_ker, kpk, vpk = fused_block_decode_pallas(x, w, kp, vp, bt, sl,
                                                    interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(kpk), np.asarray(kpr),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vpk), np.asarray(vpr),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.pallas_interpret
    def test_kernel_ragged_lengths_and_page_boundary(self):
        """seq_lens hitting 0, a page boundary (len % page == 0: the new
        token starts a FRESH page), and a full table."""
        rng = np.random.default_rng(3)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng, seq_lens=(0, 8, 31),
                                            mp=4)
        o_ref, kpr, vpr = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        o_ker, kpk, vpk = fused_block_decode_pallas(x, w, kp, vp, bt, sl,
                                                    interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(kpk), np.asarray(kpr),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.pallas_interpret
    def test_kernel_bf16(self):
        rng = np.random.default_rng(4)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng, dtype=jnp.bfloat16)
        o_ref, _, _ = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        o_ker, _, _ = fused_block_decode_pallas(x, w, kp, vp, bt, sl,
                                                interpret=True, **kw)
        np.testing.assert_allclose(
            np.asarray(o_ker, np.float32), np.asarray(o_ref, np.float32),
            rtol=5e-2, atol=5e-2)

    @pytest.mark.pallas_interpret
    def test_kernel_mha_no_gqa(self):
        rng = np.random.default_rng(5)
        x, w, kp, vp, bt, sl, kw = _mk_case(rng, nh=4, nkv=4)
        o_ref, _, _ = fused_block_decode_ref(x, w, kp, vp, bt, sl, **kw)
        o_ker, _, _ = fused_block_decode_pallas(x, w, kp, vp, bt, sl,
                                                interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)


def _prompts(rng, cfg, n, lens):
    return [rng.integers(0, cfg.vocab_size, (ln,)).astype(np.int32)
            for ln in lens]


class TestDecodeProgramCache:
    def test_no_retrace_across_steps_and_engines(self):
        """The acceptance criterion: zero retraces across repeated
        step() calls at a fixed batch bucket, and a SECOND engine over a
        same-signature model reuses the same compiled object."""
        paddle.seed(91)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(0)
        cache = decode_program_cache()

        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        for p in _prompts(rng, cfg, 2, (5, 9)):
            eng.submit(p, 6)
        eng.step()                      # first decode: compiles (or reuses)
        key = eng.decode_key
        assert key is not None and key.kind == "decode_fused"
        traced_once = cache.trace_count(key)
        assert traced_once >= 1
        while eng.has_work():
            eng.step()
        assert cache.trace_count(key) == traced_once, \
            "decode step retraced at a fixed batch bucket"

        # second engine, same model signature: same compiled object
        eng2 = ServingEngine(model, max_batch=2, page_size=8,
                             max_seq_len=32)
        for p in _prompts(rng, cfg, 2, (4, 7)):
            eng2.submit(p, 4)
        eng2.run()
        assert eng2.decode_key == key
        assert eng2._decode_fns[eng2.bucket] is eng._decode_fns[eng.bucket]
        assert cache.trace_count(key) == traced_once

    def test_distinct_buckets_get_distinct_programs(self):
        paddle.seed(92)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        e1 = ServingEngine(model, max_batch=1, page_size=8, max_seq_len=32)
        e2 = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        rng = np.random.default_rng(1)
        p = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        e1.submit(p, 2); e1.run()
        e2.submit(p, 2); e2.run()
        assert e1.decode_key != e2.decode_key
        assert e1._decode_fns[e1.bucket] is not e2._decode_fns[e2.bucket]

    def test_eager_only_flags_do_not_invalidate_programs(self):
        """The key snapshots PROGRAM_FLAGS only: changing an eager-only
        flag (log_level) between engines reuses the compiled step, while
        changing a flag a traced program reads (flash_compact_stats) keys
        a distinct one."""
        paddle.seed(96)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(5)
        p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        mk = lambda: ServingEngine(model, max_batch=1, page_size=8,
                                   max_seq_len=32)
        e1 = mk(); e1.submit(p, 2); e1.run()
        prior = flags.get_flags(["log_level", "flash_compact_stats"])
        try:
            flags.set_flags({"log_level": 0})
            e2 = mk(); e2.submit(p, 2); e2.run()
            assert e2.decode_key == e1.decode_key
            assert e2._decode_fns[e2.bucket] is e1._decode_fns[e1.bucket]
            flags.set_flags({"flash_compact_stats": False})
            e3 = mk(); e3.submit(p, 2); e3.run()
            assert e3.decode_key != e1.decode_key
        finally:
            flags.set_flags(prior)

    def test_fused_flag_off_selects_generic_step(self):
        paddle.seed(93)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(2)
        p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        flags.set_flags({"fused_block_decode": False})
        try:
            eng = ServingEngine(model, max_batch=1, page_size=8,
                                max_seq_len=32)
            eng.submit(p, 4)
            out_generic = eng.run()[0]
            assert eng.decode_key.kind == "decode_generic"
        finally:
            flags.set_flags({"fused_block_decode": True})
        eng = ServingEngine(model, max_batch=1, page_size=8, max_seq_len=32)
        eng.submit(p, 4)
        out_fused = eng.run()[0]
        assert eng.decode_key.kind == "decode_fused"
        # the whole point: the fused program is a drop-in — same tokens
        assert out_fused == out_generic

    def test_gpt_model_falls_back_to_generic(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        paddle.seed(94)
        model = GPTForCausalLM(GPTConfig.tiny())
        rng = np.random.default_rng(3)
        p = rng.integers(0, model.config.vocab_size, (5,)).astype(np.int32)
        eng = ServingEngine(model, max_batch=1, page_size=8, max_seq_len=32)
        eng.submit(p, 3)
        eng.run()
        assert eng.decode_key.kind == "decode_generic"


class TestPrefixCachePins:
    def _pool(self, num_pages=8, page=8):
        return PagedKVCache(num_layers=1, num_pages=num_pages,
                            page_size=page, num_kv_heads=1, head_dim=8,
                            max_batch=2, max_seq_len=32,
                            dtype=jnp.float32, reserve_null_page=True)

    def test_evict_refuses_pinned_pages_and_counts_real_frees(self):
        pool = self._pool()
        cache = PrefixCache(pool)
        prompt = np.arange(16, dtype=np.int32)       # 2 full pages
        pool.allocate(0, 16)
        cache.register(prompt, pool.block_tables[0])
        pool.free_sequence(0)                        # cache is sole owner

        pages, n = cache.lookup(prompt)
        assert n == 16 and len(pages) == 2
        cache.pin(pages)                             # in-flight adoption
        assert cache.evict(4) == 0, "evicted pages pinned by a live request"
        cache.unpin(pages)
        free_before = pool.free_page_count()
        freed = cache.evict(4)
        assert freed == 2                            # only 2 nodes existed
        assert pool.free_page_count() == free_before + freed

    def test_evict_skips_shared_pages_via_refcount(self):
        pool = self._pool()
        cache = PrefixCache(pool)
        prompt = np.arange(8, dtype=np.int32)        # 1 full page
        pool.allocate(0, 8)
        cache.register(prompt, pool.block_tables[0])
        # the creating sequence is STILL live (rc = owner + cache)
        assert cache.evict(4) == 0
        pool.free_sequence(0)
        assert cache.evict(4) == 1

    def test_double_pin_needs_double_unpin(self):
        pool = self._pool()
        cache = PrefixCache(pool)
        prompt = np.arange(8, dtype=np.int32)
        pool.allocate(0, 8)
        cache.register(prompt, pool.block_tables[0])
        pool.free_sequence(0)
        pages, _ = cache.lookup(prompt)
        cache.pin(pages)
        cache.pin(pages)                             # two adopters
        cache.unpin(pages)
        assert cache.evict(4) == 0                   # second pin holds
        cache.unpin(pages)
        assert cache.evict(4) == 1

    def test_engine_shared_admission_pins_until_finish(self):
        """End-to-end: a prefix-cache admission pins its adopted pages;
        evict under pool pressure cannot free them while the request is
        in flight; they unpin (and become evictable) when it finishes."""
        paddle.seed(95)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        r1 = eng.submit(prompt, 3)
        out1 = eng.run()[r1]
        # same prompt again: admission adopts the cached prefix pages
        r2 = eng.submit(prompt, 3)
        eng.step()
        req = next(s for s in eng._slots if s is not None)
        assert req.pinned, "shared admission did not pin adopted pages"
        pinned = list(req.pinned)
        for pid in pinned:
            node = eng._prefix._nodes[eng._prefix._by_page[pid]]
            assert node["pins"] > 0
        # while in flight, eviction must leave every pinned page alone
        eng._prefix.evict(64)
        for pid in pinned:
            assert pid in eng._prefix._by_page
        out = eng.run()
        for pid in pinned:
            key = eng._prefix._by_page.get(pid)
            assert key is None or eng._prefix._nodes[key]["pins"] == 0
        assert out[r2] == out1      # adoption is numerically invisible
