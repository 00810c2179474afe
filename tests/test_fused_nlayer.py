"""Cross-layer fused decode (r17): the N-layer grouped kernel
(kernels/fused_block_decode.py multi-layer section), the copy-free
chunk-prefill attention (kernels/paged_attention.py), and the serving
engine's ``FLAGS_fused_block_layers`` dispatch.

Invariants:
  - ``fused_multi_block_decode_ref`` over a stacked group IS the
    per-layer chain of ``fused_block_decode_ref`` — bitwise, because the
    merged q|k|v and gate|up matmuls contract the same columns;
  - the multi-layer Pallas kernel (interpret mode) matches the ref at
    the repo's fp32/bf16 tolerances, for N in {1, 2, 4} incl. GQA and
    ragged sequence lengths;
  - ``paged_chunk_attention`` / ``_xla`` read K/V straight through the
    block table and match the gathered-view oracle they replaced;
  - the engine under ``FLAGS_fused_block_layers=N`` serves tokens
    identical to the per-layer path, keys the grouped program on the
    layer-group shape, never retraces at a fixed bucket, and composes
    with speculative decoding and bucket migration;
  - the memwatch estimator prices the grouped program within the 10%
    acceptance bar.
"""

import contextlib

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import flags, observability as obs
from paddle_tpu.generation.program_cache import (clear_decode_program_cache,
                                                 decode_program_cache)
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.kernels.fused_block_decode import (
    BlockDecodeWeights, MultiBlockDecodeWeights, fused_block_decode_ref,
    fused_multi_block_decode_pallas, fused_multi_block_decode_ref,
    stack_block_weights)
from paddle_tpu.kernels.paged_attention import (gather_paged_view,
                                                paged_chunk_attention,
                                                paged_chunk_attention_xla,
                                                write_paged_prompt_at)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import memory as memwatch

pytestmark = pytest.mark.fused_nlayer


@contextlib.contextmanager
def set_flags(**kw):
    prev = flags.snapshot(tuple(kw)).as_tuple()
    flags.set_flags(kw)
    try:
        yield
    finally:
        flags.set_flags(dict(prev))


def _mk_group(rng, n_layers, b=3, hidden=64, nh=4, nkv=2, inter=128,
              page=8, num_pages=16, mp=4, dtype=jnp.float32,
              seq_lens=(5, 8, 11)):
    d = hidden // nh
    mk = lambda *s: jnp.asarray(
        (rng.standard_normal(s) * 0.1).astype(np.float32), dtype)
    ws = []
    for _ in range(n_layers):
        ws.append(BlockDecodeWeights(
            ln1=jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden)
                            .astype(np.float32), dtype),
            wq=mk(hidden, nh * d), wk=mk(hidden, nkv * d),
            wv=mk(hidden, nkv * d), wo=mk(nh * d, hidden),
            ln2=jnp.asarray(1.0 + 0.1 * rng.standard_normal(hidden)
                            .astype(np.float32), dtype),
            wg=mk(hidden, inter), wu=mk(hidden, inter),
            wd=mk(inter, hidden)))
    x = mk(b, hidden)
    kps = [mk(nkv, num_pages, page, d) for _ in range(n_layers)]
    vps = [mk(nkv, num_pages, page, d) for _ in range(n_layers)]
    perm = rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp) + 1
    bt = jnp.asarray(perm, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    return x, ws, kps, vps, bt, sl, dict(num_heads=nh, num_kv_heads=nkv,
                                         rope_theta=10000.0, epsilon=1e-5)


def _chain(x, ws, kps, vps, bt, sl, **kw):
    kps, vps = list(kps), list(vps)
    for i, w in enumerate(ws):
        x, kps[i], vps[i] = fused_block_decode_ref(x, w, kps[i], vps[i],
                                                   bt, sl, **kw)
    return x, kps, vps


class TestStackedWeights:
    def test_merged_projection_layout(self):
        """The stacked struct merges q|k|v and gate|up column-wise —
        split columns must be EXACTLY the separate weights."""
        rng = np.random.default_rng(0)
        _, ws, _, _, _, _, kw = _mk_group(rng, 2)
        mw = stack_block_weights(ws)
        assert isinstance(mw, MultiBlockDecodeWeights)
        assert mw.n_layers == 2
        nh, nkv = kw["num_heads"], kw["num_kv_heads"]
        d = ws[0].wq.shape[1] // nh
        qw, kvw = nh * d, nkv * d
        for i, w in enumerate(ws):
            np.testing.assert_array_equal(mw.wqkv[i, :, :qw], w.wq)
            np.testing.assert_array_equal(mw.wqkv[i, :, qw:qw + kvw], w.wk)
            np.testing.assert_array_equal(mw.wqkv[i, :, qw + kvw:], w.wv)
            inter = w.wg.shape[1]
            np.testing.assert_array_equal(mw.wgu[i, :, :inter], w.wg)
            np.testing.assert_array_equal(mw.wgu[i, :, inter:], w.wu)

    @pytest.mark.parametrize("n", [1, 2, 4])
    def test_ref_is_bitwise_the_per_layer_chain_fp32(self, n):
        """Merged matmuls contract the same columns: the grouped ref
        must be BIT-exact against the chain, not merely close."""
        rng = np.random.default_rng(10 + n)
        x, ws, kps, vps, bt, sl, kw = _mk_group(rng, n)
        oc, kc, vc = _chain(x, ws, kps, vps, bt, sl, **kw)
        om, km, vm = fused_multi_block_decode_ref(
            x, stack_block_weights(ws), kps, vps, bt, sl, **kw)
        np.testing.assert_array_equal(np.asarray(om), np.asarray(oc))
        for i in range(n):
            np.testing.assert_array_equal(np.asarray(km[i]),
                                          np.asarray(kc[i]))
            np.testing.assert_array_equal(np.asarray(vm[i]),
                                          np.asarray(vc[i]))

    def test_ref_is_bitwise_the_per_layer_chain_bf16(self):
        rng = np.random.default_rng(20)
        x, ws, kps, vps, bt, sl, kw = _mk_group(rng, 2,
                                                dtype=jnp.bfloat16)
        oc, kc, vc = _chain(x, ws, kps, vps, bt, sl, **kw)
        om, km, vm = fused_multi_block_decode_ref(
            x, stack_block_weights(ws), kps, vps, bt, sl, **kw)
        np.testing.assert_array_equal(np.asarray(om, np.float32),
                                      np.asarray(oc, np.float32))
        for i in range(2):
            np.testing.assert_array_equal(np.asarray(km[i], np.float32),
                                          np.asarray(kc[i], np.float32))


class TestMultiLayerKernel:
    @pytest.mark.pallas_interpret
    @pytest.mark.parametrize("n", [1, 2, 4])
    def test_kernel_matches_ref_fp32(self, n):
        rng = np.random.default_rng(30 + n)
        x, ws, kps, vps, bt, sl, kw = _mk_group(rng, n)
        mw = stack_block_weights(ws)
        o_ref, kr, vr = fused_multi_block_decode_ref(x, mw, kps, vps,
                                                     bt, sl, **kw)
        o_ker, kk, vk = fused_multi_block_decode_pallas(
            x, mw, kps, vps, bt, sl, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        # 2e-6 (not the single-layer 1e-6): the merged-qkv contraction
        # tiles the K reduction differently from the separate wk matmul
        for i in range(n):
            np.testing.assert_allclose(np.asarray(kk[i]), np.asarray(kr[i]),
                                       rtol=2e-6, atol=2e-6)
            np.testing.assert_allclose(np.asarray(vk[i]), np.asarray(vr[i]),
                                       rtol=2e-6, atol=2e-6)

    @pytest.mark.pallas_interpret
    def test_kernel_bf16(self):
        rng = np.random.default_rng(40)
        x, ws, kps, vps, bt, sl, kw = _mk_group(rng, 2,
                                                dtype=jnp.bfloat16)
        mw = stack_block_weights(ws)
        o_ref, kr, _ = fused_multi_block_decode_ref(x, mw, kps, vps,
                                                    bt, sl, **kw)
        o_ker, kk, _ = fused_multi_block_decode_pallas(
            x, mw, kps, vps, bt, sl, interpret=True, **kw)
        np.testing.assert_allclose(
            np.asarray(o_ker, np.float32), np.asarray(o_ref, np.float32),
            rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(
            np.asarray(kk[0], np.float32), np.asarray(kr[0], np.float32),
            rtol=5e-2, atol=5e-2)

    @pytest.mark.pallas_interpret
    def test_kernel_ragged_lengths_and_gqa_off(self):
        """seq_lens hitting 0, a page boundary, and a nearly-full table,
        plus the MHA (rep=1) layout."""
        rng = np.random.default_rng(50)
        x, ws, kps, vps, bt, sl, kw = _mk_group(
            rng, 2, nh=4, nkv=4, seq_lens=(0, 8, 31))
        mw = stack_block_weights(ws)
        o_ref, kr, vr = fused_multi_block_decode_ref(x, mw, kps, vps,
                                                     bt, sl, **kw)
        o_ker, kk, vk = fused_multi_block_decode_pallas(
            x, mw, kps, vps, bt, sl, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        for i in range(2):
            np.testing.assert_allclose(np.asarray(kk[i]), np.asarray(kr[i]),
                                       rtol=2e-6, atol=2e-6)
            np.testing.assert_allclose(np.asarray(vk[i]), np.asarray(vr[i]),
                                       rtol=2e-6, atol=2e-6)


# ------------------------------------------- copy-free chunked prefill
def _chunk_case(rng, b=2, s=8, nh=4, nkv=2, d=16, page=8, num_pages=13,
                mp=6, start=(5, 11), dtype=jnp.float32):
    mk = lambda *sh: jnp.asarray(
        (rng.standard_normal(sh) * 0.3).astype(np.float32), dtype)
    q = mk(b, s, nh, d)
    kv_k, kv_v = mk(b, s, nkv, d), mk(b, s, nkv, d)
    kp = mk(nkv, num_pages, page, d)
    vp = mk(nkv, num_pages, page, d)
    perm = rng.permutation(num_pages - 1)[:b * mp].reshape(b, mp) + 1
    bt = jnp.asarray(perm, jnp.int32)
    st = jnp.asarray(start, jnp.int32)
    # write-then-attend, the chunk path's ordering
    kp, vp = write_paged_prompt_at(kp, vp, kv_k, kv_v, bt, st)
    return q, kp, vp, bt, st


def _gather_oracle(q, kp, vp, bt, start):
    """The path the copy-free attention replaced: materialize the full
    per-sequence view, mask by absolute position, plain softmax."""
    kg, vg = gather_paged_view(kp, vp, bt)          # (B, T, Hkv, D)
    q4 = np.asarray(q, np.float32)
    kg, vg = np.asarray(kg, np.float32), np.asarray(vg, np.float32)
    b, s, h, d = q4.shape
    t = kg.shape[1]
    rep = h // kg.shape[2]
    st = np.asarray(start)
    out = np.zeros_like(q4)
    for bi in range(b):
        for hi in range(h):
            kv = kg[bi, :, hi // rep]               # (T, D)
            vv = vg[bi, :, hi // rep]
            sc = q4[bi, :, hi] @ kv.T / np.sqrt(d)  # (S, T)
            q_pos = st[bi] + np.arange(s)[:, None]
            mask = np.arange(t)[None, :] <= q_pos
            sc = np.where(mask, sc, -np.inf)
            w = np.exp(sc - sc.max(axis=1, keepdims=True))
            w /= w.sum(axis=1, keepdims=True)
            out[bi, :, hi] = w @ vv
    return out


class TestCopyFreeChunk:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_xla_twin_matches_gather_oracle(self, dtype):
        rng = np.random.default_rng(60)
        q, kp, vp, bt, st = _chunk_case(rng, dtype=dtype)
        out = paged_chunk_attention_xla(q, kp, vp, bt, st)
        ref = _gather_oracle(q, kp, vp, bt, st)
        tol = 2e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   rtol=tol, atol=tol)

    @pytest.mark.pallas_interpret
    def test_kernel_matches_gather_oracle(self):
        rng = np.random.default_rng(61)
        q, kp, vp, bt, st = _chunk_case(rng)
        out = paged_chunk_attention(q, kp, vp, bt, st)
        ref = _gather_oracle(q, kp, vp, bt, st)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-5, atol=2e-5)

    def test_padded_final_chunk_overflow(self):
        """A start near the table's end: the padded chunk rows point
        past the written prefix; the clipped page count plus position
        masking must keep them from contributing."""
        rng = np.random.default_rng(62)
        # mp=4 pages of 8 -> 32-token tables; start 29 leaves 3 rows
        q, kp, vp, bt, st = _chunk_case(rng, b=1, s=8, mp=4,
                                        num_pages=6, start=(24,))
        out = paged_chunk_attention_xla(q, kp, vp, bt, st)
        ref = _gather_oracle(q, kp, vp, bt, st)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-5, atol=2e-5)

    def test_engine_chunked_prefill_still_bit_identical(self):
        """End-to-end: chunked prefill through the copy-free path must
        serve the same tokens as the monolithic path."""
        paddle.seed(71)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(71)
        prompt = rng.integers(0, cfg.vocab_size, (21,)).astype(np.int32)
        outs = []
        for chunk in (0, 8):
            eng = ServingEngine(model, max_batch=2, page_size=8,
                                max_seq_len=48, prefill_chunk=chunk)
            rid = eng.submit(prompt, 6)
            outs.append(eng.run()[rid])
        assert outs[0] == outs[1]


# --------------------------------------------------- serving dispatch
def _solo(model, prompt, n):
    return model.generate(paddle.to_tensor(prompt[None]),
                          max_new_tokens=n, do_sample=False,
                          return_full_sequence=False).numpy()[0].tolist()


def _llama(seed=91):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    return cfg, LlamaForCausalLM(cfg)


class TestServingNLayer:
    @pytest.mark.parametrize("n", [2, 3])
    def test_tokens_identical_to_per_layer_path(self, n):
        """N=2 groups both layers; N=3 over 2 layers exercises the
        ragged final group. Either way: same tokens as N=1."""
        cfg, model = _llama()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, (ln,)).astype(np.int32)
                   for ln in (5, 9)]
        refs = [_solo(model, p, 6) for p in prompts]
        with set_flags(fused_block_layers=n):
            eng = ServingEngine(model, max_batch=2, page_size=8,
                                max_seq_len=48)
            rids = [eng.submit(p, 6) for p in prompts]
            out = eng.run()
        assert eng.decode_key.kind == "decode_fused_nlayer"
        assert [out[r] for r in rids] == refs

    def test_group_shape_in_decode_key_and_zero_retrace(self):
        cfg, model = _llama()
        rng = np.random.default_rng(8)
        cache = decode_program_cache()
        with set_flags(fused_block_layers=2):
            eng = ServingEngine(model, max_batch=2, page_size=8,
                                max_seq_len=48)
            for ln in (5, 9):
                eng.submit(rng.integers(0, cfg.vocab_size, (ln,))
                           .astype(np.int32), 8)
            eng.step()
            key = eng.decode_key
            assert key.kind == "decode_fused_nlayer"
            assert "nlayer" in str(key.extra) and "2" in str(key.extra)
            traced = cache.trace_count(key)
            assert traced >= 1
            while eng.has_work():
                eng.step()
            assert cache.trace_count(key) == traced, \
                "N-layer decode retraced at a fixed batch bucket"
            # a second engine over the same signature reuses the program
            eng2 = ServingEngine(model, max_batch=2, page_size=8,
                                 max_seq_len=48)
            eng2.submit(rng.integers(0, cfg.vocab_size, (6,))
                        .astype(np.int32), 4)
            eng2.run()
            assert eng2.decode_key == key
            assert cache.trace_count(key) == traced

    def test_flag_off_keeps_per_layer_kind(self):
        cfg, model = _llama()
        eng = ServingEngine(model, max_batch=1, page_size=8,
                            max_seq_len=32)
        eng.submit(np.arange(5, dtype=np.int32) % cfg.vocab_size, 3)
        eng.run()
        assert eng.decode_key.kind == "decode_fused"

    def test_spec_decode_composes(self):
        """Target runs the grouped program, the draft stays per-layer,
        and greedy spec output equals plain greedy."""
        cfg, target = _llama(11)
        paddle.seed(12)
        draft = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, cfg.vocab_size, (ln,)).astype(np.int32)
                   for ln in (5, 8)]
        refs = [_solo(target, p, 10) for p in prompts]
        with set_flags(fused_block_layers=2):
            eng = ServingEngine(target, max_batch=2, page_size=8,
                                max_seq_len=64, draft_model=draft)
            rids = [eng.submit(p, 10) for p in prompts]
            out = eng.run(max_wall=300.0)
        assert [out[r] for r in rids] == refs
        assert eng.spec_rounds > 0
        assert eng.decode_key.kind == "decode_fused_nlayer"
        # the draft's decode program is the per-layer kind, never grouped
        assert "nlayer" not in str(eng.spec_draft_key.kind)

    def test_bucket_migration_composes(self):
        cfg, model = _llama(13)
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, cfg.vocab_size, (int(ln),))
                   .astype(np.int32) for ln in rng.integers(4, 12, size=5)]
        refs = [_solo(model, p, 5) for p in prompts]
        with set_flags(fused_block_layers=2, serving_bucket_patience=2):
            eng = ServingEngine(model, max_batch=4, page_size=8,
                                max_seq_len=48, bucket_ladder=(2, 4))
            rids = [eng.submit(p, 5) for p in prompts]
            out = eng.run()
        assert eng.bucket_migrations >= 1
        assert eng.decode_key.kind == "decode_fused_nlayer"
        assert [out[r] for r in rids] == refs


class TestEstimatorNLayer:
    def test_grouped_program_within_tolerance(self):
        """The analytic estimator must price the grouped program's
        arithmetic sections (argument, alias, output) within 2% (the
        same bar tests/test_memwatch.py holds the other programs to;
        the fitted temp term is held by no test)."""
        prior = flags.snapshot(("memwatch",
                                "fused_block_layers")).as_tuple()
        flags.set_flags({"memwatch": True, "fused_block_layers": 2})
        clear_decode_program_cache()
        memwatch.clear_program_table()
        try:
            cfg, model = _llama(14)
            eng = ServingEngine(model, max_batch=2, page_size=8,
                                max_seq_len=48)
            rng = np.random.default_rng(14)
            for ln in (6, 7):
                eng.submit(rng.integers(0, cfg.vocab_size, (ln,))
                           .astype(np.int32), 4)
            eng.run()
            rows = [r for r in memwatch.program_table()
                    if r["kind"] == "decode_fused_nlayer"]
            assert rows, "grouped decode program was not captured"
            row = rows[0]
            dims = memwatch.ModelDims.of_config(cfg)
            geom = memwatch.PoolGeometry.of_pool(eng.pool)
            pb = sum(memwatch.aval_bytes(v)
                     for v in eng._params.values())
            pb += sum(memwatch.aval_bytes(v)
                      for v in eng._buffers.values() if v is not None)
            est = memwatch.estimate_decode_program(dims, geom, eng.bucket,
                                                   pb, fused_layers=2)
            for section in ("argument", "alias", "output"):
                pred, comp = est[section], row[section]
                assert abs(pred - comp) / comp <= 0.02, \
                    f"{section}: estimated {pred} vs compiled {comp} " \
                    f"({(pred / comp - 1) * 100:+.1f}%)"
        finally:
            flags.set_flags(dict(prior))
            clear_decode_program_cache()
            memwatch.clear_program_table()
            obs.registry().clear()
