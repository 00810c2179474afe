"""Continuous-batching serving engine (paddle_tpu/generation/serving.py).

The invariant: every request's tokens equal its SOLO greedy decode,
regardless of what else shared the batch, when it was admitted, or whose
freed pages it recycled — the whole point of paged attention.
"""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.testing import faults


def fault_spec(spec, backoff=0.001):
    """Arm FLAGS_fault_inject for the engines built inside the block
    (sites bind at construction); restores + resets on exit."""
    return faults.armed(spec, serving_retry_backoff=backoff)


def solo(model, prompt, n, eos=None):
    return model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=n,
                          do_sample=False, eos_token_id=eos,
                          return_full_sequence=False).numpy()[0].tolist()


class TestServingEngine:
    def test_staggered_admission_matches_solo_gpt(self):
        paddle.seed(71)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 9, 5, 7)]
        refs = [solo(model, p, 6) for p in prompts]

        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        eng.submit(prompts[0], 6)
        eng.submit(prompts[1], 6)
        eng.step(); eng.step()
        eng.submit(prompts[2], 6)   # queued: batch full; admitted on free
        eng.submit(prompts[3], 6)
        out = eng.run()
        for i in range(4):
            assert out[i] == refs[i]

    def test_llama_gqa_ragged_positions(self):
        """Per-slot rotary positions: two requests at DIFFERENT lengths
        decode in the same fixed-shape batch."""
        paddle.seed(72)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(1)
        p_a = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        p_b = rng.integers(0, cfg.vocab_size, (11,)).astype(np.int32)
        ref_a, ref_b = solo(model, p_a, 5), solo(model, p_b, 5)

        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        ra = eng.submit(p_a, 5)
        eng.step(); eng.step()      # a is 2 tokens ahead when b admits
        rb = eng.submit(p_b, 5)
        out = eng.run()
        assert out[ra] == ref_a
        assert out[rb] == ref_b

    def test_eos_frees_slot_early(self):
        paddle.seed(73)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        free = solo(model, prompt, 6)
        eos = free[2]
        # greedy output may repeat: the engine stops at the FIRST eos hit
        expect = free[:free.index(eos) + 1]
        eng = ServingEngine(model, max_batch=1, page_size=8, max_seq_len=32)
        rid = eng.submit(prompt, 6, eos_token_id=eos)
        out = eng.run()
        assert out[rid] == expect
        assert eng.pool.free_page_count() == eng.pool.num_pages - 1  # null

    def test_pool_pressure_queues_without_starvation(self):
        paddle.seed(74)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(3)
        # pool sized so only ONE request fits at a time
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            num_pages=1 + 2, max_seq_len=16)
        p1 = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        r1 = eng.submit(p1, 4)
        r2 = eng.submit(p2, 4)
        out = eng.run()             # r2 waits for r1's pages, then runs
        assert out[r1] == solo(model, p1, 4)
        assert out[r2] == solo(model, p2, 4)

    def test_too_long_request_rejected(self):
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        eng = ServingEngine(model, max_batch=1, page_size=8, max_seq_len=16)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(np.zeros(14, np.int32), 8)


class TestDonationDiscipline:
    """TRC003 regression (tracecheck): the compiled prefill/decode steps
    donate their pools argument, so the engine must detach the pool's
    own references BEFORE dispatch (``take_pools``) and install the
    step's returned arrays after (``install_pools``) — never leaving a
    window where ``pool.k_pages`` aliases donated (invalidated)
    buffers."""

    def _engine(self):
        paddle.seed(79)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        return eng, prompt

    def test_take_pools_detaches_and_install_restores(self):
        eng, _ = self._engine()
        before = list(eng.pool.k_pages)
        pairs = eng.pool.take_pools()
        assert all(k is None for k in eng.pool.k_pages)
        assert all(v is None for v in eng.pool.v_pages)
        # double-detach is the use-after-donate shape — must refuse
        with pytest.raises(RuntimeError, match="already detached"):
            eng.pool.take_pools()
        eng.pool.install_pools(pairs)
        assert all(k is b for k, b in zip(eng.pool.k_pages, before))

    def test_steps_reinstall_fresh_pools(self):
        eng, prompt = self._engine()
        eng.submit(prompt, 4)
        eng.step()                      # prefill dispatch (donating)
        assert all(k is not None for k in eng.pool.k_pages)
        eng.step()                      # decode dispatch (donating)
        assert all(k is not None for k in eng.pool.k_pages)
        assert all(v is not None for v in eng.pool.v_pages)
        out = eng.run()
        assert len(out[0]) == 4

    def test_pools_stay_device_arrays_across_dispatches(self):
        """The step's returned pools are installed as they come back —
        device arrays. (Unwrapping them by duck-typing on ``._value``
        matched jax.Array too and copied every pool to the host on every
        dispatch: free on the CPU, seconds a step on the chip.)"""
        eng, prompt = self._engine()
        eng.submit(prompt, 3)
        while eng.has_work():
            eng.step()
            assert all(isinstance(p, jax.Array)
                       for p in eng.pool.k_pages + eng.pool.v_pages)

    def test_transient_dispatch_failure_recovers_with_parity(self):
        """r10 replay recovery: a dispatch that raises AFTER donation
        leaves the pool detached (r08) — recovery now allocates fresh
        pools and re-queues the in-flight request for re-prefill from
        prompt + emitted tokens, and the final output is bit-identical
        to the unfailed run."""
        flags.set_flags({"serving_retry_backoff": 0.001})
        eng, prompt = self._engine()
        ref = solo(eng.model, prompt, 6)
        rid = eng.submit(prompt, 6)
        eng.step(); eng.step()          # prefill + one decode

        real = eng._decode_fns[eng.bucket]
        boomed = []

        def boom_once(*a, **k):
            if not boomed:
                boomed.append(1)
                raise RuntimeError("simulated post-dispatch failure")
            return real(*a, **k)

        eng._decode_fns[eng.bucket] = boom_once
        out = eng.run()                 # recovery happens inside
        assert boomed and out[rid] == ref
        assert eng.status(rid) == "OK"
        assert all(k is not None for k in eng.pool.k_pages)

    def test_retry_exhaustion_fails_requests_without_killing_run(self):
        """Persistent no-progress failures terminate the victims FAILED
        instead of raising out of run(), and the engine serves new
        requests afterwards on its fresh pool."""
        flags.set_flags({"serving_retry_backoff": 0.001})
        eng, prompt = self._engine()
        ref = solo(eng.model, prompt, 4)

        def boom(*a, **k):
            raise RuntimeError("wedged backend")

        eng._prefill_fn = boom          # no prefill -> no progress ever
        eng._decode_fns = {b: boom for b in eng.ladder}
        rid = eng.submit(prompt, 4)
        out = eng.run()                 # returns; does NOT raise
        assert eng.status(rid) == "FAILED"
        assert out[rid] == []           # partial tokens (none emitted)
        # the engine is NOT wedged: fresh pool + real programs serve on
        eng._prefill_fn = None
        eng._decode_fns = {}
        rid2 = eng.submit(prompt, 4)
        assert eng.run()[rid2] == ref
        assert eng.status(rid2) == "OK"

    def test_injected_decode_faults_replay_parity_generic(self):
        """FLAGS_fault_inject chaos on the GENERIC decode path: every
        3rd decode dispatch dies post-detach; outputs stay bit-identical
        to the fault-free run and nothing wedges."""
        eng, _ = self._engine()
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, eng.model.config.vocab_size,
                                (n,)).astype(np.int32)
                   for n in (5, 9, 7)]
        refs = [solo(eng.model, p, 5) for p in prompts]
        with fault_spec("decode_dispatch:every=3"):
            chaos = ServingEngine(eng.model, max_batch=2, page_size=8,
                                  max_seq_len=32)
            rids = [chaos.submit(p, 5) for p in prompts]
            out = chaos.run()
        assert chaos.decode_key.kind == "decode_generic"
        assert [out[r] for r in rids] == refs
        assert all(chaos.status(r) == "OK" for r in rids)

    def test_injected_decode_faults_replay_parity_fused(self):
        """Same chaos drill on the FUSED block-decode path (Llama
        publishes block_decode_spec): replay recovery must be
        path-agnostic."""
        paddle.seed(95)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(22)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (4, 11)]
        refs = [solo(model, p, 5) for p in prompts]
        with fault_spec("decode_dispatch:every=3;prefill:p=0.2:seed=11"):
            chaos = ServingEngine(model, max_batch=2, page_size=8,
                                  max_seq_len=32)
            rids = [chaos.submit(p, 5) for p in prompts]
            out = chaos.run()
        assert chaos.decode_key.kind == "decode_fused"
        assert [out[r] for r in rids] == refs
        assert all(chaos.status(r) == "OK" for r in rids)

    def test_trace_time_failure_surfaces_without_replay(self, monkeypatch):
        """A program that fails in the call that traces it (what a
        compiler refusal looks like) propagates from step(): no replay,
        no back-off, no FAILED status — the refusal is deterministic."""
        from paddle_tpu.generation import serving
        from paddle_tpu.generation.program_cache import (
            ProgramBuildError, clear_decode_program_cache)

        def refusing_builder(note_trace, model):
            @jax.jit
            def step(*args):
                note_trace()
                raise NotImplementedError("Mosaic says no")
            return step

        monkeypatch.setattr(serving, "_build_generic_decode",
                            refusing_builder)
        clear_decode_program_cache()    # no warmed program to re-serve
        try:
            eng, prompt = self._engine()
            recovered = []
            monkeypatch.setattr(
                eng, "_recover_dispatch", recovered.append)
            rid = eng.submit(prompt, 4)
            with pytest.raises(ProgramBuildError) as err:
                eng.run()
        finally:
            clear_decode_program_cache()
        assert err.value.key.kind == "decode_generic"
        assert isinstance(err.value.__cause__, NotImplementedError)
        assert recovered == []
        assert eng.status(rid) != "FAILED"

    def test_first_run_fault_of_a_built_program_replays(self, monkeypatch):
        """The window between the two: a program that BUILT, and whose
        very first execution dies on the device. That is a dispatch
        fault, not a refusal — it is replayed, the executable is kept
        (no second build), and the request ends OK."""
        from paddle_tpu.generation import serving
        from paddle_tpu.generation.program_cache import (
            ProgramBuildError, clear_decode_program_cache,
            decode_program_cache)

        real_build, ran = serving._build_generic_decode, []

        def device_fault(tok):
            if not ran:
                ran.append(1)
                raise RuntimeError("device fault on the first run")
            return tok

        def flaky_builder(note_trace, model):
            step = real_build(note_trace, model)

            def run(params, buffers, toks, pools, bt, sl):
                tok, states = step(params, buffers, toks, pools, bt, sl)
                return jax.pure_callback(
                    device_fault, jax.ShapeDtypeStruct(tok.shape, tok.dtype),
                    tok), states
            return jax.jit(run, donate_argnums=(3,))

        monkeypatch.setattr(serving, "_build_generic_decode", flaky_builder)
        clear_decode_program_cache()
        try:
            eng, prompt = self._engine()
            ref = solo(eng.model, prompt, 5)
            recover, replayed = eng._recover_dispatch, []
            monkeypatch.setattr(
                eng, "_recover_dispatch",
                lambda exc: (replayed.append(exc), recover(exc)))
            rid = eng.submit(prompt, 5)
            out = eng.run()
            traces = decode_program_cache().trace_count(eng.decode_key)
        finally:
            clear_decode_program_cache()
        assert ran and len(replayed) == 1
        assert not isinstance(replayed[0], ProgramBuildError)
        assert out[rid] == ref and eng.status(rid) == "OK"
        assert traces == 1

    def test_dispatch_fault_on_warmed_program_still_replays(
            self, monkeypatch):
        """The other side of the seam: once a program has run, a failed
        dispatch of it is a fault, and replay recovery absorbs it."""
        eng, prompt = self._engine()
        ref = solo(eng.model, prompt, 5)
        rid = eng.submit(prompt, 5)
        assert eng.run()[rid] == ref            # warms prefill + decode
        with fault_spec("decode_dispatch:every=2:times=2"):
            chaos = ServingEngine(eng.model, max_batch=2, page_size=8,
                                  max_seq_len=32)
            recover, replayed = chaos._recover_dispatch, []
            monkeypatch.setattr(
                chaos, "_recover_dispatch",
                lambda exc: (replayed.append(exc), recover(exc)))
            rid = chaos.submit(prompt, 5)
            out = chaos.run()
        assert out[rid] == ref and chaos.status(rid) == "OK"
        assert len(replayed) == 2
        assert all(isinstance(e, faults.InjectedFault) for e in replayed)

    def test_deadline_eviction_at_step_boundary(self):
        """submit(deadline=...): an expired request — queued or in
        flight — is terminated TIMEOUT at the next step boundary with
        its partial tokens banked, and its slot/pages recycle."""
        import time as _time
        eng, prompt = self._engine()
        rid_dead = eng.submit(prompt, 6, deadline=0.0)
        rid_live = eng.submit(prompt, 4)
        _time.sleep(0.005)
        out = eng.run()
        assert eng.status(rid_dead) == "TIMEOUT"
        assert out[rid_dead] == []
        assert eng.status(rid_live) == "OK"
        assert len(out[rid_live]) == 4
        # every page returned (null page excluded)
        assert eng.pool.free_page_count() == eng.pool.num_pages - 1

    def test_run_max_wall_watchdog(self):
        eng, prompt = self._engine()
        ra = eng.submit(prompt, 4)
        rb = eng.submit(prompt, 4)
        out = eng.run(max_wall=0.0)     # expires before the first step
        assert eng.status(ra) == "TIMEOUT" and eng.status(rb) == "TIMEOUT"
        assert out[ra] == [] and out[rb] == []
        assert not eng.has_work()

    def test_results_preserved_after_mid_run_raise(self, monkeypatch):
        """Exception safety: a raise escaping the recovery machinery
        (here: the step loop itself breaks) must leave already-completed
        results retrievable via results()."""
        paddle.seed(79)
        model = GPTForCausalLM(GPTConfig.tiny())
        prompt = np.random.default_rng(9).integers(
            0, model.config.vocab_size, (5,)).astype(np.int32)
        ref = solo(model, prompt, 4)
        # max_batch=1 serializes: r1 completes before r2 admits
        eng = ServingEngine(model, max_batch=1, page_size=8,
                            max_seq_len=32)
        r1 = eng.submit(prompt, 4)
        r2 = eng.submit(prompt, 4)
        real_step = eng.step
        calls = []

        def step_then_boom():
            # r1 completes in 3 steps (prefill + decode both emit);
            # boom while r2 is still mid-flight
            if len(calls) >= 4:
                raise RuntimeError("loop bug outside recovery")
            calls.append(1)
            real_step()

        monkeypatch.setattr(eng, "step", step_then_boom)
        with pytest.raises(RuntimeError, match="loop bug"):
            eng.run()
        assert eng.results()[r1] == ref
        assert eng.status(r1) == "OK" and eng.status(r2) == "PENDING"

    def test_serving_results_unchanged_by_handoff(self):
        eng, prompt = self._engine()
        ref = solo(eng.model, prompt, 6)
        rid = eng.submit(prompt, 6)
        out = eng.run()
        assert out[rid] == ref


class TestCrossFeatureComposition:
    def test_int8_model_serves_with_exact_parity(self):
        from paddle_tpu.nn.quant import quantize_linears

        paddle.seed(81)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        quantize_linears(model)
        rng = np.random.default_rng(0)
        p1 = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        s1, s2 = solo(model, p1, 5), solo(model, p2, 5)
        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        r1, r2 = eng.submit(p1, 5), eng.submit(p2, 5)
        out = eng.run()
        assert out[r1] == s1 and out[r2] == s2

    def test_int8_draft_speculative_lossless(self):
        from paddle_tpu.nn.quant import quantize_linears

        paddle.seed(82)
        cfg = GPTConfig.tiny()
        target = GPTForCausalLM(cfg)
        paddle.seed(83)
        draft = GPTForCausalLM(cfg)
        quantize_linears(draft)       # the production pattern: cheap draft
        prompt = paddle.to_tensor(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (1, 5)).astype(np.int32))
        ref = target.generate(prompt, max_new_tokens=8,
                              do_sample=False).numpy()
        spec = target.generate_speculative(
            prompt, draft, max_new_tokens=8,
            num_speculative_tokens=3).numpy()
        np.testing.assert_array_equal(ref, spec)

    def test_quantized_layer_activation_grads_flow(self):
        """Adapter training over a frozen int8 backbone: activations and
        bias differentiate through weight_only_linear."""
        import paddle_tpu.nn as nn
        from paddle_tpu.nn.quant import QuantizedLinear

        paddle.seed(84)
        lin = nn.Linear(8, 4)
        q = QuantizedLinear.from_linear(lin)
        x = paddle.to_tensor(np.random.default_rng(2).standard_normal(
            (3, 8)).astype(np.float32), stop_gradient=False)
        out = q(x)
        out.sum().backward()
        assert x.grad is not None
        assert float(np.abs(x.grad.numpy()).sum()) > 0
        assert q.bias.grad is not None

    def test_lazy_streamed_int8_model_serves_exactly(self):
        """The 7B-on-one-chip flow end to end at tiny scale: LazyGuard
        meta build -> streaming int8 quantize -> materialize -> the
        continuous-batching engine. Tokens must equal the solo decode of
        the SAME lazy-built model (and, by RNG replay, of an eager
        build with the same seed)."""
        from paddle_tpu.framework import materialize
        from paddle_tpu.nn.quant import quantize_linears

        def build():
            paddle.seed(85)
            return GPTForCausalLM(GPTConfig.tiny())

        eager = quantize_linears(build())
        with paddle.LazyGuard():
            model = build()
        quantize_linears(model)
        materialize(model)
        cfg = model.config
        rng = np.random.default_rng(3)
        p1 = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
        ref1, ref2 = solo(eager, p1, 5), solo(eager, p2, 5)
        eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=32)
        r1, r2 = eng.submit(p1, 5), eng.submit(p2, 5)
        out = eng.run()
        assert out[r1] == ref1 and out[r2] == ref2


class TestPrefixCache:
    """Automatic prefix caching (serving.py PrefixCache): requests with a
    common page-aligned prompt prefix adopt the cached pages read-only
    and skip that prefix's prefill. The engine invariant is unchanged:
    every request's tokens equal its solo greedy decode."""

    def _model(self, seed=86):
        paddle.seed(seed)
        return GPTForCausalLM(GPTConfig.tiny())

    def test_shared_prefix_exact_parity(self):
        model = self._model()
        rng = np.random.default_rng(7)
        prefix = rng.integers(0, 256, (16,)).astype(np.int32)  # 2 pages @ 8
        p1 = np.concatenate([prefix, rng.integers(0, 256, (3,))]).astype(np.int32)
        p2 = np.concatenate([prefix, rng.integers(0, 256, (5,))]).astype(np.int32)
        ref1, ref2 = solo(model, p1, 6), solo(model, p2, 6)

        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        r1 = eng.submit(p1, 6)
        out1 = eng.run()
        assert out1[r1] == ref1
        # second request: its 2 prefix pages must come from the cache
        pages, n_cached = eng._prefix.lookup(p2)
        assert n_cached == 16 and len(pages) == 2
        r2 = eng.submit(p2, 6)
        out2 = eng.run()
        assert out2[r2] == ref2

    def test_identical_prompt_resubmission(self):
        """Whole-prompt-cached edge: the last page is excluded so the
        first generated token still goes through compute."""
        model = self._model(87)
        rng = np.random.default_rng(8)
        p = rng.integers(0, 256, (16,)).astype(np.int32)  # exactly 2 pages
        ref = solo(model, p, 5)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        r1 = eng.submit(p, 5)
        assert eng.run()[r1] == ref
        r2 = eng.submit(p, 5)
        assert eng.run()[r2] == ref   # served from cache

    def test_pages_are_shared_while_both_live(self):
        model = self._model(88)
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, 256, (8,)).astype(np.int32)
        p1 = np.concatenate([prefix, [1, 2]]).astype(np.int32)
        p2 = np.concatenate([prefix, [3]]).astype(np.int32)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        eng.submit(p1, 20)
        eng.step()                      # r1 admitted + prefilled
        eng.submit(p2, 20)
        eng.step()                      # r2 admitted via the cache
        bt = eng.pool.block_tables
        assert bt[0, 0] == bt[1, 0]     # same physical page
        assert eng.pool._page_rc[bt[0, 0]] == 3  # 2 sequences + cache pin
        eng.run()

    def test_eviction_under_pool_pressure(self):
        """A tiny pool: cached pages must be reclaimed for new requests,
        and parity must survive the eviction. 3 usable pages; each
        request needs 3 and pins its 2 full prompt pages on finish, so
        every admission after the first MUST evict."""
        model = self._model(89)
        rng = np.random.default_rng(10)
        prompts = [rng.integers(0, 256, (16,)).astype(np.int32)
                   for _ in range(3)]
        refs = [solo(model, p, 4) for p in prompts]
        eng = ServingEngine(model, max_batch=1, page_size=8,
                            num_pages=4, max_seq_len=24, prefix_cache=True)
        for p, ref in zip(prompts, refs):
            rid = eng.submit(p, 4)
            assert eng.run()[rid] == ref
        # the evictions really ran: only the last prompt's pins survive
        assert len(eng._prefix._nodes) <= 2

    def test_trie_distinguishes_same_chunk_under_different_prefixes(self):
        model = self._model(90)
        rng = np.random.default_rng(11)
        a = rng.integers(0, 256, (8,)).astype(np.int32)
        b = rng.integers(0, 256, (8,)).astype(np.int32)
        c = rng.integers(0, 256, (8,)).astype(np.int32)
        pab = np.concatenate([a, b, [1]]).astype(np.int32)
        pcb = np.concatenate([c, b, [1]]).astype(np.int32)  # same 2nd chunk
        ref_ab, ref_cb = solo(model, pab, 4), solo(model, pcb, 4)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        rab = eng.submit(pab, 4)
        assert eng.run()[rab] == ref_ab
        # c+b must NOT reuse a+b's second page (different parent chain)
        pages, n_cached = eng._prefix.lookup(pcb)
        assert n_cached == 0
        rcb = eng.submit(pcb, 4)
        assert eng.run()[rcb] == ref_cb

    def test_extending_request_deepens_cache(self):
        """Review finding: shared admissions must register their suffix
        pages too — a request EXTENDING a cached prefix contributes its
        own full pages to the trie instead of leaving them unregistered
        (a multi-turn conversation grows one reusable chain)."""
        model = self._model(91)
        rng = np.random.default_rng(12)
        p_a = rng.integers(0, 256, (16,)).astype(np.int32)  # 2 full pages
        p_b = np.concatenate(
            [p_a, rng.integers(0, 256, (9,))]).astype(np.int32)  # +1 page
        ref_a, ref_b = solo(model, p_a, 4), solo(model, p_b, 4)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        ra = eng.submit(p_a, 4)
        assert eng.run()[ra] == ref_a
        rb = eng.submit(p_b, 4)          # adopts a's 2 pages (suffix 9)
        assert eng.run()[rb] == ref_b
        # b's shared admission registered ITS third full page
        pages, n_cached = eng._prefix.lookup(p_b)
        assert n_cached == 24

    def test_barely_covered_long_prompt_prefills_instead(self):
        """Review finding: a 1-page cache hit on a long prompt must NOT
        force a long teacher-forced replay — the coverage threshold sends
        it down the normal prefill path (and parity holds either way)."""
        model = self._model(92)
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, 256, (8,)).astype(np.int32)
        long_p = np.concatenate(
            [prefix, rng.integers(0, 256, (40,))]).astype(np.int32)
        ref = solo(model, long_p, 4)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        r0 = eng.submit(prefix.copy(), 4)   # seeds the 1-page cache...
        eng.run()
        r1 = eng.submit(long_p, 4)          # ...but 40 >> max(16, 8)
        eng.step()
        req = next(s for s in eng._slots if s is not None)
        assert req.pending == []            # went through full prefill
        assert eng.run()[r1] == ref


# ------------------------------------------ the overlapped decode step
# Decode step N+1 is dispatched while step N's tokens are still on the
# device, and the host reads them one step late. None of it may show in
# a request's tokens or in the order its callback sees them: every case
# runs on the tiny GPT and on the tiny granite hybrid (recurrent rows
# beside pages), against each model's own greedy reference.

def _counter(eng, name, **labels):
    """The engine's own series of ``name`` (its ``replica`` label),
    summed over whatever labels are not given."""
    fam = obs.registry().snapshot()["metrics"].get(name, {"series": []})
    want = dict(labels, replica=eng.replica)
    return sum(s["value"] for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in want.items()))


class _Tiny:
    """One tiny model: engines over it, prompts for it, and the check
    that ``tokens`` are the greedy continuation of ``prompt``."""

    def __init__(self, kind):
        self.kind = kind
        if kind == "gpt":
            paddle.seed(3101)
            self.cfg = GPTConfig.tiny()
            self.model = GPTForCausalLM(self.cfg)
        else:
            import dataclasses
            from benchmark.lib.system import load_reference
            from paddle_tpu.models import (GraniteHybridConfig,
                                           GraniteHybridForCausalLM)
            paddle.seed(2804)
            # an embedding that does not drown the mixers: at the
            # published multipliers a random model repeats its last token
            self.cfg = GraniteHybridConfig.tiny(embedding_multiplier=1.0,
                                                initializer_range=0.1)
            self.model = GraniteHybridForCausalLM(self.cfg)
            self.model.eval()
            self._ref = load_reference("granite-4.0-h-micro")
            self._weights = dict(self.model.raw_state()[0])
            self._md = dataclasses.asdict(self.cfg)
        self._n = 0

    def engine(self, **kw):
        self._n += 1
        kw = {"max_batch": 4, "page_size": 8, "max_seq_len": 64,
              "prefill_chunk": 16, "bucket_ladder": (1, 2, 4),
              "replica": f"ov-{self.kind}-{self._n}", **kw}
        eng = ServingEngine(self.model, **kw)
        eng.bucket_patience = 1
        return eng

    def prompts(self, lens, seed=5):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, self.cfg.vocab_size, (n,)).astype(np.int32)
                for n in lens]

    def check(self, prompt, tokens, n=None):
        tokens = list(tokens)
        assert tokens and (n is None or len(tokens) == n)
        if self.kind == "gpt":
            assert tokens == solo(self.model, prompt, len(tokens))
            return
        import jax.numpy as jnp
        ids = jnp.asarray(list(prompt) + tokens[:-1], jnp.int32)
        logits = np.asarray(self._ref.logits(self._weights, ids, self._md))
        assert tokens == logits[len(prompt) - 1:].argmax(-1).tolist()


@pytest.fixture(scope="module", params=["gpt", "granite"])
def tiny(request):
    return _Tiny(request.param)


def _stream(seen):
    def on_token(rid, tok, done):
        seen.append((rid, tok, done))
    return on_token


def _step_until_flying(eng, rid, tokens=1):
    """Step until ``rid`` has ``tokens`` on the host and a decode step
    is in flight."""
    while len(eng.poll(rid)["tokens"]) < tokens or eng._flying is None:
        eng.step()


class TestOverlappedDecode:
    def test_rows_join_while_a_step_is_in_flight(self, tiny):
        """(a) a monolithic prefill and a chunked one admitted behind a
        step in flight: every request's tokens, and the order its
        callback sees them in, are its solo greedy run's."""
        eng = tiny.engine()
        prompts = tiny.prompts((5, 9, 33, 7))
        budgets = (10, 6, 5, 8)
        seen = []
        rids = [eng.submit(prompts[0], budgets[0], on_token=_stream(seen))]
        _step_until_flying(eng, rids[0], 2)
        for p, n in zip(prompts[1:], budgets[1:]):
            assert eng._flying is not None
            rids.append(eng.submit(p, n, on_token=_stream(seen)))
            eng.step()
        out = eng.run()
        for p, r, n in zip(prompts, rids, budgets):
            tiny.check(p, out[r], n)
            mine = [(t, d) for rid, t, d in seen if rid == r]
            assert mine == [(t, False) for t in out[r]] + [(None, True)]
        assert _counter(eng, "serving_decode_overlapped") > 0
        assert eng._flying is None and not eng.has_work()

    def test_end_by_budget_dispatches_no_extra_row(self, tiny):
        """(b) an end by ``max_new_tokens`` is a count: the row is not
        dispatched behind its own last step."""
        eng = tiny.engine()
        prompts = tiny.prompts((6, 11))
        budgets = (7, 4)
        rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        out = eng.run()
        for p, r, n in zip(prompts, rids, budgets):
            tiny.check(p, out[r], n)
        # the prefill gives a request's first token, a decode row each
        # of the others
        assert _counter(eng, "serving_decode_rows") == \
            sum(n - 1 for n in budgets)

    def test_end_by_eos_costs_one_dropped_row(self, tiny):
        """(b) an end by EOS is a value, seen one step late: the row
        rode exactly one more step, whose token is never emitted."""
        # a prompt whose greedy run has, past the prefill's token, one
        # that did not occur before it: the request ends there, by value
        eng = tiny.engine()
        runs = [(p, eng.submit(p, 8))
                for p in tiny.prompts((7,) * 12, seed=9)]
        out = eng.run()
        prompt, free, at = next(
            (p, out[r], i) for p, r in runs for i in range(1, 7)
            if out[r][i] not in out[r][:i])
        tiny.check(prompt, free, 8)
        eng = tiny.engine()
        seen = []
        rid = eng.submit(prompt, 8, eos_token_id=free[at],
                         on_token=_stream(seen))
        out = eng.run()
        assert out[rid] == free[:at + 1]
        assert [t for _r, t, d in seen if not d] == free[:at + 1]
        assert seen[-1] == (rid, None, True)
        assert _counter(eng, "serving_decode_rows") == at + 1
        assert eng.pool.free_page_count() == eng.pool.num_pages - 1

    def test_migration_reads_the_step_in_flight_first(self, tiny):
        """(c) the ladder compacts rows into low slots only after the
        step in flight is read."""
        eng = tiny.engine()
        prompts = tiny.prompts((5, 19, 8, 11))
        budgets = (2, 2, 2, 12)
        rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        out = eng.run()
        for p, r, n in zip(prompts, rids, budgets):
            tiny.check(p, out[r], n)
        assert eng.bucket_migrations >= 2
        assert _counter(eng, "serving_decode_settles", reason="migrate") >= 1

    def test_preemption_reads_the_step_in_flight_first(self, tiny):
        """(c) a victim is unseated with ALL its tokens, the one in
        flight too, and replays from them."""
        eng = tiny.engine(max_batch=2, bucket_ladder=(2,))
        eng.preempt_enabled, eng.preempt_horizon = True, 3600.0
        prompts = tiny.prompts((10, 9, 6))
        long_rids = [eng.submit(p, 14) for p in prompts[:2]]
        _step_until_flying(eng, long_rids[1], 3)
        held = [len(eng.poll(r)["tokens"]) for r in long_rids]
        tight = eng.submit(prompts[2], 3, deadline=600.0)
        out = eng.run()
        assert eng.preemptions == 1
        assert _counter(eng, "serving_decode_settles", reason="preempt") == 1
        for p, r in zip(prompts, long_rids + [tight]):
            tiny.check(p, out[r], 3 if r == tight else 14)
        # the victim replayed what it held PLUS the token that was in
        # flight when it was unseated: nothing was decoded twice
        assert _counter(eng, "serving_preempted_tokens_replayed") \
            in (held[0] + 1, held[1] + 1)

    def test_deadline_expiry_keeps_the_token_in_flight(self, tiny):
        """(c) a seated request that times out ends with every token
        that was dispatched for it."""
        import time
        eng = tiny.engine()
        prompt = tiny.prompts((9,))[0]
        rid = eng.submit(prompt, 20)
        _step_until_flying(eng, rid, 3)
        req = next(r for r in eng._slots if r is not None)
        held = len(req.tokens)
        assert req.in_flight == 1
        req.deadline = time.perf_counter() - 1.0
        eng.step()
        assert eng.status(rid) == "TIMEOUT"
        partial = eng.results()[rid]
        assert len(partial) == held + 1
        tiny.check(prompt, partial)
        assert _counter(eng, "serving_decode_settles", reason="deadline") == 1
        assert not eng.has_work()

    def test_dispatch_fault_with_a_step_in_flight_replays(self, tiny):
        """(d) the step in flight is dropped with the pools it wrote;
        replay from the tokens the host has is bit-identical."""
        prompts = tiny.prompts((5, 19, 8))
        budgets = (9, 7, 8)
        with fault_spec("decode_dispatch:every=4:times=2"):
            eng = tiny.engine()
            rids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
            out = eng.run()
        for p, r, n in zip(prompts, rids, budgets):
            tiny.check(p, out[r], n)
            assert eng.status(r) == "OK"
        assert _counter(eng, "serving_decode_settles", reason="recovery") >= 1
        # every decode step that went out was read late, read first or
        # dropped; the two that faulted before their dispatch count as
        # steps (and as overlapped, where one was in flight) and went
        # nowhere
        steps = _counter(eng, "serving_decode_steps")
        assert steps - 2 <= _counter(eng, "serving_decode_overlapped") \
            + _counter(eng, "serving_decode_settles") <= steps

    def test_the_step_in_flight_is_work(self, tiny):
        """(e) ``has_work`` / ``take_results`` / ``run`` when all that
        is left is a step whose tokens are unread."""
        eng = tiny.engine()
        prompt = tiny.prompts((6,))[0]
        rid = eng.submit(prompt, 3)
        eng.step()                      # prefill + the 2nd token's step
        eng.step()                      # the 3rd token's step; 2nd read
        req = next(r for r in eng._slots if r is not None)
        assert len(req.tokens) == 2 and req.in_flight == 1
        assert eng._flying is not None
        rows = _counter(eng, "serving_decode_rows")
        assert eng.has_work()
        assert eng.take_results() == {} and eng.status(rid) == "PENDING"
        out = eng.run()                 # reads it; dispatches nothing
        tiny.check(prompt, out[rid], 3)
        assert _counter(eng, "serving_decode_rows") == rows == 2
        assert not eng.has_work() and eng._flying is None

    def test_staged_inputs_are_copies(self, tiny):
        """(f) the host advances its cursors as soon as a step is
        dispatched; what it staged for that step is its own memory and
        keeps the values the program must read."""
        eng = tiny.engine()
        prompt = tiny.prompts((6,))[0]
        rid = eng.submit(prompt, 6)
        staged = []
        stage = eng._caches.decode_inputs
        eng._caches.decode_inputs = \
            lambda b, live: (staged.append(stage(b, live)), staged[-1])[1]
        eng.step()
        eng.step()
        for host in staged:
            for arr in host:
                assert not np.shares_memory(arr, eng.pool.seq_lens)
                assert not np.shares_memory(arr, eng.pool.block_tables)
        slot = next(r for r in eng._slots if r is not None).slot
        # step 1 staged the prefill's cursor, step 2 the cursor after
        # one decode token; the pool's own is already one further
        assert [int(h[1][slot]) for h in staged] == [6, 7]
        assert int(eng.pool.seq_lens[slot]) == 8
        tiny.check(prompt, eng.run()[rid], 6)


class TestStepClock:
    def test_a_stalled_staging_leaves_one_record(self, tiny):
        """``CacheManager.decode_inputs`` sleeps 0.3 s once: that round
        is on record once, its largest phase the staging's inputs, with
        a thread CPU time that tells a sleep from work; and the step
        clock's counters hold every round."""
        import time

        # a first engine builds the programs: the second's rounds are
        # then all short, and a compile is no part of any mean
        prompt = tiny.prompts((6,))[0]
        warm = tiny.engine(bucket_ladder=(4,))
        warm.submit(prompt, 12)
        warm.run()

        eng = tiny.engine(bucket_ladder=(4,))
        rid = eng.submit(prompt, 12)
        stage, calls = eng._caches.decode_inputs, []

        def stalled(b, live):
            calls.append(b)
            if len(calls) == 6:
                time.sleep(0.3)
            return stage(b, live)

        eng._caches.decode_inputs = stalled
        t_start, rounds = time.time(), 0
        while eng.has_work():
            eng.step()
            rounds += 1
        tiny.check(prompt, eng.take_results()[rid], 12)
        assert len(calls) >= 6

        slow = [r for r in obs.tracer().slow_steps()
                if r["wall_time"] >= t_start and max(
                    r["phases"], key=r["phases"].get)
                == "engine.decode.stage.inputs"]
        (rec,) = slow
        assert rec["kind"] == "serving" and 0.3 <= rec["length_s"] < 1.0
        assert rec["phases"]["engine.decode.stage.inputs"] >= 0.3
        assert sum(rec["phases"].values()) + rec["outside_s"] == \
            pytest.approx(rec["length_s"])
        assert rec["thread_cpu_s"] < 0.1        # asleep, not working
        assert rec["builds"] == 0 and rec["gc_s"] < 0.1
        assert _counter(eng, "serving_slow_steps") >= 1
        assert _counter(eng, "serving_slow_step_seconds") >= \
            rec["length_s"] - rec["mean_s"]

        assert _counter(eng, "serving_steps") == rounds
        phases = [_counter(eng, name) for name in (
            "serving_decode_dispatch_seconds",
            "serving_decode_stage_inputs_seconds",
            "serving_decode_stage_put_seconds",
            "serving_decode_stage_caches_seconds",
            "serving_wait_seconds")]
        assert all(v > 0 for v in phases)
        assert phases[1] >= 0.3
        assert _counter(eng, "serving_step_seconds") >= sum(phases)

    def test_a_steady_engine_records_nothing(self, tiny):
        import time

        prompt = tiny.prompts((6,))[0]
        warm = tiny.engine(bucket_ladder=(4,))
        warm.submit(prompt, 8)
        warm.run()
        eng = tiny.engine(bucket_ladder=(4,))
        eng.submit(prompt, 8)
        t_start = time.time()
        eng.run()
        # a round of a tiny model that takes a tenth of a second is the
        # machine's doing (a loaded test host); it would be on record
        assert _counter(eng, "serving_slow_steps") == len(
            [r for r in obs.tracer().slow_steps()
             if r["wall_time"] >= t_start])
        assert _counter(eng, "serving_steps") > 0
