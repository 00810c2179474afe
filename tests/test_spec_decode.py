"""Speculative decoding inside the ServingEngine (r16).

The contract under test: passing ``draft_model=`` to ServingEngine
changes the SCHEDULE, never the tokens. Greedy outputs stay
bit-identical to the plain engine (and the solo decode) on both the
fused (Llama) and generic (GPT) paths, through chunked prefill, bucket
migration, and injected draft/verify faults; temperature>0 requests
sample the TARGET's law via rejection sampling; γ adapts per request
to the observed accept rate; and steady state swaps between compiled
per-rung programs with zero retraces.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.generation.program_cache import decode_program_cache
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.testing import faults

pytestmark = pytest.mark.spec


def solo(model, prompt, n, eos=None):
    return model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=n,
                          do_sample=False, eos_token_id=eos,
                          return_full_sequence=False).numpy()[0].tolist()


def gpt_pair(seed_t=7, seed_d=99):
    paddle.seed(seed_t)
    cfg = GPTConfig.tiny()
    target = GPTForCausalLM(cfg)
    paddle.seed(seed_d)
    draft = GPTForCausalLM(cfg)
    return cfg, target, draft


def zeros_draft(cfg):
    """A draft that NEVER agrees: all-zero weights make every logits
    row constant, so the draft proposes token 0 forever — rounds see
    accepted=0 and the γ rung must fall. (A merely different random
    init is not enough: untrained nets share the copy-the-last-token
    attractor and agree far too often.)"""
    paddle.seed(0)
    draft = GPTForCausalLM(cfg)
    sd = {k: paddle.to_tensor(np.zeros_like(v.numpy()))
          for k, v in draft.state_dict().items()}
    draft.set_state_dict(sd)
    return draft


def run_engine(model, prompts, max_new, draft=None, **kw):
    eng = ServingEngine(model, max_batch=kw.pop("max_batch", 2),
                        page_size=8,
                        max_seq_len=kw.pop("max_seq_len", 64),
                        draft_model=draft, **kw)
    rids = [eng.submit(p, max_new) for p in prompts]
    out = eng.run(max_wall=300.0)
    return eng, [out[r] for r in rids]


# tier-1 keeps one representative per contract (generic parity via the
# rejection test, fused parity, pricing, sampling determinism + law-
# by-replay, verify-fault replay, migration composition); the heavier
# twins ride -m slow like the serving_load full sweep
class TestGreedyParity:
    @pytest.mark.slow
    def test_generic_gpt_path(self):
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (6, 4, 9)]
        refs = [solo(target, p, 12) for p in prompts]
        _, plain = run_engine(target, prompts, 12)
        eng, spec = run_engine(target, prompts, 12, draft=draft)
        assert spec == plain == refs
        assert eng.spec_rounds > 0
        assert "generic" in eng.spec_draft_key.extra

    def test_fused_llama_path(self):
        paddle.seed(11)
        cfg = LlamaConfig.tiny()
        target = LlamaForCausalLM(cfg)
        paddle.seed(12)
        draft = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (5, 8)]
        refs = [solo(target, p, 10) for p in prompts]
        eng, spec = run_engine(target, prompts, 10, draft=draft)
        assert spec == refs
        assert eng.spec_rounds > 0
        assert "fused" in eng.spec_draft_key.extra

    def test_lossless_under_real_rejections(self):
        """A genuinely divergent (half-width, 1-layer) draft: rounds
        reject, output does not move."""
        paddle.seed(0)
        cfg = GPTConfig.tiny()
        target = GPTForCausalLM(cfg)
        paddle.seed(1)
        draft = GPTForCausalLM(GPTConfig(
            vocab_size=cfg.vocab_size, hidden_size=32,
            num_hidden_layers=1, num_attention_heads=2,
            max_position_embeddings=128))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)]
        refs = [solo(target, p, 16) for p in prompts]
        eng, spec = run_engine(target, prompts, 16, draft=draft)
        assert spec == refs
        assert eng.spec_tokens_rejected > 0

    def test_eos_inside_burst_truncates(self):
        """A round's token burst must stop at EOS exactly where the
        plain engine would have: force EOS = the token the target
        repeats, so it lands mid-burst."""
        cfg, target, draft = gpt_pair(7, 7)     # identical -> full bursts
        rng = np.random.default_rng(4)
        # find a prompt whose greedy decode FIRST hits some token at an
        # interior index (tiny random models mostly repeat one token,
        # where any eos would fire on the very first emission)
        p = eos = None
        for _ in range(40):
            cand = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
            ref = solo(target, cand, 12)
            for i in range(2, len(ref) - 1):
                if ref[i] not in ref[:i]:
                    p, eos = cand, ref[i]
                    break
            if p is not None:
                break
        assert p is not None, "no prompt with interior eos candidate"
        expect = ref[:ref.index(eos) + 1]   # engine stops at FIRST hit
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64, draft_model=draft)
        rid = eng.submit(p, 12, eos_token_id=eos)
        out = eng.run()
        assert out[rid] == expect
        assert eng.spec_rounds > 0


class TestAdaptiveGamma:
    @pytest.mark.slow
    def test_rung_climbs_on_agreeing_draft(self):
        cfg, target, draft = gpt_pair(7, 7)     # identical weights
        p = np.array([3, 5, 7, 11, 2, 9], np.int32)
        prev = flags.get_flags(("serving_spec_max_slots",))
        flags.set_flags({"serving_spec_max_slots": 16})
        try:
            eng = ServingEngine(target, max_batch=4, page_size=8,
                                max_seq_len=96, draft_model=draft)
            eng.submit(p, 48)
            gmax = 0
            while eng.has_work():
                eng.step()
                gmax = max(gmax, eng.spec_last_gamma)
        finally:
            flags.set_flags(prev)
        assert gmax >= 8                        # climbed to the top rung
        assert eng.spec_tokens_rejected == 0

    def test_rung_falls_on_disagreeing_draft(self):
        cfg, target, _ = gpt_pair()
        draft = zeros_draft(cfg)
        p = np.array([3, 5, 7, 11, 2, 9], np.int32)
        prev = flags.get_flags(("serving_spec_max_slots",))
        flags.set_flags({"serving_spec_max_slots": 16})
        try:
            eng = ServingEngine(target, max_batch=4, page_size=8,
                                max_seq_len=96, draft_model=draft)
            eng.submit(p, 32)
            gammas = []
            while eng.has_work():
                before = eng.spec_rounds
                eng.step()
                if eng.spec_rounds > before:
                    gammas.append(eng.spec_last_gamma)
        finally:
            flags.set_flags(prev)
        # never grows past the default rung, and the EMA drags the
        # steady state down to the smallest rung
        assert max(gammas) <= 4
        assert gammas[-1] == 2
        assert eng.spec_tokens_rejected > eng.spec_tokens_accepted

    def test_gamma_prices_out_as_occupancy_rises(self):
        """The γ+1 slot bill: a full batch prices speculation out and
        the step falls back to plain batched decode — while outputs
        stay bit-identical to the plain engine throughout."""
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                   for _ in range(4)]
        _, plain = run_engine(target, prompts, 8, max_batch=4)
        eng, spec = run_engine(target, prompts, 8, draft=draft,
                               max_batch=4)
        assert spec == plain
        # 4 rows x (2+1) slots > max(max_batch, 3) = 4: the saturated
        # phase ran plain, so speculation served FEWER than all tokens
        total = sum(len(t) for t in spec)
        served = eng.spec_tokens_accepted + eng.spec_rounds
        assert 0 < served < total


class TestSampling:
    def test_sampled_requires_draft(self):
        cfg, target, _ = gpt_pair()
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64)
        with pytest.raises(ValueError):
            eng.submit(np.array([1, 2, 3], np.int32), 4, temperature=1.0)

    def test_sampled_deterministic_per_seed(self):
        cfg, target, draft = gpt_pair()
        p = np.array([3, 5, 7, 11], np.int32)

        def one(seed):
            eng = ServingEngine(target, max_batch=2, page_size=8,
                                max_seq_len=64, draft_model=draft)
            rid = eng.submit(p, 12, temperature=0.9, top_k=16,
                             top_p=0.95, seed=seed)
            return eng.run()[rid]

        a, b, c = one(5), one(5), one(6)
        assert a == b
        assert a != c       # astronomically unlikely to collide

    @pytest.mark.slow
    def test_mixed_batch_keeps_greedy_parity(self):
        """A sampled row forces the whole step onto speculation; the
        greedy row sharing the batch must not move."""
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(6)
        pg = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        ps = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        ref = solo(target, pg, 10)
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64, draft_model=draft)
        rg = eng.submit(pg, 10)
        rs = eng.submit(ps, 10, temperature=1.0, top_k=8, seed=1)
        out = eng.run()
        assert out[rg] == ref
        assert len(out[rs]) == 10

    @pytest.mark.slow
    def test_rejection_sampling_matches_target_law(self):
        """The speculative-sampling identity: the emitted distribution
        is the TARGET's filtered softmax, whatever the draft proposes.
        ~400 single-token samples against the analytic law."""
        cfg, target, draft = gpt_pair()         # divergent draft
        p = np.array([3, 5, 7, 11, 2], np.int32)
        temp, top_k, n = 1.0, 4, 400
        # analytic filtered law of the next token
        logits = target(paddle.to_tensor(p[None])).numpy()[0, -1]
        lg = logits.astype(np.float64) / temp
        thresh = np.sort(lg)[-top_k]
        lg = np.where(lg >= thresh, lg, -np.inf)
        z = np.exp(lg - lg.max())
        expect = z / z.sum()
        counts = np.zeros(cfg.vocab_size)
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64, draft_model=draft)
        for seed in range(n):
            rid = eng.submit(p, 1, temperature=temp, top_k=top_k,
                             seed=seed)
            out = eng.run()
            counts[out[rid][0]] += 1
        tv = 0.5 * np.abs(counts / n - expect).sum()
        assert tv < 0.12, (tv, np.nonzero(counts)[0].tolist())


class TestSteadyState:
    def test_zero_steady_state_retrace(self):
        cfg, target, draft = gpt_pair()
        p = np.array([3, 5, 7, 11, 2, 9], np.int32)
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64, draft_model=draft)
        eng.submit(p, 12)
        eng.run()                           # warm every rung touched
        cache = decode_program_cache()
        t0 = sum(cache.stats()["traces"].values())
        import paddle_tpu.observability as obs
        fam0 = obs.snapshot()["metrics"].get("program_cache_traces")
        c0 = sum(s.get("value", 0) for s in fam0["series"]) if fam0 \
            else 0
        eng.submit(p, 12)
        eng.run()
        t1 = sum(cache.stats()["traces"].values())
        fam1 = obs.snapshot()["metrics"].get("program_cache_traces")
        c1 = sum(s.get("value", 0) for s in fam1["series"]) if fam1 \
            else 0
        assert t0 > 0
        assert t1 == t0                         # cache-level probe
        assert c1 == c0                         # telemetry-level probe

    def test_spec_telemetry_series(self):
        cfg, target, draft = gpt_pair()
        p = np.array([3, 5, 7, 11], np.int32)
        eng = ServingEngine(target, max_batch=2, page_size=8,
                            max_seq_len=64, draft_model=draft)
        eng.submit(p, 8)
        eng.run()
        import paddle_tpu.observability as obs
        snap = obs.snapshot()["metrics"]
        for name in ("serving_spec_rounds", "serving_spec_tokens_accepted",
                     "serving_spec_accept_rate", "serving_spec_gamma"):
            fam = snap.get(name)
            assert fam is not None, name
            assert all("replica" in s["labels"] for s in fam["series"])


class TestFaultReplay:
    def test_verify_fault_replay_parity(self):
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                   for _ in range(3)]
        refs = [solo(target, p, 10) for p in prompts]
        with faults.armed("spec_verify:every=2:times=2",
                          serving_retry_backoff=0.001):
            eng, out = run_engine(target, prompts, 10, draft=draft)
        assert out == refs
        assert all(k is not None for k in eng._draft_pool.k_pages)

    @pytest.mark.slow
    def test_draft_fault_replay_parity(self):
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                   for _ in range(3)]
        refs = [solo(target, p, 10) for p in prompts]
        with faults.armed("spec_draft:every=3:times=2",
                          serving_retry_backoff=0.001):
            eng, out = run_engine(target, prompts, 10, draft=draft)
        assert out == refs

    def test_sampled_fault_replay_deterministic(self):
        """Position-keyed uniforms: a replayed round redraws the SAME
        randomness, so sampled outputs survive injected faults."""
        cfg, target, draft = gpt_pair()
        p = np.array([3, 5, 7, 11, 2, 9], np.int32)

        def one(arm):
            eng = ServingEngine(target, max_batch=2, page_size=8,
                                max_seq_len=64, draft_model=draft)
            rid = eng.submit(p, 12, temperature=0.8, top_k=16, seed=5)
            return eng.run()[rid]

        clean = one(False)
        with faults.armed("spec_verify:every=2:times=3",
                          serving_retry_backoff=0.001):
            faulted = one(True)
        assert clean == faulted


class TestComposition:
    @pytest.mark.slow
    def test_chunked_prefill_composition(self):
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(9)
        long = rng.integers(0, cfg.vocab_size, (40,)).astype(np.int32)
        ref = solo(target, long, 12)
        prev = flags.get_flags(("serving_prefill_chunk",))
        flags.set_flags({"serving_prefill_chunk": 16})
        try:
            eng, out = run_engine(target, [long], 12, draft=draft,
                                  max_seq_len=128)
        finally:
            flags.set_flags(prev)
        assert out == [ref]
        assert eng.spec_rounds > 0

    def test_bucket_migration_composition(self):
        """Speculating requests survive a ladder migration: the draft
        pool's slot layout mirrors the target's move."""
        cfg, target, draft = gpt_pair()
        rng = np.random.default_rng(10)
        prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                   for _ in range(4)]
        refs = [solo(target, p, 8) for p in prompts]
        eng = ServingEngine(target, max_batch=4, page_size=8,
                            max_seq_len=64, bucket_ladder=(2, 4),
                            draft_model=draft)
        rids = [eng.submit(prompts[0], 8), eng.submit(prompts[1], 8)]
        eng.step(); eng.step(); eng.step()
        rids += [eng.submit(p, 8) for p in prompts[2:]]
        out = eng.run(max_wall=300.0)
        assert [out[r] for r in rids] == refs
        assert eng.bucket_migrations >= 1
        assert eng.spec_rounds > 0
