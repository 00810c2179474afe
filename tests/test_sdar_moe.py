"""SDAR-MoE (``models/sdar_moe.py``): the sparse-expert decoder that
generates by diffusion over blocks, against the plain reference of the
benchmark (``benchmark/refs/sdar-30b-a3b-chat.py``: float32, ``logits``
under the block-causal mask and the published ``reveal``) at a tiny size
on the CPU — the full forward, the block-causal predicate of the chunk
path against a dense mask, the block step's kernels, and the serving
engine: prompts with every remainder, rows in different phases in one
step, budgets that are no multiple of the block, a request finishing
beside its neighbours, preemption and replay mid-block, the ladder,
recovery, handoff, the prefix cache, the refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib.system import load_reference
from paddle_tpu import flags
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM, SDARMoEConfig,
                               SDARMoEForCausalLM)
from paddle_tpu.testing import faults

REF = load_reference("sdar-30b-a3b-chat")
B = 4


@pytest.fixture(scope="module")
def tiny():
    """Tiny, with weights large enough that a random model does not
    repeat one token whatever it has seen."""
    paddle.seed(32)
    cfg = SDARMoEConfig.tiny(initializer_range=0.35)
    model = SDARMoEForCausalLM(cfg)
    model.eval()
    return cfg, model, dict(model.raw_state()[0]), dataclasses.asdict(cfg)


_JITTED = {}


def ref_logits(tiny, ids, mask=None):
    """The reference's logits of ``ids``. Under the block-causal mask a
    position sees nothing past its block, so the sequence is padded to
    ONE length and the reference compiled once."""
    _, _, weights, md = tiny
    if mask is not None or len(ids) % B:
        return np.asarray(REF.logits(weights, jnp.asarray(ids, jnp.int32),
                                     md, mask=mask))
    if "fn" not in _JITTED:
        _JITTED["fn"] = jax.jit(lambda w, x: REF.logits(w, x, md))
    padded = np.zeros((64,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_JITTED["fn"](weights, jnp.asarray(padded)))[:len(ids)]


def ref_generate(tiny, prompt, n_new):
    """The published generation by the reference alone: whole blocks of
    the prompt are context, its remainder the known part of the first
    block; per block, denoising forwards reveal one position each, and
    the finished block joins the context."""
    cfg = tiny[0]
    prompt = list(int(t) for t in prompt)
    whole = len(prompt) - len(prompt) % B
    seq, known, out = prompt[:whole], prompt[whole:], []
    while len(out) < n_new:
        block = known + [cfg.mask_token_id] * (B - len(known))
        while cfg.mask_token_id in block:
            lg = ref_logits(tiny, seq + block)[-B:]
            pos, tok, _, _ = REF.reveal(
                jnp.asarray(lg), jnp.asarray(block) == cfg.mask_token_id)
            block[int(pos)] = int(tok)
        seq += block
        out += block[len(known):]
        known = []
    return out[:n_new]


def prompts_of(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    # the mask token's id is never a prompt token
    return [rng.integers(0, cfg.mask_token_id, (n,)).astype(np.int32)
            for n in lens]


def make_engine(model, **kw):
    kw = {"max_batch": 4, "page_size": 8, "max_seq_len": 64,
          "prefill_chunk": 16, "bucket_ladder": (1, 2, 4), **kw}
    return ServingEngine(model, **kw)


# remainders 0-3, one shorter than a block, three chunked (one with a
# padded final chunk)
LENS = [8, 9, 22, 3, 35, 40, 17]
BUDGETS = [8, 7, 5, 9, 6, 10, 3]


@pytest.fixture(scope="module")
def prompts(tiny):
    return prompts_of(tiny[0], LENS, seed=4)


@pytest.fixture(scope="module")
def wanted(tiny, prompts):
    return [ref_generate(tiny, p, n) for p, n in zip(prompts, BUDGETS)]


# ------------------------------------------------------------ the model
def test_forward_matches_reference(tiny):
    _, model, _, _ = tiny
    ids = prompts_of(tiny[0], [19], seed=1)[0]
    ids[[5, 6, 18]] = tiny[0].mask_token_id
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=2e-4,
                               rtol=2e-4)


def test_reference_tells_block_causal_from_causal(tiny):
    """A causal mask in place of the block-causal one moves the logits
    by far more than the comparison's limit."""
    ids = prompts_of(tiny[0], [16], seed=2)[0]
    causal = jnp.tril(jnp.ones((16, 16), bool))
    a, b = ref_logits(tiny, ids), ref_logits(tiny, ids, mask=causal)
    gap = np.abs(a - b).max(axis=-1)
    limit = REF.TIE_ATOL + REF.TIE_RTOL * np.abs(a).max()
    assert (gap > 3 * limit).all()


def test_param_count_and_cache_spec_at_published_widths():
    cfg = SDARMoEConfig(num_hidden_layers=6)
    with paddle.LazyGuard():
        model = SDARMoEForCausalLM(cfg)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    layer = (128 * 3 * 2048 * 768 + 2 * 2048 * 128 * (32 + 4)
             + 2048 * 128 + 2 * 2048 + 2 * 128)
    assert n == 6 * layer + 2 * 151936 * 2048 + 2048 == 4_361_055_744
    assert model.cache_spec() == [(4, 128)] * 6
    assert model.block_spec() == dict(block_length=4, mask_token_id=151669)
    assert model.expert_counts_width() == 129
    with pytest.raises(ValueError, match="power of two"):
        SDARMoEConfig(block_length=3)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        SDARMoEConfig(mask_token_id=151936)


def test_generate_refuses_loudly(tiny):
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        tiny[1].generate(paddle.to_tensor(np.zeros((1, 4), np.int32)))


# ---------------------------------------------------------- the kernels
@pytest.mark.parametrize("block", [1, 4, 8])
@pytest.mark.parametrize("attend", [pa.paged_chunk_attention_xla,
                                    pa.paged_chunk_attention],
                         ids=["xla", "pallas_interpret"])
def test_chunk_block_causal_predicate_against_dense_mask(block, attend):
    """The chunk path's predicate against a dense mask: a chunk of 16 at
    cursor 8 over a paged pool, block-causal at 1 (causal), 4 and 8."""
    hkv, rep, d, page, s, start = 2, 2, 16, 8, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(block), 3)
    q = jax.random.normal(ks[0], (1, s, hkv * rep, d), jnp.float32)
    k = jax.random.normal(ks[1], (hkv, 6, page, d), jnp.float32)
    v = jax.random.normal(ks[2], (hkv, 6, page, d), jnp.float32)
    bt = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = attend(q, k, v, bt, jnp.asarray([start], jnp.int32),
                     block=block)
        kk = k[:, bt[0]].reshape(hkv, -1, d)            # (hkv, T, d)
        vv = v[:, bt[0]].reshape(hkv, -1, d)
        qpos = start + jnp.arange(s)
        seen = jnp.arange(kk.shape[1])[None] <= (qpos[:, None] | (block - 1))
        qg = q[0].reshape(s, hkv, rep, d)
        sc = jnp.einsum("qgrd,gkd->grqk", qg, kk) / np.sqrt(d)
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        want = jnp.einsum("grqk,gkd->qgrd", pr, vv).reshape(s, -1, d)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)


def test_block_write_lands_on_one_page():
    """The block step's write on the TPU path (the page-write kernel in
    interpret mode) against the scatter."""
    hkv, d, page = 2, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    kp = jax.random.normal(ks[0], (hkv, 5, page, d), jnp.float32)
    vp = jax.random.normal(ks[1], (hkv, 5, page, d), jnp.float32)
    kn = jax.random.normal(ks[2], (3, B, hkv, d), jnp.float32)
    vn = jax.random.normal(ks[3], (3, B, hkv, d), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 0], [4, 0]], jnp.int32)
    st = jnp.asarray([12, 0, 4], jnp.int32)
    want = pa.write_paged_prompt_at_xla(kp, vp, kn, vn, bt, st)
    got = pa._block_write(kp, vp, kn, vn, bt, st, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ----------------------------------------------------------- the engine
def test_engine_mixed_batch_equals_reference(tiny, prompts, wanted):
    """Seven requests through four slots: every prompt remainder, rows
    in different phases in one step (they seat at different steps and
    the first block of a remainder r takes 4 - r denoising forwards),
    budgets that are no multiple of 4, requests finishing while their
    neighbours continue."""
    _, model, _, _ = tiny
    eng = make_engine(model)
    seen = {}
    rids = [eng.submit(p, n, on_token=lambda r, t, d: seen.setdefault(
        r, []).append((t, d))) for p, n in zip(prompts, BUDGETS)]
    phases = set()
    while eng.has_work():
        eng.step()
        live = [r for r in eng._slots
                if r is not None and r.prefill_pos is None]
        phases.add(frozenset(r.masks for r in live))
    assert set(eng.statuses().values()) == {"OK"}
    out = eng.take_results()
    for r, want, n in zip(rids, wanted, BUDGETS):
        assert out[r] == want and len(want) == n
        assert [t for t, _ in seen[r]] == want + [None]
    assert max(len(p) for p in phases) >= 3      # mixed phases in a step
    assert len({t for r in rids for t in out[r]}) > 8
    assert eng.chunk_dispatches == 2 + 2 + 3   # whole blocks of 22, 35, 40
    assert eng.pool.free_page_count() == eng.pool.num_pages - 1


def test_engine_records_every_forward(tiny, prompts, wanted):
    """``record_blocks``: a row's forwards with the block before and
    after and the log-confidences the step chose by; each denoising
    forward reveals exactly what the reference's ``reveal`` picks from
    the reference's logits of the same state."""
    cfg, model, _, _ = tiny
    eng = make_engine(model)
    rid = eng.submit(prompts[1], BUDGETS[1])         # remainder 1
    eng.record_blocks([rid])
    out = eng.run()
    recs = eng.block_records()[rid]
    assert out[rid] == wanted[1]
    # blocks of 3 + 4 new tokens: (3 + 1) + (4 + 1) forwards
    assert [r["commit"] for r in recs] == [False] * 3 + [True] \
        + [False] * 4 + [True]
    seq = list(prompts[1][:8])
    for r in recs:
        assert r["cursor"] == len(seq)
        masked = r["before"] == cfg.mask_token_id
        if r["commit"]:
            assert not masked.any() and (r["after"] == r["before"]).all()
            seq += list(r["after"])
            continue
        lg = ref_logits(tiny, seq + list(r["before"]))[-B:]
        pos, tok, log_conf, _ = REF.reveal(jnp.asarray(lg),
                                           jnp.asarray(masked))
        changed = np.flatnonzero(r["after"] != r["before"])
        assert changed.tolist() == [int(pos)]
        assert r["after"][int(pos)] == int(tok)
        # the log-confidences the step chose by, every position's
        np.testing.assert_allclose(r["log_conf"], log_conf, rtol=0,
                                   atol=2e-4)


def test_engine_ladder_shrink_moves_blocks(tiny, prompts, wanted):
    _, model, _, _ = tiny
    prior = flags.get_flag("serving_bucket_patience")
    flags.set_flags({"serving_bucket_patience": 1})
    try:
        eng = make_engine(model)
    finally:
        flags.set_flags({"serving_bucket_patience": prior})
    order = [6, 2, 0, 5]                 # budgets 3, 5, 8, 10
    rids = [eng.submit(prompts[i], BUDGETS[i]) for i in order]
    out = eng.run()
    assert eng.bucket_migrations >= 2
    for i, r in zip(order, rids):
        assert out[r] == wanted[i]


def test_engine_preemption_replays_a_row_mid_block(tiny, prompts, wanted):
    _, model, _, _ = tiny
    eng = make_engine(model)
    rids = [eng.submit(prompts[i], BUDGETS[i]) for i in (0, 1, 5)]
    victim = None
    while victim is None:
        eng.step()
        victim = next((r for r in eng._slots if r is not None
                       and r.rid == rids[1] and r.tokens
                       and 0 < r.masks < B), None)
    eng._settle("preempt")               # as _preempt_for reads first
    eng._unseat(victim)
    out = eng.run()
    for i, r in zip((0, 1, 5), rids):
        assert out[r] == wanted[i]
    assert eng.preemptions == 1


def test_engine_replay_recovery(tiny, prompts, wanted):
    _, model, _, _ = tiny
    with faults.armed("decode_dispatch:every=7:times=2",
                      serving_retry_backoff=0.001):
        eng = make_engine(model)
        rids = [eng.submit(prompts[i], BUDGETS[i]) for i in range(4)]
        out = eng.run()
    for i, r in enumerate(rids):
        assert out[r] == wanted[i] and eng.status(r) == "OK"


def test_engine_eos_ends_a_request_inside_a_block(tiny, prompts, wanted):
    _, model, _, _ = tiny
    eng = make_engine(model)
    want = wanted[5]                      # 10 tokens over three blocks
    eos = want[5]
    cut = want.index(eos) + 1
    rid = eng.submit(prompts[5], BUDGETS[5], eos_token_id=eos)
    other = eng.submit(prompts[0], BUDGETS[0])
    out = eng.run()
    assert out[rid] == want[:cut] and out[other] == wanted[0]


def test_engine_harvest_adopt_restarts_the_block(tiny, prompts, wanted):
    _, model, _, _ = tiny
    a, b = make_engine(model), make_engine(model)
    rid = a.submit(prompts[5], BUDGETS[5])
    while not a.poll(rid)["tokens"]:
        a.step()
    a.step()
    bundle = a.harvest_request(rid)
    done = len(bundle["request"].tokens)
    new = b.adopt_request(bundle)
    chunks = b.chunk_dispatches
    out = b.run()
    assert out[new] == wanted[5] and 0 < done < BUDGETS[5]
    assert b.chunk_dispatches == chunks           # no re-prefill


def test_engine_prefix_cache_shares_whole_pages(tiny, wanted):
    cfg, model, _, _ = tiny
    head = prompts_of(cfg, [16], seed=9)[0]
    tails = prompts_of(cfg, [0, 5, 18], seed=10)
    full = [np.concatenate([head, t]) for t in tails]
    want = [ref_generate(tiny, p, 6) for p in full]
    eng = make_engine(model, prefix_cache=True)
    first = eng.submit(full[1], 6)
    assert eng.run()[first] == want[1]
    rids = [eng.submit(p, 6) for p in full]
    out = eng.run()
    for r, w in zip(rids, want):
        assert out[r] == w


@pytest.mark.parametrize("kwargs,reason", [
    (dict(draft_model="llama"), "no verify program"),
    (dict(prefix_cache=True, prefill_chunk=0), "needs chunked prefill"),
    (dict(tp_degree=2), "no sharded program"),
    (dict(page_size=6), "straddle a page"),
], ids=["draft_model", "prefix_cache", "tp_degree", "page_size"])
def test_engine_refuses(tiny, kwargs, reason):
    _, model, _, _ = tiny
    if kwargs.get("draft_model") == "llama":
        kwargs = dict(draft_model=LlamaForCausalLM(LlamaConfig.tiny()))
    with pytest.raises(ValueError, match=reason):
        make_engine(model, **kwargs)
