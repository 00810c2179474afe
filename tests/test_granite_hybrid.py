"""granite-4.0-h (``models/granite_hybrid.py``): Mamba-2 layers beside
attention layers, against the plain reference of the benchmark
(``benchmark/refs/granite-4.0-h-micro.py``: float32, the recurrence
position by position) at a tiny size on the CPU — the full forward, the
cache path, chunk-to-chunk carry and the padded final chunk, and the
serving engine's seams: slot migration, preemption, replay recovery,
harvest/adopt, the refusals, the counters."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib.system import load_reference
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.generation import cache_manager, serving
from paddle_tpu.generation.program_cache import clear_decode_program_cache
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.jit import functional_call
from paddle_tpu.kernels.paged_attention import (PagedChunkState,
                                                PagedDecodeState,
                                                PagedKVCache)
from paddle_tpu.kernels.recurrent_state import (RecurrentSpec,
                                                RecurrentStateCache,
                                                recurrent_layout)
from paddle_tpu.models import (GraniteHybridConfig, GraniteHybridForCausalLM,
                               LlamaConfig, LlamaForCausalLM)
from paddle_tpu.testing import faults

REF = load_reference("granite-4.0-h-micro")
ATOL = 1e-4


def tiny_config(**over):
    """Tiny, and with an embedding that does not drown the mixers (at
    the published multipliers a random model repeats its last token,
    whatever its state holds)."""
    return GraniteHybridConfig.tiny(embedding_multiplier=1.0,
                                    initializer_range=0.1, **over)


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(2804)
    cfg = tiny_config()
    model = GraniteHybridForCausalLM(cfg)
    model.eval()
    return cfg, model, dict(model.raw_state()[0]), dataclasses.asdict(cfg)


def ref_logits(tiny, ids):
    _, _, weights, md = tiny
    return np.asarray(REF.logits(weights, jnp.asarray(ids, jnp.int32), md))


def assert_greedy(tiny, prompt, tokens, n=None):
    """``tokens`` are the reference's greedy continuation of ``prompt``:
    each is the argmax of the reference's logits over everything before
    it (one reference pass over prompt + tokens, as the benchmark's own
    check makes it)."""
    tokens = list(tokens)
    assert n is None or len(tokens) == n
    logits = ref_logits(tiny, list(prompt) + tokens[:-1])
    want = logits[len(prompt) - 1:].argmax(-1).tolist()
    assert tokens == want


def prompts_of(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ------------------------------------------------------------- the model
def test_forward_matches_reference(tiny):
    cfg, model, _, _ = tiny
    ids = prompts_of(cfg, (21, 21))
    got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._value)
    for row, p in zip(got, ids):
        np.testing.assert_allclose(row, ref_logits(tiny, p), atol=ATOL)
    # the reference's tokens vary: the engine tests below can tell a
    # wrong state from a right one
    assert len(set(ref_logits(tiny, ids[0]).argmax(-1).tolist())) > 8


def test_param_count_and_cache_spec_at_published_widths():
    cfg = GraniteHybridConfig()
    with paddle.LazyGuard():
        model = GraniteHybridForCausalLM(cfg)
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    assert n == cfg.num_params() == 3_191_396_096
    spec = model.cache_spec()
    assert [i for i, e in enumerate(spec)
            if not isinstance(e, RecurrentSpec)] == [5, 15, 25, 35]
    assert spec[5] == (8, 64)
    assert spec[0] == RecurrentSpec((32, 128, 128), (3, 4352))
    assert recurrent_layout(spec).count(True) == 36
    llama = LlamaForCausalLM(LlamaConfig.tiny())
    assert recurrent_layout(llama.cache_spec()) is None


def test_prefill_then_decode_equals_forward(tiny):
    cfg, model, _, _ = tiny
    ids = np.stack(prompts_of(cfg, (21, 21), seed=1))
    full = np.asarray(model(paddle.to_tensor(ids))._value)
    caches = model.init_cache(2, 32)
    lg, caches = model.forward_with_cache(paddle.to_tensor(ids[:, :13]),
                                          caches, 0)
    got = [np.asarray(lg._value)]
    for t in range(13, 21):
        lg, caches = model.forward_with_cache(
            paddle.to_tensor(ids[:, t:t + 1]), caches, t)
        got.append(np.asarray(lg._value))
    np.testing.assert_allclose(np.concatenate(got, 1), full, atol=ATOL)


def test_generate_refuses_loudly(tiny):
    _, model, _, _ = tiny
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        model.generate(paddle.to_tensor(np.zeros((1, 4), np.int32)))


class _Caches:
    """A pool and a state store for one tiny model, and the engine's own
    way of handing them to ``forward_with_cache``."""

    def __init__(self, model, slots=3, page=8, max_len=64):
        spec = model.cache_spec()
        self.model = model
        pages = [e for e in spec if not isinstance(e, RecurrentSpec)]
        self.pool = PagedKVCache(
            num_layers=len(pages), num_pages=1 + slots * (max_len // page),
            page_size=page, num_kv_heads=pages[0][0], head_dim=pages[0][1],
            max_batch=slots, max_seq_len=max_len, dtype=jnp.float32,
            reserve_null_page=True)
        self.state = RecurrentStateCache(
            [e for e in spec if isinstance(e, RecurrentSpec)], slots,
            jnp.float32)
        for s in range(slots):
            self.pool.allocate(s, max_len)

    def run(self, ids, slot, start, cls, **recurrent):
        """One b=1 call over ``ids`` at row ``slot`` from position
        ``start``; returns the logits."""
        params, buffers = self.model.raw_state()
        pools = (self.pool.take_pools(), self.state.take_arrays())
        bt = jnp.asarray(self.pool.block_tables[slot:slot + 1])
        sl = jnp.full((1,), start, jnp.int32)
        states = cache_manager.cache_entries(self.model, pools, cls, bt, sl,
                                             slot=jnp.int32(slot),
                                             **recurrent)
        logits, states = functional_call(
            self.model, params, jnp.asarray(ids[None]), states,
            jnp.int32(start), buffers=buffers, method="forward_with_cache")
        rec = [s for s in states if hasattr(s, "ssm")]
        self.state.install_arrays([(s.ssm, s.conv) for s in rec])
        self.pool.install_pools([(s.k_pages, s.v_pages) for s in states
                                 if not hasattr(s, "ssm")])
        return np.asarray(logits[0])


def test_chunk_to_chunk_carry(tiny):
    """Chunk k + 1 starts from what chunk k left in the slot: three
    chunks of 8 give the monolithic prefill's logits and state."""
    cfg, model, _, _ = tiny
    ids = prompts_of(cfg, (24,), seed=2)[0]
    mono, chunked = _Caches(model), _Caches(model)
    want = mono.run(ids, 1, 0, PagedDecodeState)
    got = np.concatenate([
        chunked.run(ids[i:i + 8], 1, i, PagedChunkState)
        for i in (0, 8, 16)])
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(want, ref_logits(tiny, ids), atol=ATOL)
    for a, b in zip(mono.state.export(1), chunked.state.export(1)):
        np.testing.assert_allclose(a, b, atol=ATOL)
    # the other rows were not touched
    assert all(not r.any() for r in chunked.state.export(0))


@pytest.mark.parametrize("real", [1, 2, 5, 8])
def test_padded_final_chunk_equals_monolithic(tiny, real):
    """A final chunk with ``real`` real positions and the rest pad
    leaves the state and the logits of the unpadded prompt — pad
    positions neither decay the state nor enter the convolution's
    window (``real`` under d_conv - 1 = 3 reaches back into the window
    the chunk before left)."""
    cfg, model, _, _ = tiny
    n = 8 + real
    ids = prompts_of(cfg, (n,), seed=3)[0]
    mono, chunked = _Caches(model), _Caches(model)
    want = mono.run(ids, 2, 0, PagedDecodeState)
    chunked.run(ids[:8], 2, 0, PagedChunkState, n_valid=jnp.int32(8))
    padded = np.zeros((8,), np.int32)
    padded[:real] = ids[8:]
    got = chunked.run(padded, 2, 8, PagedChunkState,
                      n_valid=jnp.int32(real))
    np.testing.assert_allclose(got[:real], want[8:], atol=ATOL)
    for a, b in zip(mono.state.export(2), chunked.state.export(2)):
        np.testing.assert_allclose(a, b, atol=ATOL)
    if real == 8:
        return
    # and WITHOUT the mask the pad does move the state: the test can see
    unmasked = _Caches(model)
    unmasked.run(ids[:8], 2, 0, PagedChunkState)
    unmasked.run(padded, 2, 8, PagedChunkState)
    assert max(np.abs(a - b).max() for a, b in zip(
        mono.state.export(2), unmasked.state.export(2))) > 1e-3


# ------------------------------------------------------------- the store
def test_state_store_rows():
    specs = [RecurrentSpec((2, 4, 8), (3, 5))] * 2
    store = RecurrentStateCache(specs, 4, jnp.float32)
    assert store.bytes_per_slot == 2 * (64 + 15) * 4
    assert store.nbytes == 4 * store.bytes_per_slot
    rows = [np.full(s, i + 1.0, np.float32)
            for i, s in enumerate([(2, 4, 8), (3, 5)] * 2)]
    store.import_(1, rows)
    store.move(1, 3)
    for got, want in zip(store.export(3), rows):
        np.testing.assert_array_equal(got, want)
    store.reset(1)
    assert all(not r.any() for r in store.export(1))
    assert all(r.any() for r in store.export(3))
    with pytest.raises(ValueError, match="shapes"):
        store.import_(0, rows[:1])
    pairs = store.take_arrays()
    assert store.detached and len(pairs) == 2
    with pytest.raises(RuntimeError, match="detached"):
        store.take_arrays()
    store.install_arrays(pairs)
    assert store.export(3)[0].dtype == np.float32


# ------------------------------------------------------------ the engine
LENS = (5, 19, 33, 8, 40, 11)


def make_engine(model, **kw):
    kw = {"max_batch": 4, "page_size": 8, "max_seq_len": 64,
          "prefill_chunk": 16, "bucket_ladder": (1, 2, 4), **kw}
    return ServingEngine(model, **kw)


@pytest.fixture(scope="module")
def prompts(tiny):
    """The prompts the engine tests share: mixed lengths, three of them
    chunked (33 and 40 end in a padded chunk of 1 and 8 real
    positions)."""
    return prompts_of(tiny[0], LENS, seed=4)


def test_engine_mixed_batch_equals_reference(tiny, prompts):
    _, model, _, _ = tiny
    eng = make_engine(model)
    assert len(eng.pool.k_pages) == 1          # pages for the ONE
    assert len(eng._state.specs) == 3          # attention layer only
    rids = [eng.submit(p, 6 + i) for i, p in enumerate(prompts)]
    out = eng.run()
    for i, (p, r) in enumerate(zip(prompts, rids)):
        assert_greedy(tiny, p, out[r], 6 + i)
    assert len({t for r in rids for t in out[r]}) > 8
    assert eng.chunk_dispatches >= 3 + 3 + 2


def test_engine_ladder_shrink_moves_state(tiny, prompts):
    """A shrink mid-generation compacts a decoding request into a low
    slot: its recurrent rows move with it."""
    _, model, _, _ = tiny
    prior = flags.get_flag("serving_bucket_patience")
    flags.set_flags({"serving_bucket_patience": 1})
    try:
        eng = make_engine(model)
    finally:
        flags.set_flags({"serving_bucket_patience": prior})
    moves = []
    move = eng._state.move
    eng._state.move = lambda s, d: (moves.append((s, d)), move(s, d))[1]
    # slots 0..3; the short requests finish first, the long one sits high
    budgets = [2, 2, 2, 12]
    rids = [eng.submit(p, n) for p, n in zip(prompts[:4], budgets)]
    out = eng.run()
    assert moves and all(s > d for s, d in moves)
    for p, r, n in zip(prompts, rids, budgets):
        assert_greedy(tiny, p, out[r], n)


def test_engine_preemption_replays_state(tiny, prompts):
    _, model, _, _ = tiny
    eng = make_engine(model)
    rids = [eng.submit(p, 6 + i) for i, p in enumerate(prompts[:3])]
    for _ in range(6):
        eng.step()
    victim = next(r for r in eng._slots
                  if r is not None and r.rid == rids[1])
    assert victim.tokens and victim.prefill_pos is None
    eng._unseat(victim)
    out = eng.run()
    for i, (p, r) in enumerate(zip(prompts, rids)):
        assert_greedy(tiny, p, out[r], 6 + i)
    assert eng.preemptions == 1


def test_engine_replay_recovery_rebuilds_state(tiny, prompts):
    _, model, _, _ = tiny
    with faults.armed("decode_dispatch:every=4:times=2",
                      serving_retry_backoff=0.001):
        eng = make_engine(model)
        rids = [eng.submit(p, 6 + i) for i, p in enumerate(prompts[:4])]
        out = eng.run()
    for i, (p, r) in enumerate(zip(prompts, rids)):
        assert_greedy(tiny, p, out[r], 6 + i)


def test_engine_harvest_adopt_hands_state_over(tiny, prompts):
    _, model, _, _ = tiny
    a, b = make_engine(model), make_engine(model)
    rid = a.submit(prompts[2], 8)               # 33 tokens: chunked
    while not a.poll(rid)["tokens"]:
        a.step()
    a.step()
    bundle = a.harvest_request(rid)
    assert bundle["v"] == serving.HANDOFF_SCHEMA_VERSION == 2
    assert len(bundle["state"]) == 2 * 3
    assert bundle["state"][0].dtype == np.float32
    assert all(isinstance(r, np.ndarray) for r in bundle["state"])
    done = len(bundle["request"].tokens)
    new = b.adopt_request(bundle)
    b_chunks = b.chunk_dispatches
    out = b.run()
    assert_greedy(tiny, prompts[2], out[new], 8)
    assert b.chunk_dispatches == b_chunks and 0 < done < 8   # no re-prefill
    # a model without recurrent layers refuses the bundle, and back
    llama = ServingEngine(LlamaForCausalLM(LlamaConfig.tiny()), max_batch=2,
                          page_size=8, max_seq_len=64)
    with pytest.raises(ValueError, match="recurrent state"):
        llama.adopt_request(dict(bundle, request=bundle["request"]))


@pytest.mark.parametrize("kwargs,reason", [
    (dict(prefix_cache=True), "snapshot of the recurrent state"),
    (dict(draft_model="self"), "rolled back"),
    (dict(tp_degree=2), "not sharded"),
], ids=["prefix_cache", "draft_model", "tp_degree"])
def test_engine_refuses(tiny, kwargs, reason):
    _, model, _, _ = tiny
    if kwargs.get("draft_model") == "self":
        kwargs = dict(draft_model=model)
    with pytest.raises(ValueError, match=reason):
        make_engine(model, **kwargs)


def test_engine_int8_kv_is_the_page_layers_only(tiny, prompts):
    from paddle_tpu.kernels.paged_attention import QuantizedPages
    _, model, _, _ = tiny
    eng = make_engine(model, kv_dtype="int8")
    assert isinstance(eng.pool.k_pages[0], QuantizedPages)
    rid = eng.submit(prompts[1], 4)
    out = eng.run()
    assert len(out[rid]) == 4 and eng.status(rid) in ("OK", "PENDING")
    assert all(a.dtype == jnp.float32 for a in eng._state._arrays[::2])


# ----------------------------------------------------------- telemetry
@pytest.fixture
def telemetry():
    """An empty registry and ring, before and after."""
    def fresh():
        obs.registry().clear()
        obs.tracer().clear()
        clear_decode_program_cache()
    fresh()
    yield
    fresh()


def _value(name):
    fam = obs.registry().snapshot()["metrics"][name]
    return sum(s["value"] for s in fam["series"])


@pytest.mark.telemetry
def test_state_counters_and_gauges(tiny, prompts, telemetry):
    _, model, _, _ = tiny
    prior = flags.get_flag("serving_bucket_patience")
    flags.set_flags({"serving_bucket_patience": 1})
    try:
        eng = make_engine(model)
    finally:
        flags.set_flags({"serving_bucket_patience": prior})
    rids = [eng.submit(p, n) for p, n in zip(prompts[:4], (2, 2, 2, 12))]
    eng.step()
    assert _value("serving_state_bytes") == eng._state.nbytes \
        == 4 * eng._state.bytes_per_slot
    assert _value("serving_state_slots_live") >= 1
    while not (eng.poll(rids[3])["tokens"] and eng.bucket == 1):
        eng.step()
    eng.harvest_request(rids[3])
    eng.step()                  # an idle step refreshes the gauges
    assert _value("serving_state_resets") == 4          # one an admission
    assert _value("serving_state_moves") >= 1
    assert _value("serving_state_exports") == 1
    assert _value("serving_state_slots_live") == 0
    names = {e["name"] for e in obs.tracer().events()}
    assert "recurrent_state" in names
