"""AFMoE (``models/afmoe.py``, arcee-ai Trinity): window and global
attention layers in one model, against the plain reference of the
benchmark (``benchmark/refs/trinity-mini.py``: float32, dense masks) at
a tiny size on the CPU, with a tiny window (16) and chunk (8) so that a
short test slides the window and gives pages back: the whole-sequence
forward; greedy decoding through ``ServingEngine`` on prompts inside the
window, past it, and past window + chunk; what a window row holds at
every step boundary; the ladder, preemption, replay recovery; the
engine's refusals; the counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib.system import load_reference
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.kernels.paged_attention import WindowKV
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.testing import faults

REF = load_reference("trinity-mini")
WINDOW, CHUNK, PAGE = 16, 8, 8


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def tiny():
    """Tiny (one dense layer, then three window layers and a global one
    among the sparse four), with weights large enough that a random
    model does not repeat one token whatever it has seen, and an expert
    bias as large as the scores' spread."""
    paddle.seed(34)
    cfg = AfmoeConfig.tiny(initializer_range=0.25)
    model = AfmoeForCausalLM(cfg)
    weights = dict(model.raw_state()[0])
    rng = np.random.default_rng(34)
    for name in weights:
        if name.endswith("expert_bias"):
            weights[name] = jnp.asarray(
                rng.normal(size=weights[name].shape) * 0.2, jnp.float32)
    model.load_raw_state(weights)
    model.eval()
    return cfg, model, weights, dataclasses.asdict(cfg)


_JITTED = {}


def ref_logits(tiny, ids, **kw):
    """The reference's logits of ``ids``, padded to ONE length (causal:
    a position sees nothing after it) and compiled once a variant."""
    _, _, weights, md = tiny
    key = tuple(sorted(kw.items()))
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda w, x: REF.logits(w, x, md, **kw))
    padded = np.zeros((96,), np.int32)
    padded[:len(ids)] = ids
    return np.asarray(_JITTED[key](weights, jnp.asarray(padded)))[:len(ids)]


def ref_generate(tiny, prompt, n_new, **kw):
    seq = [int(t) for t in prompt]
    for _ in range(n_new):
        seq.append(int(ref_logits(tiny, seq, **kw)[-1].argmax()))
    return seq[len(prompt):]


def make_engine(model, **kw):
    kw = {"max_batch": 4, "page_size": PAGE, "max_seq_len": 96,
          "prefill_chunk": CHUNK, "bucket_ladder": (1, 2, 4), **kw}
    return ServingEngine(model, **kw)


# inside the window, a whole prompt under the chunk; at the window; past
# it (the mask bites); past window + chunk (released pages are taken
# again by the same row); and far past
LENS = [5, 8, 16, 21, 27, 40, 61]
BUDGETS = [12, 6, 9, 12, 7, 10, 14]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, 128, (n,)).astype(np.int32) for n in LENS]


@pytest.fixture(scope="module")
def wanted(tiny, prompts):
    return [ref_generate(tiny, p, n) for p, n in zip(prompts, BUDGETS)]


# ------------------------------------------------------------ the model
def test_forward_matches_reference(tiny):
    _, model, _, _ = tiny
    ids = np.random.default_rng(1).integers(0, 128, (45,)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value)[0]
    np.testing.assert_allclose(got, ref_logits(tiny, ids), atol=1e-4,
                               rtol=1e-4)


def test_window_off_control_differs(tiny):
    """Past the window the reference with the window left off gives
    other logits (and other tokens); inside it, the same."""
    ids = np.random.default_rng(2).integers(0, 128, (45,)).astype(np.int32)
    on, off = ref_logits(tiny, ids), ref_logits(tiny, ids, window=None)
    np.testing.assert_allclose(on[:WINDOW], off[:WINDOW], atol=1e-5)
    assert np.max(np.abs(on[WINDOW + 4:] - off[WINDOW + 4:])) > 1e-2
    assert (on[WINDOW:].argmax(-1) != off[WINDOW:].argmax(-1)).any()


def test_layers_are_dense_then_sparse_and_of_two_kinds(tiny):
    cfg, model, weights, _ = tiny
    assert [layer.sparse for layer in model.model.layers] \
        == [False, True, True, True, True]
    assert "model.layers.0.mlp.gate_proj.weight" in weights
    assert "model.layers.1.mlp.expert_bias" in weights
    assert "model.layers.1.mlp.shared_gate_up" in weights
    spec = model.cache_spec()
    assert [isinstance(e, WindowKV) for e in spec] \
        == [True, True, True, False, True]
    assert spec[0] == WindowKV(2, 16, WINDOW) and spec[3] == (2, 16)
    # the sparse layers only
    assert model.expert_counts_width() == cfg.num_experts + 1


def test_rope_is_on_the_window_layers_only(tiny):
    """A global layer has no positional encoding: in a model of one
    global layer, a permutation of the earlier tokens leaves the last
    position's logits alone (it sees the same set of keys and values);
    one window layer's rotation does not."""
    cfg = tiny[0]

    def last_logits(kind, ids):
        paddle.seed(9)
        m = AfmoeForCausalLM(dataclasses.replace(
            cfg, num_hidden_layers=1, layer_types=[kind],
            initializer_range=0.25))
        m.eval()
        return np.asarray(m(paddle.to_tensor(ids[None]))._value)[0, -1]

    ids = np.random.default_rng(3).integers(0, 128, (12,)).astype(np.int32)
    swapped = ids.copy()
    swapped[[2, 7]] = swapped[[7, 2]]
    np.testing.assert_allclose(last_logits("full_attention", ids),
                               last_logits("full_attention", swapped),
                               atol=1e-4)
    assert np.max(np.abs(last_logits("sliding_attention", ids)
                         - last_logits("sliding_attention", swapped))) > 1e-3


def test_param_count_and_cache_spec_at_published_widths():
    """The whole published model by its parameters' shapes (no bytes):
    26.12 B, the family's published 26B; the benchmark's cut, one dense
    and four sparse layers with the embedding and the head:
    4,241,534,720."""
    def count(**kw):
        with paddle.LazyGuard():
            m = AfmoeForCausalLM(AfmoeConfig(**kw))
        return m, sum(int(np.prod(p.shape)) for p in m.parameters())

    whole, n = count()
    assert round(n / 1e9, 2) == 26.12
    spec = whole.cache_spec()
    assert len(spec) == 32
    assert sum(isinstance(e, WindowKV) for e in spec) == 24
    assert spec[3] == (4, 128) and spec[0] == WindowKV(4, 128, 2048)
    _, n = count(num_hidden_layers=5, num_dense_layers=1,
                 layer_types=whole.config.layer_types[:5])
    # of them 44,800 in the norms (four a layer and the final one at
    # 2,048, q and k at 128) and the expert bias (128 a sparse layer)
    assert n == 4_241_534_720


def test_generate_refuses_loudly(tiny):
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        tiny[1].generate(paddle.to_tensor(np.zeros((1, 4), np.int32)))


# ------------------------------------------------------------ the engine
def held_by_window_rows(eng):
    win = eng._caches.window
    return (win._pages_used - win._pages_first).astype(int)


def test_engine_equals_reference_and_rows_hold_a_window(tiny, prompts,
                                                        wanted):
    """Every prompt of the table, more requests than slots, chunked and
    monolithic prefill side by side with decoding rows: greedy tokens
    are the reference's; at every step boundary a window row holds at
    most ceil((window + chunk) / page) + 1 pages; at the end every page
    of both pools is free again."""
    _, model, _, _ = tiny
    eng = make_engine(model)
    bound = -(-(WINDOW + CHUNK) // PAGE) + 1
    assert eng._caches._row_bound == bound
    rids = [eng.submit(p, n) for p, n in zip(prompts, BUDGETS)]
    most = 0
    while eng.has_work():
        eng.run_step()
        most = max(most, int(held_by_window_rows(eng).max()))
    out = eng.results()
    for i, rid in enumerate(rids):
        assert eng.status(rid) == "OK"
        assert out[rid] == wanted[i], (LENS[i], out[rid], wanted[i])
    assert 0 < most <= bound
    # the longest request alone spans more pages than a row may hold
    assert -(-(LENS[-1] + BUDGETS[-1]) // PAGE) > bound
    caches = eng._caches
    assert caches.window_pages_released > 0
    assert caches.window.free_page_count() == caches.window.num_pages - 1
    assert eng.pool.free_page_count() == eng.pool.num_pages - 1
    # window-off would have given other tokens on the long prompts
    assert ref_generate(tiny, prompts[-1], BUDGETS[-1], window=None) \
        != wanted[-1]


def test_engine_long_decode_stays_inside_the_bound(tiny, prompts):
    """A long decode from a short prompt: the window slides a token at
    a time and the row never holds more than its bound."""
    _, model, _, _ = tiny
    eng = make_engine(model, max_batch=1, bucket_ladder=(1,))
    rid = eng.submit(prompts[0], 80)
    bound, most = eng._caches._row_bound, 0
    while eng.has_work():
        eng.run_step()
        most = max(most, int(held_by_window_rows(eng)[0]))
    assert len(eng.results()[rid]) == 80
    assert most <= bound
    # 85 positions are 11 pages; the window keeps 3 or 4 of them
    assert eng._caches.window_pages_released >= 7


def test_engine_monolithic_prefill_past_the_window(tiny, prompts, wanted):
    """Chunking off: a whole prompt longer than the window reads through
    the block table under the window mask."""
    _, model, _, _ = tiny
    eng = make_engine(model, prefill_chunk=0)
    rids = [eng.submit(prompts[i], BUDGETS[i]) for i in (1, 4, 5)]
    out = eng.run()
    for i, rid in zip((1, 4, 5), rids):
        assert out[rid] == wanted[i]


def test_engine_ladder_shrink_moves_both_tables(tiny, prompts, wanted):
    _, model, _, _ = tiny
    prior = flags.get_flag("serving_bucket_patience")
    flags.set_flags({"serving_bucket_patience": 1})
    try:
        eng = make_engine(model)
    finally:
        flags.set_flags({"serving_bucket_patience": prior})
    order = [4, 1, 6, 3]                 # budgets 7, 6, 14, 12
    rids = [eng.submit(prompts[i], BUDGETS[i]) for i in order]
    out = eng.run()
    assert eng.bucket_migrations >= 2
    for i, r in zip(order, rids):
        assert out[r] == wanted[i]


def test_engine_preemption_replays_a_row_past_its_window(tiny, prompts,
                                                         wanted):
    """A row whose window pages were given back is unseated and replays
    from its tokens as a prompt does: the same continuation."""
    _, model, _, _ = tiny
    eng = make_engine(model)
    rids = [eng.submit(prompts[i], BUDGETS[i]) for i in (0, 6, 3)]
    victim = None
    while victim is None:
        eng.step()
        victim = next((r for r in eng._slots if r is not None
                       and r.rid == rids[1] and len(r.tokens) >= 3), None)
    assert eng._caches.window._pages_first[victim.slot] > 0
    eng._settle("preempt")               # as _preempt_for reads first
    eng._unseat(victim)
    out = eng.run()
    for i, r in zip((0, 6, 3), rids):
        assert out[r] == wanted[i]
    assert eng.preemptions == 1


def test_engine_replay_recovery_rebuilds_both_pools(tiny, prompts, wanted):
    _, model, _, _ = tiny
    with faults.armed("decode_dispatch:every=9:times=2",
                      serving_retry_backoff=0.001):
        eng = make_engine(model)
        rids = [eng.submit(prompts[i], BUDGETS[i]) for i in (2, 3, 5, 6)]
        out = eng.run()
    for i, r in zip((2, 3, 5, 6), rids):
        assert out[r] == wanted[i] and eng.status(r) == "OK"


def test_engine_harvest_refuses_a_window_row(tiny, prompts):
    """Export cannot carry a window row yet: it says so, and the row
    decodes on."""
    _, model, _, _ = tiny
    eng = make_engine(model)
    rid = eng.submit(prompts[3], 6)
    while not eng.poll(rid)["tokens"]:
        eng.step()
    with pytest.raises(NotImplementedError, match="window layers"):
        eng.harvest_request(rid)
    assert len(eng.run()[rid]) == 6


@pytest.mark.parametrize("kwargs,reason", [
    (dict(prefix_cache=True), "prefix_cache=True with a model that has "
                              "window layers"),
    (dict(draft_model="llama"), "draft_model= with a model that has "
                                "window layers"),
    (dict(tp_degree=2), "tp_degree=2 with a model that has window layers"),
])
def test_engine_refuses(tiny, kwargs, reason):
    _, model, _, _ = tiny
    if kwargs.get("draft_model") == "llama":
        kwargs = dict(draft_model=LlamaForCausalLM(LlamaConfig.tiny()))
    with pytest.raises(ValueError, match=reason):
        make_engine(model, **kwargs)


def test_counters_and_gauges_of_the_window_pool(tiny, prompts):
    """``serving_decode_window_tokens`` counts min(len + 1, window) a
    decoding row a step, ``serving_chunk_attn_pairs`` /
    ``serving_chunk_window_pairs`` the query-key pairs of the chunks by
    layer kind and ``serving_chunk_attn_tile_pairs`` /
    ``serving_chunk_window_tile_pairs`` the pairs the chunk kernel's
    tiles made of them, ``serving_window_pages_released`` the pages
    given back, ``serving_kv_pool_bytes`` each pool by its pages in
    use; the expert counters move as they do for SDAR."""
    _, model, _, _ = tiny

    def read(name, **labels):
        fam = obs.registry().snapshot()["metrics"].get(name)
        return sum(s["value"] for s in (fam["series"] if fam else [])
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    eng = make_engine(model, max_batch=1, bucket_ladder=(1,),
                      replica="afmoe-counters")
    mine = dict(replica="afmoe-counters")
    before = {n: read(n, **mine) for n in (
        "serving_decode_window_tokens", "serving_decode_live_tokens",
        "serving_window_pages_released", "serving_chunk_attn_pairs",
        "serving_chunk_window_pairs", "serving_chunk_attn_tile_pairs",
        "serving_chunk_window_tile_pairs")}
    rid = eng.submit(prompts[5], 10)     # 40 tokens, then 9 decode steps
    peak = dict(window=0.0, **{"global": 0.0})
    while eng.has_work():
        eng.run_step()
        for pool in peak:
            peak[pool] = max(peak[pool], read(
                "serving_kv_pool_bytes", pool=pool, **mine))
    assert len(eng.results()[rid]) == 10
    delta = {n: read(n, **mine) - v for n, v in before.items()}
    # the decode steps read rows of 40 .. 48 cached tokens: each past the
    # window, so a window layer reads 16 of them
    assert delta["serving_decode_live_tokens"] == sum(range(40, 49))
    assert delta["serving_decode_window_tokens"] == 9 * WINDOW
    assert delta["serving_window_pages_released"] \
        == eng._caches.window_pages_released > 0
    # the prompt's five chunks: a query at position p sees p + 1 keys
    # in a global layer, at most the window's 16 in a window layer
    assert delta["serving_chunk_attn_pairs"] == sum(range(1, 41))
    assert delta["serving_chunk_window_pairs"] \
        == sum(min(p + 1, WINDOW) for p in range(40))
    # what the kernel's tiling computes for them: the sum over the five
    # chunks' cursors by the engine's tilings (held to the kernel's own
    # in the test below), never under the exact pairs
    from paddle_tpu.kernels.paged_attention import chunk_tile_pairs
    assert [tl.window for tl in eng._chunk_cut] == [None, WINDOW]
    for name, tl in zip(("attn", "window"), eng._chunk_cut):
        assert delta[f"serving_chunk_{name}_tile_pairs"] \
            == sum(chunk_tile_pairs(tl, pos) for pos in range(0, 40, CHUNK)) \
            >= delta[f"serving_chunk_{name}_pairs"]
    # bytes a page: layers x k and v x kv heads x page x head dim x 4
    page_bytes = 2 * 2 * PAGE * 16 * 4
    assert peak["global"] == 7 * 1 * page_bytes     # 50 tokens: 7 pages
    assert 0 < peak["window"] <= eng._caches._row_bound * 4 * page_bytes
    hist = eng.expert_histogram()
    # 4 sparse layers x top 2 x every token of every forward
    assert hist is not None and hist.sum() == 4 * 2 * (40 + 9)


def test_tile_pair_counters_count_by_the_kernels_own_tiling(tiny,
                                                            monkeypatch):
    """The tilings ``serving_chunk_*_tile_pairs`` sum by are the ones
    ``paged_chunk_attention``'s wrapper computes from the shapes the
    engine's chunk program hands it: that program traced with the
    kernels on (nothing lowers or runs), one tiling a layer kind. A
    model without ``config.num_attention_heads`` is refused at
    construction, where no recovery swallows it."""
    from paddle_tpu.generation import serving
    from paddle_tpu.kernels import paged_attention as pa

    _, model, _, _ = tiny
    eng = make_engine(model, max_batch=1, bucket_ladder=(1,))
    monkeypatch.setattr("paddle_tpu.flags.is_tpu_backend", lambda: True)
    seen, real = [], pa.chunk_tiling

    def spy(*args, **kw):
        seen.append(real(*args, **kw))
        return seen[-1]

    monkeypatch.setattr(pa, "chunk_tiling", spy)
    # the wrapper keeps one trace a shape: start from none
    pa._paged_chunk.clear_cache()
    fn = serving._build_chunk_prefill(lambda: None, model)
    jax.eval_shape(fn, eng._packed, eng._buffers,
                   jnp.zeros((1, CHUNK), jnp.int32),
                   eng._caches.take_caches(), eng._caches.tables(0, CHUNK),
                   jnp.zeros((1,), jnp.int32), jnp.int32(CHUNK - 1))
    pa._paged_chunk.clear_cache()
    # one trace a layer kind: a global layer's and the window layers'
    assert sorted(seen, key=lambda tl: tl.window is not None) \
        == list(eng._chunk_cut)
    assert [tl.window for tl in eng._chunk_cut] == [None, WINDOW]

    class Headless:
        config = None

        def __getattr__(self, name):
            return getattr(model, name)

    with pytest.raises(ValueError, match="num_attention_heads"):
        make_engine(Headless())


def test_prefill_programs_run_the_head_on_one_position(tiny):
    """``forward_with_cache(logits_at=)`` gives that position's row of
    the whole logits, and the engine's two prefill programs take their
    row through ``_prefill_row``, which hands a model that publishes
    ``logits_at_position`` the position and indexes any other's whole
    logits (``tests/test_chip_compile.py`` holds that the compiled
    chunk has no logits of the whole chunk)."""
    from paddle_tpu import models
    from paddle_tpu.generation import serving
    from paddle_tpu.generation.cache_manager import (CacheManager,
                                                     cache_entries)
    from paddle_tpu.kernels.paged_attention import PagedDecodeState
    geom = dict(max_batch=1, page_size=PAGE, num_pages=13, max_seq_len=96,
                kv_dtype="native", dtype=jnp.float32, step_tokens=CHUNK)
    ids = jnp.asarray(np.arange(3, 11)[None], jnp.int32)

    def forward(model, call, *args, **kw):
        params, buffers = model.raw_state()
        m = CacheManager(model, **geom)
        m.allocate(0, 16)
        states = cache_entries(model, m.take_caches(), PagedDecodeState,
                               m.tables(0, 8), jnp.zeros((1,), jnp.int32))
        return call(model, serving.PackedParams.pack(params), buffers,
                    ids, states, jnp.int32(0), *args, **kw)[0]

    afmoe = tiny[1]
    assert afmoe.logits_at_position
    whole = forward(afmoe, serving._forward_with_cache)
    one = forward(afmoe, serving._forward_with_cache,
                  logits_at=jnp.int32(5))
    assert whole.shape == (1, 8, 128) and one.shape == (1, 1, 128)
    np.testing.assert_allclose(one[0, 0], whole[0, 5], atol=1e-5)
    np.testing.assert_array_equal(
        forward(afmoe, serving._prefill_row, jnp.int32(5)), one[0, 0])
    # a model without the hook is handed nothing and indexed as before
    gpt = models.GPTForCausalLM(models.GPTConfig(
        vocab_size=64, hidden_size=16, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=32))
    gpt.eval()
    assert not getattr(gpt, "logits_at_position", False)
    np.testing.assert_array_equal(
        forward(gpt, serving._prefill_row, 5),
        forward(gpt, serving._forward_with_cache)[0, 5])
