"""``generation/cache_manager.py``: one owner for what a request keeps per
layer kind, driven directly (no engine but for the rebuild case) over
two models — a tiny GPT (pages only) and a tiny granite-hybrid (pages
and recurrent rows). The engine-level tests cover the same behaviour
from outside (test_granite_hybrid, test_serving_engine, test_fleet,
test_faults)."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.generation.cache_manager import (CacheManager,
                                                 cache_entries,
                                                 has_recurrent_layers,
                                                 kv_heads)
from paddle_tpu.generation.program_cache import decode_program_cache
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.kernels.paged_attention import PagedDecodeState
from paddle_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM

KINDS = ("gpt", "granite")
GEOM = dict(max_batch=4, page_size=8, num_pages=1 + 4 * 4, max_seq_len=32,
            kv_dtype="native", dtype=jnp.float32)


@pytest.fixture(scope="module")
def zoo():
    paddle.seed(30)
    gpt = models.GPTForCausalLM(models.GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=128))
    cfg = GraniteHybridConfig.tiny(embedding_multiplier=1.0,
                                   initializer_range=0.1)
    granite = GraniteHybridForCausalLM(cfg)
    for m in (gpt, granite):
        m.eval()
    return {"gpt": gpt, "granite": granite}


def manager(zoo, kind, **over):
    """A manager whose stores hold recognisable (random) contents."""
    m = CacheManager(zoo[kind], **{**GEOM, **over})
    rng = np.random.default_rng(5)

    def rand(a):
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    m.pool.install_pools([(rand(k), rand(v))
                          for k, v in m.pool.take_pools()])
    if m.state is not None:
        m.state.install_arrays([(rand(s), rand(c))
                                for s, c in m.state.take_arrays()])
    return m


def snapshot(m, slot):
    """What ``slot``'s sequence holds, on the host: its pages' contents
    layer by layer, its cursor, its rows."""
    pids = [int(p) for p in m.pool.sequence_pages(slot)]
    pages = [np.asarray(a)[:, pids] for kv in zip(m.pool.k_pages,
                                                  m.pool.v_pages)
             for a in kv]
    rows = [] if m.state is None else m.state.export(slot)
    return pages, int(m.pool.seq_lens[slot]), rows


def assert_same(a, b):
    assert a[1] == b[1]
    for x, y in zip(a[0] + a[2], b[0] + b[2], strict=True):
        np.testing.assert_array_equal(x, y)


def test_helpers_read_the_spec(zoo):
    assert [has_recurrent_layers(zoo[k]) for k in KINDS] == [False, True]
    assert [kv_heads(zoo[k]) for k in KINDS] == [4, 2]


@pytest.mark.parametrize("kind", KINDS)
def test_allocate_free_ledger_round_trip(zoo, kind):
    m = manager(zoo, kind)
    before = m.ledger()
    assert before["pages_in_use"] == 0 and before["usable_pages"] == 16
    # the state store is billed whole, whether or not a slot is taken
    assert (before["state_bytes"] > 0) == (kind == "granite")
    assert before["state_bytes"] == 4 * before["state_bytes_per_slot"]
    assert m.state_rows == (1 if kind == "granite" else 0)
    m.allocate(1, 20)                       # 3 pages of 8
    m.allocate(2, 8)
    led = m.ledger()
    assert led["pages_in_use"] == 4 and led["pages_free"] == 12
    assert led["bytes_in_use"] == 4 * led["bytes_per_page"]
    assert len(m.pool.sequence_pages(1)) == 3
    m.free(1)
    m.free(2)
    after = m.ledger()
    assert after.pop("epoch") > before.pop("epoch")
    assert after == before
    small = manager(zoo, kind, num_pages=1 + 4)
    small.allocate(0, 32)
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        small.allocate(1, 8)


@pytest.mark.parametrize("kind", KINDS)
def test_move_keeps_pages_and_rows(zoo, kind):
    m = manager(zoo, kind)
    m.allocate(3, 20)
    m.pool.seq_lens[3] = 13
    want = snapshot(m, 3)
    other = snapshot(m, 1)[2]               # slot 1's rows: untouched
    m.move(3, 0)
    assert_same(snapshot(m, 0), want)
    assert len(m.pool.sequence_pages(3)) == 0 and m.pool.seq_lens[3] == 0
    for x, y in zip(snapshot(m, 1)[2], other, strict=True):
        np.testing.assert_array_equal(x, y)
    with pytest.raises(RuntimeError, match="destination slot 0"):
        m.allocate(2, 8)
        m.move(2, 0)


@pytest.mark.parametrize("kind", KINDS)
def test_reset_zeroes_the_rows_only(zoo, kind):
    m = manager(zoo, kind)
    m.allocate(2, 16)
    m.pool.seq_lens[2] = 9
    pages, seq_len, rows = snapshot(m, 2)
    m.reset(2)
    got = snapshot(m, 2)
    assert_same((got[0], got[1], []), (pages, seq_len, []))
    assert all(not r.any() for r in got[2])
    assert len(got[2]) == len(rows) == (6 if kind == "granite" else 0)


@pytest.mark.parametrize("kind", KINDS)
def test_export_into_adopt_is_bit_identical(zoo, kind):
    src, dst = manager(zoo, kind), CacheManager(zoo[kind], **GEOM)
    src.allocate(2, 24)
    src.pool.seq_lens[2] = 19
    want = snapshot(src, 2)
    pages, seq_len, state = src.export_slot(2)
    assert len(pages) == 3 and seq_len == 19
    assert (state is None) == (kind == "gpt")
    # the export detached the sequence: its pages are the pool's again,
    # and the copies were never this pool's host-tier residents
    assert src.ledger()["pages_in_use"] == 0
    assert src.ledger()["pages_spilled"] == 0
    dst.adopt_slot(1, 24, pages, seq_len, state)
    assert_same(snapshot(dst, 1), want)
    assert dst.ledger()["pages_in_use"] == 3


def exported(zoo, kind, n_tokens=24, **over):
    src = manager(zoo, kind, **over)
    src.allocate(0, n_tokens)
    src.pool.seq_lens[0] = n_tokens - 3
    return src.export_slot(0)


@pytest.mark.parametrize("case", [
    "page_bytes", "state_missing", "state_unexpected", "too_many_pages",
    "state_shapes", "no_free_slot", "detached"])
def test_adopt_refusals_leave_no_page_allocated(zoo, case):
    """Each mismatch raises the message ``adopt_request`` has always
    raised, and the refused manager holds nothing afterwards."""
    kind, slot, n_tokens, exc = "granite", 1, 24, ValueError
    if case == "page_bytes":
        bundle = exported(zoo, kind, page_size=16, num_pages=9)
        match = "page layout mismatch — bundle pages are"
    elif case == "state_missing":
        bundle = exported(zoo, "gpt")
        match = ("carries no recurrent state but this engine's model has "
                 "recurrent layers")
    elif case == "state_unexpected":
        bundle, kind = exported(zoo, "granite"), "gpt"
        match = ("carries recurrent state but this engine's model has no "
                 "recurrent layers")
    elif case == "too_many_pages":
        bundle, n_tokens = exported(zoo, kind), 8
        match = "bundle carries 3 pages but the span only needs 1"
    elif case == "state_shapes":
        pages, seq_len, state = exported(zoo, kind)
        bundle = (pages, seq_len, [r[..., :1] for r in state])
        match = "the bundle's rows do not have this store's shapes"
    elif case == "no_free_slot":
        bundle, slot, exc = exported(zoo, kind), None, RuntimeError
        match = "no free slot"
    else:
        bundle, exc, match = exported(zoo, kind), RuntimeError, \
            "adopt_request: pool is detached"
    dst = CacheManager(zoo[kind], **GEOM)
    if case == "detached":
        held = dst.take_caches()
    with pytest.raises(exc, match=match):
        dst.adopt_slot(slot, n_tokens, *bundle)
    if case == "detached":
        dst.pool.install_pools(held[0])
        dst.state.install_arrays(held[1])
    assert dst.ledger()["pages_in_use"] == 0
    assert not dst.pool.seq_lens.any()


@pytest.mark.parametrize("kind", KINDS)
def test_take_then_install_leaves_nothing_detached(zoo, kind):
    m = manager(zoo, kind)
    assert not m.detached
    taken = m.take_caches()
    assert m.detached
    with pytest.raises(RuntimeError, match="already detached"):
        m.take_caches()
    with pytest.raises(RuntimeError, match="harvest_request: pool is "
                                           "detached"):
        m.export_slot(0)
    # what a program returns: one entry a layer, in cache_spec() order
    bt = jnp.zeros((4, 4), jnp.int32)
    sl = jnp.zeros((4,), jnp.int32)
    entries = cache_entries(zoo[kind], taken, PagedDecodeState, bt, sl)
    assert len(entries) == len(zoo[kind].cache_spec())
    m.install_caches(entries)
    assert not m.detached
    assert all(k is not None for k in m.pool.k_pages + m.pool.v_pages)
    assert m.slot_args(3) == (() if kind == "gpt" else (jnp.int32(3),))


@pytest.mark.parametrize("kind", KINDS)
def test_decode_inputs_carry_a_live_mask_for_recurrent_rows_only(zoo, kind):
    m = manager(zoo, kind)
    m.allocate(0, 8)
    m.allocate(1, 8)
    m.pool.seq_lens[:2] = (5, 7)
    bt, sl, *live = m.decode_inputs(2, [1])
    np.testing.assert_array_equal(bt, m.pool.block_tables[:2])
    assert sl.tolist() == [5, 7]
    if kind == "gpt":
        assert live == []
    else:
        (mask,) = live
        assert mask.dtype == np.int32 and mask.tolist() == [0, 1]


@pytest.mark.parametrize("kind", KINDS)
def test_rebuild_keeps_the_geometry(zoo, kind):
    """Fresh stores, the same shapes: the same ``DecodeKey``s, and a
    replay after it builds (and traces) no program."""
    eng = ServingEngine(zoo[kind], max_batch=2, page_size=8, max_seq_len=64,
                        prefill_chunk=16, bucket_ladder=(2,))
    prompts = [np.arange(1, n + 1, dtype=np.int32) for n in (7, 21)]

    def serve():
        rids = [eng.submit(p, 5) for p in prompts]
        out = eng.run()
        return [out[r] for r in rids]
    want = serve()
    cache = decode_program_cache()
    keys = (eng._key("prefill"), eng._key("decode_generic", bucket=2),
            eng.decode_key)
    traces = dict(cache.stats()["traces"])
    shapes = [a.shape for a in eng.pool.k_pages]
    old_pool, old_state = eng.pool, eng._state
    eng._rebuild_pool()
    assert eng.pool is not old_pool and not eng._caches.detached
    assert (eng._state is None) == (old_state is None)
    assert eng._state is None or eng._state is not old_state
    assert [a.shape for a in eng.pool.k_pages] == shapes
    assert eng._caches.ledger()["pages_in_use"] == 0
    assert (eng._key("prefill"), eng._key("decode_generic", bucket=2),
            eng.decode_key) == keys
    assert serve() == want
    assert dict(cache.stats()["traces"]) == traces
