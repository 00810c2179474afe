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


# ------------------------------------- two pools: window layers (PR 34)
# a tiny afmoe: layers window, window, window, global, window; window 16,
# and dispatches of at most 8 tokens: a window row holds at most
# ceil((16 + 8) / 8) + 1 = 4 pages
WINDOW_GEOM = dict(GEOM, max_seq_len=96, num_pages=1 + 4 * 12, step_tokens=8)


@pytest.fixture(scope="module")
def windowed():
    paddle.seed(34)
    model = models.AfmoeForCausalLM(models.AfmoeConfig.tiny())
    model.eval()
    return model


def window_manager(windowed, **over):
    return CacheManager(windowed, **{**WINDOW_GEOM, **over})


def held(m, slot):
    return len(m.window.sequence_pages(slot))


def test_two_pools_by_the_spec(windowed, zoo):
    from paddle_tpu.generation.cache_manager import has_window_layers
    assert has_window_layers(windowed)
    assert not any(has_window_layers(zoo[k]) for k in KINDS)
    assert kv_heads(windowed) == 2
    m = window_manager(windowed)
    assert len(m.pool.k_pages) == 1 and len(m.window.k_pages) == 4
    assert m.window_len == 16 and m._row_bound == 4
    # every slot at its bound, and the null page
    assert m.window.num_pages == 1 + 4 * 4
    assert m.page_budget == (49, 8, 12, 17)
    # never more than the global pool has
    assert window_manager(windowed, num_pages=9).window.num_pages == 9
    # a whole prompt a dispatch (no chunk): the bound is the row's width
    assert window_manager(windowed, step_tokens=0)._row_bound == 12
    assert CacheManager(zoo["gpt"], **GEOM).page_budget == (17, 8, 4)


def test_window_row_releases_from_the_front_and_stays_in_its_bound(windowed):
    m = window_manager(windowed)
    m.allocate(1, 90)                       # 12 pages of the global pool
    led = m.ledger()
    assert led["pages_in_use"] == 12 and led["window_pages_in_use"] == 0
    assert m._span.tolist() == [0, 90, 0, 0]
    most, tables = 0, []
    # a prompt of 61 in chunks of 8, then decode to 90
    cursor = 0
    while cursor < 90:
        step = 8 if cursor < 61 else 1
        if step == 8:
            _, wt = m.tables(1, step)
            wt = np.asarray(wt)[0]
            cursor = min(cursor + 8, 61)
        else:
            (_, wt), _ = m.decode_inputs(2, [1])
            wt = wt[1]
            cursor += 1
        tables.append(wt.copy())
        most = max(most, held(m, 1))
        assert held(m, 1) == m.ledger()["window_pages_in_use"]
        # every position the next read visits has a page
        first = max(0, m.pool.seq_lens[1] + 1 - 16) // 8
        assert (wt[first:-(-cursor // 8)] > 0).all()
        m.pool.seq_lens[1] = cursor         # as the engine does
    # the window's two pages and the chunk's one (the bound's fourth is
    # for a window or a chunk that starts inside a page)
    assert most == 3 <= m._row_bound
    assert m.window_pages_released == 12 - held(m, 1)
    assert m.ledger()["window_pages_released"] == m.window_pages_released
    # released slots point at the null page; a page came back to the row
    assert (tables[-1][:8] == 0).all()
    assert len({int(p) for t in tables for p in t if p}) <= 4 < 12
    m.free(1)
    led = m.ledger()
    assert led["pages_in_use"] == 0 and led["window_pages_in_use"] == 0
    assert m.window.free_page_count() == m.window.num_pages - 1
    assert not m._span.any()


def test_window_pool_holds_every_slot_at_its_bound(windowed):
    """Admission prices the global pool alone, and that is enough: the
    window pool has every slot's bound, or as many pages as the global
    pool, so whatever rows the global pool admitted find their window
    pages, at every cursor, with the most a dispatch writes."""
    for kw in (dict(), dict(num_pages=1 + 20, max_batch=2)):
        m = window_manager(windowed, **kw)
        rows = m.pool.block_tables.shape[0]
        spans = [90] * rows if not kw else [90, 60]   # 12 + 8 = 20 pages
        for slot, span in enumerate(spans):
            m.allocate(slot, span)
        assert m.pool.free_page_count() == 0 if kw else True
        for cursor in range(0, 88, 8):
            for slot, span in enumerate(spans):
                if cursor < span:
                    m.tables(slot, 8)       # would raise if the pool ran out
                    assert held(m, slot) <= m._row_bound
            for slot, span in enumerate(spans):
                m.pool.seq_lens[slot] = min(cursor + 8, span)
        for slot in range(len(spans)):
            m.free(slot)
        assert m.window.free_page_count() == m.window.num_pages - 1


def test_tables_hands_out_copies_of_both_rows(windowed, zoo):
    """The CPU backend aliases a 64-byte-aligned host view instead of
    copying it (a row of 12 int32 is 48 bytes, so one slot in four of
    such a table is aligned), and a non-final chunk is not waited for:
    what ``tables`` handed a program must not change when a move, a
    free or the next slide rewrites the rows."""
    for m in (window_manager(windowed),
              CacheManager(zoo["gpt"], **dict(GEOM, max_seq_len=96,
                                              num_pages=49))):
        for slot in range(4):
            m.allocate(slot, 90)
            handed = m.tables(slot, 8)
            handed = handed if isinstance(handed, tuple) else (handed,)
            want = [np.array(t) for t in handed]
            assert all((w[0, :1] > 0).all() for w in want)
            m.free(slot)                    # zeroes the rows
            for pool in (m.pool, m.window):
                if pool is not None:
                    pool.block_tables[slot] = 7
            for t, w in zip(handed, want):
                np.testing.assert_array_equal(np.asarray(t), w)


def test_move_and_rebuild_cover_both_pools(windowed):
    m = window_manager(windowed)
    m.allocate(3, 40)
    m.tables(3, 8)
    m.pool.seq_lens[3] = 8
    m.tables(3, 8)
    m.pool.seq_lens[3] = 32
    m.tables(3, 8)                          # gives two pages back
    want = (m.window.sequence_pages(3).copy(), int(m.window._pages_first[3]),
            m.pool.sequence_pages(3).copy())
    assert want[1] == 2
    m.move(3, 0)
    np.testing.assert_array_equal(m.window.sequence_pages(0), want[0])
    np.testing.assert_array_equal(m.pool.sequence_pages(0), want[2])
    assert m.window._pages_first[0] == 2 and held(m, 3) == 0
    assert m._span.tolist() == [40, 0, 0, 0]
    released = m.window_pages_released
    m.rebuild()
    led = m.ledger()
    assert led["pages_in_use"] == 0 and led["window_pages_in_use"] == 0
    assert not m._span.any()
    assert m.window_pages_released == released  # a running total


def test_take_then_install_with_two_pools(windowed):
    m = window_manager(windowed)
    taken = m.take_caches()
    assert m.detached and len(taken) == 2
    assert [len(p) for p in taken] == [1, 4]
    bt = (jnp.zeros((4, 12), jnp.int32), jnp.ones((4, 12), jnp.int32))
    sl = jnp.zeros((4,), jnp.int32)
    entries = cache_entries(windowed, taken, PagedDecodeState, bt, sl)
    assert len(entries) == 5
    # each layer is handed the table of its kind
    assert [int(e.block_tables[0, 0]) for e in entries] == [1, 1, 1, 0, 1]
    assert entries[3].k_pages.shape[1] == 49
    assert entries[0].k_pages.shape[1] == 17
    m.install_caches(entries)
    assert not m.detached
    assert all(k is not None for k in m.window.k_pages + m.pool.k_pages)


def test_export_of_a_window_row_refuses_loudly(windowed):
    m = window_manager(windowed)
    m.allocate(0, 24)
    with pytest.raises(NotImplementedError, match="does not travel"):
        m.export_slot(0)
    with pytest.raises(NotImplementedError, match="does not travel"):
        m.adopt_slot(1, 24, [], 0, None)
    assert m.ledger()["pages_in_use"] == 3
