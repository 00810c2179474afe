"""Key-matrix fuzz: the DYNAMIC twin of the keycheck static suite.

keycheck (tests/test_keycheck.py) proves by AST that every compiled
program admitted to the decode program cache is keyed on everything
that can change its traced behaviour.  This module proves the same
contract BY RUNNING IT — minting keys across the serving config
lattice without compiling anything (``jax.jit`` is lazy, so the
program getters are cheap until first dispatch):

  - distinct configs (fused / N-layer / int8-KV / int4-weights /
    generic / chunked-prefill / tp / spec rungs / sampling modes /
    bucket rungs) mint pairwise-DISTINCT keys;
  - identical configs over two fresh model instances share ONE cached
    program (model_signature is structural — weights are traced
    arguments, never identity);
  - eager-only flag toggles (log_level, benchmark, serving_preempt)
    change NO key — byte-identical keys, cache HIT on re-admission;
  - every one of the 13 ``flags.PROGRAM_FLAGS`` toggles changes ALL
    program-family keys (the flag tuple rides every key);
  - every minted key's ``extra`` conforms to the
    ``analysis/key_vocab.py`` grammar (the KEY006 tag registry, checked
    live), and the runtime imports THE SAME vocabulary object the lint
    reads — no drift possible;
  - the KEY005 fixes hold: ``enable/disable_tensor_checker`` and
    ``install_check.run_check`` re-arm the cache around their
    PROGRAM_FLAGS flips;
  - the model_signature address-canonicalization fix holds: a config
    member with a default ``object.__repr__`` no longer splits
    signatures per instance;
  - the tp all-singleton-group arm keys as plain ``decode_fused``
    (one extra schema per kind — the KEY006 finding fixed in r22);
  - ``tools/telemetry_dump.py --programs`` renders the live census.

Static analysis sees every config the code CAN mint; these probes see
only the configs they exercise — which is exactly why both exist.
"""

import importlib.util
import os

import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.analysis import key_vocab
from paddle_tpu.generation import serving
from paddle_tpu.generation.program_cache import (DecodeKey,
                                                 clear_decode_program_cache,
                                                 decode_program_cache,
                                                 model_signature)
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.keycheck

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _pin_decode_path():
    """The lattice's kind expectations (decode_fused as the base arm)
    assume the fused path is armed; pin it in case an earlier test left
    the flags elsewhere, and restore whatever was set."""
    prev = flags.get_flags(["fused_block_decode", "fused_block_layers"])
    flags.set_flags({"fused_block_decode": True, "fused_block_layers": 1})
    yield
    flags.set_flags(prev)


def _llama(seed=91):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _engine(model, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    return ServingEngine(model, **kw)


def _decode_key(eng, bucket=None):
    """Mint (and cache-admit) the decode program for one bucket rung and
    return its key.  The builders return jitted callables without
    tracing, so this never compiles."""
    eng._decode_program(eng.max_batch if bucket is None else bucket)
    return eng.decode_key


def _assert_extra_grammar(key):
    """The live KEY006/KEY003 check: extra is a flat tuple of hashable
    components whose string heads are all registered in key_vocab, with
    the engine-appended discriminant pairs present and ordered last."""
    hash(key)                               # KEY003: every component hashable
    assert isinstance(key.extra, tuple)
    for item in key.extra:
        if isinstance(item, tuple) and item and isinstance(item[0], str):
            assert item[0] in key_vocab.EXTRA_TAGS, item
        elif isinstance(item, str):
            # atoms, or a flat tag head ("nlayer" precedes its shape)
            assert (item in key_vocab.EXTRA_ATOMS
                    or item in key_vocab.EXTRA_TAGS), item
        else:
            assert isinstance(item, (int, tuple)), item
    # engine-minted decode-family keys carry the kv/wt discriminants
    tags = [i[0] for i in key.extra
            if isinstance(i, tuple) and i and isinstance(i[0], str)]
    if key.kind.startswith(("decode", "prefill", "spec")):
        assert tags.count(key_vocab.TAG_KV) == 1
        assert tags.count(key_vocab.TAG_WT) == 1


# ------------------------------------------------------------- the lattice
class TestConfigLattice:
    def test_distinct_configs_mint_distinct_keys(self):
        clear_decode_program_cache()
        model, draft = _llama(), _llama(seed=7)
        keys = {}

        base = _engine(model)
        keys["fused"] = _decode_key(base)
        keys["fused_b2"] = _decode_key(base, bucket=2)   # bucket rung
        keys["prefill"] = base._key("prefill")

        prev = flags.get_flag("fused_block_layers")
        flags.set_flags({"fused_block_layers": 2})
        try:
            keys["nlayer"] = _decode_key(_engine(_llama()))
        finally:
            flags.set_flags({"fused_block_layers": prev})

        prev = flags.get_flag("fused_block_decode")
        flags.set_flags({"fused_block_decode": False})
        try:
            keys["generic"] = _decode_key(_engine(_llama()))
        finally:
            flags.set_flags({"fused_block_decode": prev})

        keys["kv_int8"] = _decode_key(_engine(_llama(), kv_dtype="int8"))
        keys["wt_int4"] = _decode_key(_engine(_llama(),
                                              weight_dtype="int4"))
        keys["tp2"] = _decode_key(_engine(_llama(), tp_degree=2))

        chunked = _engine(_llama(), prefill_chunk=32)
        chunked._chunk_program()
        keys["chunk"] = chunked._key("prefill_chunk", bucket=1,
                                     extra=(chunked.chunk,))

        spec = _engine(model, draft_model=draft)
        spec._spec_draft_program(2, False, 0)
        spec._spec_verify_program(2, False, 0)
        keys["spec_draft_g2"] = spec.spec_draft_key
        keys["spec_verify_g2"] = spec.spec_verify_key
        spec._spec_draft_program(4, False, 0)
        keys["spec_draft_g4"] = spec.spec_draft_key      # γ rung splits
        spec._spec_draft_program(2, True, 8)
        keys["spec_draft_s8"] = spec.spec_draft_key      # sampling splits

        labels = list(keys)
        assert len(set(keys.values())) == len(labels), labels
        for label, key in keys.items():
            assert isinstance(key, DecodeKey), label
            _assert_extra_grammar(key)
        # kinds land where the lattice says they land
        assert keys["fused"].kind == "decode_fused"
        assert keys["nlayer"].kind == "decode_fused_nlayer"
        assert keys["generic"].kind == "decode_generic"
        assert keys["chunk"].kind == "prefill_chunk"
        assert (key_vocab.TAG_KV, "int8") in keys["kv_int8"].extra
        assert (key_vocab.TAG_WT, "int4") in keys["wt_int4"].extra
        assert (key_vocab.TAG_TP, 2) in keys["tp2"].extra

    def test_identical_configs_share_one_program(self):
        # two FRESH model instances with different weights: structural
        # signature → one key → the second engine re-admits from cache
        clear_decode_program_cache()
        e1 = _engine(_llama(seed=1))
        k1 = _decode_key(e1)
        cache = decode_program_cache()
        s0 = cache.stats()
        assert s0["programs"] == 1 and s0["misses"] == 1
        e2 = _engine(_llama(seed=2))
        k2 = _decode_key(e2)
        s1 = cache.stats()
        assert k1 == k2
        assert s1["programs"] == 1          # no second build
        assert s1["hits"] == s0["hits"] + 1

    def test_tp1_keys_carry_no_tp_entry(self):
        # the r18-byte-identity contract: tp rides extra ONLY when armed
        key = _decode_key(_engine(_llama()))
        assert not any(isinstance(e, tuple) and e and e[0] == key_vocab.TAG_TP
                       for e in key.extra)

    def test_tp_singleton_groups_key_as_plain_fused(self):
        # the KEY006 finding fixed in r22: the tp N=1 stacked layout is
        # the SAME program family as decode_fused — ("tp", N) separates
        # it from the single-device program; a (1,)*L nlayer shape tag
        # would have given the kind two extra schemas
        key = _decode_key(_engine(_llama(), tp_degree=2))
        assert key.kind == "decode_fused"
        assert (key_vocab.TAG_TP, 2) in key.extra
        assert key_vocab.TAG_NLAYER not in key.extra
        prev = flags.get_flag("fused_block_layers")
        flags.set_flags({"fused_block_layers": 2})
        try:
            nkey = _decode_key(_engine(_llama(), tp_degree=2))
        finally:
            flags.set_flags({"fused_block_layers": prev})
        assert nkey.kind == "decode_fused_nlayer"
        assert key_vocab.TAG_NLAYER in nkey.extra
        assert (key_vocab.TAG_TP, 2) in nkey.extra


# --------------------------------------------------------- flag behaviour
# two legal values per flag; _alt() picks whichever differs from the
# session's CURRENT value (an earlier test may have left a flag
# non-default — the toggle must move relative to what it finds)
_PROGRAM_ALTS = {
    "fused_block_decode": (True, False),
    "fused_block_layers": (1, 2),
    "use_pallas": (True, False),
    "flash_attn_min_seqlen": (1024, 2048),
    "flash_compact_stats": (True, False),
    "flash_dispatch_table": ("", "0:flash"),
    "tpu_matmul_precision": ("default", "highest"),
    "embedding_matmul_grad": ("auto", "off"),
    "deterministic": (False, True),
    "check_nan_inf": (False, True),
    "check_nan_inf_level": (0, 1),
}

_EAGER_ALTS = {"log_level": (1, 3), "benchmark": (False, True),
               "serving_preempt": (True, False)}


def _alt(name, cur, table):
    return next(v for v in table[name] if v != cur)


def _mint_family(model, draft):
    """One key per program family, minted from a fresh engine (the
    engine snapshots PROGRAM_FLAGS at construction)."""
    eng = _engine(model, draft_model=draft)
    eng._decode_program(eng.max_batch)
    eng._spec_draft_program(2, False, 0)
    eng._spec_verify_program(2, False, 0)
    return {"decode": eng.decode_key,
            "prefill": eng._key("prefill"),
            "prefill_chunk": eng._key("prefill_chunk", bucket=1,
                                      extra=(32,)),
            "spec_draft": eng.spec_draft_key,
            "spec_verify": eng.spec_verify_key}


class TestFlagIdentity:
    def test_every_program_flag_toggle_changes_all_keys(self):
        assert set(_PROGRAM_ALTS) == set(flags.PROGRAM_FLAGS)
        clear_decode_program_cache()
        model, draft = _llama(), _llama(seed=7)
        base = _mint_family(model, draft)
        for name in flags.PROGRAM_FLAGS:
            cur = flags.get_flag(name)
            flags.set_flags({name: _alt(name, cur, _PROGRAM_ALTS)})
            try:
                toggled = _mint_family(model, draft)
            finally:
                flags.set_flags({name: cur})
            for label, key in base.items():
                assert toggled[label] != key, (name, label)
                assert toggled[label].flags != key.flags, (name, label)

    def test_eager_toggles_change_no_key(self):
        clear_decode_program_cache()
        model = _llama()
        base = _decode_key(_engine(model))
        programs = decode_program_cache().stats()["programs"]
        for name in _EAGER_ALTS:
            cur = flags.get_flag(name)
            flags.set_flags({name: _alt(name, cur, _EAGER_ALTS)})
            try:
                key = _decode_key(_engine(model))
            finally:
                flags.set_flags({name: cur})
            assert key == base, name        # byte-identical key ...
        stats = decode_program_cache().stats()
        assert stats["programs"] == programs   # ... served from cache
        assert stats["hits"] >= len(_EAGER_ALTS)


# ----------------------------------------------------------- regressions
class _Opaque:
    pass                                    # default repr: "<... at 0x7f..>"


class _AddrConfig:
    def __init__(self, n):
        self.n = n
        self.handle = _Opaque()

    def __repr__(self):
        return f"_AddrConfig(n={self.n}, handle={self.handle!r})"


class _AddrModel:
    training = False

    def __init__(self, n=1):
        self.config = _AddrConfig(n)

    def named_parameters(self):
        return []

    def named_buffers(self):
        return []


class TestRegressions:
    def test_model_signature_canonicalizes_addresses(self):
        # a config member with a default object.__repr__ embeds its
        # memory address; before the fix every instance minted a
        # DISTINCT signature, silently defeating program sharing
        assert "0x" in repr(_AddrModel().config)
        assert model_signature(_AddrModel()) == model_signature(_AddrModel())
        # real structural differences still split the signature
        assert model_signature(_AddrModel(2)) != model_signature(_AddrModel())
        # and two fresh real models (different weights) share one
        assert model_signature(_llama(seed=1)) == model_signature(
            _llama(seed=2))

    def test_tensor_checker_flips_rearm_the_cache(self):
        # the KEY005 fix in amp/debugging.py: check_nan_inf rides
        # PROGRAM_FLAGS, so flipping it must drop cached programs
        from paddle_tpu.amp.debugging import (disable_tensor_checker,
                                              enable_tensor_checker)
        clear_decode_program_cache()
        model = _llama()
        before = _decode_key(_engine(model))
        assert decode_program_cache().stats()["programs"] == 1
        enable_tensor_checker()
        try:
            assert decode_program_cache().stats()["programs"] == 0
            after = _decode_key(_engine(model))
            assert after != before          # the flag tuple moved
            assert decode_program_cache().stats()["programs"] == 1
        finally:
            disable_tensor_checker()
        assert decode_program_cache().stats()["programs"] == 0

    def test_install_check_precision_flip_rearms_the_cache(self):
        # the KEY005 fix in utils/install_check.py: the matmul probe
        # flips tpu_matmul_precision (PROGRAM_FLAGS) and must clear the
        # cache on BOTH edges of the flip
        from paddle_tpu.utils.install_check import run_check
        clear_decode_program_cache()
        _decode_key(_engine(_llama()))
        assert decode_program_cache().stats()["programs"] == 1
        run_check()
        assert flags.get_flag("tpu_matmul_precision") == "default"
        assert decode_program_cache().stats()["programs"] == 0

    def test_runtime_and_lint_share_one_vocabulary(self):
        # serving mints keys with THE SAME module object keycheck reads
        assert serving.key_vocab is key_vocab
        assert frozenset(flags.PROGRAM_FLAGS) == \
            key_vocab.PROGRAM_FLAGS_FALLBACK
        for name in key_vocab.DISCRIMINANT_FLAGS:
            flags.get_flag(name)            # every discriminant is real
        missing = key_vocab.KEY_DERIVED_ATTRS - {"chunk", "spec_sync_chunk",
                                                 "_tp_mesh", "_tp_axis"}
        eng = _engine(_llama())
        for attr in missing:
            assert hasattr(eng, attr), attr


# ------------------------------------------------------------- the census
def _load_telemetry_dump():
    spec = importlib.util.spec_from_file_location(
        "ptpu_telemetry_dump",
        os.path.join(ROOT, "tools", "telemetry_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestProgramCensus:
    def test_render_programs_live_census(self):
        td = _load_telemetry_dump()
        clear_decode_program_cache()
        assert "(no cached programs" in td.render_programs()
        eng = _engine(_llama())
        key = _decode_key(eng)
        text = td.render_programs()
        assert "1 program(s)" in text
        assert key.kind in text
        assert key.model_sig[:8] in text
        clear_decode_program_cache()
