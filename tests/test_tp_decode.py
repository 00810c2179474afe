"""Tensor-parallel sharded decode (r19): the ServingEngine under
``tp_degree > 1`` runs its fused block chain inside ``jax.shard_map``
over the mp axis — stacked weights split head-/column-/row-wise (the
``shard_block_weights`` Megatron layout), the paged KV pool partitions
over kv-heads, and every layer pays exactly two psums (the wo and wd
row-parallel exits).

Invariants:
  - greedy token streams are BIT-IDENTICAL to the tp=1 engine on the
    fused, N-layer, int8-KV, spec-verify and generic (GSPMD) arms;
  - the sharded program keys on ``("tp", N)`` in ``DecodeKey.extra``
    and never retraces in steady state; tp=1 keys stay byte-identical
    to r18 (no tp entry at all);
  - int4 weight tiles and indivisible kv-head counts are REFUSED at
    engine construction, never silently rounded;
  - replay recovery under injected decode faults reproduces the clean
    stream with tp armed — pool bookkeeping stays host-pure and
    kv-head-partition-invariant;
  - ``harvest_request``/``adopt_request`` move a live greedy request
    WITH its KV pages between engines (prefill→decode disaggregation)
    and the continuation is bit-identical — no prefill re-run;
  - a tp>1 engine observes ``serving_collective_seconds`` host-side at
    the dispatch boundary, and the program-cache families carry the
    ``tp`` label.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, observability as obs
from paddle_tpu.generation.program_cache import (clear_decode_program_cache,
                                                 decode_program_cache)
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.testing import faults, transport

pytestmark = pytest.mark.tp_decode

PROMPTS = [[1, 5, 9, 2], [3, 7, 4], [2, 2, 8, 6, 1]]


def fault_spec(spec, **extra_flags):
    extra_flags.setdefault("serving_retry_backoff", 0.001)
    return faults.armed(spec, **extra_flags)


def _llama(seed=91):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _gpt(seed=91):
    paddle.seed(seed)
    return GPTForCausalLM(GPTConfig.tiny())


def _run(model, prompts=PROMPTS, tokens=8, **kw):
    clear_decode_program_cache()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    eng = ServingEngine(model, **kw)
    rids = [eng.submit(p, max_new_tokens=tokens, temperature=0.0)
            for p in prompts]
    res = eng.run()
    return eng, [res[r] for r in rids]


# ------------------------------------------------------------- parity
class TestShardedParity:
    def test_fused_parity_keys_and_zero_retrace(self):
        _, ref = _run(_llama())
        eng, out = _run(_llama(), tp_degree=2)
        assert out == ref
        key = eng.decode_key
        assert key.kind == "decode_fused"
        assert ("tp", 2) in key.extra
        # steady state: drain a second wave without a single retrace
        cache = decode_program_cache()
        traced = cache.trace_count(key)
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8, temperature=0.0)
        eng.run()
        assert cache.trace_count(key) == traced

    def test_tp1_keys_stay_r18_identical(self):
        eng, _ = _run(_llama())
        assert not any(isinstance(e, tuple) and e and e[0] == "tp"
                       for e in eng.decode_key.extra)

    def test_nlayer_parity(self):
        prev = flags.get_flag("fused_block_layers")
        flags.set_flags({"fused_block_layers": 2})
        try:
            _, ref = _run(_llama())
            eng, out = _run(_llama(), tp_degree=2)
            assert out == ref
            assert eng.decode_key.kind == "decode_fused_nlayer"
            assert ("tp", 2) in eng.decode_key.extra
        finally:
            flags.set_flags({"fused_block_layers": prev})

    def test_int8_kv_parity(self):
        _, ref = _run(_llama(), kv_dtype="int8")
        eng, out = _run(_llama(), kv_dtype="int8", tp_degree=2)
        assert out == ref
        assert ("kv", "int8") in eng.decode_key.extra
        assert ("tp", 2) in eng.decode_key.extra

    def test_spec_verify_parity(self):
        paddle.seed(7)
        d1 = LlamaForCausalLM(LlamaConfig.tiny())
        _, ref = _run(_llama(), draft_model=d1)
        paddle.seed(7)
        d2 = LlamaForCausalLM(LlamaConfig.tiny())
        _, out = _run(_llama(), draft_model=d2, tp_degree=2)
        assert out == ref

    def test_generic_gspmd_parity(self):
        # no fused spec for GPT: the generic program compiles against
        # the kv-head-sharded pool and GSPMD places the collectives
        _, ref = _run(_gpt())
        eng, out = _run(_gpt(), tp_degree=2)
        assert out == ref
        assert ("tp", 2) in eng.decode_key.extra


# ----------------------------------------------------- recovery / faults
class TestShardedRecovery:
    def test_fault_replay_parity(self):
        _, ref = _run(_llama(), tp_degree=2)
        with fault_spec("decode_dispatch:every=3", serving_max_retries=8):
            eng, out = _run(_llama(), tp_degree=2)
        assert out == ref
        assert not eng.has_work()


# ------------------------------------------------------------- refusals
class TestRefusals:
    def test_int4_weights_refused(self):
        with pytest.raises(ValueError, match="int4"):
            ServingEngine(_llama(), max_batch=4, max_seq_len=128,
                          weight_dtype="int4", tp_degree=2)

    def test_indivisible_kv_heads_refused(self):
        with pytest.raises(ValueError, match="kv-head"):
            ServingEngine(_llama(), max_batch=4, max_seq_len=128,
                          tp_degree=3)

    def test_degenerate_degree_refused(self):
        with pytest.raises(ValueError, match="tp_degree"):
            ServingEngine(_llama(), max_batch=4, max_seq_len=128,
                          tp_degree=0)


# ------------------------------------------------------------ telemetry
class TestCollectiveTelemetry:
    @pytest.fixture(autouse=True)
    def _armed(self):
        obs.registry().clear()
        clear_decode_program_cache()
        yield
        obs.registry().clear()
        clear_decode_program_cache()

    def test_collective_histogram_and_tp_label(self):
        _run(_llama(), tp_degree=2)
        snap = obs.registry().snapshot()
        fam = snap["metrics"]["serving_collective_seconds"]
        rows = [s for s in fam["series"]
                if s["labels"].get("tp") == "2"]
        assert rows and rows[0]["count"] >= 1
        traces = snap["metrics"]["program_cache_traces"]["series"]
        assert all("tp" in s["labels"] for s in traces)
        assert any(s["labels"]["tp"] == "2" for s in traces)

    def test_tp1_engine_never_observes_collectives(self):
        _run(_llama())
        snap = obs.registry().snapshot()
        fam = snap["metrics"].get("serving_collective_seconds")
        assert fam is None or all(s["count"] == 0 for s in fam["series"])
        traces = snap["metrics"]["program_cache_traces"]["series"]
        assert all(s["labels"]["tp"] == "1" for s in traces)


# ----------------------------------------- prefill→decode disaggregation
def _harvest_midstream(tokens=8, **kw):
    """Run a request past prefill on a fresh engine and harvest it;
    returns (solo_reference_tokens, bundle, engine_kw)."""
    prompt = PROMPTS[0]
    _, ref = _run(_llama(), prompts=[prompt], tokens=tokens, **kw)

    clear_decode_program_cache()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 128)
    a = ServingEngine(_llama(), **kw)
    rid = a.submit(prompt, max_new_tokens=tokens, temperature=0.0)
    # step until the request is seated past prefill with >= 1 token
    for _ in range(64):
        a.step()
        req = next((r for r in a._slots
                    if r is not None and r.rid == rid), None)
        if (req is not None and req.tokens
                and req.prefill_pos is None and not req.pending):
            break
    else:
        raise AssertionError("request never reached mid-stream state")
    bundle = a.harvest_request(rid)
    assert all(r is None or r.rid != rid for r in a._slots)
    return ref[0], bundle, kw


def _handoff(tokens=8, **kw):
    """Solo reference vs. a mid-stream harvest/adopt pair; returns
    (solo_tokens, adopted_tokens)."""
    solo, bundle, kw = _harvest_midstream(tokens=tokens, **kw)
    b = ServingEngine(_llama(), **kw)
    new_rid = b.adopt_request(bundle)
    res = b.run()
    return solo, res[new_rid]


class TestHandoff:
    def test_harvest_adopt_bit_identical(self):
        solo, adopted = _handoff()
        assert adopted == solo

    def test_harvest_adopt_int8_tp2(self):
        # quantized pages (payload + scale band) travel verbatim and
        # land in a kv-head-sharded pool on the adopting engine
        solo, adopted = _handoff(kv_dtype="int8", tp_degree=2)
        assert adopted == solo

    def test_harvest_unknown_rid_refused(self):
        eng = ServingEngine(_llama(), max_batch=4, max_seq_len=128)
        with pytest.raises(ValueError, match="not seated"):
            eng.harvest_request(12345)


class TestCrossProcessHandoff:
    """The same harvest/adopt pair across a REAL process boundary
    (multiprocessing spawn): the bundle must survive pickle with every
    KV page byte-identical, and the child's continuation must equal the
    solo stream — in-process handoff tests pass by reference and cannot
    catch a device array or a bound callback riding in the bundle."""

    def test_spawn_roundtrip_bit_identical(self):
        solo, bundle, kw = _harvest_midstream()
        report = transport.assert_bundle_transportable(bundle)
        assert report.n_arrays >= 2       # >=1 page -> k and v payloads
        adopted = transport.adopt_and_decode_in_child(bundle,
                                                      engine_kw=kw)
        assert adopted == solo

    def test_spawn_roundtrip_int8_kv(self):
        # quantized pages (payload + scale band) must cross the
        # boundary verbatim — a re-quantization on adopt would drift
        solo, bundle, kw = _harvest_midstream(kv_dtype="int8")
        transport.assert_bundle_transportable(bundle)
        adopted = transport.adopt_and_decode_in_child(bundle,
                                                      engine_kw=kw)
        assert adopted == solo
