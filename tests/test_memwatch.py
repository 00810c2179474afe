"""memwatch (paddle_tpu/observability/memory.py): compiled-program
memory capture, the live KV-pool ledger, the analytic estimator vs
XLA's CompiledMemoryStats, the Perfetto counter track, the zero-residue
contract, and the MEMWATCH regression gate.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags, observability as obs
from paddle_tpu.generation.program_cache import clear_decode_program_cache
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.kernels.paged_attention import PagedKVCache
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.observability import memory as memwatch
from paddle_tpu.testing import faults

pytestmark = pytest.mark.memwatch

TOL = 0.10          # the regression gate's growth bar
# the estimator's ARITHMETIC sections (argument, alias, output: aval
# walks) against XLA. Its fitted temp term is held by no test: the
# constants were fitted to one jax's CPU buffer assignment and read
# two thirds under this one's (ROADMAP Queue 3 item 7)
ARITH_TOL = 0.02


@pytest.fixture(autouse=True)
def _armed_memwatch():
    """Each test runs with memwatch ON (conftest turns it off
    suite-wide to keep tier-1 wall clock — capture costs a duplicate
    compile per program) over a fresh registry/ring/table."""
    prior = flags.snapshot(("memwatch",)).as_tuple()
    flags.set_flags({"memwatch": True})
    obs.registry().clear()
    obs.tracer().clear()
    memwatch.clear_program_table()
    clear_decode_program_cache()
    yield
    flags.set_flags(dict(prior))
    obs.registry().clear()
    obs.tracer().clear()
    memwatch.clear_program_table()
    clear_decode_program_cache()


def metric(snap, name):
    return snap["metrics"][name]["series"]


def _llama_engine(seed=91, prompt_lens=(6, 7), tokens=4, **kw):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=48,
                        **kw)
    rng = np.random.default_rng(seed)
    for n in prompt_lens:
        eng.submit(rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                   tokens)
    return eng, cfg


# ------------------------------------------------------- program capture
class TestProgramCapture:
    def test_serving_programs_captured(self):
        eng, cfg = _llama_engine()
        eng.run()
        rows = {r["kind"]: r for r in memwatch.program_table()}
        assert "decode_fused" in rows and "prefill" in rows
        for r in rows.values():
            # every section present and self-consistent
            assert r["argument"] > 0 and r["output"] > 0
            assert r["peak"] == (r["argument"] + r["output"] - r["alias"]
                                 + r["temp"] + r["generated_code"])
            # the donated pools alias: output is dominated by them
            assert r["alias"] > 0 and r["alias"] <= r["output"]
        # ...and the same rows are in the registry snapshot as gauges
        snap = obs.registry().snapshot()
        series = metric(snap, "program_memory_bytes")
        kinds = {(s["labels"]["kind"], s["labels"]["section"])
                 for s in series}
        assert ("decode_fused", "temp") in kinds
        assert ("prefill", "peak") in kinds
        # capture fired once per (re)trace: two prompt lengths = two
        # prefill traces, one decode trace
        assert rows["prefill"]["captures"] == 2
        assert rows["decode_fused"]["captures"] == 1

    def test_chunk_program_captured(self):
        eng, cfg = _llama_engine(prompt_lens=(20,), prefill_chunk=8)
        eng.run()
        rows = {r["kind"]: r for r in memwatch.program_table()}
        assert "prefill_chunk" in rows
        # extra = chunk width + the r18 kv/weight dtype discriminant
        assert rows["prefill_chunk"]["extra"].startswith("8,")
        assert "('kv', 'native')" in rows["prefill_chunk"]["extra"]
        assert rows["prefill_chunk"]["bucket"] == 1

    def test_two_models_do_not_collide(self):
        """Same-shaped programs of different models must keep distinct
        rows (the model label carries the signature prefix)."""
        eng, _ = _llama_engine(prompt_lens=(6,))
        eng.run()
        paddle.seed(92)
        gcfg = GPTConfig.tiny()
        gmodel = GPTForCausalLM(gcfg)
        geng = ServingEngine(gmodel, max_batch=2, page_size=8,
                             max_seq_len=48)
        geng.submit(np.arange(6, dtype=np.int32) % gcfg.vocab_size, 4)
        geng.run()
        prefills = [r for r in memwatch.program_table()
                    if r["kind"] == "prefill"]
        assert len(prefills) == 2
        assert len({r["model"] for r in prefills}) == 2

    def test_train_step_captured(self):
        from paddle_tpu.hapi import TrainStep

        paddle.seed(93)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

        def loss_fn(logits, y):
            import paddle_tpu.nn.functional as F
            return F.cross_entropy(
                logits.reshape([-1, logits.shape[-1]]), y.reshape([-1]))

        step = TrainStep(model, opt, loss_fn=loss_fn)
        rng = np.random.default_rng(7)
        ids = rng.integers(0, cfg.vocab_size, (2, 9))
        x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
        y = paddle.to_tensor(ids[:, 1:].astype(np.int32))
        for _ in range(3):
            step(x, y)
        step.sync()
        rows = [r for r in memwatch.program_table()
                if r["kind"] == "train_step"]
        assert len(rows) == 1
        r = rows[0]
        # model label = signature prefix (serving idiom): class name
        # alone would collide for differently-sized models of one class
        from paddle_tpu.generation.program_cache import model_signature
        assert r["model"] == model_signature(model)[:8]
        assert r["bucket"] == 2
        # train step donates params+opt_state: alias must cover them
        assert r["alias"] > 0
        # one trace -> exactly one capture, three dispatches
        assert r["captures"] == 1 and step.trace_count == 1

    def test_memwatch_off_keeps_other_telemetry(self):
        flags.set_flags({"memwatch": False})
        clear_decode_program_cache()
        eng, _ = _llama_engine(prompt_lens=(6,))
        eng.run()
        snap = obs.registry().snapshot()
        assert "program_memory_bytes" not in snap["metrics"]
        assert memwatch.program_table() == []
        # the rest of telemetry (r09) still flows, incl. the pool ledger
        assert "serving_decode_steps" in snap["metrics"]
        assert "kv_pool_pages" in snap["metrics"]


# ------------------------------------------------------------- estimator
class TestEstimator:
    def _compiled(self, kind, sig=None):
        rows = [r for r in memwatch.program_table() if r["kind"] == kind
                and (sig is None or r["model"] == sig)]
        assert rows, f"no captured {kind} row"
        return rows[0]

    def _check(self, est, row):
        for section in ("argument", "alias", "output"):
            pred, comp = est[section], row[section]
            assert abs(pred - comp) / comp <= ARITH_TOL, \
                f"{row['kind']} {section}: estimated {pred} vs compiled " \
                f"{comp} ({(pred / comp - 1) * 100:+.1f}% > " \
                f"{ARITH_TOL:.0%})"

    def _param_bytes(self, eng):
        pb = sum(memwatch.aval_bytes(v) for v in eng._params.values())
        return pb + sum(memwatch.aval_bytes(v)
                        for v in eng._buffers.values() if v is not None)

    def test_decode_estimate_fused_llama(self):
        eng, cfg = _llama_engine(prompt_lens=(6,))
        eng.run()
        dims = memwatch.ModelDims.of_config(cfg)
        geom = memwatch.PoolGeometry.of_pool(eng.pool)
        est = memwatch.estimate_decode_program(
            dims, geom, eng.bucket, self._param_bytes(eng))
        self._check(est, self._compiled("decode_fused"))

    def test_decode_estimate_fused_llama_int8_kv(self):
        """The quantized program rides the same bar: the estimator
        prices the int8 pool (payload + scale rows)."""
        eng, cfg = _llama_engine(prompt_lens=(6,), kv_dtype="int8")
        eng.run()
        dims = memwatch.ModelDims.of_config(cfg)
        geom = memwatch.PoolGeometry.of_pool(eng.pool)
        assert geom.kv_quant
        est = memwatch.estimate_decode_program(
            dims, geom, eng.bucket, self._param_bytes(eng))
        self._check(est, self._compiled("decode_fused"))

    def test_decode_estimate_generic_gpt(self):
        paddle.seed(94)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=48)
        eng.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size, 4)
        eng.run()
        dims = memwatch.ModelDims.of_config(cfg)
        geom = memwatch.PoolGeometry.of_pool(eng.pool)
        est = memwatch.estimate_decode_program(
            dims, geom, eng.bucket, self._param_bytes(eng))
        self._check(est, self._compiled("decode_generic"))

    def test_prefill_and_chunk_estimates(self):
        # chunking OFF: the 16-token prompt runs the monolithic S=16
        # prefill program (with chunking on it would chunk at 8)
        eng, cfg = _llama_engine(prompt_lens=(16,), prefill_chunk=0)
        eng.run()
        dims = memwatch.ModelDims.of_config(cfg)
        geom = memwatch.PoolGeometry.of_pool(eng.pool)
        pb = self._param_bytes(eng)
        self._check(memwatch.estimate_prefill_program(dims, geom, 16, pb),
                    self._compiled("prefill"))
        # chunking ON over a long prompt: the fixed (1, 8) chunk program
        # — priced on the r17 copy-free block-table path (no gathered
        # K/V view term)
        eng2, _ = _llama_engine(prompt_lens=(20,), prefill_chunk=8)
        eng2.run()
        self._check(memwatch.estimate_prefill_program(dims, geom, 8, pb,
                                                      chunked=True),
                    self._compiled("prefill_chunk"))

    def test_planner_7b_arithmetic(self):
        dims = memwatch.ModelDims.of_config(LlamaConfig.llama2_7b())
        plan = memwatch.estimate_engine_memory(
            dims, page_size=64, page_budget=512, max_batch=32,
            max_seq_len=2048, chunk=256, weight_dtype="int8",
            kv_dtype="int8")
        b = plan["breakdown"]
        n = dims.param_count
        # int8 weights: 1 byte/param + bounded scale overhead
        assert n <= b["weights"] <= int(n * 1.1)
        # kv pool arithmetic is exact: L * 2 * Hkv * (P+1) * page * D
        # at 1 byte + per-TOKEN f32 amax scales (r18: one scale per
        # cached token row, so replay is write-order independent)
        pool_raw = 32 * 2 * 32 * 513 * 64 * 128
        assert b["kv_pool"] == pool_raw + 32 * 2 * 32 * 513 * 64 * 4
        # verdicts are monotone in the page budget
        small = memwatch.estimate_engine_memory(
            dims, page_size=64, page_budget=64, max_batch=32,
            max_seq_len=2048, chunk=256, weight_dtype="int8",
            kv_dtype="int8")
        assert small["total"] < plan["total"]
        hbm = 16 << 30
        assert memwatch.fits(small, hbm)["fits"]
        big = memwatch.estimate_engine_memory(
            dims, page_size=64, page_budget=4096, max_batch=32,
            max_seq_len=2048, chunk=256, weight_dtype="int8",
            kv_dtype="int8")
        assert not memwatch.fits(big, hbm)["fits"]

    def test_planner_tp_split(self):
        # r19: --tp N prices ONE SHARD — weights split minus the
        # replicated embed/lm_head, the KV pool (incl. the int8 scale
        # band) divides exactly over kv-heads, draft terms replicate
        dims = memwatch.ModelDims.of_config(LlamaConfig.llama2_7b())
        kw = dict(page_size=64, page_budget=512, max_batch=32,
                  max_seq_len=2048, chunk=256, weight_dtype="bfloat16",
                  kv_dtype="bfloat16")
        full = memwatch.estimate_engine_memory(dims, **kw)
        half = memwatch.estimate_engine_memory(dims, tp=2, **kw)
        assert half["config"]["tp"] == 2
        # the acceptance criterion: per-shard weight+KV within 10% of
        # half the tp=1 bill (embed + lm_head replicate, hence > 0.5x)
        got = half["breakdown"]["weights"] + half["breakdown"]["kv_pool"]
        want = (full["breakdown"]["weights"]
                + full["breakdown"]["kv_pool"]) / 2
        assert want <= got <= 1.1 * want
        # pool arithmetic is linear in kv-heads: exactly /2
        assert half["breakdown"]["kv_pool"] * 2 == \
            full["breakdown"]["kv_pool"]
        assert half["total"] < full["total"]
        # int8 scale band divides with its payload
        q = dict(kw, kv_dtype="int8")
        fq = memwatch.estimate_engine_memory(dims, **q)
        hq = memwatch.estimate_engine_memory(dims, tp=2, **q)
        assert hq["breakdown"]["kv_pool"] * 2 == fq["breakdown"]["kv_pool"]
        # draft terms stay replicated (the r16 chain runs un-sharded)
        tiny = memwatch.ModelDims.of_config(LlamaConfig.tiny())
        d = dict(kw, draft_dims=tiny, spec_gamma=4,
                 draft_param_count=tiny.param_count or 1 << 20)
        fd = memwatch.estimate_engine_memory(dims, **d)
        hd = memwatch.estimate_engine_memory(dims, tp=2, **d)
        assert hd["breakdown"]["draft_weights"] == \
            fd["breakdown"]["draft_weights"]
        assert hd["breakdown"]["draft_kv_pool"] == \
            fd["breakdown"]["draft_kv_pool"]
        # indivisible degrees are REFUSED, never rounded
        with pytest.raises(ValueError, match="must divide"):
            memwatch.estimate_engine_memory(dims, tp=3, **kw)
        with pytest.raises(ValueError):
            memwatch.estimate_engine_memory(dims, tp=0, **kw)
        # int4 tiles cannot shard (nibble row-pairing vs the head
        # permutation) — the planner refuses exactly like the engine
        with pytest.raises(ValueError, match="int4"):
            memwatch.estimate_engine_memory(
                dims, tp=2, **dict(kw, weight_dtype="int4"))

    def test_sharded_param_bytes_ceil_division(self):
        from jax.sharding import PartitionSpec as P
        # 10 rows over a 4-way axis pad to 3 rows/device -> 12 f32 bytes
        assert memwatch.sharded_param_bytes(
            (10,), np.float32, P("mp"), {"mp": 4}) == 3 * 4
        # replicated dim untouched; multi-axis entries multiply
        assert memwatch.sharded_param_bytes(
            (8, 6), np.float32, P(("dp", "mp"), None), {"dp": 2, "mp": 2}
        ) == 2 * 6 * 4
        assert memwatch.sharded_param_bytes(
            (8, 6), np.float16, None, {"dp": 2}) == 8 * 6 * 2


# ------------------------------------------------------------ pool ledger
class TestPoolLedger:
    def test_pool_ledger_counts(self):
        pool = PagedKVCache(num_layers=2, num_pages=9, page_size=8,
                            num_kv_heads=2, head_dim=16, max_batch=2,
                            max_seq_len=64, reserve_null_page=True)
        led = pool.ledger()
        assert led["usable_pages"] == 8 and led["pages_in_use"] == 0
        assert led["fragmentation"] == 0.0
        pool.allocate(0, 20)                  # 3 pages
        led = pool.ledger()
        assert led["pages_in_use"] == 3 and led["pages_free"] == 5
        assert led["bytes_in_use"] == 3 * led["bytes_per_page"]
        # share two of them (prefix-cache style extra refs)
        ids = [int(pool.block_tables[0, i]) for i in range(2)]
        for pid in ids:
            pool.ref_page(pid)
        assert pool.ledger()["pages_shared"] == 2
        for pid in ids:
            pool.unref_page(pid)
        assert pool.ledger()["pages_shared"] == 0
        pool.free_sequence(0)
        led = pool.ledger()
        assert led["pages_in_use"] == 0 and led["pages_free"] == 8

    def test_fragmentation_metric(self):
        pool = PagedKVCache(num_layers=1, num_pages=8, page_size=8,
                            num_kv_heads=1, head_dim=16, max_batch=4,
                            max_seq_len=32)
        # free list is one contiguous run
        assert pool.free_list_fragmentation() == 0.0
        pool.allocate(0, 8)
        pool.allocate(1, 8)
        pool.allocate(2, 8)
        pool.free_sequence(1)                 # hole in the middle
        frag = pool.free_list_fragmentation()
        assert 0.0 < frag < 1.0
        led = pool.ledger()
        assert led["fragmentation"] == pytest.approx(frag)

    def test_move_sequence_preserves_ledger(self):
        """Bucket-shrink compaction (r12 move_sequence) is pure
        bookkeeping: the ledger must not move."""
        pool = PagedKVCache(num_layers=1, num_pages=9, page_size=8,
                            num_kv_heads=1, head_dim=16, max_batch=4,
                            max_seq_len=32, reserve_null_page=True)
        pool.allocate(2, 16)
        before = pool.ledger()
        pool.move_sequence(2, 0)
        after = pool.ledger()
        assert after == before

    def test_engine_gauges_track_lifecycle(self):
        eng, cfg = _llama_engine(prompt_lens=(16, 7), tokens=3,
                                 prefix_cache=True)
        eng.step()                            # admission + prefill
        snap = obs.registry().snapshot()
        pages = {s["labels"]["state"]: s["value"]
                 for s in metric(snap, "kv_pool_pages")}
        led = eng.pool.ledger()
        assert pages["used"] == led["pages_in_use"] > 0
        assert pages["free"] == led["pages_free"]
        assert pages["used"] + pages["free"] == led["usable_pages"]
        eng.run()
        snap = obs.registry().snapshot()
        pages = {s["labels"]["state"]: s["value"]
                 for s in metric(snap, "kv_pool_pages")}
        bytes_ = {s["labels"]["state"]: s["value"]
                  for s in metric(snap, "kv_pool_bytes")}
        # drained: only prefix-cache-retained pages remain in use
        assert pages["used"] == eng.pool.ledger()["pages_in_use"]
        assert pages["pinned"] == 0
        assert bytes_["used"] == pages["used"] * eng.pool.bytes_per_page

    def test_shared_pages_gauge_on_prefix_admission(self):
        eng, cfg = _llama_engine(seed=95, prompt_lens=(16,), tokens=3,
                                 prefix_cache=True)
        out = eng.run()
        prompt = None
        # resubmit the identical prompt: shared admission refs its pages
        rng = np.random.default_rng(95)
        prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng.submit(prompt, 3)
        eng.step()
        snap = obs.registry().snapshot()
        pages = {s["labels"]["state"]: s["value"]
                 for s in metric(snap, "kv_pool_pages")}
        series = {s["labels"]["state"]: s["value"] for s in
                  metric(snap, "kv_pool_pages")}
        assert series["shared"] > 0           # adopted prefix pages
        assert pages["pinned"] > 0            # pinned while in flight
        eng.run()
        snap = obs.registry().snapshot()
        series = {s["labels"]["state"]: s["value"] for s in
                  metric(snap, "kv_pool_pages")}
        assert series["shared"] == 0 and series["pinned"] == 0

    def test_ledger_across_bucket_migration(self):
        paddle.seed(96)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        eng = ServingEngine(model, max_batch=4, page_size=8,
                            max_seq_len=32, bucket_ladder=(1, 2, 4))
        rng = np.random.default_rng(96)
        for _ in range(4):
            eng.submit(rng.integers(0, cfg.vocab_size, (5,))
                       .astype(np.int32), 6)
        eng.run()
        assert eng.bucket_migrations > 0
        snap = obs.registry().snapshot()
        pages = {s["labels"]["state"]: s["value"]
                 for s in metric(snap, "kv_pool_pages")}
        assert pages["used"] == 0             # drained, rows compacted
        assert pages["free"] == eng.pool.ledger()["usable_pages"]

    def test_ledger_after_replay_recovery(self):
        with faults.armed("decode_dispatch:every=3",
                          serving_max_retries=8, serving_retry_backoff=0.0):
            eng, cfg = _llama_engine(seed=97, prompt_lens=(6, 7),
                                     tokens=4)
            out = eng.run()
        assert all(eng.status(r) == "OK" for r in out)
        snap = obs.registry().snapshot()
        assert metric(snap, "serving_recoveries")[0]["value"] > 0
        pages = {s["labels"]["state"]: s["value"]
                 for s in metric(snap, "kv_pool_pages")}
        # the FRESH pool's ledger, fully drained
        assert pages["used"] == 0
        assert pages["free"] == eng.pool.ledger()["usable_pages"]

    def test_counter_track_in_chrome_export(self):
        eng, _ = _llama_engine(prompt_lens=(6,))
        eng.run()
        doc = json.loads(json.dumps(obs.tracer().chrome_trace()))
        counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert counters and all(e["name"] == "kv_pool" for e in counters)
        for e in counters:
            assert {"pages_in_use", "bytes_in_use", "pages_shared",
                    "pages_pinned"} <= set(e["args"])
        # the track moved: pages in use rose above the drained tail
        vals = [e["args"]["pages_in_use"] for e in counters]
        assert max(vals) > vals[-1]
        # spans and counters share the timeline
        assert any(e["ph"] == "X" and e["name"] == "engine.decode_step"
                   for e in doc["traceEvents"])


# -------------------------------------------------------- regression gate
class TestRegressionGate:
    def _rows(self):
        eng, _ = _llama_engine(prompt_lens=(6,))
        eng.run()
        rows = memwatch.program_table()
        assert rows
        return rows

    def test_round_trip_passes(self, tmp_path):
        rows = self._rows()
        path = tmp_path / "bank.json"
        path.write_text(json.dumps({"schema": 1, "rows": rows}))
        banked = json.loads(path.read_text())["rows"]
        findings = memwatch.compare_program_rows(banked, rows,
                                                 tolerance=TOL)
        assert [f for f in findings if f["verdict"] == "grew"] == []

    def test_growth_flagged(self):
        rows = self._rows()
        banked = [dict(r) for r in rows]
        # bank a smaller temp: current "grew" past tolerance
        banked[0]["temp"] = int(banked[0]["temp"] / 1.5)
        findings = memwatch.compare_program_rows(banked, rows,
                                                 tolerance=TOL)
        grew = [f for f in findings if f["verdict"] == "grew"]
        assert grew and grew[0]["section"] == "temp"
        assert grew[0]["growth"] == pytest.approx(0.5, abs=0.01)
        # within tolerance: clean
        banked[0]["temp"] = int(rows[0]["temp"] / 1.05)
        findings = memwatch.compare_program_rows(banked, rows,
                                                 tolerance=TOL)
        assert [f for f in findings if f["verdict"] == "grew"] == []

    def test_missing_and_new_are_informational(self):
        rows = self._rows()
        phantom = dict(rows[0])
        phantom["kind"] = "decode_phantom"
        findings = memwatch.compare_program_rows(
            rows + [phantom], rows, tolerance=TOL)
        verdicts = {f["verdict"] for f in findings}
        assert verdicts == {"missing"}
        findings = memwatch.compare_program_rows(
            rows, rows + [phantom], tolerance=TOL)
        assert {f["verdict"] for f in findings} == {"new"}

    @pytest.mark.parametrize("artifact", ["MEMWATCH_r17.json",
                                          "MEMWATCH_r18.json"])
    def test_banked_artifact_is_valid(self, artifact):
        """The checked-in artifacts must stay loadable and carry the
        capture suite's program rows (r17 adds the N-layer grouped
        decode program; r18 adds the int8-KV and int8+int4 quantized
        rows, whose estimates ride the same 10% bar)."""
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), artifact)
        doc = json.load(open(path))
        assert doc["schema"] == 1 and doc["bench"] == "memwatch"
        kinds = {r["kind"] for r in doc["rows"]}
        assert {"decode_fused", "decode_fused_nlayer", "decode_generic",
                "prefill", "prefill_chunk", "train_step"} <= kinds
        for r in doc["rows"]:
            assert r["peak"] >= r["temp"] >= 0
        # banked estimator evidence stays inside the acceptance bar
        for e in doc["estimates"]:
            assert abs(e["rel_err"]) <= TOL
        if artifact == "MEMWATCH_r18.json":
            extras = {r["extra"] for r in doc["rows"]}
            assert any("('kv', 'int8')" in x for x in extras)
            assert any("('wt', 'int4')" in x for x in extras)
            # the quantized rows' estimates are banked, not just rows
            assert any("('kv', 'int8')" in e["extra"]
                       for e in doc["estimates"])
