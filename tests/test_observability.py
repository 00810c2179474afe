"""Runtime telemetry (paddle_tpu/observability): registry correctness,
span tracing, the instrumented serving/train/cache subsystems, and the
TRC007 tracecheck rule ("no telemetry write reachable under trace").
"""

import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.generation.program_cache import (clear_decode_program_cache,
                                                 decode_program_cache)
from paddle_tpu.generation.serving import ServingEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test sees an empty registry/ring; the decode program cache
    is dropped so it binds its instruments on the cleared registry."""
    obs.registry().clear()
    obs.tracer().clear()
    clear_decode_program_cache()
    yield
    obs.registry().clear()
    obs.tracer().clear()
    clear_decode_program_cache()


def metric(snap, name, **labels):
    """The first series of ``name`` (that carries ``labels``)."""
    return next(s for s in snap["metrics"][name]["series"]
                if all(s["labels"].get(k) == v for k, v in labels.items()))


# ------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_and_gauge(self):
        r = obs.registry()
        c = r.counter("t_reqs", "help text")
        c.inc()
        c.inc(2.5)
        g = r.gauge("t_depth")
        g.set(7)
        g.inc()
        g.dec(3)
        snap = r.snapshot()
        assert metric(snap, "t_reqs")["value"] == 3.5
        assert snap["metrics"]["t_reqs"]["help"] == "help text"
        assert metric(snap, "t_depth")["value"] == 5

    def test_families_are_idempotent_and_typed(self):
        r = obs.registry()
        assert r.counter("t_same") is r.counter("t_same")
        with pytest.raises(ValueError):
            r.gauge("t_same")
        with pytest.raises(ValueError):
            r.counter("t_same", labels=("k",))
        # histogram bucket layout is part of the schema: a silent
        # re-registration under different buckets would quantize the
        # second caller's data onto the wrong ladder
        h = r.histogram("t_same_h", buckets=(0.1, 1.0))
        assert r.histogram("t_same_h", buckets=(0.1, 1.0)) is h
        with pytest.raises(ValueError):
            r.histogram("t_same_h", buckets=(0.5, 5.0))

    def test_labels(self):
        r = obs.registry()
        fam = r.counter("t_hits", labels=("kind",))
        fam.labels(kind="a").inc()
        fam.labels(kind="a").inc()
        fam.labels(kind="b").inc(5)
        with pytest.raises(ValueError):
            fam.labels(wrong="x")
        series = {tuple(s["labels"].items()): s["value"]
                  for s in r.snapshot()["metrics"]["t_hits"]["series"]}
        assert series[(("kind", "a"),)] == 2
        assert series[(("kind", "b"),)] == 5

    def test_histogram_buckets_and_quantiles(self):
        h = obs.registry().histogram(
            "t_lat", buckets=obs.exponential_buckets(0.001, 2.0, 10))
        for v in (0.0015, 0.003, 0.003, 0.1):
            h.observe(v)
        entry = metric(obs.registry().snapshot(), "t_lat")
        assert entry["count"] == 4
        assert entry["counts"][-1] == 0           # nothing overflowed
        assert sum(entry["counts"]) == 4
        assert entry["min"] == pytest.approx(0.0015)
        assert entry["max"] == pytest.approx(0.1)
        p50 = obs.series_quantile(entry, 0.5)
        assert 0.0015 <= p50 <= 0.004
        # quantiles clamp to the observed range
        assert obs.series_quantile(entry, 0.99) <= 0.1
        assert h.quantile(0.5) == p50

    def test_histogram_overflow_bucket(self):
        h = obs.registry().histogram("t_over",
                                     buckets=(0.1, 0.2))
        h.observe(99.0)
        entry = metric(obs.registry().snapshot(), "t_over")
        assert entry["counts"] == [0, 0, 1]
        assert obs.series_quantile(entry, 0.5) == pytest.approx(99.0)

    def test_snapshot_json_round_trip(self):
        h = obs.registry().histogram("t_rt")
        h.observe(0.01)
        h.observe(0.02)
        snap = json.loads(json.dumps(obs.registry().snapshot()))
        entry = metric(snap, "t_rt")
        assert entry["count"] == 2
        assert obs.series_quantile(entry, 0.5) is not None

    def test_prometheus_text(self):
        r = obs.registry()
        r.counter("t_c", "a counter").inc(3)
        fam = r.histogram("t_h", labels=("k",), buckets=(0.1, 1.0))
        fam.labels(k="x").observe(0.5)
        text = obs.to_prometheus()
        assert "# TYPE t_c counter" in text
        assert "t_c 3" in text
        assert 't_h_bucket{k="x",le="0.1"} 0' in text
        assert 't_h_bucket{k="x",le="1"} 1' in text
        assert 't_h_bucket{k="x",le="+Inf"} 1' in text
        assert 't_h_count{k="x"} 1' in text


# ---------------------------------------------------------------- spans
class TestSpans:
    def test_nesting_containment(self):
        tr = obs.tracer()
        with tr.span("outer", a=1):
            with tr.span("inner"):
                pass
        ev = {e["name"]: e for e in tr.events()}
        o, i = ev["outer"], ev["inner"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
        assert o["args"] == {"a": 1}

    def test_chrome_trace_schema(self, tmp_path):
        tr = obs.tracer()
        with tr.span("s1"):
            pass
        tr.event("retro", 1.0, 2.0, rid=4)
        path = tmp_path / "trace.json"
        tr.save(str(path))
        doc = json.loads(path.read_text())
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        for e in doc["traceEvents"]:
            assert e["ph"] == "X"
            assert {"name", "ts", "dur", "pid", "tid", "args", "id",
                    "parent"} <= set(e)
            assert e["dur"] >= 0
        retro = [e for e in doc["traceEvents"] if e["name"] == "retro"][0]
        assert retro["dur"] == pytest.approx(1e6)
        assert retro["args"]["rid"] == 4

    def test_ids_and_parents(self):
        """A span's parent is the span open on the same thread when it
        began; a retroactive event nests under nothing unless told."""
        import threading

        tr = obs.tracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                tr.event("retro", 1.0, 2.0)
                tr.event("retro_inside", 1.0, 2.0, parent=inner.id)
            other = threading.Thread(
                target=lambda: tr.span("elsewhere").__enter__().__exit__())
            other.start()
            other.join(timeout=10)
            with tr.span("sibling"):
                pass
        with tr.span("after"):
            pass
        ev = {e["name"]: e for e in tr.events()}
        assert len({e["id"] for e in ev.values()}) == len(ev)   # unique
        assert all(e["id"] > 0 for e in ev.values())
        assert ev["outer"]["parent"] == 0 and ev["after"]["parent"] == 0
        assert ev["inner"]["parent"] == ev["outer"]["id"] == outer.id
        assert ev["sibling"]["parent"] == outer.id
        assert ev["retro"]["parent"] == 0
        assert ev["retro_inside"]["parent"] == inner.id
        # the stack of open spans is the thread's own
        assert ev["elsewhere"]["parent"] == 0

    def test_capacity_tells_a_reader_when_the_ring_may_have_wrapped(self):
        tr = obs.SpanTracer(capacity=3)
        assert tr.capacity == 3
        tr.event("a", 0.0, 0.1)
        assert len(tr) < tr.capacity
        for _ in range(5):
            tr.event("b", 0.0, 0.1)
        assert len(tr) == tr.capacity

    def test_decorator_form(self):
        calls = []

        @obs.tracer().span("deco")
        def f(x):
            calls.append(x)
            return x + 1

        assert f(1) == 2 and f(2) == 3
        assert [e["name"] for e in obs.tracer().events()] == ["deco", "deco"]

    def test_ring_is_bounded(self):
        tr = obs.SpanTracer(capacity=4)
        for i in range(10):
            tr.event(f"e{i}", 0.0, 0.1)
        names = [e["name"] for e in tr.events()]
        assert names == ["e6", "e7", "e8", "e9"]

    def test_record_event_mirrors_into_ring(self):
        from paddle_tpu.profiler import RecordEvent
        with RecordEvent("user_scope"):
            pass
        assert [e["name"] for e in obs.tracer().events()] == ["user_scope"]


# ------------------------------------------------------- the step clock
class _Clock:
    """The injected clock of ``tracing._now``: it moves when told."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


@pytest.fixture
def clock(monkeypatch):
    from paddle_tpu.observability import tracing
    c = _Clock()
    monkeypatch.setattr(tracing, "_now", c)
    return c


RECORD_COUNTS = ("length_s", "mean_s", "outside_s", "baseline_age_s",
                 "thread_cpu_s",
                 "process_cpu_s", "voluntary_switches",
                 "involuntary_switches", "major_faults", "gc_s", "gc_count",
                 "builds", "cpus", "wall_time")


class TestStepClock:
    def _scope(self, tr, kind, name):
        return tr.phase(name) if kind == "phase" else tr.span(name)

    @pytest.mark.parametrize("kind", ["phase", "span"])
    def test_a_scope_feeds_its_phase(self, clock, kind):
        """Calls, seconds and the longest are whole lengths; a phase
        scope and a span feed the same account."""
        tr = obs.SpanTracer(capacity=16)
        for dt in (0.002, 0.005, 0.003):
            with self._scope(tr, kind, "engine.decode.dispatch"):
                clock.tick(dt)
        acct = tr.phases()["engine.decode.dispatch"]
        assert acct["calls"] == 3
        assert acct["seconds"] == pytest.approx(0.010)
        assert acct["longest"] == pytest.approx(0.005)
        # only the span left ring records
        assert len(tr) == (3 if kind == "span" else 0)

    @pytest.mark.parametrize("outer, inner", [
        ("phase", "phase"), ("span", "phase"), ("phase", "span"),
        ("span", "span")])
    def test_nesting_gives_self_seconds_in_the_open_step(self, clock, outer,
                                                         inner):
        tr = obs.SpanTracer(capacity=16)
        step = tr.step_clock("serving")
        step.begin()
        with self._scope(tr, outer, "engine.decode.stage"):
            clock.tick(0.001)
            with self._scope(tr, inner, "engine.decode.stage.put"):
                clock.tick(0.004)
            with self._scope(tr, inner, "engine.decode.stage.put"):
                clock.tick(0.002)
        ph = tr.phases()
        assert ph["engine.decode.stage"]["seconds"] == pytest.approx(0.007)
        assert ph["engine.decode.stage"]["in_step"] == pytest.approx(0.001)
        assert ph["engine.decode.stage.put"]["in_step"] == \
            pytest.approx(0.006)
        assert ph["engine.decode.stage.put"]["longest"] == \
            pytest.approx(0.004)
        assert step.end(1) == (pytest.approx(0.007), 0.0)
        # the next step opens every phase's in_step anew
        step.begin()
        assert tr.phases()["engine.decode.stage.put"]["in_step"] == 0.0
        assert tr.phases()["engine.decode.stage.put"]["calls"] == 2

    def test_the_same_phase_nests_in_itself_and_across_threads(self, clock):
        import threading

        tr = obs.SpanTracer(capacity=4)
        with tr.phase("p"):
            clock.tick(0.001)
            with tr.phase("p"):
                clock.tick(0.002)
            other = threading.Thread(
                target=lambda: tr.phase("p").__enter__().__exit__())
            other.start()
            other.join(timeout=10)
            assert not other.is_alive()
        acct = tr.phases()["p"]
        assert acct["calls"] == 3
        assert acct["seconds"] == pytest.approx(0.002 + 0.003 + 0.0)
        assert acct["longest"] == pytest.approx(0.003)

    @pytest.mark.parametrize("kind", ["phase", "span"])
    def test_the_annotation_of_the_same_name_is_entered(self, monkeypatch,
                                                        kind):
        """A device capture holds the scope under its plain name."""
        from paddle_tpu.observability import tracing
        seen = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(tracing, "_ANNOTATION", Annotation)
        tr = obs.SpanTracer(capacity=4)
        with self._scope(tr, kind, "engine.decode.pull"):
            assert seen == [("enter", "engine.decode.pull")]
        assert seen == [("enter", "engine.decode.pull"),
                        ("exit", "engine.decode.pull")]

    def test_a_phase_scope_reaches_a_profiler_trace(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.tracer().phase("engine.decode.stage.put"):
                pass
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        names = {e.name for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events}
        assert "engine.decode.stage.put" in names
        assert not obs.tracer().events()

    def _steady(self, clock, tr, step, n, dt=0.005):
        """``n`` steps of ``dt``; the seconds over, step by step."""
        over = []
        for i in range(n):
            step.begin()
            with tr.span("engine.step"):
                with tr.phase("engine.decode.dispatch"):
                    clock.tick(dt)
            length, late = step.end(i)
            assert length == pytest.approx(dt)
            over.append(late)
        return over

    def test_one_slow_step_after_sixty_four_steady_ones(self, clock, caplog):
        import logging

        from paddle_tpu.observability import tracing
        tr = obs.SpanTracer(capacity=8)     # the ring wraps many times
        step = tr.step_clock("serving")
        over = self._steady(clock, tr, step, tracing.SLOW_STEP_HISTORY)
        # a steady run flags nothing
        assert not any(over) and tr.slow_steps() == []
        step.begin()
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.observability.tracing"):
            with tr.span("engine.step"):
                with tr.phase("engine.decode.stage.put"):
                    clock.tick(0.45)
                with tr.phase("engine.decode.dispatch"):
                    clock.tick(0.05)
            length, over = step.end(64)
        assert length == pytest.approx(0.5)
        assert over == pytest.approx(0.5 - 0.005)
        (rec,) = tr.slow_steps()
        assert (rec["kind"], rec["step"]) == ("serving", 64)
        assert rec["length_s"] == pytest.approx(0.5)
        assert rec["mean_s"] == pytest.approx(0.005)
        assert max(rec["phases"], key=rec["phases"].get) == \
            "engine.decode.stage.put"
        assert sum(rec["phases"].values()) + rec["outside_s"] == \
            pytest.approx(rec["length_s"])
        assert rec["outside_s"] == pytest.approx(0.0, abs=1e-9)
        # logged once, on one line
        lines = [r.getMessage() for r in caplog.records]
        assert len(lines) == 1 and "\n" not in lines[0]
        assert "slow serving step 64" in lines[0]
        assert json.loads(lines[0].split(": ", 2)[2])["step"] == 64
        # the steps after it are held against a mean that holds it
        assert not any(self._steady(clock, tr, step, 3))
        assert len(tr.slow_steps()) == 1
        assert len(tr) == tr.capacity       # the ring wrapped; it stayed
        tr.clear()
        assert len(tr.slow_steps()) == 1

    def test_every_field_of_a_record_is_there_and_not_negative(self, clock):
        tr = obs.SpanTracer(capacity=4)
        step = tr.step_clock("train")
        step.begin(builds=3)
        clock.tick(0.004)
        with tr.phase("train.dispatch"):
            clock.tick(0.2)
        step.end(7, builds=5)           # two programs built in the step
        (rec,) = tr.slow_steps()        # no history: 0.2 s alone is slow
        assert set(rec) == set(RECORD_COUNTS) | {
            "kind", "step", "phases", "loadavg", "bytes_in_use",
            "largest_free_block_bytes"}
        for key in RECORD_COUNTS:
            assert rec[key] >= 0, key
        assert rec["builds"] == 2 and rec["step"] == 7
        assert rec["outside_s"] == pytest.approx(0.004)
        assert len(rec["loadavg"]) == 3 and min(rec["loadavg"]) >= 0
        # the device's memory where the backend reports it (not the CPU)
        for key in ("bytes_in_use", "largest_free_block_bytes"):
            assert rec[key] is None or rec[key] >= 0
        assert json.loads(json.dumps(rec)) == rec

    @pytest.mark.parametrize("dt, ratio, slow", [
        (0.09, 30.0, False),    # thirty times the mean, under 0.1 s
        (0.50, 2.5, False),     # half a second, under 3 times the mean
        (0.50, 3.5, True)])
    def test_the_rule_needs_both_the_floor_and_the_ratio(self, clock, dt,
                                                         ratio, slow):
        from paddle_tpu.observability import tracing
        tr = obs.SpanTracer(capacity=4)
        step = tr.step_clock("serving")
        first = self._steady(clock, tr, step, 10, dt=dt / ratio)
        # with no step before it the floor alone decides the first
        assert [late > 0 for late in first] == \
            [dt / ratio > tracing.SLOW_STEP_MIN_S] + [False] * 9
        before = len(tr.slow_steps())
        step.begin()
        clock.tick(dt)
        assert (step.end(10)[1] > 0) is slow
        assert len(tr.slow_steps()) - before == int(slow)

    def test_the_mean_is_of_the_last_sixty_four(self, clock):
        from paddle_tpu.observability import tracing
        tr = obs.SpanTracer(capacity=4)
        step = tr.step_clock("serving")
        self._steady(clock, tr, step, 5, dt=1.0)   # long ago: compiles
        self._steady(clock, tr, step, tracing.SLOW_STEP_HISTORY - 5,
                     dt=0.01)
        step.begin()
        clock.tick(0.2)
        assert step.end(0)[1] == 0.0    # the compiles are among the 64
        self._steady(clock, tr, step, tracing.SLOW_STEP_HISTORY, dt=0.01)
        step.begin()
        clock.tick(0.2)
        assert step.end(0)[1] == pytest.approx(0.19)

    def test_the_deque_holds_sixty_four(self, clock):
        from paddle_tpu.observability import tracing
        tr = obs.SpanTracer(capacity=4)
        for i in range(tracing.SLOW_STEP_HISTORY + 6):
            step = tr.step_clock("serving")     # no history: slow at once
            step.begin()
            clock.tick(0.2)
            step.end(i)
        steps = [r["step"] for r in tr.slow_steps()]
        assert steps == list(range(6, tracing.SLOW_STEP_HISTORY + 6))

    def test_the_system_calls_are_read_at_most_every_fifty_ms(
            self, clock, monkeypatch):
        """A short step does not pay the thread's CPU time and switch
        counts every time; a record says how old its baselines were."""
        from paddle_tpu.observability import tracing
        reads = []
        monkeypatch.setattr(tracing, "_thread_usage",
                            lambda: reads.append(1) or (0, 0, 0))
        tr = obs.SpanTracer(capacity=4)
        step = tr.step_clock("serving")
        self._steady(clock, tr, step, 25, dt=0.004)    # 0.1 s of steps
        assert len(reads) == 2      # at 0.0 and at 0.052 s
        step.begin()                # at 0.100 s: 0.048 s after the last
        clock.tick(0.3)
        step.end(25)
        (rec,) = tr.slow_steps()
        assert rec["baseline_age_s"] == pytest.approx(0.048)
        assert len(reads) == 3      # and once more, for the record
        step.begin()                # a step of 50 ms or more: every time
        assert len(reads) == 4
        clock.tick(0.3)
        step.end(26)
        assert [r["baseline_age_s"] for r in tr.slow_steps()] == \
            [pytest.approx(0.048), 0.0]

    def test_collections_inside_a_step_are_counted(self, clock):
        import gc

        tr = obs.SpanTracer(capacity=4)
        step = tr.step_clock("serving")
        step.begin()
        gc.collect()
        gc.collect()
        clock.tick(0.3)
        step.end(0)
        (rec,) = tr.slow_steps()
        assert rec["gc_count"] >= 2 and rec["gc_s"] > 0

    def test_clocks_in_turn_read_their_own_steps_phases(self, clock):
        """Two engines of one process step in turn on one thread."""
        tr = obs.SpanTracer(capacity=4)
        a, b = tr.step_clock("serving"), tr.step_clock("serving")
        for step, dt in ((a, 0.001), (b, 0.002), (a, 0.003)):
            step.begin()
            with tr.phase("engine.decode.dispatch"):
                clock.tick(dt)
            assert tr.phase("engine.decode.dispatch").in_step == \
                pytest.approx(dt)
            step.end(0)


# ------------------------------------------------- serving lifecycle
def _run_engine(model, cfg, n_req=3, tokens=5, **engine_kw):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (4 + 3 * i,))
               .astype(np.int32) for i in range(n_req)]
    eng = ServingEngine(model, max_batch=2, page_size=8, max_seq_len=48,
                        **engine_kw)
    for p in prompts:
        eng.submit(p, tokens)
    out = eng.run()
    return eng, out


class TestServingTelemetry:
    def _check_lifecycle(self, model, cfg, expected_kind):
        n_req, tokens = 3, 5
        eng, out = _run_engine(model, cfg, n_req, tokens)
        assert eng.decode_key.kind == expected_kind
        snap = obs.registry().snapshot()
        assert metric(snap, "serving_requests_submitted")["value"] == n_req
        assert metric(snap, "serving_requests_finished")["value"] == n_req
        assert metric(snap, "serving_prefills")["value"] == n_req
        # one TTFT per request; ITL covers every later token
        assert metric(snap, "serving_ttft_seconds")["count"] == n_req
        total = sum(len(v) for v in out.values())
        assert metric(snap, "serving_inter_token_seconds")["count"] == \
            total - n_req
        assert obs.series_quantile(
            metric(snap, "serving_ttft_seconds"), 0.99) is not None
        assert metric(snap, "serving_queue_depth")["value"] == 0
        assert metric(snap, "serving_kv_pages_in_use")["value"] == 0
        assert metric(snap, "serving_decode_steps")["value"] > 0
        # a complete per-request timeline in the span ring
        names = [e["name"] for e in obs.tracer().events()]
        assert names.count("request.queued") == n_req
        assert names.count("request.prefill") == n_req
        assert names.count("request.complete") == n_req
        assert names.count("engine.decode_step") == \
            metric(snap, "serving_decode_steps")["value"]
        completes = [e for e in obs.tracer().events()
                     if e["name"] == "request.complete"]
        assert sorted(e["args"]["rid"] for e in completes) == list(out)
        # zero steady-state retraces, now visible in the snapshot
        traces = {s["labels"]["kind"]: s["value"] for s in
                  snap["metrics"]["program_cache_traces"]["series"]}
        assert traces[expected_kind] == 1
        # chrome export is valid JSON with the same events
        doc = json.loads(json.dumps(obs.tracer().chrome_trace()))
        assert len(doc["traceEvents"]) == len(names)

    def test_lifecycle_fused_decode_path(self):
        paddle.seed(81)
        cfg = LlamaConfig.tiny()
        self._check_lifecycle(LlamaForCausalLM(cfg), cfg, "decode_fused")

    def test_lifecycle_generic_decode_path(self):
        paddle.seed(82)
        cfg = GPTConfig.tiny()
        self._check_lifecycle(GPTForCausalLM(cfg), cfg, "decode_generic")

    def test_prefix_cache_hit_miss_counters(self):
        paddle.seed(83)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, cfg.vocab_size, (19,)).astype(np.int32)
        eng = ServingEngine(model, max_batch=1, page_size=8,
                            max_seq_len=64, prefix_cache=True)
        eng.submit(prompt, 4)
        eng.run()
        eng.submit(prompt.copy(), 4)      # identical prompt: shared admit
        eng.run()
        snap = obs.registry().snapshot()
        assert metric(snap, "prefix_cache_misses")["value"] == 1
        assert metric(snap, "prefix_cache_hits")["value"] == 1
        assert metric(snap, "prefix_cache_hit_pages")["value"] == 2
        assert metric(snap, "prefix_cache_registered_pages")["value"] >= 2
        assert metric(snap, "serving_shared_admissions")["value"] == 1

    def test_evict_shortfall_records_pinned_pressure(self):
        """A pool too tight to admit while cached pages are pinned must
        bank the shortfall + pinned-page gauge instead of silently
        under-freeing (the old callers dropped evict()'s return)."""
        paddle.seed(84)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        rng = np.random.default_rng(6)
        p_long = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
        # pool: null + 4 usable pages; the 16-token prompt + 8 new takes 3
        eng = ServingEngine(model, max_batch=2, page_size=8,
                            max_seq_len=24, num_pages=5, prefix_cache=True)
        eng.submit(p_long, 6)
        eng.step()                         # admitted; 2 prompt pages cached
        eng.submit(rng.integers(0, cfg.vocab_size, (16,))
                   .astype(np.int32), 6)   # needs 3 pages; 1 free; evict
        eng.step()                         # shortfall: pages rc>1 + pinned
        snap = obs.registry().snapshot()
        assert metric(snap, "serving_prefix_evict_shortfall_pages")[
            "value"] > 0
        eng.run()

    def test_program_cache_compile_time_banked(self):
        paddle.seed(85)
        cfg = GPTConfig.tiny()
        _run_engine(GPTForCausalLM(cfg), cfg, n_req=2)
        cache = decode_program_cache()
        stats = cache.stats()
        assert stats["compile_seconds"]            # some key was charged
        assert all(v > 0 for v in stats["compile_seconds"].values())
        snap = obs.registry().snapshot()
        series = {s["labels"]["kind"]: s for s in
                  snap["metrics"]["program_cache_compile_seconds"]["series"]}
        assert series["decode_generic"]["count"] == 1
        assert series["decode_generic"]["sum"] > 0
        # a second engine over the same model reuses both programs
        paddle.seed(85)
        _run_engine(GPTForCausalLM(cfg), cfg, n_req=2)
        assert metric(obs.registry().snapshot(),
                      "program_cache_hits")["value"] >= 2


# ------------------------------------------- the step's spans and counts
RING_ONLY = {"request.queued", "request.first_token", "request.complete",
             "engine.spec_round"}
# scopes of the profiler alone: a device capture holds them, the ring
# does not
STAGE_PARTS = ["engine.decode.stage.inputs", "engine.decode.stage.put",
               "engine.decode.stage.caches"]
DECODE_PARTS = ["engine.decode.stage", "engine.decode.dispatch",
                "engine.decode.pull"]


def _spans():
    return [e for e in obs.tracer().events() if e["ph"] == "X"]


def _scripted_engine(**kw):
    """GPT-tiny, four slots on a (2, 4) ladder, prompts over 8 tokens
    chunked by 8, shrink after one step of lower demand."""
    paddle.seed(90)
    cfg = GPTConfig.tiny()
    eng = ServingEngine(GPTForCausalLM(cfg), max_batch=4, page_size=8,
                        max_seq_len=64, bucket_ladder=(2, 4),
                        prefill_chunk=8, **kw)
    eng.bucket_patience = 1
    rng = np.random.default_rng(11)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
    return eng, prompt


def _scripted_run():
    """Three requests whose every step can be counted by hand:

    step 1  demand 3 -> rung 4; r0 (5 tokens) prefills whole, r1 (19) is
            seated for chunking, r2 (6) waits for the prefill unit;
            decode: r0 alone, 5 cached tokens, 4 slots; left in flight
    step 2  r2 prefills whole; decode: r0 (6 cached) and r2 (6), 4 slots,
            dispatched BEHIND step 1's, whose token (r0's second) is
            read after that dispatch
    step 3  all three still seated, rung 4; r1's chunk 0..8; nothing to
            dispatch (the step in flight fills r0's and r2's budgets), so
            that step is read: both finish (r0 3 tokens, r2 2)
    step 4  demand 1 -> rung 2; r1's chunk 8..16; nothing decodes
    step 5  r1's last chunk 16..19, padded to 8; decode: r1, 19 cached
            tokens, 2 slots; left in flight
    step 6  nothing to dispatch: the step in flight is read, r1
            finishes (2 tokens)
    """
    eng, prompt = _scripted_engine()
    rids = [eng.submit(prompt(5), 3), eng.submit(prompt(19), 2),
            eng.submit(prompt(6), 2)]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    return eng, rids, steps


class TestStepSpans:
    def test_counters_equal_a_hand_count(self):
        eng, rids, steps = _scripted_run()
        assert steps == 6
        assert [len(eng.results()[r]) for r in rids] == [3, 2, 2]
        snap = obs.registry().snapshot()
        assert metric(snap, "serving_decode_steps")["value"] == 3
        assert metric(snap, "serving_decode_rows")["value"] == 1 + 2 + 1
        # the rung of each step that decoded: 4, 4, then 2
        assert metric(snap, "serving_decode_slots")["value"] == 4 + 4 + 2
        assert metric(snap, "serving_decode_live_tokens")["value"] == \
            5 + (6 + 6) + 19
        # pages the kernel reads, over every row of the rung (page 8;
        # an idle or not-yet-chunked row reads one): 1+1+1+1, 1+1+1+1,
        # then 3+1; the table is rung x 8 pages a row
        assert metric(snap, "serving_decode_read_pages")["value"] == \
            4 + 4 + 4
        assert metric(snap, "serving_decode_table_pages")["value"] == \
            (4 + 4 + 2) * 8
        # the last chunk's five pad positions are not prompt tokens
        assert metric(snap, "serving_prefill_tokens")["value"] == 5 + 6 + 19
        assert metric(snap, "serving_prefills")["value"] == 3
        assert metric(snap, "serving_bucket_migrations")["value"] == 2
        # one step went out behind a step in flight; two were read with
        # nothing behind them, for want of a row to dispatch
        assert metric(snap, "serving_decode_overlapped")["value"] == 1
        assert metric(snap, "serving_decode_settles",
                      reason="drained")["value"] == 2

    def test_every_span_of_a_step_nests_in_its_engine_step(self):
        _scripted_run()
        spans = {e["id"]: e for e in _spans()}
        roots = [e for e in spans.values() if e["name"] == "engine.step"]
        assert [e["args"]["step"] for e in roots] == [1, 2, 3, 4, 5, 6]
        assert all(e["parent"] == 0 for e in roots)
        seen = set()
        for e in spans.values():
            if e["name"] in RING_ONLY or e["name"] == "engine.step":
                continue
            seen.add(e["name"])
            # up the parent chain to the root, each span inside its parent
            cur = e
            while cur["parent"]:
                up = spans[cur["parent"]]
                assert up["ts"] <= cur["ts"] + 1e-3
                assert cur["ts"] + cur["dur"] <= up["ts"] + up["dur"] + 1e-3
                cur = up
            assert cur["name"] == "engine.step", e["name"]
            if "step" in e["args"]:
                assert e["args"]["step"] == cur["args"]["step"]
        assert seen == {"engine.schedule", "engine.migrate", "engine.admit",
                        "request.prefill", "engine.prefill_chunk",
                        "engine.decode_step", "engine.emit",
                        "engine.callbacks", "engine.ledger"}
        by_name = {}
        for e in spans.values():
            by_name.setdefault(e["name"], []).append(e)
        # the prefill is the admission's child, the migration the
        # scheduler's; one decode span (not also an event) a decode step
        assert all(spans[e["parent"]]["name"] == "engine.admit"
                   for e in by_name["request.prefill"])
        assert all(spans[e["parent"]]["name"] == "engine.schedule"
                   for e in by_name["engine.migrate"])
        assert [(e["args"]["from"], e["args"]["to"])
                for e in by_name["engine.migrate"]] == [(2, 4), (4, 2)]
        assert [(e["args"]["active"], e["args"]["bucket"],
                 e["args"]["overlapped"])
                for e in by_name["engine.decode_step"]] == \
            [(1, 4, False), (2, 4, True), (1, 2, False)]
        # a late read emits inside the decode step that went out before
        # it, a settle under the step itself
        assert [(e["args"]["step"], spans[e["parent"]]["name"])
                for e in by_name["engine.emit"]] == \
            [(2, "engine.decode_step"), (3, "engine.step"),
             (6, "engine.step")]
        assert [e["args"]["admitted"] for e in by_name["engine.admit"]] == \
            [2, 1, 0, 0, 0, 0]
        assert [(e["args"]["pos"], e["args"]["last"])
                for e in by_name["engine.prefill_chunk"]] == \
            [(0, False), (8, False), (16, True)]

    def test_decode_parts_show_dispatch_before_the_late_read(self, tmp_path):
        """The decode step's three parts are scopes of the profiler (no
        ring record), and within the one step that overlaps, the
        dispatch of step N+1 comes before the read of step N."""
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            _scripted_run()
        finally:
            jax.profiler.stop_trace()
        assert not {e["name"] for e in _spans()} & set(DECODE_PARTS)
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        seen = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name in DECODE_PARTS or e.name == "engine.step")
        steps = [(a, b) for a, b, name in seen if name == "engine.step"]
        assert len(steps) == 6
        per_step = [[name.rsplit(".", 1)[1] for a, _b, name in seen
                     if name != "engine.step" and lo <= a <= hi]
                    for lo, hi in steps]
        assert per_step == [
            ["stage", "dispatch"],              # left in flight
            ["stage", "dispatch", "pull"],      # N+1 out, then N read
            ["pull"],                           # nothing to dispatch
            [],
            ["stage", "dispatch"],
            ["pull"]]
        # the staging's three parts and the prefill's wait are on the
        # same clock: each inside a staging, in order; one wait a prompt
        # that ends in a token (two whole prefills, one final chunk)
        events = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name in STAGE_PARTS + ["engine.decode.stage",
                                        "engine.prefill.pull"])
        stagings = [(a, b) for a, b, name in events
                    if name == "engine.decode.stage"]
        assert [[name.rsplit(".", 1)[1] for a, b, name in events
                 if name in STAGE_PARTS and lo <= a and b <= hi]
                for lo, hi in stagings] == [["inputs", "put", "caches"]] * 3
        assert [name for _a, _b, name in events].count(
            "engine.prefill.pull") == 3
        assert not {e["name"] for e in _spans()} & set(
            STAGE_PARTS + ["engine.prefill.pull"])

    def test_a_plain_decode_step_leaves_seven_ring_records(self):
        """The phases are accounted beside the ring, not in it."""
        eng, prompt = _scripted_engine()
        eng.submit(prompt(5), 6)
        eng.step()                      # prefill, first decode dispatch
        eng.step()
        obs.tracer().clear()
        eng.step()                      # a plain decode step
        assert sorted(e["name"] for e in _spans()) == sorted([
            "engine.step", "engine.schedule", "engine.admit",
            "engine.decode_step", "engine.emit", "engine.ledger",
            "engine.callbacks"])

    def test_the_step_clock_publishes_every_round(self):
        """``serving_steps`` counts the rounds, ``serving_step_seconds``
        their lengths, and each phase family the phase's seconds: as the
        tracer's accounts have them, the counters' sums inside the
        rounds' whole."""
        import time

        # the tracer's accounts are the process's: this run's share
        t_start, before = time.time(), obs.tracer().phases()
        eng, rids, steps = _scripted_run()
        snap = obs.registry().snapshot()

        def value(name):
            return metric(snap, name, replica="0", tp="1")["value"]

        ph = {name: dict(acct, **{
            k: acct[k] - before.get(name, {}).get(k, 0)
            for k in ("calls", "seconds")})
            for name, acct in obs.tracer().phases().items()}
        assert value("serving_steps") == steps == 6
        assert ph["engine.step"]["calls"] == 6
        assert value("serving_step_seconds") >= ph["engine.step"]["seconds"]
        assert ph["engine.decode.dispatch"]["calls"] == 3
        assert value("serving_decode_dispatch_seconds") == pytest.approx(
            ph["engine.decode.dispatch"]["seconds"])
        for part in ("inputs", "put", "caches"):
            assert ph[f"engine.decode.stage.{part}"]["calls"] == 3
            assert value(f"serving_decode_stage_{part}_seconds") == \
                pytest.approx(ph[f"engine.decode.stage.{part}"]["seconds"])
        assert ph["engine.decode.pull"]["calls"] == 3
        assert ph["engine.prefill.pull"]["calls"] == 3
        assert value("serving_wait_seconds") == pytest.approx(
            ph["engine.decode.pull"]["seconds"]
            + ph["engine.prefill.pull"]["seconds"])
        # a chunk's host seconds leave its token's wait out
        assert ph["engine.prefill_chunk"]["calls"] == 3
        assert 0 < value("serving_prefill_chunk_host_seconds") < \
            ph["engine.prefill_chunk"]["seconds"]
        families = ["serving_decode_dispatch_seconds",
                    "serving_decode_stage_inputs_seconds",
                    "serving_decode_stage_put_seconds",
                    "serving_decode_stage_caches_seconds",
                    "serving_prefill_chunk_host_seconds",
                    "serving_wait_seconds"]
        assert all(value(f) > 0 for f in families)
        assert sum(value(f) for f in families) <= \
            value("serving_step_seconds")
        # the longest of each phase stays with the tracer
        whole = obs.tracer().phases()
        assert all(0 < whole[n]["longest"] <= whole[n]["seconds"]
                   for n in whole if whole[n]["calls"])
        # the slow rounds (the ones that built programs) are on record
        # with what they built, and the counters agree with the records
        slow = [r for r in obs.tracer().slow_steps()
                if r["kind"] == "serving" and r["wall_time"] >= t_start]
        assert value("serving_slow_steps") == len(slow) >= 1
        assert slow[0]["step"] == 1 and slow[0]["builds"] >= 2
        assert value("serving_slow_step_seconds") == pytest.approx(
            sum(r["length_s"] - r["mean_s"] for r in slow))
        for r in slow:
            assert sum(r["phases"].values()) + r["outside_s"] == \
                pytest.approx(r["length_s"])

    def test_every_decode_step_is_overlapped_or_settled(self):
        """``serving_decode_overlapped`` and ``serving_decode_settles``
        over all reasons add up to ``serving_decode_steps`` once nothing
        is in flight, and a decode step leaves few records in the ring:
        7 where nothing is admitted or ends, under 9 on average."""
        eng, prompt = _scripted_engine()
        rids = [eng.submit(prompt(5), 12), eng.submit(prompt(6), 9),
                eng.submit(prompt(7), 12)]
        out = eng.run()
        assert [len(out[r]) for r in rids] == [12, 9, 12]
        assert eng._flying is None
        snap = obs.registry().snapshot()
        steps = metric(snap, "serving_decode_steps")["value"]
        overlapped = metric(snap, "serving_decode_overlapped")["value"]
        settles = {s["labels"]["reason"]: s["value"] for s in
                   snap["metrics"]["serving_decode_settles"]["series"]}
        assert overlapped + sum(settles.values()) == steps
        # the rung fell from 4 to 2 when the 9-token request ended, with
        # a step in flight; the last step was read for want of a row
        assert settles == {"migrate": 1, "drained": 1}
        assert overlapped == steps - 2
        by_step = {}
        for e in _spans():
            by_step.setdefault(e["args"].get("step"), []).append(e["name"])
        decode = [names for names in by_step.values()
                  if "engine.decode_step" in names]
        assert len(decode) == steps
        assert sorted(by_step[6]) == sorted([
            "engine.step", "engine.schedule", "engine.admit",
            "engine.decode_step", "engine.emit", "engine.ledger",
            "engine.callbacks"])
        assert sum(map(len, decode)) <= 9 * len(decode)

    @pytest.mark.parametrize("callback", [True, False],
                             ids=["callback", "polled"])
    @pytest.mark.parametrize("admission", ["monolithic", "chunked",
                                           "shared_prefix"])
    def test_request_life_in_order_with_one_first_token(self, admission,
                                                        callback):
        eng, prompt = _scripted_engine(
            prefix_cache=admission == "shared_prefix")
        seen = []
        on_token = (lambda rid, tok, done: seen.append(rid)) \
            if callback else None
        p = prompt({"monolithic": 6, "chunked": 19, "shared_prefix": 17}[
            admission])
        if admission == "shared_prefix":
            eng.submit(p.copy(), 3)       # leaves its two full pages cached
            eng.run()
        rid = eng.submit(p, 4, on_token=on_token)
        eng.run()
        mine = [e for e in _spans() if e["args"].get("rid") == rid]
        want = {"monolithic": ["request.prefill"],
                "chunked": ["engine.prefill_chunk"] * 3,
                "shared_prefix": []}[admission]
        assert [e["name"] for e in mine] == (
            ["request.queued"] + want
            + ["request.first_token", "request.complete"])
        steps = [e["args"]["step"] for e in mine]
        assert steps == sorted(steps) and steps[0] >= 1
        first = mine[-2]
        # known on the host before it was handed over, inside one step
        assert first["dur"] > 0
        if admission == "shared_prefix":
            snap = obs.registry().snapshot()
            assert metric(snap, "serving_shared_admissions")["value"] == 1
        assert not eng._first_known      # nothing left waiting

    def test_self_times_are_length_less_children(self):
        """tools/telemetry_dump.py --spans: the operator's use of
        ``parent``. The roots' self times and every child's add up to
        the roots' lengths, and a parent's self time is what its
        children leave."""
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "telemetry_dump.py")
        spec = importlib.util.spec_from_file_location("_tdump", path)
        tdump = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tdump)
        _scripted_run()
        events = obs.tracer().events()
        table = tdump.self_times(events)
        in_step = [n for n in table if n not in RING_ONLY]
        assert table["engine.step"][0] == 6
        assert sum(table[n][2] for n in in_step) == pytest.approx(
            table["engine.step"][1], rel=1e-6)
        # the decode step's one child in the ring: the late read's emit
        spans = {e["id"]: e for e in events if e["ph"] == "X"}
        emits = sum(e["dur"] / 1e3 for e in spans.values()
                    if e["name"] == "engine.emit"
                    and spans[e["parent"]]["name"] == "engine.decode_step")
        decode = table["engine.decode_step"]
        assert emits > 0
        assert decode[2] == pytest.approx(decode[1] - emits, abs=1e-6)
        assert "engine.decode_step" in tdump.render_self_times(events)

    def test_span_names_reach_a_profiler_trace(self, tmp_path):
        """Every span the engine and TrainStep put in the ring during a
        step is in a jax.profiler capture too, under its plain name —
        the names benchmark/lib/trace.py attributes idle gaps to."""
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            _scripted_run()
            TestTrainTelemetry()._fit(steps=3, k=2)
        finally:
            jax.profiler.stop_trace()
        in_ring = {e["name"] for e in _spans()} - RING_ONLY
        assert {"engine.step", "engine.decode_step", "train.dispatch",
                "train.stage", "train.pull_metrics"} <= in_ring
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        in_trace = {e.name for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for e in line.events}
        assert in_ring <= in_trace, in_ring - in_trace
        assert set(DECODE_PARTS) <= in_trace - in_ring
        # ring-only records are no host activity: they stay out
        assert not (RING_ONLY & in_trace)


# -------------------------------------------- the block step's telemetry
BLOCK_SPANS = {"engine.block_step", "request.block_commit"}
BLOCK_COUNTERS = {"serving_block_forwards", "serving_block_commits",
                  "serving_block_tokens", "moe_assignments",
                  "moe_experts_touched"}


class TestBlockStepTelemetry:
    def test_a_block_step_leaves_its_spans_and_counters(self, tmp_path):
        """Two requests of a block-diffusion model: a prompt of 6 (one
        whole block, 2 known in the first generated block) for 5 tokens
        and a prompt of 8 for 4. Row-forwards, commits and tokens by
        hand; the shared decode counters keep their meaning."""
        import glob

        import jax
        from jax.profiler import ProfileData
        from paddle_tpu.models import SDARMoEConfig, SDARMoEForCausalLM

        paddle.seed(91)
        cfg = SDARMoEConfig.tiny()
        eng = ServingEngine(SDARMoEForCausalLM(cfg), max_batch=2,
                            page_size=8, max_seq_len=32)
        rng = np.random.default_rng(3)
        jax.profiler.start_trace(str(tmp_path))
        try:
            rids = [eng.submit(rng.integers(0, 100, (n,)).astype(np.int32),
                               new) for n, new in ((6, 5), (8, 4))]
            out = eng.run()
            hist = eng.expert_histogram()
        finally:
            jax.profiler.stop_trace()
        assert [len(out[r]) for r in rids] == [5, 4]
        snap = obs.registry().snapshot()
        value = lambda name: metric(snap, name)["value"]  # noqa: E731
        # request 0: blocks of 2 + 4 new tokens (the second's surplus of
        # one is dropped at emission): (2 + 1) + (4 + 1) forwards;
        # request 1: one block of 4: 4 + 1
        assert value("serving_block_forwards") == 3 + 5 + 5
        assert value("serving_block_commits") == 3
        assert value("serving_block_tokens") == 2 + 4 + 4
        assert value("serving_decode_rows") == 13
        assert value("serving_decode_slots") \
            == 2 * value("serving_decode_steps")
        assert value("serving_prefill_tokens") == 4 + 8
        # every row of the rung routes, and prefill's tokens: (slots x
        # block + prompt tokens) x top-k x layers
        assert value("moe_assignments") == hist.sum() \
            == (value("serving_decode_slots") * 4 + 4 + 8) * 2 * 2
        calls = value("serving_decode_steps") + 2
        assert 2 * 2 * calls <= value("moe_experts_touched") \
            <= 2 * 8 * calls
        spans = _spans()
        names = {e["name"] for e in spans}
        assert BLOCK_SPANS <= names and "engine.decode_step" not in names
        steps = [e for e in spans if e["name"] == "engine.block_step"]
        assert len(steps) == value("serving_decode_steps")
        commits = [e for e in spans if e["name"] == "request.block_commit"]
        assert sorted((e["args"]["rid"], e["args"]["tokens"])
                      for e in commits) == [(rids[0], 2), (rids[0], 3),
                                            (rids[1], 4)]
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        in_trace = {e.name for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for e in line.events}
        assert {"engine.block_step", "engine.block.stage",
                "engine.block.dispatch"} <= in_trace

    def test_experts_served_token_by_token_count_without_blocks(self):
        """The two capabilities are apart: an expert model that does NOT
        generate by blocks (no ``block_spec``; a block of 1 is plain
        causal) is served by the decode step, which returns its expert
        layers' counts; the ``moe_*`` counters bind and move, the block
        step's series and spans do not exist."""
        from paddle_tpu.models import SDARMoEConfig, SDARMoEForCausalLM

        class TokenByToken(SDARMoEForCausalLM):
            block_spec = None

        paddle.seed(92)
        eng = ServingEngine(TokenByToken(SDARMoEConfig.tiny(block_length=1)),
                            max_batch=2, page_size=8, max_seq_len=32)
        rng = np.random.default_rng(4)
        rids = [eng.submit(rng.integers(0, 100, (n,)).astype(np.int32), 3)
                for n in (5, 7)]
        out = eng.run()
        assert [len(out[r]) for r in rids] == [3, 3]
        hist = eng.expert_histogram()
        snap = obs.registry().snapshot()
        value = lambda name: metric(snap, name)["value"]  # noqa: E731
        # prompt tokens and every slot of every decode step route: top-k
        # 2 over 2 layers
        assert value("moe_assignments") == hist.sum() \
            == (5 + 7 + value("serving_decode_slots")) * 2 * 2
        assert value("moe_experts_touched") > 0
        assert eng.expert_histogram() is None       # read and zeroed
        assert not (BLOCK_COUNTERS - {"moe_assignments",
                                      "moe_experts_touched"}) \
            & set(snap["metrics"])
        names = {e["name"] for e in _spans()}
        assert "engine.decode_step" in names and not BLOCK_SPANS & names
        with pytest.raises(ValueError, match="would undercount"):
            ServingEngine(TokenByToken(SDARMoEConfig.tiny(block_length=1)),
                          max_batch=2, page_size=8, max_seq_len=32,
                          draft_model=TokenByToken(
                              SDARMoEConfig.tiny(block_length=1)))

    def test_a_plain_decode_step_leaves_none_of_them(self):
        _scripted_run()
        snap = obs.registry().snapshot()
        assert not BLOCK_COUNTERS & set(snap["metrics"])
        assert not BLOCK_SPANS & {e["name"] for e in _spans()}
        assert metric(snap, "serving_decode_steps")["value"] > 0


def _program_names():
    from jax.sharding import Mesh

    import jax
    from paddle_tpu.generation import serving

    model = object()
    spec = dict(num_heads=4, num_kv_heads=4, rope_theta=1e4, epsilon=1e-6,
                layer_groups=[[0]])

    def mesh():
        return Mesh(np.array(jax.devices()[:2]), ("mp",))
    return {
        "serving_prefill": lambda: serving._build_prefill(None, model),
        "serving_prefill_chunk":
            lambda: serving._build_chunk_prefill(None, model),
        "serving_decode_generic":
            lambda: serving._build_generic_decode(None, model),
        "serving_spec_draft":
            lambda: serving._build_spec_draft(None, model, 2, False, 0),
        "serving_spec_verify":
            lambda: serving._build_spec_verify(None, model, False, 0),
        "serving_decode_fused":
            lambda: serving._build_fused_decode(None, spec, None),
        "serving_decode_fused_nlayer":
            lambda: serving._build_fused_nlayer_decode(None, spec, None),
        "serving_decode_fused_tp":
            lambda: serving._build_fused_nlayer_decode_tp(
                None, spec, None, mesh(), "mp", 2),
        "serving_block_step":
            lambda: serving._build_block_step(None, model, 0),
    }


@pytest.mark.parametrize("name", sorted(_program_names()))
def test_serving_program_is_named_after_its_kind(name):
    """The XLA module of a jitted program is ``jit_<function name>``:
    what a device trace's ``XLA Modules`` line calls it."""
    assert _program_names()[name]().__name__ == name


@pytest.mark.parametrize("name,kw", [
    ("train_step", {}),
    ("train_step_merge", {"gradient_merge_k": 2}),
    ("train_step_localsgd", {"localsgd_k": 2, "mesh": "dp2"}),
])
def test_train_program_is_named_after_its_kind(name, kw):
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.hapi import TrainStep

    paddle.seed(91)
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    if kw.get("mesh"):
        kw = dict(kw, mesh=Mesh(np.array(jax.devices()[:2]), ("dp",)))
    step = TrainStep(model, opt, loss_fn=lambda logits, y: logits.sum(), **kw)
    assert step._jit_step.__name__ == name
    if not kw:      # lower() takes the plain step only
        ids = paddle.to_tensor(np.zeros((2, 8), np.int32))
        assert "@jit_train_step" in step.lower(ids, ids).as_text()


# ------------------------------------------------------------ training
class TestTrainTelemetry:
    def _fit(self, steps=6, k=2):
        from paddle_tpu.hapi import TrainStep

        paddle.seed(86)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

        def loss_fn(logits, y):
            import paddle_tpu.nn.functional as F
            return F.cross_entropy(
                logits.reshape([-1, logits.shape[-1]]), y.reshape([-1]))

        step = TrainStep(model, opt, loss_fn=loss_fn, metrics_every=k)
        rng = np.random.default_rng(7)
        ids = rng.integers(0, cfg.vocab_size, (2, 9))
        x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
        y = paddle.to_tensor(ids[:, 1:].astype(np.int32))
        for _ in range(steps):
            step(x, y)
        step.sync()
        return step

    def test_counters_mirror_probes_and_spans_recorded(self):
        step = self._fit(steps=6, k=2)
        snap = obs.registry().snapshot()
        assert metric(snap, "train_syncs")["value"] == step.sync_count
        assert metric(snap, "train_step_traces")["value"] == \
            step.trace_count == 1
        assert metric(snap, "train_throttles")["value"] == 0
        assert metric(snap, "train_in_flight")["value"] == 0  # post-sync
        names = [e["name"] for e in obs.tracer().events()]
        # one pull span a pull: the span IS the pull's wall clock
        assert names.count("train.pull_metrics") + names.count(
            "train.sync") == step.sync_count
        assert "train.pull_metrics" in names
        assert "train.sync" in names
        # one dispatch span a step (the step's number on it); an unstaged
        # batch is staged first, outside the dispatch
        dispatch = [e for e in obs.tracer().events()
                    if e["name"] == "train.dispatch"]
        assert [e["args"]["step"] for e in dispatch] == list(range(6))
        assert names.count("train.stage") == 6
        assert "train.throttle" not in names        # nothing was throttled

    def test_throttle_span_when_the_window_is_full(self):
        from paddle_tpu.hapi import TrainStep

        paddle.seed(86)
        cfg = GPTConfig.tiny()
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
        step = TrainStep(model, opt, loss_fn=lambda lg, y: lg.mean(),
                         max_in_flight=1)
        x = paddle.to_tensor(np.zeros((2, 8), np.int32))
        for _ in range(4):
            step(x, x)
        step.sync()
        # whether the oldest loss was still outstanding is the device's
        # to say; the span is there exactly when the step had to wait
        names = [e["name"] for e in obs.tracer().events()]
        assert names.count("train.throttle") == step.throttle_count

    def test_fit_epoch_sync_span_nests_train_sync(self):
        from paddle_tpu.hapi import Model
        from paddle_tpu.io import Dataset

        paddle.seed(87)
        cfg = GPTConfig.tiny()
        net = GPTForCausalLM(cfg)

        class DS(Dataset):
            def __init__(self):
                rng = np.random.default_rng(8)
                self.d = rng.integers(0, cfg.vocab_size,
                                      (8, 9)).astype(np.int32)

            def __len__(self):
                return len(self.d)

            def __getitem__(self, i):
                return self.d[i, :-1], self.d[i, 1:]

        def ce(logits, y):
            import paddle_tpu.nn.functional as F
            return F.cross_entropy(
                logits.reshape([-1, logits.shape[-1]]), y.reshape([-1]))

        m = Model(net)
        m.prepare(paddle.optimizer.AdamW(1e-4,
                                         parameters=net.parameters()),
                  loss=ce)
        m.fit(DS(), batch_size=4, epochs=1, verbose=0)
        ev = {e["name"]: e for e in obs.tracer().events()}
        assert "fit.epoch_sync" in ev and "train.sync" in ev
        outer, inner = ev["fit.epoch_sync"], ev["train.sync"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        # the prefetcher staged batches through the instrumented path
        snap = obs.registry().snapshot()
        assert metric(snap, "io_batches_staged")["value"] >= 2


# ------------------------------------------------- tracecheck: TRC007
class TestTrc007:
    def run_snippet(self, tmp_path, source):
        import textwrap

        from paddle_tpu.analysis.tracecheck import analyze_package

        pkg = tmp_path / "fixpkg"
        pkg.mkdir(exist_ok=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "mod.py").write_text(textwrap.dedent(source))
        res = analyze_package(str(pkg))
        assert not res.errors, res.errors
        return res

    FLAGGED = """
        import jax
        from . import observability as obs

        def body(x):
            obs.registry().counter("c").inc()
            return x

        step = jax.jit(body)
    """

    def test_write_under_trace_flagged(self, tmp_path):
        res = self.run_snippet(tmp_path, self.FLAGGED)
        assert "TRC007" in [f.rule for f in res.findings]
        assert "host-side" in [f for f in res.findings
                               if f.rule == "TRC007"][0].message

    def test_clean_host_side_twin(self, tmp_path):
        res = self.run_snippet(tmp_path, """
            import jax
            from . import observability as obs

            def body(x):
                return x * 2

            step = jax.jit(body)

            def drive(x):
                c = obs.registry().counter("c")
                out = step(x)
                c.inc()
                return out
        """)
        assert [f.rule for f in res.findings] == []

    def test_hotpath_write_needs_pragma(self, tmp_path):
        src = """
            from . import observability as obs

            _C = obs.registry().counter("c")

            def hot(x):  # tracecheck: hotpath
                _C.inc()
                return x
        """
        res = self.run_snippet(tmp_path, src)
        assert [f.rule for f in res.findings] == ["TRC007"]
        res = self.run_snippet(tmp_path, src.replace(
            "_C.inc()", "_C.inc()  # tracecheck: disable=TRC007"))
        assert [f.rule for f in res.findings] == []
        assert len(res.suppressed) == 1

    def test_hotpath_reaches_one_level_into_helpers(self, tmp_path):
        """Routing a hot path's writes through a plain same-module
        helper doesn't dodge the annotation contract; the sanctioned
        `_observe_*` helper idiom is exempt by name."""
        src = """
            from . import observability as obs

            class Eng:
                def __init__(self):
                    self._c = obs.registry().counter("c")

                def step(self, x):  # tracecheck: hotpath
                    self.{helper}(x)
                    return x

                def {helper}(self, x):
                    self._c.inc()
        """
        res = self.run_snippet(tmp_path, src.format(helper="_note"))
        assert [f.rule for f in res.findings] == ["TRC007"]
        assert "_note" in res.findings[0].func
        res = self.run_snippet(tmp_path, src.format(helper="_observe_x"))
        assert [f.rule for f in res.findings] == []

    def test_method_heuristic_needs_observability_import(self, tmp_path):
        # .observe() in a module that never imports observability (e.g.
        # a quantization observer) is not telemetry
        res = self.run_snippet(tmp_path, """
            import jax

            def body(x, watcher):
                watcher.observe(x)
                return x

            step = jax.jit(body)
        """)
        assert [f.rule for f in res.findings] == []

    def test_package_has_no_telemetry_under_trace(self):
        """The repo-wide assertion: no registry/span write is reachable
        under trace anywhere in paddle_tpu (hotpath sites are pragma'd
        with reasons, which is exactly the annotation contract)."""
        import os

        from paddle_tpu.analysis.tracecheck import (AnalyzerConfig,
                                                    analyze_package)

        pkg = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "paddle_tpu")
        res = analyze_package(pkg, AnalyzerConfig(rules=("TRC007",)))
        assert [f.format() for f in res.findings] == []
