"""The readers of the program's spans, on hand-made intervals and on
the recorded trace. Times are nanoseconds on one clock, as the profiler
gives them."""

import os

import pytest

from benchmark.lib import registry, span_readers, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "v5e_flash_step.xplane.pb")
MS = 1e6
SIZES = dict(n_params=1000, layers=2, kv_heads=4, head_dim=8)
PEAKS = dict(hbm_bytes_per_s=1e6)


def ctx_of(ops, spans, **scalars):
    """``ops``: (start, end) in ms of the one device's ops; ``spans``:
    (name, start, end) in ms."""
    return dict(
        device_ops={"/device:TPU:0": [("%op.1 = f32[1] fusion()", a * MS,
                                       (b - a) * MS) for a, b in ops]},
        host_spans=[(n, a * MS, (b - a) * MS) for n, a, b in spans],
        scalars=scalars, sizes=SIZES, peaks=PEAKS)


# one engine step, 0..100 ms: the device runs 10-30 (a prefill) and
# 50-90 (the decode program); it waits 30-50 while the host stages the
# decode dispatch, and the step's first and last 10 ms have no device op
# on either side inside the step
STEP = [("bench.run_step", -1, 101),
        ("engine.step", 0, 100),
        ("engine.schedule", 0, 4),
        ("engine.admit", 4, 32),
        ("request.prefill", 6, 31),
        ("engine.decode_step", 33, 92),
        ("engine.decode.stage", 33, 48),
        ("engine.decode.dispatch", 48, 52),
        ("engine.decode.pull", 52, 92),
        ("engine.emit", 92, 96),
        ("engine.callbacks", 96, 99)]


def test_two_gaps_under_different_child_spans():
    # ops before and after the step bound its outer gaps: 100-120 lies
    # under no span of the program
    ctx = ctx_of([(-5, -2), (10, 30), (50, 90), (120, 125)], STEP)
    gaps = trace.idle_gaps(ctx["device_ops"], ctx["host_spans"])
    # -2..10: middle 4 is in engine.schedule's last instant and
    # engine.admit's first; the shortest span that holds it wins
    assert gaps["engine.schedule"] == pytest.approx(0.012)
    # 30..50: middle 40, the staging of the decode dispatch
    assert gaps["engine.decode.stage"] == pytest.approx(0.020)
    assert gaps["unlabelled"] == pytest.approx(0.030)
    # 32 of the 62 idle ms fall to spans of the program
    assert span_readers.gap_attributed(ctx) == pytest.approx(100 * 32 / 62)


def test_gaps_that_fall_to_the_benchmark_do_not_count():
    spans = [("bench.run_step", 0, 100), ("request.prefill", 10, 20)]
    ctx = ctx_of([(0, 12), (18, 40), (60, 100)], spans)
    # 12..18 under request.prefill, 40..60 under bench.run_step only
    assert span_readers.gap_attributed(ctx) == pytest.approx(100 * 6 / 26)
    # gaps too short to have a cause are in neither sum
    ctx = ctx_of([(0, 12), (12.001, 40)], spans)
    assert span_readers.gap_attributed(ctx) is None


def test_host_time_of_a_step_is_its_length_less_the_busy_time_inside():
    ctx = ctx_of([(10, 30), (50, 90)], STEP)
    assert span_readers.host_per_step(ctx) == pytest.approx(40.0)
    # an op that straddles the step's edge counts by the part inside:
    # 95..130 gives the step 5 ms more of busy time, not 35
    ctx = ctx_of([(-20, 5), (10, 30), (50, 90), (95, 130)], STEP)
    assert span_readers.host_per_step(ctx) == pytest.approx(30.0)


def test_scheduler_time_leaves_the_prefill_out():
    ctx = ctx_of([(10, 30)], STEP)
    # engine.schedule 4 + engine.admit 28 less request.prefill 25
    assert span_readers.sched_per_step(ctx) == pytest.approx(7.0)
    # a second, idle step: the median of 7 and 1 by the linear rule
    more = STEP + [("engine.step", 200, 210), ("engine.schedule", 200, 201),
                   ("engine.admit", 201, 201)]
    assert span_readers.sched_per_step(ctx_of([(10, 30)], more)) == \
        pytest.approx(4.0)


def test_decode_span_with_and_without_device_time():
    spans = STEP + [("engine.step", 200, 260),
                    ("engine.decode_step", 210, 250)]
    ctx = ctx_of([(10, 30), (50, 90)], spans,
                 serving_decode_steps=2.0, serving_decode_live_tokens=100.0)
    # 40 ms under the first decode span (the prefill's 10-30 lies
    # outside it), none under the second
    assert span_readers.decode_device(ctx) == pytest.approx(20.0)
    # 2 steps x 2,000 B of weights + 100 tokens x 256 B, at 1e6 B/s,
    # over the 0.040 s that ran under a decode span
    need = 2 * 2000 + 100 * 2 * 2 * 4 * 8 * 2
    assert span_readers.decode_floor_in_span(ctx) == \
        pytest.approx(100.0 * need / 1e6 / 0.040)
    # a device that never ran under a decode span gives no share
    idle = ctx_of([(0, 5)], spans, serving_decode_steps=2.0,
                  serving_decode_live_tokens=100.0)
    assert span_readers.decode_floor_in_span(idle) is None
    assert span_readers.decode_device(idle) == pytest.approx(0.0)


def test_a_program_without_the_spans_or_counters_reads_nothing():
    """What the parent commit gives these readers: the benchmark's own
    spans, ``request.prefill``, and none of the new counters."""
    ctx = ctx_of([(10, 30), (50, 90)],
                 [("bench.run_step", 0, 100), ("request.prefill", 6, 31)],
                 serving_decode_steps=2.0, window_s=1.0)
    for read in (span_readers.host_per_step, span_readers.sched_per_step,
                 span_readers.decode_device,
                 span_readers.decode_floor_in_span):
        assert read(ctx) is None
    registry.load_all()
    ratio = registry.READERS["ratio"]
    assert ratio(ctx, num="serving_decode_rows", den="serving_decode_slots",
                 scale=100.0) is None
    ctx["scalars"].update(serving_decode_rows=9.0, serving_decode_slots=16.0)
    assert ratio(ctx, num="serving_decode_rows", den="serving_decode_slots",
                 scale=100.0) == pytest.approx(56.25)
    # no trace at all (a run with --trace 0 never asks, but a reader
    # must not raise)
    bare = dict(device_ops={}, host_spans=[], scalars={})
    assert span_readers.gap_attributed(bare) is None
    assert span_readers.host_per_step(bare) is None


def test_ring_reader_clips_to_the_window_and_refuses_a_full_ring():
    from paddle_tpu import observability as obs
    tr = obs.tracer()
    tr.clear()
    try:
        # an old record, 100 s before the window's last; then a window
        # of 10 s that holds holds of 40, 60 and 80 ms
        tr.event("request.first_token", 0.0, 0.5, rid=0)
        for i, ms in enumerate((40, 80, 60)):
            t0 = 95.0 + i
            tr.event("request.first_token", t0, t0 + ms / 1e3, rid=i + 1)
        tr.event("request.complete", 90.0, 100.0, rid=1)
        ctx = dict(scalars=dict(window_s=10.0))
        read = span_readers.ring_span
        assert read(ctx, name="request.first_token", q=50) == \
            pytest.approx(60.0)
        assert read(ctx, name="request.none", q=50) is None
        # fill the ring: the oldest records may be gone, so no number
        for i in range(tr.capacity):
            tr.event("filler", 99.0, 99.1)
        assert len(tr) == tr.capacity
        assert read(ctx, name="request.first_token", q=50) is None
    finally:
        tr.clear()


def test_recorded_trace_attributes_by_the_spans_name():
    """The recorded trace has the benchmark's spans only, so none of its
    gap seconds falls to the program; the same trace with its pull span
    under a name of the program's gives that span's share."""
    device_ops, host_spans = trace.read_xplane(DATA)
    ctx = dict(device_ops=device_ops, host_spans=host_spans)
    assert span_readers.gap_attributed(ctx) == 0.0
    renamed = [("train.pull_metrics" if n == "bench.pull" else n, s, d)
               for n, s, d in host_spans]
    gaps = trace.idle_gaps(device_ops, renamed)
    share = span_readers.gap_attributed(dict(device_ops=device_ops,
                                             host_spans=renamed))
    caused = sum(v for k, v in gaps.items() if k != "short_gaps")
    assert share == pytest.approx(100 * gaps["train.pull_metrics"] / caused)
    # the gap between two steps has its middle in the pull of the loss
    assert share > 50.0
