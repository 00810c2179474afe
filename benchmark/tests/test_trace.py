"""The trace reduction, on a small recorded trace and on hand-made
events.

``testdata/v5e_flash_step.xplane.pb`` was recorded on the chip (PR 23):
eight calls of one jitted step — a causal flash-attention Pallas call
over bf16[32,1024,64], a few copies, two matmul fusions — each under a
``bench.step`` span with a ``bench.pull`` inside and a ``bench.sleep`` of
2 ms after.
"""

import os

import pytest

from benchmark.lib import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "v5e_flash_step.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.read_xplane(DATA)


def test_recorded_trace_planes(recorded):
    device_ops, host_spans = recorded
    assert list(device_ops) == ["/device:TPU:0"]
    assert len(device_ops["/device:TPU:0"]) == 128      # 16 ops x 8 steps
    names = {n for n, _, _ in host_spans}
    assert names == {"bench.step", "bench.pull", "bench.sleep"}
    assert sum(1 for n, _, _ in host_spans if n == "bench.step") == 8


def test_recorded_busy_and_ops(recorded):
    device_ops, _ = recorded
    busy = trace.busy_seconds(device_ops)
    by_op = trace.op_seconds(device_ops)
    # ops run one after another on the core: the union is their sum
    assert busy == pytest.approx(sum(by_op.values()), rel=1e-6)
    assert 2.4e-3 < busy < 2.8e-3
    top = trace.top(by_op, 3)
    assert top[0][0] == "jvp__ bf16[32,1024,64]"
    assert top[0][1] == pytest.approx(8 * 243.9e-6, rel=0.01)


def test_recorded_pallas_kernel_by_operand_shape(recorded):
    device_ops, _ = recorded
    flash = trace.pallas_seconds(
        device_ops, lambda ops: any(d[-2:] == [1024, 64] for d in ops))
    assert flash == pytest.approx(8 * 243.9e-6, rel=0.01)
    assert trace.pallas_seconds(
        device_ops, lambda ops: any(d[-2:] == [2048, 64] for d in ops)) == 0
    assert trace.collective_exposed_seconds(device_ops) == 0.0


def test_recorded_gaps_go_to_the_host_span_that_holds_them(recorded):
    device_ops, host_spans = recorded
    gaps = trace.idle_gaps(device_ops, host_spans)
    # between two steps the device waits while the host pulls the loss,
    # sleeps 2 ms and dispatches again; seven gaps of about 4 ms
    assert set(gaps) <= {"bench.pull", "bench.sleep", "bench.step",
                         "unlabelled", "short_gaps"}
    assert 0.020 < sum(gaps.values()) < 0.040
    assert gaps.get("short_gaps", 0.0) < 1e-4


def test_hlo_names():
    text = ("%copy.12 = bf16[16,513,64,64]{3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[16,513,64,64]{2,3,1,0:T(8,128)(2,1)} %bitcast.3)")
    assert trace.parse_hlo(text)[0::2] == ("copy.12", "copy")
    assert trace.op_label(text) == "copy bf16[16,513,64,64]"
    assert trace.operand_arrays(text) == ["bf16[16,513,64,64]"]
    tup = ("%all-reduce-start.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) "
           "all-reduce-start(f32[8,128]{1,0} %x), replica_groups={{0,1}}")
    assert trace.parse_hlo(tup)[2] == "all-reduce-start"
    assert trace.is_collective(tup) and not trace.is_collective(text)
    assert trace.op_label(tup) == "all-reduce-start f32[8,128]"
    assert trace.parse_hlo("bench.step") == ("bench.step", "", "")
    call = ('%jvp__.1 = (bf16[32,1024,64]{2,1,0}, f32[32,1,1024]{2,1,0}) '
            'custom-call(bf16[32,1024,64]{2,1,0} %a, bf16[8,1024,64]{2,1,0} '
            '%b), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[32,1024,64]{2,1,0}}')
    assert trace.is_pallas(call)
    assert trace.operand_arrays(call) == ["bf16[32,1024,64]",
                                          "bf16[8,1024,64]"]
    assert trace.array_dims("bf16[8,1024,64]") == [8, 1024, 64]


def test_intervals_by_hand():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace.length([(0, 3), (5, 6)]) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]


def test_exposed_collective_time_by_hand():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)"
    mm = "%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a)"
    ops = {
        # device 0: a 4 us all-reduce, 1 us of it under a fusion
        "/device:TPU:0": [(mm, 0.0, 3000.0), (ar, 2000.0, 4000.0)],
        # device 1: the all-reduce wholly hidden
        "/device:TPU:1": [(mm, 0.0, 8000.0), (ar, 2000.0, 4000.0)],
    }
    assert trace.collective_exposed_seconds(ops) == pytest.approx(
        (3000.0 + 0.0) / 2 / 1e9)
    assert trace.busy_seconds(ops) == pytest.approx((6000 + 8000) / 2 / 1e9)
    gaps = trace.idle_gaps(
        {"/device:TPU:0": [(mm, 0.0, 1000.0), (mm, 5000.0, 1000.0),
                           (mm, 9000.0, 1000.0)]},
        [("bench.step", 0.0, 20000.0), ("bench.pull", 900.0, 4000.0)],
        min_gap_ns=3500.0)
    # the 4 us gap lies in bench.pull; the 3 us one is under the least
    # gap that is attributed, so it is lumped
    assert gaps == {"bench.pull": pytest.approx(4e-6),
                    "short_gaps": pytest.approx(3e-6)}
    assert trace.busy_seconds({}) is None
