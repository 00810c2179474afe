"""The step clock's metrics on hand-made scalars: the reader this PR's
file adds, and the accepted readers its other data files name."""

import json
import os

import pytest

from benchmark.lib import registry

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a window of 1,000 rounds of engine.step, 800 of which dispatched a
# decode step: 5.2 s of rounds, 1.2 s of them waiting for the device
SERVING = dict(serving_steps=1000.0, serving_step_seconds=5.2,
               serving_wait_seconds=1.2, serving_decode_steps=800.0,
               serving_decode_dispatch_seconds=2.6,
               serving_decode_stage_put_seconds=0.4,
               serving_slow_step_seconds=0.0)
TRAIN = dict(train_steps=100.0, train_dispatch_seconds=0.41,
             train_slow_step_seconds=2.5)


def read(metric, scalars):
    registry.load_all()
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as fh:
        spec = json.load(fh)
    fn = registry.lookup(registry.READERS, spec["reader"], "reader")
    return fn(dict(scalars=scalars), **spec["params"])


@pytest.mark.parametrize("metric, scalars, want", [
    ("host_serial_ms_per_step.chat", SERVING, 4.0),
    ("host_serial_ms_per_step.longprompt", SERVING, 4.0),
    ("decode_dispatch_ms.chat", SERVING, 3.25),
    ("decode_dispatch_ms.longprompt", SERVING, 3.25),
    ("decode_stage_put_ms.chat", SERVING, 0.5),
    ("decode_stage_put_ms.longprompt", SERVING, 0.5),
    ("slow_step_s.chat", SERVING, 0.0),
    ("slow_step_s.longprompt", SERVING, 0.0),
    ("slow_step_s.train", TRAIN, 2.5),
    ("train_dispatch_ms", TRAIN, 4.1),
])
def test_metric_from_the_programs_counters(metric, scalars, want):
    assert read(metric, scalars) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "host_serial_ms_per_step.chat", "decode_dispatch_ms.chat",
    "decode_stage_put_ms.longprompt", "slow_step_s.longprompt",
    "slow_step_s.train", "train_dispatch_ms"])
def test_a_program_without_the_clock_reads_nothing(metric):
    """The parent commit writes none of these counters: the reader
    returns None (the line leaves the metric out) and does not raise."""
    assert read(metric, dict(window_s=10.0, serving_decode_steps=800.0,
                             steps=100.0)) is None


@pytest.mark.parametrize("missing", ["serving_step_seconds",
                                     "serving_wait_seconds",
                                     "serving_steps"])
def test_difference_ratio_needs_all_three(missing):
    scalars = {k: v for k, v in SERVING.items() if k != missing}
    assert read("host_serial_ms_per_step.chat", scalars) is None


def test_no_round_in_the_window_reads_nothing():
    assert read("host_serial_ms_per_step.chat",
                dict(SERVING, serving_steps=0.0)) is None
