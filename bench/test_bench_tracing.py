"""The trace-to-metrics reduction, on a small profiler trace recorded on
one TPU v5e chip (``bench/testdata``): one ``run_adaptive`` job on a
32 x 32 grid, whose BFS levels run the Pallas frontier kernel, inside
``bench.window`` and ``bench.job.1`` annotations."""
from __future__ import annotations

import os

import pytest

from bench import harness, tracing

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "tpu_small_1chip.xplane.pb")


@pytest.mark.parametrize("text,key", [
    ("%fusion.56 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion:fusion"),
    ("%while.367 = (s32[], f32[1,2]) while((s32[], f32[1,2]) %t), "
     "condition=%c, body=%b", "while:while"),
    ("%frontier_expand.8 = f32[1280,1]{1,0} custom-call(s32[1,4352] %r), "
     'custom_call_target="tpu_custom_call"', "frontier_expand:custom-call"),
    ("%all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add",
     "all-reduce:all-reduce"),
    ("%copy-start.2 = (s32[2], s32[2], u32[]) copy-start(s32[2] %c)",
     "copy-start:copy-start"),
])
def test_op_key(text, key):
    assert tracing.op_key(text) == key


def test_exclusive_time_of_nested_operations():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (50, 60, "c"), (120, 130, "a")]
    assert tracing._exclusive(events) == {"while": 30, "a": 30, "b": 40,
                                          "c": 10}


@pytest.fixture(scope="module")
def summary():
    return tracing.summarize(TRACE)


def test_recorded_trace_busy_and_window(summary):
    assert len(summary.devices) == 1
    assert 0 < summary.busy_s() <= summary.window_s()
    dev = summary.devices[0]
    gaps = sum(e - s for s, e in dev.gaps)
    assert gaps + dev.busy_ns == pytest.approx(summary.window_ns, rel=1e-9)
    # self times never exceed what the device was busy
    assert sum(dev.op_ns.values()) == pytest.approx(dev.busy_ns, rel=1e-6)


def test_recorded_trace_kernel_and_breakdown(summary):
    run = {"jobs": [], "setup_s": 0.0, "window_s": 0.0, "trace": summary}
    kernel = 100.0 * summary.op_share(
        lambda op: "frontier_expand:custom-call" in op)
    idle = harness.load_module("metrics", "device_idle_share").read(run)
    assert 0 < kernel < 100
    assert 0 <= idle < 100
    assert summary.op_share(lambda op: "all-reduce" in op) == 0
    bd = summary.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10
    assert len(bd["idle_gaps"]) <= 10
    assert all(isinstance(name, str) and secs >= 0
               for name, secs in bd["device_ops"] + bd["idle_gaps"])
    assert any("frontier_expand:custom-call" in name
               for name, _ in bd["device_ops"])
