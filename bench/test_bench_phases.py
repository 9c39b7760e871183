"""The readers of the program's own phases (``bench/phases.py`` and the
metrics built on it): synthetic runs, the committed chip trace, which
predates the annotations, and a profiler trace of a small run recorded
here on the CPU."""
from __future__ import annotations

import os

import pytest

from bench import harness, phases, tracing

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "tpu_small_1chip.xplane.pb")


def _counters(compile_s, trace_lower_s, **extra):
    return {"compile_s": compile_s, "compiles": 1, "cache_load_s": 0.5,
            "cache_hits": 4, "trace_lower_s": trace_lower_s, "traces": 300,
            **extra}


def _run(trace=object()):
    """Two jobs of 512 samples, each with 8 s of sampling, of which its
    two epochs and flush counted 3 + 1 s of compile and trace."""
    jobs = [{"seconds": 12.0, "tau": 512,
             "phase_seconds": {"diameter": 1.0, "calibration": 2.0,
                               "sampling": 8.0}}] * 2
    anns = []
    for j in range(2):
        t = j * 100e9
        anns += [(t, t + 1e9, "phase.diameter", _counters(0.1, 0.2)),
                 (t + 1e9, t + 3e9, "phase.calibration", _counters(0.3, 0.4)),
                 (t + 3e9, t + 9e9, "phase.epoch",
                  _counters(2.5, 0.5, epoch=1)),
                 (t + 9e9, t + 10e9, "phase.epoch",
                  _counters(0.0, 0.0, epoch=2)),
                 (t + 10e9, t + 11e9, "phase.flush", _counters(0.25, 0.75)),
                 (t + 11e9, t + 11e9, "run.end", _counters(31.0, 3.0))]
    return {"jobs": jobs, "setup_s": 60.0, "window_s": 24.0,
            "trace": trace, "phases": anns}


@pytest.mark.parametrize("name,want", [
    ("compile_s", 31.0),
    ("trace_lower_s", 3.0),
    ("calibration_s", 2.0),
    ("epoch_samples_per_s", 1024 / (16.0 - 2 * 4.0)),
])
def test_host_counter_metric_readers(name, want):
    metric = harness.load_module("metrics", name)
    assert metric.read(_run()) == pytest.approx(want)
    # nothing to read without jobs; the counters need the trace's
    # annotations, which a program without them does not leave
    assert metric.read(dict(_run(), jobs=[])) is None
    if name != "calibration_s":
        assert metric.read(_run(trace=None)) is None
        assert metric.read(dict(_run(), phases=[])) is None


def test_epoch_idle_share_reads_gaps_inside_the_epochs():
    # job 1's epochs hold 3.5 s of device gaps in 7 s, 3 s of them the
    # epoch compile; job 2's epochs hold 0.5 s of gaps in the same 7 s
    gaps = [(2e9, 2.5e9), (3e9, 6e9), (9.5e9, 10e9), (103e9, 103.5e9)]
    dev = tracing.DeviceSummary("/device:TPU:0", 0.0, {}, gaps)
    run = _run(trace=tracing.TraceSummary(200e9, [dev], []))
    share = harness.load_module("metrics", "epoch_idle_share").read(run)
    host = 2 * 3.0
    assert share == pytest.approx(100 * (4.0 - host) / (14.0 - host))


def test_epoch_idle_share_reads_nothing_without_epochs():
    metric = harness.load_module("metrics", "epoch_idle_share")
    assert metric.read(_run(trace=None)) is None
    summary = tracing.summarize(TRACE)
    # the committed trace predates the program's annotations
    run = {"jobs": [], "setup_s": 0.0, "window_s": 0.0, "trace": summary,
           "phases": phases.read(TRACE)}
    assert run["phases"] == []
    assert metric.read(run) is None


def test_annotations_are_read_from_the_run_trace_once(monkeypatch):
    trace_dir = os.path.dirname(TRACE)
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    run = {"jobs": [], "trace": object()}
    assert phases.annotations(run) == [] and run["phases"] == []
    assert phases.annotations({"jobs": [], "trace": None}) is None


def test_a_traced_run_leaves_its_phases_in_the_window(tmp_path):
    import jax
    from repro.core import AdaptiveConfig, rmat_graph, run_adaptive

    g = rmat_graph(9, 8, seed=1)
    cfg = AdaptiveConfig(eps=0.01, delta=0.1, n0_base=64, max_epochs=2)
    run_adaptive(g, config=cfg, key=jax.random.PRNGKey(0))    # warm
    jax.profiler.start_trace(str(tmp_path))
    run_adaptive(g, config=cfg, key=jax.random.PRNGKey(1))   # outside
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        res = run_adaptive(g, config=cfg, key=jax.random.PRNGKey(2))
    jax.profiler.stop_trace()
    anns = phases.read(tracing.find_xplane(str(tmp_path)))
    assert [a[2] for a in anns] == ["phase.diameter", "phase.calibration",
                                    "phase.epoch", "phase.epoch",
                                    "phase.flush", "run.end"]
    run = {"jobs": [{"tau": res.tau, "phase_seconds": res.phase_seconds}],
           "trace": object(), "phases": anns}
    total = sum(c["compile_s"] for c in res.host_counters.values())
    compile_s = harness.load_module("metrics", "compile_s").read(run)
    assert compile_s == pytest.approx(total)
    rate = harness.load_module("metrics", "epoch_samples_per_s").read(run)
    sampling = res.host_counters["sampling"]
    assert rate == pytest.approx(res.tau / (
        res.phase_seconds["sampling"] - sampling["compile_s"]
        - sampling["trace_lower_s"]), rel=1e-6)
