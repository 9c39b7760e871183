"""Telemetry bus tests: the two production contracts (off is a true
no-op, on is bit-identical on all three lanes), the taxonomy validator,
sinks, spans, the JSONL wire format, the Chrome-trace exporter, and the
trace_report tool reproducing a run's outcome from the file alone.
"""
import json
import os
import subprocess
import sys
import tracemalloc

import jax
import numpy as np
import pytest

from repro.core.adaptive import AdaptiveConfig
from repro.core.engine import run_adaptive
from repro.core.graph import build_graph
from repro.runtime import (FaultSchedule, FaultSpec, ResilientRunner,
                           RetryPolicy)
from repro.runtime.events import (EVENT_KINDS, SPAN_NAMES, Event, from_json,
                                  read_jsonl, to_json, validate_event)
from repro.runtime.telemetry import (JSONLSink, NULL_TELEMETRY, RingSink,
                                     Telemetry, chrome_trace,
                                     resolve_telemetry, write_chrome_trace)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_report  # noqa: E402


def _small_graph(seed=0, v=100, e=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e)
    dst = (src + 1 + rng.integers(0, v - 1, e)) % v
    return build_graph(np.concatenate([src, dst]),
                       np.concatenate([dst, src]), v)


# ---------------------------------------------------------------------------
# Off is a true no-op
# ---------------------------------------------------------------------------

def test_null_telemetry_is_falsy_noop():
    assert not NULL_TELEMETRY
    assert NULL_TELEMETRY.emit("run.end", tau=1) is None
    # one reusable null context manager: span() allocates nothing
    s1 = NULL_TELEMETRY.span("phase.epoch", epoch=1)
    s2 = NULL_TELEMETRY.span("phase.diameter")
    assert s1 is s2
    with s1:
        pass
    # a disabled Telemetry with sinks attached still swallows everything
    ring = RingSink()
    tel = Telemetry([ring], enabled=False)
    assert not tel
    tel.emit("run.end", tau=1)
    with tel.span("phase.epoch"):
        pass
    assert ring.events == []


def test_null_telemetry_hot_path_allocates_nothing():
    """The disabled emit/span path must not build records: after warmup,
    a tight loop leaves no net allocations behind."""
    for _ in range(4):                          # warm any lazy setup
        NULL_TELEMETRY.emit("epoch.stats", epoch=0)
        NULL_TELEMETRY.span("phase.epoch")
    tracemalloc.start()
    try:
        base = tracemalloc.take_snapshot()
        for i in range(1000):
            NULL_TELEMETRY.emit("epoch.stats", epoch=i, tau=i)
            with NULL_TELEMETRY.span("phase.epoch", epoch=i):
                pass
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    # Transient allocations exist on any **kwargs call (the kwargs dict
    # lives in the callee frame, attributed to telemetry.py), and a
    # snapshot can catch the last iteration's in flight.  The contract
    # is that nothing is *retained* per call: after 1000 iterations the
    # module's net growth stays O(1), not O(iterations).
    mod = os.sep + os.path.join("runtime", "telemetry.py")
    grown = [d for d in snap.compare_to(base, "lineno")
             if (d.traceback[0].filename or "").endswith(mod)
             and d.size_diff > 0]
    assert sum(d.count_diff for d in grown) < 10, grown
    assert sum(d.size_diff for d in grown) < 4096, grown


# ---------------------------------------------------------------------------
# Resolution, sinks, validation, wire format
# ---------------------------------------------------------------------------

def test_resolve_telemetry_forms(tmp_path):
    assert resolve_telemetry(None) is NULL_TELEMETRY
    tel = Telemetry([RingSink()])
    assert resolve_telemetry(tel) is tel
    path = str(tmp_path / "t.jsonl")
    tp = resolve_telemetry(path)
    tp.emit("checkpoint.quarantine", step=3)
    tp.close()
    evs = read_jsonl(path, validate=True)
    assert [e.kind for e in evs] == ["checkpoint.quarantine"]
    # any object with .write(event) works as a sink
    got = []
    class Sink:
        def write(self, ev):
            got.append(ev)
    ts = resolve_telemetry(Sink())
    ts.emit("checkpoint.quarantine", step=9)
    assert got[0].fields["step"] == 9
    with pytest.raises(TypeError):
        resolve_telemetry(42)


def test_ring_sink_keeps_newest():
    ring = RingSink(capacity=3)
    tel = Telemetry([ring])
    for i in range(7):
        tel.emit("checkpoint.quarantine", step=i)
    assert [e.fields["step"] for e in ring.events] == [4, 5, 6]


def test_validate_event_rejects_unregistered_and_incomplete():
    with pytest.raises(ValueError, match="unregistered"):
        validate_event(Event(kind="made.up", t=0.0, fields={}))
    with pytest.raises(ValueError, match="missing"):
        validate_event(Event(kind="run.end", t=0.0, fields={"tau": 1}))
    with pytest.raises(ValueError):
        validate_event(Event(kind="span.begin", t=0.0,
                             fields={"name": "phase.epoch"}))  # no span id
    ok = Event(kind="run.end", t=0.0,
               fields={"tau": 1, "n_epochs": 2, "converged": True})
    validate_event(ok)


def test_jsonl_wire_roundtrip():
    ev = Event(kind="epoch.stats", t=1.5, span=7, parent=3, tid=11,
               fields={"epoch": 2, "tau": 100, "samples": 50,
                       "seconds": 0.25, "max_f": [0.1], "max_g": [0.2]})
    back = from_json(to_json(ev))
    assert back == ev


def test_taxonomy_registry_shape():
    """Every registered kind carries a required-field tuple and a doc
    line; span names map to doc strings."""
    for kind, (req, doc) in EVENT_KINDS.items():
        assert isinstance(req, tuple) and isinstance(doc, str) and doc
    assert set(SPAN_NAMES) >= {"phase.diameter", "phase.calibration",
                               "phase.epoch", "phase.flush"}


def test_span_nesting_and_thread_ids(tmp_path):
    ring = RingSink()
    tel = Telemetry([ring], validate=True)
    with tel.span("phase.epoch", epoch=1):
        with tel.span("checkpoint.publish", step=4):
            pass
    kinds = [e.kind for e in ring.events]
    assert kinds == ["span.begin", "span.begin", "span.end", "span.end"]
    outer_b, inner_b, inner_e, outer_e = ring.events
    assert inner_b.parent == outer_b.span
    assert outer_b.parent is None
    assert inner_e.span == inner_b.span and outer_e.span == outer_b.span
    assert inner_e.fields["seconds"] >= 0.0
    assert all(e.tid == outer_b.tid for e in ring.events)
    # timestamps are monotonic within the thread
    ts = [e.t for e in ring.events]
    assert ts == sorted(ts)


def test_chrome_trace_structure():
    ring = RingSink()
    tel = Telemetry([ring], validate=True)
    tel.emit("run.start", lane="single", metrics=["betweenness"],
             n_nodes=4, eps=0.1, delta=0.1)
    with tel.span("phase.epoch", epoch=1):
        pass
    with tel.span("phase.flush"):
        pass
    doc = chrome_trace(ring.events)
    rows = doc["traceEvents"]
    assert [r["ph"] for r in rows].count("X") == 2
    assert any(r["ph"] == "i" and r["name"] == "run.start" for r in rows)
    assert all(r["ts"] >= 0 for r in rows)
    assert rows == sorted(rows, key=lambda r: r["ts"])
    xs = [r for r in rows if r["ph"] == "X"]
    assert xs[0]["args"]["epoch"] == 1          # begin fields merged in


# ---------------------------------------------------------------------------
# Bit-identity: single lane in-process, SPMD + sharded via subprocess
# ---------------------------------------------------------------------------

def test_single_lane_bit_identical_with_telemetry():
    g = _small_graph()
    cfg = AdaptiveConfig(eps=0.1, delta=0.1, max_epochs=8)
    key = jax.random.PRNGKey(0)
    off = run_adaptive(g, ("betweenness",), config=cfg, key=key)
    tel = Telemetry([RingSink()], validate=True)
    on = run_adaptive(g, ("betweenness",), config=cfg, key=key,
                      telemetry=tel)
    np.testing.assert_array_equal(np.asarray(on.reports[0].scores),
                                  np.asarray(off.reports[0].scores))
    assert (on.tau, on.n_epochs, on.converged) == \
        (off.tau, off.n_epochs, off.converged)
    evs = tel.events()
    kinds = {e.kind for e in evs}
    assert {"run.start", "run.end", "epoch.stats",
            "span.begin", "span.end"} <= kinds
    assert "exchange.epoch" not in kinds        # single lane: no exchange
    stats = [e for e in evs if e.kind == "epoch.stats"]
    assert len(stats) == on.n_epochs
    assert all(e.fields["samples"] > 0 for e in stats)
    # the stats list mirrors the events whether or not telemetry is on
    assert [s.samples for s in on.stats] == \
        [e.fields["samples"] for e in stats]
    assert [s.samples for s in off.stats] == [s.samples for s in on.stats]
    assert all(s.exchange is None for s in on.stats)


_MESH_TELEMETRY_BODY = r"""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core import AdaptiveConfig, erdos_renyi_graph, partition_graph
from repro.core.engine import run_adaptive
from repro.runtime import RingSink, Telemetry

g = erdos_renyi_graph(96, 5.0, seed=5)
key = jax.random.PRNGKey(11)
cfg = AdaptiveConfig(eps=0.08, delta=0.1, n0_base=400)

mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 3)
off = run_adaptive(g, ("betweenness",), mesh=mesh3, config=cfg, key=key)
tel = Telemetry([RingSink()], validate=True)
on = run_adaptive(g, ("betweenness",), mesh=mesh3, config=cfg, key=key,
                  telemetry=tel)
np.testing.assert_array_equal(np.asarray(on.reports[0].scores),
                              np.asarray(off.reports[0].scores))
assert (on.tau, on.n_epochs, on.converged) == (off.tau, off.n_epochs,
                                               off.converged)
assert any(e.kind == "epoch.stats" for e in tel.events())
print("OK spmd")

pg = partition_graph(g, 8)
mesh1 = Mesh(np.asarray(jax.devices()[:8]), ("dev",))
off = run_adaptive(pg, ("betweenness",), mesh=mesh1, config=cfg, key=key)
tel = Telemetry([RingSink()], validate=True)
on = run_adaptive(pg, ("betweenness",), mesh=mesh1, config=cfg, key=key,
                  telemetry=tel)
np.testing.assert_array_equal(np.asarray(on.reports[0].scores),
                              np.asarray(off.reports[0].scores))
assert (on.tau, on.n_epochs, on.converged) == (off.tau, off.n_epochs,
                                               off.converged)
xch = [e for e in tel.events() if e.kind == "exchange.epoch"]
assert len(xch) == on.n_epochs, (len(xch), on.n_epochs)
for e in xch:
    f = e.fields
    assert (f["levels_sparse"] + f["levels_dense_fallback"]
            + f["levels_dense_only"]) == f["levels_total"]
    assert f["levels_total"] > 0 and f["bytes"] > 0
# the exchange accounting also lands on the stats rows, telemetry or not
assert all(s.exchange is not None for s in on.stats)
assert all(s.exchange is not None for s in off.stats)
assert [s.exchange["bytes"] for s in on.stats] == \
    [e.fields["bytes"] for e in xch]
assert [s.exchange for s in off.stats] == [s.exchange for s in on.stats]
print("OK sharded")
"""


def test_spmd_and_sharded_lanes_bit_identical_with_telemetry_8dev():
    """Telemetry on vs off on the SPMD and sharded cooperative lanes (8
    fake devices).  Subprocess because the fake-device flag must precede
    JAX init."""
    script = ('import os\nos.environ["XLA_FLAGS"] = '
              '"--xla_force_host_platform_device_count=8"\n'
              + _MESH_TELEMETRY_BODY)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 2


# ---------------------------------------------------------------------------
# End-to-end JSONL round-trip through the supervisor + trace_report
# ---------------------------------------------------------------------------

def test_resilient_run_jsonl_roundtrip_and_report(tmp_path):
    """A faulted resilient run streamed to JSONL: every line re-validates,
    the supervisor's RunEvents all have bus counterparts in order, and
    trace_report reproduces the final tau and epoch count from the file
    alone."""
    g = _small_graph()
    cfg = AdaptiveConfig(eps=0.1, delta=0.1, max_epochs=16)
    trace = str(tmp_path / "run.jsonl")
    out = ResilientRunner(
        g, config=cfg, key=jax.random.PRNGKey(3),
        checkpoint_dir=str(tmp_path / "ck"),
        schedule=FaultSchedule([FaultSpec("kill", 1)]),
        policy=RetryPolicy(max_retries=4, backoff_base=1e-3,
                           backoff_cap=1e-3),
        telemetry=trace).run()
    evs = read_jsonl(trace, validate=True)
    sup = [e.kind.split(".", 1)[1] for e in evs
           if e.kind.startswith("supervisor.")]
    assert sup == [e.kind for e in out.events]
    assert "fault" in sup and "retry" in sup
    # the retried run leaves two run.start stretches; the last one wins
    assert sum(1 for e in evs if e.kind == "run.start") >= 2
    s = trace_report.summarize(evs)
    assert s["end"]["tau"] == out.result.tau
    assert s["end"]["n_epochs"] == out.result.n_epochs
    assert s["timeline"]                       # supervisor rows made it
    text = trace_report.render(evs)
    assert f"tau={out.result.tau}" in text
    assert "resilience timeline" in text
    # chrome export of the same stream is well-formed trace-event JSON
    chrome = str(tmp_path / "trace.json")
    write_chrome_trace(chrome, evs)
    with open(chrome) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    assert all(r["ph"] in ("X", "i") for r in doc["traceEvents"])


def test_jsonl_sink_appends_and_closes(tmp_path):
    path = str(tmp_path / "a.jsonl")
    s1 = JSONLSink(path)
    t1 = Telemetry([s1])
    t1.emit("checkpoint.quarantine", step=1)
    t1.close()
    s2 = JSONLSink(path)
    t2 = Telemetry([s2])
    t2.emit("checkpoint.quarantine", step=2)
    t2.close()
    assert [e.fields["step"] for e in read_jsonl(path)] == [1, 2]
