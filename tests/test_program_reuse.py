"""Every lane's programs are built once per static configuration and
reused by every later ``run_adaptive`` call (``repro.core.programs``).

On the single lane, a repeat call on one graph at a new key lowers
nothing and compiles nothing, and gives what the same key gives first
in a cleared cache.  Two graphs of one shape with different edges share
every program and each still gets its own results, in either order: no
array is held in a cached program.  Each case runs on the ``bidir``
stream (betweenness) and on the ``forward`` stream (closeness and
harmonic).  On the SPMD and sharded lanes (8 fake devices, in a
subprocess) a repeat call builds nothing and gives the first call's
results.
"""
import contextlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import clear_programs
from repro.core.adaptive import AdaptiveConfig
from repro.core.diameter import estimate_diameter
from repro.core.engine import run_adaptive
from repro.core.graph import from_edge_list, grid_graph
from repro.runtime import RingSink, Telemetry

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CFG = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=64, max_epochs=2,
                     sample_batch_size=16)
CASES = {"bidir": (("betweenness",), "bidir"),
         "forward": (("closeness", "harmonic"), "forward")}
KEY_A, KEY_B = jax.random.PRNGKey(1), jax.random.PRNGKey(2)


def _relabelled(g):
    """The lattice under other vertex labels: the same array shapes and
    counts, other edges.  Of the relabellings tried, the first whose
    phase-1 bound matches the lattice's, so both graphs run under one
    static configuration."""
    vd = int(estimate_diameter(g).vertex_diameter)
    edges = np.stack([np.asarray(g.src[: g.n_edges]),
                      np.asarray(g.dst[: g.n_edges])], axis=1)
    for seed in range(32):
        perm = np.random.default_rng(seed).permutation(g.n_nodes)
        h = from_edge_list(perm[edges], g.n_nodes)
        if int(estimate_diameter(h).vertex_diameter) == vd:
            return h
    raise AssertionError("no relabelling keeps the phase-1 bound")


@pytest.fixture(scope="module")
def graphs():
    g = grid_graph(24, 24)
    h = _relabelled(g)
    assert h.src.shape == g.src.shape and h.n_edges == g.n_edges
    assert not np.array_equal(np.asarray(h.dst), np.asarray(g.dst))
    return g, h


def _run(g, case, key):
    """-> (the call's results, its run.end fields, its host counters)."""
    metrics, stream = CASES[case]
    sink = RingSink()
    res = run_adaptive(g, metrics, config=CFG, key=key, stream=stream,
                       telemetry=Telemetry([sink]))
    (end,) = [ev.fields for ev in sink.events if ev.kind == "run.end"]
    got = (res.tau, res.n_epochs, res.vertex_diameter,
           tuple((r.name, r.tau, r.stop_epoch,
                  np.asarray(r.scores).tobytes()) for r in res.reports))
    return got, end, res.host_counters


@pytest.fixture(scope="module")
def alone(graphs):
    """Each graph's results at KEY_B, each run first in a cleared cache."""
    out = {}
    for case in CASES:
        for i, g in enumerate(graphs):
            clear_programs()
            out[case, i] = _run(g, case, KEY_B)[0]
    return out


@contextlib.contextmanager
def _lowerings():
    names = []

    def listen(event, duration, **kw):
        if event == LOWERING:
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_repeat_call_lowers_nothing(graphs, case, cold_programs):
    g = graphs[0]
    _, first, _ = _run(g, case, KEY_A)
    with _lowerings() as lowered:
        _, second, counters = _run(g, case, KEY_B)
    assert lowered == []
    assert sum(c["compiles"] + c["cache_hits"]
               for c in counters.values()) == 0
    assert sum(c["traces"] for c in counters.values()) == 0
    assert first["programs_built"] >= 5 and first["programs_reused"] == 0
    assert second["programs_built"] == 0
    assert second["programs_reused"] == first["programs_built"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_repeat_call_gives_what_a_first_call_gives(graphs, alone, case,
                                                     cold_programs):
    g = graphs[0]
    _run(g, case, KEY_A)
    got, end, _ = _run(g, case, KEY_B)
    assert end["programs_built"] == 0
    assert got == alone[case, 0]


@pytest.mark.parametrize("order", [(0, 1), (1, 0)],
                         ids=["lattice_first", "relabelled_first"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_two_graphs_of_one_shape_get_their_own_results(graphs, alone, case,
                                                       order, cold_programs):
    (i, got_i), (j, got_j) = [(k, _run(graphs[k], case, KEY_B))
                              for k in order]
    # the second graph ran the first one's programs ...
    assert got_j[1]["programs_built"] == 0
    assert got_j[1]["programs_reused"] == got_i[1]["programs_built"]
    # ... and each got its own results
    assert got_i[0] == alone[case, i]
    assert got_j[0] == alone[case, j]
    assert got_i[0] != got_j[0]


_MESH_CALLS = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro.core import (AdaptiveConfig, erdos_renyi_graph, partition_graph,
                        run_adaptive)
from repro.runtime import RingSink, Telemetry

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
g = erdos_renyi_graph(96, 5.0, seed=5)
if sys.argv[1] == "sharded":
    g = partition_graph(g, 8)
cfg = AdaptiveConfig(eps=0.08, delta=0.1, n0_base=200, max_epochs=3)
lowered = []

def listen(event, duration, **kw):
    if event == "%s":
        lowered.append(kw.get("fun_name"))

jax.monitoring.register_event_duration_secs_listener(listen)
out = []
for _ in range(2):
    del lowered[:]
    sink = RingSink()
    res = run_adaptive(g, config=cfg, key=jax.random.PRNGKey(3), mesh=mesh,
                       telemetry=Telemetry([sink]))
    (end,) = [ev.fields for ev in sink.events if ev.kind == "run.end"]
    out.append(dict(built=end["programs_built"],
                    reused=end["programs_reused"], epochs=res.n_epochs,
                    lowerings=len(lowered),
                    epoch_lowerings=lowered.count("jit(epoch_step)"),
                    tau=res.tau,
                    scores=np.asarray(res.reports[0].scores).tobytes().hex()))
print(json.dumps(out))
""" % LOWERING


@pytest.mark.parametrize("lane", ["spmd", "sharded"])
def test_a_repeat_call_on_a_mesh_lane_builds_nothing_8dev(lane):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1]
                            / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _MESH_CALLS, lane], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    first, second = json.loads(out.stdout.strip().splitlines()[-1])
    assert first["built"] >= 5 and first["reused"] == 0
    # one epoch program for all epochs: the lane commits the state
    assert first["epochs"] >= 2 and first["epoch_lowerings"] == 1
    assert second["built"] == 0 and second["reused"] == first["built"]
    assert second["lowerings"] == 0
    assert (second["tau"], second["scores"]) == (first["tau"],
                                                 first["scores"])
