"""The single lane's epoch program: the same at every key on one graph.

``run_adaptive`` passes the graph and the calibration's stop-rule
params into the jitted epoch step as arguments, so a second call at
another key runs the same program: in one process it reuses the built
program and lowers nothing, and in a new process the persistent
compilation cache serves it instead of a backend compile.  The pins
hold the single lane's results at a fixed key, so a change to what the
program holds as constants cannot move them unnoticed.
"""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.adaptive import AdaptiveConfig
from repro.core.engine import run_adaptive
from repro.core.graph import rmat_graph, symmetric_dyadic_weights, with_weights

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# one process: run_adaptive at each of argv[3]'s keys, in order
_CALLS = """
import json, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.core.adaptive import AdaptiveConfig
from repro.core.engine import run_adaptive
from repro.core.graph import rmat_graph
from repro.runtime import RingSink, Telemetry
g = rmat_graph(9, 8, seed=1)
if sys.argv[2] == "committed":
    g = jax.device_put(g, jax.devices()[0])
# the call at key 2 with the bus on: it reads the epochs' work counters
cfg = AdaptiveConfig(eps=0.01, delta=0.1, n0_base=64, max_epochs=2)
lowered = []

def listen(event, duration, **kw):
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        lowered.append(kw.get("fun_name"))

jax.monitoring.register_event_duration_secs_listener(listen)
out = []
for k in map(int, sys.argv[3].split(",")):
    del lowered[:]
    tel = (Telemetry([RingSink()], validate=True)
           if k == 2 and sys.argv[2] == "telemetry" else None)
    res = run_adaptive(g, config=cfg, key=jax.random.PRNGKey(k),
                       telemetry=tel)
    out.append(dict(res.host_counters, lowerings=len(lowered),
                    epoch_lowerings=lowered.count("jit(epoch_step)"),
                    bfs_levels=[s.bfs_levels for s in res.stats]))
print(json.dumps(out))
"""


def _calls(cache_dir, placement, keys):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run(
        [sys.executable, "-c", _CALLS, str(cache_dir), placement, keys],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placement", ["default", "committed", "telemetry"])
def test_second_key_loads_the_epoch_program_from_the_cache(tmp_path,
                                                           placement):
    """Calls on one graph at different keys, in fresh interpreters so
    the cache settings stay out of this process.  In the first process
    the call at the second key reuses the first call's programs and
    lowers nothing.  A second process that shares the cache directory
    lowers the epoch step once and loads it from the cache: its sampling
    phase compiles nothing.  Each process lowers the epoch step once: a
    graph committed to its device (``jax.device_put``) commits the
    epochs' outputs, which must not make the second epoch a second
    program.  With the telemetry bus on in the calls at the second key
    only, they still reuse or load the first call's program: the work
    counters are computed either way."""
    cache = tmp_path / "xla_cache"
    first, again = _calls(cache, placement, "1,2")
    (second,) = _calls(cache, placement, "2")
    assert first["sampling"]["compiles"] >= 1       # the cache starts empty
    assert first["epoch_lowerings"] == second["epoch_lowerings"] == 1
    assert again["lowerings"] == 0, again
    assert all(again[p]["compiles"] == 0 for p in
               ("diameter", "calibration", "sampling", "other")), again
    assert second["sampling"]["compiles"] == 0, second
    assert second["sampling"]["cache_hits"] >= 1, second
    assert all(n > 0 for n in first["bfs_levels"] + again["bfs_levels"]
               + second["bfs_levels"])


@pytest.fixture(scope="module")
def rmat9():
    g = rmat_graph(9, 8, seed=1)
    return g, with_weights(g, symmetric_dyadic_weights(g, seed=1))


_CAPPED = AdaptiveConfig(eps=0.01, delta=0.1, n0_base=64, max_epochs=2)
_CONVERGES = AdaptiveConfig(eps=0.1, delta=0.1, n0_base=64, max_epochs=8)

# case -> (weighted graph, metrics, stream, config,
#          (tau, n_epochs, converged, vertex_diameter,
#           ((sha256 of the scores' bytes, stop_epoch) per metric)))
_PINS = {
    "bc_bidir": (False, ("betweenness",), None, _CAPPED, (
        128, 2, False, 1,
        (("7d22c3dfd08dd2483a35a29b1c70ab334e103b6ec2dd35f91c6975f23d7dc7cb",
          2),))),
    "bc_forward": (False, ("betweenness",), "forward", _CAPPED, (
        128, 2, False, 1,
        (("a565989630570ba12cfab8b6757603b7af2933fb2c3567250694ecd61436b8e2",
          2),))),
    "bc_closeness": (False, ("betweenness", "closeness"), None, _CAPPED, (
        128, 2, False, 1,
        (("a565989630570ba12cfab8b6757603b7af2933fb2c3567250694ecd61436b8e2",
          2),
         ("5938b00e6f6c922585ba73db8827d295916c01a6723ef185875f6ec1f28ae90d",
          2)))),
    "bc_converged": (False, ("betweenness",), None, _CONVERGES, (
        320, 5, True, 1,
        (("542e77fcee3511d6194cccd5eebd3ea2b7917b5df03cf2b9e4d0adbe618f2673",
          5),))),
    "weighted": (True, ("betweenness", "closeness"), "weighted", _CAPPED, (
        128, 2, False, 1,
        (("ead338dc93117053319a0943777642f549e60c298483a09750849334b4dbb8b5",
          2),
         ("0def38869c894156ae09c73c651aeac0657baae4bb1f38fbe44547be227830c5",
          2)))),
}


@pytest.mark.parametrize("case", sorted(_PINS))
def test_single_lane_results_are_pinned(rmat9, case):
    weighted, metrics, stream, cfg, want = _PINS[case]
    g = rmat9[1] if weighted else rmat9[0]
    res = run_adaptive(g, metrics, config=cfg, key=jax.random.PRNGKey(7),
                       stream=stream)
    got = (int(res.tau), int(res.n_epochs), bool(res.converged),
           int(res.vertex_diameter),
           tuple((hashlib.sha256(np.asarray(r.scores).tobytes()).hexdigest(),
                  int(r.stop_epoch)) for r in res.reports))
    assert got == want
