"""Quickstart: approximate betweenness on a real graph, then a
multi-metric run amortizing one BFS stream across three centralities.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core import (AdaptiveConfig, brandes_numpy, hyperbolic_graph,
                        run_adaptive, run_kadabra)
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# a power-law graph (the paper's synthetic family, laptop scale)
graph = hyperbolic_graph(2000, avg_degree=12.0, seed=0)
print(f"graph: |V|={graph.n_nodes}  |E|={graph.n_edges_undirected}")

# (eps, delta)-approximation: every betweenness value within eps of the
# truth with probability 1 - delta
cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=400)
res = run_kadabra(graph, config=cfg, key=jax.random.PRNGKey(0))

print(f"converged={res.converged}  samples={res.tau} "
      f"(static cap omega={res.omega:.0f})  epochs={res.n_epochs}")
top = np.argsort(res.btilde)[::-1][:5]
print("top-5 vertices by approximate betweenness:")
for v in top:
    print(f"  v={v:<6} b~={res.btilde[v]:.4f}")

# verify against the exact Brandes oracle (feasible at this scale)
exact = brandes_numpy(graph)
err = np.abs(res.btilde - exact).max()
print(f"max |b~ - b| = {err:.4f}  (guarantee: < {cfg.eps} w.p. >= 0.9)")
assert err < cfg.eps

# the same engine runs any estimator stack on ONE shared BFS stream:
# each metric keeps its own stopping rule, the expensive traversals
# are paid once (DESIGN.md §Estimator substrate)
multi = run_adaptive(graph, ("betweenness", "closeness", "harmonic"),
                     config=cfg, key=jax.random.PRNGKey(0))
print(f"\nmulti-metric run: {multi.tau} samples, "
      f"{multi.n_epochs} epochs, converged={multi.converged}")
for rep in multi.reports:
    top_v = int(np.argmax(rep.scores))
    print(f"  {rep.name:<12} stopped at epoch {rep.stop_epoch} "
          f"(tau={rep.tau}); top vertex {top_v} "
          f"score={rep.scores[top_v]:.4f}")
print("OK")
