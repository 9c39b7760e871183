"""The paper's experiment, laptop scale: epoch-based adaptive sampling on
an SPMD mesh, comparing the three aggregation strategies (Alg. 1 flat
reduce, reduce-to-root + broadcast, and the hierarchical local/global
scheme of §IV-E), then the vertex-partitioned lane — the same mesh
acting as ONE cooperative sampler over a sharded graph, with the
bitmap-scheduled frontier exchange (DESIGN.md §Frontier exchange) and
its per-level dense vs sparse volume printed from a real BFS trace.

    PYTHONPATH=src python examples/betweenness_scaling.py
"""
import os
# eight fake host devices, on the CPU: never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (AdaptiveConfig, brandes_numpy, exchange_plan,
                        grid_graph, max_active_source_chunks,
                        partition_graph, rmat_graph, run_kadabra)
from repro.core.bfs import bfs_sssp_batched
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

graph = rmat_graph(10, 8, seed=1)   # R-MAT, Graph500 parameters
print(f"R-MAT graph: |V|={graph.n_nodes} |E|={graph.n_edges_undirected}")

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
exact = brandes_numpy(graph)

for agg in ["hierarchical", "flat", "root"]:
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, aggregation=agg, n0_base=400)
    t0 = time.perf_counter()
    res = run_kadabra(graph, mesh=mesh, config=cfg,
                      key=jax.random.PRNGKey(0))
    dt = time.perf_counter() - t0
    err = np.abs(res.btilde - exact).max()
    print(f"{agg:>13}: {dt:6.2f}s  epochs={res.n_epochs:<4} "
          f"tau={res.tau:<7} max_err={err:.4f} (eps={cfg.eps})")
    assert err < cfg.eps
print("all aggregation modes converged within eps")


# --- the partitioned lane: mesh = ONE cooperative sampler ----------------
# Each device keeps only its vertex shard's edge buckets (O(E/n_dev));
# every BFS level exchanges the frontier through the bitmap-scheduled
# protocol: active source chunks when they fit the static budget, the
# dense all-gather as fallback.  Both are bit-identical, so the sampling
# stream matches the replicated lane exactly.

def exchange_stats(g, pg, batch, seed):
    """Per-level dense vs sparse exchange volume from a BFS trace."""
    plan = exchange_plan(pg, batch)
    rng = np.random.default_rng(seed)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, batch), jnp.int32)
    res = jax.jit(bfs_sssp_batched)(g, sources)
    dist = np.asarray(res.dist)
    depth = int(np.asarray(res.levels).max())
    total, n_sparse = 0, 0
    for lv in range(depth + 1):
        mab = max_active_source_chunks(pg, (dist == lv).any(axis=1))
        total += plan.level_bytes(mab)
        n_sparse += plan.sparse_taken(mab)
    print(f"    {depth + 1} BFS levels: dense protocol "
          f"{plan.dense_bytes / 1024:.1f} KiB/level, sparse "
          f"{plan.sparse_bytes / 1024:.1f} KiB/level "
          f"(budget {plan.budget} x {plan.chunk_rows}-row chunks/shard)")
    print(f"    sparse taken on {n_sparse}/{depth + 1} levels -> "
          f"{total / ((depth + 1) * plan.dense_bytes):.2f}x the dense "
          f"volume")


print("\npartitioned lane (8 shards, bitmap-scheduled frontier exchange):")
road = grid_graph(2048, 8)          # narrow grid ~ road network
pg_road = partition_graph(road, 8)
print(f"  high-diameter narrow grid |V|={road.n_nodes}:")
exchange_stats(road, pg_road, batch=8, seed=0)
pg_rmat = partition_graph(graph, 8)
print(f"  low-diameter R-MAT |V|={graph.n_nodes} (fallback regime):")
exchange_stats(graph, pg_rmat, batch=8, seed=0)

cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=400)
t0 = time.perf_counter()
res = run_kadabra(pg_rmat, mesh=mesh, config=cfg, key=jax.random.PRNGKey(0))
dt = time.perf_counter() - t0
err = np.abs(res.btilde - exact).max()
print(f"  cooperative run_kadabra on the R-MAT shards: {dt:6.2f}s  "
      f"epochs={res.n_epochs} tau={res.tau} max_err={err:.4f} "
      f"(eps={cfg.eps})")
assert err < cfg.eps
print("partitioned lane converged within eps")
