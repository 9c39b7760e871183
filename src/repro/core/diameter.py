"""Diameter estimation (phase 1 of KADABRA).

KADABRA only needs an *upper bound* on the vertex diameter VD(G) (the
number of vertices on the longest shortest path) to compute the static
sample-size cap omega.  The paper uses the sequential iFUB-style algorithm
of Borassi et al. [6]; here we use the classic double-sweep scheme built on
batched BFS:

  * BFS from a seed vertex -> farthest vertex u      (ecc(seed))
  * BFS from u             -> farthest vertex v      (lower bound = d(u,v))
  * upper bound            = 2 * min(ecc(seed), ecc(u))   [undirected]

Double sweep is known to be exact on most real-world complex networks and
the upper bound only loosens omega (never the guarantee).  All K seed
chains run as ONE batched BFS per sweep phase (K seeds batched, then
their K far-vertices batched), so phase 1 — the paper's Fig. 2b
scalability bottleneck, which it runs as sequential scalar BFS —
streams the edge list once per level for all chains.

The replicated unweighted sweeps need distances only, so they run their
own driver, :func:`bfs_dist_pull`, and not the sampler's path-counting
``bfs_sssp_batched``: a level *pulls* over the CSR rows.  It gathers
each edge slot's source-is-frontier bit, takes an exact int32 prefix
sum over the edge slots, and reads every vertex's count of frontier
neighbours as the difference of that sum at its two row pointers — no
scatter, no float and no path counts.  Its cost does not depend on an
attached CSC layout, so a graph with one takes it too.  The sharded and
weighted sweeps keep the drivers of their lanes.  Every BFS runs to
frontier exhaustion, so its levels are the eccentricity (with an early
stop they would only be a lower bound — see ``BFSResult``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .bfs import (bfs_sssp_batched_sharded, delta_sssp_batched,
                  delta_sssp_batched_sharded)
from .graph import Graph
from .partition import PartitionedGraph, axis_tuple

__all__ = ["DiameterEstimate", "PullBFS", "WeightedDiameterEstimate",
           "bfs_dist_pull", "estimate_diameter", "estimate_diameter_sharded",
           "estimate_diameter_weighted",
           "estimate_diameter_weighted_sharded"]


class DiameterEstimate(NamedTuple):
    lower: jax.Array        # () int32 — best shortest-path length found
    upper: jax.Array        # () int32 — valid upper bound on the diameter
    vertex_diameter: jax.Array  # () int32 — upper bound on VD = upper + 1
    # () int32 — BFS levels the sweeps expanded, summed over both sweeps;
    # None on the sharded lane, whose sweeps do not count them
    levels: Optional[jax.Array] = None


class PullBFS(NamedTuple):
    """K distance-only BFS of :func:`bfs_dist_pull`."""
    dist: jax.Array    # (K, V) int32, chain-major; -1 unreached
    levels: jax.Array  # (K,) int32 — eccentricity of each source
    trips: jax.Array   # () int32 — levels expanded: max(levels) + 1


def bfs_dist_pull(graph: Graph, sources) -> PullBFS:
    """K full BFS from ``sources`` (K,), distances only.

    The K chains are rows of one (K, V) state.  One level for all of
    them, with ``front`` 0/1 and 0 in the sink column (where the padded
    edge slots point)::

        hit = front[:, indices]                     # (K, E_pad) gather
        C   = [0, cumsum(hit)]                      # exact in int32
        cnt[k, v] = C[k, indptr[v+1]] - C[k, indptr[v]]

    ``cnt`` counts v's frontier neighbours: ``indices`` holds both
    directions of every edge, sorted by source, so v's row lists its
    neighbours.  The loop runs until every chain's frontier is empty,
    so ``levels`` is each source's eccentricity and ``trips`` counts the
    empty last level too, as ``bfs_sssp_batched``'s ``steps`` does.
    """
    sources = jnp.asarray(sources, jnp.int32)
    k = sources.shape[0]
    dist0 = jnp.full((k, graph.n_nodes), -1,
                     jnp.int32).at[jnp.arange(k), sources].set(0)

    def body(state):
        dist, level, _ = state
        front = jnp.pad((dist == level).astype(jnp.int32), ((0, 0), (0, 1)))
        hit = front[:, graph.indices]
        c = jnp.pad(jnp.cumsum(hit, axis=1), ((0, 0), (1, 0)))
        at_rows = c[:, graph.indptr]
        cnt = at_rows[:, 1:] - at_rows[:, :-1]
        new = (cnt > 0) & (dist == -1)
        return jnp.where(new, level + 1, dist), level + 1, jnp.any(new)

    # one trip per level, so the last level is the trip count
    dist, trips, _ = jax.lax.while_loop(
        lambda state: state[2], body,
        (dist0, jnp.int32(0), jnp.bool_(True)))
    return PullBFS(dist, jnp.max(dist, axis=1), trips)


def _sweep_batched(graph: Graph, seeds):
    """One batched sweep: K seeds -> (ecc (K,), farthest vertex (K,),
    levels expanded ())."""
    res = bfs_dist_pull(graph, seeds)
    # farthest *reached* vertex per chain (ties broken towards lower id)
    far = jnp.argmax(res.dist, axis=1)
    return res.levels, far.astype(jnp.int32), res.trips


def estimate_diameter(graph: Graph, key=None, n_sweeps: int = 2) -> DiameterEstimate:
    """Double-sweep diameter bounds; extra sweeps tighten the bounds.

    ``n_sweeps - 1`` independent chains (minimum one) run concurrently:
    each phase is a single batched BFS over all chains' frontiers.
    ``levels`` counts the BFS levels both sweeps expanded.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (max(1, n_sweeps - 1),), 0, graph.n_nodes)

    ecc0, far0, trips0 = _sweep_batched(graph, seeds)
    ecc1, _far1, trips1 = _sweep_batched(graph, far0)
    lowers = ecc1                       # d(far0, far1) realized by BFS
    uppers = 2 * jnp.minimum(ecc0, ecc1)
    uppers = jnp.maximum(uppers, lowers)  # keep each interval consistent
    lower = jnp.max(lowers)
    upper = jnp.maximum(jnp.min(uppers), lower)
    return DiameterEstimate(lower, upper, upper + 1, trips0 + trips1)


# ---------------------------------------------------------------------------
# Weighted lane (delta-stepping double sweep)
# ---------------------------------------------------------------------------

class WeightedDiameterEstimate(NamedTuple):
    """Double-sweep bounds on the WEIGHTED diameter plus a hop-count
    vertex-diameter bound for omega.

    ``lower``/``upper`` bound the weighted diameter (float distances —
    ``upper`` is what closeness uses as its distance cap on the
    weighted stream).  ``vertex_diameter`` bounds the number of
    vertices on any weighted shortest path, derived from the sweeps'
    shortest-path-DAG hop depths by the same 2*min(ecc) arithmetic as
    the unweighted bound; on weighted graphs concatenating two shortest
    paths need not be shortest, so this is the double-sweep *estimate*
    the same way the unweighted one is exact only up to the scheme —
    omega uses it as a cap, never as a guarantee.
    """
    lower: jax.Array            # () float32 — realized weighted distance
    upper: jax.Array            # () float32 — weighted-diameter bound
    vertex_diameter: jax.Array  # () int32 — hop VD bound (feeds omega)


def _sweep_weighted(graph: Graph, seeds, delta):
    """One batched weighted sweep: K seeds -> (weighted ecc (K,), DAG
    hop depth (K,), farthest vertex (K,))."""
    res = delta_sssp_batched(graph, seeds, delta=delta)
    masked = jnp.where(res.dist >= 0, res.dist, -1.0)[: graph.n_nodes, :]
    wecc = jnp.max(jnp.maximum(masked, 0.0), axis=0)
    far = jnp.argmax(masked, axis=0).astype(jnp.int32)
    return wecc, res.levels, far


def _fold_weighted_sweeps(wecc0, h0, wecc1, h1):
    """Shared bound arithmetic of the weighted double sweep."""
    lowers = wecc1
    uppers = jnp.maximum(2.0 * jnp.minimum(wecc0, wecc1), lowers)
    lower = jnp.max(lowers)
    upper = jnp.maximum(jnp.min(uppers), lower)
    vds = jnp.maximum(2 * jnp.minimum(h0, h1), h1)
    vd = jnp.maximum(jnp.min(vds), jnp.max(h1)) + 1
    return WeightedDiameterEstimate(lower, upper, vd)


def estimate_diameter_weighted(graph: Graph, key=None, n_sweeps: int = 2, *,
                               delta=None) -> WeightedDiameterEstimate:
    """Weighted double-sweep bounds on a graph with per-edge weights.

    Identical chain structure (and seed draw — same key, same seeds) as
    :func:`estimate_diameter`, with each sweep a batched delta-stepping
    SSSP instead of a BFS: the farthest-vertex hop runs on weighted
    distances, the distance bounds on weighted eccentricities, and the
    vertex-diameter bound on the sweeps' DAG hop depths.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (max(1, n_sweeps - 1),), 0, graph.n_nodes)
    wecc0, h0, far0 = _sweep_weighted(graph, seeds, delta)
    wecc1, h1, _far1 = _sweep_weighted(graph, far0, delta)
    return _fold_weighted_sweeps(wecc0, h0, wecc1, h1)


# ---------------------------------------------------------------------------
# Sharded lane (vertex-partitioned graphs, inside shard_map)
# ---------------------------------------------------------------------------

def _sweep_batched_sharded(pg: PartitionedGraph, seeds, axis):
    """Sharded sweep: the per-chain farthest vertex is the two-level
    argmax (local argmax per shard, then argmax over the all-gathered
    per-shard winners).  Ties break towards the lower global id exactly
    like the replicated argmax: within a shard argmax prefers the
    lowest local row, and across shards the gathered winners are in
    shard (= ascending global-row) order."""
    res = bfs_sssp_batched_sharded(pg, seeds, axis=axis)
    masked = jnp.where(res.dist >= 0, res.dist, -1)   # pad rows stay -1
    loc_val = jnp.max(masked, axis=0)                              # (K,)
    loc_far = jnp.argmax(masked, axis=0)                           # (K,)
    offset = jax.lax.axis_index(axis) * pg.shard_rows
    vals = jax.lax.all_gather(loc_val, axis, axis=0)            # (S, K)
    fars = jax.lax.all_gather(offset + loc_far, axis, axis=0)   # (S, K)
    best = jnp.argmax(vals, axis=0)
    far = fars[best, jnp.arange(seeds.shape[0])].astype(jnp.int32)
    return res.levels, far, res.dist


def estimate_diameter_sharded(pg: PartitionedGraph, key=None,
                              n_sweeps: int = 2, *,
                              axis=None, return_dist: bool = False):
    """Sharded twin of :func:`estimate_diameter` — call inside
    shard_map with the shard axis name(s).  Phase 1 was the paper's
    Fig. 2b scalability bottleneck; on a partitioned graph it runs the
    same cooperative sharded BFS lane as sampling, so no device ever
    materializes the full edge structure — and the sweeps inherit the
    bitmap-scheduled frontier exchange transparently from the shared
    driver (double sweeps are exactly the high-diameter, sparse-
    frontier regime the sparse protocol is built for; see DESIGN.md
    §Frontier exchange).  The seed draw matches the replicated
    estimator key-for-key (bit-identical bounds on the same graph).

    ``return_dist=True`` additionally returns the SECOND sweep's local
    dist block, shape ``(shard_rows, n_seeds)`` int32 (unreached / pad
    rows hold -1).  Those sweeps start from eccentric vertices — long
    BFS traces whose per-level frontiers are exactly what the
    ``exchange_budget="auto"`` rule samples occupancy from
    (:func:`repro.core.partition.auto_exchange_budget`)."""
    if axis is None:
        raise ValueError("estimate_diameter_sharded requires the shard "
                         "axis name(s) (axis=...)")
    axis = axis_tuple(axis)
    if key is None:
        key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (max(1, n_sweeps - 1),), 0, pg.n_nodes)

    ecc0, far0, _ = _sweep_batched_sharded(pg, seeds, axis)
    ecc1, _far1, dist1 = _sweep_batched_sharded(pg, far0, axis)
    lowers = ecc1
    uppers = 2 * jnp.minimum(ecc0, ecc1)
    uppers = jnp.maximum(uppers, lowers)
    lower = jnp.max(lowers)
    upper = jnp.maximum(jnp.min(uppers), lower)
    est = DiameterEstimate(lower, upper, upper + 1)
    return (est, dist1) if return_dist else est


def _sweep_weighted_sharded(pg: PartitionedGraph, seeds, delta, axis):
    """Sharded weighted sweep: the same two-level argmax (with the same
    lower-global-id tie-break) as :func:`_sweep_batched_sharded`, on the
    delta-stepping distance state."""
    res = delta_sssp_batched_sharded(pg, seeds, axis=axis, delta=delta)
    masked = jnp.where(res.dist >= 0, res.dist, -1.0)  # pad rows stay -1
    loc_val = jnp.max(masked, axis=0)
    loc_far = jnp.argmax(masked, axis=0)
    offset = jax.lax.axis_index(axis) * pg.shard_rows
    vals = jax.lax.all_gather(loc_val, axis, axis=0)
    fars = jax.lax.all_gather(offset + loc_far, axis, axis=0)
    best = jnp.argmax(vals, axis=0)
    far = fars[best, jnp.arange(seeds.shape[0])].astype(jnp.int32)
    wecc = jax.lax.pmax(jnp.max(jnp.maximum(masked, 0.0), axis=0), axis)
    return wecc, res.levels, far


def estimate_diameter_weighted_sharded(pg: PartitionedGraph, key=None,
                                       n_sweeps: int = 2, *, axis=None,
                                       delta=None
                                       ) -> WeightedDiameterEstimate:
    """Sharded twin of :func:`estimate_diameter_weighted` — call inside
    shard_map.  Seed draw and bound arithmetic match the replicated
    weighted estimator key-for-key; each sweep is a cooperative
    delta-stepping SSSP (bucket exchange per round)."""
    if axis is None:
        raise ValueError("estimate_diameter_weighted_sharded requires the "
                         "shard axis name(s) (axis=...)")
    axis = axis_tuple(axis)
    if key is None:
        key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (max(1, n_sweeps - 1),), 0, pg.n_nodes)
    wecc0, h0, far0 = _sweep_weighted_sharded(pg, seeds, delta, axis)
    wecc1, h1, _far1 = _sweep_weighted_sharded(pg, far0, delta, axis)
    return _fold_weighted_sweeps(wecc0, h0, wecc1, h1)
