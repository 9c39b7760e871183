"""Edge-centric BFS with shortest-path counting (the sampling hot path).

The paper's sampler takes one *balanced bidirectional BFS* per sample
(KADABRA, Borassi & Natale 2016).  A CPU implementation expands one vertex
at a time from a queue; that formulation is hostile to TPUs (serial,
pointer-chasing).  The TPU-native adaptation used here is *linear-algebra
BFS*: a frontier is a dense (V+1,) vector and one BFS level is one
edge-centric relaxation

    contrib[v] = sum_{(u,v) in E} sigma[u] * [dist[u] == level]

i.e. a masked SpMV over the COO edge list, expressed as a gather +
``segment_sum``.

Everything in this module is *batched* and *vertex-major*: the state of
B concurrent searches is a (V+1, B) frontier matrix — vertices down the
rows, samples across the columns — and one relaxation is a masked SpMM
that streams the edge list ONCE for all B searches —

    contrib[v, b] = sum_{(u,v) in E} sigma[u, b] * [dist[u, b] == level[b]]

Relative to B independent SpMVs this amortizes the edge-index reads and
turns the scatter into a wide segment reduction (on TPU: a one-hot MXU
matmul with a (block_e, B) right-hand side — see ``repro.kernels.frontier``),
raising arithmetic intensity by ~B on the memory-bound edge stream.  The
(V+1, B) orientation is exactly the kernels' native layout (both the
flat and the two-level node-blocked kernel tile the vertex axis), so the
batched state flows from init through both while_loops into the sampler
without a single transpose — the previous sample-major (B, V+1) state
paid three full-state copies per BFS level on TPU.  This is the
intra-device analogue of the paper's epoch-level parallelism: each
device relaxes B sample-frontiers per level instead of one.  Per-sample
level counters, per-sample balanced-side selection and per-sample
termination are handled by masking inside one shared ``while_loop`` that
runs until every search in the batch has met/finished.  The scalar
(single-search) API is kept as a thin B=1 wrapper.

Past graph replication, the ``*_sharded`` twins at the bottom of this
module run the same drivers with the while_loop state SHARDED
vertex-major over a mesh axis (each device holds the rows of its
``repro.core.partition`` vertex shard) and only the masked frontier
values all-gathered per level — see DESIGN.md §Partitioning.

Numerical note: shortest-path counts grow combinatorially (binomial on
grid-like graphs), so float32 would overflow on high-diameter inputs.  We
rescale each sample's ``sigma`` column by 1/max whenever its max crosses
1e30.  Every consumer (path sampling, meeting-vertex selection) only uses
*ratios* of sigma values under a uniform per-side scale, so the rescale is
exact in distribution.  For small graphs the scale stays 1 and sigma
remains an exact integer count (used by the unit tests against networkx).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .graph import Graph
from .partition import PartitionedGraph, axis_tuple
from repro.kernels import resolve_interpret
from repro.kernels.frontier import (dag_sigma_batched_ref,
                                    dag_sigma_sharded_ref,
                                    edge_bitmap_from_source_bits,
                                    frontier_expand, frontier_relax,
                                    frontier_source_block_bitmap,
                                    select_route)

__all__ = [
    "BFSResult", "bfs_sssp", "bfs_sssp_batched", "bfs_sssp_batched_sharded",
    "BidirResult", "bidirectional_bfs", "bidirectional_bfs_batched",
    "bidirectional_bfs_batched_sharded",
    "SSSPResult", "delta_sssp_batched", "delta_sssp_batched_sharded",
    "frontier_route",
]

_RESCALE_THRESHOLD = 1e30
_SINK_DIST = jnp.int32(-3)   # dist value of the padding sink row


class BFSResult(NamedTuple):
    """Result of (batched) single-source BFS with path counting.

    ``dist``/``sigma`` are (V+1, B) vertex-major in the batched API and
    (V+1,) in the scalar wrapper — (csc.v_pad, B) / (csc.v_pad,) when
    the graph carries a persisted CSC layout (rows past the sink are
    inert: dist -3, sigma 0; slice to ``n_nodes`` for per-vertex
    consumers, exactly as with the sink row).  ``levels`` is the
    deepest *settled* distance per sample: every vertex at distance <=
    levels has final dist/sigma.  It equals ecc(source) only when the
    search ran to frontier exhaustion; with ``stop_nodes`` the search
    exits as soon as its stop node settles, so levels = dist(source,
    stop_node) — a *lower bound* on the eccentricity, not the
    eccentricity itself.  Diameter estimation (``estimate_diameter``)
    therefore always runs its sweeps without stop nodes.
    """
    dist: jax.Array    # (rows, B) | (rows,) int32; -1 unreached, -3 sink/pad
    sigma: jax.Array   # (rows, B) | (rows,) float32; rescaled path counts
    levels: jax.Array  # (B,) | () int32; deepest settled distance (see above)
    # (2,) int32 [levels_exchanged, levels_sparse] — the sharded drivers'
    # per-search exchange-protocol tally (ExchangePlan.epoch_accounting
    # prices it); None on the replicated lanes, which exchange nothing.
    exchange: Optional[jax.Array] = None


def _state_rows(graph: Graph) -> int:
    """Rows of the batched BFS state: V+1, or csc.v_pad when a CSC
    layout is persisted — allocating at the kernel's padded row count up
    front is what makes every while_loop iteration pad/slice-free."""
    return graph.csc.v_pad if graph.csc is not None else graph.n_nodes + 1


def frontier_route(graph, batch: int, *, weighted: bool = False) -> str:
    """The frontier lane the BFS drivers take on ``graph`` (a
    :class:`Graph` or a :class:`PartitionedGraph`) at sample width
    ``batch`` on this backend — the automatic dispatch of
    ``repro.kernels.frontier.frontier_expand`` (``frontier_relax`` when
    ``weighted``), decided from the same static shapes: one of "flat",
    "node_blocked", "ref", "sharded_nb", "sharded_ref"."""
    interpret = resolve_interpret(None)
    if isinstance(graph, PartitionedGraph):
        return select_route(graph.n_nodes, batch, shard=graph.shards,
                            interpret=interpret, weighted=weighted)
    return select_route(_state_rows(graph) - 1, batch, csc=graph.csc,
                        interpret=interpret, weighted=weighted)


def _init_state(graph: Graph, sources):
    """Batched BFS init: sources (B,) -> vertex-major dist/sigma.

    (V+1, B), or (csc.v_pad, B) for a graph with a persisted CSC layout
    — all rows >= n_nodes (the sink and the tile-padding rows) start at
    dist -3 / sigma 0 and stay there: no edge targets them.
    """
    b = sources.shape[0]
    rows = _state_rows(graph)
    cols = jnp.arange(b)
    dist = jnp.full((rows, b), -1, jnp.int32)
    dist = dist.at[graph.n_nodes:, :].set(_SINK_DIST)
    dist = dist.at[sources, cols].set(0)
    sigma = jnp.zeros((rows, b), jnp.float32).at[sources, cols].set(1.0)
    return dist, sigma


def _expand_level(graph: Graph, dist, sigma, level, active):
    """One batched edge-centric BFS relaxation (a masked SpMM).

    dist/sigma are vertex-major (rows, B), ``level`` is the per-sample
    (B,) frontier depth and ``active`` a (B,) mask — inactive columns
    are left untouched.  The contribution matrix comes from the
    ``repro.kernels.frontier`` dispatcher: the graph's persisted CSC
    layout (if any) rides along, so on TPU hardware the expansion runs
    the node-blocked kernel with occupancy skipping, and on this
    container it auto-routes to the bit-identical XLA reference — the
    state layout is the kernels' native one either way, no transposes,
    no pads.  Both BFS drivers (single-source and bidirectional) share
    this one expansion.  Returns updated (dist, sigma, n_new (B,)).
    """
    contrib = frontier_expand(graph.src, graph.dst, dist, sigma, level,
                              csc=graph.csc)
    new = (contrib > 0) & (dist == -1) & active[None, :]
    dist = jnp.where(new, level[None, :] + 1, dist)
    sigma = jnp.where(new, contrib, sigma)
    # rescale per sample to avoid float32 overflow (uniform column scale
    # => exact ratios)
    m = jnp.max(jnp.where(new, sigma, 0.0), axis=0, keepdims=True)
    scale = jnp.where(m > _RESCALE_THRESHOLD, 1.0 / m, 1.0)
    sigma = sigma * scale
    return dist, sigma, jnp.sum(new.astype(jnp.int32), axis=0)


def bfs_sssp_batched(graph: Graph, sources, *, stop_nodes=None) -> BFSResult:
    """B concurrent full single-source BFS with path counting.

    ``sources`` is (B,); one shared while_loop relaxes all B frontiers per
    level and runs until every search exhausted its frontier.  If
    ``stop_nodes`` (B,) is given, each search additionally stops as soon
    as its own stop node is settled (the whole level is still fully
    expanded, so sigma[stop_nodes[b], b] is final) — in that case
    ``levels`` under-reports the eccentricity (see :class:`BFSResult`).
    """
    sources = jnp.asarray(sources, jnp.int32)
    b = sources.shape[0]
    dist0, sigma0 = _init_state(graph, sources)
    cols = jnp.arange(b)

    def go_mask(dist, level, n_new):
        go = (n_new > 0) & (level < graph.n_nodes)
        if stop_nodes is not None:
            go = go & (dist[stop_nodes, cols] < 0)
        return go

    def cond(state):
        dist, _sigma, level, n_new = state
        return jnp.any(go_mask(dist, level, n_new))

    def body(state):
        dist, sigma, level, n_new = state
        active = go_mask(dist, level, n_new)
        dist, sigma, n_new2 = _expand_level(graph, dist, sigma, level, active)
        level = jnp.where(active, level + 1, level)
        n_new = jnp.where(active, n_new2, n_new)
        return dist, sigma, level, n_new

    dist, sigma, _levels, _ = jax.lax.while_loop(
        cond, body, (dist0, sigma0, jnp.zeros((b,), jnp.int32),
                     jnp.ones((b,), jnp.int32)))
    # deepest level actually settled per sample (the loop counter
    # overshoots by one when a search exits on an empty frontier); equals
    # ecc(source) iff the search ran to exhaustion
    settled = jnp.max(jnp.where(dist >= 0, dist, 0), axis=0)
    return BFSResult(dist, sigma, settled)


def bfs_sssp(graph: Graph, source, *, stop_node=None) -> BFSResult:
    """Full single-source BFS with path counting (Brandes forward phase).

    Thin B=1 wrapper over :func:`bfs_sssp_batched` (the batch column is
    squeezed away: dist/sigma come back as (V+1,)).  If ``stop_node`` is
    given, stops as soon as that node is settled — ``levels`` then
    reports dist(source, stop_node), not the eccentricity.
    """
    sources = jnp.asarray(source, jnp.int32).reshape(1)
    stops = (None if stop_node is None
             else jnp.asarray(stop_node, jnp.int32).reshape(1))
    res = bfs_sssp_batched(graph, sources, stop_nodes=stops)
    return BFSResult(res.dist[:, 0], res.sigma[:, 0], res.levels[0])


class BidirResult(NamedTuple):
    """State of balanced bidirectional BFS after the frontiers met.

    ``dist_*``/``sigma_*`` are vertex-major (V+1, B) in the batched API
    ((V+1,) from the scalar wrapper); ``d``/``split`` are (B,) (scalars
    from the wrapper).  ``d`` is the s-t distance (or -1 if s,t are
    disconnected).  ``split`` is the s-side level L such that every
    shortest s-t path crosses exactly one vertex w with
    dist_s(w) == L; the set of such vertices carries weight
    sigma_s(w) * sigma_t(w).  Both sides' sigma values are final for
    all vertices at levels <= their expanded radius.
    """
    dist_s: jax.Array   # (V+1, B) | (V+1,) int32
    dist_t: jax.Array   # (V+1, B) | (V+1,) int32
    sigma_s: jax.Array  # (V+1, B) | (V+1,) float32
    sigma_t: jax.Array  # (V+1, B) | (V+1,) float32
    d: jax.Array        # (B,) | () int32
    split: jax.Array    # (B,) | () int32
    # (2,) int32 [levels_exchanged, levels_sparse]; None off the sharded
    # lane — same contract as BFSResult.exchange.
    exchange: Optional[jax.Array] = None


def bidirectional_bfs_batched(graph: Graph, s, t, *,
                              max_levels: int | None = None) -> BidirResult:
    """B balanced bidirectional BFS sharing one edge stream per level.

    ``s``/``t`` are (B,).  Each iteration every still-active sample
    expands its own smaller frontier (the "balanced" strategy of KADABRA):
    the per-sample chosen side is gathered into one (V+1, B) matrix, a
    single batched relaxation streams the edge list once for all B
    searches, and the result is scattered back to the chosen side.  A
    sample leaves the loop when some vertex has a final distance from both
    of its sides (the frontiers met) or its frontier died (disconnected
    pair); the shared while_loop runs until all B searches are done.  On
    an undirected graph the same edge list serves both directions
    (NetworKit stores graph + transpose; for us symmetry makes them
    identical).
    """
    max_levels = graph.n_nodes if max_levels is None else max_levels
    s = jnp.asarray(s, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    b = s.shape[0]
    dist_s0, sigma_s0 = _init_state(graph, s)
    dist_t0, sigma_t0 = _init_state(graph, t)

    def active_mask(dist_s, rad_s, dist_t, rad_t, alive):
        # met: some vertex settled from both sides
        met = jnp.any((dist_s >= 0) & (dist_t >= 0), axis=0)
        return (~met) & alive & (rad_s + rad_t < max_levels)

    # state: dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive
    def cond(st):
        dist_s, _, rad_s, dist_t, _, rad_t, alive = st
        return jnp.any(active_mask(dist_s, rad_s, dist_t, rad_t, alive))

    def body(st):
        dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive = st
        active = active_mask(dist_s, rad_s, dist_t, rad_t, alive)
        fs = jnp.sum((dist_s == rad_s[None, :]).astype(jnp.int32), axis=0)
        ft = jnp.sum((dist_t == rad_t[None, :]).astype(jnp.int32), axis=0)
        # Balanced rule, per sample: expand the smaller frontier; if a
        # side's frontier died out the pair is disconnected.
        pick_s = fs <= ft
        exp_dist = jnp.where(pick_s[None, :], dist_s, dist_t)
        exp_sigma = jnp.where(pick_s[None, :], sigma_s, sigma_t)
        exp_level = jnp.where(pick_s, rad_s, rad_t)
        nd, ns, n_new = _expand_level(graph, exp_dist, exp_sigma, exp_level,
                                      active)
        upd_s = pick_s & active
        upd_t = (~pick_s) & active
        dist_s = jnp.where(upd_s[None, :], nd, dist_s)
        sigma_s = jnp.where(upd_s[None, :], ns, sigma_s)
        rad_s = jnp.where(upd_s, rad_s + 1, rad_s)
        dist_t = jnp.where(upd_t[None, :], nd, dist_t)
        sigma_t = jnp.where(upd_t[None, :], ns, sigma_t)
        rad_t = jnp.where(upd_t, rad_t + 1, rad_t)
        alive = jnp.where(active, n_new > 0, alive)
        return dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive

    zeros = jnp.zeros((b,), jnp.int32)
    init = (dist_s0, sigma_s0, zeros, dist_t0, sigma_t0, zeros,
            jnp.ones((b,), jnp.bool_))
    dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, _alive = \
        jax.lax.while_loop(cond, body, init)

    both = (dist_s >= 0) & (dist_t >= 0)
    dsum = jnp.where(both, dist_s + dist_t, jnp.iinfo(jnp.int32).max)
    d = jnp.min(dsum, axis=0)
    connected = d < jnp.iinfo(jnp.int32).max
    d = jnp.where(connected, d, -1)
    # Split level: all vertices with dist_s == split are settled on the s
    # side (split <= rad_s) and their dist_t (= d - split) side is settled
    # too (d - split <= rad_t).  split = d - rad_t satisfies both when the
    # loop exits right after the meeting expansion; clamp for safety.
    split = jnp.clip(d - rad_t, 0, rad_s)
    split = jnp.where(connected, split, 0)
    return BidirResult(dist_s, dist_t, sigma_s, sigma_t, d, split)


def bidirectional_bfs(graph: Graph, s, t, *,
                      max_levels: int | None = None) -> BidirResult:
    """Balanced bidirectional BFS from s to t — B=1 wrapper over
    :func:`bidirectional_bfs_batched`."""
    res = bidirectional_bfs_batched(
        graph,
        jnp.asarray(s, jnp.int32).reshape(1),
        jnp.asarray(t, jnp.int32).reshape(1),
        max_levels=max_levels)
    return BidirResult(res.dist_s[:, 0], res.dist_t[:, 0], res.sigma_s[:, 0],
                       res.sigma_t[:, 0], res.d[0], res.split[0])


# ---------------------------------------------------------------------------
# Weighted lane: bucketed delta-stepping + shortest-path-DAG counting
# ---------------------------------------------------------------------------
#
# delta-stepping (Meyer & Sanders 2003) adapted to the same vertex-major
# batched discipline as the BFS above: where BFS advances one exact
# level per relaxation, delta-stepping advances one *distance window*
# [ws, ws + delta) per sample — every "fresh" vertex (tentative
# distance improved since it last served as a relax source) inside the
# window relaxes its out-edges through the min-plus dispatcher
# ``repro.kernels.frontier.frontier_relax``, and the window only slides
# forward (by whole delta multiples, to the bucket holding the closest
# fresh vertex) once no fresh vertex remains inside it.  Bucket
# membership is exactly the BFS frontier mask generalized to a float
# window test, so the sharded twin ships it through the SAME
# chunk-occupancy exchange protocol (``_exchange_masked_values``) —
# buckets instead of levels on the wire.
#
# Two degeneracies pin the lane against the BFS drivers bit-for-bit
# (tests/test_weighted.py):
#   * delta = +inf     -> the window never constrains: every fresh
#                         vertex relaxes every round (batched
#                         Bellman-Ford);
#   * integer weights, delta = 1 -> each round's relax set IS the BFS
#                         frontier at that depth, tent is the float
#                         image of BFS dist, and the DAG sigma below
#                         reproduces the BFS segment sums bitwise (same
#                         COO edge order, same masked addends).
#
# sigma is computed post hoc instead of on the fly: once tent has
# converged, edge (u, v) is on the shortest-path DAG iff
# tent[u] + w(u,v) == tent[v] (exact float equality — the drivers are
# meant for exactly representable weights, see graph.with_weights), and
# path counts are the fixed point of one segment-sum sweep per DAG hop
# depth.  This costs extra sweeps but keeps the relaxation loop free of
# the settled-order bookkeeping a fused Brandes forward phase needs,
# and the sweep count it returns is the weighted analogue of
# BFSResult.levels (a vertex-diameter observable for the engine).


class SSSPResult(NamedTuple):
    """Result of (batched) delta-stepping SSSP with path counting.

    Same layout contract as :class:`BFSResult` with float distances:
    ``dist``/``sigma`` are vertex-major (rows, B) — rows = V+1 or
    csc.v_pad replicated, shard_rows on the sharded lane.  ``dist`` is
    the true shortest-path distance, with the BFS sentinels carried
    over as *negative floats* so estimator reachability tests
    (``d >= 0``) work unchanged: -1.0 unreached, -3.0 sink/pad rows
    (the source itself is 0.0 — nonnegative weights keep every real
    distance >= 0).  ``levels`` is the shortest-path DAG hop depth per
    sample (max edge count over all shortest paths — the quantity that
    bounds a weighted path-sampler walk, and the drop-in replacement
    for BFS ``levels`` in vertex-diameter arithmetic).  ``buckets`` is
    the number of window advances the relaxation loop took — the
    delta-stepping cost observable the weighted_sweep benchmark
    compares against BFS level counts (0 when delta = +inf: the
    Bellman-Ford degeneracy never slides the window).
    """
    dist: jax.Array     # (rows, B) float32; -1.0 unreached, -3.0 sink/pad
    sigma: jax.Array    # (rows, B) float32; rescaled DAG path counts
    levels: jax.Array   # (B,) int32; shortest-path DAG hop depth
    buckets: jax.Array  # (B,) int32; window advances taken
    exchange: Optional[jax.Array] = None   # (2,) [rounds, sparse] | None


def _default_delta(weight, n_edges: int):
    """Paper-standard bucket width heuristic: the mean positive edge
    weight (padded weight slots are 0.0, so the padded sum is the real
    sum).  Matches delta = Theta(1/avg-degree * avg-weight) up to the
    constant on the graphs the benchmark sweeps."""
    return jnp.sum(weight) / jnp.float32(max(int(n_edges), 1))


def _finalize_weighted_dist(tent, n_nodes: int):
    """Map internal +inf tentative distances to the public sentinel
    encoding (-1.0 unreached, -3.0 sink/pad rows)."""
    dist = jnp.where(jnp.isfinite(tent), tent, jnp.float32(-1.0))
    rows = tent.shape[0]
    grow = jnp.arange(rows)
    return jnp.where((grow >= n_nodes)[:, None], jnp.float32(-3.0), dist)


def delta_sssp_batched(graph: Graph, sources, *, delta=None) -> SSSPResult:
    """B concurrent weighted SSSP (bucketed delta-stepping) with
    shortest-path counting.

    Requires ``graph.weight`` (attach via :func:`repro.core.graph.
    with_weights`); ``delta`` is the bucket width (default: mean edge
    weight; ``jnp.inf`` degrades to batched Bellman-Ford).  One shared
    while_loop relaxes all B samples per round; a sample's window only
    advances when none of its fresh vertices sit inside it, so settled
    vertices (strictly positive weights) never relax again and the
    round count is bounded by buckets + DAG depth per sample.
    """
    if graph.weight is None:
        raise ValueError(
            "delta_sssp_batched needs per-edge weights; attach them with "
            "repro.core.graph.with_weights(graph, w)")
    sources = jnp.asarray(sources, jnp.int32)
    b = sources.shape[0]
    rows = _state_rows(graph)
    cols = jnp.arange(b)
    inf = jnp.float32(jnp.inf)
    if delta is None:
        delta = _default_delta(graph.weight, graph.n_edges)
    delta = jnp.asarray(delta, jnp.float32)
    tent0 = jnp.full((rows, b), inf, jnp.float32).at[sources, cols].set(0.0)
    fresh0 = jnp.zeros((rows, b), jnp.bool_).at[sources, cols].set(True)
    # generous static cap: every round either empties a window or
    # improves some tentative distance; 4V + 8 covers both phases with
    # slack (the tests never get near it)
    max_rounds = 4 * graph.n_nodes + 8

    # state: tent, fresh, ws (per-sample window start), nbuckets, round,
    # anyfresh (carried so cond reads no reduction over big state)
    def cond(st):
        _t, _f, _w, _n, it, anyfresh = st
        return jnp.any(anyfresh) & (it < max_rounds)

    def body(st):
        tent, fresh, ws, nbuckets, it, _any = st
        relax_src = fresh & (tent < ws[None, :] + delta)
        cand = frontier_relax(graph.src, graph.dst, graph.weight, tent,
                              relax_src, csc=graph.csc)
        improved = cand < tent
        tent = jnp.where(improved, cand, tent)
        # a relaxed vertex stops being fresh unless this very round
        # improved it again (possible: same-window predecessors)
        fresh = (fresh & ~relax_src) | improved
        in_win = fresh & (tent < ws[None, :] + delta)
        settled = ~jnp.any(in_win, axis=0)
        m = jnp.min(jnp.where(fresh, tent, inf), axis=0)
        # slide to the bucket of the closest fresh vertex (skipping
        # empty buckets); with delta = inf the floor would be nan —
        # Bellman-Ford never slides, so pin ws to m (any finite value
        # keeps the window all-covering)
        ws_next = jnp.where(jnp.isinf(delta), m,
                            delta * jnp.floor(m / delta))
        adv = settled & jnp.isfinite(m)
        ws = jnp.where(adv, ws_next, ws)
        nbuckets = jnp.where(adv & ~jnp.isinf(delta), nbuckets + 1, nbuckets)
        anyfresh = jnp.any(fresh, axis=0)
        return tent, fresh, ws, nbuckets, it + 1, anyfresh

    init = (tent0, fresh0, jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.int32(0),
            jnp.ones((b,), jnp.bool_))
    tent, _f, _w, nbuckets, _it, _a = jax.lax.while_loop(cond, body, init)
    sigma, depth = _dag_sigma_fixed_point(graph, tent, sources)
    return SSSPResult(_finalize_weighted_dist(tent, graph.n_nodes), sigma,
                      depth, nbuckets)


def _dag_sigma_fixed_point(graph: Graph, tent, sources):
    """Shortest-path counts on the converged distance state: iterate
    the DAG segment-sum sweep (``dag_sigma_batched_ref``) with source
    rows pinned to 1 until nothing changes.  A vertex at DAG hop depth
    h is final after sweep h (all its predecessors are), and the last
    sweep recomputes every count from final predecessor values in COO
    edge order — exactly the BFS lane's per-level segment sums, which
    is the bitwise hinge of the integer-weight degeneracy tests.
    Returns (sigma, depth) with depth (B,) = the last sweep that
    changed each column = the DAG hop depth.  The BFS rescale guard is
    applied per sweep (uniform column scale — ratio consumers only); a
    column that rescales keeps "changing" and exits on the V+1 cap,
    which is the correct conservative depth for such graphs.
    """
    b = tent.shape[1]
    cols = jnp.arange(b)
    sources = jnp.asarray(sources, jnp.int32)
    sigma0 = jnp.zeros(tent.shape, jnp.float32).at[sources, cols].set(1.0)
    max_sweeps = graph.n_nodes + 1

    def cond(st):
        _s, it, changed, _d = st
        return jnp.any(changed) & (it < max_sweeps)

    def body(st):
        sigma, it, _c, depth = st
        new = dag_sigma_batched_ref(graph.src, graph.dst, graph.weight,
                                    tent, sigma)
        new = new.at[sources, cols].set(1.0)
        m = jnp.max(new, axis=0, keepdims=True)
        scale = jnp.where(m > _RESCALE_THRESHOLD, 1.0 / m, 1.0)
        new = new * scale
        col_changed = jnp.any(new != sigma, axis=0)
        depth = jnp.where(col_changed, it + 1, depth)
        return new, it + 1, col_changed, depth

    sigma, _it, _c, depth = jax.lax.while_loop(
        cond, body, (sigma0, jnp.int32(0), jnp.ones((b,), jnp.bool_),
                     jnp.zeros((b,), jnp.int32)))
    return sigma, depth


# ---------------------------------------------------------------------------
# Sharded lane (vertex-partitioned graphs, inside shard_map)
# ---------------------------------------------------------------------------
#
# The sharded drivers mirror the replicated ones, with the while_loop
# state kept SHARDED vertex-major: each device carries only the
# (shard_rows, B) slice of dist/sigma for its owned rows, and one level
# exchanges only the masked frontier slice sigma * [dist == level] (the
# paper's "communicate only the sampling state" discipline applied to
# the BFS itself) — through the bitmap-scheduled exchange of
# _gather_frontier_sharded below, which ships only the source chunks
# that actually hold frontier rows whenever they fit the partition's
# static chunk budget, and the dense all_gather otherwise.  Collectives
# per level: the occupancy-bitmap all_gather + its pmax, the frontier
# exchange (sparse pair of all_gathers or the dense one), the pmax of
# the rescale guard, and the psum of the new-vertex count; everything
# else is local.  Loop conditions read only carried (replicated)
# scalars, so no collective ever runs inside a while_loop cond.  Parity
# contract: max/min/sum reductions over the vertex axis split exactly
# into (local reduce, cross-shard reduce), the sparse exchange
# reconstructs bit-for-bit the array the dense gather would produce
# (skipped blocks are exactly the all-zero blocks of the masked
# frontier), and the per-destination contribution order inside a shard
# equals the replicated CSC bucket order — so on integer-valued sigma
# the sharded lane is bit-for-bit identical to the replicated drivers
# regardless of which protocol each level takes (asserted in
# tests/test_partition).


def _init_state_sharded(pg: PartitionedGraph, sources, axis):
    """Batched sharded BFS init: this device's (shard_rows, B) slice.

    Rows map to global rows ``offset + r`` (offset from the device's
    flattened mesh index — shard i lives on device i); rows at or past
    ``n_nodes`` (the sink and tile padding) start at dist -3 / sigma 0
    and stay there.  A source lands only on its owner's slice.
    """
    b = sources.shape[0]
    rows = pg.shard_rows
    cols = jnp.arange(b)
    offset = jax.lax.axis_index(axis) * rows
    grow = offset + jnp.arange(rows)
    dist = jnp.broadcast_to(
        jnp.where(grow < pg.n_nodes, jnp.int32(-1), _SINK_DIST)[:, None],
        (rows, b))
    loc = jnp.clip(sources - offset, 0, rows - 1)
    own = (sources >= offset) & (sources < offset + rows)
    dist = dist.at[loc, cols].set(jnp.where(own, 0, dist[loc, cols]))
    sigma = jnp.zeros((rows, b), jnp.float32)
    sigma = sigma.at[loc, cols].set(jnp.where(own, 1.0, 0.0))
    return dist, sigma


def _read_rows_sharded(pg: PartitionedGraph, state, idx, axis):
    """Gather ``state[idx[b], b]`` (global rows) from the sharded state:
    the owner contributes its value, everyone else 0, one psum."""
    b = idx.shape[0]
    rows = pg.shard_rows
    offset = jax.lax.axis_index(axis) * rows
    loc = jnp.clip(idx - offset, 0, rows - 1)
    own = (idx >= offset) & (idx < offset + rows)
    vals = jnp.where(own, state[loc, jnp.arange(b)], 0)
    return jax.lax.psum(vals, axis)


def _gather_frontier_sharded(pg: PartitionedGraph, dist, sigma, level,
                             active, axis):
    """The per-level frontier exchange (DESIGN.md §Frontier exchange).

    Returns ``(fvals, src_bits, took_sparse)``: the (v_pad, B) masked
    frontier values ``sigma * [dist == level][active]`` over the GLOBAL
    rows, the (n_global_chunks,) int32 source-chunk occupancy bits that
    scheduled them, and a replicated int32 flag — 1 when this level
    went over the sparse protocol, 0 on dense (including the
    dense-only degenerate below).  The flag is an observation of the
    ``lax.cond`` predicate, feeds nothing, and exists so the drivers
    can tally protocol choices for telemetry.  Two protocols produce
    the identical ``fvals``:

    * **dense** — one tiled all_gather of the local (shard_rows, B)
      masked slice (the only protocol when ``pg.exchange_budget == 0``);
    * **bitmap-scheduled sparse** — each shard compacts its active
      source chunks (cumsum of its occupancy bits) into
      ``pg.exchange_budget`` static (chunk_rows, B) slots, all-gathers
      the slot values + their global chunk indices, and scatters
      received chunks into the zeroed dense view.  Inactive chunks of
      the masked frontier are all-zero by construction, so the
      reconstruction is bit-for-bit the dense gather's result.

    The schedule works at ``pg.exchange_chunk_rows`` granularity (a
    divisor of the kernel node block — see the partition module
    docstring for why node blocks themselves are too coarse).  The
    occupancy bits are always exchanged (coarsened by a reshape-max,
    they double as the expansion kernel's edge-block skip schedule),
    and their pmaxed per-shard count picks the protocol: a replicated
    scalar, so every shard takes the same ``lax.cond`` branch and the
    while_loop stays shape-stable — any level whose worst shard
    overflows the budget falls back to dense for that level only.

    ``active`` (B,) masks FINISHED samples out of the wire entirely:
    a sample that left its loop keeps a frozen ``level`` entry, so its
    last frontier would otherwise be exchanged (and counted by the
    bitmap) on every remaining iteration.  Dropping it is
    semantics-preserving — inactive columns' contributions are
    discarded by every caller — and is what makes the measured
    occupancy match the per-level accounting of
    :class:`repro.core.partition.ExchangePlan`.
    """
    chunk = pg.exchange_chunk_rows
    fmask = (dist == level[None, :]) & active[None, :]
    fvals_local = jnp.where(fmask, sigma, 0.0)
    bits_local = frontier_source_block_bitmap(dist, level, chunk,
                                              active)     # (cps,)
    return _exchange_masked_values(pg, fvals_local, bits_local, axis)


def _exchange_masked_values(pg: PartitionedGraph, fvals_local, bits_local,
                            axis):
    """The wire half of the frontier exchange, payload-agnostic.

    ``fvals_local`` is this shard's (shard_rows, B) masked value slice —
    zero everywhere outside the rows its ``bits_local`` occupancy bits
    (one per ``exchange_chunk_rows`` chunk) mark as occupied; that
    invariant is what makes the sparse reconstruction bit-for-bit equal
    to the dense gather.  Both the BFS level exchange (values = masked
    sigma) and the delta-stepping bucket exchange (values = tent + 1 of
    this round's relax set) ship through here, so the two drivers share
    one protocol, one break-even guard and one accounting convention.
    Returns ``(fvals, src_bits, took_sparse)`` exactly as documented on
    :func:`_gather_frontier_sharded`.
    """
    chunk = pg.exchange_chunk_rows
    cps = pg.exchange_chunks_per_shard
    b = fvals_local.shape[1]
    budget = pg.exchange_budget
    src_bits = jax.lax.all_gather(bits_local, axis, axis=0, tiled=True)
    # break-even guard at the ACTUAL batch width (ExchangePlan
    # .sparse_available, same arithmetic): a budget whose padded sparse
    # send — values + indices — would not undercut the dense gather
    # degenerates to dense-only, so the sparse branch is never traced
    # at a loss
    if budget <= 0 or budget * (chunk * b + 1) >= cps * chunk * b:
        fvals = jax.lax.all_gather(fvals_local, axis, axis=0, tiled=True)
        return fvals, src_bits, jnp.int32(0)

    n_gchunks = pg.n_shards * cps
    fits = jax.lax.pmax(jnp.sum(bits_local), axis) <= budget

    def sparse(_):
        # compact: active local chunk j -> slot cumsum(bits)[j] - 1
        # (< budget whenever this branch runs), inactive -> dump slot
        pos = jnp.cumsum(bits_local) - 1
        slot = jnp.where(bits_local == 1, pos, budget)
        chk_of_slot = jnp.full((budget + 1,), cps, jnp.int32).at[slot].set(
            jnp.arange(cps, dtype=jnp.int32), mode="drop")[:budget]
        chunks = jnp.concatenate(
            [fvals_local.reshape(cps, chunk, b),
             jnp.zeros((1, chunk, b), fvals_local.dtype)])
        send_vals = chunks[chk_of_slot]               # (budget, chunk, B)
        offset = jax.lax.axis_index(axis) * cps       # global chunk ids
        send_idx = jnp.where(chk_of_slot < cps, offset + chk_of_slot,
                             n_gchunks)               # sentinel: dump row
        g_vals = jax.lax.all_gather(send_vals, axis, axis=0, tiled=True)
        g_idx = jax.lax.all_gather(send_idx, axis, axis=0, tiled=True)
        # scatter-reconstruct; padded slots carry zero chunks and all
        # land on the sliced-off sentinel row, active global chunks are
        # unique across shards — deterministic despite the duplicates
        dense_view = jnp.zeros((n_gchunks + 1, chunk, b),
                               fvals_local.dtype).at[g_idx].set(
            g_vals, mode="drop")
        return dense_view[:n_gchunks].reshape(n_gchunks * chunk, b)

    def dense(_):
        return jax.lax.all_gather(fvals_local, axis, axis=0, tiled=True)

    return (jax.lax.cond(fits, sparse, dense, None), src_bits,
            fits.astype(jnp.int32))


def _expand_level_sharded(pg: PartitionedGraph, dist, sigma, level, active,
                          axis):
    """One sharded batched BFS relaxation.

    The only place the per-level exchange happens:
    :func:`_gather_frontier_sharded` delivers the masked frontier
    values over the global rows (dist itself never crosses the wire;
    the dispatcher's sharded-lane (dist, sigma) operands are
    synthesized from the gathered values, which XLA fuses away), then
    each device expands only its owned destination rows through the
    ``shard=`` route of ``repro.kernels.frontier.frontier_expand`` —
    with the exchange schedule's source-block bits recycled as the
    kernel's edge-block skip bitmap.  The rescale guard and the
    new-vertex count are the only other cross-shard reductions.
    Returns updated local (dist, sigma, n_new (B,) global,
    took_sparse () replicated int32).
    """
    fvals, src_bits, took = _gather_frontier_sharded(pg, dist, sigma, level,
                                                     active, axis)
    # reached frontier vertices always carry sigma > 0, so fvals > 0 is
    # exactly the frontier mask — synthesize the dispatcher's contract
    fdist = jnp.where(fvals > 0.0, level[None, :], jnp.int32(-1))
    lcsc = pg.shards.local()
    contrib = frontier_expand(
        lcsc.src, lcsc.dst, fdist, fvals, level, shard=lcsc,
        block_active=edge_bitmap_from_source_bits(
            lcsc, src_bits, pg.exchange_chunk_rows))
    new = (contrib > 0) & (dist == -1) & active[None, :]
    dist = jnp.where(new, level[None, :] + 1, dist)
    sigma = jnp.where(new, contrib, sigma)
    # rescale per sample against the GLOBAL max (uniform column scale
    # across shards => exact ratios, bit-identical to the replicated
    # lane's guard)
    m = jax.lax.pmax(jnp.max(jnp.where(new, sigma, 0.0), axis=0), axis)
    scale = jnp.where(m > _RESCALE_THRESHOLD, 1.0 / m, 1.0)
    sigma = sigma * scale[None, :]
    n_new = jax.lax.psum(jnp.sum(new.astype(jnp.int32), axis=0), axis)
    return dist, sigma, n_new, took


def bfs_sssp_batched_sharded(pg: PartitionedGraph, sources, *, axis,
                             stop_nodes=None) -> BFSResult:
    """Sharded twin of :func:`bfs_sssp_batched` — call inside shard_map.

    ``axis`` names the mesh axis (or axes) carrying the shard
    dimension.  The returned ``dist``/``sigma`` are this device's LOCAL
    (shard_rows, B) slices; ``levels`` is replicated.  The stop-node
    check reads one sharded row per sample in the loop BODY and carries
    the result, so the while_loop cond stays collective-free.
    """
    axis = axis_tuple(axis)
    sources = jnp.asarray(sources, jnp.int32)
    b = sources.shape[0]
    dist0, sigma0 = _init_state_sharded(pg, sources, axis)
    if stop_nodes is not None:
        stop_open0 = _read_rows_sharded(pg, dist0, stop_nodes, axis) < 0
    else:
        stop_open0 = jnp.ones((b,), jnp.bool_)

    def go_mask(level, n_new, stop_open):
        return (n_new > 0) & (level < pg.n_nodes) & stop_open

    def cond(state):
        _dist, _sigma, level, n_new, stop_open, _xch = state
        return jnp.any(go_mask(level, n_new, stop_open))

    def body(state):
        dist, sigma, level, n_new, stop_open, xch = state
        active = go_mask(level, n_new, stop_open)
        dist, sigma, n_new2, took = _expand_level_sharded(pg, dist, sigma,
                                                          level, active, axis)
        # every body iteration is exactly one frontier exchange; tally
        # [levels, of which sparse] for ExchangePlan pricing (telemetry
        # observation only — nothing downstream reads it)
        xch = xch + jnp.stack([jnp.int32(1), took])
        level = jnp.where(active, level + 1, level)
        n_new = jnp.where(active, n_new2, n_new)
        if stop_nodes is not None:
            stop_open = _read_rows_sharded(pg, dist, stop_nodes, axis) < 0
        return dist, sigma, level, n_new, stop_open, xch

    dist, sigma, _levels, _, _, xch = jax.lax.while_loop(
        cond, body, (dist0, sigma0, jnp.zeros((b,), jnp.int32),
                     jnp.ones((b,), jnp.int32), stop_open0,
                     jnp.zeros((2,), jnp.int32)))
    settled = jax.lax.pmax(
        jnp.max(jnp.where(dist >= 0, dist, 0), axis=0), axis)
    return BFSResult(dist, sigma, settled, xch)


def bidirectional_bfs_batched_sharded(pg: PartitionedGraph, s, t, *, axis,
                                      max_levels: int | None = None
                                      ) -> BidirResult:
    """Sharded twin of :func:`bidirectional_bfs_batched` (inside
    shard_map).  Both sides' states stay sharded; per iteration the
    balanced rule compares GLOBAL frontier sizes (one psum), the chosen
    side expands through :func:`_expand_level_sharded`, and the
    meeting test (any vertex settled from both sides) is a psum carried
    into the next cond.  ``dist_*``/``sigma_*`` come back as local
    (shard_rows, B) slices; ``d``/``split`` replicated.
    """
    axis = axis_tuple(axis)
    max_levels = pg.n_nodes if max_levels is None else max_levels
    s = jnp.asarray(s, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    b = s.shape[0]
    dist_s0, sigma_s0 = _init_state_sharded(pg, s, axis)
    dist_t0, sigma_t0 = _init_state_sharded(pg, t, axis)

    def met_of(dist_s, dist_t):
        local = jnp.sum(((dist_s >= 0) & (dist_t >= 0)).astype(jnp.int32),
                        axis=0)
        return jax.lax.psum(local, axis) > 0

    def active_mask(rad_s, rad_t, alive, met):
        return (~met) & alive & (rad_s + rad_t < max_levels)

    # state: dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive, met,
    # xch ((2,) exchange tally — see bfs_sssp_batched_sharded)
    def cond(st):
        _, _, rad_s, _, _, rad_t, alive, met, _xch = st
        return jnp.any(active_mask(rad_s, rad_t, alive, met))

    def body(st):
        dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive, met, xch = st
        active = active_mask(rad_s, rad_t, alive, met)
        fs = jax.lax.psum(jnp.sum(
            (dist_s == rad_s[None, :]).astype(jnp.int32), axis=0), axis)
        ft = jax.lax.psum(jnp.sum(
            (dist_t == rad_t[None, :]).astype(jnp.int32), axis=0), axis)
        pick_s = fs <= ft
        exp_dist = jnp.where(pick_s[None, :], dist_s, dist_t)
        exp_sigma = jnp.where(pick_s[None, :], sigma_s, sigma_t)
        exp_level = jnp.where(pick_s, rad_s, rad_t)
        nd, ns, n_new, took = _expand_level_sharded(pg, exp_dist, exp_sigma,
                                                    exp_level, active, axis)
        xch = xch + jnp.stack([jnp.int32(1), took])
        upd_s = pick_s & active
        upd_t = (~pick_s) & active
        dist_s = jnp.where(upd_s[None, :], nd, dist_s)
        sigma_s = jnp.where(upd_s[None, :], ns, sigma_s)
        rad_s = jnp.where(upd_s, rad_s + 1, rad_s)
        dist_t = jnp.where(upd_t[None, :], nd, dist_t)
        sigma_t = jnp.where(upd_t[None, :], ns, sigma_t)
        rad_t = jnp.where(upd_t, rad_t + 1, rad_t)
        alive = jnp.where(active, n_new > 0, alive)
        met = met_of(dist_s, dist_t)
        return (dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, alive, met,
                xch)

    zeros = jnp.zeros((b,), jnp.int32)
    init = (dist_s0, sigma_s0, zeros, dist_t0, sigma_t0, zeros,
            jnp.ones((b,), jnp.bool_), met_of(dist_s0, dist_t0),
            jnp.zeros((2,), jnp.int32))
    dist_s, sigma_s, rad_s, dist_t, sigma_t, rad_t, _alive, _met, xch = \
        jax.lax.while_loop(cond, body, init)

    both = (dist_s >= 0) & (dist_t >= 0)
    dsum = jnp.where(both, dist_s + dist_t, jnp.iinfo(jnp.int32).max)
    d = jax.lax.pmin(jnp.min(dsum, axis=0), axis)
    connected = d < jnp.iinfo(jnp.int32).max
    d = jnp.where(connected, d, -1)
    split = jnp.clip(d - rad_t, 0, rad_s)
    split = jnp.where(connected, split, 0)
    return BidirResult(dist_s, dist_t, sigma_s, sigma_t, d, split, xch)


def _relax_round_sharded(pg: PartitionedGraph, tent, relax_mask, axis):
    """One sharded min-plus relaxation round: ship this round's bucket
    (the ``relax_mask`` rows of ``tent``) through the frontier exchange
    and relax the local destination rows.

    The wire payload must satisfy the exchange invariant (zero outside
    occupied chunks) and survive the zero-masking, but a relax-active
    source can legitimately sit at tent 0.0 (the source vertex), so the
    bucket ships as ``tent + 1`` where active / 0 elsewhere — exact in
    float32 for every tentative distance below 2**23, far beyond the
    quantized-weight graphs this lane targets — and is decoded back on
    arrival.  The occupancy bits are the BFS chunk bitmap generalized
    to the bucket mask (any sample active in the chunk), so protocol
    choice, budget arithmetic and the ``took`` tally mean exactly what
    they mean on the BFS lane.  Returns (cand (shard_rows, B), took).
    """
    chunk = pg.exchange_chunk_rows
    fvals_local = jnp.where(relax_mask, tent + 1.0, 0.0)
    occ = jnp.any(relax_mask, axis=1)
    bits_local = jnp.max(occ.reshape(-1, chunk).astype(jnp.int32), axis=1)
    fvals, _src_bits, took = _exchange_masked_values(pg, fvals_local,
                                                     bits_local, axis)
    active_g = fvals > 0.0
    tent_g = jnp.where(active_g, fvals - 1.0, jnp.inf)
    lcsc = pg.shards.local()
    cand = frontier_relax(lcsc.src, lcsc.dst, lcsc.weight, tent_g, active_g,
                          shard=lcsc)
    return cand, took


def delta_sssp_batched_sharded(pg: PartitionedGraph, sources, *, axis,
                               delta=None) -> SSSPResult:
    """Sharded twin of :func:`delta_sssp_batched` — call inside
    shard_map.  ``tent``/``fresh`` stay sharded vertex-major; per round
    only the bucket slice crosses the wire (through the same
    bitmap-scheduled exchange as the BFS levels — buckets instead of
    levels on the wire), and the window-advance decision is made on
    replicated scalars (one psum for the in-window count, one pmin for
    the closest fresh tent), so every shard slides in lockstep and the
    loop conditions stay collective-free.  ``dist``/``sigma`` come back
    as this device's (shard_rows, B) slices; ``levels``/``buckets``/
    ``exchange`` replicated.  The sigma phase all-gathers the converged
    tent once, then runs the DAG fixed point with one dense sigma
    all_gather per sweep (DAG sweeps don't have a sparse frontier — on
    a converged state every reached row is "active").
    """
    if pg.weight is None:
        raise ValueError(
            "delta_sssp_batched_sharded needs per-edge weights; partition "
            "a graph built with repro.core.graph.with_weights")
    axis = axis_tuple(axis)
    sources = jnp.asarray(sources, jnp.int32)
    b = sources.shape[0]
    rows = pg.shard_rows
    cols = jnp.arange(b)
    inf = jnp.float32(jnp.inf)
    if delta is None:
        delta = _default_delta(pg.weight, pg.n_edges)
    delta = jnp.asarray(delta, jnp.float32)
    offset = jax.lax.axis_index(axis) * rows
    loc = jnp.clip(sources - offset, 0, rows - 1)
    own = (sources >= offset) & (sources < offset + rows)
    tent0 = jnp.full((rows, b), inf, jnp.float32)
    tent0 = tent0.at[loc, cols].set(jnp.where(own, 0.0, tent0[loc, cols]))
    fresh0 = jnp.zeros((rows, b), jnp.bool_)
    fresh0 = fresh0.at[loc, cols].set(own)
    max_rounds = 4 * pg.n_nodes + 8

    # state mirrors the replicated driver + the (2,) exchange tally;
    # anyfresh/ws/nbuckets are replicated by construction (psum / pmin
    # inputs only), so cond stays collective-free
    def cond(st):
        _t, _f, _w, _n, it, anyfresh, _x = st
        return jnp.any(anyfresh) & (it < max_rounds)

    def body(st):
        tent, fresh, ws, nbuckets, it, _any, xch = st
        relax_mask = fresh & (tent < ws[None, :] + delta)
        cand, took = _relax_round_sharded(pg, tent, relax_mask, axis)
        xch = xch + jnp.stack([jnp.int32(1), took])
        improved = cand < tent
        tent = jnp.where(improved, cand, tent)
        fresh = (fresh & ~relax_mask) | improved
        in_win = fresh & (tent < ws[None, :] + delta)
        unsettled = jax.lax.psum(
            jnp.sum(in_win.astype(jnp.int32), axis=0), axis)
        m = jax.lax.pmin(jnp.min(jnp.where(fresh, tent, inf), axis=0), axis)
        ws_next = jnp.where(jnp.isinf(delta), m,
                            delta * jnp.floor(m / delta))
        adv = (unsettled == 0) & jnp.isfinite(m)
        ws = jnp.where(adv, ws_next, ws)
        nbuckets = jnp.where(adv & ~jnp.isinf(delta), nbuckets + 1, nbuckets)
        anyfresh = jax.lax.psum(
            jnp.sum(fresh.astype(jnp.int32), axis=0), axis) > 0
        return tent, fresh, ws, nbuckets, it + 1, anyfresh, xch

    init = (tent0, fresh0, jnp.zeros((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.int32(0),
            jnp.ones((b,), jnp.bool_), jnp.zeros((2,), jnp.int32))
    tent, _f, _w, nbuckets, _it, _a, xch = jax.lax.while_loop(cond, body,
                                                              init)

    # --- sigma phase: DAG fixed point over the gathered distance state
    tent_g = jax.lax.all_gather(tent, axis, axis=0, tiled=True)
    sigma0 = jnp.zeros((rows, b), jnp.float32)
    sigma0 = sigma0.at[loc, cols].set(jnp.where(own, 1.0, 0.0))
    lcsc = pg.shards.local()
    max_sweeps = pg.n_nodes + 1

    def scond(st):
        _s, it, changed, _d = st
        return jnp.any(changed) & (it < max_sweeps)

    def sbody(st):
        sigma, it, _c, depth = st
        sigma_g = jax.lax.all_gather(sigma, axis, axis=0, tiled=True)
        new = dag_sigma_sharded_ref(lcsc, tent_g, sigma_g, tent)
        new = new.at[loc, cols].set(jnp.where(own, 1.0, new[loc, cols]))
        m = jax.lax.pmax(jnp.max(new, axis=0), axis)
        scale = jnp.where(m > _RESCALE_THRESHOLD, 1.0 / m, 1.0)
        new = new * scale[None, :]
        col_changed = jax.lax.psum(
            jnp.sum((new != sigma).astype(jnp.int32), axis=0), axis) > 0
        depth = jnp.where(col_changed, it + 1, depth)
        return new, it + 1, col_changed, depth

    sigma, _it, _c, depth = jax.lax.while_loop(
        scond, sbody, (sigma0, jnp.int32(0), jnp.ones((b,), jnp.bool_),
                       jnp.zeros((b,), jnp.int32)))

    grow = offset + jnp.arange(rows)
    dist = jnp.where(jnp.isfinite(tent), tent, jnp.float32(-1.0))
    dist = jnp.where((grow >= pg.n_nodes)[:, None], jnp.float32(-3.0), dist)
    return SSSPResult(dist, sigma, depth, nbuckets, xch)
