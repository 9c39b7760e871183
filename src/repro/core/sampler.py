"""Uniform shortest-path sampling (the per-sample work of KADABRA).

One sample = (i) draw a uniform vertex pair (s, t), s != t; (ii) run a
balanced bidirectional BFS; (iii) draw ONE uniform-at-random shortest s-t
path; (iv) add 1 to the count of every *internal* vertex of that path.
KADABRA's estimator is then b~(x) = c~(x)/tau.

Samples are taken B at a time (``sample_path_batched``): the B
bidirectional searches share one batched frontier relaxation per level
(see ``repro.core.bfs``), so the edge list is streamed once per level for
the whole batch instead of once per sample — the arithmetic-intensity
move that makes the sampling phase run at memory bandwidth instead of at
edge-stream latency.  ``sample_batch`` accumulates ceil(n/B) such rounds
under a ``lax.scan``; B = 1 degenerates to the paper's one-sample-per-
thread formulation and is kept as the reference lane for parity tests.

Uniform path sampling is factorized through the BFS DAG:

  * every shortest s-t path crosses exactly one vertex w with
    dist_s(w) == L (the split level returned by the bidirectional search);
    the number of paths through w is sigma_s(w) * sigma_t(w), so w is
    drawn with probability proportional to that product (a batched
    per-column Gumbel-max over the vertex-major (V+1, B) weight matrix);
  * from w we walk backwards to s: at a vertex v on level l, the
    predecessor u in N(v) with dist_s(u) == l-1 is drawn with probability
    sigma_s(u) / sum(sigma_s over predecessors); symmetrically towards t.
    The B walks run under ``vmap`` (they touch O(path * deg) entries, not
    the edge stream, so per-sample execution costs nothing extra).

The backward step uses a *chunked weighted-reservoir* draw over the CSR
neighbor list: neighbors are visited in fixed-size chunks (static shapes
for XLA), a Gumbel-max picks a within-chunk candidate, and the candidate
replaces the running choice with probability W_chunk / W_total_so_far.
This is an exact weighted draw with O(deg) work and O(chunk) memory,
independent of the (power-law) maximum degree.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .bfs import (BidirResult, bfs_sssp_batched, bfs_sssp_batched_sharded,
                  bidirectional_bfs_batched,
                  bidirectional_bfs_batched_sharded, delta_sssp_batched,
                  delta_sssp_batched_sharded)
from .graph import Graph
from .partition import PartitionedGraph, axis_tuple

__all__ = ["PathSample", "ForwardSample", "sample_pair", "sample_pairs",
           "sample_path", "sample_path_batched",
           "sample_path_batched_sharded", "sample_path_forward_batched",
           "sample_path_forward_batched_sharded",
           "sample_path_weighted_batched",
           "sample_path_weighted_batched_sharded", "sample_batch"]

_NEG_INF = -1e30
_CHUNK = 128  # matches Graph pad_to; guarantees in-bounds dynamic slices


class PathSample(NamedTuple):
    contrib: jax.Array   # (..., V+1) float32 — 1.0 on internal path vertices
    valid: jax.Array     # (...) bool — False when s,t were disconnected
    length: jax.Array    # (...) int32 — path length d (edges), -1 if invalid
    # (2,) int32 [levels_exchanged, levels_sparse] from the sharded BFS
    # (telemetry observation; None on the replicated lanes)
    exchange: Optional[jax.Array] = None
    # (2,) int32 [expansions, streamed_blocks] of the round's replicated
    # BFS (BFSResult.steps); None on the sharded lanes
    steps: Optional[jax.Array] = None


def sample_pairs(key, n_nodes: int, batch: int):
    """``batch`` uniform pairs (s, t) with s != t, as (B,) arrays."""
    ks, kt = jax.random.split(key)
    s = jax.random.randint(ks, (batch,), 0, n_nodes)
    t = jax.random.randint(kt, (batch,), 0, n_nodes - 1)
    t = jnp.where(t >= s, t + 1, t)
    return s, t


def sample_pair(key, n_nodes: int):
    """Uniform (s, t) with s != t."""
    s, t = sample_pairs(key, n_nodes, 1)
    return s[0], t[0]


def _gumbel(key, shape):
    """Standard Gumbel noise of ``shape``."""
    return -jnp.log(-jnp.log(jax.random.uniform(
        key, shape, minval=1e-20, maxval=1.0)))


def _gumbel_argmax(key, logw, axis=-1):
    """Gumbel-max draw along ``axis``; works on (C,) weight vectors and
    on vertex-major (V+1, B) weight matrices (axis=0: one draw per sample
    column)."""
    return jnp.argmax(logw + _gumbel(key, logw.shape), axis=axis)


def _sample_predecessor(graph: Graph, key, v, level, dist, sigma):
    """Draw u ~ sigma[u] * [dist[u] == level-1] among neighbors of v.

    Weights are used as they are, however small: path counts are
    float32, and the BFS scales a whole column down each time a level's
    largest count passes 1e30 (``repro.core.bfs``), so on a graph with
    hundreds of levels, such as a lattice, the counts of the early
    levels end far below 1e-30 and those of the earliest flush to 0.
    Where every predecessor of ``v`` flushed, u is drawn uniformly among
    them with the same noise, which the weighted draw then leaves
    unused: the walk still reaches the source along a shortest path,
    and the key stream is the same either way."""
    start = graph.indptr[v]
    deg = graph.degree[v]
    n_chunks = (deg + _CHUNK - 1) // _CHUNK

    def body(i, carry):
        wsum, chosen, npred, anyone, key = carry
        key, k_in, k_acc = jax.random.split(key, 3)
        nbr = jax.lax.dynamic_slice(graph.indices, (start + i * _CHUNK,),
                                    (_CHUNK,))
        valid = jnp.arange(_CHUNK) < (deg - i * _CHUNK)
        pred = valid & (dist[nbr] == level - 1)
        w = jnp.where(pred, sigma[nbr], 0.0)
        wc = jnp.sum(w)
        pos = w > 0
        logw = jnp.where(pos, jnp.log(jnp.where(pos, w, 1.0)), _NEG_INF)
        g = _gumbel(k_in, logw.shape)
        cand = nbr[jnp.argmax(logw + g)]
        u = jax.random.uniform(k_acc)
        accept_p = jnp.where(wc > 0,
                             wc / jnp.where(wc > 0, wsum + wc, 1.0), 0.0)
        chosen = jnp.where(u < accept_p, cand, chosen)
        # the same draw with every predecessor weighted 1
        nc = jnp.sum(pred.astype(jnp.int32))
        anyone = jnp.where(
            u * (npred + nc).astype(jnp.float32) < nc.astype(jnp.float32),
            nbr[jnp.argmax(jnp.where(pred, g, _NEG_INF))], anyone)
        return wsum + wc, chosen, npred + nc, anyone, key

    wsum, chosen, _, anyone, _ = jax.lax.fori_loop(
        0, n_chunks, body, (jnp.float32(0.0), jnp.int32(-1), jnp.int32(0),
                            jnp.int32(-1), key))
    return jnp.where(wsum > 0, chosen, anyone)


def _walk_to_source(graph: Graph, key, start_node, start_level, dist, sigma,
                    contrib):
    """Walk from ``start_node`` (at ``start_level``) down to level 0,
    marking the *strictly internal* vertices visited (levels l-1 .. 1)."""

    def cond(carry):
        _v, l, _key, _contrib = carry
        return l > 1

    def body(carry):
        v, l, key, contrib = carry
        key, k = jax.random.split(key)
        u = _sample_predecessor(graph, k, v, l, dist, sigma)
        contrib = contrib.at[u].add(1.0)
        return u, l - 1, key, contrib

    _, _, _, contrib = jax.lax.while_loop(
        cond, body, (start_node, start_level, key, contrib))
    return contrib


def _finish_paths(graph, k_meet, k_s, k_t, res: BidirResult,
                  batch: int) -> PathSample:
    """Meeting-vertex draw + the two backward walks, from a completed
    bidirectional BFS state (shared by the replicated and the sharded
    sampling lanes — the sharded lane hands in the all-gathered state,
    so the draws below are stream-identical across lanes).  ``graph``
    only needs ``n_nodes`` and the CSR arrays (``indptr``/``indices``/
    ``degree``): both ``Graph`` and ``PartitionedGraph`` qualify."""
    valid = res.d >= 0                                          # (B,)

    # --- choose the meeting vertices w ~ sigma_s(w) * sigma_t(w) --------
    # (vertex-major BFS state: one Gumbel-max per sample column; the
    # weight matrix is cut to the logical V+1 rows so the Gumbel noise
    # shape — and with it the whole sample stream — is independent of
    # whether the state rides at csc.v_pad rows: a graph with and
    # without a persisted CSC layout draws identical samples)
    v1 = graph.n_nodes + 1
    on_split = ((res.dist_s[:v1] == res.split[None, :])
                & (res.dist_t[:v1] == (res.d - res.split)[None, :]))
    logw = jnp.where(
        on_split & valid[None, :],
        jnp.log(jnp.maximum(res.sigma_s[:v1], 1e-30))
        + jnp.log(jnp.maximum(res.sigma_t[:v1], 1e-30)),
        _NEG_INF,
    )
    w = _gumbel_argmax(k_meet, logw, axis=0).astype(jnp.int32)  # (B,)

    contrib = jnp.zeros((batch, graph.n_nodes + 1), jnp.float32)
    # w itself is internal iff it is neither s (split==0) nor t (split==d)
    w_internal = valid & (res.split > 0) & (res.split < res.d)
    contrib = contrib.at[jnp.arange(batch), w].add(
        jnp.where(w_internal, 1.0, 0.0))

    # --- backward walks; skipped naturally when levels are 0/invalid ----
    # (each walk reads its own sample's (V+1,) column: in_axes=1 on the
    # vertex-major state; contrib stays sample-major — it is reduced over
    # samples right after, never streamed through the kernels)
    lvl_s = jnp.where(valid, res.split, 0)
    lvl_t = jnp.where(valid, res.d - res.split, 0)
    walk = jax.vmap(_walk_to_source, in_axes=(None, 0, 0, 0, 1, 1, 0))
    contrib = walk(graph, jax.random.split(k_s, batch), w, lvl_s,
                   res.dist_s, res.sigma_s, contrib)
    contrib = walk(graph, jax.random.split(k_t, batch), w, lvl_t,
                   res.dist_t, res.sigma_t, contrib)
    # the sink row never receives contributions, but zero it defensively
    contrib = contrib.at[:, graph.n_nodes].set(0.0)
    return PathSample(contrib, valid, jnp.where(valid, res.d, -1))


def sample_path_batched(graph: Graph, key, batch: int) -> PathSample:
    """Take ``batch`` KADABRA samples concurrently.

    One batched bidirectional BFS serves all B pairs (shared edge
    stream, vertex-major (V+1, B) state); the meeting-vertex draw is a
    per-column Gumbel-max over the path-count products; the two backward
    walks are vmapped over the state's sample axis.  Returns a
    PathSample whose fields have a leading (B,) axis — fold ``contrib``
    with one sum over axis 0 to get the per-round count increment.
    """
    k_pair, k_meet, k_s, k_t = jax.random.split(key, 4)
    s, t = sample_pairs(k_pair, graph.n_nodes, batch)
    res: BidirResult = bidirectional_bfs_batched(graph, s, t)
    out = _finish_paths(graph, k_meet, k_s, k_t, res, batch)
    return out._replace(steps=res.steps)


def sample_path_batched_sharded(pg: PartitionedGraph, key, batch: int, *,
                                axis) -> PathSample:
    """Sharded twin of :func:`sample_path_batched` — call inside
    shard_map with a key REPLICATED across the shard axis (the whole
    mesh cooperatively advances one batch of samples; per-device keys
    would desynchronize the collective BFS).

    The bidirectional BFS runs with sharded state end-to-end — its
    per-level communication is the bitmap-scheduled frontier exchange
    of ``repro.core.bfs`` (KADABRA's balanced bidirectional frontiers
    are precisely the sparse regime it targets; the partition's
    ``exchange_budget`` governs it, no knob here); only after it
    completes is the per-sample state all-gathered ONCE for
    the meeting-vertex draw and the backward walks (O(V * B) per round
    vs O(V * B) per *level* if the BFS itself were replicated).  The
    key splits, the pair draw, the Gumbel draws and the walks are
    stream-identical to the replicated lane, so on the same key the two
    lanes produce bit-identical samples (given bit-identical BFS
    states).  Shard-local walks over halo-cached neighbor rows are the
    recorded follow-up that would drop the post-BFS gather too.
    """
    axis = axis_tuple(axis)
    k_pair, k_meet, k_s, k_t = jax.random.split(key, 4)
    s, t = sample_pairs(k_pair, pg.n_nodes, batch)
    res = bidirectional_bfs_batched_sharded(pg, s, t, axis=axis)

    def gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    full = BidirResult(gather(res.dist_s), gather(res.dist_t),
                       gather(res.sigma_s), gather(res.sigma_t),
                       res.d, res.split)
    out = _finish_paths(pg, k_meet, k_s, k_t, full, batch)
    return out._replace(exchange=res.exchange)


class ForwardSample(NamedTuple):
    """One round of B *forward-stream* draws (estimator-substrate lane).

    Extends :class:`PathSample` with the exhausted per-source distance
    columns and the drawn sources — the extra state that closeness /
    harmonic estimators consume (``repro.core.estimators``).  ``dist``
    rides at the BFS state's native row count (csc.v_pad when a CSC
    layout is persisted, V+1 otherwise); consumers slice to V+1.  On
    the WEIGHTED stream (``sample_path_weighted_batched``) ``dist`` is
    float32 (true weighted distances, sentinels -1.0/-3.0 — the
    estimators' ``d >= 0`` reachability tests hold for both dtypes) and
    ``length`` is the drawn path's EDGE count (hops), not its weight.
    """
    contrib: jax.Array   # (B, V+1) float32 — internal path-vertex marks
    valid: jax.Array     # (B,) bool — s,t connected
    length: jax.Array    # (B,) int32 — path edge count, -1 if invalid
    dist: jax.Array      # (rows, B) int32|float32 — dist from s (full SSSP)
    sources: jax.Array   # (B,) int32 — the drawn s
    # (2,) int32 exchange tally from the sharded BFS; None otherwise
    exchange: Optional[jax.Array] = None
    # (2,) int32 work count of the replicated BFS (PathSample.steps);
    # None on the sharded and weighted streams
    steps: Optional[jax.Array] = None


def _finish_forward_paths(graph, k_walk, s, t, dist, sigma,
                          batch: int) -> ForwardSample:
    """Backward path walk from t over a completed FORWARD BFS state.

    With the full (dist_s, sigma_s) in hand there is no meeting-vertex
    draw: walking back from t, choosing at each level-l vertex v the
    predecessor u ~ sigma_s(u) / sum over predecessors, selects every
    shortest s-t path with probability telescoping to 1 / sigma_s(t) —
    the same uniform-path law as the bidirectional lane, from one side.
    """
    v1 = graph.n_nodes + 1
    d = dist[t, jnp.arange(batch)]                              # (B,)
    valid = d > 0                                # s==t never drawn; d>=1
    contrib = jnp.zeros((batch, v1), jnp.float32)
    # the walk from t at level d marks levels d-1 .. 1 — exactly the
    # strictly internal vertices of the drawn path (t itself is the
    # start node and is never marked; s sits at level 0)
    lvl = jnp.where(valid, d, 0)
    walk = jax.vmap(_walk_to_source, in_axes=(None, 0, 0, 0, 1, 1, 0))
    contrib = walk(graph, jax.random.split(k_walk, batch), t, lvl,
                   dist, sigma, contrib)
    contrib = contrib.at[:, graph.n_nodes].set(0.0)
    return ForwardSample(contrib, valid, jnp.where(valid, d, -1), dist, s)


def sample_path_forward_batched(graph: Graph, key,
                                batch: int) -> ForwardSample:
    """Take ``batch`` samples through the FORWARD stream.

    One batched *full* single-source BFS per round (no stop nodes: each
    source's search runs to exhaustion so the distance columns are
    unbiased per-source distance vectors — the bidirectional lane
    truncates both sides at the meeting level and cannot provide this),
    then one backward walk per sample.  Betweenness contributions drawn
    from this stream follow the exact same uniform-shortest-path law as
    :func:`sample_path_batched`; the stream additionally exposes
    ``dist``/``sources`` for distance-based estimators.  The *sample
    stream differs* from the bidirectional lane (different key layout,
    different searches), so KADABRA bit-compatibility runs stay on
    ``sample_path_batched``.
    """
    k_pair, k_walk = jax.random.split(key)
    s, t = sample_pairs(k_pair, graph.n_nodes, batch)
    res = bfs_sssp_batched(graph, s)
    out = _finish_forward_paths(graph, k_walk, s, t, res.dist, res.sigma,
                                batch)
    return out._replace(steps=res.steps)


def sample_path_forward_batched_sharded(pg: PartitionedGraph, key,
                                        batch: int, *, axis
                                        ) -> ForwardSample:
    """Sharded twin of :func:`sample_path_forward_batched` — call inside
    shard_map with the key replicated across the shard axis.  The
    forward BFS runs with sharded state end-to-end (bitmap-scheduled
    frontier exchange per level); the per-sample state is all-gathered
    once after it completes, and the key splits / pair draw / walks are
    stream-identical to the replicated forward lane.
    """
    axis = axis_tuple(axis)
    k_pair, k_walk = jax.random.split(key)
    s, t = sample_pairs(k_pair, pg.n_nodes, batch)
    res = bfs_sssp_batched_sharded(pg, s, axis=axis)

    def gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    out = _finish_forward_paths(pg, k_walk, s, t, gather(res.dist),
                                gather(res.sigma), batch)
    return out._replace(exchange=res.exchange)


def _sample_predecessor_weighted(graph, key, v, tv, dist, sigma):
    """Draw u ~ sigma[u] * [dist[u] + w(u,v) == tv] among neighbors of v.

    The weighted twin of :func:`_sample_predecessor`: the DAG-membership
    test is the exact float equality of the delta-stepping lane (the
    same predicate ``dag_sigma_batched_ref`` counted paths with, so the
    draw weights are consistent with sigma by construction).  ``dist``
    is the PUBLIC float encoding — the ``dn >= 0`` guard keeps the
    -1.0/-3.0 sentinel rows out of the arithmetic.  The CSR neighbor
    chunks slice ``graph.weight`` alongside ``graph.indices``: CSR
    order IS the COO/weight order (build_graph's stable sort), so slot
    j of a chunk pairs neighbor ``indices[start+j]`` with its edge's
    weight.  Returns -1 when v has no predecessor (only possible on
    corrupt state; the walk guards on it).
    """
    start = graph.indptr[v]
    deg = graph.degree[v]
    n_chunks = (deg + _CHUNK - 1) // _CHUNK

    def body(i, carry):
        wsum, chosen, key = carry
        key, k_in, k_acc = jax.random.split(key, 3)
        nbr = jax.lax.dynamic_slice(graph.indices, (start + i * _CHUNK,),
                                    (_CHUNK,))
        ew = jax.lax.dynamic_slice(graph.weight, (start + i * _CHUNK,),
                                   (_CHUNK,))
        valid = jnp.arange(_CHUNK) < (deg - i * _CHUNK)
        dn = dist[nbr]
        w = jnp.where(valid & (dn >= 0.0) & (dn + ew == tv), sigma[nbr], 0.0)
        wc = jnp.sum(w)
        logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), _NEG_INF)
        cand = nbr[_gumbel_argmax(k_in, logw)]
        accept_p = jnp.where(wc > 0, wc / jnp.maximum(wsum + wc, 1e-30), 0.0)
        take = jax.random.uniform(k_acc) < accept_p
        chosen = jnp.where(take, cand, chosen)
        return wsum + wc, chosen, key

    _, chosen, _ = jax.lax.fori_loop(
        0, n_chunks, body, (jnp.float32(0.0), jnp.int32(-1), key))
    return chosen


def _walk_to_source_weighted(graph, key, start_node, dist, sigma, contrib):
    """Walk from ``start_node`` down the shortest-path DAG to the
    source (dist 0.0), marking the strictly internal vertices (every
    stop except the start node and the source).  Levels are gone — the
    loop walks on distances (``tv`` strictly decreases every step:
    positive weights) and counts hops; the V+1 step cap only bites on
    corrupt state (so does the ``u >= 0`` no-predecessor guard, which
    aborts the walk instead of looping).  Returns (contrib, hops).
    """
    tv0 = jnp.maximum(dist[start_node], 0.0)

    def cond(carry):
        _v, tv, steps, _key, _contrib = carry
        return (tv > 0.0) & (steps <= graph.n_nodes)

    def body(carry):
        v, tv, steps, key, contrib = carry
        key, k = jax.random.split(key)
        u = _sample_predecessor_weighted(graph, k, v, tv, dist, sigma)
        u_ok = u >= 0
        u_c = jnp.maximum(u, 0)
        du = jnp.where(u_ok, dist[u_c], 0.0)
        contrib = contrib.at[u_c].add(
            jnp.where(u_ok & (du > 0.0), 1.0, 0.0))
        return (jnp.where(u_ok, u_c, v), jnp.where(u_ok, du, 0.0),
                steps + 1, key, contrib)

    _, _, steps, _, contrib = jax.lax.while_loop(
        cond, body, (start_node, tv0, jnp.int32(0), key, contrib))
    return contrib, steps


def _finish_weighted_paths(graph, k_walk, s, t, dist, sigma,
                           batch: int) -> ForwardSample:
    """Backward DAG walk from t over a completed weighted SSSP state —
    the weighted twin of :func:`_finish_forward_paths` (same telescoping
    argument: predecessor draws proportional to sigma select each
    weighted shortest s-t path with probability 1 / sigma(t))."""
    v1 = graph.n_nodes + 1
    d = dist[t, jnp.arange(batch)]                              # (B,) f32
    valid = d > 0.0
    contrib = jnp.zeros((batch, v1), jnp.float32)
    walk = jax.vmap(_walk_to_source_weighted, in_axes=(None, 0, 0, 1, 1, 0))
    contrib, steps = walk(graph, jax.random.split(k_walk, batch), t,
                          dist, sigma, contrib)
    contrib = contrib.at[:, graph.n_nodes].set(0.0)
    return ForwardSample(contrib, valid, jnp.where(valid, steps, -1),
                         dist, s)


def sample_path_weighted_batched(graph: Graph, key,
                                 batch: int) -> ForwardSample:
    """Take ``batch`` samples through the WEIGHTED forward stream.

    One batched delta-stepping SSSP per round (``delta_sssp_batched``,
    default bucket width), then one backward DAG walk per sample —
    uniform over each pair's weighted shortest paths.  The key layout
    matches the unweighted forward stream exactly, and the pair draw
    never touches the weights: the same key draws the same (s, t)
    sequence whatever the weights are (the seed-contract invariance
    the property suite pins).
    """
    if graph.weight is None:
        raise ValueError(
            "sample_path_weighted_batched needs per-edge weights; attach "
            "them with repro.core.graph.with_weights(graph, w)")
    k_pair, k_walk = jax.random.split(key)
    s, t = sample_pairs(k_pair, graph.n_nodes, batch)
    res = delta_sssp_batched(graph, s)
    return _finish_weighted_paths(graph, k_walk, s, t, res.dist, res.sigma,
                                  batch)


def sample_path_weighted_batched_sharded(pg: PartitionedGraph, key,
                                         batch: int, *, axis
                                         ) -> ForwardSample:
    """Sharded twin of :func:`sample_path_weighted_batched` — call
    inside shard_map with the key replicated across the shard axis.
    The delta-stepping SSSP runs with sharded state end-to-end (bucket
    exchange per round); dist/sigma are all-gathered once after it
    converges and the walks read the partition's replicated CSR view
    (``pg.indptr``/``indices``/``degree``/``weight``) exactly like the
    unweighted forward lane — stream-identical draws on the same key.
    """
    axis = axis_tuple(axis)
    k_pair, k_walk = jax.random.split(key)
    s, t = sample_pairs(k_pair, pg.n_nodes, batch)
    res = delta_sssp_batched_sharded(pg, s, axis=axis)

    def gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    out = _finish_weighted_paths(pg, k_walk, s, t, gather(res.dist),
                                 gather(res.sigma), batch)
    return out._replace(exchange=res.exchange)


def sample_path(graph: Graph, key) -> PathSample:
    """Take one KADABRA sample — B=1 wrapper over the batched lane."""
    ps = sample_path_batched(graph, key, 1)
    return PathSample(ps.contrib[0], ps.valid[0], ps.length[0])


def sample_batch(graph: Graph, key, n_samples: int, *, batch_size: int = 1,
                 carry=None, return_carry: bool = False, axis=None):
    """Take exactly ``n_samples`` *new* samples, accumulating counts.

    ``batch_size`` = B concurrent samples per round; ceil(n_samples / B)
    rounds run under a ``lax.scan`` so memory stays O(B * V) regardless of
    the epoch length.  When B does not divide n_samples the
    ``ceil(n/B) * B - n`` surplus samples of the final round are masked
    out of the returned frame (they are i.i.d., so cutting a fixed
    suffix is exact) — but they are *computed* either way, and with
    ``return_carry=True`` they come back as a second ``(surplus_counts
    (V+1,), surplus_tau ())`` frame that the adaptive driver folds into
    the NEXT epoch via ``carry=...`` instead of dropping: every sample
    the BFS paid for lands in some frame exactly once, and every frame's
    tau counts exactly the samples inside it, so the estimator and the
    epoch/omega bookkeeping stay exact (reusing i.i.d. surplus only
    reshuffles which frame a sample is attributed to — the estimate is
    unchanged in distribution).

    ``carry`` (counts (V+1,), tau ()) from a previous call's surplus is
    folded into the returned frame: counts/tau come back as carry +
    the ``n_samples`` new draws.  B = 1 reproduces the paper's
    one-sample-per-thread formulation exactly (one (V+1,) frontier per
    scan step, never any surplus).

    ``axis`` (shard axis name(s)) switches each round to the SHARDED
    path sampler: ``graph`` must be a ``PartitionedGraph``, the call
    must run inside shard_map, and ``key`` must be replicated across
    the shard axis — the mesh takes the ``n_samples`` samples
    *cooperatively* (one collective BFS batch at a time) instead of
    independently per device, so the returned frame is replicated.

    Returns ``(counts (V+1,) float32, tau () int32)`` — plus the
    surplus frame when ``return_carry=True``.
    """
    # clamp: a batch wider than the request would only compute masked work
    batch_size = max(1, min(int(batch_size), int(n_samples)))
    rounds = -(-n_samples // batch_size)
    v1 = graph.n_nodes + 1

    def step(state, xs):
        # the surplus accumulators only ride in the scan carry when the
        # caller asked for them (return_carry is a static python bool):
        # the common mask-and-drop lane pays nothing extra
        if return_carry:
            counts, tau, sur_counts, sur_tau = state
        else:
            counts, tau = state
        k, offset = xs
        if axis is not None:
            ps = sample_path_batched_sharded(graph, k, batch_size, axis=axis)
        else:
            ps = sample_path_batched(graph, k, batch_size)
        keep = (offset + jnp.arange(batch_size)) < n_samples
        counts = counts + jnp.sum(
            jnp.where(keep[:, None], ps.contrib, 0.0), axis=0)
        tau = tau + jnp.sum(keep.astype(jnp.int32))
        if return_carry:
            # the masked suffix of the last round — valid i.i.d. samples
            sur_counts = sur_counts + jnp.sum(
                jnp.where(keep[:, None], 0.0, ps.contrib), axis=0)
            sur_tau = sur_tau + jnp.sum((~keep).astype(jnp.int32))
            state = (counts, tau, sur_counts, sur_tau)
        else:
            state = (counts, tau)
        return state, jnp.sum((ps.valid & keep).astype(jnp.int32))

    if carry is None:
        counts0, tau0 = jnp.zeros((v1,), jnp.float32), jnp.int32(0)
    else:
        counts0 = jnp.asarray(carry[0], jnp.float32).reshape(v1)
        tau0 = jnp.asarray(carry[1], jnp.int32).reshape(())
    init = (counts0, tau0)
    if return_carry:
        init = init + (jnp.zeros((v1,), jnp.float32), jnp.int32(0))
    keys = jax.random.split(key, rounds)
    offsets = jnp.arange(rounds, dtype=jnp.int32) * batch_size
    state, _valids = jax.lax.scan(step, init, (keys, offsets))
    if return_carry:
        counts, tau, sur_counts, sur_tau = state
        return (counts, tau), (sur_counts, sur_tau)
    counts, tau = state
    return counts, tau
