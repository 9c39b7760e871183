"""Plain reference for adaptive betweenness jobs (host NumPy and SciPy).

Nothing here imports the program under test.  The reference builds its
own graph from the benchmark's edge list and computes what a job's
answer is compared with:

* the vertex degrees, so that a vertex that cannot lie inside a shortest
  path (degree 0 or 1) is known;
* a lower bound on the vertex diameter, from BFS sweeps;
* the mean and variance of the number of vertices inside a shortest path
  between a uniform ordered pair of distinct vertices (0 for pairs that
  are not connected), from full BFS out of sources drawn from the seed.

It also holds a plain KADABRA sampler (uniform pair, uniform shortest
path, count of the inside vertices) that can be put in the program's
place, with the configuration's control switched on, and the exact law
of that sampler's path for one pair, in a precision of choice.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


@dataclasses.dataclass
class RefGraph:
    n: int
    indptr: np.ndarray       # (n+1,) int64 CSR row pointers, both directions
    indices: np.ndarray      # (2m,) int32
    deg: np.ndarray          # (n,) int64
    adj: sp.csr_matrix       # the same CSR as a SciPy matrix


def build(edges: np.ndarray, n: int) -> RefGraph:
    """Undirected simple graph from an (M, 2) edge list: self-loops and
    duplicate edges dropped."""
    u = np.minimum(edges[:, 0], edges[:, 1]).astype(np.int64)
    v = np.maximum(edges[:, 0], edges[:, 1]).astype(np.int64)
    keep = u != v
    uv = np.unique(u[keep] * n + v[keep])
    u, v = uv // n, uv % n
    rows = np.concatenate([u, v])
    cols = np.concatenate([v, u]).astype(np.int32)
    adj = sp.csr_matrix((np.ones(rows.size, np.int8), (rows, cols)),
                        shape=(n, n))
    adj.sort_indices()
    return RefGraph(n, adj.indptr.astype(np.int64), adj.indices,
                    np.diff(adj.indptr).astype(np.int64), adj)


def bfs_dist(rg: RefGraph, sources) -> np.ndarray:
    """(K, n) hop distances from each source; -1 where unreachable."""
    d = csgraph.shortest_path(rg.adj, unweighted=True, directed=False,
                              indices=np.asarray(sources))
    d = np.atleast_2d(d)
    return np.where(np.isinf(d), -1, d).astype(np.int64)


@dataclasses.dataclass
class PairMoments:
    mean: float      # E[inside vertices] over uniform ordered pairs s != t
    var: float       # its variance over pairs
    se: float        # standard error of ``mean`` (0 where exact)


def sampled_pair_moments(rg: RefGraph, rng, n_sources: int):
    """Pair moments from full BFS out of ``n_sources`` vertices of degree
    at least 1 (isolated sources contribute exactly 0 and are counted by
    weight), plus the largest eccentricity seen and where it was seen."""
    live = np.nonzero(rg.deg > 0)[0]
    k = min(n_sources, live.size)
    srcs = rng.choice(live, k, replace=False)
    m1, m2, best = np.empty(k), np.empty(k), (-1, 0, 0)
    for i in range(0, k, 16):
        d = bfs_dist(rg, srcs[i:i + 16])
        inside = np.where(d >= 2, d - 1, 0).astype(np.float64)
        m1[i:i + 16] = inside.sum(1) / (rg.n - 1)
        m2[i:i + 16] = (inside ** 2).sum(1) / (rg.n - 1)
        ecc = d.max(1)
        j = int(np.argmax(ecc))
        if ecc[j] > best[0]:
            best = (int(ecc[j]), int(srcs[i + j]), int(np.argmax(d[j])))
    w = live.size / rg.n
    mean, second = w * m1.mean(), w * m2.mean()
    se = w * m1.std(ddof=1) / np.sqrt(k) if k > 1 else float("inf")
    return PairMoments(mean, second - mean ** 2, se), best


def vertex_diameter_lower(rg: RefGraph, best) -> int:
    """A lower bound on the vertex diameter: one more than the longest
    shortest path realised by a BFS out of the farthest vertex seen."""
    ecc, _src, far = best
    d = bfs_dist(rg, [far])[0]
    return max(ecc, int(d.max())) + 1


# ---------------------------------------------------------------------------
# The plain KADABRA sampler, for controls
# ---------------------------------------------------------------------------

CONTROLS = ("pairs_skip_isolated",)


def _bfs_sigma(rg: RefGraph, s: int, t: int, dtype):
    """Level-synchronous BFS from ``s`` until ``t``'s level is complete:
    hop distances and path counts (each level rescaled to max 1, which
    leaves the ratios within a level exact), both held in ``dtype``."""
    dist = np.full(rg.n, -1, dtype)
    sigma = np.zeros(rg.n, dtype)
    dist[s], sigma[s] = 0, 1
    frontier = np.array([s], np.int64)
    level = dtype(0)
    while frontier.size and dist[t] == -1:
        owner, nbr = _level_edges(rg, frontier)
        fresh = dist[nbr] == -1
        nbr, owner = nbr[fresh], owner[fresh]
        acc = np.zeros(rg.n, dtype)
        np.add.at(acc, nbr, sigma[owner])
        new = np.unique(nbr)
        level = dtype(level + dtype(1))
        dist[new] = level
        top = acc[new].max() if new.size else dtype(1)
        sigma[new] = acc[new] / top
        frontier = new
    return dist, sigma


def _level_edges(rg: RefGraph, frontier: np.ndarray):
    """(owner, neighbour) of every edge out of ``frontier``."""
    starts, ends = rg.indptr[frontier], rg.indptr[frontier + 1]
    counts = ends - starts
    owner = np.repeat(frontier, counts)
    offs = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts,
                                               counts)
    return owner, rg.indices[np.repeat(starts, counts) + offs].astype(np.int64)


def path_visit_probabilities(rg: RefGraph, s: int, t: int, dtype):
    """Exact probability that the sampler's shortest path from ``s`` to
    ``t`` holds each vertex inside, with distances and path counts held
    in ``dtype``; ``None`` where ``t`` is not reachable."""
    dist, sigma = _bfs_sigma(rg, s, t, dtype)
    if dist[t] == -1:
        return None
    p = np.zeros(rg.n)
    p[t] = 1.0
    frontier, d = np.array([t], np.int64), int(dist[t])
    while d > 1:
        owner, nbr = _level_edges(rg, frontier)
        closer = dist[nbr] == d - 1
        owner, nbr = owner[closer], nbr[closer]
        w = sigma[nbr].astype(np.float64)
        total = np.zeros(rg.n)
        np.add.at(total, owner, w)
        np.add.at(p, nbr, p[owner] * w / total[owner])
        frontier, d = np.unique(nbr), d - 1
    p[t] = 0.0
    return p


def _walk(rg: RefGraph, rng, t: int, dist, sigma) -> list[int]:
    """Uniform shortest path back from ``t``: each step picks a neighbour
    one level closer with probability proportional to its path count.
    Returns the inside vertices (a walk with no way on ends there)."""
    inside, cur = [], t
    while dist[cur] > 1:
        nbr = rg.indices[rg.indptr[cur]:rg.indptr[cur + 1]]
        w = np.where(dist[nbr] == dist[cur] - 1,
                     sigma[nbr].astype(np.float64), 0.0)
        if not (w > 0).any():
            break
        cur = int(rng.choice(nbr, p=w / w.sum()))
        inside.append(cur)
    return inside


def sample_scores(rg: RefGraph, n_samples: int, rng, control=None):
    """``n_samples`` KADABRA samples; returns (scores, tau) as a job of
    the program would.  ``control`` switches on one broken guarantee:
    ``pairs_skip_isolated`` draws pairs only among vertices of degree at
    least 1, not among all vertices.
    """
    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    dtype = np.float32
    pool = (np.nonzero(rg.deg > 0)[0] if control == "pairs_skip_isolated"
            else np.arange(rg.n))
    counts = np.zeros(rg.n, dtype)
    one = dtype(1)
    for _ in range(n_samples):
        s, t = rng.choice(pool, 2, replace=False)
        dist, sigma = _bfs_sigma(rg, int(s), int(t), dtype)
        if dist[t] == -1:
            continue
        for v in _walk(rg, rng, int(t), dist, sigma):
            counts[v] = counts[v] + one
    scores = (counts / dtype(n_samples)).astype(np.float32)
    return scores, n_samples
