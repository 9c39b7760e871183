"""Phase 1's distance-only pull BFS against the path-counting driver.

``bfs_dist_pull`` (the replicated double sweep's BFS) must give every
chain the distances, eccentricity and farthest vertex that
``bfs_sssp_batched`` gives, and the same trip count as its ``steps``;
``estimate_diameter`` must give the bounds of the double sweep built on
``bfs_sssp_batched`` at the same key.  Cases: a lattice, R-MAT, a small
Graph500 Kronecker graph, and a graph with two components and isolated
vertices (its seed in a component, or isolated); one chain and three;
with and without an attached CSC layout, which the pull driver ignores.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import kronecker_graph
from repro.core import (bfs_sssp_batched, estimate_diameter,
                        from_edge_list, grid_graph, rmat_graph,
                        with_csc_layout)
from repro.core.diameter import bfs_dist_pull

KEY = jax.random.PRNGKey(7)
N_COMPONENTS = 40


def _seeds(n_nodes, k):
    """The chains' seeds ``estimate_diameter`` draws at KEY."""
    return jax.random.randint(KEY, (k,), 0, n_nodes)


def _components(k, isolated_seed):
    """A path of 12 vertices and a 4 x 4 lattice on 40 vertices, the
    rest isolated.  The first seed is one of the isolated vertices, or
    the path's middle vertex."""
    seed = int(_seeds(N_COMPONENTS, k)[0])
    rest = [v for v in range(N_COMPONENTS) if v != seed]
    path = rest[:12] if isolated_seed else [seed] + rest[:11]
    if not isolated_seed:
        path = path[1:7] + [seed] + path[7:]
    lat = rest[12:28]
    edges = [(a, b) for a, b in zip(path, path[1:])]
    edges += [(lat[4 * r + c], lat[4 * r + c + 1])
              for r in range(4) for c in range(3)]
    edges += [(lat[4 * r + c], lat[4 * r + c + 4])
              for r in range(3) for c in range(4)]
    return from_edge_list(np.asarray(edges), N_COMPONENTS)


GRAPHS = {
    "grid16": lambda k: grid_graph(16, 16),
    "rmat9": lambda k: rmat_graph(9, 16, seed=1),
    "kronecker10": lambda k: kronecker_graph(),
    "components": lambda k: _components(k, isolated_seed=False),
    "components_isolated_seed": lambda k: _components(k, isolated_seed=True),
}


def _push_sweep(g, seeds):
    """The double sweep's step on the path-counting driver."""
    res = bfs_sssp_batched(g, seeds)
    dist = res.dist[: g.n_nodes].T
    far = jnp.argmax(jnp.where(dist >= 0, dist, -1), axis=1)
    return dist, res.levels, far.astype(jnp.int32), res.steps[0]


def _push_estimate(g, k):
    """``estimate_diameter``'s bounds from sweeps on ``bfs_sssp_batched``."""
    _, ecc0, far0, trips0 = _push_sweep(g, _seeds(g.n_nodes, k))
    _, ecc1, _, trips1 = _push_sweep(g, far0)
    lower = jnp.max(ecc1)
    upper = jnp.maximum(
        jnp.min(jnp.maximum(2 * jnp.minimum(ecc0, ecc1), ecc1)), lower)
    return lower, upper, upper + 1, trips0 + trips1


@pytest.mark.parametrize("layout", [False, True], ids=["plain", "csc"])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_pull_sweep_matches_the_path_counting_driver(name, k, layout):
    g = GRAPHS[name](k)
    if layout:
        g = with_csc_layout(g, batch=k)
    seeds = _seeds(g.n_nodes, k)
    if name == "components_isolated_seed":
        assert int(g.degree[int(seeds[0])]) == 0

    pull = jax.jit(bfs_dist_pull)(g, seeds)
    dist, levels, far, trips = jax.jit(_push_sweep)(g, seeds)
    np.testing.assert_array_equal(np.asarray(pull.dist), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(pull.levels),
                                  np.asarray(levels))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(pull.dist, axis=1)), np.asarray(far))
    assert int(pull.trips) == int(trips)

    est = jax.jit(estimate_diameter, static_argnames="n_sweeps")(
        g, KEY, n_sweeps=k + 1)
    want = jax.jit(_push_estimate, static_argnums=1)(g, k)
    assert tuple(int(x) for x in est) == tuple(int(x) for x in want)
