"""Path-sampling distribution + KADABRA statistics tests."""
import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.core import (brandes_numpy, calibrate_deltas, check_stop,
                        compute_omega, from_edge_list, sample_path,
                        sample_batch)
from repro.core.kadabra import KadabraParams, f_term, g_term


def _diamond():
    """s=0 -> {1,2} -> t=3 plus a longer detour 0-4-5-3.

    Two shortest 0-3 paths: 0-1-3 and 0-2-3 (each internal vertex hit
    with prob 1/2 conditioned on the pair (0,3)).
    """
    edges = np.array([[0, 1], [0, 2], [1, 3], [2, 3], [0, 4], [4, 5], [5, 3]])
    return from_edge_list(edges, 6)


def test_sample_path_uniform_over_paths():
    g = _diamond()
    # force pair (0, 3) by monkey-testing through many keys and filtering
    # instead: use the internal machinery via fixed pair — easiest is to
    # count over full sampling and check expectation against exact b
    n = 2000
    counts, tau = jax.jit(lambda k: sample_batch(g, k, n))(
        jax.random.PRNGKey(0))
    btilde = np.asarray(counts[: g.n_nodes]) / int(tau)
    exact = brandes_numpy(g)
    np.testing.assert_allclose(btilde, exact, atol=0.05)


def test_sample_path_statistics_on_random_graph():
    rng = np.random.default_rng(0)
    G = nx.gnp_random_graph(25, 0.15, seed=3)
    comps = list(nx.connected_components(G))
    for a, b in zip(comps, comps[1:]):
        G.add_edge(next(iter(a)), next(iter(b)))
    g = from_edge_list(np.array(G.edges()), G.number_of_nodes())
    n = 4000
    counts, tau = jax.jit(lambda k: sample_batch(g, k, n))(
        jax.random.PRNGKey(1))
    btilde = np.asarray(counts[: g.n_nodes]) / int(tau)
    exact = brandes_numpy(g)
    # 4000 samples -> standard error ~ sqrt(b(1-b)/4000) <= 0.008
    np.testing.assert_allclose(btilde, exact, atol=0.04)


def test_sample_counts_path_length():
    g = _diamond()
    ps = jax.jit(lambda k: sample_path(g, k))(jax.random.PRNGKey(7))
    assert bool(ps.valid)
    # contributions = internal vertices only = length-1 vertices
    assert float(jnp.sum(ps.contrib)) == pytest.approx(int(ps.length) - 1)


def test_long_lattice_paths_keep_every_inside_vertex():
    """Past a few hundred levels a lattice's path counts span more than
    float32's range: the early levels' counts end below 1e-30 or flush to
    0.  Every walk still reaches its source, so a sample of two vertices
    d apart holds d - 1 inside vertices."""
    from repro.core import grid_graph
    from repro.core.bfs import bidirectional_bfs_batched
    from repro.core.sampler import _finish_paths

    g = grid_graph(80, 300)
    n = g.n_nodes
    s = jnp.asarray([0, 0, 299, 40 * 300], jnp.int32)
    t = jnp.asarray([n - 1, n - 150, n - 300, 40 * 300 + 299], jnp.int32)

    @jax.jit
    def inside(key):
        res = bidirectional_bfs_batched(g, s, t)
        ps = _finish_paths(g, *jax.random.split(key, 3), res, 4)
        return res.d, ps.contrib.sum(axis=1)

    for k in range(3):
        d, got = inside(jax.random.PRNGKey(k))
        assert int(d.max()) > 300
        np.testing.assert_array_equal(np.asarray(got), np.asarray(d) - 1)


def test_omega_monotonic():
    w1 = float(compute_omega(10, 0.05, 0.1))
    w2 = float(compute_omega(10, 0.01, 0.1))
    w3 = float(compute_omega(100, 0.05, 0.1))
    assert w2 > w1  # tighter eps -> more samples
    assert w3 > w1  # larger diameter -> more samples


def test_f_g_positive_and_decreasing_in_tau():
    omega = jnp.float32(1e5)
    b = jnp.array([0.0, 0.01, 0.3], jnp.float32)
    ell = jnp.full((3,), 10.0, jnp.float32)
    f1 = f_term(b, ell, omega, jnp.float32(1e3))
    f2 = f_term(b, ell, omega, jnp.float32(5e4))
    g1 = g_term(b, ell, omega, jnp.float32(1e3))
    g2 = g_term(b, ell, omega, jnp.float32(5e4))
    assert np.all(np.asarray(f1) >= 0) and np.all(np.asarray(g1) > 0)
    assert np.all(np.asarray(f2) <= np.asarray(f1))
    assert np.all(np.asarray(g2) <= np.asarray(g1))
    # f at b=0 is exactly 0 (no lower-deviation risk for unseen vertices)
    assert float(f1[0]) == 0.0


def test_calibration_budget_union_bound():
    eps, delta = 0.05, 0.1
    omega = compute_omega(12, eps, delta)
    btilde0 = jnp.asarray(
        np.random.default_rng(0).random(100).astype(np.float32) * 0.2)
    lil, liu, tau_star = calibrate_deltas(btilde0, eps, delta, omega)
    used = float(jnp.sum(jnp.exp(-lil)) + jnp.sum(jnp.exp(-liu)))
    assert used <= delta * 1.01
    assert 1.0 <= float(tau_star) <= float(omega)
    # no NaNs in the budgets
    assert np.isfinite(np.asarray(lil)).all()
    assert np.isfinite(np.asarray(liu)).all()


def test_check_stop_semantics():
    eps, delta = 0.05, 0.1
    omega = jnp.float32(compute_omega(12, eps, delta))
    V = 50
    lil = jnp.full((V,), 5.0, jnp.float32)
    liu = jnp.full((V,), 5.0, jnp.float32)
    params = KadabraParams(eps, delta, omega, lil, liu)
    counts = jnp.zeros((V,), jnp.float32)
    # tiny tau: cannot stop
    done, _, _ = check_stop(counts, jnp.int32(3), params)
    assert not bool(done)
    # tau beyond omega: must stop (static VC cap)
    done, _, _ = check_stop(counts, jnp.int32(int(omega) + 1), params)
    assert bool(done)
