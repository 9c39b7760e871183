"""The weighted (delta-stepping) lane, pinned against scipy oracles.

Acceptance battery of the weighted-betweenness PR:

  * ``delta_sssp_batched`` distances BIT-match ``scipy.sparse.csgraph``
    Dijkstra (float64, cast to float32) on every lane — flat,
    CSC-persisted, and sharded — over ER, grid and skewed-weight
    instances.  Weights are dyadic rationals (k/16), so f32 min-plus
    arithmetic is exact and bitwise comparison is meaningful, not
    hopeful.
  * shortest-path counts match a distance-ordered numpy DP on the
    scipy distance matrix (the sigma half of weighted Brandes).
  * the two degeneracies that pin the driver to the unweighted code:
    ``delta=inf`` collapses to Bellman-Ford (bit-identical distances,
    zero bucket advances) and unit integer weights with ``delta=1``
    collapse to BFS (dist AND sigma bit-identical to the BFS lane,
    bucket counts == BFS level counts).
  * ``select_route`` / ``frontier_relax`` dispatcher contract: every
    route x (weighted, unweighted) combination either runs or raises
    the loud forced-lane ``ValueError`` — no silent fallback.
  * end-to-end: ``run_adaptive(..., stream="weighted")`` betweenness
    within eps of exact weighted Brandes (normalized by n(n-1)), with
    closeness/harmonic riding the same stream against closed-form
    oracles.

Instances are built DEDUPLICATED (``np.unique`` over canonicalized
pairs): scipy's csr_matrix SUMS duplicate entries and networkx
collapses them, so duplicate edges silently corrupt both oracle
distances and oracle path counts.
"""
import os
import subprocess
import sys
import textwrap
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("scipy.sparse.csgraph",
                    reason="the oracle battery needs scipy's Dijkstra")
import scipy.sparse as sp
import scipy.sparse.csgraph as csg

from jax.sharding import PartitionSpec as P

from repro.core import (build_csc_layout, build_graph, grid_graph,
                        partition_graph, run_adaptive, run_fixed,
                        symmetric_dyadic_weights, with_csc_layout,
                        with_weights)
from repro.core.bfs import (bfs_sssp_batched, delta_sssp_batched,
                            delta_sssp_batched_sharded)
from repro.core.diameter import estimate_diameter_weighted
from repro.kernels.frontier import frontier_relax, select_route

AXES = ("data",)


# ---------------------------------------------------------------------------
# instances (deduplicated) + oracles
# ---------------------------------------------------------------------------

def _dedup_pairs(a, b):
    """Canonicalized, deduplicated undirected pair list (u < v)."""
    lo = np.minimum(a, b)
    hi = np.maximum(a, b)
    keep = lo != hi
    return np.unique(np.stack([lo[keep], hi[keep]], axis=1), axis=0)


def _er_weighted(n, m, seed, *, skew=False):
    """Deduped symmetric ER graph with dyadic weights.

    ``skew=True`` draws power-of-two weights 2^k/16, k in [0, 8) — a
    heavy-tailed (road-network-like) weight profile that still keeps
    every path sum exactly representable in float32.
    """
    rng = np.random.default_rng(seed)
    rnd = _dedup_pairs(rng.integers(0, n, 4 * m),
                       rng.integers(0, n, 4 * m))[:m]
    ring = np.stack([np.arange(n), np.roll(np.arange(n), -1)], axis=1)
    pairs = _dedup_pairs(np.concatenate([rnd[:, 0], ring[:, 0]]),
                         np.concatenate([rnd[:, 1], ring[:, 1]]))
    src = np.concatenate([pairs[:, 0], pairs[:, 1]])
    dst = np.concatenate([pairs[:, 1], pairs[:, 0]])
    g = build_graph(src, dst, n)
    if skew:
        wmap = {tuple(p): float(2 ** rng.integers(0, 8)) / 16.0
                for p in pairs}
        gs = np.asarray(g.src[: g.n_edges])
        gd = np.asarray(g.dst[: g.n_edges])
        w = np.array([wmap[(min(a, b), max(a, b))]
                      for a, b in zip(gs, gd)], np.float32)
        return with_weights(g, w)
    return with_weights(g, symmetric_dyadic_weights(g, seed=seed))


def _grid_weighted(w, h, seed):
    g = grid_graph(w, h)
    return with_weights(g, symmetric_dyadic_weights(g, seed=seed))


def _scipy_dists(g):
    """(n, n) float64 Dijkstra distance matrix from the graph's weights."""
    n = g.n_nodes
    W = sp.csr_matrix((np.asarray(g.weight[: g.n_edges], np.float64),
                       (np.asarray(g.src[: g.n_edges]),
                        np.asarray(g.dst[: g.n_edges]))), shape=(n, n))
    return csg.dijkstra(W, directed=True)


def _sigma_numpy(g, D, s):
    """Shortest-path counts from source s by distance-ordered DP over the
    scipy distance row (the forward half of weighted Brandes)."""
    n = g.n_nodes
    srcs = np.asarray(g.src[: g.n_edges])
    dsts = np.asarray(g.dst[: g.n_edges])
    ws = np.asarray(g.weight[: g.n_edges], np.float64)
    d = D[s]
    sigma = np.zeros(n)
    sigma[s] = 1.0
    for v in np.argsort(d, kind="stable"):
        if v == s or not np.isfinite(d[v]):
            continue
        on = (dsts == v) & np.isfinite(d[srcs]) & (d[srcs] + ws == d[v])
        sigma[v] = sigma[srcs[on]].sum()
    return sigma


def _brandes_weighted_numpy(g):
    """Exact weighted betweenness, normalized by n(n-1) (the estimator's
    scale: expected fraction of shortest paths through v)."""
    n = g.n_nodes
    D = _scipy_dists(g)
    srcs = np.asarray(g.src[: g.n_edges])
    dsts = np.asarray(g.dst[: g.n_edges])
    ws = np.asarray(g.weight[: g.n_edges], np.float64)
    bc = np.zeros(n)
    for s in range(n):
        d = D[s]
        order = np.argsort(d, kind="stable")
        sigma = np.zeros(n)
        sigma[s] = 1.0
        for v in order:
            if v == s or not np.isfinite(d[v]):
                continue
            on = (dsts == v) & np.isfinite(d[srcs]) & (d[srcs] + ws == d[v])
            sigma[v] = sigma[srcs[on]].sum()
        delta = np.zeros(n)
        for v in order[::-1]:
            if v == s or not np.isfinite(d[v]):
                continue
            on = (dsts == v) & np.isfinite(d[srcs]) & (d[srcs] + ws == d[v])
            for u in srcs[on]:
                delta[u] += sigma[u] / sigma[v] * (1.0 + delta[v])
        bc += delta
        bc[s] -= delta[s]
    return bc / (n * (n - 1))


def _oracle_dist_cols(D, sources, n_nodes):
    """Expected (V+1, B) float32 dist frame: scipy rows cast to f32,
    -1.0 unreached, -3.0 sink row."""
    cols = D[np.asarray(sources)].T                       # (n, B)
    out = np.where(np.isfinite(cols), cols, -1.0).astype(np.float32)
    sink = np.full((1, len(sources)), -3.0, np.float32)
    return np.concatenate([out, sink], axis=0)


_INSTANCES = {
    "er": lambda: _er_weighted(48, 110, seed=3),
    "grid": lambda: _grid_weighted(12, 9, seed=5),
    "skew": lambda: _er_weighted(40, 90, seed=11, skew=True),
}


# ---------------------------------------------------------------------------
# Dijkstra-oracle parity: flat, CSC, sharded (1-shard in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_INSTANCES))
def test_dijkstra_parity_flat(name):
    g = _INSTANCES[name]()
    rng = np.random.default_rng(17)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, 8), jnp.int32)
    res = jax.jit(delta_sssp_batched)(g, sources)
    D = _scipy_dists(g)
    np.testing.assert_array_equal(
        np.asarray(res.dist), _oracle_dist_cols(D, sources, g.n_nodes))
    for j, s in enumerate(np.asarray(sources)):
        np.testing.assert_array_equal(
            np.asarray(res.sigma[: g.n_nodes, j]), _sigma_numpy(g, D, s))


@pytest.mark.parametrize("name", sorted(_INSTANCES))
def test_dijkstra_parity_csc(name):
    g = _INSTANCES[name]()
    gc = with_csc_layout(g, block_v=32, block_e=128)
    rng = np.random.default_rng(17)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, 8), jnp.int32)
    flat = jax.jit(delta_sssp_batched)(g, sources)
    csc = jax.jit(delta_sssp_batched)(gc, sources)
    np.testing.assert_array_equal(np.asarray(csc.dist[: g.n_nodes + 1]),
                                  np.asarray(flat.dist))
    np.testing.assert_array_equal(np.asarray(csc.sigma[: g.n_nodes + 1]),
                                  np.asarray(flat.sigma))
    np.testing.assert_array_equal(np.asarray(csc.levels),
                                  np.asarray(flat.levels))
    np.testing.assert_array_equal(np.asarray(csc.buckets),
                                  np.asarray(flat.buckets))


@pytest.mark.parametrize("name", sorted(_INSTANCES))
def test_dijkstra_parity_sharded_1dev(name):
    g = _INSTANCES[name]()
    pg = partition_graph(g, 1)
    mesh = jax.make_mesh((1,), AXES,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(AXES))
    rng = np.random.default_rng(17)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, 8), jnp.int32)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(pg.partition_spec(AXES),),
             out_specs=(P("data"), P("data"), P(), P(), P()),
             check_vma=False)
    def run(pgl):
        r = delta_sssp_batched_sharded(pgl, sources, axis=AXES)
        return r.dist, r.sigma, r.levels, r.buckets, r.exchange

    d, s, lv, bk, _ = run(pg)
    ref = jax.jit(delta_sssp_batched)(g, sources)
    v1 = g.n_nodes + 1
    np.testing.assert_array_equal(np.asarray(d[:v1]), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(s[:v1]), np.asarray(ref.sigma))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ref.levels))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(ref.buckets))


# ---------------------------------------------------------------------------
# degeneracies: delta=inf (Bellman-Ford) and delta=1 on unit weights (BFS)
# ---------------------------------------------------------------------------

def test_delta_inf_is_bellman_ford():
    g = _INSTANCES["er"]()
    sources = jnp.asarray([0, 7, 21, 40], jnp.int32)
    auto = jax.jit(delta_sssp_batched)(g, sources)
    bf = jax.jit(partial(delta_sssp_batched, delta=float("inf")))(g, sources)
    np.testing.assert_array_equal(np.asarray(bf.dist), np.asarray(auto.dist))
    np.testing.assert_array_equal(np.asarray(bf.sigma),
                                  np.asarray(auto.sigma))
    # one window [0, inf): never advances, so zero bucket boundaries
    np.testing.assert_array_equal(np.asarray(bf.buckets),
                                  np.zeros(4, np.int32))


def test_unit_weights_delta_1_is_bfs():
    base = grid_graph(10, 7)
    g = with_weights(base, np.ones(base.n_edges, np.float32))
    sources = jnp.asarray([0, 13, 69, 34], jnp.int32)
    wres = jax.jit(partial(delta_sssp_batched, delta=1.0))(g, sources)
    bres = jax.jit(bfs_sssp_batched)(base, sources)
    # float dist == int dist exactly (small ints are exact in f32), same
    # -1/-3 sentinels; sigma and per-column depth/bucket counts identical
    np.testing.assert_array_equal(np.asarray(wres.dist),
                                  np.asarray(bres.dist, np.float32))
    np.testing.assert_array_equal(np.asarray(wres.sigma),
                                  np.asarray(bres.sigma))
    np.testing.assert_array_equal(np.asarray(wres.buckets),
                                  np.asarray(bres.levels))
    np.testing.assert_array_equal(np.asarray(wres.levels),
                                  np.asarray(bres.levels))


def test_weighted_requires_weights():
    g = grid_graph(6, 6)                                  # no weight column
    with pytest.raises(ValueError, match="weight"):
        delta_sssp_batched(g, jnp.asarray([0], jnp.int32))
    with pytest.raises(ValueError, match="weight"):
        run_adaptive(g, ("betweenness",), stream="weighted")


# ---------------------------------------------------------------------------
# dispatcher contract: every route x (weighted, unweighted)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas,weighted,expect", [
    # weighted: XLA-only — automatic and explicit-False dispatch to the
    # reference lanes, forced Pallas raises loudly
    (None, True, "ref"),
    (False, True, "ref"),
    (True, True, ValueError),
    ("node_blocked", True, ValueError),
    # unweighted: the PR-2/4 routes, unchanged
    (None, False, "ref"),               # interpret=True -> XLA ref
    (False, False, "ref"),
    (True, False, "flat"),
    ("node_blocked", False, "node_blocked"),
])
def test_select_route_contract(use_pallas, weighted, expect):
    g = _grid_weighted(8, 8, seed=0)
    csc = build_csc_layout(g, block_v=32, block_e=128)
    kw = dict(csc=csc, use_pallas=use_pallas, interpret=True,
              weighted=weighted)
    if expect is ValueError:
        with pytest.raises(ValueError, match="Pallas"):
            select_route(g.n_nodes, 4, **kw)
    else:
        assert select_route(g.n_nodes, 4, **kw) == expect


@pytest.mark.parametrize("use_pallas", [True, "node_blocked"])
def test_frontier_relax_rejects_forced_pallas(use_pallas):
    g = _grid_weighted(8, 8, seed=0)
    v1 = g.n_nodes + 1
    tent = jnp.full((v1, 4), jnp.inf, jnp.float32).at[0].set(0.0)
    active = jnp.zeros((v1, 4), bool).at[0].set(True)
    with pytest.raises(ValueError, match="Pallas"):
        frontier_relax(g.src, g.dst, g.weight, tent, active,
                       use_pallas=use_pallas)


def test_frontier_relax_runs_on_ref_routes():
    """The non-raising half of the contract: the dispatcher actually
    executes the weighted workload on both permitted settings and they
    agree bitwise."""
    g = _grid_weighted(8, 8, seed=0)
    v1 = g.n_nodes + 1
    tent = jnp.full((v1, 4), jnp.inf, jnp.float32).at[0].set(0.0)
    active = jnp.zeros((v1, 4), bool).at[0].set(True)
    auto = frontier_relax(g.src, g.dst, g.weight, tent, active)
    forced = frontier_relax(g.src, g.dst, g.weight, tent, active,
                            use_pallas=False)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))
    assert np.isfinite(np.asarray(auto)).any()


# ---------------------------------------------------------------------------
# weighted diameter bounds
# ---------------------------------------------------------------------------

def test_weighted_diameter_brackets_truth():
    g = _grid_weighted(12, 9, seed=5)
    D = _scipy_dists(g)
    true_diam = float(D[np.isfinite(D)].max())
    est = jax.jit(estimate_diameter_weighted)(g)
    assert float(est.lower) <= true_diam <= float(est.upper)
    assert int(est.vertex_diameter) >= 1


# ---------------------------------------------------------------------------
# end-to-end: adaptive weighted betweenness vs exact weighted Brandes
# ---------------------------------------------------------------------------

def test_adaptive_weighted_brandes_convergence():
    g = _er_weighted(40, 90, seed=7)
    eps, delta = 0.05, 0.1
    res = run_adaptive(g, ("betweenness", "closeness", "harmonic"),
                       eps=eps, delta=delta, stream="weighted",
                       key=jax.random.PRNGKey(2))
    bc, cl, ha = res.reports
    assert bc.converged and cl.converged and ha.converged

    exact = _brandes_weighted_numpy(g)
    assert np.abs(bc.scores - exact).max() < eps

    D = _scipy_dists(g)
    n = g.n_nodes
    assert np.isfinite(D).all(), "oracle regime needs a connected instance"
    far = D.sum(1)
    np.testing.assert_allclose(cl.scores, (n - 1) / far, atol=0.1)
    H = np.where(D > 0, 1.0 / np.maximum(D, 1.0), 0.0)
    np.testing.assert_allclose(ha.scores, H.sum(0) / (n - 1), atol=0.1)


def test_run_fixed_weighted_all_metrics():
    g = _er_weighted(40, 90, seed=7)
    reports = run_fixed(g, 2048,
                        metrics=("betweenness", "closeness", "harmonic"),
                        stream="weighted", key=jax.random.PRNGKey(4))
    assert [r.name for r in reports] == ["betweenness", "closeness",
                                         "harmonic"]
    exact = _brandes_weighted_numpy(g)
    assert np.abs(reports[0].scores - exact).max() < 0.1
    for r in reports:
        assert int(r.tau) == 2048
        assert np.all(np.isfinite(r.scores))


# ---------------------------------------------------------------------------
# 8-device mesh (subprocess): sharded parity + sharded engine equality
# ---------------------------------------------------------------------------

_MESH8_WEIGHTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from functools import partial
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import (grid_graph, partition_graph, run_adaptive,
                            symmetric_dyadic_weights, with_weights)
    from repro.core.bfs import (delta_sssp_batched,
                                delta_sssp_batched_sharded)
    from repro.core.sampler import (sample_path_weighted_batched,
                                    sample_path_weighted_batched_sharded)

    axes = ("data",)
    mesh = jax.make_mesh((8,), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

    g = with_weights(grid_graph(24, 16),
                     symmetric_dyadic_weights(grid_graph(24, 16), seed=2))
    pg = partition_graph(g, 8, block_v=16, block_e=128, exchange_budget=1)
    gspec = pg.partition_spec(axes)
    rng = np.random.default_rng(13)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, 16), jnp.int32)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec,),
             out_specs=(P("data"), P("data"), P(), P(), P()),
             check_vma=False)
    def run_sssp(pgl):
        r = delta_sssp_batched_sharded(pgl, sources, axis=axes)
        return r.dist, r.sigma, r.levels, r.buckets, r.exchange

    d, s, lv, bk, xch = run_sssp(pg)
    ref = jax.jit(delta_sssp_batched)(g, sources)
    v1 = g.n_nodes + 1
    np.testing.assert_array_equal(np.asarray(d[:v1]), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(s[:v1]), np.asarray(ref.sigma))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(ref.levels))
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(ref.buckets))
    assert int(np.asarray(xch)[0]) > 0          # exchange tally engaged
    print("OK sssp_parity_mesh8")

    key = jax.random.PRNGKey(9)

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(gspec, P()),
             out_specs=(P(), P(), P(), P(), P(), P()), check_vma=False)
    def run_draw(pgl, k):
        smp = sample_path_weighted_batched_sharded(pgl, k, 8, axis=axes)
        return (smp.contrib, smp.valid, smp.length, smp.dist, smp.sources,
                smp.exchange)

    got = run_draw(pg, key)
    want = jax.jit(partial(sample_path_weighted_batched, batch=8))(g, key)
    np.testing.assert_array_equal(np.asarray(got[0])[:, :v1],
                                  np.asarray(want.contrib))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want.valid))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want.length))
    np.testing.assert_array_equal(np.asarray(got[3])[:v1],
                                  np.asarray(want.dist))
    np.testing.assert_array_equal(np.asarray(got[4]),
                                  np.asarray(want.sources))
    print("OK sampler_parity_mesh8")

    res_sh = run_adaptive(pg, ("betweenness", "closeness"),
                          eps=0.2, delta=0.1, stream="weighted",
                          mesh=mesh, key=jax.random.PRNGKey(0))
    res_1 = run_adaptive(g, ("betweenness", "closeness"),
                         eps=0.2, delta=0.1, stream="weighted",
                         key=jax.random.PRNGKey(0))
    for a, b in zip(res_sh.reports, res_1.reports):
        assert a.converged == b.converged
        assert int(a.tau) == int(b.tau)
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-6)
    print("OK engine_weighted_mesh8")
""")


def test_weighted_mesh8_subprocess():
    """Sharded weighted parity on an 8-device host mesh: SSSP bits,
    the weighted draw stream, and the full adaptive engine."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _MESH8_WEIGHTED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 3
