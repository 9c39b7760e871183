"""The epoch program's BFS work counters, and the frontier route of the
graphs the benchmark runs.

Each replicated BFS driver counts its shared loop's expansions (one per
BFS level of the batch) and the edge blocks the node-blocked kernel
streamed in them, as the frontier dispatcher reports them; the single
lane's epoch program sums them per epoch (``EngineEpochStats.bfs_levels``
/ ``nb_steps``), whether or not anyone reads them.  Off the TPU the
drivers route to the XLA reference, which streams no blocks; the
dispatcher's own count is checked with the node-blocked kernel forced in
interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdaptiveConfig, brandes_numpy, grid_graph,
                        rmat_graph, run_adaptive, with_csc_layout)
from repro.core import bfs, graph as graph_mod
from repro.core.bfs import bfs_sssp_batched, bidirectional_bfs_batched
from repro.core.epoch import epoch_length
from repro.core.sampler import sample_pairs
from repro.kernels.frontier import (frontier_block_bitmap,
                                    frontier_expand, select_route)
from repro.runtime import RingSink, Telemetry

B = 64


def _kronecker(scale=10, edgefactor=16, seed=3):
    """A small Graph500 Kronecker graph (initiator 0.57/0.19/0.19,
    labels permuted)."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > 0.76
        jj = rng.random(m) > np.where(ii, 0.19 / 0.24, 0.57 / 0.76)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    return graph_mod.from_edge_list(rng.permutation(n)[ij].T, n)


@pytest.mark.parametrize("make", [lambda: rmat_graph(9, 16, seed=1),
                                  _kronecker],
                         ids=["rmat9", "kronecker10"])
def test_low_diameter_graphs_build_no_layout(monkeypatch, make):
    """A plain Kronecker-class graph samples on the route its size gives,
    never the node-blocked one, and no call builds a CSC layout for it."""
    built = []
    real = graph_mod.bucket_layout
    monkeypatch.setattr(graph_mod, "bucket_layout",
                        lambda *a, **k: built.append(1) or real(*a, **k))
    monkeypatch.setattr(bfs, "resolve_interpret", lambda _: False)
    g = make()
    res = run_adaptive(g, config=AdaptiveConfig(n0_base=64, max_epochs=1),
                       key=jax.random.PRNGKey(0))
    assert res.batch_size == B
    assert bfs.frontier_route(g, B) == "flat"     # small: VMEM holds it
    assert built == []
    # at kron18.bc's size the flat kernel no longer fits: the XLA route
    assert select_route((1 << 18), B, interpret=False) == "ref"


def test_small_grid_with_a_layout_is_within_eps_of_brandes():
    """24 x 24 at B = 64: a hand-attached layout gives the plain graph's
    results, within eps of exact betweenness."""
    g = grid_graph(24, 24)
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=256,
                         sample_batch_size=B)
    key = jax.random.PRNGKey(11)
    plain = run_adaptive(g, config=cfg, key=key)
    laid = run_adaptive(with_csc_layout(g, batch=B), config=cfg, key=key)
    s = np.asarray(laid.reports[0].scores)
    np.testing.assert_array_equal(s, np.asarray(plain.reports[0].scores))
    assert laid.tau == plain.tau and laid.converged
    assert np.abs(s - brandes_numpy(g)).max() < cfg.eps


# ---------------------------------------------------------------------------
# The work counters
# ---------------------------------------------------------------------------

def _manhattan(side, s, t):
    s, t = np.asarray(s), np.asarray(t)
    return np.abs(s // side - t // side) + np.abs(s % side - t % side)


def test_bidirectional_search_counts_its_trip_count():
    side = 20
    g = grid_graph(side, side)
    rng = np.random.default_rng(0)
    s = rng.integers(0, g.n_nodes, 16)
    t = (s + 1 + rng.integers(0, g.n_nodes - 1, 16)) % g.n_nodes
    res = jax.jit(lambda *a: bidirectional_bfs_batched(*a))(g, s, t)
    # each iteration takes every open search one level further, and a
    # search meets after exactly d(s, t) of them
    assert int(res.steps[0]) == _manhattan(side, s, t).max()
    assert int(res.steps[1]) == 0           # no node-blocked kernel here
    full = jax.jit(lambda *a: bfs_sssp_batched(*a))(g, jnp.asarray(s[:3]))
    ecc = [_manhattan(side, v, np.arange(g.n_nodes)).max() for v in s[:3]]
    assert int(full.steps[0]) == max(ecc) + 1   # + the empty level


def test_streamed_blocks_are_the_kernels_bitmaps():
    """The dispatcher counts the edge blocks the node-blocked kernel's
    occupancy bitmap lets through; the XLA route counts none and gives
    the same contributions."""
    g = with_csc_layout(grid_graph(64, 64), block_v=128, block_e=128)
    src = jnp.asarray([0, 100, 2000, 4095, 7, 64, 65, 1], jnp.int32)
    full = bfs_sssp_batched(g, src)
    for level in (0, 3, 40):
        # the state after ``level`` expansions: its frontier is ``level``
        fresh = full.dist > level
        dist = jnp.where(fresh & (full.dist >= 0), -1, full.dist)
        sigma = jnp.where(fresh, 0.0, full.sigma)
        lv = jnp.full((8,), level, jnp.int32)
        out, streamed = frontier_expand(
            g.src, g.dst, dist, sigma, lv, csc=g.csc,
            use_pallas="node_blocked", interpret=True, with_streamed=True)
        ref, none = frontier_expand(g.src, g.dst, dist, sigma, lv,
                                    csc=g.csc, use_pallas=False,
                                    with_streamed=True)
        want = int(jnp.sum(frontier_block_bitmap(g.csc, dist, lv)))
        assert 0 < int(streamed) == want < g.csc.n_edge_blocks
        assert int(none) == 0
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_epoch_levels_are_the_rounds_trip_counts():
    """bfs_levels of each epoch is the sum over its rounds of the longest
    of the round's pair distances, on a grid where those are known."""
    side = 16
    g = grid_graph(side, side)
    bsz = 16
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=64, max_epochs=2,
                         sample_batch_size=bsz)
    key = jax.random.PRNGKey(5)
    tel = Telemetry([RingSink()], validate=True)
    res = run_adaptive(g, config=cfg, key=key, telemetry=tel)
    # run_adaptive's key stream: calibration first, then one key per epoch
    key, _ = jax.random.split(key)
    n0 = epoch_length(1, base=cfg.n0_base, exponent=cfg.n0_exponent)
    want = []
    for _ in range(res.n_epochs):
        key, ke = jax.random.split(key)
        total = 0
        for kr in jax.random.split(ke, -(-n0 // bsz)):
            s, t = sample_pairs(jax.random.split(kr, 4)[0], g.n_nodes, bsz)
            total += int(_manhattan(side, s, t).max())
        want.append(total)
    assert [st.bfs_levels for st in res.stats] == want
    assert [st.nb_steps for st in res.stats] == [0] * res.n_epochs
    # the bus carries them on each epoch's span end and totals on run.end
    ends = [e.fields for e in tel.events() if e.kind == "span.end"
            and e.fields["name"] == "phase.epoch"]
    assert [(f["bfs_levels"], f["nb_steps"]) for f in ends] == \
        [(w, 0) for w in want]
    (end,) = [e.fields for e in tel.events() if e.kind == "run.end"]
    assert (end["bfs_levels"], end["nb_steps"], end["route"]) == \
        (sum(want), 0, "ref")
