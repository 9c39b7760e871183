"""The CSC-aware BFS driver end-to-end: persisted-layout state shapes
(zero per-call pads/slices, asserted via shape identity), bit-for-bit
parity of the full batched BFS and bidirectional BFS against the plain
(V+1)-state XLA lane — including the over-VMEM-budget regime the
node-blocked kernel exists for — the occupancy-bitmap contract, the
block-size heuristic, and a smoke run of the csc_driver_sweep benchmark
section so the work-efficiency measurement can't rot.

Parity here is *driver-level*: a graph with a persisted CSCLayout must
produce the same BFS results (and the same sample stream — the Gumbel
noise shapes are layout-independent by construction) as the same graph
without one.  On this container both drivers route to the XLA reference
expansion, so dist parity is bit-for-bit at any scale; the kernel-lane
three-way parity lives in tests/test_node_blocked.py.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_csc_layout, erdos_renyi_graph, grid_graph,
                        with_csc_layout)
from repro.core.bfs import bfs_sssp_batched, bidirectional_bfs_batched
from repro.kernels.frontier import (choose_csc_blocks, frontier_block_bitmap,
                                    frontier_expand,
                                    frontier_expand_batched_ref,
                                    frontier_expand_node_blocked_pallas,
                                    frontier_expand_node_blocked_ref,
                                    node_blocked_supported, pallas_supported)
from repro.kernels.frontier.ops import _VMEM_CELL_BUDGET, _nb_cells


# ---------------------------------------------------------------------------
# Block-size heuristic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_nodes,batch", [(300, 4), (32_768, 8),
                                           (70_000, 16), (1 << 20, 64),
                                           (50, 512)])
def test_choose_csc_blocks_aligned_and_within_budget(n_nodes, batch):
    block_v, block_e = choose_csc_blocks(n_nodes, batch)
    assert block_v % 128 == 0 and block_e % 128 == 0
    assert _nb_cells(block_v, block_e, batch) <= _VMEM_CELL_BUDGET
    # no tiling past the graph's padded vertex count
    assert block_v <= max(128, -(-(n_nodes + 1) // 128) * 128)


def test_choose_csc_blocks_raises_when_budget_infeasible():
    """A batch so wide that even the minimum 128-aligned tiling busts
    the budget must fail loudly, not persist a layout that
    node_blocked_supported rejects downstream."""
    with pytest.raises(ValueError, match="budget"):
        choose_csc_blocks(1000, 4096)


def test_brandes_jax_on_csc_persisted_graph():
    """The exact-betweenness oracle must keep working on a graph that
    carries a persisted layout (regression: its backward phase mixed
    the padded v_pad-row BFS state with a (V+1,) delta)."""
    from repro.core import brandes_numpy
    from repro.core.brandes import brandes_jax
    g = grid_graph(16, 8)
    gc = with_csc_layout(g, block_v=64, block_e=128)
    b_csc = np.asarray(brandes_jax(gc))
    np.testing.assert_array_equal(b_csc, np.asarray(brandes_jax(g)))
    np.testing.assert_allclose(b_csc, brandes_numpy(g), rtol=1e-5)


def test_build_csc_layout_heuristic_defaults_and_overrides():
    g = erdos_renyi_graph(500, 6.0, seed=3)
    auto = build_csc_layout(g, batch=8)
    assert (auto.block_v, auto.block_e) == choose_csc_blocks(g.n_nodes, 8)
    assert node_blocked_supported(auto, 8)
    # explicit blocking always wins over the heuristic
    explicit = build_csc_layout(g, block_v=64, block_e=128)
    assert (explicit.block_v, explicit.block_e) == (64, 128)
    partial = build_csc_layout(g, block_e=256, batch=8)
    assert partial.block_e == 256


# ---------------------------------------------------------------------------
# Copy-free state: shape identity + parity
# ---------------------------------------------------------------------------

def test_persisted_csc_state_shape_identity_over_budget():
    """The acceptance contract of the CSC-aware driver: with a persisted
    layout the batched BFS state lives at csc.v_pad rows END-TO-END —
    result shapes equal the kernel's padded row count (had any per-call
    pad/slice of dist/sigma happened inside the while_loop, the output
    would be (V+1, B) again) — on an instance whose (V+1) * B state is
    over the flat kernel's VMEM budget.  A grid instance: the staged
    gather's pair-bucketed layout targets source-locality-friendly
    graphs (road networks in the paper), where a destination block's
    sources span O(1) source blocks."""
    batch = 64
    g = grid_graph(126, 126)
    assert (g.n_nodes + 1) * batch > 1_000_000
    gc = with_csc_layout(g, batch=batch)
    assert not pallas_supported(g.n_nodes, batch=batch)
    assert node_blocked_supported(gc.csc, batch)
    assert gc.csc.v_pad > g.n_nodes + 1
    rng = np.random.default_rng(11)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, batch), jnp.int32)
    res_csc = jax.jit(bfs_sssp_batched)(gc, sources)
    # shape identity: the state was allocated padded and stayed padded
    assert res_csc.dist.shape == (gc.csc.v_pad, batch)
    assert res_csc.sigma.shape == (gc.csc.v_pad, batch)
    # parity with the plain (V+1)-state lane, bit-for-bit
    res_plain = jax.jit(bfs_sssp_batched)(g, sources)
    v1 = g.n_nodes + 1
    np.testing.assert_array_equal(np.asarray(res_csc.dist[:v1]),
                                  np.asarray(res_plain.dist))
    np.testing.assert_array_equal(np.asarray(res_csc.sigma[:v1]),
                                  np.asarray(res_plain.sigma))
    np.testing.assert_array_equal(np.asarray(res_csc.levels),
                                  np.asarray(res_plain.levels))
    # the tile-padding rows are inert: sink dist, zero sigma
    assert (np.asarray(res_csc.dist[g.n_nodes:]) == -3).all()
    assert (np.asarray(res_csc.sigma[v1:]) == 0).all()


def test_csc_driver_high_diameter_grid_parity():
    """Bit-for-bit full-BFS parity on the workload occupancy skipping
    exists for (every vertex's contribution is a sum of <= 2 equal-level
    predecessors on a grid, so even huge sigma values are order-exact)."""
    g = grid_graph(64, 32)
    gc = with_csc_layout(g, block_v=128, block_e=256)
    sources = jnp.asarray([0, 5, 1000, 2047], jnp.int32)
    res_csc = jax.jit(bfs_sssp_batched)(gc, sources)
    res_plain = jax.jit(bfs_sssp_batched)(g, sources)
    v1 = g.n_nodes + 1
    assert res_csc.dist.shape[0] == gc.csc.v_pad
    np.testing.assert_array_equal(np.asarray(res_csc.dist[:v1]),
                                  np.asarray(res_plain.dist))
    np.testing.assert_array_equal(np.asarray(res_csc.sigma[:v1]),
                                  np.asarray(res_plain.sigma))


def test_bidirectional_routes_through_dispatcher_with_parity():
    """Both directions of the balanced bidirectional search share the
    dispatcher's expansion (one _expand_level); a persisted layout must
    not change any of the returned state."""
    g = grid_graph(32, 24)
    gc = with_csc_layout(g, block_v=128, block_e=256)
    s = jnp.asarray([0, 7, 300], jnp.int32)
    t = jnp.asarray([767, 400, 13], jnp.int32)
    r0 = jax.jit(bidirectional_bfs_batched)(g, s, t)
    r1 = jax.jit(bidirectional_bfs_batched)(gc, s, t)
    v1 = g.n_nodes + 1
    assert r1.dist_s.shape[0] == gc.csc.v_pad
    for a, b in [(r1.dist_s[:v1], r0.dist_s), (r1.dist_t[:v1], r0.dist_t),
                 (r1.sigma_s[:v1], r0.sigma_s), (r1.sigma_t[:v1], r0.sigma_t),
                 (r1.d, r0.d), (r1.split, r0.split)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_frontier_expand_padded_state_is_row_preserving():
    """Every dispatcher lane hands back the row count it was given — the
    property that lets the while_loop carry a padded state with zero
    pads/slices per call."""
    g = erdos_renyi_graph(400, 6.0, seed=2)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    gc = with_csc_layout(g, block_v=64, block_e=128)
    sources = jnp.asarray([1, 2, 3], jnp.int32)
    res = bfs_sssp_batched(gc, sources)     # padded (v_pad, 3) state
    assert res.dist.shape[0] == csc.v_pad > g.n_nodes + 1
    levels = jnp.zeros((3,), jnp.int32)
    ref = frontier_expand(g.src, g.dst, res.dist, res.sigma, levels,
                          csc=csc, use_pallas=False)
    nb = frontier_expand(g.src, g.dst, res.dist, res.sigma, levels,
                         csc=csc, use_pallas="node_blocked")
    nb_ref = frontier_expand_node_blocked_ref(csc, res.dist, res.sigma,
                                              levels)
    assert ref.shape == nb.shape == nb_ref.shape == res.dist.shape
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(nb_ref), np.asarray(ref))


# ---------------------------------------------------------------------------
# Occupancy bitmap
# ---------------------------------------------------------------------------

def test_occupancy_bitmap_confined_frontier():
    """A frontier confined to one node block must activate only the
    edge blocks holding that block's outgoing edges — and skipping the
    rest must not change the expansion output at all."""
    g = grid_graph(48, 32)
    csc = build_csc_layout(g, block_v=128, block_e=128)
    batch = 3
    v1 = g.n_nodes + 1
    # frontier: a handful of vertices inside node block 0, at level 0
    dist = jnp.full((v1, batch), -1, jnp.int32).at[g.n_nodes, :].set(-3)
    sigma = jnp.zeros((v1, batch), jnp.float32)
    for v in (0, 1, 50):
        dist = dist.at[v, :].set(0)
        sigma = sigma.at[v, :].set(1.0)
    levels = jnp.zeros((batch,), jnp.int32)
    bitmap = np.asarray(frontier_block_bitmap(csc, dist, levels))
    # exactness: block k is active iff it holds an edge from a frontier src
    src = np.asarray(csc.src).reshape(csc.n_edge_blocks, csc.block_e)
    want = np.isin(src, [0, 1, 50]).any(axis=1).astype(np.int32)
    np.testing.assert_array_equal(bitmap, want)
    # confinement: O(frontier) blocks, far fewer than the grid total
    assert 1 <= bitmap.sum() < csc.n_edge_blocks / 4
    # parity: skip lane == forced all-ones lane == XLA ref, bit-for-bit
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    out_skip = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels,
                                                   skip_inactive=True)
    out_full = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels,
                                                   skip_inactive=False)
    np.testing.assert_array_equal(np.asarray(out_skip), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(out_full), np.asarray(ref))
    # an explicit (conservative, correct) bitmap is equally legal
    out_explicit = frontier_expand_node_blocked_pallas(
        csc, dist, sigma, levels, block_active=jnp.asarray(want))
    np.testing.assert_array_equal(np.asarray(out_explicit), np.asarray(ref))


def test_occupancy_bitmap_real_bfs_levels():
    """On real BFS states every level's bitmap-skipped expansion matches
    the unskipped one bit-for-bit (the bitmap is per-sample-aware: a
    block is active if ANY sample's frontier touches it)."""
    g = grid_graph(24, 16)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    sources = jnp.asarray([0, 100, 383], jnp.int32)
    res = bfs_sssp_batched(g, sources)
    rng = np.random.default_rng(0)
    for lv in [0, 1, 3, 7]:
        levels = jnp.asarray(rng.integers(0, lv + 1, 3), jnp.int32)
        ref = frontier_expand_batched_ref(g.src, g.dst, res.dist, res.sigma,
                                          levels)
        got = frontier_expand_node_blocked_pallas(csc, res.dist, res.sigma,
                                                  levels, skip_inactive=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# csc_driver_sweep smoke (tier-1 guard for the benchmark section)
# ---------------------------------------------------------------------------

def test_csc_driver_sweep_smoke():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.run import run_csc_driver_sweep
    rec = run_csc_driver_sweep(scale=10, batch=2, reps=1,
                               probe_levels=[1, 2], write_json=False)
    assert rec["section"] == "csc_driver_sweep"
    assert rec["bfs_depth"] > 2
    assert len(rec["results"]) == 2
    for row in rec["results"]:
        assert 0.0 <= row["skipped_ratio"] <= 1.0
        assert row["us_skip"] > 0 and row["us_noskip"] > 0
        assert row["active_blocks"] <= row["n_edge_blocks"]
    assert rec["aggregate_speedup"] > 0
