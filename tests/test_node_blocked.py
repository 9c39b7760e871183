"""Node-blocked CSC frontier lane: layout integrity, three-way kernel
parity (node-blocked Pallas vs flat Pallas vs XLA refs), the dispatch
contract of ``frontier_expand``, and the above-VMEM-budget regime where
only the node-blocked kernel may run.

Sigma values come from real BFS runs, so they are exact small-integer
floats: additions commute exactly and every parity assertion below is
*bit-for-bit* (assert_array_equal), not allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_csc_layout, erdos_renyi_graph, grid_graph,
                        rmat_graph)
from repro.core.bfs import bfs_sssp_batched
from repro.kernels.frontier import (frontier_expand,
                                    frontier_expand_batched_pallas,
                                    frontier_expand_batched_ref,
                                    frontier_expand_node_blocked_pallas,
                                    frontier_expand_node_blocked_ref,
                                    node_blocked_supported, pallas_supported,
                                    select_route)


def _bfs_state(g, batch, seed=0):
    rng = np.random.default_rng(seed)
    sources = jnp.asarray(rng.integers(0, g.n_nodes, batch), jnp.int32)
    res = bfs_sssp_batched(g, sources)
    levels = jnp.asarray(rng.integers(0, 4, batch), jnp.int32)
    return res.dist, res.sigma, levels


# ---------------------------------------------------------------------------
# CSC layout integrity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_v,block_e", [(64, 128), (100, 256), (37, 128)])
def test_csc_layout_holds_every_edge_once(block_v, block_e):
    """Every real directed edge appears exactly once in the CSC order,
    every non-edge slot is sink padding, buckets are dst-block-pure, and
    the block tables are consistent."""
    g = rmat_graph(9, 8, seed=5)
    csc = build_csc_layout(g, block_v=block_v, block_e=block_e)
    src = np.asarray(csc.src)
    dst = np.asarray(csc.dst)
    real = dst != g.n_nodes  # sink-padded slots have dst == n_nodes
    # padding slots are pure sink->sink edges
    assert (src[~real] == g.n_nodes).all()
    got = set(zip(src[real].tolist(), dst[real].tolist()))
    want_src = np.asarray(g.src[: g.n_edges])
    want_dst = np.asarray(g.dst[: g.n_edges])
    want = set(zip(want_src.tolist(), want_dst.tolist()))
    assert got == want
    assert real.sum() == g.n_edges  # no duplicates (edge list is deduped)
    # bucket purity: each edge block only targets its block_nb's rows
    nb = np.repeat(np.asarray(csc.block_nb), csc.block_e)
    assert (dst[real] // block_v == nb[real]).all()
    # block tables: one 'first' flag per node block, ids non-decreasing
    assert np.asarray(csc.block_first).sum() == csc.n_node_blocks
    assert (np.diff(np.asarray(csc.block_nb)) >= 0).all()
    assert csc.v_pad >= g.n_nodes + 1


def test_csc_layout_non_block_aligned_edges():
    """Edge counts that are not multiples of block_e pad per bucket."""
    g = erdos_renyi_graph(257, 6.0, seed=3)
    assert g.n_edges % 128 != 0  # genuinely unaligned instance
    csc = build_csc_layout(g, block_v=64, block_e=128)
    assert csc.e_slots % csc.block_e == 0
    dist, sigma, levels = _bfs_state(g, 5, seed=3)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    got = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Three-way kernel parity (bit-for-bit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,batch,block_v,block_e", [
    (lambda: rmat_graph(9, 8, seed=1), 4, 64, 128),
    (lambda: rmat_graph(10, 4, seed=2), 8, 128, 256),
    (lambda: grid_graph(24, 16), 5, 96, 128),
    (lambda: erdos_renyi_graph(500, 8.0, seed=7), 3, 256, 512),
])
def test_node_blocked_matches_flat_and_refs(make, batch, block_v, block_e):
    g = make()
    csc = build_csc_layout(g, block_v=block_v, block_e=block_e)
    dist, sigma, levels = _bfs_state(g, batch, seed=batch)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    nb_ref = frontier_expand_node_blocked_ref(csc, dist, sigma, levels)
    nb = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels)
    flat = frontier_expand_batched_pallas(g.src, g.dst, dist, sigma, levels,
                                          block_e=block_e)
    np.testing.assert_array_equal(np.asarray(nb_ref), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(flat))


def test_node_blocked_above_vmem_budget_bit_for_bit():
    """The regime the tentpole exists for: (V+1) * B above the 1M-cell
    VMEM budget, where ``pallas_supported`` rejects the flat kernel; the
    node-blocked kernel must still run and match the XLA reference
    bit-for-bit.  A grid instance (the paper's road-network stand-in):
    the staged gather's pair-bucketed layout is sized for
    source-locality-friendly graphs — on a grid a destination block's
    sources span O(1) source blocks, so the slot padding stays small."""
    batch = 64
    g = grid_graph(126, 126)
    assert (g.n_nodes + 1) * batch > 1_000_000
    assert not pallas_supported(g.n_nodes, batch=batch)
    csc = build_csc_layout(g, batch=batch)  # default blocking fits
    assert node_blocked_supported(csc, batch)
    dist, sigma, levels = _bfs_state(g, batch, seed=11)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    got = frontier_expand_node_blocked_pallas(csc, dist, sigma, levels)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # on hardware (interpret=False) the dispatcher auto-routes this
    # instance to the node-blocked lane — the flat kernel cannot fit
    assert select_route(g.n_nodes, batch, csc=csc,
                        interpret=False) == "node_blocked"
    # the forced node-blocked lane through the dispatcher agrees too
    forced = frontier_expand(g.src, g.dst, dist, sigma, levels, csc=csc,
                             use_pallas="node_blocked")
    np.testing.assert_array_equal(np.asarray(forced), np.asarray(ref))


# ---------------------------------------------------------------------------
# Dispatch contract
# ---------------------------------------------------------------------------

def test_dispatch_route_selection():
    """The routing decision itself (``select_route`` is what
    ``frontier_expand`` executes): auto-dispatch consults the fit
    predicates on hardware, stays on the XLA ref under interpret mode
    (interpreted Pallas is a debug lane, never a win), and alignment of
    e_pad is NOT a constraint (the kernels pad internally)."""
    g = erdos_renyi_graph(300, 6.0, seed=1)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    assert g.e_pad % 2048 != 0  # unaligned to the default block_e ...
    # ... yet the flat kernel is supported (it pads the edge stream)
    assert pallas_supported(g.n_nodes, batch=4)
    # hardware auto-routing: flat while it fits, node-blocked above the
    # budget (csc given), ref as the last resort
    assert select_route(g.n_nodes, 4,
                        interpret=False) == "flat"
    assert select_route(70_000, 16, csc=csc,
                        interpret=False) == "node_blocked"
    assert select_route(70_000, 16, csc=None,
                        interpret=False) == "ref"
    # interpret mode: auto never picks an interpreted kernel ...
    assert select_route(g.n_nodes, 4, interpret=True) == "ref"
    # ... but forcing engages it (how the parity tests below run)
    assert select_route(g.n_nodes, 4, use_pallas=True,
                        interpret=True) == "flat"
    assert select_route(g.n_nodes, 4, csc=csc,
                        use_pallas="node_blocked",
                        interpret=True) == "node_blocked"


def test_dispatch_lanes_agree():
    """Every reachable lane of ``frontier_expand`` produces bit-identical
    output, for the batched and the unbatched contract."""
    g = erdos_renyi_graph(300, 6.0, seed=1)
    csc = build_csc_layout(g, block_v=64, block_e=128)
    dist, sigma, levels = _bfs_state(g, 4, seed=1)
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    for kwargs in [dict(), dict(use_pallas=True, block_e=128),
                   dict(use_pallas=True),  # unaligned e_pad: kernel pads
                   dict(use_pallas="node_blocked", csc=csc),
                   dict(use_pallas=False)]:
        out = frontier_expand(g.src, g.dst, dist, sigma, levels, **kwargs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # unbatched contract routes through the same lanes
    sref = frontier_expand(g.src, g.dst, dist[:, 0], sigma[:, 0],
                           levels[0], use_pallas=False)
    for kwargs in [dict(use_pallas=True, block_e=128),
                   dict(use_pallas="node_blocked", csc=csc)]:
        out = frontier_expand(g.src, g.dst, dist[:, 0], sigma[:, 0],
                              levels[0], **kwargs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(sref))


def test_forced_flat_kernel_fails_loudly_when_oversized():
    """An oversized V * B must not silently compile a VMEM-busting flat
    kernel: forcing it raises, auto falls back to the XLA ref."""
    batch = 16
    g = erdos_renyi_graph(70_000, 2.0, seed=13)
    dist, sigma, levels = _bfs_state(g, batch, seed=13)
    with pytest.raises(ValueError, match="VMEM"):
        frontier_expand(g.src, g.dst, dist, sigma, levels, use_pallas=True)
    # without a CSC layout the auto route degrades to the XLA ref
    ref = frontier_expand_batched_ref(g.src, g.dst, dist, sigma, levels)
    out = frontier_expand(g.src, g.dst, dist, sigma, levels)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_forced_node_blocked_requires_csc_and_fitting_tiles():
    g = erdos_renyi_graph(300, 6.0, seed=2)
    dist, sigma, levels = _bfs_state(g, 4, seed=2)
    with pytest.raises(ValueError, match="CSCLayout"):
        frontier_expand(g.src, g.dst, dist, sigma, levels,
                        use_pallas="node_blocked")
    # tiles sized beyond the budget are rejected loudly too
    huge = build_csc_layout(g, block_v=2048, block_e=2048)
    assert not node_blocked_supported(huge, batch=512)
    fat_dist = jnp.tile(dist[:, :1], (1, 512))
    fat_sigma = jnp.tile(sigma[:, :1], (1, 512))
    fat_levels = jnp.tile(levels[:1], (512,))
    with pytest.raises(ValueError, match="budget"):
        frontier_expand(g.src, g.dst, fat_dist, fat_sigma, fat_levels,
                        csc=huge, use_pallas="node_blocked")
