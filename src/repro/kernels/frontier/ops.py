"""Dispatching wrapper for the frontier-expansion kernels.

``frontier_expand`` routes one batched (or unbatched) frontier expansion
to the right lane.  The routing decision is the pure function
:func:`select_route` (exported so tests can assert the chosen lane
without relying on output differences — all lanes agree bit-for-bit by
design).  With the default ``use_pallas=None`` the dispatch is automatic
and actually consults the fit predicates:

  * flat Pallas kernel      — when :func:`pallas_supported` says the
    whole vertex-major (V+1, B) dist/sigma/contrib state and its one-hot
    operands fit VMEM at some edge block (``kernel.flat_fits``);
  * node-blocked kernel     — above that budget, when a
    :class:`repro.core.graph.CSCLayout` is supplied (``csc=...``) and
    :func:`node_blocked_supported` accepts its per-step tiles;
  * XLA segment-sum ref     — otherwise (no CSC layout, or tiles sized
    beyond the budget), and ALWAYS under ``interpret=True``:
    interpret-mode Pallas executes the kernel body op-by-op on CPU —
    a debugging lane, never a performance win — so the automatic route
    only engages the Pallas kernels when compiling for real hardware
    (``interpret=False``).

Forcing a lane (``use_pallas=True`` for flat, ``use_pallas="node_blocked"``,
``use_pallas=False`` for the XLA ref) bypasses the automatic choice —
that is how the parity tests drive the interpret-mode kernels — but
*fails loudly* with a ``ValueError`` at trace time when the forced path
cannot fit, instead of silently compiling a VMEM-busting kernel.  Edge
alignment is NOT a fit constraint: both kernels pad the edge stream to
``block_e`` internally with inert sink->sink edges.

Batched state is vertex-major end-to-end (``levels`` (B,)): (V+1, B),
or — when the caller persists a CSC layout on its graph and allocates
its BFS state at ``csc.v_pad`` rows — the padded row count, which every
lane preserves exactly (padded in -> padded out, zero pads/slices per
call).  The unbatched contract (dist/sigma (V1,), scalar level) is
routed through the same lanes.  ``repro.core.bfs._expand_level`` calls
this dispatcher inside its while_loop bodies with ``interpret`` left at
its ``None`` default, which resolves by backend (``interpret=False``
iff running on real TPUs): on TPU that engages the Pallas kernels —
with occupancy skipping on the node-blocked lane, see ``skip_inactive``
and the bitmap contract in ``kernel.py`` — while on this CPU container
the automatic route is the XLA path (identical numerics — asserted by
the kernel tests).

:func:`choose_csc_blocks` is the blocking policy: (block_v, block_e)
from the VMEM cell budget with 128-alignment on both axes, the default
of ``repro.core.graph.build_csc_layout``.

A fourth lane serves the vertex-partitioned graph shards of
``repro.core.partition`` (DESIGN.md §Partitioning): passing ``shard=``
(one shard's local layout view, ``ShardedCSCLayout.local()``) routes to
the SHARDED expansion.  The operand contract of that route, precisely:

  * the caller runs INSIDE shard_map over the mesh axes carrying the
    shard dimension;
  * ``src``/``dst`` are ignored — the shard's bucket arrays drive the
    expansion (``shard.src`` holds GLOBAL ids, ``shard.dst`` LOCAL
    shard rows; padding slots are sink-source / ``shard_rows``-dst);
  * ``dist``/``sigma`` cover the all-gathered per-level frontier state
    over the *global* padded rows (>= ``shard.v_pad`` rows; typically
    the (fdist, fvals) pair the BFS driver synthesizes from the
    bitmap-scheduled exchange, DESIGN.md §Frontier exchange);
  * the output is the shard's local (shard_rows, B) contribution tile
    stack — output rows != input rows, which is why the flat kernel can
    never serve this route.

Its fit predicate is :func:`sharded_supported` (the shard's local
blocking only: the gathered state stays in HBM, so the GLOBAL
vertex count never enters the VMEM budget); on compiled TPU backends
the lane reuses the node-blocked kernel in ``wide_state`` mode,
elsewhere the ``frontier_expand_sharded_ref`` segment sum.

``block_active=`` lets a caller hand any lane a precomputed occupancy
bitmap instead of the O(E) exact pass the kernel would run itself —
the sharded BFS drivers derive it from the exchange schedule's
source-block bits (``edge_bitmap_from_source_bits``), which is
conservative (a superset of the exact bitmap) and therefore
bit-identical by the skipping contract in ``kernel.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .kernel import (flat_block_e, flat_fits, frontier_block_bitmap,
                     frontier_expand_batched_pallas,
                     frontier_expand_node_blocked_pallas,
                     frontier_expand_pallas)
from .ref import (frontier_expand_batched_ref,
                  frontier_expand_node_blocked_ref, frontier_expand_ref,
                  frontier_expand_sharded_ref, frontier_relax_batched_ref,
                  frontier_relax_sharded_ref)

# Node-blocked blocking budget, in 4-byte cells of per-step residency
# (:func:`_nb_cells`); well inside the 16 MiB of scoped VMEM.
_VMEM_CELL_BUDGET = 1_000_000


def pallas_supported(n_nodes: int, batch: int = 1, block_e=None) -> bool:
    """True when the *flat* kernel fits VMEM: some edge block (or the
    given ``block_e``) keeps its VMEM-resident state and one-hot
    operands within budget (:func:`repro.kernels.frontier.kernel.flat_block_e`).
    Edge alignment is no constraint: the kernel pads the edge stream
    to ``block_e`` with inert sink edges."""
    if block_e is None:
        return flat_block_e(n_nodes, batch) > 0
    return flat_fits(n_nodes + 1, block_e, batch)


def node_blocked_supported(csc, batch: int = 1) -> bool:
    """True when the node-blocked kernel's per-step tiles fit VMEM.

    Resident per grid step: the (block_v, B) contrib tile, the
    frontier-value tile and the double-buffered dist/sigma source tiles
    (6 * block_v * B total), the two (block_v, block_e) one-hot
    operands, the (block_e, B) gathered values, and the double-buffered
    src/dst edge blocks — independent of V.  The cell count is a budget
    heuristic; ``tests/test_tpu_compile.py`` checks that the default
    blockings compile for a v5e chip.
    """
    b = max(batch, 1)
    cells = _nb_cells(csc.block_v, csc.block_e, b)
    return cells <= _VMEM_CELL_BUDGET


def _nb_cells(block_v: int, block_e: int, b: int) -> int:
    return (6 * block_v * b             # contrib + fval + 4 staged tiles
            + 2 * block_v * block_e     # src + dst one-hot operands
            + block_e * b               # gathered values (src one-hot @ fval)
            + 2 * 2 * block_e)          # double-buffered src/dst edge stage


def sharded_supported(shard, batch: int = 1) -> bool:
    """Fit predicate of the sharded lane's Pallas kernel.

    ``shard`` is one shard's local layout view (or the whole
    :class:`repro.core.partition.ShardedCSCLayout` — only the static
    blocking is read).  Per grid step the sharded kernel touches the
    same tiles as the node-blocked kernel over the shard's LOCAL
    (block_v, block_e) blocking; the all-gathered frontier state stays
    in HBM (only its source tiles enter VMEM), so a
    shard fits iff its blocking does — independent of the global V.
    Because :func:`partition_graph` blocks shards with the same
    :func:`choose_csc_blocks` heuristic the replicated layout uses,
    a default-blocked shard always fits: this predicate only rejects
    hand-picked oversize blockings (and then the automatic dispatch
    falls back to the segment-sum reference rather than erroring).
    """
    b = max(batch, 1)
    return _nb_cells(shard.block_v, shard.block_e, b) <= _VMEM_CELL_BUDGET


def choose_csc_blocks(n_nodes: int, batch: int = 16, *,
                      budget: int = _VMEM_CELL_BUDGET) -> tuple:
    """Pick ``(block_v, block_e)`` for a :class:`CSCLayout` from the
    VMEM cell budget, 128-aligned on both axes (f32 MXU tiling).

    ``block_e`` is taken as large as possible — longer contiguous
    copies amortize the double-buffered edge stream — subject to
    leaving room for a contrib/one-hot tile of at least 256 vertex
    rows; ``block_v`` is then the largest 128-multiple whose per-step
    residency (:func:`node_blocked_supported`'s accounting) fits,
    capped at the graph's padded vertex count (tiling past the graph
    only adds inert sink cells).
    """
    b = max(int(batch), 1)
    v_cap = max(128, -(-(n_nodes + 1) // 128) * 128)
    best = None
    for block_e in (2048, 1024, 512, 256, 128):
        rem = budget - block_e * b - 4 * block_e
        if rem <= 0:
            continue  # the edge-stream residency alone busts the budget
        block_v = min((rem // (6 * b + 2 * block_e)) // 128 * 128, v_cap)
        if block_v >= 256 or block_v == v_cap:
            return block_v, block_e
        if block_v >= 128 and best is None:
            best = (block_v, block_e)
    if best is None:
        # even the minimum 128-aligned tiling cannot fit: fail loudly
        # here rather than persisting a layout node_blocked_supported
        # would reject downstream
        raise ValueError(
            f"no 128-aligned (block_v, block_e) fits the VMEM cell budget "
            f"{budget} at batch={b}; shrink the sample batch")
    return best


def select_route(n_nodes: int, batch: int, *, csc=None, shard=None,
                 use_pallas=None, interpret: bool = True, block_e=None,
                 weighted: bool = False) -> str:
    """The dispatch decision of :func:`frontier_expand`, as a pure
    function of static shapes/flags: one of "flat", "node_blocked",
    "ref", "sharded_nb", "sharded_ref".  Raises ``ValueError`` when a
    forced lane cannot fit.

    ``shard`` (a shard's local layout view) selects the SHARDED lane:
    the caller runs inside shard_map, dist/sigma are the all-gathered
    global frontier state and the output is the shard's local tile
    stack.  The flat kernel can never serve it (its output rows equal
    its input rows), so ``use_pallas=True`` is rejected;
    ``use_pallas='node_blocked'`` forces the sharded Pallas kernel
    (parity tests), ``False`` the sharded XLA reference, and the
    automatic dispatch picks the kernel exactly like the replicated
    routes: on compiled TPU backends when :func:`sharded_supported`
    accepts the shard's blocking, the XLA ref otherwise/interpreted.

    ``weighted`` selects the min-plus relaxation workload
    (:func:`frontier_relax`) instead of the one-hot expansion.  The
    Pallas kernels implement only the first-touch expansion semantics,
    so the weighted workload is XLA-only for now: the automatic
    dispatch and ``use_pallas=False`` return the reference lanes
    ("ref" / "sharded_ref"), and FORCING a Pallas lane
    (``use_pallas=True`` or ``'node_blocked'``) raises the loud
    forced-lane error — pinned route by route in
    tests/test_weighted.py.
    """
    if weighted:
        if use_pallas in (True, "node_blocked"):
            raise ValueError(
                "the weighted min-plus relaxation has no Pallas lane: "
                f"use_pallas={use_pallas!r} cannot be honored; use "
                "use_pallas=None or False (XLA segment-min reference)")
        return "sharded_ref" if shard is not None else "ref"
    if shard is not None:
        sh_ok = sharded_supported(shard, batch)
        if use_pallas is None:
            return ("sharded_nb" if (not interpret and sh_ok)
                    else "sharded_ref")
        if use_pallas is False:
            return "sharded_ref"
        if use_pallas == "node_blocked":
            if not sh_ok:
                raise ValueError(
                    f"sharded tiles (block_v={shard.block_v}, "
                    f"block_e={shard.block_e}, B={batch}) exceed the VMEM "
                    f"cell budget {_VMEM_CELL_BUDGET}; shrink the blocking")
            return "sharded_nb"
        raise ValueError(
            "the flat kernel cannot serve the sharded lane (local output "
            "rows != gathered input rows); use use_pallas=None, False, or "
            "'node_blocked'")
    flat_ok = pallas_supported(n_nodes, batch, block_e)
    nb_ok = csc is not None and node_blocked_supported(csc, batch)
    if use_pallas is None:                       # automatic dispatch
        if interpret:
            # interpreted Pallas is a debug lane (force it to use it);
            # the XLA ref is strictly faster off-TPU
            return "ref"
        return ("flat" if flat_ok else
                "node_blocked" if nb_ok else "ref")
    if use_pallas is False:
        return "ref"
    if use_pallas == "node_blocked":
        if csc is None:
            raise ValueError(
                "use_pallas='node_blocked' requires a CSCLayout (csc=...)")
        if not nb_ok:
            raise ValueError(
                f"node-blocked tiles (block_v={csc.block_v}, "
                f"block_e={csc.block_e}, B={batch}) exceed the VMEM cell "
                f"budget {_VMEM_CELL_BUDGET}; shrink the blocking")
        return "node_blocked"
    # use_pallas=True: the flat kernel
    if not flat_ok:
        raise ValueError(
            f"flat Pallas kernel forced but V+1 = {n_nodes + 1} rows at "
            f"B={batch} exceed its VMEM budget; pass a CSCLayout and "
            f"use_pallas='node_blocked', or use_pallas=None to "
            f"auto-dispatch")
    return "flat"


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_e",
                                   "skip_inactive"))
def frontier_expand(src, dst, dist, sigma, level, *, csc=None, shard=None,
                    use_pallas=None, interpret=None,
                    block_e=None, skip_inactive=True,
                    block_active=None):
    """Route one frontier expansion to the right lane (module docstring).

    ``block_active`` (optional, (n_edge_blocks,) int32) is a
    precomputed occupancy bitmap for the node-blocked/sharded kernels —
    any conservative bitmap is legal; ``None`` lets the kernel compute
    the exact one (or skip nothing under ``skip_inactive=False``).  The
    XLA reference lanes reduce over every edge regardless, so the
    bitmap is ignored there.
    """
    # default by backend: compile the Pallas kernels on real TPUs,
    # interpret (and hence auto-route to the XLA ref) elsewhere — this is
    # what makes the kernel lanes reachable from the BFS drivers, which
    # call this dispatcher without an interpret flag
    interpret = resolve_interpret(interpret)
    batched = dist.ndim == 2
    batch = dist.shape[1] if batched else 1
    v1 = dist.shape[0]
    # dist may arrive pre-padded to csc.v_pad rows (the CSC-aware BFS
    # driver's allocation): every lane is row-count-preserving, so the
    # caller's shape flows through with zero pads/slices; v1 - 1 is then
    # a conservative stand-in for n_nodes in the flat-fit check.  On the
    # SHARDED lanes (``shard=...``) dist instead covers the all-gathered
    # global rows and the output is the shard's local tile stack.
    route = select_route(v1 - 1, batch, csc=csc, shard=shard,
                         use_pallas=use_pallas, interpret=interpret,
                         block_e=block_e)

    if route in ("sharded_nb", "sharded_ref"):
        d2 = dist if batched else dist[:, None]
        s2 = sigma if batched else sigma[:, None]
        lv = jnp.asarray(level, jnp.int32).reshape(batch)
        if route == "sharded_nb":
            out = frontier_expand_node_blocked_pallas(
                shard, d2, s2, lv, interpret=interpret,
                skip_inactive=skip_inactive, block_active=block_active,
                wide_state=True)
        else:
            out = frontier_expand_sharded_ref(shard, d2, s2, lv)
        return out if batched else out[:, 0]
    if route == "node_blocked":
        d2 = dist if batched else dist[:, None]
        s2 = sigma if batched else sigma[:, None]
        lv = (jnp.asarray(level, jnp.int32).reshape(batch) if batched
              else jnp.asarray(level, jnp.int32).reshape(1))
        out = frontier_expand_node_blocked_pallas(
            csc, d2, s2, lv, interpret=interpret,
            skip_inactive=skip_inactive, block_active=block_active)
        return out if batched else out[:, 0]
    if route == "flat":
        if batched:
            return frontier_expand_batched_pallas(
                src, dst, dist, sigma, level, block_e=block_e,
                interpret=interpret)
        return frontier_expand_pallas(src, dst, dist, sigma, level,
                                      block_e=block_e, interpret=interpret)
    if batched:
        return frontier_expand_batched_ref(src, dst, dist, sigma, level)
    return frontier_expand_ref(src, dst, dist, sigma, level)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def frontier_relax(src, dst, weight, tent, active, *, csc=None, shard=None,
                   use_pallas=None, interpret=None):
    """Route one batched min-plus relaxation round (the weighted-lane
    sibling of :func:`frontier_expand`).

    ``tent`` is the (rows, B) float32 tentative-distance state (+inf
    unreached), ``active`` the (rows, B) bool relax mask — this
    delta-stepping round's bucket membership.  Returns per-destination
    candidate distances (empty minimum = +inf); the caller folds
    ``min(tent, cand)``.  With ``shard=`` the sharded route relaxes one
    shard's local rows from the all-gathered state, reading the shard's
    own bucketed weight column (``src``/``dst``/``weight`` operands are
    ignored there, matching :func:`frontier_expand`'s shard contract).
    Routing is :func:`select_route` with ``weighted=True``: XLA lanes
    only — forcing a Pallas lane raises the loud forced-lane error at
    trace time.
    """
    interpret = resolve_interpret(interpret)
    batch = tent.shape[1]
    route = select_route(tent.shape[0] - 1, batch, csc=csc, shard=shard,
                         use_pallas=use_pallas, interpret=interpret,
                         weighted=True)
    if route == "sharded_ref":
        return frontier_relax_sharded_ref(shard, tent, active)
    return frontier_relax_batched_ref(src, dst, weight, tent, active)
