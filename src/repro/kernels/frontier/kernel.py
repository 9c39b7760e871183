"""Pallas TPU kernels: batched edge-centric BFS frontier expansion.

This is the hot loop of the paper's sampler (one bidirectional BFS per
sample; each level is one frontier expansion).  The GPU/CPU formulation
is a queue + atomics; the TPU-native adaptation is:

  * edges live in HBM as an index list, streamed through VMEM in blocks
    of ``block_e`` (BlockSpec over the edge dimension — purely
    sequential, perfectly prefetchable);
  * the BFS state (dist, sigma, contrib) of all B concurrent samples is
    *vertex-major* ``(V+1, B)`` — the layout ``repro.core.bfs`` now keeps
    end-to-end, so no transposes happen on the way in or out;
  * the scatter-accumulate into ``contrib`` is a *one-hot matmul*:
    scattering the (block_e, B) value matrix to rows ``dst`` is
    onehot(dst)ᵀ @ vals — a (rows x block_e) x (block_e x B) MXU
    product.  With B > 1 the systolic array has a real right-hand side:
    the edge block (and the one-hot operand built from it) is read ONCE
    for all B samples, so arithmetic intensity on the edge stream grows
    linearly in B.

Two kernels share that skeleton:

``frontier_expand_batched_pallas`` — the *flat* (single-level) kernel.
Grid ``(E_pad / block_e,)``; the whole (V+1, B) dist/sigma/contrib state
is VMEM-resident across all steps, and both the per-edge gather and the
scatter are one-hot matmuls against (V+1, block_e) operands.
:func:`flat_fits` is its fit model: VMEM pads B to 128 lanes, so each
state array costs (V+1) * 128 * 4 bytes for any B <= 128, and the
one-hot operand grows with V * block_e.  :func:`flat_block_e` picks the
largest edge block that fits; at most ~4K vertices fit at all.

``frontier_expand_node_blocked_pallas`` — the *two-level* (node-blocked
CSC) kernel that lifts the cap.  Edges are pre-bucketed by
destination-node block (:class:`repro.core.graph.CSCLayout`); the grid
walks the flattened (node block, edge block) cells.  Per step only the
``(block_v, B)`` contrib tile of the current node block is VMEM-resident
(zeroed on each bucket's first edge block via the scalar-prefetched
``block_first`` flags; the output index map follows ``block_nb``), the
one-hot operand shrinks from (V+1, block_e) to (block_v, block_e), and
dist/sigma stay in HBM: each edge block reads one (block_v, B) source
tile of them rather than the whole state.  VMEM residency is O(block_v * B + block_v * block_e)
independent of V, so the kernel reaches million-vertex graphs; it also
does V/block_v fewer one-hot MACs than the flat kernel (each edge is
compared against one tile of rows, not all of them).  Revisits of an
output tile are consecutive (buckets are contiguous), which is exactly
the accumulation pattern Mosaic supports.

Two work-efficiency mechanisms ride on the two-level grid:

**Occupancy bitmap (grid-cell skipping).**  A BFS level only has to
touch edge blocks that contain at least one *frontier source* — on
high-diameter graphs (grids, roads) that is O(frontier) blocks, not
O(E / block_e).  The contract: ``block_active`` is an
``(n_edge_blocks,)`` int32 vector, ``block_active[k] == 1`` iff edge
block ``k`` holds at least one edge whose source ``u`` satisfies
``dist[u, b] == levels[b]`` for *some* sample ``b``
(:func:`frontier_block_bitmap` computes exactly this: a blockwise
segment-max of the per-sample frontier mask gathered over the CSC
source ids).  It rides in as a third scalar-prefetch operand; inactive
cells skip the whole copy + gather + matmul body under ``pl.when`` and
only perform the (mandatory) tile zeroing on each bucket's first edge
block.  A conservative all-ones bitmap is always legal — skipping is
semantics-preserving, the kernel output is bit-identical with any
correct bitmap.  Cost trade-off: the bitmap itself is one O(E) integer
pass per level, so skipping pays on high-diameter instances (grids,
roads — most levels touch O(frontier) blocks; up to ~20x per level in
csc_driver_sweep) and roughly breaks even when nearly every block is
active (dense-frontier levels of low-diameter graphs; the sweep's
0.74-0.97x rows are interpret-mode numbers whose per-cell cond
overhead overstates the penalty a compiled kernel would see).  Callers
that know their frontiers are dense can pass ``skip_inactive=False``
through the dispatcher.

**Pipelined edge blocks and source tiles.**  The ``(1, block_e)``
src/dst edge blocks and the (block_v, B) dist/sigma source tiles are
plain BlockSpec operands, which the Pallas pipeline double-buffers:
step ``k + 1``'s copies run behind step ``k``'s matmuls.  Their index
maps follow a fifth scalar-prefetch operand, the fetch map
(:func:`_fetch_map`: the last active edge block at or before each
step), so an inactive step names the blocks already resident and the
pipeline issues no copy for it.  (Copies staged by hand into a
``(2, block_e)`` int32 scratch do not compile: Mosaic refuses the
one-row slot slice, which is not aligned to the buffer's (2, 128)
tiling.)

**Block-local dist/sigma gather.**  Every edge block of the layout is
*source-block-pure* (:func:`repro.core.graph.bucket_layout` sorts each
destination bucket by source block and records the block in
``block_sb``), so the per-edge gather needs rows from exactly ONE
(block_v, B) dist tile and one sigma tile; consecutive edge blocks of
one (dst, src)-block pair name the same tiles, which the pipeline does
not fetch again.  The frontier-value tile
``where(dist_tile == levels, sigma_tile, 0)`` is computed once per
step, and the per-edge read is a second one-hot matmul
``onehot(src_local)^T @ fval``.  No kernel indexes a ref with a vector
of row ids (Mosaic has no such gather; ``tools/check_kernels.py`` also
rejects direct reads of ``pltpu.ANY`` refs).  Sink-padded edges carry
``src = n_nodes``: when the sink row lies outside the tile the one-hot
column is all zero, and when it lies inside, the tile's sink dist (-3)
never matches a level — inert either way.

**Precision.**  Every one-hot matmul runs at ``Precision.HIGHEST``
(:func:`_onehot_matmul`).  The MXU's default pass rounds float32
operands to bfloat16, and sigma outgrows bfloat16's 8-bit significand
within a few BFS levels; at HIGHEST a gathered value keeps all its
float32 bits and the scatter accumulates in float32, so the kernels
match the XLA reference bit for bit (checked on a v5e chip on a
512 x 512 grid by ``chip_smoke.py``).  The staged path reorders edges
within a bucket; the additions of exact small-integer sigma commute
exactly.

Both kernels compile for TPU v5e (``tests/test_tpu_compile.py``
compiles them for a described chip) and run there through
``chip_smoke.py``.  The pair sort trades slot padding for block-local
reads: every (dst block, src block) pair is padded to a ``block_e``
multiple, which stays ~2-3x on locality-friendly instances (grids,
roads) but grows with the number of populated pairs on scattered graphs
(DESIGN.md §Perf "Staged gather").

All shapes static; padded edges target the sink row V (dist = -3) and
contribute exactly 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# The flat kernel's fit model.  Per-kernel VMEM budget: v5e's 16 MiB
# of default scoped VMEM less headroom.
_VMEM_BYTES = 14 * 2**20
# Cap on the cells of a (rows, block_e) one-hot operand.  Mosaic
# unrolls the one-hot matmuls, so compile time grows with this product:
# about 2.5 s at 2^19 cells for v5e.
_ONEHOT_CELLS = 1 << 19


def _tile_bytes(rows: int, batch: int) -> int:
    """VMEM bytes of a (rows, B) 32-bit tile: B pads to 128 lanes."""
    return rows * 128 * -(-max(batch, 1) // 128) * 4


def flat_fits(v1: int, block_e: int, batch: int) -> bool:
    """The flat kernel at ``block_e`` fits: dist, sigma and the contrib
    accumulator, each double-buffered, one float32 one-hot operand and
    the edge stream within :data:`_VMEM_BYTES`, and the one-hot within
    :data:`_ONEHOT_CELLS`.  The model is conservative: the v5e compiler
    reported 2.8 MiB of scoped VMEM at V+1 = 2001, B = 64,
    block_e = 256, where it counts 7.8 MiB."""
    state = 6 * _tile_bytes(v1, batch)
    return (v1 * block_e <= _ONEHOT_CELLS
            and state + 4 * v1 * block_e + 16 * block_e <= _VMEM_BYTES)


def flat_block_e(n_nodes: int, batch: int) -> int:
    """Largest edge block (128..2048) the flat kernel fits with at
    ``n_nodes`` vertices and sample width ``batch``; 0 when none does."""
    for block_e in (2048, 1024, 512, 256, 128):
        if flat_fits(n_nodes + 1, block_e, batch):
            return block_e
    return 0


def _onehot_matmul(onehot, x, dims):
    """``dot_general(onehot, x)`` of a 0/1 matrix and float32 values at
    full float32 contract precision.

    Sigma values outgrow bfloat16's 8-bit significand after a few BFS
    levels, so the MXU's default single bfloat16 pass would round them;
    ``Precision.HIGHEST`` keeps every float32 bit of a gathered value
    and accumulates the scatter in float32.
    """
    return jax.lax.dot_general(onehot.astype(jnp.float32), x, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NN = (((1,), (0,)), ((), ()))    # (m, k) @ (k, n)
_TN = (((0,), (0,)), ((), ()))    # (k, m)^T @ (k, n)


def _flat_kernel(src_ref, dst_ref, dist_ref, sigma_ref, level_ref, out_ref,
                 *, block_e: int, v1: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    src = src_ref[...]           # (1, block_e)
    dst = dst_ref[...]           # (1, block_e)
    levels = level_ref[...]      # (1, B) per-sample frontier depth
    # frontier values of the VMEM-resident (V1, B) state: rows on a
    # sample's frontier carry their sigma
    fval = jnp.where(dist_ref[...] == levels, sigma_ref[...], 0.0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (v1, block_e), 0)
    # gather as a one-hot matmul: vals[e, b] = fval[src[e], b]
    vals = _onehot_matmul(src == rows, fval, _TN)           # (block_e, B)
    # scatter-add as a one-hot matmul on the MXU:
    #   contrib[v, b] += sum_e [dst[e] == v] * vals[e, b]
    out_ref[...] += _onehot_matmul(dst == rows, vals, _NN)


def _pad_edges(src, dst, block_e, sink):
    e_pad = src.shape[0]
    if e_pad % block_e:
        # extend with sink->sink edges (dist[sink] = -3 never matches a
        # level, so padded edges contribute exactly 0)
        extra = block_e - e_pad % block_e
        fill = jnp.full((extra,), sink, src.dtype)
        src = jnp.concatenate([src, fill])
        dst = jnp.concatenate([dst, fill])
    return src, dst


def frontier_expand_batched_pallas(src, dst, dist, sigma, levels, *,
                                   block_e: int | None = None,
                                   interpret: bool | None = None):
    """B batched BFS frontier expansions sharing one edge stream.

    ``dist``/``sigma`` are vertex-major (V+1, B) with per-sample frontier
    depths ``levels`` (B,); returns the (V+1, B) contribution matrix.
    Same contract as ``ref.frontier_expand_batched_ref`` — no layout
    conversions happen here, the caller's vertex-major state is used
    as-is.

    ``block_e=None`` takes the largest edge block that fits
    (:func:`flat_block_e`).  ``interpret=None`` compiles the kernel on a
    TPU backend and interprets it elsewhere.
    """
    v1, batch = dist.shape
    if block_e is None:
        block_e = flat_block_e(v1 - 1, batch)
        if not block_e:
            raise ValueError(
                f"the flat kernel cannot fit V+1 = {v1} rows at B={batch} "
                "in VMEM; use the node-blocked kernel")
    src, dst = _pad_edges(src, dst, block_e, v1 - 1)
    grid = (src.shape[0] // block_e,)
    levels = jnp.asarray(levels, jnp.int32).reshape(1, batch)

    return pl.pallas_call(
        functools.partial(_flat_kernel, block_e=block_e, v1=v1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_e), lambda i: (0, i)),  # src: stream
            pl.BlockSpec((1, block_e), lambda i: (0, i)),  # dst: stream
            pl.BlockSpec((v1, batch), lambda i: (0, 0)),   # dist: pinned
            pl.BlockSpec((v1, batch), lambda i: (0, 0)),   # sigma: pinned
            pl.BlockSpec((1, batch), lambda i: (0, 0)),    # levels
        ],
        out_specs=pl.BlockSpec((v1, batch), lambda i: (0, 0)),  # accumulate
        out_shape=jax.ShapeDtypeStruct((v1, batch), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(src.reshape(1, -1), dst.reshape(1, -1), dist, sigma, levels)


def frontier_expand_pallas(src, dst, dist, sigma, level, *,
                           block_e: int | None = None,
                           interpret: bool | None = None):
    """One BFS frontier expansion (B=1 lane of the batched kernel); same
    contract as ``ref.frontier_expand_ref``."""
    out = frontier_expand_batched_pallas(
        src, dst, dist[:, None], sigma[:, None],
        jnp.asarray(level, jnp.int32).reshape(1),
        block_e=block_e, interpret=interpret)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Two-level node-blocked CSC kernel
# ---------------------------------------------------------------------------

def frontier_row_mask(dist, levels, active=None):
    """(rows,) bool — row is on SOME sample's frontier this level.

    The shared primitive of every occupancy bitmap: ``dist`` is
    vertex-major (rows, B), ``levels`` (B,) per-sample frontier depths.
    Rows at or past ``n_nodes`` (the sink, dist -3) never match.
    ``active`` (optional (B,) bool) drops finished samples: a sample
    that left its loop keeps a FROZEN ``levels`` entry, so its last
    frontier would otherwise stay in the mask for every remaining
    iteration — harmless for correctness (its contributions are
    discarded) but inflating every occupancy bitmap built from the
    mask.
    """
    hit = dist == levels[None, :]
    if active is not None:
        hit = hit & active[None, :]
    return jnp.any(hit, axis=1)


def frontier_block_bitmap(csc, dist, levels):
    """Per-edge-block "any active source" occupancy bitmap.

    ``dist`` is vertex-major (rows, B) with rows >= n_nodes + 1 (the
    sink row's dist of -3 never matches a level), ``levels`` (B,).
    Returns an (n_edge_blocks,) int32 vector with 1 exactly on the
    blocks that hold at least one edge whose source is on some sample's
    frontier — a blockwise segment-max of the frontier mask gathered
    over the CSC source ids (blocks are fixed-size, so the segment-max
    is a reshape + max).  O(E) comparisons, no floats, no matmuls —
    cheap relative to the expansion it lets the kernel skip.
    """
    hit = frontier_row_mask(dist, levels)[csc.src]             # (e_slots,)
    return jnp.max(hit.reshape(csc.n_edge_blocks, csc.block_e)
                   .astype(jnp.int32), axis=1)


def frontier_source_block_bitmap(dist, levels, block_rows: int,
                                 active=None):
    """Per-source-block occupancy: 1 iff the ``block_rows``-row block
    holds at least one frontier row.

    This is the *exchange schedule* of the sharded lane
    (DESIGN.md §Frontier exchange): each device computes it over its own
    (shard_rows, B) state slice at the partition's exchange-chunk
    granularity (``PartitionedGraph.exchange_chunk_rows`` — a divisor
    of the kernel's ``block_v``, so chunk boundaries nest inside node
    blocks), the bits decide which chunks are worth exchanging at all,
    and — all-gathered — they double as a conservative edge-block
    bitmap via :func:`edge_bitmap_from_source_bits`.  ``dist`` rows
    must be a multiple of ``block_rows`` (shard rows always are);
    ``active`` as in :func:`frontier_row_mask`.
    Returns (rows // block_rows,) int32.
    """
    mask = frontier_row_mask(dist, levels, active)
    return jnp.max(mask.reshape(-1, block_rows).astype(jnp.int32), axis=1)


def edge_bitmap_from_source_bits(csc, src_bits, chunk_rows: int):
    """Derive the kernel's per-edge-block bitmap from per-source-chunk
    occupancy bits (the all-gathered exchange schedule).

    ``src_bits`` is (global_rows // chunk_rows,) int32 over the GLOBAL
    ``chunk_rows``-row source tiling; an edge block is marked active
    when any of its sources lies in an active chunk.  This is a
    *superset* of :func:`frontier_block_bitmap`'s exact bitmap (a chunk
    can be active through a row that no edge of this block reads) —
    conservative bitmaps are always legal, the kernel output is
    bit-identical.  The win over the exact pass: the sharded driver
    already holds the gathered bits, so this costs one O(E) int gather
    with no (rows, B) comparison behind it.
    """
    hit = src_bits[csc.src // chunk_rows]                      # (e_slots,)
    return jnp.max(hit.reshape(csc.n_edge_blocks, csc.block_e), axis=1)


def _nb_kernel(nb_ref, sb_ref, first_ref, act_ref, fetch_ref, level_ref,
               src_ref, dst_ref, dist_ref, sigma_ref, out_ref, *,
               block_v: int, block_e: int):
    del fetch_ref                # read by the index maps only
    k = pl.program_id(0)         # flattened (node block, edge block) cell

    @pl.when(first_ref[k] == 1)
    def _init():                 # first edge block of this bucket: the
        out_ref[...] = jnp.zeros_like(out_ref)   # tile must always zero

    @pl.when(act_ref[k] == 1)
    def _expand():               # skipped entirely on inactive cells
        src = src_ref[...]       # (1, block_e) — all inside source block
        dst = dst_ref[...]       # (1, block_e) — all inside this node block
        # block-local frontier values of the staged source tile: rows
        # whose dist matches a sample's level carry their sigma
        fval = jnp.where(dist_ref[...] == level_ref[...],
                         sigma_ref[...], 0.0)         # (block_v, B)
        # gather = one-hot matmul against the staged tile.  Sink-padded
        # edges (src = n_nodes) either fall outside [0, block_v) — an
        # all-zero one-hot row — or hit the sink row whose dist (-3)
        # never matches a level: inert either way.
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_v, block_e), 0)
        vals = _onehot_matmul((src - sb_ref[k] * block_v) == rows, fval,
                              _TN)                    # (block_e, B)
        # local scatter rows inside the current (block_v, B) contrib
        # tile; sink-padded edges fall outside [0, block_v) (all-zero
        # one-hot column) or hit the sink row with a 0 value — inert
        out_ref[...] += _onehot_matmul((dst - nb_ref[k] * block_v) == rows,
                                       vals, _NN)


def _fetch_map(block_active):
    """(n_edge_blocks,) int32: the last active edge block at or before
    each grid step (0 before the first).  The input index maps follow
    it, so an inactive step names the block already resident and the
    pipeline issues no copy for it."""
    idx = jnp.arange(block_active.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(block_active == 1, idx, 0))


def frontier_expand_node_blocked_pallas(csc, dist, sigma, levels, *,
                                        interpret: bool | None = None,
                                        block_active=None,
                                        skip_inactive: bool = True,
                                        wide_state: bool = False):
    """Two-level frontier expansion over a node-blocked CSC layout.

    ``csc`` is a :class:`repro.core.graph.CSCLayout`; ``dist``/``sigma``
    are vertex-major (V+1, B) — or, copy-free, already padded to
    (csc.v_pad, B) as the CSC-aware BFS driver allocates them —
    ``levels`` (B,).  Returns the contribution matrix at the same row
    count it was handed (padded in -> padded out, NO per-call pad/slice
    of the state), numerically identical (bit-for-bit on exact sigma)
    to the flat kernel and the XLA reference: only a (block_v, B)
    contrib tile is VMEM-resident per grid step, so V is not bounded by
    the VMEM cell budget.

    ``block_nb``/``block_sb``/``block_first``/``block_active`` ride in
    as scalar-prefetch operands (``PrefetchScalarGridSpec``): the
    output index map follows ``block_nb`` to the current node block's
    tile, ``block_sb`` names the (block_v, B) dist/sigma source tile
    each edge block reads (module docstring, "Block-local dist/sigma
    gather"), the tile is zeroed on each bucket's
    first edge block, and cells whose edge block holds no frontier
    source are skipped (see the module docstring for the bitmap
    contract).  ``block_active=None`` with ``skip_inactive=True``
    computes the bitmap from dist/levels; ``skip_inactive=False``
    forces the all-ones bitmap (every cell runs — the lane the
    occupancy benchmark compares against).
    """
    v_rows, batch = dist.shape
    levels = jnp.asarray(levels, jnp.int32).reshape(batch)
    v_pad = csc.v_pad
    # the gather reads source tiles [sb*block_v, (sb+1)*block_v) for
    # every sb < n_src_blocks — the state must cover them all
    src_rows = csc.n_src_blocks * csc.block_v
    if wide_state:
        # Sharded lane: ``csc`` is one shard's LOCAL layout view
        # (ShardedCSCLayout.local(): global src ids, local dst rows)
        # while dist/sigma cover the all-gathered GLOBAL row space —
        # strictly more rows than the local tiles.  The gather tiles
        # the wide state by GLOBAL source block, the
        # output is the local (csc.v_pad, B) tile stack; no pad/slice
        # of the state.
        if v_rows < max(v_pad, src_rows):
            raise ValueError(
                f"wide_state expects >= {max(v_pad, src_rows)} gathered "
                f"rows, got {v_rows}")
    elif v_pad > v_rows:
        # Compat lane for (V+1, B) callers: rows in [V+1, v_pad) back the
        # last tile; no edge targets them.  This pad (and the [:v_rows]
        # slice below) copies the full state per call — the CSC-aware
        # BFS driver avoids it by allocating at v_pad rows up front.
        dist = jnp.pad(dist, ((0, v_pad - v_rows), (0, 0)),
                       constant_values=-3)
        sigma = jnp.pad(sigma, ((0, v_pad - v_rows), (0, 0)))
    elif v_rows != v_pad:
        raise ValueError(
            f"state rows {v_rows} exceed the CSC layout's v_pad {v_pad}")

    if block_active is None:
        if skip_inactive:
            block_active = frontier_block_bitmap(csc, dist, levels)
        else:
            block_active = jnp.ones((csc.n_edge_blocks,), jnp.int32)
    else:
        block_active = jnp.asarray(block_active, jnp.int32).reshape(
            csc.n_edge_blocks)

    block_v, block_e = csc.block_v, csc.block_e
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_nb, block_sb, block_first, block_active, fetch
        num_scalar_prefetch=5,
        grid=(csc.n_edge_blocks,),
        in_specs=[
            pl.BlockSpec((1, batch),
                         lambda k, nb, sb, first, act, f: (0, 0)),  # levels
            pl.BlockSpec((1, block_e),                           # src
                         lambda k, nb, sb, first, act, f: (0, f[k])),
            pl.BlockSpec((1, block_e),                           # dst
                         lambda k, nb, sb, first, act, f: (0, f[k])),
            pl.BlockSpec((block_v, batch),                       # dist tile
                         lambda k, nb, sb, first, act, f: (sb[f[k]], 0)),
            pl.BlockSpec((block_v, batch),                       # sigma tile
                         lambda k, nb, sb, first, act, f: (sb[f[k]], 0)),
        ],
        out_specs=pl.BlockSpec((block_v, batch),
                               lambda k, nb, sb, first, act, f: (nb[k], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_nb_kernel, block_v=block_v, block_e=block_e),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((v_pad, batch), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(csc.block_nb, csc.block_sb, csc.block_first, block_active,
      _fetch_map(block_active), levels.reshape(1, batch),
      csc.src.reshape(1, -1), csc.dst.reshape(1, -1), dist, sigma)
    if wide_state:
        return out                     # local (csc.v_pad, B) tile stack
    return out if v_rows == v_pad else out[:v_rows]
