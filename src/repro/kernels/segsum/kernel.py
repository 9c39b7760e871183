"""Pallas TPU kernel: fused gather + weighted segment-sum.

The scatter/gather primitive shared by the GNN stacks (message passing:
ids = edge.src, seg = edge.dst) and the recsys embedding bag (ids = item
id, seg = bag).  JAX has no native EmbeddingBag and only BCOO sparse; this
kernel (and its XLA reference) *is* the system's implementation of both.

TPU adaptation (vs. the CUDA gather/atomic-scatter formulation):

  * the (N,) index stream is blocked over the grid's first axis and the
    feature dim D over the second — both streamed through VMEM;
  * the gather table is VMEM-pinned per feature block (table rows x
    block_d), so the random row access never leaves the chip;
  * the scatter-add over segments is a one-hot matmul
        out[s, d] += sum_i [seg[i] == s] * w[i] * table[ids[i], d]
    i.e. onehot(seg)ᵀ (S x block_n)  @  rows (block_n x block_d)
    — contraction dim = block_n, runs on the MXU, no atomics needed.

Accumulation across index blocks relies on the sequential TPU grid and an
output BlockSpec that revisits the same (S, block_d) tile for every index
block (index_map drops the first grid axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_D = 128


def _kernel(ids_ref, seg_ref, w_ref, table_ref, out_ref, *,
            block_n: int, n_segments: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]
    seg = seg_ref[...]
    w = w_ref[...]
    # gather (VMEM) then promote: accumulation always runs in float32
    # (MXU-style), the caller rounds once at the end
    rows = table_ref[ids, :].astype(jnp.float32) * w[:, None]
    onehot = (seg[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (n_segments, block_n), 0)).astype(jnp.float32)
    out_ref[...] += onehot @ rows                  # MXU scatter-add


def gather_segment_sum_pallas(ids, seg, w, table, n_segments: int, *,
                              block_n: int = DEFAULT_BLOCK_N,
                              block_d: int = DEFAULT_BLOCK_D,
                              interpret: bool | None = None):
    n = ids.shape[0]
    v1, d = table.shape
    block_n = min(block_n, n)
    block_d = min(block_d, d)
    assert n % block_n == 0, (n, block_n)
    assert d % block_d == 0, (d, block_d)
    grid = (n // block_n, d // block_d)

    return pl.pallas_call(
        functools.partial(_kernel, block_n=block_n, n_segments=n_segments),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda i, j: (i,)),      # ids
            pl.BlockSpec((block_n,), lambda i, j: (i,)),      # seg
            pl.BlockSpec((block_n,), lambda i, j: (i,)),      # w
            pl.BlockSpec((v1, block_d), lambda i, j: (0, j)),  # table
        ],
        out_specs=pl.BlockSpec((n_segments, block_d), lambda i, j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n_segments, d), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(ids, seg, w.astype(jnp.float32), table).astype(table.dtype)
