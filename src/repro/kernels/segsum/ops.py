"""Dispatching wrapper: Pallas fused gather+segment-sum vs XLA reference.

The Pallas path requires the gather table (V1 x block_d slice) and the
one-hot tile (S x block_n) to fit VMEM; the model layers call this wrapper
and large-vocabulary cases (recsys tables with 10^7+ rows, sharded over
the "model" mesh axis) fall back to the XLA take+segment_sum path that
partitions cleanly under pjit.
"""
from __future__ import annotations

from functools import partial

import jax

from .kernel import (DEFAULT_BLOCK_D, DEFAULT_BLOCK_N,
                     gather_segment_sum_pallas)
from .ref import gather_segment_sum_ref

_VMEM_TABLE_ROWS = 250_000   # f32 rows x 128 feat block ~ 12 MiB
_VMEM_SEGMENTS = 4096        # one-hot tile budget


@partial(jax.jit, static_argnames=("n_segments", "use_pallas", "interpret"))
def gather_segment_sum(ids, seg, w, table, n_segments, *, use_pallas=False,
                       interpret=None):
    if use_pallas:
        return gather_segment_sum_pallas(ids, seg, w, table, n_segments,
                                         interpret=interpret)
    return gather_segment_sum_ref(ids, seg, w, table, n_segments)


def pallas_supported(n_rows: int, n_segments: int) -> bool:
    return n_rows <= _VMEM_TABLE_ROWS and n_segments <= _VMEM_SEGMENTS
