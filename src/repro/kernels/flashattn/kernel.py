"""Pallas TPU kernel: causal flash attention (fused online-softmax).

The §Perf analysis (DESIGN.md §Perf, cell 1) leaves LM training
memory-bound on the f32 attention score chains: XLA materializes the
(q_block, kv) score tiles in HBM between elementwise ops.  This kernel is
the TPU answer: scores never leave VMEM — per (batch*head, q-block) the
kv blocks stream through, the MXU computes q@k^T and p@v, and the
running (m, l, acc) online-softmax state lives in VMEM scratch.  HBM
traffic drops to q + k + v + out exactly.

Layout: q/k/v (BH, S, dh) — the ops.py wrapper folds batch x heads and
repeats GQA kv heads.  Grid (BH, n_q_blocks, n_kv_blocks), kv innermost
(sequential on TPU, accumulating into scratch); causal masking by
absolute positions; whole kv blocks in the strict upper triangle are
masked (structurally skippable with a predicated grid — kept simple
here, the trapezoid schedule in the JAX layer already handles skipping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_k: int, n_kv: int, sm_scale: float,
            causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (block_q, dh)
    k = k_ref[0].astype(jnp.float32)            # (block_k, dh)
    s = (q @ k.T) * sm_scale                    # MXU, stays in VMEM
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    scale = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * scale + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * scale[:, None] \
        + p @ v_ref[0].astype(jnp.float32)      # MXU
    m_scr[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None):
    """q, k, v: (BH, S, dh) -> (BH, S, dh).  S must divide the blocks."""
    bh, s, dh = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q, n_kv = s // block_q, s // block_k
    sm_scale = 1.0 / (dh ** 0.5)

    return pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          n_kv=n_kv, sm_scale=sm_scale, causal=causal),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max m
            pltpu.VMEM((block_q,), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, dh), jnp.float32),    # accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
