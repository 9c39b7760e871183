"""Pallas TPU kernel: fused KADABRA stopping-condition evaluation.

Streams the three per-vertex vectors (counts, ln(1/dL), ln(1/dU)) through
VMEM in blocks, computes f and g in registers and folds the running max
into a (1, 2) accumulator tile.  One HBM pass, no temporaries — the
elementwise math (div, sqrt, fma) is VPU work fully hidden behind the
streaming loads.

Scalars (tau, omega) ride in a (4,) prefetch-style operand pinned to every
grid step.  Output is a (1, 2) tile: [max f, max g].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_V = 16384
_NEG = -1e30  # python scalar: jnp constants would be captured by the trace


def _kernel(scal_ref, counts_ref, lil_ref, liu_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _NEG)

    tau = jnp.maximum(scal_ref[0], 1.0)
    omega = scal_ref[1]
    counts = counts_ref[...]
    ell_l = jnp.maximum(lil_ref[...], 1e-8)
    ell_u = jnp.maximum(liu_ref[...], 1e-8)
    btilde = counts / tau
    a = omega / tau - 1.0 / 3.0
    b = omega / tau + 1.0 / 3.0
    f = (ell_l / tau) * (-a + jnp.sqrt(a * a + 2.0 * btilde * omega / ell_l))
    g = (ell_u / tau) * (b + jnp.sqrt(b * b + 2.0 * btilde * omega / ell_u))
    # [max f, max g] as one (1, 2) vector: Mosaic stores no scalars
    # to VMEM
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
    out_ref[...] = jnp.maximum(out_ref[...],
                               jnp.where(col == 0, jnp.max(f), jnp.max(g)))


def stopcheck_pallas(counts, tau, log_inv_delta_l, log_inv_delta_u, omega, *,
                     block_v: int = DEFAULT_BLOCK_V,
                     interpret: bool | None = None):
    v = counts.shape[0]
    block_v = min(block_v, v)
    # pad to a block multiple; padding rows get counts=0, ell=tiny -> f=g~0
    v_pad = ((v + block_v - 1) // block_v) * block_v
    if v_pad != v:
        pad = v_pad - v
        counts = jnp.pad(counts, (0, pad))
        log_inv_delta_l = jnp.pad(log_inv_delta_l, (0, pad),
                                  constant_values=1e-8)
        log_inv_delta_u = jnp.pad(log_inv_delta_u, (0, pad),
                                  constant_values=1e-8)
    scal = jnp.stack([jnp.asarray(tau, jnp.float32),
                      jnp.asarray(omega, jnp.float32),
                      jnp.float32(0), jnp.float32(0)])
    grid = (v_pad // block_v,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4,), lambda i: (0,)),          # scalars, pinned
            pl.BlockSpec((block_v,), lambda i: (i,)),    # counts stream
            pl.BlockSpec((block_v,), lambda i: (i,)),    # ln(1/dL) stream
            pl.BlockSpec((block_v,), lambda i: (i,)),    # ln(1/dU) stream
        ],
        out_specs=pl.BlockSpec((1, 2), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(scal, counts, log_inv_delta_l, log_inv_delta_u)
    return out[0]
