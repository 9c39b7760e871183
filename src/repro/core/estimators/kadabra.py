"""Betweenness centrality as an estimator plugin (KADABRA).

This is the pre-refactor hard-wired algorithm of ``core/adaptive.py``
re-expressed through the :class:`~repro.core.estimators.base.Estimator`
protocol — the C=1 special case every other plugin generalizes.  All of
the statistics (omega, f/g Bernstein bounds, per-vertex delta
waterfilling) stay in ``repro.core.kadabra``; this module only adapts
them to the hook signatures, and does so with the *exact same jnp
expressions* the PR 1-6 drivers used, which is what keeps
``run_kadabra`` through the plugin engine bit-for-bit identical to the
pre-refactor output (tests/test_estimators.py pins this on all three
lanes).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.kadabra import (KadabraParams, calibrate_deltas,
                                compute_omega)
from repro.core.programs import program
from repro.kernels.stopcheck.ops import get_stop_rule

from .base import DrawBatch, Estimator, RunContext

__all__ = ["BetweennessEstimator"]


def _params_impl(vd, btilde0, *, eps: float, delta: float) -> KadabraParams:
    # identical computation (and jit boundary) to the pre-refactor
    # adaptive._make_params: omega from the diameter bound, then the
    # per-vertex delta waterfilling on the calibration estimates
    omega = compute_omega(vd, eps, delta)
    lil, liu, _tau_star = calibrate_deltas(btilde0, eps, delta, omega)
    return KadabraParams(eps, delta, omega, lil, liu)


def _stop_params(eps: float, delta: float):
    def stop_params(n, b):    # a partial would lower as jit__unknown
        return _params_impl(n, b, eps=eps, delta=delta)
    return stop_params


class BetweennessEstimator(Estimator):
    """KADABRA betweenness: one 'path_counts' channel, bidir-compatible.

    The observation for vertex x in one sample is the indicator that x
    is internal to the drawn uniform shortest path — in [0, 1], so the
    Bernstein stop rule applies with the per-vertex budgets from the
    calibration waterfilling.  Consumes either stream: ``contrib`` is
    distributed identically in both (the forward stream's one-sided walk
    telescopes to the same 1/sigma_s(t) path law).
    """

    name = "betweenness"
    channels = ("path_counts",)
    needs_forward = False
    needs_diameter = True
    stop_rule = "bernstein"

    def make_params(self, graph, ctx: RunContext, eps: float, delta: float,
                    calib_counts, calib_tau):
        btilde0 = (calib_counts[0][: ctx.n_nodes]
                   / jnp.maximum(calib_tau.astype(jnp.float32), 1.0))

        return program(_stop_params, eps, delta)(ctx.vertex_diameter, btilde0)

    def accumulate(self, batch: DrawBatch, keep, ctx: RunContext):
        # verbatim the sample_batch fold: masked sum over the round's
        # sample axis (bit-parity anchor — do not "simplify")
        c = jnp.sum(jnp.where(keep[:, None], batch.contrib, 0.0), axis=0)
        return c[None, :]

    def stopping_rule(self, counts, tau, params, ctx: RunContext):
        rule = get_stop_rule(self.stop_rule)
        return rule(counts[0][: ctx.n_nodes], tau, params)

    def finalize(self, counts, tau, params, ctx: RunContext) -> np.ndarray:
        return np.asarray(counts[0][: ctx.n_nodes]) / max(int(tau), 1)
