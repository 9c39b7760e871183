"""Closeness centrality as an adaptive-sampling estimator plugin.

Eppstein-Wang style: sample uniform sources s, read the FULL per-source
distance vector the forward BFS already computed for the betweenness
draw, and estimate each vertex's *farness* as the sample mean of its
distance from the drawn sources.  The per-vertex observation is
normalized into [0, 1] by the phase-1 vertex-diameter estimate ``cap``:

    x_v(s) = min(d(s, v), cap) / cap      (reached)
           = 1                            (unreached — cap penalty)
           = 0                            (v == s, d = 0, and the sink)

so the shared Bernstein stop rule applies unchanged (its f/g bounds use
only that observations live in [0, 1]).  ``finalize`` de-normalizes:

    farness(v)  ~= mean_v * cap * n/(n-1)     (the n/(n-1) corrects for
                                               the s == v draws, which
                                               contribute exactly 0)
    closeness(v) = 1 / farness(v)

On connected graphs (the oracle regime of tests/test_estimators.py) the
cap never binds and the estimate is unbiased for the classic
(n-1) / sum_u d(u, v).  On disconnected graphs the cap acts as a
truncated-farness penalty — harmonic centrality is the estimator that
handles disconnection without a cap.

A second channel counts reached sources per vertex (a reachability
diagnostic, and the substrate's first C>1 frame — it exercises the
heterogeneous-schema paths of engine/checkpoint for free).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.kadabra import KadabraParams, calibrate_deltas
from repro.core.programs import program
from repro.kernels.stopcheck.ops import get_stop_rule

from .base import DrawBatch, Estimator, RunContext

__all__ = ["ClosenessEstimator", "hoeffding_omega"]


def hoeffding_omega(n_nodes: int, eps: float, delta: float,
                    c: float = 0.5):
    """Static sample cap for mean estimation of n [0,1] observables.

    Hoeffding + union bound over the n vertices:
    omega = c/eps^2 * ln(2n/delta) samples guarantee every per-vertex
    mean is within eps with probability 1 - delta (c = 0.5 exactly; kept
    as a parameter to mirror ``compute_omega``'s form).
    """
    n = jnp.maximum(jnp.asarray(n_nodes, jnp.float32), 2.0)
    return (c / (eps * eps)) * jnp.log(2.0 * n / delta)


def _params_impl(n_nodes, btilde0, *, eps: float,
                 delta: float) -> KadabraParams:
    omega = hoeffding_omega(n_nodes, eps, delta)
    lil, liu, _tau_star = calibrate_deltas(btilde0, eps, delta, omega)
    return KadabraParams(eps, delta, omega, lil, liu)


def _stop_params(eps: float, delta: float):
    def stop_params(n, b):    # a partial would lower as jit__unknown
        return _params_impl(n, b, eps=eps, delta=delta)
    return stop_params


class DistanceEstimator(Estimator):
    """Shared base of the distance-reading plugins: forward stream only,
    Hoeffding omega + calibration waterfilling over channel 0 (both
    observables are per-vertex [0, 1] means, so the generic Bernstein
    machinery is reused verbatim — only ``_obs`` differs)."""

    needs_forward = True
    stop_rule = "bernstein"

    def _obs(self, batch: DrawBatch, ctx: RunContext):
        raise NotImplementedError

    def _dist(self, batch: DrawBatch, ctx: RunContext):
        """(V+1, B) float32 distance columns, sliced off the BFS rows."""
        if batch.dist is None:
            raise ValueError(
                f"estimator {self.name!r} needs the forward (full-SSSP) "
                "stream; the bidirectional stream carries no unbiased "
                "per-source distances")
        return batch.dist[: ctx.n_nodes + 1, :].astype(jnp.float32)

    def make_params(self, graph, ctx: RunContext, eps: float, delta: float,
                    calib_counts, calib_tau):
        btilde0 = (calib_counts[0][: ctx.n_nodes]
                   / jnp.maximum(calib_tau.astype(jnp.float32), 1.0))

        return program(_stop_params, eps, delta)(ctx.n_nodes, btilde0)

    def accumulate(self, batch: DrawBatch, keep, ctx: RunContext):
        obs = self._obs(batch, ctx)                   # (C, V+1, B)
        keepf = keep.astype(jnp.float32)[None, None, :]
        return jnp.sum(obs * keepf, axis=2)           # (C, V+1)

    def stopping_rule(self, counts, tau, params, ctx: RunContext):
        rule = get_stop_rule(self.stop_rule)
        return rule(counts[0][: ctx.n_nodes], tau, params)


class ClosenessEstimator(DistanceEstimator):
    name = "closeness"
    channels = ("dist_sum", "reached")
    needs_diameter = True   # the [0,1] normalization cap

    def _cap(self, ctx: RunContext):
        # weighted stream: the phase-1 weighted-diameter bound (float
        # distances are not bounded by the hop-count vertex diameter
        # once weights exceed 1); unweighted runs leave distance_cap 0
        # and keep the PR-8 hop cap bit-for-bit
        dcap = float(getattr(ctx, "distance_cap", 0.0))
        if dcap > 0.0:
            return jnp.float32(dcap)
        return jnp.float32(max(int(ctx.vertex_diameter), 1))

    def _obs(self, batch: DrawBatch, ctx: RunContext):
        d = self._dist(batch, ctx)
        cap = self._cap(ctx)
        x = jnp.where(d < 0.0, 1.0, jnp.clip(d / cap, 0.0, 1.0))
        x = x.at[ctx.n_nodes, :].set(0.0)             # padding sink row
        reached = jnp.where(d >= 0.0, 1.0, 0.0).at[ctx.n_nodes, :].set(0.0)
        return jnp.stack([x, reached])

    def finalize(self, counts, tau, params, ctx: RunContext) -> np.ndarray:
        n = ctx.n_nodes
        tauf = max(int(tau), 1)
        cap = float(self._cap(ctx))
        mean = np.asarray(counts[0][:n]) / tauf
        farness = mean * cap * n / max(n - 1, 1)
        return np.where(farness > 0.0, 1.0 / np.maximum(farness, 1e-30),
                        0.0)

    def extras(self, params, ctx: RunContext) -> dict:
        return {"distance_cap": float(self._cap(ctx)),
                "scale_note": "eps/delta hold on the cap-normalized "
                              "farness scale"}
