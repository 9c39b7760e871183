"""Betweenness entry points over the estimator-generic adaptive engine.

PR 1-6 grew three hard-wired betweenness drivers here (single-device,
SPMD, vertex-sharded).  They are gone: the phases, the epoch loop, the
aggregation strategies, checkpointing and all three execution lanes now
live ONCE in ``repro.core.engine``, generic over the estimator plugins
of ``repro.core.estimators`` — betweenness is just the C=1 plugin.
What remains here is the historical public surface:

  * :func:`run_kadabra` — the paper's parallel KADABRA, now a thin
    mapping of the engine's multi-metric result onto the classic
    :class:`BetweennessResult`.  Bit-for-bit identical to the
    pre-refactor drivers on all three lanes at a fixed seed (pinned by
    tests/test_estimators.py);
  * :func:`run_fixed_sampling` — the non-adaptive baseline, routed
    through the same engine;
  * the engine's configuration surface (``AdaptiveConfig``,
    ``DEFAULT_SAMPLE_BATCH_SIZE``, ``resolve_sample_batch_size``).

For closeness/harmonic — or several metrics amortized over one BFS
stream — call ``repro.core.engine.run_adaptive`` directly.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np
from jax.sharding import Mesh

from .engine import (DEFAULT_SAMPLE_BATCH_SIZE, AdaptiveConfig,
                     AdaptiveRunResult, resolve_sample_batch_size,
                     run_adaptive, run_fixed)

__all__ = ["DEFAULT_SAMPLE_BATCH_SIZE", "AdaptiveConfig",
           "BetweennessResult", "EpochStats", "resolve_sample_batch_size",
           "run_kadabra", "run_fixed_sampling"]


class EpochStats(NamedTuple):
    epoch: int
    tau: int
    max_f: float
    max_g: float
    seconds: float


class BetweennessResult(NamedTuple):
    btilde: np.ndarray          # (V,) approximate betweenness
    tau: int                    # total samples
    n_epochs: int
    converged: bool
    omega: float
    vertex_diameter: int
    stats: list                 # list[EpochStats]
    phase_seconds: dict         # diameter / calibration / sampling


def run_kadabra(graph, *, eps: Optional[float] = None,
                delta: Optional[float] = None,
                key=None, mesh: Optional[Mesh] = None,
                config: Optional[AdaptiveConfig] = None,
                checkpoint_dir: Optional[str] = None,
                checkpoint_every: int = 1,
                on_epoch=None) -> BetweennessResult:
    """Approximate betweenness with the paper's parallel KADABRA.

    A thin wrapper over ``repro.core.engine.run_adaptive`` with the
    single betweenness estimator on the bidirectional draw stream — the
    exact sample stream, key flow and arithmetic of the pre-refactor
    drivers, so results are bit-for-bit identical to PR 1-6 at a fixed
    seed on every lane.

    Explicitly passed ``eps``/``delta`` take precedence over the
    corresponding fields of ``config``; left as ``None`` they fall back
    to the config's values (``AdaptiveConfig`` defaults 0.01 / 0.1).

    ``graph`` may be a replicated :class:`repro.core.graph.Graph` (each
    device samples independently; ``mesh=None`` is the single-device
    lane) or a :class:`repro.core.partition.PartitionedGraph` (the
    vertex-sharded lane: the mesh samples cooperatively; its device
    count must equal ``pg.n_shards``).

    ``checkpoint_dir`` enables schema-stamped mid-run persistence; a
    rerun pointed at the same directory resumes from the latest
    checkpoint with a bit-identical trajectory.

    ``on_epoch`` is the engine's per-epoch supervision hook (see
    ``run_adaptive``) — the seam ``repro.runtime.supervisor`` attaches
    its watchdog and fault injection to.
    """
    res: AdaptiveRunResult = run_adaptive(
        graph, ("betweenness",), eps=eps, delta=delta, key=key, mesh=mesh,
        config=config, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, stream="bidir",
        on_epoch=on_epoch)
    rep = res.reports[0]
    stats = [EpochStats(s.epoch, s.tau, s.max_f[0], s.max_g[0], s.seconds)
             for s in res.stats]
    return BetweennessResult(
        rep.scores, rep.tau, res.n_epochs, rep.converged, rep.omega,
        res.vertex_diameter, stats, res.phase_seconds)


def run_fixed_sampling(graph, n_samples: int, *, key=None,
                       batch_size: Optional[int] = None):
    """Non-adaptive baseline (RK-style fixed sample count, no stop rule).

    Routed through ``repro.core.engine.run_fixed`` with the betweenness
    estimator — same draw stream and fold as before the estimator
    substrate (bit-for-bit at a fixed seed).  ``batch_size=None`` falls
    back to ``DEFAULT_SAMPLE_BATCH_SIZE``; pass an explicit width to
    measure a specific lane."""
    reports = run_fixed(graph, n_samples, metrics=("betweenness",),
                        key=key, batch_size=batch_size)
    return reports[0].scores
