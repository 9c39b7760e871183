"""The estimator-plugin protocol of the adaptive-sampling substrate.

The source paper closes with the claim that its parallelization "can be
applied in the same manner to adaptive sampling algorithms for other
problems", and its companion paper (van der Grinten et al., *Parallel
Adaptive Sampling with almost no Synchronization*, 1903.09422) gives the
decomposition this package encodes: an adaptive sampling algorithm is a
*draw* (one BFS-backed sample), an *accumulate* (fold the draw into a
per-vertex state frame), a *stopping rule* (read a consistent aggregated
frame, decide whether the guarantee holds) and a *finalize* (turn the
frame into scores).  Everything else — epochs, double-buffered frames,
hierarchical aggregation, surplus reuse, checkpointing, the three
execution lanes — is estimator-independent and lives in
``repro.core.engine``.

An estimator contributes:

  * ``name`` / ``channels`` — its :class:`FrameSchema`: the engine's
    state frames carry one (v_pad,) float32 row per channel, stacked
    into a (C_total, v_pad) matrix across all active estimators (the
    KADABRA frame of PR 1-6 is exactly the C=1 special case);
  * ``needs_forward`` / ``needs_diameter`` — which draw stream it can
    consume (see :class:`DrawBatch`) and whether its parameters read the
    phase-1 diameter estimate;
  * ``stop_rule`` — the name of its registered stopping-rule kernel in
    ``repro.kernels.stopcheck.ops`` (per-estimator dispatch);
  * the four hooks: ``make_params`` / ``accumulate`` / ``stopping_rule``
    / ``finalize``.

Hooks are pure jnp and traced inside the engine's jitted epoch step, so
they must be shape-stable; ``ctx`` (a :class:`RunContext` of static
ints) carries everything resolved before tracing.  ``accumulate`` gets
the whole :class:`DrawBatch` plus the round's ``keep`` mask and must
fold *only* kept samples — the engine calls it a second time with the
negated mask to build the surplus frame, which is how every estimator
inherits KADABRA's surplus-sample reuse for free.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np

__all__ = ["DrawBatch", "FrameSchema", "RunContext", "Estimator",
           "MetricReport"]


class RunContext(NamedTuple):
    """Static per-run facts every hook may close over (python ints /
    floats, so they are trace-time constants inside the jitted epoch
    step).  ``distance_cap`` is only nonzero on the WEIGHTED stream: the
    phase-1 weighted-diameter upper bound, which distance-normalizing
    estimators (closeness) prefer over the hop-count
    ``vertex_diameter`` — float distances are not bounded by hop counts
    once weights exceed 1."""
    n_nodes: int
    vertex_diameter: int
    distance_cap: float = 0.0


class FrameSchema(NamedTuple):
    """One estimator's slice of the stacked state frame."""
    name: str
    channels: tuple  # channel names, in frame-row order


class DrawBatch(NamedTuple):
    """One round of B shared draws, as seen by every accumulator.

    Produced once per sampling round by the engine's draw step and
    handed to *all* active estimators — the multi-estimator mode's
    amortization is exactly that this batch (one BFS stream) is paid
    for once.

    Two streams exist (``repro.core.sampler``):

      * ``bidir`` — KADABRA's balanced bidirectional BFS + uniform
        shortest-path draw.  ``dist`` is ``None``: the bidirectional
        search truncates each side's distance field at the meeting
        level, so there is no unbiased per-source distance vector to
        hand out.  This is ``run_kadabra``'s bit-compatibility stream.
      * ``forward`` — one full forward SSSP from each source s plus a
        backward path walk from t (probability telescopes to
        1/sigma_s(t): the drawn path is uniform among shortest s-t
        paths, so ``contrib`` is distributed exactly as in the bidir
        stream).  ``dist`` holds the exhausted per-source distance
        columns that closeness/harmonic consume.

    A third, opt-in stream ``weighted`` (``stream="weighted"`` on a
    graph carrying per-edge weights) has the forward stream's shape
    with FLOAT32 ``dist`` columns (true weighted distances; the
    -1.0/-3.0 sentinels keep every ``d >= 0`` reachability test valid
    on both dtypes) and ``length`` counting the drawn path's edges.
    """
    contrib: jax.Array          # (B, V+1) float32 — internal-vertex marks
    valid: jax.Array            # (B,) bool — s,t connected
    length: jax.Array           # (B,) int32 — path edge count, -1 invalid
    dist: Optional[jax.Array]   # (rows>=V+1, B) i32|f32 dist from s, or None
    sources: Optional[jax.Array]  # (B,) int32 — the drawn s, or None


class MetricReport(NamedTuple):
    """Per-metric result of an adaptive run (``AdaptiveRunResult.reports``)."""
    name: str
    scores: np.ndarray   # (V,) final estimates
    tau: int             # samples in this metric's deciding snapshot
    converged: bool      # its own stopping rule fired (vs max_epochs)
    omega: float         # its static sample cap
    stop_epoch: int      # epoch whose snapshot produced ``scores``
    extras: dict         # estimator-specific (e.g. closeness's distance cap)


class Estimator:
    """Base class: subclasses override the four hooks + class attrs.

    Instances are stateless (all run state lives in the engine's
    frames), so one instance per ``get_estimator`` call is safe to
    close over in jitted functions.
    """

    name: str = "?"
    channels: tuple = ()
    needs_forward: bool = False   # requires the forward (full-SSSP) stream
    needs_diameter: bool = False  # make_params/accumulate read ctx.vd
    stop_rule: str = "bernstein"  # registered kernel in kernels.stopcheck

    # compared and hashed by value (type and instance fields): a run's
    # programs are cached by the estimators they fold
    # (repro.core.programs), and every call resolves fresh instances
    def __eq__(self, other):
        return type(self) is type(other) and vars(self) == vars(other)

    def __hash__(self):
        return hash((type(self), tuple(sorted(vars(self).items()))))

    @property
    def schema(self) -> FrameSchema:
        return FrameSchema(self.name, tuple(self.channels))

    @property
    def n_channels(self) -> int:
        return len(self.channels)

    # ---- hooks ---------------------------------------------------------

    def make_params(self, graph, ctx: RunContext, eps: float, delta: float,
                    calib_counts, calib_tau):
        """Build the stopping-rule parameters from the calibration frame.

        ``calib_counts`` is this estimator's (C, V+1-or-V_pad) slice of
        the calibration counts; ``calib_tau`` the shared sample count.
        Runs eagerly (host side) once per run, before the epoch loop is
        traced."""
        raise NotImplementedError

    def accumulate(self, batch: DrawBatch, keep, ctx: RunContext):
        """Fold the kept samples of one round into a (C, V+1) increment.

        ``keep`` is the round's (B,) mask; samples with ``keep`` False
        must contribute exactly zero (the engine re-invokes with ~keep
        for the surplus frame)."""
        raise NotImplementedError

    def stopping_rule(self, counts, tau, params, ctx: RunContext):
        """(done, max_f, max_g) from this estimator's aggregated slice.

        ``counts`` is (C, v_pad); implementations strip padding rows
        themselves (ctx.n_nodes)."""
        raise NotImplementedError

    def finalize(self, counts, tau, params, ctx: RunContext) -> np.ndarray:
        """Scores (V,) from the flushed deciding snapshot."""
        raise NotImplementedError

    def extras(self, params, ctx: RunContext) -> dict:
        """Estimator-specific report fields (JSON-able)."""
        return {}
