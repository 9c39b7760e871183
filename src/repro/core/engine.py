"""The estimator-generic adaptive sampling engine (one driver, three lanes).

PR 1-6 grew three copies of the KADABRA driver (single-device, SPMD,
vertex-sharded) with betweenness hard-wired into each.  This module is
the refactor the paper's closing claim calls for — its parallelization
"can be applied in the same manner to adaptive sampling algorithms for
other problems": the phases

  phase 1  diameter        — double-sweep BFS bounds (repro.core.diameter)
  phase 2  calibration     — fixed sample count, blocking reduce, then
                             each estimator builds its stop-rule params
  phase 3  adaptive loop   — per epoch: aggregate the previous frame
                             while sampling the next one, then evaluate
                             every estimator's stopping rule on the
                             consistent snapshot

are estimator-independent and live HERE, once; what varies per metric is
the :class:`repro.core.estimators.base.Estimator` plugin (accumulate /
stopping_rule / finalize hooks plus a per-estimator frame schema).

State frames are channel-stacked: (C_total, V_pad) with one row per
estimator channel, C_total summed over the active estimators — the
PR 1-6 KADABRA frame is exactly the C=1 slice, and every jnp expression
along that slice is kept verbatim so ``run_kadabra`` (the thin wrapper
in ``repro.core.adaptive``) stays bit-for-bit identical on all three
lanes (pinned by tests/test_estimators.py).

Multi-estimator runs amortize the sampling: ONE draw stream (one BFS
per round) feeds every accumulator, so adding closeness+harmonic to a
betweenness run costs extra accumulation arithmetic but zero extra
graph traversals — the dominant cost.  Each metric keeps its OWN
stopping rule; because the f/g bounds are not monotone in tau, a
metric's result is frozen from the flushed snapshot of the FIRST epoch
its rule fires (identical to what its single-metric run would have
returned at the same seed), and the loop continues until every metric
has stopped (union stopping).  See DESIGN.md §Estimator substrate.

Checkpointing covers the generalized state (frames + per-metric frozen
snapshots) and stamps each checkpoint with the frame-schema id
(``repro.core.epoch.frame_schema_id``); restoring across layouts —
including any pre-refactor checkpoint — fails loudly with
:class:`repro.checkpoint.store.CheckpointSchemaError`.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, NamedTuple, Optional
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import distributed as dist
from .diameter import (estimate_diameter, estimate_diameter_sharded,
                       estimate_diameter_weighted,
                       estimate_diameter_weighted_sharded)
from .epoch import epoch_length, frame_schema_id
from .estimators import get_estimator
from .estimators.base import DrawBatch, Estimator, MetricReport, RunContext
from .bfs import frontier_route
from .graph import Graph
from .partition import (PartitionedGraph, auto_exchange_budget, exchange_plan,
                        max_active_source_chunks)
from .programs import ProgramTally, program
from .sampler import (sample_path_batched, sample_path_batched_sharded,
                      sample_path_forward_batched,
                      sample_path_forward_batched_sharded,
                      sample_path_weighted_batched,
                      sample_path_weighted_batched_sharded)

__all__ = ["DEFAULT_SAMPLE_BATCH_SIZE", "AdaptiveConfig",
           "AdaptiveRunResult", "EngineEpochStats", "MetricReport",
           "draw_fold", "make_agg_fn", "make_epoch_step_sharded",
           "make_epoch_step_spmd", "resolve_estimators",
           "resolve_sample_batch_size", "resolve_stream", "run_adaptive",
           "run_fixed", "total_channels"]

# Fallback B of the batched sampling lane (concurrent samples per BFS
# round) for entry points that run without a diameter estimate (the
# fixed-sampling baseline, the dry-run, the benchmarks).  run_adaptive
# itself resolves B per instance — see resolve_sample_batch_size.
DEFAULT_SAMPLE_BATCH_SIZE = 16


def resolve_sample_batch_size(requested, n_nodes: int,
                              vertex_diameter: int) -> int:
    """Pick the concurrent-sample width B for an instance.

    An explicitly ``requested`` B always wins.  Left as ``None`` it is
    derived from the phase-1 diameter estimate (free by the time
    sampling starts) and V: per-sample BFS depth tracks the diameter,
    and the batched lane masks a sample's column once its own search
    finishes while the rest of the batch keeps relaxing — so wide
    batches only pay off when path lengths are short and uniform.
    Low-diameter instances (R-MAT/social: VD within ~4 log2 V) run wide
    (B=64, edge-stream amortization maxed); mid-range runs the default
    16; high-diameter instances (grids/roads: VD beyond ~12 log2 V,
    widely varying path lengths within a batch) drop to 8 to bound the
    masked-round waste.  The batch_sweep/csc_driver_sweep sections of
    ``benchmarks/run.py`` are the empirical basis (BENCH_sampling.json).
    """
    if requested is not None:
        return max(1, int(requested))
    logv = max(1.0, float(np.log2(max(n_nodes, 2))))
    ratio = float(vertex_diameter) / logv
    if ratio <= 4.0:
        return 64
    if ratio <= 12.0:
        return DEFAULT_SAMPLE_BATCH_SIZE
    return 8


@dataclasses.dataclass(frozen=True)
class AdaptiveConfig:
    eps: float = 0.01
    delta: float = 0.1
    calib_samples_per_device: int = 32
    n0_base: int = 1000
    n0_exponent: float = 1.33
    max_epochs: int = 10_000
    diameter_sweeps: int = 2
    aggregation: str = "hierarchical"  # "hierarchical" | "flat" | "root"
    # Concurrent samples per batched BFS round: each device draws
    # ceil(n0 / B) rounds of B samples sharing one edge stream per BFS
    # level (the intra-device analogue of the paper's thread parallelism).
    # None = resolve per instance from the diameter estimate and V at
    # run time (resolve_sample_batch_size); an explicit value always
    # wins.  1 = the paper's sequential per-thread lane.
    sample_batch_size: Optional[int] = None


class EngineEpochStats(NamedTuple):
    """Per-epoch telemetry; max_f/max_g carry one entry per estimator
    (metric order = the run's ``metrics`` order)."""
    epoch: int
    tau: int
    max_f: tuple
    max_g: tuple
    seconds: float
    # samples drawn this epoch (mesh-wide; the tau delta the epoch
    # contributed) and — sharded lane only — the priced exchange
    # accounting dict from ExchangePlan.epoch_accounting (None off it)
    samples: int = 0
    exchange: Optional[dict] = None
    # single lane: the epoch's BFS expansions (one per level of its
    # rounds' shared search loops) and the edge blocks the node-blocked
    # kernel streamed in them (0 on other routes); 0 on the other lanes
    bfs_levels: int = 0
    nb_steps: int = 0


class AdaptiveRunResult(NamedTuple):
    reports: tuple              # MetricReport per estimator, metrics order
    tau: int                    # samples in the final flush (largest frame)
    n_epochs: int
    converged: bool             # every metric's own rule fired
    vertex_diameter: int
    stats: list                 # list[EngineEpochStats]
    # host seconds per phase, each ending on a blocking read: "diameter"
    # (phase 1, ends on the bound's int()), "calibration" (draws +
    # stop-rule params, ends on block_until_ready of both), "sampling"
    # (the epochs, each ending on its stop flags, and the final flush)
    phase_seconds: dict
    # the sampling phase: concurrent samples per BFS round and the
    # frontier-expansion lane its BFS levels ran on (bfs.frontier_route)
    batch_size: int = 0
    route: str = ""
    # compiles, cache loads and traces per phase ("diameter",
    # "calibration", "sampling" as in phase_seconds, "other" the rest of
    # the call): {phase: {key: value for HOST_COUNTER_KEYS}}
    host_counters: Optional[dict] = None


def _pad_len(v: int, n_dev: int) -> int:
    """counts length: V+1 (sink) padded so psum_scatter tiles evenly."""
    base = v + 1
    return ((base + n_dev - 1) // n_dev) * n_dev


def resolve_estimators(metrics) -> tuple:
    """Metric names (or Estimator instances) -> tuple of plugins."""
    if isinstance(metrics, (str, Estimator)):
        metrics = (metrics,)
    ests = tuple(m if isinstance(m, Estimator) else get_estimator(m)
                 for m in metrics)
    if not ests:
        raise ValueError("metrics must name at least one estimator")
    names = [e.name for e in ests]
    if len(set(names)) != len(names):
        raise ValueError(
            f"duplicate metrics {names}: each estimator owns its channel "
            "rows exactly once")
    return ests


def resolve_stream(estimators, stream: Optional[str] = None) -> str:
    """Pick the draw stream: 'bidir' (KADABRA's bidirectional search,
    the run_kadabra bit-compatibility stream) unless some estimator
    needs the forward full-SSSP stream's distance columns.  'weighted'
    (delta-stepping SSSP, graphs with per-edge weights) is opt-in only
    — it satisfies forward-stream needs (full float distance columns)
    but is never auto-selected."""
    need_fwd = [e.name for e in estimators if e.needs_forward]
    if stream is None:
        return "forward" if need_fwd else "bidir"
    if stream not in ("bidir", "forward", "weighted"):
        raise ValueError(
            f"unknown stream {stream!r} (expected 'bidir', 'forward' or "
            "'weighted')")
    if stream == "bidir" and need_fwd:
        raise ValueError(
            f"estimators {need_fwd} need the forward (full-SSSP) stream; "
            "the bidirectional stream carries no per-source distances")
    return stream


def total_channels(estimators) -> int:
    return sum(e.n_channels for e in estimators)


def _channel_offsets(estimators) -> tuple:
    offs, o = [], 0
    for e in estimators:
        offs.append(o)
        o += e.n_channels
    return tuple(offs)


# ---------------------------------------------------------------------------
# The shared draw-and-fold (generalized sampler.sample_batch)
# ---------------------------------------------------------------------------

def draw_fold(graph, key, n_samples: int, *, estimators, ctx: RunContext,
              stream: str = "bidir", batch_size: int = 1, carry=None,
              return_carry: bool = False, axis=None,
              with_exchange: bool = False, with_steps: bool = False):
    """Take exactly ``n_samples`` new samples, folding ONE shared draw
    stream through every estimator's ``accumulate`` hook.

    Structural twin of ``repro.core.sampler.sample_batch`` — identical
    batch-size clamp, round count, key split, offsets, keep masks and
    scan layout — generalized from the hard-wired betweenness fold to a
    channel-stacked (C_total, V+1) counts frame.  With a single
    betweenness estimator on the 'bidir' stream, every per-channel jnp
    expression matches sample_batch's elementwise, which is the
    bit-parity contract run_kadabra rests on (tests/test_estimators.py).

    The multi-estimator amortization happens here: one
    ``sample_path*_batched`` call per round — one (batched) BFS — feeds
    all accumulators; the per-metric cost is the accumulate arithmetic
    only.  Surplus samples of the final round are folded through the
    same hooks under the negated keep mask and returned as a second
    (C_total, V+1) frame when ``return_carry=True``, so every estimator
    inherits KADABRA's surplus-reuse for free; ``carry`` folds a
    previous surplus frame into this call's result.

    ``axis`` switches each round to the cooperative sharded samplers
    (call inside shard_map on a PartitionedGraph with a replicated key).

    ``with_exchange`` (sharded stream only) additionally returns the
    summed (2,) [levels_exchanged, levels_sparse] exchange tally of the
    rounds' BFS runs, appended as the trailing element of the return
    tuple.  The tally rides the scan's *outputs* — never the carry,
    never the key stream — so the counts/tau computation is the same
    program with or without it (the bit-parity contract above is
    untouched; the counters are dead code until observed).

    ``with_steps`` likewise appends the summed (2,) [expansions,
    streamed_blocks] work count of the rounds' replicated BFS runs
    (``repro.core.bfs`` ``BFSResult.steps``; zeros on streams that keep
    none), after the exchange tally when both are asked for.
    """
    batch_size = max(1, min(int(batch_size), int(n_samples)))
    rounds = -(-n_samples // batch_size)
    v1 = ctx.n_nodes + 1
    C = total_channels(estimators)

    if stream == "forward":
        draw = (partial(sample_path_forward_batched_sharded, axis=axis)
                if axis is not None else sample_path_forward_batched)
    elif stream == "weighted":
        draw = (partial(sample_path_weighted_batched_sharded, axis=axis)
                if axis is not None else sample_path_weighted_batched)
    elif stream == "bidir":
        draw = (partial(sample_path_batched_sharded, axis=axis)
                if axis is not None else sample_path_batched)
    else:
        raise ValueError(
            f"unknown stream {stream!r} (expected 'bidir', 'forward' or "
            "'weighted')")

    def fold_all(ps, keep):
        batch = DrawBatch(ps.contrib, ps.valid, ps.length,
                          getattr(ps, "dist", None),
                          getattr(ps, "sources", None))
        return jnp.concatenate(
            [est.accumulate(batch, keep, ctx) for est in estimators], axis=0)

    def step(state, xs):
        if return_carry:
            counts, tau, sur_counts, sur_tau = state
        else:
            counts, tau = state
        k, offset = xs
        ps = draw(graph, k, batch_size)
        keep = (offset + jnp.arange(batch_size)) < n_samples
        counts = counts + fold_all(ps, keep)
        tau = tau + jnp.sum(keep.astype(jnp.int32))
        if return_carry:
            sur_counts = sur_counts + fold_all(ps, ~keep)
            sur_tau = sur_tau + jnp.sum((~keep).astype(jnp.int32))
            state = (counts, tau, sur_counts, sur_tau)
        else:
            state = (counts, tau)
        out = jnp.sum((ps.valid & keep).astype(jnp.int32))
        extra = ()
        if with_exchange:
            extra += (ps.exchange,)
        if with_steps:
            steps = getattr(ps, "steps", None)
            extra += (jnp.zeros((2,), jnp.int32) if steps is None
                      else steps,)
        return state, ((out,) + extra if extra else out)

    if carry is None:
        counts0, tau0 = jnp.zeros((C, v1), jnp.float32), jnp.int32(0)
    else:
        counts0 = jnp.asarray(carry[0], jnp.float32).reshape(C, v1)
        tau0 = jnp.asarray(carry[1], jnp.int32).reshape(())
    init = (counts0, tau0)
    if return_carry:
        init = init + (jnp.zeros((C, v1), jnp.float32), jnp.int32(0))
    keys = jax.random.split(key, rounds)
    offsets = jnp.arange(rounds, dtype=jnp.int32) * batch_size
    state, outs = jax.lax.scan(step, init, (keys, offsets))
    tail = (tuple(jnp.sum(o, axis=0) for o in outs[1:])
            if with_exchange or with_steps else ())
    if return_carry:
        counts, tau, sur_counts, sur_tau = state
        return ((counts, tau), (sur_counts, sur_tau)) + tail
    counts, tau = state
    return (counts, tau) + tail


def _check_all(estimators, offsets, agg_counts, agg_tau, params,
               ctx: RunContext):
    """Every estimator's stopping rule on its channel slice of the
    aggregated snapshot -> ((E,) done, (E,) max_f, (E,) max_g)."""
    ds, fs, gs = [], [], []
    for est, off, p in zip(estimators, offsets, params):
        d, f, g = est.stopping_rule(
            agg_counts[off: off + est.n_channels], agg_tau, p, ctx)
        ds.append(d)
        fs.append(f)
        gs.append(g)
    return jnp.stack(ds), jnp.stack(fs), jnp.stack(gs)


def make_agg_fn(mesh, aggregation: str):
    all_axes = tuple(mesh.axis_names)
    local_axes, global_axes = dist.sampler_axes(mesh)
    if aggregation == "hierarchical":
        return lambda x: dist.hierarchical_allreduce(x, local_axes,
                                                     global_axes)
    if aggregation == "flat":
        return lambda x: dist.flat_allreduce(x, all_axes)
    return lambda x: dist.reduce_to_root_and_broadcast(x, all_axes)


def _agg_channels(agg_fn, x):
    """Apply a flat-vector allreduce to a (C, v_pad) channel-stacked
    frame: hierarchical_allreduce's psum_scatter tiles its leading axis
    over the devices, so the frame is flattened to (C*v_pad,) around
    the collective (n_dev divides v_pad ⇒ divides C*v_pad).  For C=1
    the reshape is the identity on the PR 1-6 (v_pad,) layout, keeping
    the lane bit-compatible."""
    return agg_fn(x.reshape(-1)).reshape(x.shape)


# ---------------------------------------------------------------------------
# Epoch steps (exposed for the multi-pod dry-run's HLO accounting)
# ---------------------------------------------------------------------------

def make_epoch_step_spmd(draw, mesh, aggregation: str, estimators,
                         stream: str, ctx: RunContext, n0: int,
                         batch_size: int):
    """One jit-able SPMD epoch (paper Alg. 2): aggregate the previous
    frame (collectives) while sampling the next one — ceil(n0 /
    batch_size) batched BFS rounds per device — then evaluate every
    estimator's stop rule on the consistent snapshot.  Exposed at module
    level so the multi-pod dry-run can .lower()/.compile() it on the
    production mesh and extract its roofline terms (DESIGN.md §Perf).

    ``draw`` is the draw-and-fold (:func:`draw_fold`).  Frames are
    channel-stacked; each device's masked surplus tail is carried into
    its next epoch's frame instead of dropped.  ``ctx`` carries what
    the estimators read: V, and the phase-1 bounds that
    distance-normalizing estimators (closeness) use as their cap.

    Signature of the returned fn:
      (graph, params: tuple (one per estimator),
       agg_counts (C, V_pad), agg_tau (),
       frame_counts (n_dev, C, V_pad) sharded, frame_tau (),
       sur_counts (n_dev, C, V+1) sharded, sur_tau (), keys (n_dev, 2))
      -> (agg_counts, agg_tau, new_frame, new_tau, new_sur_counts,
          new_sur_tau, done (E,), max_f (E,), max_g (E,))
    """
    offsets = _channel_offsets(estimators)
    C = total_channels(estimators)
    v_pad = _pad_len(ctx.n_nodes, mesh.devices.size)
    all_axes = tuple(mesh.axis_names)
    agg_fn = make_agg_fn(mesh, aggregation)
    rep = P()
    frame_spec = P(all_axes, None, None)
    key_spec = P(all_axes)

    def epoch_step(g, params, agg_counts, agg_tau, frame_counts, frame_tau,
                   sur_counts, sur_tau, keys):
        gspec = jax.tree.map(lambda _: rep, g)
        pspec = jax.tree.map(lambda _: rep, params)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(gspec, pspec, rep, rep, frame_spec, rep,
                           frame_spec, rep, key_spec),
                 out_specs=(rep, rep, frame_spec, rep, frame_spec, rep,
                            rep, rep, rep),
                 check_vma=False)
        def _step(g, params, agg_counts, agg_tau, frame_counts, frame_tau,
                  sur_counts, sur_tau, keys):
            # 1. hand the previous frame to the (async) reduction
            inc_counts = _agg_channels(agg_fn, frame_counts[0])
            inc_tau = dist.flat_allreduce(frame_tau, all_axes)
            # 2. sample the next frame — no data dependency on the
            #    collective, so the scheduler overlaps it (paper Alg. 2,
            #    lines 15/21/27); the previous surplus tail seeds it,
            #    this round's tail comes back as the next carry (the
            #    surplus sample count is the same on every device, so
            #    new_sur_tau stays a replicated scalar)
            (c, t), (sc, st) = draw(g, keys[0], n0, estimators=estimators,
                                    ctx=ctx, stream=stream,
                                    batch_size=batch_size,
                                    carry=(sur_counts[0], sur_tau),
                                    return_carry=True)
            new_counts = jnp.zeros(
                (1, C, v_pad), jnp.float32).at[0, :, : c.shape[1]].set(c)
            new_sur = sc[None]
            # 3. thread-0-equivalent: stop rules on the consistent snapshot
            agg_counts = agg_counts + inc_counts
            agg_tau = agg_tau + inc_tau
            done, mf, mg = _check_all(estimators, offsets, agg_counts,
                                      agg_tau, params, ctx)
            return (agg_counts, agg_tau, new_counts, t, new_sur, st,
                    done, mf, mg)

        return _step(g, params, agg_counts, agg_tau, frame_counts,
                     frame_tau, sur_counts, sur_tau, keys)

    return epoch_step


def make_epoch_step_sharded(draw, mesh, estimators, stream: str,
                            ctx: RunContext, n0: int, batch_size: int):
    """One jit-able COOPERATIVE epoch on a :class:`PartitionedGraph`.

    The graph is sharded over the whole mesh, so the mesh advances one
    batch of B samples per BFS round *collectively* (the
    bitmap-scheduled frontier exchange inside ``repro.core.bfs``,
    governed by the partition's static ``exchange_budget``) instead of
    sampling independently per device: the frame is replicated by
    construction and folds into the aggregate without any reduction
    collective.  ``n0`` is samples per epoch for the WHOLE mesh
    (``epoch_length(1)``: the cooperative mesh is one fast sampler).
    The other arguments are as in :func:`make_epoch_step_spmd`.

    Signature of the returned fn (all frames replicated):
      (pg, params tuple, agg_counts (C, V_pad), agg_tau (),
       frame_counts (C, V_pad), frame_tau (), sur_counts (C, V+1),
       sur_tau (), key (2,) replicated)
      -> (agg_counts, agg_tau, new_frame, new_tau, new_sur_counts,
          new_sur_tau, done (E,), max_f (E,), max_g (E,), exchange (2,))

    The 10th output is the epoch's summed [levels_exchanged,
    levels_sparse] frontier-exchange tally
    (``ExchangePlan.epoch_accounting`` prices it into telemetry).

    Exposed at module level so the multi-pod dry-run can
    .lower()/.compile() it on the production mesh and read the
    per-level frontier-exchange volume off its optimized HLO
    (DESIGN.md §Partitioning).
    """
    offsets = _channel_offsets(estimators)
    C = total_channels(estimators)
    v_pad = _pad_len(ctx.n_nodes, mesh.devices.size)
    all_axes = tuple(mesh.axis_names)
    rep = P()

    def epoch_step(g, params, agg_counts, agg_tau, frame_counts, frame_tau,
                   sur_counts, sur_tau, k):
        gspec = g.partition_spec(all_axes)
        pspec = jax.tree.map(lambda _: rep, params)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(gspec, pspec, rep, rep, rep, rep, rep, rep, rep),
                 out_specs=(rep,) * 10,
                 check_vma=False)
        def _step(g, params, agg_counts, agg_tau, frame_counts, frame_tau,
                  sur_counts, sur_tau, k):
            # 1. previous frame -> aggregate (replicated: no collective)
            agg_counts = agg_counts + frame_counts
            agg_tau = agg_tau + frame_tau
            # 2. cooperatively sample the next frame over the sharded
            #    graph; the previous surplus tail seeds it
            (c, t), (sc, st), xch = draw(
                g, k, n0, estimators=estimators, ctx=ctx, stream=stream,
                batch_size=batch_size, carry=(sur_counts, sur_tau),
                return_carry=True, axis=all_axes, with_exchange=True)
            new_counts = jnp.zeros(
                (C, v_pad), jnp.float32).at[:, : c.shape[1]].set(c)
            # 3. stop rules on the consistent snapshot
            done, mf, mg = _check_all(estimators, offsets, agg_counts,
                                      agg_tau, params, ctx)
            return (agg_counts, agg_tau, new_counts, t, sc, st,
                    done, mf, mg, xch)

        return _step(g, params, agg_counts, agg_tau, frame_counts,
                     frame_tau, sur_counts, sur_tau, k)

    return epoch_step


# ---------------------------------------------------------------------------
# Checkpointing (schema-stamped generalized state)
# ---------------------------------------------------------------------------

class _EngineCheckpointer:
    """Mid-run persistence of the engine loop's state (the elastic
    restart of long billion-edge runs): every ``checkpoint_every``
    epochs the 10-leaf tuple

        (agg counts (C, V_pad), agg tau, frame counts, frame tau,
         surplus counts (…, C, V+1), surplus tau,
         frozen counts (C, V_pad), frozen tau (E,), stop epoch (E,),
         rng key)

    is published atomically via ``repro.checkpoint.store``, stamped with
    the run's frame-schema id.  The frozen leaves carry each stopped
    metric's deciding snapshot so a resumed multi-metric run reports
    exactly what the uninterrupted one would; the loop key is saved
    *after* the epoch's split, so the resumed trajectory is
    bit-identical.  A restore against a different schema — a different
    metric set, or any pre-refactor checkpoint — raises
    ``CheckpointSchemaError`` before any shape assert.
    """

    def __init__(self, checkpoint_dir, checkpoint_every: int, schema: str,
                 shardings=None, telemetry=None):
        self.mgr = None
        self.shardings = shardings
        if checkpoint_dir:
            from repro.checkpoint.store import CheckpointManager
            self.mgr = CheckpointManager(checkpoint_dir, keep=3,
                                         save_every=max(1, checkpoint_every),
                                         schema=schema, telemetry=telemetry)

    def restore_state(self, state):
        """-> (state, epoch, done): the latest checkpoint when one
        exists, the passed-in templates (epoch 0, not done) otherwise."""
        if self.mgr is None:
            return state, 0, False
        out = self.mgr.restore_or_none(tuple(state),
                                       shardings=self.shardings)
        if out is None:
            return state, 0, False
        st, step, meta = out
        return (tuple(st), int(meta.get("epoch", step)),
                bool(meta.get("done", False)))

    def save_state(self, epoch: int, state, done: bool = False):
        if self.mgr is not None:
            self.mgr.maybe_save(epoch, tuple(state),
                                metadata={"epoch": epoch,
                                          "done": bool(done)})

    def wait(self):
        if self.mgr is not None:
            self.mgr.wait()


# ---------------------------------------------------------------------------
# Programs: phase 1, the draws, the flush (the epoch steps are above)
# ---------------------------------------------------------------------------
#
# Every program of every lane is a builder for ``repro.core.programs``
# (its docstring has the contract).  The mesh programs build their
# shard_map specs from the arguments inside the traced function.  The
# draw function is a static value, read where the lane runs.  Each
# builder returns a named function, whose name a device trace shows
# (``jit_phase1_diameter``); a ``partial`` lowers as ``jit__unknown``.

def _phase1_program(weighted: bool, n_sweeps: int):
    """Phase 1 on a replicated graph: the vertex-diameter bound, and on
    a weighted graph the weighted-diameter bound too."""
    if weighted:
        # weighted phase 1: hop-based VD bound for omega PLUS the
        # weighted-diameter bound distance-normalizing estimators use
        # as their cap (RunContext.distance_cap)
        def phase1_weighted_diameter(g):
            return estimate_diameter_weighted(g, n_sweeps=n_sweeps)
        return phase1_weighted_diameter

    def phase1_diameter(g):
        return estimate_diameter(g, n_sweeps=n_sweeps)
    return phase1_diameter


def _sharded_phase1_program(mesh, weighted: bool, n_sweeps: int):
    """Phase 1 on a partitioned graph, the mesh sweeping cooperatively:
    the BFS sweeps' vertex-diameter bound and the second sweep's
    distances (the occupancy sample of ``exchange_budget="auto"``), or
    on a weighted graph the vertex- and weighted-diameter bounds."""
    all_axes = tuple(mesh.axis_names)
    rep = P()

    def sharded_phase1(g):
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(g.partition_spec(all_axes),),
                 out_specs=(rep, rep if weighted else P(all_axes)),
                 check_vma=False)
        def sweep(g):
            if weighted:
                est = estimate_diameter_weighted_sharded(
                    g, n_sweeps=n_sweeps, axis=all_axes)
                return est.vertex_diameter, est.upper
            est, d = estimate_diameter_sharded(
                g, n_sweeps=n_sweeps, axis=all_axes, return_dist=True)
            return est.vertex_diameter, d

        return sweep(g)
    return sharded_phase1


def _phase1(graph, mesh, stream: str, n_sweeps: int):
    """Phase 1 on any lane's graph -> (vertex diameter, distance cap,
    graph, stats).  The cap is the weighted-diameter bound on the
    'weighted' stream, 0.0 on the others.  ``stats`` is what the
    ``phase.diameter`` span carries: on a replicated graph's BFS sweeps
    (``bfs_dist_pull``) ``phase1_route`` "pull" and ``phase1_levels``,
    the levels both sweeps expanded; empty on the other sweeps.

    On a partitioned graph the BFS double sweep always runs first: with
    ``exchange_budget="auto"`` it doubles as the budget's occupancy
    sample, and the returned graph carries the resolved static budget,
    so every later program compiles against it.  The weighted sweep
    then runs on that graph."""
    weighted = stream == "weighted"
    if not isinstance(graph, PartitionedGraph):
        est = program(_phase1_program, weighted, n_sweeps)(graph)
        if weighted:
            return int(est.vertex_diameter), float(est.upper), graph, {}
        vd, levels = jax.device_get((est.vertex_diameter, est.levels))
        return (int(vd), 0.0, graph,
                {"phase1_route": "pull", "phase1_levels": int(levels)})
    vd, dist_dev = program(_sharded_phase1_program, mesh, False,
                           n_sweeps)(graph)
    vd = int(vd)
    if graph.exchange_budget_auto:
        dist_np = np.asarray(dist_dev)             # (v_pad, n_sweep_seeds)
        occupancies = []
        for lvl in range(int(dist_np.max(initial=-1)) + 1):
            rows = (dist_np == lvl).any(axis=1)
            if rows.any():
                occupancies.append(max_active_source_chunks(graph, rows))
        graph = dataclasses.replace(
            graph, exchange_budget=auto_exchange_budget(graph, occupancies),
            exchange_budget_auto=False)
    if not weighted:
        return vd, 0.0, graph, {}
    vd_w, cap_w = program(_sharded_phase1_program, mesh, True,
                          n_sweeps)(graph)
    return int(vd_w), float(cap_w), graph, {}


def _calibration_program(draw, estimators, stream: str, ctx: RunContext,
                         n_samples: int, bsz: int):
    """The single lane's draws: (C, V+1) counts and tau of
    ``n_samples`` samples at one key."""
    def calibration_draws(g, k):
        return draw(g, k, n_samples=n_samples, batch_size=bsz,
                    estimators=estimators, ctx=ctx, stream=stream)
    return calibration_draws


def _spmd_draw_program(draw, mesh, estimators, stream: str,
                       ctx: RunContext, n_samples: int, bsz: int):
    """The SPMD lane's draws: ``n_samples`` on every device, each at its
    own key (pleasingly parallel), then one blocking reduce
    (MPI_Reduce) of the (C, v_pad) counts and tau."""
    all_axes = tuple(mesh.axis_names)
    C = total_channels(estimators)
    v_pad = _pad_len(ctx.n_nodes, mesh.devices.size)
    rep = P()

    def spmd_draws(g, keys):
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(jax.tree.map(lambda _: rep, g), P(all_axes)),
                 out_specs=(rep, rep), check_vma=False)
        def per_device(g, keys):
            c, t = draw(g, keys[0], n_samples, batch_size=bsz,
                        estimators=estimators, ctx=ctx, stream=stream)
            cp = jnp.zeros(
                (C, v_pad), jnp.float32).at[:, : c.shape[1]].set(c)
            return (dist.flat_allreduce(cp, all_axes),
                    dist.flat_allreduce(t, all_axes))

        return per_device(g, keys)
    return spmd_draws


def _sharded_draw_program(draw, mesh, estimators, stream: str,
                          ctx: RunContext, n_samples: int, bsz: int):
    """The sharded lane's draws: ``n_samples`` drawn by the whole mesh
    cooperatively at one replicated key -> (C, v_pad) counts and tau,
    replicated by construction."""
    all_axes = tuple(mesh.axis_names)
    C = total_channels(estimators)
    v_pad = _pad_len(ctx.n_nodes, mesh.devices.size)
    rep = P()

    def sharded_draws(g, k):
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(g.partition_spec(all_axes), rep),
                 out_specs=(rep, rep), check_vma=False)
        def cooperative(g, k):
            c, t = draw(g, k, n_samples, batch_size=bsz,
                        estimators=estimators, ctx=ctx, stream=stream,
                        axis=all_axes)
            cp = jnp.zeros(
                (C, v_pad), jnp.float32).at[:, : c.shape[1]].set(c)
            return cp, t

        return cooperative(g, k)
    return sharded_draws


def _epoch_program(draw, estimators, stream: str, ctx: RunContext, n0: int,
                   bsz: int):
    C = total_channels(estimators)
    offsets = _channel_offsets(estimators)
    v_pad = _pad_len(ctx.n_nodes, 1)

    def epoch_step(g, params, agg_c, agg_t, fr_c, fr_t, sur_c, sur_t, k):
        agg_c = agg_c + fr_c
        agg_t = agg_t + fr_t
        # surplus reuse: the masked tail of the previous epoch's last
        # round seeds this epoch's frame (valid i.i.d. samples; tau
        # counts them, so every estimator stays exact)
        (c, t), (sc, st), steps = draw(
            g, k, n0, batch_size=bsz, estimators=estimators, ctx=ctx,
            stream=stream, carry=(sur_c, sur_t), return_carry=True,
            with_steps=True)
        new_c = jnp.zeros(
            (C, v_pad), jnp.float32).at[:, : c.shape[1]].set(c)
        done, mf, mg = _check_all(estimators, offsets, agg_c, agg_t,
                                  params, ctx)
        return agg_c, agg_t, new_c, t, sc, st, done, mf, mg, steps
    return epoch_step


def _flush_program():
    # replicated frames; the association of the single-estimator flush
    # exactly: (agg + frame) first, then the surplus tail onto [: V+1]
    def flush(agg_c, agg_t, fr_c, fr_t, sur_c, sur_t):
        c = (agg_c + fr_c).at[:, : sur_c.shape[1]].add(sur_c)
        return c, agg_t + fr_t + sur_t
    return flush


def _spmd_flush_program(mesh, aggregation: str):
    # per-device frame + its surplus tail, then one reduction onto the
    # aggregate — the PR 1-6 flush association, channel-stacked
    all_axes = tuple(mesh.axis_names)
    agg_fn = make_agg_fn(mesh, aggregation)
    rep, frame_spec = P(), P(all_axes, None, None)

    def spmd_flush(agg_c, agg_t, fr_c, fr_t, sur_c, sur_t):
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(frame_spec, rep, frame_spec, rep),
                 out_specs=(rep, rep), check_vma=False)
        def reduce(fr_c, fr_t, sur_c, sur_t):
            c = fr_c[0].at[:, : sur_c.shape[2]].add(sur_c[0])
            return (_agg_channels(agg_fn, c),
                    dist.flat_allreduce(fr_t + sur_t, all_axes))

        inc_c, inc_t = reduce(fr_c, fr_t, sur_c, sur_t)
        return agg_c + inc_c, agg_t + inc_t
    return spmd_flush


# ---------------------------------------------------------------------------
# The lanes
# ---------------------------------------------------------------------------

class EpochOut(NamedTuple):
    """One epoch on any lane: the 6-leaf state, every estimator's stop
    flag and f/g margins, and the lane's work tallies — the sharded
    lane's [levels_exchanged, levels_sparse] frontier exchange and the
    single lane's [expansions, streamed_blocks] BFS work, None on a
    lane that keeps none."""
    agg_counts: jax.Array
    agg_tau: jax.Array
    frame_counts: jax.Array
    frame_tau: jax.Array
    sur_counts: jax.Array
    sur_tau: jax.Array
    done: jax.Array
    max_f: jax.Array
    max_g: jax.Array
    exchange: Optional[jax.Array] = None
    steps: Optional[jax.Array] = None

    @property
    def state(self) -> tuple:
        return tuple(self[:6])


@dataclasses.dataclass
class Lane:
    """One execution lane, as ``run_adaptive`` and ``run_fixed`` drive it.

    - ``draw(key, n, bsz, ctx) -> (counts, tau)``: ``n`` samples per
      sampler (per device on the SPMD lane, for the whole mesh on the
      sharded lane); counts (C, V+1) on the single lane, (C, v_pad) on
      the mesh lanes.  Calibration runs it.
    - ``make_epoch(params, ctx, n0, bsz)`` -> ``run(state, key)`` ->
      :class:`EpochOut`.
    - ``flush(state) -> (counts, tau)``: aggregate, frame and surplus.
    - ``init_state()``: the zero 6-leaf state.
    - ``shardings`` places the 10-leaf checkpoint tuple on a restore
      (None: default placement).
    """
    v_pad: int
    n_samplers: int         # independent samplers (the epoch schedule's)
    shardings: Optional[tuple]
    draw: Callable
    make_epoch: Callable
    flush: Callable
    init_state: Callable


def _zero_state(C: int, v_pad: int, v1: int) -> tuple:
    return (jnp.zeros((C, v_pad), jnp.float32), jnp.int32(0),
            jnp.zeros((C, v_pad), jnp.float32), jnp.int32(0),
            jnp.zeros((C, v1), jnp.float32), jnp.int32(0))


def _lane_name(graph, mesh) -> str:
    """Which lane runs ``graph`` on ``mesh``: 'single', 'spmd' or
    'sharded'."""
    if isinstance(graph, PartitionedGraph):
        if mesh is None:
            raise ValueError(
                "a PartitionedGraph needs the mesh its shards map onto "
                "(mesh=...); use a plain Graph for the single-device lane")
        if graph.n_shards != mesh.devices.size:
            raise ValueError(
                f"PartitionedGraph carries {graph.n_shards} shards but the "
                f"mesh has {mesh.devices.size} devices; rebuild with "
                f"partition_graph(graph, {mesh.devices.size})")
        return "sharded"
    if mesh is None or mesh.devices.size == 1:
        return "single"
    return "spmd"


def _lane(name: str, graph, mesh, estimators, stream: str,
          aggregation: str) -> Lane:
    if name == "single":
        return _single_lane(graph, estimators, stream)
    if name == "spmd":
        return _spmd_lane(graph, mesh, estimators, stream, aggregation)
    return _sharded_lane(graph, mesh, estimators, stream)


def _single_lane(graph: Graph, estimators, stream: str) -> Lane:
    v_pad = _pad_len(graph.n_nodes, 1)

    def draw(key, n, bsz, ctx):
        return program(_calibration_program, draw_fold, estimators, stream,
                       ctx, n, bsz)(graph, key)

    def make_epoch(params, ctx, n0, bsz):
        epoch_step = program(_epoch_program, draw_fold, estimators, stream,
                             ctx, n0, bsz)
        # jit lowers one program per mix of committed and uncommitted
        # arguments; the step's outputs are committed once the graph is,
        # the initial (or a restored) state is not: commit the state to
        # the graph's device, so every epoch runs the same program
        dev = graph.src.sharding

        def run(state, ke):
            out = epoch_step(graph, params, *jax.device_put(state, dev), ke)
            return EpochOut(*out[:9], steps=out[9])

        return run

    return Lane(v_pad, 1, None, draw, make_epoch,
                lambda state: program(_flush_program)(*state),
                partial(_zero_state, total_channels(estimators), v_pad,
                        graph.n_nodes + 1))


def _spmd_lane(graph: Graph, mesh: Mesh, estimators, stream: str,
               aggregation: str) -> Lane:
    all_axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    C = total_channels(estimators)
    v_pad = _pad_len(graph.n_nodes, n_dev)
    v1 = graph.n_nodes + 1
    rep = NamedSharding(mesh, P())
    frames = NamedSharding(mesh, P(all_axes, None, None))
    key_sharding = NamedSharding(mesh, P(all_axes))
    # shardings follow the 10-leaf checkpoint tuple: frames sharded over
    # the device axis, everything else (incl. frozen snapshots) replicated
    shardings = (rep, rep, frames, rep, frames, rep, rep, rep, rep, rep)

    def draw(key, n, bsz, ctx):
        return program(_spmd_draw_program, draw_fold, mesh, estimators,
                       stream, ctx, n, bsz)(
            graph, jax.random.split(key, n_dev))

    def make_epoch(params, ctx, n0, bsz):
        epoch_step = program(make_epoch_step_spmd, draw_fold, mesh,
                             aggregation, estimators, stream, ctx, n0, bsz)

        def run(state, ke):
            # the state committed, as on the single lane
            dev_keys = jax.device_put(jax.random.split(ke, n_dev),
                                      key_sharding)
            return EpochOut(*epoch_step(
                graph, params, *jax.device_put(state, shardings[:6]),
                dev_keys))

        return run

    def init_state():
        return jax.device_put(
            (jnp.zeros((C, v_pad), jnp.float32), jnp.int32(0),
             jnp.zeros((n_dev, C, v_pad), jnp.float32), jnp.int32(0),
             jnp.zeros((n_dev, C, v1), jnp.float32), jnp.int32(0)),
            shardings[:6])

    return Lane(v_pad, n_dev, shardings, draw, make_epoch,
                lambda state: program(_spmd_flush_program, mesh,
                                      aggregation)(*state),
                init_state)


def _sharded_lane(pg: PartitionedGraph, mesh: Mesh, estimators,
                  stream: str) -> Lane:
    v_pad = _pad_len(pg.n_nodes, mesh.devices.size)

    def draw(key, n, bsz, ctx):
        return program(_sharded_draw_program, draw_fold, mesh, estimators,
                       stream, ctx, n, bsz)(pg, key)

    def make_epoch(params, ctx, n0, bsz):
        epoch_step = program(make_epoch_step_sharded, draw_fold, mesh,
                             estimators, stream, ctx, n0, bsz)
        # the state committed, as on the single lane
        rep = NamedSharding(mesh, P())
        return lambda state, ke: EpochOut(
            *epoch_step(pg, params, *jax.device_put(state, rep), ke))

    # the cooperative mesh is ONE fast sampler: paper's shared-memory
    # epoch schedule, not the per-device one
    return Lane(v_pad, 1, None, draw, make_epoch,
                lambda state: program(_flush_program)(*state),
                partial(_zero_state, total_channels(estimators), v_pad,
                        pg.n_nodes + 1))


# ---------------------------------------------------------------------------
# The one driver
# ---------------------------------------------------------------------------

def run_adaptive(graph, metrics=("betweenness",), *,
                 eps: Optional[float] = None,
                 delta: Optional[float] = None, key=None,
                 mesh: Optional[Mesh] = None,
                 config: Optional[AdaptiveConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1,
                 stream: Optional[str] = None,
                 on_epoch=None, telemetry=None) -> AdaptiveRunResult:
    """Adaptive sampling for one or more centrality estimators.

    ``metrics`` names the estimator plugins (``repro.core.estimators``):
    e.g. ``("betweenness",)``, ``("closeness", "harmonic")`` or all
    three.  One shared draw stream feeds every estimator (one BFS per
    round, amortized across metrics); each metric keeps its own
    eps/delta stopping rule, its result frozen from the epoch its rule
    first fires, and the loop runs until all have stopped.

    ``graph`` may be a replicated :class:`Graph` (``mesh=None`` is the
    single-device lane; a mesh makes each device sample independently)
    or a :class:`repro.core.partition.PartitionedGraph` (the
    vertex-sharded lane: the mesh samples cooperatively; its device
    count must equal ``pg.n_shards``).

    The single lane's frontier route follows the graph it is handed
    (``bfs.frontier_route``, reported as ``route``): on a TPU the flat
    kernel where the whole (V+1, B) state fits VMEM, else the
    node-blocked kernel where the caller attached a CSC layout
    (``with_csc_layout``), else the XLA reference; off the TPU always
    the XLA reference.  The lane attaches no layout itself: on a
    256 x 256 lattice, the graph class that kernel targets, the XLA
    route ran a job faster (PERF.md).  Each epoch counts its BFS
    expansions and the edge blocks the node-blocked kernel streamed
    (``EngineEpochStats.bfs_levels``/``nb_steps``).

    Explicitly passed ``eps``/``delta`` take precedence over ``config``;
    left as ``None`` they fall back to the config's values
    (``AdaptiveConfig`` defaults 0.01 / 0.1).  ``stream=None`` picks
    'bidir' unless some metric needs the forward full-SSSP stream.

    ``checkpoint_dir`` enables schema-stamped mid-run persistence with
    bit-identical resume (see :class:`_EngineCheckpointer`).

    ``on_epoch(epoch, state)`` is an optional supervision hook (the
    resilience layer, :mod:`repro.runtime.supervisor`) called once per
    completed epoch with the 1-based epoch number and the lane's
    6-leaf state tuple, BEFORE the epoch is frozen into any metric
    snapshot and before it is checkpointed — so a hook that raises
    aborts the epoch without persisting it (the rollback contract),
    and a hook that returns a replacement state tuple (``None`` keeps
    the current one) substitutes it for everything downstream.  If the
    hook raises, pending async checkpoint publishes of *earlier* good
    epochs are still flushed before the exception propagates.

    ``telemetry`` accepts ``None`` (a true no-op), a
    :class:`repro.runtime.Telemetry` bus, or a JSONL path
    (``repro.runtime.telemetry.resolve_telemetry``).  Enabled, the run
    emits ``run.start``/``run.end``, per-epoch ``epoch.stats`` (tau,
    samples, wall time, stop-rule margins) — and on the sharded lane
    ``exchange.epoch`` (the priced frontier-exchange accounting) —
    and wraps the phase structure in spans.  Every counter published
    host-side already rides the jitted state at the ``on_epoch``
    boundary (the sharded step *always* carries its exchange tally),
    so telemetry on is bit-identical to telemetry off on every lane:
    the compiled programs and the key stream are the same; only
    host-side observation differs.

    ``phase_seconds`` and ``host_counters`` split the call into phases:
    ``diameter`` (phase 1, ending on the host read of the bound; its
    counters include the lane setup around it), ``calibration``
    (calibration draws and stop-rule params, ending on
    ``jax.block_until_ready`` of both, so their device work is timed
    here and not in the first epoch) and ``sampling`` (the epochs, each
    ending on the host read of its stop flags, and the final flush);
    ``host_counters`` adds ``other``, the rest of the call.  Each phase
    counts the backend compiles, cache loads and traces it spent
    (``repro.runtime.telemetry`` ``host_counter_delta``); ``run.end``
    carries their totals.  While a ``jax.profiler`` trace runs, the
    spans are trace annotations too.

    Every lane's programs (phase 1, the calibration draws, the stop-rule
    params, the epoch step, the flush) are built once per static
    configuration and kept (``repro.core.programs``): the graph, the
    keys, the stop-rule params and the state are their arguments.  A
    repeat call with the same graph shape, mesh and configuration, at
    any key and on any graph of that shape, reuses them and traces and
    lowers nothing.  ``run.end`` carries ``programs_built`` and
    ``programs_reused``: of the programs the call ran, how many were
    built in it and how many came from the cache.
    """
    from repro.runtime.telemetry import (host_counter_delta,
                                         host_counter_totals,
                                         resolve_telemetry)
    c_start = host_counter_totals()
    with ProgramTally() as used:
        telemetry = resolve_telemetry(telemetry)
        cfg = config if config is not None else AdaptiveConfig()
        overrides = {}
        if eps is not None:
            overrides["eps"] = eps
        if delta is not None:
            overrides["delta"] = delta
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        if key is None:
            key = jax.random.PRNGKey(0)
        estimators = resolve_estimators(metrics)
        stream = resolve_stream(estimators, stream)
        C = total_channels(estimators)
        offsets = _channel_offsets(estimators)
        E = len(estimators)
        # which metric owns each channel row (frozen-snapshot row masks)
        row_metric = np.concatenate(
            [np.full(est.n_channels, i) for i, est in enumerate(estimators)])

        # ---- lane selection + phase 1 (diameter) ---------------------------
        lane_name = _lane_name(graph, mesh)
        telemetry.emit("run.start", lane=lane_name,
                       metrics=[e.name for e in estimators],
                       n_nodes=int(graph.n_nodes), eps=float(cfg.eps),
                       delta=float(cfg.delta))
        c_diam = host_counter_totals()
        with telemetry.span("phase.diameter") as span:
            t0 = time.perf_counter()
            vd, dist_cap, graph, phase1_stats = _phase1(
                graph, mesh, stream, cfg.diameter_sweeps)
            span.set(**phase1_stats)
            t_diam = time.perf_counter() - t0
            lane = _lane(lane_name, graph, mesh, estimators, stream,
                         cfg.aggregation)
        c_diam_end = host_counter_totals()

        ctx = RunContext(int(graph.n_nodes), vd, dist_cap)
        bsz = resolve_sample_batch_size(cfg.sample_batch_size, ctx.n_nodes,
                                        ctx.vertex_diameter)
        route = frontier_route(graph, bsz, weighted=stream == "weighted")
        # the static per-level price list for the sharded lane's exchange
        # tally (host-side observation only)
        xplan = (exchange_plan(graph, bsz)
                 if isinstance(graph, PartitionedGraph) else None)

        # ---- phase 2: calibration + per-estimator stop-rule params ---------
        t0 = time.perf_counter()
        c_cal = host_counter_totals()
        with telemetry.span("phase.calibration"):
            key, k_cal = jax.random.split(key)
            # calib_samples_per_device keeps its meaning on every lane:
            # the cooperative mesh (one sampler) draws what its devices
            # would draw independently
            n_dev = 1 if mesh is None else mesh.devices.size
            counts0, tau0 = lane.draw(
                k_cal, cfg.calib_samples_per_device * n_dev // lane.n_samplers,
                bsz, ctx)
            params = tuple(
                est.make_params(graph, ctx, cfg.eps, cfg.delta,
                                counts0[off: off + est.n_channels], tau0)
                for est, off in zip(estimators, offsets))
            jax.block_until_ready((counts0, tau0, params))
        t_cal = time.perf_counter() - t0
        c_cal_end = host_counter_totals()

        # ---- phase 3: the adaptive loop ------------------------------------
        n0 = epoch_length(lane.n_samplers, base=cfg.n0_base,
                          exponent=cfg.n0_exponent)
        epoch_run = lane.make_epoch(params, ctx, n0, bsz)
        flush = lane.flush

        state = lane.init_state()
        frozen_c = jnp.zeros((C, lane.v_pad), jnp.float32)
        frozen_tau = jnp.zeros((E,), jnp.int32)
        stop_epoch = jnp.full((E,), -1, jnp.int32)
        k = key
        epoch = 0
        ckpt = None
        if checkpoint_dir:
            schema = frame_schema_id(est.schema for est in estimators)
            ckpt = _EngineCheckpointer(checkpoint_dir, checkpoint_every,
                                       schema, shardings=lane.shardings,
                                       telemetry=telemetry)
            full, epoch, _done = ckpt.restore_state(
                state + (frozen_c, frozen_tau, stop_epoch, k))
            state = full[:6]
            frozen_c, frozen_tau, stop_epoch, k = full[6:]
        stopped = np.asarray(stop_epoch) >= 0
        stats = []
        last_flush = None
        t0 = time.perf_counter()
        c_samp = host_counter_totals()
        try:
            while not stopped.all() and epoch < cfg.max_epochs:
                with telemetry.span("phase.epoch", epoch=epoch + 1) as span:
                    te = time.perf_counter()
                    k, ke = jax.random.split(k)
                    # any sequence in EpochOut's order will do
                    out = EpochOut._make(epoch_run(state, ke))
                    state = out.state
                    epoch += 1
                    if on_epoch is not None:
                        # supervision point: runs before freeze + save so a
                        # refused (or replaced) epoch never reaches a snapshot
                        # or the checkpoint store.  Pending async publishes are
                        # flushed first: the hook (and any disk fault it
                        # injects) must observe a settled on-disk state, and a
                        # swallowed publish error surfaces at the epoch after
                        # its save, not at the end of the run
                        if ckpt is not None:
                            ckpt.wait()
                        replacement = on_epoch(epoch, state)
                        if replacement is not None:
                            state = tuple(replacement)
                    newly = np.asarray(out.done) & ~stopped
                    if newly.any():
                        # freeze the newly stopped metrics' deciding snapshot:
                        # the flush of THIS epoch's state — identical to what
                        # each metric's single-run result would be at the same
                        # seed (f/g are non-monotone, so re-reading a later
                        # snapshot would not reproduce the single-run decision)
                        last_flush = flush(state)
                        fl_c, fl_t = last_flush
                        rows = jnp.asarray(
                            np.isin(row_metric, np.nonzero(newly)[0]))
                        newly_j = jnp.asarray(newly)
                        frozen_c = jnp.where(rows[:, None], fl_c, frozen_c)
                        frozen_tau = jnp.where(newly_j, fl_t, frozen_tau)
                        stop_epoch = jnp.where(newly_j, jnp.int32(epoch),
                                               stop_epoch)
                        stopped = stopped | newly
                    # host-side publication of the epoch's counters, at the
                    # on_epoch boundary where the state is already materialized
                    n_samples_epoch = int(state[3]) * lane.n_samplers
                    xch = out.exchange
                    xacct = (
                        xplan.epoch_accounting(int(xch[0]), int(xch[1]))
                        if xch is not None and xplan is not None else None)
                    levels, streamed = 0, 0
                    if out.steps is not None:
                        levels, streamed = (int(x)
                                            for x in np.asarray(out.steps))
                        span.set(bfs_levels=levels, nb_steps=streamed)
                    e_seconds = time.perf_counter() - te
                    max_f = [float(x) for x in np.asarray(out.max_f)]
                    max_g = [float(x) for x in np.asarray(out.max_g)]
                    stats.append(EngineEpochStats(
                        epoch, int(state[1]), tuple(max_f), tuple(max_g),
                        e_seconds, n_samples_epoch, xacct, levels, streamed))
                    if telemetry:
                        telemetry.emit(
                            "epoch.stats", epoch=epoch, tau=int(state[1]),
                            samples=n_samples_epoch, seconds=e_seconds,
                            max_f=max_f, max_g=max_g)
                        if xacct is not None:
                            telemetry.emit("exchange.epoch", epoch=epoch,
                                           **xacct)
                    if ckpt is not None:
                        ckpt.save_state(
                            epoch,
                            state + (frozen_c, frozen_tau, stop_epoch, k),
                            done=bool(stopped.all()))
        finally:
            # flush pending async publishes even when the loop aborts (an
            # on_epoch supervisor raising) — earlier good epochs must land,
            # and a swallowed publish error must surface here, not vanish
            if ckpt is not None:
                ckpt.wait()
        converged = stopped.copy()
        if not stopped.all():
            # max_epochs freeze of whatever never converged (reported with
            # converged=False; NOT recorded in stop_epoch's checkpoint state,
            # so a resume with a higher max_epochs keeps sampling)
            with telemetry.span("phase.flush"):
                last_flush = flush(state)
            fl_c, fl_t = last_flush
            remaining = ~stopped
            rows = jnp.asarray(np.isin(row_metric, np.nonzero(remaining)[0]))
            rem_j = jnp.asarray(remaining)
            frozen_c = jnp.where(rows[:, None], fl_c, frozen_c)
            frozen_tau = jnp.where(rem_j, fl_t, frozen_tau)
            stop_epoch = jnp.where(rem_j, jnp.int32(epoch), stop_epoch)
        t_samp = time.perf_counter() - t0
        c_samp_end = host_counter_totals()

        ft_np = np.asarray(frozen_tau)
        se_np = np.asarray(stop_epoch)
        reports = []
        for i, (est, off, p) in enumerate(zip(estimators, offsets, params)):
            sl = frozen_c[off: off + est.n_channels]
            reports.append(MetricReport(
                name=est.name,
                scores=est.finalize(sl, int(ft_np[i]), p, ctx),
                tau=int(ft_np[i]),
                converged=bool(converged[i]),
                omega=float(getattr(p, "omega", np.nan)),
                stop_epoch=int(se_np[i]),
                extras=est.extras(p, ctx)))
        tau_total = (int(last_flush[1]) if last_flush is not None
                     else int(ft_np.max(initial=0)))
        c_end = host_counter_totals()
        host_counters = {
            "diameter": host_counter_delta(c_diam, c_diam_end),
            "calibration": host_counter_delta(c_cal, c_cal_end),
            "sampling": host_counter_delta(c_samp, c_samp_end),
            "other": host_counter_delta(c_start, c_diam, c_diam_end, c_cal,
                                        c_cal_end, c_samp, c_samp_end, c_end)}
        csc = getattr(graph, "csc", None)
        telemetry.emit("run.end", tau=tau_total, n_epochs=epoch,
                       converged=bool(converged.all()), batch_size=bsz,
                       route=route, n_nodes=int(graph.n_nodes),
                       n_edges=int(graph.n_edges),
                       block_v=csc.block_v if csc is not None else 0,
                       block_e=csc.block_e if csc is not None else 0,
                       bfs_levels=sum(s.bfs_levels for s in stats),
                       nb_steps=sum(s.nb_steps for s in stats),
                       programs_built=used.built,
                       programs_reused=used.reused,
                       **host_counter_delta(c_start, c_end))
        return AdaptiveRunResult(
            tuple(reports), tau_total, epoch, bool(converged.all()),
            ctx.vertex_diameter, stats,
            {"diameter": t_diam, "calibration": t_cal,
             "sampling": t_samp}, bsz, route, host_counters)


def run_fixed(graph, n_samples: int, *, metrics=("betweenness",),
              key=None, batch_size: Optional[int] = None,
              mesh: Optional[Mesh] = None,
              stream: Optional[str] = None) -> tuple:
    """Non-adaptive baseline (RK-style fixed sample count, no stop rule)
    through the estimator substrate — one shared draw stream feeds every
    requested metric, and every engine lane is available: single-device,
    per-device independent (replicated graph + mesh, counts reduced
    once) and the cooperative vertex-sharded lane (PartitionedGraph +
    mesh).  Returns a tuple of :class:`MetricReport` in metrics order
    (``converged=False``: no guarantee attaches to a fixed run).

    ``batch_size=None`` falls back to ``DEFAULT_SAMPLE_BATCH_SIZE``
    (this baseline skips phase 1 when it can, so there is usually no
    diameter estimate to resolve a per-instance B from).  A diameter
    sweep IS run when a requested metric normalizes by the diameter cap
    (closeness), and on a PartitionedGraph (where it doubles as the
    frontier-exchange budget resolution).
    """
    estimators = resolve_estimators(metrics)
    stream = resolve_stream(estimators, stream)
    if key is None:
        key = jax.random.PRNGKey(0)
    if batch_size is None:
        batch_size = DEFAULT_SAMPLE_BATCH_SIZE
    offsets = _channel_offsets(estimators)
    lane_name = _lane_name(graph, mesh)
    # the diameter only feeds accumulate-side normalization (closeness's
    # cap); pure path-count / inverse-distance runs skip phase 1 — the
    # PR 1-6 fixed baseline's exact behavior (and bit-stream) — except
    # on a partitioned graph, where the BFS sweep resolves the exchange
    # budget
    needs_vd = (stream in ("forward", "weighted")
                and any(e.needs_diameter for e in estimators))
    ctx = RunContext(int(graph.n_nodes), 0, 0.0)
    if needs_vd or lane_name == "sharded":
        # the weighted sweep only where its bounds are used
        vd, dcap, graph, _ = _phase1(graph, mesh,
                                     stream if needs_vd else "bidir", 2)
        if needs_vd:
            ctx = RunContext(int(graph.n_nodes), vd, dcap)
    # only the epochs aggregate: the draws reduce with one flat psum
    lane = _lane(lane_name, graph, mesh, estimators, stream,
                 AdaptiveConfig.aggregation)
    # n samples in all, rounded up to a whole number per sampler (tau
    # counts what was drawn, so the estimates stay exact)
    counts, tau = lane.draw(key, -(-n_samples // lane.n_samplers),
                            batch_size, ctx)
    counts = counts[:, : graph.n_nodes + 1]

    reports = []
    for est, off in zip(estimators, offsets):
        sl = counts[off: off + est.n_channels]
        reports.append(MetricReport(
            name=est.name,
            scores=est.finalize(sl, int(tau), None, ctx),
            tau=int(tau), converged=False, omega=float("nan"),
            stop_epoch=0, extras=est.extras(None, ctx)))
    return tuple(reports)
