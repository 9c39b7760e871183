"""Resilience tests: checkpoint integrity (checksums, quarantine,
crash consistency, publish-error surfacing, GC guard), the fault
framework, and ResilientRunner recovery — bit-identical replay for
kill/corruption/poison faults, (eps, delta) + exact tau accounting for
the elastic degradation ladder.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import (CheckpointError, CheckpointIntegrityError,
                                    CheckpointLayoutError,
                                    CheckpointManager, CheckpointSchemaError,
                                    install_publish_fault_hook, latest_step,
                                    restore, restore_arrays, save)
from repro.checkpoint import store as store_mod
from repro.core.adaptive import AdaptiveConfig, run_kadabra
from repro.core.engine import run_adaptive
from repro.core.graph import build_graph
from repro.runtime import (DeviceLoss, FaultContext, FaultSchedule,
                           FaultSpec, InjectedFault, InvariantViolation,
                           ResilienceExhausted, ResilientRunner, RetryPolicy,
                           apply_fault, available_faults,
                           check_state_invariants, elastic_migrate_state)
from repro.runtime.faults import (corrupt_newest_step, poison_state,
                                  truncate_newest_manifest)


def _tree():
    return {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}


def _small_graph(seed=0, v=100, e=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, e)
    dst = (src + 1 + rng.integers(0, v - 1, e)) % v
    return build_graph(np.concatenate([src, dst]),
                       np.concatenate([dst, src]), v)


# ---------------------------------------------------------------------------
# Checkpoint integrity: checksums, quarantine, fallback
# ---------------------------------------------------------------------------

def test_corrupt_leaf_quarantined_and_fallback(tmp_path):
    """Bit-rot in the newest step: restore detects the CRC mismatch,
    quarantines the step, and silently falls back to the previous
    verifying one."""
    root = str(tmp_path / "ck")
    tree = _tree()
    save(root, 1, tree)
    save(root, 2, jax.tree.map(lambda x: x + 1, tree))
    assert corrupt_newest_step(root) is not None
    restored, step, _ = restore(root, tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    # the damaged step is renamed out of the step namespace, not deleted
    names = sorted(os.listdir(root))
    assert any(n.startswith("step_00000002.quarantined") for n in names)
    assert latest_step(root) == 1


def test_explicit_step_corruption_raises_no_quarantine(tmp_path):
    """A pinned step is a debugging request: restore it exactly or
    raise — never quarantine, never fall back."""
    root = str(tmp_path / "ck")
    tree = _tree()
    save(root, 1, tree)
    corrupt_newest_step(root)
    with pytest.raises(CheckpointIntegrityError):
        restore(root, tree, step=1)
    assert latest_step(root) == 1       # still in place


def test_torn_manifest_restore_or_none_falls_back(tmp_path):
    """Satellite: a torn manifest.json (the power-loss tear) must route
    through quarantine-and-fallback, not crash startup with a
    JSONDecodeError."""
    root = str(tmp_path / "ck")
    tree = _tree()
    mgr = CheckpointManager(root, save_every=1)
    mgr.maybe_save(1, tree)
    mgr.maybe_save(2, tree)
    mgr.wait()
    truncate_newest_manifest(root)
    out = mgr.restore_or_none(tree)
    assert out is not None
    _, step, _ = out
    assert step == 1
    # and with NO fallback available, torn-manifest maps to None
    root2 = str(tmp_path / "ck2")
    mgr2 = CheckpointManager(root2, save_every=1)
    mgr2.maybe_save(1, tree)
    mgr2.wait()
    truncate_newest_manifest(root2)
    assert mgr2.restore_or_none(tree) is None


def test_missing_leaf_file_quarantined(tmp_path):
    root = str(tmp_path / "ck")
    tree = _tree()
    save(root, 1, tree)
    save(root, 2, tree)
    os.remove(str(tmp_path / "ck" / "step_00000002" / "arr_000001.npy"))
    _, step, _ = restore(root, tree)
    assert step == 1


def test_layout_and_schema_errors_are_typed(tmp_path):
    """Satellite: the bare asserts are gone — leaf-count and shape
    mismatches raise typed CheckpointErrors (still loud under
    ``python -O``), and they are caller bugs: no quarantine."""
    root = str(tmp_path / "ck")
    save(root, 1, _tree(), schema="schema-A")
    with pytest.raises(CheckpointLayoutError):
        restore(root, {"a": jnp.arange(8.0)})            # 2 leaves on disk
    with pytest.raises(CheckpointLayoutError):
        restore(root, {"a": jnp.arange(9.0),
                       "b": {"c": jnp.ones((3, 3))}})    # shape mismatch
    with pytest.raises(CheckpointSchemaError):
        restore(root, _tree(), expect_schema="schema-B")
    # typed errors share one base for supervisor-level handling, and
    # the schema error stays a ValueError for pre-existing call sites
    assert issubclass(CheckpointLayoutError, CheckpointError)
    assert issubclass(CheckpointSchemaError, ValueError)
    assert latest_step(root) == 1       # nothing was quarantined


def test_restore_arrays_verifies_and_falls_back(tmp_path):
    root = str(tmp_path / "ck")
    save(root, 1, _tree(), metadata={"epoch": 1})
    save(root, 2, _tree(), metadata={"epoch": 2})
    corrupt_newest_step(root)
    arrays, step, meta = restore_arrays(root)
    assert step == 1 and meta["epoch"] == 1
    assert len(arrays) == 2
    np.testing.assert_array_equal(arrays[0], np.arange(8.0))


# ---------------------------------------------------------------------------
# Publish-error surfacing + crash consistency + GC contracts
# ---------------------------------------------------------------------------

def test_async_publish_error_surfaces_in_wait(tmp_path):
    """Satellite: a disk error on the background publish thread must
    re-raise from wait()/maybe_save(), never vanish."""
    def boom(kind, step, i):
        raise OSError(28, "No space left on device")

    mgr = CheckpointManager(str(tmp_path / "ck"), save_every=1)
    install_publish_fault_hook(boom)
    try:
        mgr.maybe_save(1, _tree())
        with pytest.raises(OSError):
            mgr.wait()
        # the next maybe_save also surfaces a still-pending failure
        mgr.maybe_save(2, _tree())
        with pytest.raises(OSError):
            mgr.maybe_save(3, _tree())
    finally:
        install_publish_fault_hook(None)
    assert latest_step(str(tmp_path / "ck")) is None


def test_unwritable_root_raises_from_save(tmp_path):
    """The root path collides with an existing file — the sync save
    path must raise the OS error, not swallow it."""
    f = tmp_path / "not_a_dir"
    f.write_text("x")
    with pytest.raises(OSError):
        save(str(f / "ck"), 1, _tree())


def test_crash_mid_publish_leaves_no_torn_step(tmp_path):
    """Satellite: kill mid-publish (fault hook inside the leaf-write
    loop) — the torn .tmp is invisible, latest_step skips it, restore
    falls back to the previous verified step."""
    root = str(tmp_path / "ck")
    tree = _tree()
    save(root, 1, tree)

    def kill_on_second_leaf(kind, step, i):
        if kind == "leaf" and step == 2 and i == 1:
            raise InjectedFault("killed mid-publish")

    install_publish_fault_hook(kill_on_second_leaf)
    try:
        with pytest.raises(InjectedFault):
            save(root, 2, tree)
    finally:
        install_publish_fault_hook(None)
    # the torn write never became a step
    assert os.path.isdir(os.path.join(root, "step_00000002.tmp"))
    assert latest_step(root) == 1
    _, step, _ = restore(root, tree)
    assert step == 1
    # and a crash BEFORE the manifest fsync behaves the same
    def kill_on_manifest(kind, step, i):
        if kind == "manifest" and step == 3:
            raise InjectedFault("killed before manifest")

    install_publish_fault_hook(kill_on_manifest)
    try:
        with pytest.raises(InjectedFault):
            save(root, 3, tree)
    finally:
        install_publish_fault_hook(None)
    assert latest_step(root) == 1


def test_keep_zero_disables_gc(tmp_path):
    """Satellite: keep=0 is the explicit unlimited-retention contract
    (and negative keep is rejected)."""
    root = str(tmp_path / "ck")
    for s in range(1, 6):
        save(root, s, _tree(), keep=0)
    steps = sorted(d for d in os.listdir(root) if d.startswith("step_"))
    assert len(steps) == 5
    with pytest.raises(ValueError):
        save(root, 9, _tree(), keep=-1)
    with pytest.raises(ValueError):
        CheckpointManager(root, keep=-2)


def test_gc_skips_step_being_read(tmp_path):
    """Satellite: GC must never delete a step a concurrent restore is
    mid-read on."""
    root = str(tmp_path / "ck")
    save(root, 1, _tree())
    d1 = os.path.join(root, "step_00000001")
    with store_mod._reading(d1):
        # publish steps 2..4 with keep=1 while step 1 is "being read"
        for s in range(2, 5):
            save(root, s, _tree(), keep=1)
        assert os.path.isdir(d1)        # survived every GC pass
    save(root, 5, _tree(), keep=1)      # read finished: now collectable
    steps = sorted(d for d in os.listdir(root) if d.startswith("step_"))
    assert steps == ["step_00000005"]


# ---------------------------------------------------------------------------
# The fault framework
# ---------------------------------------------------------------------------

def test_fault_schedule_seeded_determinism():
    a = FaultSchedule.from_seed(42, n_faults=6, max_epoch=10)
    b = FaultSchedule.from_seed(42, n_faults=6, max_epoch=10)
    assert a.specs == b.specs
    c = FaultSchedule.from_seed(43, n_faults=6, max_epoch=10)
    assert a.specs != c.specs
    assert all(1 <= s.epoch <= 10 for s in a.specs)


def test_fault_schedule_one_shot_take():
    sched = FaultSchedule([FaultSpec("kill", 2), FaultSpec("nan", 2),
                           FaultSpec("hang", 3)])
    first = sched.take(2)
    assert [s.kind for s in first] == ["kill", "nan"]
    assert sched.take(2) == []          # a retried pass does not re-trip
    assert not sched.exhausted
    assert [s.kind for s in sched.take(3)] == ["hang"]
    assert sched.exhausted
    sched.reset()
    assert len(sched.take(2)) == 2


def test_apply_fault_kinds(tmp_path):
    ctx = FaultContext(checkpoint_root=str(tmp_path), n_devices=8)
    with pytest.raises(InjectedFault):
        apply_fault(FaultSpec("kill", 1), ctx, None)
    with pytest.raises(DeviceLoss) as e:
        apply_fault(FaultSpec("shrink", 1), ctx, None)
    assert e.value.survivors == 4       # defaults to half the mesh
    with pytest.raises(DeviceLoss) as e:
        apply_fault(FaultSpec("shrink", 1, survivors=3), ctx, None)
    assert e.value.survivors == 3
    with pytest.raises(ValueError):
        FaultSpec("meteor", 1)
    state = (np.ones((2, 4)),) * 6
    out = apply_fault(FaultSpec("nan", 1), ctx, state)
    assert not np.isfinite(np.asarray(out[2])).all()
    assert apply_fault(FaultSpec("hang", 1, delay=0.0), ctx, state) is state
    assert set(available_faults()) == {"kill", "shrink", "corrupt",
                                       "truncate", "nan", "hang"}


# ---------------------------------------------------------------------------
# Watchdog + elastic migration units
# ---------------------------------------------------------------------------

def _fake_state(tau=10):
    c = np.ones((2, 8), np.float32)
    return [c.copy(), np.int32(tau), c.copy(), np.int32(3),
            np.ones((2, 9), np.float32), np.int32(1)]


def test_invariant_watchdog():
    assert check_state_invariants(tuple(_fake_state())) == 10
    s = _fake_state()
    s[2] = poison_state(tuple(s))[2]
    with pytest.raises(InvariantViolation, match="non-finite"):
        check_state_invariants(tuple(s))
    s = _fake_state()
    s[0][0, 0] = -1.0
    with pytest.raises(InvariantViolation, match="negative"):
        check_state_invariants(tuple(s))
    s = _fake_state()
    s[1] = np.int32(-2)
    with pytest.raises(InvariantViolation, match="negative sample"):
        check_state_invariants(tuple(s))
    with pytest.raises(InvariantViolation, match="backwards"):
        check_state_invariants(tuple(_fake_state(tau=5)), last_tau=7)
    assert check_state_invariants(tuple(_fake_state(tau=7)), last_tau=7) == 7


def test_elastic_migrate_state_accounting():
    """The migrated state keeps the aggregate (only folded epochs) and
    zeroes the in-flight frame/surplus — no draw double-counted."""
    C, v1 = 2, 101
    agg = np.random.default_rng(0).random((C, 104)).astype(np.float32)
    key = np.zeros(2, np.uint32)
    arrays = [agg, np.int32(5000), np.ones((C, 104), np.float32),
              np.int32(77), np.ones((C, v1), np.float32), np.int32(3),
              agg * 0.5, np.int32(4000), np.full(1, -1, np.int32), key]
    out = elastic_migrate_state(arrays, n_channels=C, v1=v1,
                                v_pad_new=112, lane_new="spmd", n_dev_new=4)
    assert out[0].shape == (C, 112)
    np.testing.assert_array_equal(out[0][:, :104], agg)   # aggregate kept
    assert int(out[1]) == 5000                            # agg tau kept
    assert out[2].shape == (4, C, 112) and not out[2].any()
    assert int(out[3]) == 0                               # frame discarded
    assert out[4].shape == (4, C, v1) and not out[4].any()
    assert int(out[5]) == 0                               # surplus discarded
    assert int(out[7]) == 4000                            # frozen tau kept
    # shrinking v_pad is allowed too (rows >= V+1 are structurally zero)
    out2 = elastic_migrate_state(arrays, n_channels=C, v1=v1,
                                 v_pad_new=102, lane_new="single",
                                 n_dev_new=1)
    assert out2[0].shape == (C, 102)
    np.testing.assert_array_equal(out2[0], agg[:, :102])


# ---------------------------------------------------------------------------
# ResilientRunner end-to-end (single-device lane, in-process)
# ---------------------------------------------------------------------------

def test_resilient_runner_bit_identical_under_faults(tmp_path):
    """Acceptance: mid-epoch kill, NaN-poisoned frame, checkpoint
    corruption and a torn manifest — the supervised run retries from
    the last good checkpoint and its final estimate is bit-identical
    to an uninterrupted run at the same seed."""
    g = _small_graph(v=120, e=480)
    # short epochs: the clean run takes 5, so every fault below fires
    cfg = AdaptiveConfig(eps=0.05, delta=0.1, n0_base=250, max_epochs=12)
    base = run_kadabra(g, config=cfg, key=jax.random.PRNGKey(7),
                       checkpoint_dir=str(tmp_path / "clean"))
    assert base.n_epochs > 3
    # one fault epoch each: a raising fault aborts the hook, so faults
    # sharing an epoch with it would be consumed without applying
    sched = FaultSchedule([FaultSpec("kill", 1), FaultSpec("nan", 2),
                           FaultSpec("hang", 2, delay=0.01),
                           FaultSpec("corrupt", 3)])
    r = ResilientRunner(
        g, config=cfg, key=jax.random.PRNGKey(7),
        checkpoint_dir=str(tmp_path / "res"), schedule=sched,
        policy=RetryPolicy(max_retries=8, backoff_base=1e-3,
                           backoff_cap=1e-3))
    out = r.run()
    rep = out.result.reports[0]
    np.testing.assert_array_equal(np.asarray(rep.scores),
                                  np.asarray(base.btilde))
    assert rep.tau == base.tau
    assert out.lane == "single" and out.n_devices == 1
    kinds = [e.kind for e in out.events]
    assert kinds.count("failure") == out.attempts >= 3
    assert "retry" in kinds
    # the NaN poison was caught by the watchdog, not persisted
    assert any("InvariantViolation" in e.detail for e in out.events)
    # corruption was detected + quarantined during the resume
    assert any(d.startswith("step_") and ".quarantined" in d
               for d in os.listdir(tmp_path / "res" / "rung0"))


def test_resilient_runner_hang_timeout_retries(tmp_path):
    g = _small_graph(seed=1, v=80, e=300)
    cfg = AdaptiveConfig(eps=0.08, delta=0.1, max_epochs=12)
    sched = FaultSchedule([FaultSpec("hang", 2, delay=0.3)])
    r = ResilientRunner(
        g, config=cfg, key=jax.random.PRNGKey(1),
        checkpoint_dir=str(tmp_path / "ck"), schedule=sched,
        epoch_timeout=0.1,
        policy=RetryPolicy(max_retries=3, backoff_base=1e-3,
                           backoff_cap=1e-3))
    out = r.run()
    assert any("EpochTimeoutError" in e.detail for e in out.events)
    base = run_kadabra(g, config=cfg, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(out.result.reports[0].scores),
                                  np.asarray(base.btilde))


def test_resilient_runner_exhaustion_raises(tmp_path):
    """The bottom of the ladder: the single-device lane exhausting its
    budget raises ResilienceExhausted (generic bugs still propagate
    as themselves, not as resilience failures)."""
    g = _small_graph(seed=2, v=60, e=200)
    cfg = AdaptiveConfig(eps=0.1, delta=0.1, max_epochs=8)
    sched = FaultSchedule([FaultSpec("kill", 1), FaultSpec("kill", 1),
                           FaultSpec("kill", 2)])
    r = ResilientRunner(
        g, config=cfg, key=jax.random.PRNGKey(2),
        checkpoint_dir=str(tmp_path / "ck"), schedule=sched,
        policy=RetryPolicy(max_retries=0, backoff_base=1e-3))
    with pytest.raises(ResilienceExhausted):
        r.run()

    class Bug(Exception):
        pass

    def buggy_hook(epoch, state):
        raise Bug("not a fault")

    with pytest.raises(Bug):
        run_adaptive(g, ("betweenness",), config=cfg,
                     key=jax.random.PRNGKey(2),
                     checkpoint_dir=str(tmp_path / "ck3"),
                     on_epoch=buggy_hook)


def test_engine_on_epoch_hook_contract(tmp_path):
    """The engine hook sees 1-based epochs, a raising hook aborts the
    run WITHOUT persisting the refused epoch, and earlier good epochs
    are still flushed to disk."""
    g = _small_graph(seed=3, v=80, e=300)
    cfg = AdaptiveConfig(eps=0.03, delta=0.1, max_epochs=10)
    seen = []

    def hook(epoch, state):
        seen.append(epoch)
        assert len(state) == 6
        if epoch == 2:
            raise InjectedFault("refused epoch 2")

    root = str(tmp_path / "ck")
    with pytest.raises(InjectedFault):
        run_adaptive(g, ("betweenness",), config=cfg,
                     key=jax.random.PRNGKey(3), checkpoint_dir=root,
                     on_epoch=hook)
    assert seen == [1, 2]
    assert latest_step(root) == 1       # epoch 2 never reached disk


# ---------------------------------------------------------------------------
# The elastic degradation ladder (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

_LADDER_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, tempfile
    from jax.sharding import Mesh
    from repro.core.graph import build_graph
    from repro.core.partition import partition_graph, gather_graph
    from repro.core.adaptive import AdaptiveConfig
    from repro.core.brandes import brandes_numpy
    from repro.runtime import (ResilientRunner, FaultSchedule, FaultSpec,
                               RetryPolicy)

    rng = np.random.default_rng(0)
    V, E = 200, 800
    src = rng.integers(0, V, E)
    dst = (src + 1 + rng.integers(0, V - 1, E)) % V
    g = build_graph(np.concatenate([src, dst]),
                    np.concatenate([dst, src]), V)
    pg = partition_graph(g, 8)
    for f in ("indptr", "indices", "degree", "src", "dst"):
        assert np.array_equal(np.asarray(getattr(g, f)),
                              np.asarray(getattr(gather_graph(pg), f))), f
    print("GATHER_OK")
    cfg = AdaptiveConfig(eps=0.08, delta=0.1, max_epochs=16)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("dev",))
    exact = brandes_numpy(g)

    # --- elastic shrink: 8 -> 4 devices, stays on the sharded lane ----
    with tempfile.TemporaryDirectory() as d:
        sched = FaultSchedule([FaultSpec("shrink", 2, survivors=4)])
        out = ResilientRunner(
            pg, mesh=mesh, config=cfg, key=jax.random.PRNGKey(3),
            checkpoint_dir=d, schedule=sched,
            policy=RetryPolicy(backoff_base=1e-3)).run()
        assert out.lane == "sharded" and out.n_devices == 4
        assert [e.kind for e in out.events if e.kind != "failure"] == [
            "fault", "shrink", "migrate"]
        rep = out.result.reports[0]
        assert rep.converged
        # tau accounting is exact: the per-epoch tau trace of the
        # completing run is non-decreasing (no draw counted twice,
        # discarded in-flight draws never reappear)
        taus = [s.tau for s in out.result.stats]
        assert all(b >= a for a, b in zip(taus, taus[1:])), taus
        err = float(np.max(np.abs(np.asarray(rep.scores) - exact)))
        assert err <= cfg.eps, err
        print("SHRINK_OK", out.n_devices, "err", err)

    # --- rung exhaustion: sharded -> spmd -> single -------------------
    with tempfile.TemporaryDirectory() as d:
        sched = FaultSchedule([FaultSpec("kill", 1), FaultSpec("kill", 1),
                               FaultSpec("kill", 2)])
        out = ResilientRunner(
            pg, mesh=mesh, config=cfg, key=jax.random.PRNGKey(3),
            checkpoint_dir=d, schedule=sched,
            policy=RetryPolicy(max_retries=0, backoff_base=1e-3)).run()
        assert out.lane == "single" and out.n_devices == 1
        degrades = [e.detail for e in out.events if e.kind == "degrade"]
        assert any("sharded -> spmd" in s for s in degrades)
        assert any("spmd -> single" in s for s in degrades)
        rep = out.result.reports[0]
        err = float(np.max(np.abs(np.asarray(rep.scores) - exact)))
        assert err <= cfg.eps, err
        print("LADDER_OK err", err)
""")


def test_degradation_ladder_8_devices(tmp_path):
    """Acceptance (elastic path): an 8->4 shrink re-partitions onto the
    surviving mesh and converges within (eps, delta) with exact tau
    accounting; repeated kills walk the full ladder down to the
    single-device lane."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _LADDER_SCRIPT],
                       env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-4000:]
    for marker in ("GATHER_OK", "SHRINK_OK", "LADDER_OK"):
        assert marker in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# Telemetry integration: RunEvent ordering/re-emission, checkpoint spans
# ---------------------------------------------------------------------------

def test_run_event_timestamps_and_bus_reemission(tmp_path):
    """PR 9 regression: every RunEvent carries a monotonic timestamp, the
    in-memory log is time-ordered, and each event is re-emitted on the
    telemetry bus as supervisor.<kind> from the same _record call, so
    the two views can never disagree on order."""
    from repro.runtime import RingSink, Telemetry

    ring = RingSink()
    g = _small_graph(seed=3, v=80, e=300)
    cfg = AdaptiveConfig(eps=0.08, delta=0.1, max_epochs=12)
    sched = FaultSchedule([FaultSpec("kill", 1), FaultSpec("nan", 2)])
    out = ResilientRunner(
        g, config=cfg, key=jax.random.PRNGKey(4),
        checkpoint_dir=str(tmp_path / "ck"), schedule=sched,
        policy=RetryPolicy(max_retries=6, backoff_base=1e-3,
                           backoff_cap=1e-3),
        telemetry=Telemetry([ring], validate=True)).run()
    assert out.events, "faulted run recorded no events"
    ts = [e.t for e in out.events]
    assert all(t > 0.0 for t in ts)         # stamped, not the 0.0 default
    assert ts == sorted(ts)
    bus = [e for e in ring.events if e.kind.startswith("supervisor.")]
    assert [b.kind.split(".", 1)[1] for b in bus] == \
        [e.kind for e in out.events]
    for b, e in zip(bus, out.events):
        assert b.fields["epoch"] == e.epoch
        assert b.fields["attempt"] == e.attempt
        assert b.fields["detail"] == e.detail


def test_checkpoint_publish_restore_telemetry(tmp_path):
    """The async publish and the restore path surface as spans + typed
    events: a clean save/restore emits ok=True pairs, a corrupted step
    emits a quarantine event plus an ok=False restore attempt before
    the fallback succeeds."""
    from repro.runtime import RingSink, Telemetry

    ring = RingSink()
    tel = Telemetry([ring], validate=True)
    root = str(tmp_path / "ck")
    tree = _tree()
    save(root, 1, tree, telemetry=tel)
    save(root, 2, jax.tree.map(lambda x: x + 1, tree), telemetry=tel)
    pubs = [e for e in ring.events if e.kind == "checkpoint.publish"]
    assert [p.fields["step"] for p in pubs] == [1, 2]
    assert all(p.fields["ok"] and p.fields["seconds"] >= 0 for p in pubs)
    spans = [e for e in ring.events
             if e.kind == "span.end"
             and e.fields["name"] == "checkpoint.publish"]
    assert len(spans) == 2
    corrupt_newest_step(root)
    restored, step, _ = restore(root, tree, telemetry=tel)
    assert step == 1
    kinds = [e.kind for e in ring.events]
    assert "checkpoint.quarantine" in kinds
    rests = [e for e in ring.events if e.kind == "checkpoint.restore"]
    # step 2 failed integrity, step 1 verified
    assert [r.fields["ok"] for r in rests] == [False, True]
    assert "error" in rests[0].fields
    assert [r.fields["step"] for r in rests] == [2, 1]


def test_checkpoint_publish_failure_emits_error_event(tmp_path):
    """A publish that dies on the background thread still reports
    through the bus: the checkpoint.publish event carries ok=False and
    the error type (cross-thread emission is the JSONLSink/RingSink
    lock's job)."""
    from repro.runtime import RingSink, Telemetry

    ring = RingSink()
    tel = Telemetry([ring], validate=True)

    def boom(kind, step, i):
        raise OSError(28, "No space left on device")

    install_publish_fault_hook(boom)
    try:
        with pytest.raises(OSError):
            save(str(tmp_path / "ck"), 1, _tree(), telemetry=tel)
    finally:
        install_publish_fault_hook(None)
    pubs = [e for e in ring.events if e.kind == "checkpoint.publish"]
    assert len(pubs) == 1
    assert pubs[0].fields["ok"] is False
    assert pubs[0].fields["error"] == "OSError"
