"""Distributed-runtime tests: optimizer, compression, checkpoint
fault tolerance, elastic restore, deterministic data, neighbor sampler."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointManager, latest_step, restore, save
from repro.data.pipeline import NeighborSampler, lm_batch_fn, recsys_batch_fn
from repro.optim.adamw import AdamWConfig, apply_updates, init_state
from repro.train.step import make_train_step


def _quadratic_problem():
    target = jnp.asarray(np.random.default_rng(0).standard_normal(32),
                         jnp.float32)
    params = {"w": jnp.zeros(32, jnp.float32)}

    def loss(p, batch):
        return jnp.sum((p["w"] - target) ** 2)
    return params, loss, target


def test_adamw_converges_quadratic():
    params, loss, target = _quadratic_problem()
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0)
    state = init_state(params)
    step = jax.jit(make_train_step(loss, cfg))
    for _ in range(400):
        params, state, m = step(params, state, {})
    assert float(m["loss"]) < 1e-2


def test_int8_compression_error_feedback_converges():
    """Compression must not break convergence (error feedback carries the
    quantization residual)."""
    params, loss, target = _quadratic_problem()
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, compress="int8")
    state = init_state(params, compress=True)
    step = jax.jit(make_train_step(loss, cfg))
    for _ in range(500):
        params, state, m = step(params, state, {})
    assert float(m["loss"]) < 5e-2


def test_microbatch_equals_full_batch_gradients():
    rng = np.random.default_rng(0)
    w = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    cfg = AdamWConfig(lr=1e-2, weight_decay=0.0, grad_clip=None)
    s1 = init_state(w)
    p1, _, m1 = jax.jit(make_train_step(loss, cfg))(w, s1, {"x": x, "y": y})
    s2 = init_state(w)
    p2, _, m2 = jax.jit(make_train_step(loss, cfg, microbatch=4))(
        w, s2, {"x": x, "y": y})
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)


def test_checkpoint_atomic_and_keep_k(tmp_path):
    root = str(tmp_path / "ck")
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((3, 3))}}
    for step in [10, 20, 30, 40]:
        save(root, step, tree, keep=2)
    assert latest_step(root) == 40
    # keep-2 gc
    kept = sorted(d for d in os.listdir(root) if d.startswith("step_"))
    assert kept == ["step_00000030", "step_00000040"]
    restored, step, _ = restore(root, tree)
    assert step == 40
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    # a stray .tmp dir must be invisible to restore
    os.makedirs(os.path.join(root, "step_00000099.tmp"))
    assert latest_step(root) == 40


def test_checkpoint_restart_determinism(tmp_path):
    """Train 10 steps straight vs 5 + restart + 5: identical params."""
    params, loss, _ = _quadratic_problem()
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0)
    step_fn = jax.jit(make_train_step(loss, cfg))

    p, s = params, init_state(params)
    for i in range(10):
        p, s, _ = step_fn(p, s, {})
    straight = np.asarray(p["w"])

    root = str(tmp_path / "ck2")
    p, s = params, init_state(params)
    for i in range(5):
        p, s, _ = step_fn(p, s, {})
    save(root, 5, (p, s))
    (p2, s2), st, _ = restore(root, (p, s))
    assert st == 5
    for i in range(5):
        p2, s2, _ = step_fn(p2, s2, {})
    np.testing.assert_allclose(np.asarray(p2["w"]), straight, rtol=1e-6)


_ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.store import save, restore
    mesh = jax.make_mesh((%d,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P(None, "model")))
    tree = {"w": w}
    if %s:   # writer
        save("%s", 1, tree)
        print("SAVED", jax.device_count())
    else:
        t2, step, _ = restore("%s", tree,
            shardings={"w": NamedSharding(mesh, P(None, "model"))})
        np.testing.assert_array_equal(np.asarray(t2["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("RESTORED on", jax.device_count(), "devices")
""")


def test_elastic_restore_across_device_counts(tmp_path):
    """Save on 8 'devices', restore on 4 — the elastic resume path."""
    root = str(tmp_path / "eck")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    w = subprocess.run([sys.executable, "-c",
                        _ELASTIC_SCRIPT % (8, 8, "True", root, root)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert w.returncode == 0, w.stderr
    r = subprocess.run([sys.executable, "-c",
                        _ELASTIC_SCRIPT % (4, 4, "False", root, root)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "RESTORED on 4" in r.stdout


def test_lm_batch_determinism():
    f = lm_batch_fn(vocab=1000, batch=4, seq=16, seed=7)
    b1, b2 = f(3), f(3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = f(4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].max() < 1000
    # shift-by-one property
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])


def test_neighbor_sampler_shapes_and_validity():
    from repro.core import erdos_renyi_graph
    g = erdos_renyi_graph(500, 6.0, seed=1)
    s = NeighborSampler(g, fanouts=(5, 3), batch_nodes=16, seed=0)
    assert s.total_nodes == 16 + 80 + 240
    sub = s.sample(0)
    assert len(sub["node_ids"]) == s.total_nodes
    assert len(sub["src"]) == s.total_edges == 80 + 240
    # edges connect children to parents within the local id space
    assert sub["src"].max() < s.total_nodes
    assert sub["dst"].max() < 16 + 80
    # sampled neighbors really are neighbors (check a few live edges)
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)[: g.n_edges]
    ids = sub["node_ids"]
    live = np.nonzero(sub["edge_mask"] > 0)[0][:50]
    for e in live:
        child = ids[sub["src"][e]]
        parent = ids[sub["dst"][e]]
        assert child in indices[indptr[parent]: indptr[parent + 1]]


def test_recsys_batch_latent_structure():
    f = recsys_batch_fn(n_items=6400, batch=32, hist_len=20, seed=0)
    b = f(0)
    assert b["hist"].shape == (32, 20)
    assert b["hist"].max() < 6400
    assert set(np.unique(b["hist_mask"])) <= {0.0, 1.0}
    # items of one user concentrate in few clusters
    cluster = b["hist"][0] // 100
    assert len(np.unique(cluster)) <= 3
