"""Train the NequIP E(3)-equivariant potential on batched synthetic
molecules (the `molecule` cell at laptop scale) and verify the energy
prediction is rotation-invariant after training.

    PYTHONPATH=src python examples/gnn_molecules.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import irreps
from repro.models.gnn.message_passing import GraphBatch
from repro.models.gnn.models import NequipConfig, nequip_init, nequip_loss
from repro.optim.adamw import AdamWConfig, init_state
from repro.train.step import make_train_step
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()


def make_molecules(step, n_mol=16, atoms=8, seed=0):
    rng = np.random.default_rng((seed, step))
    n = n_mol * atoms
    pos = rng.standard_normal((n, 3)) * 1.5
    gid = np.repeat(np.arange(n_mol), atoms)
    # edges: full graphs within each molecule
    src, dst = [], []
    for m in range(n_mol):
        ii = np.arange(m * atoms, (m + 1) * atoms)
        a, b = np.meshgrid(ii, ii)
        keep = a != b
        src.append(a[keep]); dst.append(b[keep])
    src = np.concatenate(src); dst = np.concatenate(dst)
    z = rng.integers(0, 4, n)
    # target: simple pair potential (invariant by construction)
    d = np.linalg.norm(pos[src] - pos[dst], axis=1)
    e_pair = np.exp(-d)
    y = np.zeros(n_mol)
    np.add.at(y, gid[src], 0.5 * e_pair)
    return GraphBatch(
        x=jnp.zeros((n, 1), jnp.float32), z=jnp.asarray(z, jnp.int32),
        pos=jnp.asarray(pos, jnp.float32),
        src=jnp.asarray(src, jnp.int32), dst=jnp.asarray(dst, jnp.int32),
        edge_mask=jnp.ones(len(src), jnp.float32),
        node_mask=jnp.ones(n, jnp.float32),
        labels=jnp.zeros(n, jnp.int32), graph_id=jnp.asarray(gid, jnp.int32),
        y=jnp.asarray(y, jnp.float32), n_graphs=n_mol)


cfg = NequipConfig(n_layers=2, d_hidden=16)
params = nequip_init(jax.random.PRNGKey(0), cfg)
step_fn = jax.jit(make_train_step(lambda p, b: nequip_loss(p, b, cfg),
                                  AdamWConfig(lr=3e-3, weight_decay=0.0)))
state = init_state(params)
first = last = None
for step in range(60):
    batch = make_molecules(step)
    params, state, m = step_fn(params, state, batch)
    if step == 0:
        first = float(m["loss"])
    last = float(m["loss"])
    if step % 15 == 0:
        print(f"step {step:3d} loss {last:.4f}")
print(f"loss {first:.3f} -> {last:.3f}")
assert last < first

# rotation invariance of the trained energy
from repro.models.gnn.models import nequip_forward
b = make_molecules(999)
R = irreps.random_rotation(3)
_, e1 = jax.jit(lambda p, bb: nequip_forward(p, bb, cfg))(params, b)
b2 = GraphBatch(**{**b.__dict__, "pos": jnp.asarray(np.asarray(b.pos) @ R.T)})
_, e2 = jax.jit(lambda p, bb: nequip_forward(p, bb, cfg))(params, b2)
err = np.abs(np.asarray(e1) - np.asarray(e2)).max()
print(f"rotation-invariance error of trained model: {err:.2e}")
assert err < 1e-3
print("OK")
